"""One run of one cell: build the engine, warm up the cell's programs,
drive its traffic for the window as the client, check what the window
served against the configuration's plain reference (`Cell.reference`),
and make the result line.

The engine is synchronous, so the harness is the client. Each pass of its
loop enqueues every request that is due (`ServeEngine.add_request`),
advances every running row by one `step_all` while any row or queued
request exists, and otherwise sleeps until the next request is due. A
request is timed from the moment it was due. The harness marks its calls
into the engine with profiler annotations (`bench.add_request`,
`bench.step_all`, `bench.idle_wait`, around them `bench.window`), so that
a traced run can say what the host was doing while the device idled.
"""
from __future__ import annotations

import dataclasses
import gc
import os
import statistics
import sys
import time

import numpy as np

import cell as cell_mod
import counts
import traffic

WARM_ID = 10 ** 9              # request id of the chat warm-up request
BACKLOG_WARM_STEPS = 2         # decode steps of the set-up batch


class CompileClock:
    """Counts XLA compiles (persistent-cache reads included), their
    seconds, and persistent-cache hits, from JAX's monitoring events."""

    def __init__(self, jax):
        self.compiles, self.seconds, self.cache_hits = 0, 0.0, 0

        def on_duration(name, secs, **_):
            if name == "/jax/core/compile/backend_compile_duration":
                self.compiles += 1
                self.seconds += secs

        def on_event(name, **_):
            if name == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def snapshot(self) -> tuple[int, float, int]:
        return self.compiles, self.seconds, self.cache_hits


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# the engine under test
# ---------------------------------------------------------------------------

def model_config(c: cell_mod.Cell, arch_override=None):
    """The repo's ModelConfig for the cell's configuration file, checked
    by the configuration's reference: the model is one it covers, and
    every size the file states is the one that runs."""
    import dataclasses as dc

    from repro.configs import get_arch
    conf = c.config
    cfg = arch_override or get_arch(conf["repro_arch"])
    a = conf["amc"]
    cfg = dc.replace(cfg, amc=dc.replace(
        cfg.amc, weight_mode=a["weight_mode"], kv_mode=a["kv_mode"],
        pool_mode=a["pool_mode"], page_size=a["page_size"]))
    c.reference.check(cfg, conf)
    return cfg


def build_engine(jax, cfg, shape: dict, seed: int, chips: int):
    from repro.launch.mesh import make_local_mesh
    from repro.serve import make_serving
    eng = make_serving(cfg, make_local_mesh(jax.devices()[:chips]),
                       max_batch=shape["max_batch"],
                       max_seq=shape["max_seq"], seed=seed)
    jax.block_until_ready((eng.params, eng.store.state))
    return eng


# ---------------------------------------------------------------------------
# the client
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Step:
    start: float
    end: float
    contexts: list[int]        # attended tokens of each token it served


class Client:
    """Offers requests to the engine and records when each was due,
    enqueued, admitted, and when each of its tokens came back."""

    def __init__(self, eng, gen: traffic.Generator, annotate):
        self.eng, self.gen, self.annotate = eng, gen, annotate
        self.offers: dict[int, traffic.Offer] = {}
        self.due: dict[int, float] = {}
        self.enqueued: dict[int, float] = {}
        self.admitted: dict[int, float] = {}
        self.tokens: dict[int, list[float]] = {}
        self.live: list[int] = []
        self.steps: list[Step] = []
        self.next = 0

    def enqueue(self, offer: traffic.Offer, due: float) -> None:
        from repro.serve import Request
        rid = offer.index
        self.offers[rid], self.due[rid] = offer, due
        self.tokens[rid] = []
        with self.annotate("bench.add_request"):
            row = self.eng.add_request(Request(
                prompt=offer.prompt, max_new_tokens=offer.max_new_tokens,
                id=rid))
        t = time.perf_counter()
        self.enqueued[rid] = t
        if row is not None:
            self.admitted[rid] = t
        self.live.append(rid)

    def step(self) -> None:
        t0 = time.perf_counter()
        with self.annotate("bench.step_all"):
            self.eng.step_all()
        t1 = time.perf_counter()
        contexts, still = [], []
        outs = self.eng.outputs
        for rid in self.live:
            toks = self.tokens[rid]
            n = len(outs.get(rid, ()))
            if n > len(toks):
                self.admitted.setdefault(rid, t0)
                for i in range(len(toks), n):
                    # served token i (from 0) attends to P + i tokens
                    contexts.append(len(self.offers[rid].prompt) + i)
                toks.extend([t1] * (n - len(toks)))
            if n < self.offers[rid].max_new_tokens:
                still.append(rid)
        self.live = still
        self.steps.append(Step(t0, t1, contexts))

    def busy(self) -> bool:
        return bool(self.eng.active.any()) or bool(self.eng.scheduler.queue)

    def queued(self) -> int:
        return len(self.eng.scheduler.queue)


def warm_up(client: Client, backlog: bool, max_batch: int) -> None:
    """Compile and run every program the window will use: the prefill
    and decode steps and the host-side readback of the next tokens.
    Backlog traffic admits (and prefills) its first batch here, each
    request part-way through its output as steady serving holds it
    (`traffic.Generator.in_flight`), and queues the next batch; other
    traffic serves one warm-up request to completion."""
    if backlog:
        for i in range(2 * max_batch):
            client.enqueue(client.gen.in_flight(i, max_batch)
                           if i < max_batch else client.gen.offer(i), 0.0)
        client.next = 2 * max_batch
        for _ in range(BACKLOG_WARM_STEPS):
            client.step()
        return
    o = client.gen.offer(0)
    warm = traffic.Offer(WARM_ID, o.prompt, 3, None)
    client.enqueue(warm, 0.0)
    while client.busy():
        client.step()
    for d in (client.offers, client.due, client.enqueued, client.admitted,
              client.tokens):
        d.pop(WARM_ID, None)
    client.steps.clear()


def drive(client: Client, backlog: bool, max_batch: int, t0: float,
          seconds: float) -> float:
    """The measured window: returns the time it closed (the end of the
    last step begun before t0 + seconds)."""
    t_end = t0 + seconds
    t_close = t0
    while True:
        now = time.perf_counter()
        if now >= t_end:
            break
        if backlog:
            while client.queued() < max_batch:
                client.enqueue(client.gen.offer(client.next), now)
                client.next += 1
        else:
            while t0 + client.gen.due_at(client.next) <= now:
                offer = client.gen.offer(client.next)
                client.enqueue(offer, t0 + offer.due)
                client.next += 1
        if client.busy():
            client.step()
            t_close = client.steps[-1].end
        else:
            wake = min(t0 + client.gen.due_at(client.next), t_end)
            with client.annotate("bench.idle_wait"):
                time.sleep(max(0.0, wake - time.perf_counter()))
            t_close = time.perf_counter()
    return max(t_close, min(time.perf_counter(), t_end))


# ---------------------------------------------------------------------------
# end-to-end numbers of the window
# ---------------------------------------------------------------------------

def percentile(values: list[float], q: float) -> float:
    """The q-th percentile (0..100) by linear interpolation between
    order statistics, over every value."""
    return float(np.percentile(np.asarray(values, float), q))


def window_numbers(client: Client, t0: float, t_end: float) -> dict:
    """The window's work: tokens that came back in [t0, t_end], over the
    whole window, and the TTFT and inter-token samples inside it. A step
    still running at t_end counts the share of its tokens that its time
    inside the window bears (so the count does not jump by a batch of
    tokens with the close); a request due in the window with no first
    token by t_end counts the wait it has had so far."""
    tokens = sum(1 for ts in client.tokens.values() for t in ts
                 if t0 < t <= t_end)
    tokens += sum(len(s.contexts) * (t_end - s.start) / (s.end - s.start)
                  for s in client.steps if s.start < t_end < s.end)
    ttft, itl = [], []
    for rid, due in client.due.items():
        ts = client.tokens[rid]
        if t0 <= due < t_end:
            ttft.append((ts[0] if ts and ts[0] <= t_end else t_end) - due)
        itl += [b - a for a, b in zip(ts, ts[1:])
                if a >= t0 and b <= t_end]
    late = [client.enqueued[r] - client.due[r] for r in client.due
            if t0 <= client.due[r] < t_end]
    return {"tokens": tokens, "window_s": t_end - t0, "ttft": ttft,
            "itl": itl, "late": late}


def end_to_end(names: list[str], w: dict, setup_s: float) -> dict:
    ms = 1e3
    fns = {
        "setup_s": lambda: (setup_s, "s"),
        "output_tok_s": lambda: (w["tokens"] / w["window_s"], "tokens/s"),
        "ttft_mean_ms": lambda: (statistics.fmean(w["ttft"]) * ms, "ms"),
        "itl_p95_ms": lambda: (percentile(w["itl"], 95) * ms, "ms"),
    }
    out = {}
    for n in names:
        value, unit = fns[n]()
        out[n] = {"value": value, "unit": unit}
    return out


# ---------------------------------------------------------------------------
# the comparison that decides `correct`
# ---------------------------------------------------------------------------

def sample(client: Client, outputs: dict, seed: int, k: int) -> list[int]:
    """Requests to compare, drawn from the seed: those that finished
    first, then those still running, always with the one that was served
    the most tokens."""
    served = {r: len(outputs.get(r, ())) for r in client.offers}
    served = {r: n for r, n in served.items() if n > 0}
    if not served:
        return []
    longest = max(served, key=lambda r: (served[r], -r))
    done = [r for r in served
            if served[r] >= client.offers[r].max_new_tokens]
    rest = [r for r in served if r not in done]
    rng = np.random.default_rng([seed, 3])
    pick = [longest]
    for pool in (done, rest):
        pool = [r for r in pool if r != longest]
        rng.shuffle(pool)
        pick += pool[:max(0, k - len(pick))]
    return pick[:k]


def compare(ref, dims, seed: int, picked: dict, *,
            control: bool = False) -> dict:
    """Widest gap, over every served token of the picked requests, by
    which the reference's logit of the served token lies below the
    reference's best; `ref` is the configuration's reference module and
    `dims` its `Dims`. With `control`, also the same reading of the
    reference computed a precision step lower (`served_gaps(...,
    control=True)`), read at the same positions: the reading a sound
    limit has to fail."""
    w = ref.make_weights(dims, seed)
    gaps, ctl, n = [], [], 0
    for prompt, served in picked.values():
        gaps.append(float(ref.served_gaps(dims, w, prompt, served).max()))
        if control:
            ctl.append(float(ref.served_gaps(
                dims, w, prompt, served, control=True).max()))
        n += len(served)
    del w
    out = {"max_logit_gap": max(gaps) if gaps else float("inf"),
           "tokens_compared": n, "per_request": gaps}
    if control:
        out["control_max_logit_gap"] = max(ctl) if ctl else float("nan")
        out["control_per_request"] = ctl
    return out


def judge(max_logit_gap: float, limit: float | None, failed: int) -> bool:
    """The rule that decides `correct`: no request failed, and the widest
    logit gap of the compared tokens is within the cell's limit."""
    return limit is not None and failed == 0 and max_logit_gap <= limit


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

def run(c: cell_mod.Cell, *, seed: int, seconds: float, trace: bool,
        t_start: float, arch_override=None, fault=None,
        control: bool = False) -> dict:
    """One run of cell `c`; returns the result line's object. `fault`, if
    given, is called with the engine before the window (tests use it to
    break the timed path); `control` adds the control's reading to the
    checks (calibrate.py)."""
    import jax

    from repro.launch.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    clock = CompileClock(jax)
    dev = jax.devices()[0]
    log(f"setup: {jax.device_count()} x {dev.device_kind} "
        f"({dev.platform}); compile cache {cache_dir}")

    cfg = model_config(c, arch_override)
    dims = c.reference.Dims.from_config(c.config) if arch_override is None \
        else c.reference.Dims.from_arch(cfg)
    shape = c.shape
    backlog = c.traffic["arrivals"]["kind"] == "backlog"
    t_b = time.perf_counter()
    eng = build_engine(jax, cfg, shape, seed, c.chips)
    log(f"setup: {cfg.name} built (weights {eng.cfg.amc.weight_mode}, kv "
        f"{eng.cfg.amc.kv_mode}, pool {eng.cfg.amc.resolved_pool_mode}, "
        f"max_batch {shape['max_batch']}, max_seq {shape['max_seq']}) in "
        f"{time.perf_counter() - t_b!r} s")
    if fault is not None:
        fault(eng)

    annotate = jax.profiler.TraceAnnotation
    gen = traffic.Generator(c.traffic, seed, cfg.vocab)
    client = Client(eng, gen, annotate)
    t_w = time.perf_counter()
    warm_up(client, backlog, shape["max_batch"])
    jax.block_until_ready(eng.store.state)
    compiles0 = clock.snapshot()
    gc.collect()
    gc.freeze()         # the window's collections skip what set-up made
    log(f"setup: warm-up {time.perf_counter() - t_w!r} s; compiles "
        f"{compiles0[0]} ({compiles0[1]!r} s, persistent-cache hits "
        f"{compiles0[2]})")

    trace_dir = os.path.join(cell_mod.ROOT, ".bench_trace", c.name)
    if trace:
        import shutil
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0        # host spans, not every call
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    t0 = time.perf_counter()
    setup_s = t0 - t_start
    with annotate("bench.window"):
        t_close = drive(client, backlog, shape["max_batch"], t0, seconds)
    if trace:
        jax.profiler.stop_trace()
    compiles1 = clock.snapshot()
    t_end = t0 + seconds
    w = window_numbers(client, t0, t_end)
    peak = (dev.memory_stats() or {}).get("peak_bytes_in_use")
    in_window = compiles1[0] - compiles0[0]
    due = sum(1 for r, d in client.due.items() if t0 <= d < t_end)
    admitted = sum(1 for t in client.admitted.values() if t0 < t <= t_end)
    completed = sum(1 for r, ts in client.tokens.items()
                    if ts and t0 < ts[-1] <= t_end
                    and len(ts) >= client.offers[r].max_new_tokens)
    log(f"window: {w['window_s']!r} s, {len(client.steps)} steps (the "
        f"last ending {t_close - t_end!r} s past the close), {w['tokens']} "
        f"tokens; requests due {due}, admitted {admitted}, completed "
        f"{completed}, queued at close {client.queued()}; compiles in "
        f"window {in_window} ({compiles1[1] - compiles0[1]!r} s)")
    for key in ("ttft", "itl"):
        if w[key]:
            log(f"window: {key} mean, p50/p95/p99 ms "
                f"{statistics.fmean(w[key]) * 1e3!r}, "
                f"{[percentile(w[key], q) * 1e3 for q in (50, 95, 99)]} "
                f"over {len(w[key])}")
    log(f"setup: setup_s {setup_s!r}")
    if not backlog:
        rate = c.traffic["arrivals"]["rate_per_s"]
        log(f"window: offered {rate} req/s; generator lateness mean "
            f"{statistics.fmean(w['late']) if w['late'] else 0.0!r} s, "
            f"max {max(w['late'], default=0.0)!r} s")
    log(f"window: peak_bytes_in_use {peak}; engine dispatches "
        f"{eng.dispatch_count} (prefill {eng.prefill_dispatch_count})")

    # -- correctness: the reference, once the program's state is freed ----
    outputs = {r: list(v) for r, v in eng.outputs.items()}
    picked_ids = sample(client, outputs, seed, shape["sample_requests"])
    picked = {r: (client.offers[r].prompt, outputs[r]) for r in picked_ids}
    failed = len(eng.failed)
    client.eng = None
    del eng
    gc.unfreeze()
    gc.collect()
    t_r = time.perf_counter()
    check = compare(c.reference, dims, seed, picked, control=control)
    limit = shape["limits"]["max_logit_gap"]
    correct = judge(check["max_logit_gap"], limit, failed)
    log(f"check: {len(picked)} requests, {check['tokens_compared']} served "
        f"tokens against the reference in {time.perf_counter() - t_r!r} s; "
        f"per request {check['per_request']}")

    result = {"correct": correct,
              "attempted": len(client.offers),
              "failed": failed}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": jax.device_count(), "memory_peak_bytes": peak}
    if trace:
        import trace_reduce
        red = trace_reduce.reduce_dir(trace_dir, c.chips)
        ctx = Context(cell=c, dims=dims, client=client, window=w,
                      trace=red, t0=t0, t_close=t_close,
                      peaks=counts.peaks_for(dev.device_kind))
        metrics = {}
        for m in c.per_layer:
            value = c.reader(m["name"])(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device.update(busy_s=red.busy_s, window_s=red.window_s)
        result["metrics"] = metrics
        result["device"] = device
        result["breakdown"] = red.breakdown()
    else:
        result["metrics"] = end_to_end([m["name"] for m in c.end_to_end],
                                       w, setup_s)
        result["device"] = device
    checks = {"max_logit_gap": {"value": check["max_logit_gap"],
                                "limit": limit}}
    if control:
        log(f"check: control per request {check['control_per_request']}")
        checks["control_max_logit_gap"] = {
            "value": check["control_max_logit_gap"], "limit": limit}
    result["checks"] = checks
    for name, v in checks.items():
        log(f"check {name}: {v['value']!r} (limit {v['limit']!r})")
    return result


@dataclasses.dataclass
class Context:
    """What a per-layer metric's reader may read."""
    cell: cell_mod.Cell
    dims: object               # the reference's Dims of the model that runs
    client: Client
    window: dict
    trace: object
    t0: float
    t_close: float
    peaks: dict

    @property
    def reference(self):
        """The configuration's reference module (`Cell.reference`)."""
        return self.cell.reference

    def note(self, msg: str) -> None:
        log(f"metric: {msg}")

    def steps_in_window(self) -> list[Step]:
        return [s for s in self.client.steps
                if s.start >= self.t0 and s.end <= self.t_close]
