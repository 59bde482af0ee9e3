"""Observability plane: tracing, metrics, exporters and engine wiring.

Contracts under test:

  * well-formed traces — every span closed at end of run, globally
    monotonic timestamps, schema-valid Chrome trace JSON, and request-id
    continuity: a preempted request's whole life stays on ONE track
  * trace/metrics agreement — TTFT percentiles recomputed from the trace
    instants land within one log-bucket of the histogram estimates
  * stats() idempotence — repeated calls return deep-equal payloads
  * zero-overhead disabled mode — the Null facade leaves no state behind
  * per-step wall times surfaced for every run (straggler monitor feed)
  * profiler annotations — every engine phase lands in a jax.profiler
    capture as an `engine.<phase>` event nested in its `engine.step`,
    from the Null facade and the recording one alike, with the same
    phases the Chrome-JSON plane records; the package itself (and the
    trace validator) imports without JAX
"""
import collections
import dataclasses
import glob
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.configs import get_arch
from repro.configs.base import AMCConfig
from repro.launch.mesh import make_local_mesh
from repro.obs import (ENGINE_TRACK, REQ_TRACK_BASE, LogHistogram,
                       MetricsRegistry, NullEngineObs, TimeSeries, Tracer,
                       make_engine_obs, validate_chrome_trace,
                       validate_chrome_trace_file)
from repro.serve import Request, ServeEngine

MESH = make_local_mesh()


def _cfg(arch="qwen1.5-0.5b", **amc):
    return dataclasses.replace(get_arch(arch).reduced(),
                               amc=AMCConfig(**amc))


def _reqs(cfg, n, plen, max_new, seed=0, id0=0):
    rng = np.random.default_rng(seed)
    return [Request(prompt=rng.integers(0, cfg.vocab, size=(plen,))
                    .astype(np.int32), max_new_tokens=max_new, id=id0 + i)
            for i in range(n)]


# ---------------------------------------------------------------------------
# metrics primitives
# ---------------------------------------------------------------------------

def test_log_histogram_buckets_and_percentiles():
    h = LogHistogram()
    for v in (1e-5, 1e-4, 1e-3, 1e-3, 1e-3, 1e-2):
        h.observe(v)
    s = h.summary()
    assert s["count"] == 6
    assert s["min"] == 1e-5 and s["max"] == 1e-2
    # p50 of six values is the 3rd: a 1e-3 — reported as its bucket edge
    assert h.bucket_index(s["p50"]) - h.bucket_index(1e-3) <= 1
    assert h.within_one_bucket(s["p50"], 1e-3)
    # a value and its own report always agree within one bucket
    assert h.within_one_bucket(s["p99"], 1e-2)
    assert not h.within_one_bucket(1e-5, 1e-2)


def test_log_histogram_overflow_and_observe_n():
    h = LogHistogram(lo=1e-6, n_buckets=4)
    h.observe(1e9)                               # overflow bucket
    assert h.percentile(99) == 1e9               # reports max, not inf
    h.observe_n(2e-6, 3)
    assert h.count == 4 and h.counts[h.bucket_index(2e-6)] == 3


def test_timeseries_bounded_with_uniform_coverage():
    ts = TimeSeries(max_samples=8)
    for t in range(1000):
        ts.sample(t, t * 10)
    assert len(ts.samples) <= 8
    steps = [t for t, _ in ts.samples]
    assert steps == sorted(steps)
    assert steps[0] < 300 and steps[-1] > 700    # covers the whole run
    assert ts.last() == ts.samples[-1][1]


def test_prometheus_text_exposition():
    m = MetricsRegistry()
    m.inc("requests", 3)
    m.gauge("depth", 2)
    m.observe("lat_s", 0.01)
    m.observe("lat_s", 0.5)
    text = m.prometheus_text()
    assert "# TYPE amc_requests counter" in text
    assert "amc_requests 3" in text
    assert "amc_depth 2" in text
    assert "# TYPE amc_lat_s histogram" in text
    assert 'amc_lat_s_bucket{le="+Inf"} 2' in text
    assert "amc_lat_s_count 2" in text
    # cumulative bucket counts: every le value's count <= total
    lines = [ln for ln in text.splitlines() if ln.startswith("amc_lat_s_bucket")]
    counts = [int(ln.rsplit(" ", 1)[1]) for ln in lines]
    assert counts == sorted(counts)


# ---------------------------------------------------------------------------
# tracer well-formedness
# ---------------------------------------------------------------------------

def test_tracer_spans_instants_counters_schema():
    clk = iter(x * 1e-3 for x in range(100))
    tr = Tracer(clock=lambda: next(clk))
    sid = tr.begin(ENGINE_TRACK, "step", step=0)
    tr.instant(tr.request_track(5), "enqueue", step=0)
    tr.counter("mode_mix", normal=3, augmented=1)
    tr.end(sid, kind="decode")
    with tr.span(ENGINE_TRACK, "step", step=1):
        pass
    assert tr.open_spans() == 0
    obj = tr.chrome_trace()
    assert validate_chrome_trace(obj) == []
    phases = {e["ph"] for e in obj["traceEvents"]}
    assert {"X", "i", "C", "M"} <= phases
    names = {e["args"]["name"] for e in obj["traceEvents"]
             if e["ph"] == "M" and e["name"] == "thread_name"}
    assert "req 5" in names and "engine/steps" in names


def test_tracer_open_span_flagged_at_export():
    tr = Tracer()
    tr.begin(ENGINE_TRACK, "step", step=0)
    obj = tr.chrome_trace()
    assert tr.open_spans() == 1                  # export does not close it
    bad = [p for p in validate_chrome_trace(obj) if "left open" in p]
    assert bad, "open span must be flagged by the validator"


def test_validator_catches_malformed_traces():
    assert validate_chrome_trace([]) != []
    assert validate_chrome_trace({"traceEvents": [{"ph": "Z"}]}) != []
    # non-monotonic timestamps
    ev = [{"name": "a", "ph": "i", "s": "t", "ts": 5.0, "pid": 0, "tid": 0},
          {"name": "b", "ph": "i", "s": "t", "ts": 1.0, "pid": 0, "tid": 0}]
    probs = validate_chrome_trace({"traceEvents": ev})
    assert any("monotonic" in p for p in probs)


def test_trace_validator_imports_without_jax():
    """`python -m repro.obs.validate` checks a Chrome-JSON file alone: the
    package's profiler annotation binds JAX on first use, not on import."""
    code = ("import sys, repro.obs.validate; "
            "sys.exit('jax' in sys.modules)")
    env = {**os.environ, "PYTHONPATH": os.pathsep.join(sys.path)}
    run = subprocess.run([sys.executable, "-c", code], env=env)
    assert run.returncode == 0


# ---------------------------------------------------------------------------
# engine wiring: full lifecycle trace
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def traced_run():
    """Overloaded engine (queueing + preemption pressure), obs fully on."""
    cfg = _cfg(kv_mode="int4", pool_mode="always-augmented",
               trace=True, metrics=True)
    eng = ServeEngine(cfg, MESH, max_batch=2, max_seq=48, prefill_chunk=8,
                      pool_budget_bytes=40_000)
    reqs = _reqs(cfg, 5, 6, 10)
    outs = eng.generate(reqs)
    return eng, reqs, outs


def test_trace_all_spans_closed_and_schema_valid(traced_run, tmp_path):
    eng, _, _ = traced_run
    assert eng.obs.tracer.open_spans() == 0
    path = str(tmp_path / "trace.json")
    eng.export_trace(path)
    assert validate_chrome_trace_file(path) == []
    obj = json.load(open(path))
    ts = [e["ts"] for e in obj["traceEvents"] if e["ph"] != "M"]
    assert ts == sorted(ts)


def test_trace_covers_request_lifecycle(traced_run):
    eng, reqs, outs = traced_run
    obj = eng.obs.tracer.chrome_trace()
    for r in reqs:
        tid = REQ_TRACK_BASE + r.id
        lane = [e for e in obj["traceEvents"]
                if e["tid"] == tid and e["ph"] != "M"]
        names = [e["name"] for e in lane]
        assert "enqueue" in names and "first_token" in names
        assert "queue" in names and "active" in names
        assert "completed" in names
        # prefill chunk spans ride on the request's own lane
        assert any(n == "prefill_chunk" for n in names)
        done = [e for e in lane if e["name"] == "completed"]
        assert done[0]["args"]["tokens"] == len(outs[r.id])


def test_trace_request_id_continuity_across_preemption():
    """A preempted+resumed request's whole life lives on ONE track:
    preempt instant, a SECOND queue span, a second active span — all on
    the same tid."""
    cfg = _cfg(kv_mode="int8", pool_mode="always-augmented",
               trace=True, metrics=True)
    # 2 growing rows, 3 pages of storage: growth outruns augmentation and
    # the youngest row is preempted (test_scheduler.py's known-tight cell)
    eng = ServeEngine(cfg, MESH, max_batch=2, max_seq=32, prefill_chunk=16,
                      seed=4, pool_budget_bytes=3 * 8704)
    outs = eng.generate(_reqs(cfg, 2, 14, 6, seed=3))
    assert all(len(v) == 6 for v in outs.values())
    st = eng.stats()
    assert st["preemptions"] >= 1, "config must force preemption"
    obj = eng.obs.tracer.chrome_trace()
    by_tid = {}
    for e in obj["traceEvents"]:
        if e["ph"] != "M":
            by_tid.setdefault(e["tid"], []).append(e["name"])
    preempted = [tid for tid, names in by_tid.items()
                 if tid >= REQ_TRACK_BASE and "preempt" in names]
    assert preempted
    for tid in preempted:
        names = by_tid[tid]
        assert names.count("queue") >= 2        # re-queued on the same lane
        assert names.count("active") >= 2       # re-admitted on the same lane
        assert "completed" in names
    assert eng.obs.tracer.open_spans() == 0
    counters = st["obs"]["counters"]
    assert counters["preempt_capacity"] == st["preemptions"]


def test_ttft_metrics_agree_with_trace_within_one_bucket(traced_run):
    eng, _, _ = traced_run
    obj = eng.obs.tracer.chrome_trace()
    enq, first = {}, {}
    for e in obj["traceEvents"]:
        if e["ph"] != "i":
            continue
        if e["name"] == "enqueue":
            enq[e["tid"]] = e["ts"]
        elif e["name"] == "first_token":
            first.setdefault(e["tid"], e["ts"])
    ttfts = [(first[t] - enq[t]) * 1e-6 for t in enq]
    ref = LogHistogram()
    for t in ttfts:
        ref.observe(t)
    h = eng.stats()["obs"]["histograms"]["ttft_s"]
    assert h["count"] == len(ttfts)
    for p in (50, 90, 99):
        assert ref.within_one_bucket(ref.percentile(p), h[f"p{p}"])


def test_mode_mix_and_occupancy_timelines(traced_run):
    eng, _, _ = traced_run
    st = eng.stats()
    # the O(1) incremental mode-mix counters agree with the reduction
    # describe() computes from the allocation tables
    assert eng.store.mode_mix() == (st["pool"]["pages_live_normal"],
                                    st["pool"]["pages_live_augmented"])
    ts = st["obs"]["timeseries"]
    for key in ("mode_normal", "mode_augmented", "pool_occupancy",
                "queue_depth", "refresh_debt"):
        assert key in ts and ts[key]["n_samples"] >= 1, key
    # always-augmented store: every live unit is in the dynamic plane
    full = eng.obs.metrics.dump_timeseries()
    assert all(v == 0 for _, v in full["mode_normal"])
    assert any(v > 0 for _, v in full["mode_augmented"])
    assert any(v > 0 for _, v in full["energy_kv_read_fj"])
    # perfetto counter events mirror the sampled series
    obj = eng.obs.tracer.chrome_trace()
    assert any(e["ph"] == "C" and e["name"] == "mode_mix"
               for e in obj["traceEvents"])


# ---------------------------------------------------------------------------
# stats() idempotence + step-time surfacing (satellites)
# ---------------------------------------------------------------------------

def test_mode_mix_counters_match_reduction_on_slab_store():
    cfg = _cfg("mamba2-130m", pool_mode="always-augmented",
               trace=True, metrics=True)
    eng = ServeEngine(cfg, MESH, max_batch=2, max_seq=32, prefill_chunk=8)
    eng.generate(_reqs(cfg, 3, 6, 6))
    pool = eng.stats()["pool"]
    assert eng.store.mode_mix() == (pool["slabs_live_normal"],
                                    pool["slabs_live_augmented"])
    ts = eng.stats()["obs"]["timeseries"]
    assert ts["mode_augmented"]["n_samples"] >= 1


def test_stats_idempotent_with_and_without_obs():
    for amc in (dict(kv_mode="int4", pool_mode="always-augmented"),
                dict(kv_mode="int4", pool_mode="always-augmented",
                     trace=True, metrics=True)):
        cfg = _cfg(**amc)
        eng = ServeEngine(cfg, MESH, max_batch=2, max_seq=32,
                          prefill_chunk=8)
        eng.generate(_reqs(cfg, 3, 4, 6))
        first = eng.stats()
        for _ in range(3):
            assert eng.stats() == first


def test_step_times_surfaced_for_every_run():
    cfg = _cfg(kv_mode="int4", pool_mode="always-augmented")  # no faults
    eng = ServeEngine(cfg, MESH, max_batch=2, max_seq=32, prefill_chunk=8)
    eng.generate(_reqs(cfg, 2, 4, 6))
    st = eng.stats()["step_times"]
    assert st["n_steps"] >= 6
    assert 0 < st["min_s"] <= st["mean_s"] <= st["max_s"]
    assert st["mitigations"] == 0


# ---------------------------------------------------------------------------
# fault + speculative lanes
# ---------------------------------------------------------------------------

def test_fault_injected_spec_run_traces_heal_events(tmp_path):
    """The acceptance scenario: speculative decoding under fault
    injection with tracing on — the exported trace is schema-valid and
    carries admit/prefill/decode/fault lanes; fault-lane instants agree
    with the engine's own fault counters."""
    cfg = _cfg(kv_mode="int4", pool_mode="always-augmented", spec_k=3,
               retention_steps=8, fault_rate=0.5, fault_seed=1,
               trace=True, metrics=True)
    eng = ServeEngine(cfg, MESH, max_batch=2, max_seq=64, prefill_chunk=16)
    outs = eng.generate(_reqs(cfg, 3, 20, 8))
    st = eng.stats()
    assert st["faults"]["faults_injected"] > 0
    assert st["faults"]["zero_silent_corruption"]
    path = str(tmp_path / "fault_trace.json")
    eng.export_trace(path)
    assert validate_chrome_trace_file(path) == []
    obj = json.load(open(path))
    names = [e["name"] for e in obj["traceEvents"] if e["ph"] != "M"]
    assert "fault_pass" in names                 # fault lane spans
    assert "spec_draft" in names and "spec_verify" in names
    assert names.count("inject") == st["faults"]["faults_injected"]
    assert names.count("detect") == st["faults"]["faults_detected"]
    heals = names.count("heal_scrub") + names.count("heal_recompute")
    assert heals == st["faults"]["recovered"]
    c = st["obs"]["counters"]
    assert c["fault_inject"] == st["faults"]["faults_injected"]
    assert c.get("store_augment", 0) == st["augment_events"]
    # spec metrics plane
    assert st["obs"]["histograms"]["accepted_per_round"]["count"] \
        == st["spec"]["spec_rounds"]
    assert c["tokens_emitted"] == sum(len(v) for v in outs.values())


def test_obs_off_by_default_and_null_exports_raise():
    cfg = _cfg(kv_mode="int4", pool_mode="always-augmented")
    eng = ServeEngine(cfg, MESH, max_batch=2, max_seq=32, prefill_chunk=8)
    assert isinstance(eng.obs, NullEngineObs)
    eng.generate(_reqs(cfg, 2, 4, 4))
    assert eng.stats()["obs"] == {"enabled": False, "trace": False,
                                  "metrics": False}
    with pytest.raises(ValueError, match="disabled"):
        eng.export_trace("/tmp/nope.json")
    with pytest.raises(ValueError, match="disabled"):
        eng.export_metrics("/tmp/nope.prom")
    assert make_engine_obs(cfg.amc) is eng.obs   # shared Null singleton


def test_single_plane_modes_serve_and_export(tmp_path):
    """metrics-only and trace-only engines run, stats() describes them,
    and only the enabled plane exports (regression: describe() used to
    assume a recording tracer and crash metrics-only serving)."""
    for trace, metrics in ((False, True), (True, False)):
        cfg = _cfg(kv_mode="int8", trace=trace, metrics=metrics)
        eng = ServeEngine(cfg, MESH, max_batch=2, max_seq=32,
                          prefill_chunk=8)
        eng.generate(_reqs(cfg, 2, 4, 4))
        obs = eng.stats()["obs"]
        assert obs["enabled"] and obs["trace"] == trace \
            and obs["metrics"] == metrics
        if trace:
            eng.export_trace(str(tmp_path / "t.json"))
        else:
            assert obs["trace_events"] == 0 and obs["open_spans"] == 0
        if metrics:
            eng.export_metrics(str(tmp_path / "m.prom"))


def test_engine_prometheus_export(traced_run, tmp_path):
    eng, reqs, outs = traced_run
    path = str(tmp_path / "metrics.prom")
    text = eng.export_metrics(path)
    assert open(path).read() == text
    assert f"amc_requests_completed {len(reqs)}" in text
    total = sum(len(v) for v in outs.values())
    assert f"amc_tokens_emitted {total}" in text
    assert 'amc_ttft_s_bucket{le="+Inf"}' in text


# ---------------------------------------------------------------------------
# profiler annotations: the engine's phases in a jax.profiler capture
# ---------------------------------------------------------------------------

STEP_PHASES = {"admit", "refresh", "capacity", "dispatch", "readback",
               "bookkeeping"}


def _profile(tmp_path, fn):
    """Run `fn` under a jax.profiler capture; returns its `engine.*` host
    events as (name, start_ns, end_ns, {stat: value}), by start."""
    import jax
    jax.profiler.start_trace(str(tmp_path))
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    pd = jax.profiler.ProfileData.from_file(path)
    out = [(e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
           for plane in pd.planes if plane.name.startswith("/host:")
           for line in plane.lines for e in line.events
           if e.name.startswith("engine.")]
    return sorted(out, key=lambda e: (e[1], -e[2]))


def _inside(e, outer):
    return outer[1] <= e[1] and e[2] <= outer[2]


def _serve_with_queue(cfg, **kw):
    """Two rows, three requests: two admitted by add_request, the third
    by a later step's admission pass once a row frees."""
    eng = ServeEngine(cfg, MESH, max_batch=2, max_seq=48, prefill_chunk=8,
                      **kw)
    rows = [eng.add_request(r) for r in _reqs(cfg, 3, 12, 4)]
    assert rows[:2] == [0, 1] and rows[2] is None
    while eng.active.any() or eng.scheduler.queue:
        eng.step_all()
    return eng


@pytest.mark.parametrize("planes", [{}, {"trace": True, "metrics": True}],
                         ids=["null", "recording"])
def test_profiler_capture_nests_engine_phases_in_step(tmp_path, planes):
    cfg = _cfg(kv_mode="int4", pool_mode="always-augmented", **planes)
    holder = []
    ev = _profile(tmp_path,
                  lambda: holder.append(_serve_with_queue(cfg)))
    eng = holder[0]
    assert isinstance(eng.obs, NullEngineObs) == (not planes)
    steps = [e for e in ev if e[0] == "engine.step"]
    assert len(steps) == eng.step_idx
    assert [e[3]["step"] for e in steps] == list(range(eng.step_idx))
    for st in steps:
        inner = {e[0][len("engine."):] for e in ev
                 if e is not st and _inside(e, st)}
        assert STEP_PHASES <= inner, sorted(inner)
        assert 0 <= st[3]["rows"] <= 2 and st[3]["admitted"] >= 0
    # the step after add_request's two admissions counts them; the step
    # whose own pass admits the third request nests its prefill
    assert steps[0][3]["admitted"] == 2 and steps[0][3]["rows"] == 2
    late = [s for s in steps[1:] if s[3]["admitted"]]
    assert len(late) == 1 and late[0][3]["admitted"] == 1
    admit = [e for e in ev if e[0] == "engine.admit"
             and _inside(e, late[0])]
    assert admit[0][3] == {"admitted": 1, "prompt_tokens": 11}
    for name in ("engine.prefill", "engine.prefill_chunk"):
        assert any(e[0] == name and _inside(e, admit[0]) for e in ev)
    chunks = [e[3]["tokens"] for e in ev if e[0] == "engine.prefill_chunk"
              and _inside(e, admit[0])]
    assert chunks == [8, 3]


def test_add_request_admission_carries_admit_span(tmp_path):
    cfg = _cfg(kv_mode="int4", pool_mode="always-augmented")
    eng = ServeEngine(cfg, MESH, max_batch=2, max_seq=48, prefill_chunk=8)
    reqs = _reqs(cfg, 3, 10, 3)
    eng.add_request(reqs[0])
    eng.step_all()                     # compile outside the capture
    ev = _profile(tmp_path, lambda: [eng.add_request(r) for r in reqs[1:]])
    admits = [e for e in ev if e[0] == "engine.admit"]
    assert [a[3] for a in admits] == [
        {"admitted": 1, "prompt_tokens": 9},
        {"admitted": 0, "prompt_tokens": 0}]   # no free row: queued
    assert not any(e[0] == "engine.step" for e in ev)
    assert any(e[0] == "engine.prefill" and _inside(e, admits[0])
               and e[3] == {"tokens": 9} for e in ev)


def test_spec_and_fault_phases_nest_in_step(tmp_path):
    cfg = _cfg(kv_mode="int4", pool_mode="always-augmented", spec_k=3,
               retention_steps=8, fault_rate=0.5, fault_seed=1)
    eng = ServeEngine(cfg, MESH, max_batch=2, max_seq=64, prefill_chunk=16)
    ev = _profile(tmp_path, lambda: eng.generate(_reqs(cfg, 3, 20, 8)))
    steps = [e for e in ev if e[0] == "engine.step"]
    assert steps and all(s[3]["kind"] == "spec" for s in steps)
    spec = [s for s in steps if s[3]["rows"]]
    for st in spec:
        inner = collections.Counter(e[0] for e in ev
                                    if e is not st and _inside(e, st))
        for name in ("admit", "refresh", "capacity", "spec_draft",
                     "spec_verify", "readback", "bookkeeping",
                     "fault_pass"):
            assert inner["engine." + name] >= 1, (name, inner)
        # one readback per draft token and one after the verify
        assert inner["engine.readback"] == 3
    drafts = [e for e in ev if e[0] == "engine.spec_draft"]
    assert all(any(r[0] == "engine.readback" and _inside(r, d) for r in ev)
               for d in drafts)


def test_chrome_plane_records_the_profiled_phases(tmp_path):
    """With the Chrome-JSON plane on, each span site writes both sinks:
    the same phase names, as many times, with the same arguments."""
    cfg = _cfg(kv_mode="int4", pool_mode="always-augmented",
               trace=True, metrics=False)
    holder = []
    ev = _profile(tmp_path,
                  lambda: holder.append(_serve_with_queue(cfg)))
    eng = holder[0]
    prof = collections.Counter(e[0][len("engine."):] for e in ev)
    assert set(prof) == STEP_PHASES | {"step", "prefill", "prefill_chunk"}
    obj = eng.obs.tracer.chrome_trace()
    spans = [e for e in obj["traceEvents"]
             if e["ph"] == "X" and e["name"] in prof]
    assert collections.Counter(e["name"] for e in spans) == prof
    assert [e["args"] for e in spans if e["name"] == "step"] == [
        e[3] for e in ev if e[0] == "engine.step"]
    assert [e["args"] for e in spans if e["name"] == "admit"] == [
        e[3] for e in ev if e[0] == "engine.admit"]
