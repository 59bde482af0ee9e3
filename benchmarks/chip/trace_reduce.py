"""Reduction of one profiler trace (`.xplane.pb`) to what the per-layer
metrics read: the device's busy intervals in the window, device time by
operation and by program, the harness's host spans, and the idle gaps
with the host span that covers them.

What a TPU v5e trace holds (read by hand from a chip trace): each
`/device:TPU:<n>` plane has an "XLA Modules" line, one event per run of
a compiled program, named `jit_<function>(<fingerprint>)` (the engine's
decode and prefill steps are both `jit__lambda(...)`), and an "XLA Ops"
line, one event per HLO operation, named by the operation's HLO text
(`%paged_kv_attention.8 = bf16[...] custom-call(...)`). A Pallas kernel's
operation carries the name of the kernel's jitted wrapper
(`paged_kv_attention`, `dual_plane_matmul`, `quantize_pack_kv`). A
`while` operation (the layer scan) spans the operations of its body.
The host plane holds the harness's `bench.*` spans on the same clock.

The window is the `bench.window` span. Busy time is the union of the
operations' intervals, averaged over the chips used. A program run is
told to be the engine's decode step by the paged-attention kernel inside
it, and its prefill step by a dual-plane matmul without one.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PREFIX = "bench."
DECODE_KERNEL = "paged_kv_attention"
PREFILL_KERNEL = "dual_plane_matmul"


def op_kind(name: str) -> str:
    """`%paged_kv_attention.8 = bf16[...] ...` -> `paged_kv_attention`."""
    head = name.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"\.\d+$", "", head)


@dataclasses.dataclass
class Event:
    name: str
    start: float          # seconds, on the trace's clock
    end: float


@dataclasses.dataclass
class Reduction:
    window: tuple[float, float]
    ops: list[Event]                 # device operations in the window
    modules: list[Event]             # device program runs in the window
    host: list[Event]                # bench.* spans
    busy: list[tuple[float, float]]  # union of the ops' intervals, all chips
    busy_by_chip: list[float]        # busy seconds of each chip

    def __post_init__(self):
        self.ops.sort(key=lambda e: e.start)
        self._starts = [e.start for e in self.ops]
        self._kinds = [op_kind(e.name) for e in self.ops]
        self._program = self._classify()

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    @property
    def busy_s(self) -> float:
        return sum(self.busy_by_chip) / len(self.busy_by_chip)

    def ops_within(self, run: Event) -> range:
        """Indices of the operations that start inside a program run."""
        lo = bisect.bisect_left(self._starts, run.start)
        hi = bisect.bisect_right(self._starts, run.end)
        return range(lo, hi)

    def _classify(self) -> dict[str, str]:
        """Program name -> "decode" | "prefill" | "other", from the
        kernels that run inside its runs."""
        kinds: dict[str, set] = {}
        for run in self.modules:
            seen = kinds.setdefault(run.name, set())
            if DECODE_KERNEL in seen:
                continue
            seen.update(self._kinds[i] for i in self.ops_within(run))
        return {name: ("decode" if DECODE_KERNEL in seen else
                       "prefill" if PREFILL_KERNEL in seen else "other")
                for name, seen in kinds.items()}

    def program_runs(self, which: str) -> list[Event]:
        return [e for e in self.modules if self._program[e.name] == which]

    def kernel_seconds(self, kernel: str, within: str | None = None) -> float:
        """Device seconds of the operations of `kernel`, in the window or
        only inside runs of the `within` program."""
        if within is None:
            return sum(e.end - e.start for e, k in zip(self.ops, self._kinds)
                       if k == kernel)
        return sum(self.ops[i].end - self.ops[i].start
                   for run in self.program_runs(within)
                   for i in self.ops_within(run) if self._kinds[i] == kernel)

    def self_seconds(self) -> dict[str, float]:
        """Device time by operation kind: each operation's own time less
        that of the operations nested in it (a while loop's body)."""
        out: dict[str, float] = {}
        stack: list[list] = []          # [end, kind, nested s, own s]

        def close(entry):
            out[entry[1]] = out.get(entry[1], 0.0) + entry[3] - entry[2]

        for e, k in zip(self.ops, self._kinds):
            while stack and stack[-1][0] <= e.start:
                close(stack.pop())
            if stack:
                stack[-1][2] += e.end - e.start
            stack.append([e.end, k, 0.0, e.end - e.start])
        while stack:
            close(stack.pop())
        return out

    def gaps(self) -> list[tuple[float, float]]:
        """Intervals of the window in which no operation ran."""
        out, t = [], self.window[0]
        for a, b in self.busy:
            if a > t:
                out.append((t, a))
            t = max(t, b)
        if self.window[1] > t:
            out.append((t, self.window[1]))
        return out

    def host_at(self, a: float, b: float) -> str:
        """The bench.* span (other than the window) that covers most of
        [a, b], or "none"."""
        best, cover = "none", 0.0
        for e in self.host:
            if e.name == "bench.window":
                continue
            c = min(b, e.end) - max(a, e.start)
            if c > cover:
                best, cover = e.name, c
        return best

    def overlap(self, a: float, b: float) -> float:
        """Device-busy seconds inside [a, b]."""
        lo = bisect.bisect_left(self.busy, (a, a)) - 1
        total = 0.0
        for x, y in self.busy[max(lo, 0):]:
            if x >= b:
                break
            total += max(0.0, min(b, y) - max(a, x))
        return total

    def breakdown(self) -> dict:
        ops = sorted(self.self_seconds().items(), key=lambda kv: -kv[1])
        gaps = sorted(self.gaps(), key=lambda g: g[0] - g[1])[:10]
        return {"device_ops": [[n, s] for n, s in ops[:10]],
                "idle_gaps": [[self.host_at(a, b), b - a] for a, b in gaps]}


def _union(intervals) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _events(line) -> list[Event]:
    return [Event(e.name, e.start_ns * 1e-9,
                  (e.start_ns + e.duration_ns) * 1e-9) for e in line.events]


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def reduce_file(path: str, chips: int) -> Reduction:
    """Reduce an `.xplane.pb` file (or its gzip, `.xplane.pb.gz`)."""
    import jax
    if path.endswith(".gz"):
        import gzip
        with gzip.open(path) as f:
            pd = jax.profiler.ProfileData.from_serialized_xspace(f.read())
    else:
        pd = jax.profiler.ProfileData.from_file(path)
    ops, modules, host = {}, [], []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m and int(m.group(1)) < chips:
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops[int(m.group(1))] = _events(line)
                elif line.name == MODULES_LINE:
                    modules += _events(line)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [e for e in _events(line)
                         if e.name.startswith(HOST_PREFIX)]
    win = [e for e in host if e.name == "bench.window"]
    if not win:
        raise ValueError(f"{path}: no bench.window span")
    if len(ops) != chips:
        raise ValueError(f"{path}: device operations of {len(ops)} chips, "
                         f"{chips} expected")
    w0, w1 = win[0].start, win[0].end

    def inside(e):
        return e.end > w0 and e.start < w1

    def clip(es):
        return [(max(e.start, w0), min(e.end, w1)) for e in es if inside(e)]

    by_chip = [sum(b - a for a, b in _union(clip(es)))
               for es in ops.values()]
    flat = [e for es in ops.values() for e in es if inside(e)]
    return Reduction((w0, w1), flat, [e for e in modules if inside(e)],
                     host, _union(clip(flat)), by_chip)


def reduce_dir(trace_dir: str, chips: int) -> Reduction:
    return reduce_file(find_xplane(trace_dir), chips)
