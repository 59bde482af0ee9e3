"""The serving program's own phase spans in a run's profiler trace, and
the arithmetic of the per-layer metrics that read them.

The engine marks each phase of its work with a `jax.profiler` annotation
named `engine.<phase>`, which the profiler records on the host plane, on
the clock of the device trace, with the span's arguments as event stats:

  engine.step {step, kind, rows, admitted}   one `step_all`; `rows` are
      the rows its dispatch served, `admitted` the requests admitted
      since the previous step (by `add_request` or by its own pass)
    engine.admit {admitted, prompt_tokens}   the admission pass
      engine.prefill {tokens}, engine.prefill_chunk {tokens}
    engine.refresh, engine.fault_pass, engine.capacity,
    engine.dispatch (or engine.spec_draft, engine.spec_verify),
    engine.readback (the argmax read back to the host),
    engine.bookkeeping
  engine.admit outside any step: an admission made by `add_request`

A "plain step" is an `engine.step` with no admission since the previous
step. Only spans wholly inside the harness's `bench.window` are read. A
program that writes no such annotations yields no spans, and every
reader built on them returns None.
"""
from __future__ import annotations

import dataclasses
import os
import statistics

import trace_reduce

PREFIX = "engine."


@dataclasses.dataclass
class Span:
    name: str             # the phase, without PREFIX
    start: float          # seconds, on the trace's clock
    end: float
    args: dict
    parent: "Span | None" = None
    depth: int = 0

    @property
    def wall(self) -> float:
        return self.end - self.start


def read_spans(path: str) -> list[Span]:
    """Every `engine.*` host event of an `.xplane.pb` (or `.xplane.pb.gz`)
    file, by start, each with its enclosing span."""
    import jax
    if path.endswith(".gz"):
        import gzip
        with gzip.open(path) as f:
            pd = jax.profiler.ProfileData.from_serialized_xspace(f.read())
    else:
        pd = jax.profiler.ProfileData.from_file(path)
    spans = [Span(e.name[len(PREFIX):], e.start_ns * 1e-9,
                  (e.start_ns + e.duration_ns) * 1e-9, dict(e.stats))
             for plane in pd.planes if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events
             if e.name.startswith(PREFIX)]
    spans.sort(key=lambda s: (s.start, -s.end))
    stack: list[Span] = []
    for s in spans:
        while stack and stack[-1].end < s.end:
            stack.pop()
        if stack:
            s.parent, s.depth = stack[-1], stack[-1].depth + 1
        stack.append(s)
    return spans


class Program:
    """The window's program spans beside the trace's device intervals
    (`trace_reduce.Reduction`)."""

    def __init__(self, spans: list[Span], red):
        w0, w1 = red.window
        self.red = red
        self.spans = [s for s in spans if s.start >= w0 and s.end <= w1]
        self.steps = [s for s in self.spans if s.name == "step"]
        self.plain = [s for s in self.steps if s.args.get("admitted") == 0]
        self._children: dict[int, list[Span]] = {}
        for s in self.spans:
            if s.parent is not None:
                self._children.setdefault(id(s.parent), []).append(s)

    def children(self, span: Span) -> list[Span]:
        return self._children.get(id(span), [])

    def idle(self, a: float, b: float) -> float:
        """Seconds of [a, b] in which the device ran no operation."""
        return max(0.0, (b - a) - self.red.overlap(a, b))

    def idle_in(self, span: Span, phase: str | None = None) -> float:
        """Device-idle seconds inside `span`, or only inside its direct
        children named `phase`."""
        if phase is None:
            return self.idle(span.start, span.end)
        return sum(self.idle(c.start, c.end) for c in self.children(span)
                   if c.name == phase)

    def outside_admits(self) -> list[Span]:
        """Admission passes made outside any step (by `add_request`) that
        admitted a request."""
        return [s for s in self.spans if s.name == "admit"
                and s.parent is None and s.args.get("admitted", 0) > 0]

    def admitted(self) -> int:
        """Requests admitted by the window's admission passes."""
        return sum(s.args.get("admitted", 0) for s in self.spans
                   if s.name == "admit")

    def phase_at(self, a: float, b: float) -> str | None:
        """The innermost program span covering most of [a, b]: the
        innermost one that covers more than half of it, else, where the
        program's spans together do, the one covering the largest part.
        None where they cover half of it or less (an idle client)."""
        half = (b - a) / 2
        cover = [(min(b, s.end) - max(a, s.start), s) for s in self.spans]
        cover = [(c, s) for c, s in cover if c > 0]
        if sum(c for c, s in cover if s.parent is None) <= half:
            return None
        _, best = max(cover, key=lambda cs: (
            cs[0] > half, cs[1].depth if cs[0] > half else cs[0],
            cs[1].depth))
        return PREFIX + best.name

    def gap_name(self, a: float, b: float) -> str:
        """`bench.<span>>engine.<phase>`, or the bench span's name alone
        where the program's spans cover half of the gap or less."""
        bench = self.red.host_at(a, b)
        phase = self.phase_at(a, b)
        return bench if phase is None else f"{bench}>{phase}"

    def idle_gaps(self, n: int = 10) -> list[list]:
        """The n longest device-idle gaps of the window, named."""
        gaps = sorted(self.red.gaps(), key=lambda g: g[0] - g[1])[:n]
        return [[self.gap_name(a, b), b - a] for a, b in gaps]

    def idle_by_phase(self) -> dict[str, float]:
        """The window's device-idle seconds split by where the host was:
        `step>admit`, `step>readback`, ... for the phases of the steps,
        `step` for a step's time between its phases, `admit` (and
        `admit>prefill`) for admissions outside a step, and "none"
        outside every program span."""
        out: dict[str, float] = {}

        def add(k, v):
            out[k] = out.get(k, 0.0) + v

        for s in self.spans:
            if s.parent is not None:
                continue
            inner = 0.0
            for c in self.children(s):
                v = self.idle(c.start, c.end)
                add(f"{s.name}>{c.name}", v)
                inner += v
            add(s.name, self.idle(s.start, s.end) - inner)
        total = sum(b - a for a, b in self.red.gaps())
        add("none", total - sum(out.values()))
        return out


def trace_file(ctx) -> str:
    """The run's profiler trace: where the harness wrote it, or the file a
    test's context names as `trace_path`."""
    path = getattr(ctx, "trace_path", None)
    if path:
        return path
    import cell
    return trace_reduce.find_xplane(
        os.path.join(cell.ROOT, ".bench_trace", ctx.cell.name))


def of(ctx) -> Program | None:
    """The run's program spans, read once per run (kept on the run's
    context as `program_spans`); None where the program writes none. The
    first read notes the window's idle gaps by phase."""
    if "program_spans" in vars(ctx):
        return ctx.program_spans
    prog = Program(read_spans(trace_file(ctx)), ctx.trace)
    if not prog.steps:
        prog = None
    else:
        ctx.note(f"program spans: {len(prog.steps)} steps "
                 f"({len(prog.plain)} plain), {prog.admitted()} admitted")
        ctx.note(f"idle gaps by phase: {prog.idle_gaps()}")
        split = sorted(prog.idle_by_phase().items(), key=lambda kv: -kv[1])
        ctx.note(f"idle s by phase: {split}")
    ctx.program_spans = prog
    return prog


# -- the readers' arithmetic --------------------------------------------------

def readback_ms_per_step(ctx) -> float | None:
    """Mean device-idle ms inside `engine.readback` per plain step."""
    p = of(ctx)
    if p is None or not p.plain:
        return None
    return 1e3 * statistics.fmean(p.idle_in(s, "readback") for s in p.plain)


def host_work_ms_per_step(ctx) -> float | None:
    """Mean device-idle ms inside `engine.step` and outside its
    `engine.readback`, per plain step."""
    p = of(ctx)
    if p is None or not p.plain:
        return None
    work = [p.idle_in(s) - p.idle_in(s, "readback") for s in p.plain]
    named = sum(p.idle(c.start, c.end) for s in p.plain
                for c in p.children(s) if c.name != "readback")
    if sum(work) > 0:
        ctx.note(f"host work: the named phases hold "
                 f"{100 * named / sum(work)!r} % of its idle time")
    bench = [e for e in p.red.host if e.name == "bench.step_all"
             and any(e.start <= s.start and s.end <= e.end
                     for s in p.plain)]
    if bench:
        idle = statistics.fmean(p.idle(e.start, e.end) for e in bench)
        ctx.note(f"host work: bench.step_all idle per plain step "
                 f"{1e3 * idle!r} ms")
    return 1e3 * statistics.fmean(work)


def admission_stall_ms(ctx) -> float | None:
    """Per admitted request: the wall of the steps with an admission since
    the previous step, less the median plain-step wall each, plus the
    wall of the admissions made outside any step."""
    p = of(ctx)
    if p is None or not p.plain:
        return None
    n = p.admitted()
    if n == 0:
        return None
    plain = statistics.median(s.wall for s in p.plain)
    stall = sum(s.wall - plain for s in p.steps if s.args.get("admitted"))
    stall += sum(s.wall for s in p.outside_admits())
    prefill = sum(e.end - e.start for e in p.red.program_runs("prefill"))
    ctx.note(f"admissions: {n} admitted, stall {stall!r} s, prefill "
             f"program device time {prefill!r} s")
    return 1e3 * stall / n


def rows_per_step(ctx) -> float | None:
    """Mean rows served by the window's steps."""
    p = of(ctx)
    if p is None:
        return None
    return statistics.fmean(s.args.get("rows", 0) for s in p.steps)
