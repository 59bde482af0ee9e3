"""The program-span readers on a trace recorded on the chip: a 1.74 s
window of granite-3-2b.decode on one TPU v5e holding four engine steps of
16 rows, the second of which admits a queued request and prefills its
161 prompt tokens (record_admission_trace.py), gzipped. On the older
fixture, recorded before the engine wrote its own spans, every reader
returns None."""
import os
import types

import pytest

import program_spans as ps
import trace_reduce as tr
from conftest import HERE

TRACE = os.path.join(HERE, "data", "granite-decode-admit.xplane.pb.gz")
OLD = os.path.join(HERE, "data", "granite-decode-1s.xplane.pb.gz")
PHASES = {"admit", "refresh", "capacity", "dispatch", "readback",
          "bookkeeping"}


def _ctx(path):
    notes = []
    return types.SimpleNamespace(trace=tr.reduce_file(path, chips=1),
                                 trace_path=path, note=notes.append,
                                 notes=notes)


@pytest.fixture(scope="module")
def ctx():
    return _ctx(TRACE)


@pytest.fixture(scope="module")
def prog(ctx):
    return ps.of(ctx)


def test_steps_nest_the_engine_phases(prog):
    assert [s.args["admitted"] for s in prog.steps] == [0, 1, 0, 0]
    assert len(prog.plain) == 3
    for s in prog.steps:
        assert s.args["rows"] == 16 and s.args["kind"] == "decode"
        assert PHASES <= {c.name for c in prog.children(s)}
    admit, = [c for c in prog.children(prog.steps[1]) if c.name == "admit"]
    assert admit.args == {"admitted": 1, "prompt_tokens": 161}
    prefill, = prog.children(admit)
    chunks = prog.children(prefill)
    assert {c.name for c in chunks} == {"prefill_chunk"}
    assert sum(c.args["tokens"] for c in chunks) \
        == admit.args["prompt_tokens"] == prefill.args["tokens"]
    # the client's top-up of the queue after the admission: a pass
    # outside any step that finds no free row
    outside = [s for s in prog.spans if s.name == "admit" and not s.parent]
    assert [s.args["admitted"] for s in outside] == [0]
    assert prog.outside_admits() == []


def test_programs_named_by_their_functions(ctx):
    red = ctx.trace
    decode = {e.name.split("(")[0] for e in red.program_runs("decode")}
    prefill = {e.name.split("(")[0] for e in red.program_runs("prefill")}
    assert decode == {"jit_decode_step"}
    assert prefill == {"jit_prefill_step"}
    kinds = red.self_seconds()
    assert "paged_kv_attention" in kinds and "dual_plane_matmul" in kinds


def test_host_loop_readers(ctx, prog):
    readback = ps.readback_ms_per_step(ctx)
    work = ps.host_work_ms_per_step(ctx)
    assert readback == pytest.approx(2.7597, abs=1e-3)
    assert work == pytest.approx(3.3847, abs=1e-3)
    # together they are the device-idle time of a plain step's span
    idle = sum(prog.idle_in(s) for s in prog.plain) / len(prog.plain)
    assert readback + work == pytest.approx(1e3 * idle)
    # ... and nearly all of the harness's own step_all span around it
    bench = [e for e in ctx.trace.host if e.name == "bench.step_all"
             and any(e.start <= s.start and s.end <= e.end
                     for s in prog.plain)]
    assert len(bench) == 3
    outer = 1e3 * sum(prog.idle(e.start, e.end) for e in bench) / 3
    assert readback + work == pytest.approx(outer, rel=0.15)
    # the named phases hold the host work
    named = sum(prog.idle(c.start, c.end) for s in prog.plain
                for c in prog.children(s) if c.name != "readback")
    assert 1e3 * named / len(prog.plain) >= 0.9 * work


def test_admission_stall_reads_the_prefill(ctx, prog):
    stall = ps.admission_stall_ms(ctx)
    assert stall == pytest.approx(891.47, abs=0.01)
    prefill = sum(e.end - e.start
                  for e in ctx.trace.program_runs("prefill"))
    assert stall == pytest.approx(1e3 * prefill, rel=0.15)
    assert ps.rows_per_step(ctx) == 16


def test_idle_gaps_carry_engine_phases(prog):
    gaps = prog.idle_gaps()
    assert len(gaps) == 10
    named = [n for n, _ in gaps if n.startswith("bench.step_all>engine.")]
    assert len(named) >= 8
    split = prog.idle_by_phase()
    total = sum(b - a for a, b in prog.red.gaps())
    assert sum(split.values()) == pytest.approx(total)
    assert split["step>readback"] > 0


def test_metric_files_read_the_program_spans(ctx):
    import cell
    c = cell.load("granite-3-2b.decode")
    names = [m["name"] for m in c.per_layer]
    for name, fn in (("readback_ms_per_step.decode", "readback_ms_per_step"),
                     ("host_work_ms_per_step.decode",
                      "host_work_ms_per_step"),
                     ("admission_stall_ms.decode", "admission_stall_ms")):
        assert name in names
        assert c.reader(name)(ctx) == getattr(ps, fn)(ctx)
    chat = cell.load("granite-3-2b.chat")
    for name in ("admission_stall_ms.chat", "rows_per_step.chat"):
        assert name in [m["name"] for m in chat.per_layer]
        assert chat.reader(name)(ctx) is not None


def test_no_program_spans_reads_none():
    """A program that writes no `engine.*` spans (the older fixture)
    gives every reader nothing to read, and none raises."""
    old = _ctx(OLD)
    assert ps.of(old) is None
    for fn in (ps.readback_ms_per_step, ps.host_work_ms_per_step,
               ps.admission_stall_ms, ps.rows_per_step):
        assert fn(old) is None
    assert old.notes == []
