"""The trace reduction on a trace recorded on the chip: a one-second
window of granite-3-2b.decode on one TPU v5e, six decode steps
of 16 rows, gzipped."""
import os

import pytest

import trace_reduce as tr
from conftest import HERE

TRACE = os.path.join(HERE, "data", "granite-decode-1s.xplane.pb.gz")


@pytest.fixture(scope="module")
def red():
    return tr.reduce_file(TRACE, chips=1)


def test_window_and_busy(red):
    assert red.window_s == pytest.approx(1.0393, abs=1e-3)
    assert 0 < red.busy_s <= red.window_s
    # the device idles a few % of a decode window (host work per step)
    assert 0.01 < 1 - red.busy_s / red.window_s < 0.05
    gaps = sum(b - a for a, b in red.gaps())
    assert gaps + red.busy_s == pytest.approx(red.window_s)


def test_programs_are_told_apart_by_their_kernels(red):
    runs = red.program_runs("decode")
    assert len(runs) == 6
    assert {e.name for e in runs} == {"jit__lambda(8319238475946320838)"}
    assert red.program_runs("prefill") == []
    for e in runs:
        assert 0.16 < e.end - e.start < 0.18


def test_kernel_seconds(red):
    paged = red.kernel_seconds("paged_kv_attention")
    assert paged == pytest.approx(red.kernel_seconds(
        "paged_kv_attention", within="decode"))
    # 40 layers x 6 steps of the paged kernel, ~3 ms a call
    assert 0.6 < paged < 0.8
    assert 0 < red.kernel_seconds("dual_plane_matmul", within="decode") < 0.1


def test_self_time_counts_nested_ops_once(red):
    own = red.self_seconds()
    assert "while" in own and own["while"] < 0.05 * red.busy_s
    assert sum(own.values()) == pytest.approx(red.busy_s, rel=1e-3)
    top = red.breakdown()
    assert top["device_ops"][0][0] == "paged_kv_attention"
    assert len(top["device_ops"]) <= 10 and len(top["idle_gaps"]) <= 10
    assert {name for name, _ in top["idle_gaps"]} <= {
        "bench.step_all", "bench.add_request", "bench.idle_wait", "none"}


def test_host_spans(red):
    steps = [e for e in red.host if e.name == "bench.step_all"]
    assert len(steps) == 6
    for e in steps:
        assert red.window[0] <= e.start < e.end <= red.window[1] + 1e-6
        assert red.overlap(e.start, e.end) <= e.end - e.start
