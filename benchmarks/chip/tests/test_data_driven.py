"""A cell made of new files only: a configuration with a reference module
of its own, a traffic mix, a serving shape and a per-layer metric reader,
added beside a copy of the benchmark. It loads by name and runs, and no
file that was there changes.
"""
import hashlib
import os
import time

import pytest

import tiny

# appended to a copy of the dense reference: a second reference module
# that records each request it judges
RECORDER = """

CALLS = []
_dense_served_gaps = served_gaps


def served_gaps(m, w, prompt, served, **kw):
    g = _dense_served_gaps(m, w, prompt, served, **kw)
    CALLS.append((len(prompt), len(served), float(g.max())))
    return g
"""


def _digest(root: str) -> dict:
    """sha256 of every file under `root`, bytecode caches left out."""
    out = {}
    for d, dirs, files in os.walk(root):
        dirs[:] = [x for x in dirs if x != "__pycache__"]
        for f in files:
            p = os.path.join(d, f)
            with open(p, "rb") as fh:
                out[os.path.relpath(p, root)] = hashlib.sha256(
                    fh.read()).hexdigest()
    return out


def test_new_cell_from_files_only(tmp_path):
    import harness
    root = tiny.make(str(tmp_path))
    bench = os.path.join(root, "benchmarks", "chip")
    before = _digest(bench)
    dense = tiny.load(root, "tiny.decode").reference
    # a configuration whose reference is a module of its own, new beside
    # the dense one, and a cell of it on the tiny decode mix
    with open(os.path.join(bench, "references", "dense_decoder.py")) as f:
        source = f.read()
    with open(os.path.join(bench, "references", "recorded_decoder.py"),
              "w") as f:
        f.write(source + RECORDER)
    b = tiny.read_bench(root)
    tiny.add_config(root, b, "tiny-recorded", dict(
        tiny.TINY_CONFIG,
        reference="benchmarks/chip/references/recorded_decoder.py"))
    name = tiny.add_workload(root, b, "tiny-recorded", "decode")
    # one more per-layer metric, defined by its reader file alone
    with open(os.path.join(bench, "metrics", "tokens_per_step.decode.py"),
              "w") as f:
        f.write("def read(ctx):\n"
                "    steps = ctx.steps_in_window()\n"
                "    return (sum(len(s.contexts) for s in steps) / len(steps)"
                " if steps else None)\n")
    b["per_layer"].append({"name": "tokens_per_step.decode", "unit": "tokens",
                           "better": "higher", "source": "host_clock",
                           "layer": "engine host loop",
                           "moves": "output_tok_s",
                           "workloads": [name]})
    tiny.write_bench(root, b)
    c = tiny.load(root, name)
    assert c.reference is not dense and c.reference.CALLS == []
    assert c.shape["max_batch"] == 4
    assert c.traffic["arrivals"]["kind"] == "backlog"
    assert "tokens_per_step.decode" in [m["name"] for m in c.per_layer]
    assert [m["name"] for m in c.end_to_end] == ["setup_s", "output_tok_s",
                                                 "itl_p95_ms"]
    r = harness.run(c, seed=2 ** 31 + 5, seconds=1.5, trace=False,
                    t_start=time.perf_counter(), arch_override=tiny.arch())
    assert list(r)[-1] == "checks"
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(r)
    assert set(r["metrics"]) == {"setup_s", "output_tok_s", "itl_p95_ms"}
    assert r["metrics"]["output_tok_s"]["value"] > 0
    assert r["device"]["platform"] == "cpu"
    # the comparison read its gaps through the configuration's module
    calls = c.reference.CALLS
    assert 0 < len(calls) <= c.shape["sample_requests"]
    assert r["checks"]["max_logit_gap"]["value"] == max(g for *_, g in calls)
    # the reader reads the run's own records
    ctx_reader = c.reader("tokens_per_step.decode")
    assert callable(ctx_reader)
    after = _digest(bench)
    added = {"references/recorded_decoder.py", "configs/tiny-recorded.json",
             "cells/tiny-recorded.decode.json",
             "metrics/tokens_per_step.decode.py"}
    assert set(after) - set(before) == added
    assert {k: after[k] for k in before} == before


@pytest.mark.parametrize("reference", [
    None, "benchmarks/chip/references/no_such_reference.py"])
def test_config_without_reference_fails_at_load(tmp_path, reference):
    """A configuration that names no reference module, or a missing one,
    is refused when its cell loads, by the configuration's name; there is
    no default reference."""
    root = tiny.make(str(tmp_path))
    conf = {k: v for k, v in tiny.TINY_CONFIG.items() if k != "reference"}
    if reference is not None:
        conf["reference"] = reference
    b = tiny.read_bench(root)
    tiny.add_config(root, b, "tiny-unreferenced", conf)
    name = tiny.add_workload(root, b, "tiny-unreferenced", "decode")
    tiny.write_bench(root, b)
    with pytest.raises((KeyError, FileNotFoundError),
                       match="tiny-unreferenced"):
        tiny.load(root, name)


def test_dense_reference_refuses_moe_before_engine(tmp_path, monkeypatch):
    """The dense reference's check refuses a mixture-of-experts model, and
    a run of it stops there, before an engine is built."""
    import harness
    from repro.configs import get_arch
    c = tiny.load(tiny.make(str(tmp_path)), "tiny.decode")
    moe = get_arch("qwen3-moe-30b-a3b").reduced()
    with pytest.raises(ValueError, match="dense decoder only"):
        c.reference.check(moe, c.config)

    def no_engine(*args, **kwargs):
        raise AssertionError("an engine was built")
    monkeypatch.setattr(harness, "build_engine", no_engine)
    with pytest.raises(ValueError, match="dense decoder only"):
        harness.run(c, seed=3, seconds=0.5, trace=False,
                    t_start=time.perf_counter(), arch_override=moe)


def test_window_without_admissions(tmp_path, monkeypatch):
    """A backlog window short against its requests' outputs, in which
    few or none of them finish, still reports its numbers."""
    import copy

    import harness
    traffic = copy.deepcopy(tiny.TRAFFIC)
    traffic["decode"]["output_tokens"].update(min=100, max=100)
    monkeypatch.setattr(tiny, "TRAFFIC", traffic)
    c = tiny.load(tiny.make(str(tmp_path)), "tiny.decode")
    r = harness.run(c, seed=4, seconds=0.5, trace=False,
                    t_start=time.perf_counter(), arch_override=tiny.arch())
    assert r["metrics"]["output_tok_s"]["value"] > 0
    assert r["checks"]["max_logit_gap"]["value"] >= 0
