"""A cell made of new files only: a configuration, a traffic mix, a
serving shape and a per-layer metric reader, added beside a copy of the
benchmark. It loads by name and runs, and no file that was there changes.
"""
import hashlib
import json
import os
import time

import tiny


def _digest(root: str) -> dict:
    out = {}
    for d, _, files in os.walk(root):
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
    # one more per-layer metric, defined by its reader file alone
    with open(os.path.join(bench, "metrics", "tokens_per_step.decode.py"),
              "w") as f:
        f.write("def read(ctx):\n"
                "    steps = ctx.steps_in_window()\n"
                "    return (sum(len(s.contexts) for s in steps) / len(steps)"
                " if steps else None)\n")
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        b = json.load(f)
    b["per_layer"].append({"name": "tokens_per_step.decode", "unit": "tokens",
                           "better": "higher", "source": "host_clock",
                           "layer": "engine host loop",
                           "moves": "output_tok_s",
                           "workloads": ["tiny.decode"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(b, f)
    c = tiny.load(root, "tiny.decode")
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
    # the reader reads the run's own records
    ctx_reader = c.reader("tokens_per_step.decode")
    assert callable(ctx_reader)
    after = _digest(bench)
    del after["metrics/tokens_per_step.decode.py"]
    assert after == before


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
