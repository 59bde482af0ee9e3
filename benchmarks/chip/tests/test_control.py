"""The control of the comparison, at a size a CPU test can hold: the
reference computed a precision step lower (float8 where the
configuration computes in bfloat16) reads as not correct, where the
program's own served tokens read as correct, on the same prompts and
served tokens. The engine serves a fixed set of requests to completion,
so the readings do not depend on how fast the test machine is."""
import numpy as np
import pytest

import tiny

LIMIT = 0.3          # the test cells' limit (test_faults.py)


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_control_reads_incorrect(seed):
    import harness
    import reference
    from repro.launch.mesh import make_local_mesh
    from repro.serve import Request, ServeEngine
    cfg = tiny.arch()
    eng = ServeEngine(cfg, make_local_mesh(), max_batch=4, max_seq=128,
                      seed=seed)
    rng = np.random.default_rng(seed)
    reqs = [Request(prompt=rng.integers(0, cfg.vocab, int(n)).astype(
        np.int32), max_new_tokens=40, id=i)
        for i, n in enumerate(rng.integers(8, 60, size=8))]
    outs = eng.generate(reqs)
    picked = {r.id: (r.prompt, outs[r.id]) for r in reqs}
    dims = reference.Dims(cfg.n_layers, cfg.d_model, cfg.n_heads,
                          cfg.n_kv_heads, cfg.hd, cfg.d_ff, cfg.vocab)
    check = harness.compare(dims, seed, picked, control=True)
    program = check["max_logit_gap"]
    control = check["control_max_logit_gap"]
    print(f"seed {seed}: program {program!r}, control {control!r}")
    assert program <= LIMIT < control
