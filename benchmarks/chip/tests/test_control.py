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


@pytest.fixture(scope="module")
def tiny_decode(tmp_path_factory):
    return tiny.load(tiny.make(str(tmp_path_factory.mktemp("bench"))),
                     "tiny.decode")


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_control_reads_incorrect(tiny_decode, seed):
    import harness
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
    ref = tiny_decode.reference
    check = harness.compare(ref, ref.Dims.from_arch(cfg), seed, picked,
                            control=True)
    program = check["max_logit_gap"]
    control = check["control_max_logit_gap"]
    print(f"seed {seed}: program {program!r}, control {control!r}")
    assert program <= LIMIT < control


def test_tiny_decode_gaps_as_recorded(tiny_decode):
    """The comparison of the tiny decode cell reads, through the cell's
    reference module, exactly what it read when the harness imported the
    dense reference by name: six requests of the cell's mix at a fixed
    seed, served to completion by the harness's engine."""
    import jax

    import harness
    import traffic
    from repro.serve import Request
    c, cfg, seed = tiny_decode, tiny.arch(), 2 ** 31 + 5
    gen = traffic.Generator(c.traffic, seed, cfg.vocab)
    eng = harness.build_engine(jax, cfg, c.shape, seed, 1)
    offers = [gen.offer(i) for i in range(6)]
    outs = eng.generate([Request(prompt=o.prompt,
                                 max_new_tokens=o.max_new_tokens,
                                 id=o.index) for o in offers])
    picked = {o.index: (o.prompt, outs[o.index]) for o in offers}
    check = harness.compare(c.reference, c.reference.Dims.from_arch(cfg),
                            seed, picked, control=True)
    assert check["tokens_compared"] == 120
    assert check["per_request"] == [
        0.019990205764770508, 0.0, 0.02291560173034668,
        0.04322671890258789, 0.06877899169921875, 0.027533531188964844]
    assert check["max_logit_gap"] == 0.06877899169921875
    assert check["control_per_request"] == [
        0.014917135238647461, 0.10884547233581543, 0.023528337478637695,
        0.04322671890258789, 0.23496496677398682, 0.0463482141494751]
