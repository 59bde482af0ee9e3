"""Distributed substrate: sharding rules, gradient compression, pipeline
parallelism, fault/straggler handling. Runs on a 4-device CPU sub-mesh via
XLA host-device override in a subprocess-free way (this file re-execs jax
with 4 devices only if the flag isn't already set — so it composes with
the 1-device default used elsewhere: tests here use mesh shapes of 1)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType

from repro.configs import get_arch, get_shape
from repro.launch.mesh import mesh_context
from repro.distributed.fault import (SimulatedFailure, StragglerMonitor,
                                     Supervisor)
from repro.distributed.sharding import Rules


# ---------------------------------------------------------------------------
# sharding rules (pure logic — use fake meshes via jax.make_mesh on 1 dev)
# ---------------------------------------------------------------------------

class FakeMesh:
    def __init__(self, shape):
        self.shape = shape


def _rules(cfg, shape_name, mesh_shape=None):
    mesh = FakeMesh(mesh_shape or {"data": 16, "model": 16})
    return Rules.make(mesh, cfg, get_shape(shape_name))


def test_rules_head_tp_disabled_for_indivisible_heads():
    r = _rules(get_arch("minicpm-2b"), "train_4k")       # 36 heads
    assert r.resolve("heads") is None
    assert r.resolve("mlp") == ("model",)                 # 5760 % 16 == 0
    r2 = _rules(get_arch("whisper-tiny"), "train_4k")     # 6 heads
    assert r2.resolve("heads") is None
    r3 = _rules(get_arch("granite-3-2b"), "train_4k")     # 32 heads
    assert r3.resolve("heads") == ("model",)


def test_rules_heads_degrade_to_replication_on_fleet_submeshes():
    """Per-array fleet meshes put the array's devices on "model"; heads
    shard TP only where the count divides that axis and replicate
    otherwise — never a crash, never a silent mis-shard."""
    cfg = get_arch("granite-3-2b")                        # 32 heads
    for model_axis, want in [(1, ("model",)), (2, ("model",)),
                             (4, ("model",)), (3, None), (5, None),
                             (7, None)]:
        r = _rules(cfg, "train_4k", {"data": 1, "model": model_axis})
        assert r.resolve("heads") == want, \
            f"32 heads over model={model_axis}: got {r.resolve('heads')}"
    # indivisible head count degrades even on a power-of-two axis
    r = _rules(get_arch("minicpm-2b"), "train_4k",        # 36 heads
               {"data": 1, "model": 8})
    assert r.resolve("heads") is None
    assert r.resolve("mlp") == ("model",)                  # 5760 % 8 == 0


def test_rules_single_device_array_replicates_trivially():
    """The over-host fleet case: every logical array shares one CPU
    device, model axis 1 — everything "shards" onto the single device
    (resolve returns the axis; the mesh makes it a no-op)."""
    r = _rules(get_arch("granite-3-2b"), "train_4k",
               {"data": 1, "model": 1})
    assert r.resolve("heads") == ("model",)
    assert r.resolve("mlp") == ("model",)


def test_rules_kv_vs_cache_seq_exclusive():
    # kv=16 divides 16 -> kv TP, no cache seq sharding
    r = _rules(get_arch("qwen1.5-0.5b"), "decode_32k")
    assert r.resolve("kv_heads") == ("model",)
    assert r.resolve("cache_seq") is None
    # kv=8 doesn't divide 16 -> SP on the cache
    r2 = _rules(get_arch("granite-3-2b"), "decode_32k")
    assert r2.resolve("kv_heads") is None
    assert r2.resolve("cache_seq") == ("model",)


def test_rules_batch_not_sharded_when_too_small():
    r = _rules(get_arch("mamba2-130m"), "long_500k")      # batch 1
    assert r.resolve("batch") is None


def test_rules_moe_modes():
    r = _rules(get_arch("qwen3-moe-30b-a3b"), "train_4k")
    assert r.resolve("experts") == ("model",)              # EP: 128/16
    r2 = _rules(get_arch("grok-1-314b"), "train_4k")
    assert r2.resolve("experts") is None                   # TP mode: 8 experts
    assert r2.resolve("mlp") == ("model",)


def test_param_and_opt_spec_trees_align():
    from repro.models import model as M
    from repro.train import step as step_lib
    cfg = get_arch("qwen3-moe-30b-a3b")
    ap = M.abstract_params(cfg)
    oa = step_lib.opt_abstract(ap, "amc_adamw")
    # same tree structure for m_q as params
    assert (jax.tree.structure(oa.m_q, is_leaf=lambda x: hasattr(x, "axes"))
            == jax.tree.structure(ap, is_leaf=lambda x: hasattr(x, "axes")))


# ---------------------------------------------------------------------------
# gradient compression (single-device axis: semantics = identity + residual)
# ---------------------------------------------------------------------------

def test_compressed_allreduce_error_feedback():
    from repro.distributed.collectives import make_compressed_grad_allreduce
    mesh = jax.make_mesh((1, 1), ("pod", "data"),
                         axis_types=(AxisType.Auto,) * 2)
    # pod axis size 1 -> compression disabled (returns None)
    assert make_compressed_grad_allreduce(mesh, "pod") is None


def test_compressed_quantization_bounded_and_unbiased():
    from repro.distributed.collectives import _q8
    g = jax.random.normal(jax.random.PRNGKey(0), (64, 256)) * 1e-3
    q, scale = _q8(g)
    deq = np.asarray(q, np.float32) * np.asarray(scale)
    err = np.abs(deq - np.asarray(g))
    assert (err <= np.asarray(scale) * 0.5 + 1e-9).all()
    # residual carries exactly the lost mass (error feedback invariant)
    res = np.asarray(g) - deq
    assert np.allclose(res + deq, np.asarray(g), atol=1e-7)


# ---------------------------------------------------------------------------
# pipeline parallelism (1-stage degenerate case on CPU = identity schedule)
# ---------------------------------------------------------------------------

def test_pipeline_single_stage_equals_direct():
    from repro.distributed.pipeline import pipeline_forward
    mesh = jax.make_mesh((1,), ("pod",), axis_types=(AxisType.Auto,))
    w = jax.random.normal(jax.random.PRNGKey(0), (1, 8, 8))

    def stage(p, x):
        return jnp.tanh(x @ p)

    fn = pipeline_forward(mesh, stage, n_micro=3)
    xs = jax.random.normal(jax.random.PRNGKey(1), (3, 4, 8))
    with mesh_context(mesh):
        out = fn(w, xs)
    expect = jnp.tanh(xs @ w[0])
    assert np.allclose(np.asarray(out), np.asarray(expect), atol=1e-5)


# ---------------------------------------------------------------------------
# fault handling
# ---------------------------------------------------------------------------

def test_supervisor_restores_and_resumes():
    calls = {"restores": 0, "runs": 0}

    def restore():
        calls["restores"] += 1
        return 0

    sup = Supervisor(restore, max_restarts=2)
    state = {"fail": True}

    def step():
        calls["runs"] += 1
        if state["fail"]:
            state["fail"] = False
            raise SimulatedFailure("node died")

    assert not sup.run_step(step)     # failed + recovered
    assert sup.run_step(step)         # clean
    assert calls["restores"] == 1 and calls["runs"] == 2


def test_supervisor_gives_up_after_max_restarts():
    sup = Supervisor(lambda: 0, max_restarts=1)
    with pytest.raises(SimulatedFailure):
        for _ in range(3):
            sup.run_step(lambda: (_ for _ in ()).throw(SimulatedFailure())
                         .__next__())


def test_straggler_monitor_flags_slow_steps():
    mon = StragglerMonitor(threshold=2.0, patience=2)
    for i in range(5):
        mon.record(i, 1.0)
    assert not mon.events
    flagged = [mon.record(10 + i, 5.0) for i in range(3)]
    assert any(flagged) and mon.events


def test_supervisor_exhaustion_reraises_past_budget():
    """The restart budget is spent silently; the failure PAST it re-raises
    to the caller (the serving engine surfaces it instead of looping)."""
    restores = {"n": 0}

    def restore():
        restores["n"] += 1

    sup = Supervisor(restore, max_restarts=3)

    def boom():
        raise SimulatedFailure("array lost")

    for _ in range(3):
        assert not sup.run_step(boom)      # recovered, budget spent
    with pytest.raises(SimulatedFailure):
        sup.run_step(boom)                 # budget exhausted: re-raise
    assert restores["n"] == 3 and sup.restarts == 4


def test_straggler_ewma_resists_poisoning():
    """Pathologically slow steps barely move the EWMA baseline (weight
    0.98), so a burst can't drag the threshold up and hide itself."""
    mon = StragglerMonitor(threshold=2.0, patience=3)
    for i in range(10):
        mon.record(i, 1.0)
    assert mon._ewma == pytest.approx(1.0)
    for i in range(10, 13):
        mon.record(i, 100.0)               # 100x burst, patience-long
    assert mon.events, "burst should have requested mitigation"
    # plain decay=0.9 weighting would leave the baseline near 28; the
    # poisoning-resistant weight keeps it single-digit...
    assert mon._ewma < 10.0
    # ...so the very next 100x step is still detected as slow
    assert 100.0 > mon.threshold * mon._ewma
