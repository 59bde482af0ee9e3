"""Dry-run machinery tests: HLO analyzer calibration + one real cell lowered
on the production mesh in a subprocess (512 forced host devices)."""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import AxisType

from repro.launch.hlo_analysis import analyze_hlo
from repro.launch.mesh import mesh_context

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_analyzer_matches_xla_loop_free():
    def f(x, w):
        return jnp.tanh(x @ w)
    x = jax.ShapeDtypeStruct((128, 256), jnp.float32)
    w = jax.ShapeDtypeStruct((256, 512), jnp.float32)
    c = jax.jit(f).lower(x, w).compile()
    r = analyze_hlo(c.as_text())
    ca = c.cost_analysis()
    if isinstance(ca, list):  # pre-0.5 jax returns one dict per computation
        ca = ca[0]
    assert r["flops"] == ca["flops"]
    if jax.__version_info__ >= (0, 5):
        # pre-0.5 XLA charges fused-parameter bytes differently; the
        # analyzer tracks the current cost model
        assert r["bytes_accessed"] == ca["bytes accessed"]
    else:
        assert r["bytes_accessed"] >= ca["bytes accessed"] > 0


def test_analyzer_multiplies_trip_counts():
    def f(x, w):
        def body(c, _):
            return jnp.tanh(c @ w), None
        y, _ = jax.lax.scan(body, x, None, length=10)
        return y
    x = jax.ShapeDtypeStruct((128, 256), jnp.float32)
    w = jax.ShapeDtypeStruct((256, 256), jnp.float32)
    c = jax.jit(f).lower(x, w).compile()
    r = analyze_hlo(c.as_text())
    assert r["flops"] == 2 * 128 * 256 * 256 * 10
    ca = c.cost_analysis()
    if isinstance(ca, list):  # pre-0.5 jax returns one dict per computation
        ca = ca[0]
    # XLA counts the body once — exactly 10x less
    assert ca["flops"] * 10 == r["flops"]


def test_analyzer_nested_loops():
    def f(x, w):
        def outer(c, _):
            def inner(c2, _):
                return c2 @ w, None
            c, _ = jax.lax.scan(inner, c, None, length=5)
            return jnp.tanh(c), None
        y, _ = jax.lax.scan(outer, x, None, length=3)
        return y
    x = jax.ShapeDtypeStruct((64, 128), jnp.float32)
    w = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    c = jax.jit(f).lower(x, w).compile()
    assert analyze_hlo(c.as_text())["flops"] == 2 * 64 * 128 * 128 * 15


def test_analyzer_counts_collectives():
    mesh = jax.make_mesh((1,), ("d",), axis_types=(AxisType.Auto,))
    from jax.sharding import NamedSharding, PartitionSpec as P

    def f(x):
        return jax.lax.with_sharding_constraint(
            x.sum(0, keepdims=True), NamedSharding(mesh, P(None, None)))
    # single device: no collectives expected — analyzer must return zeros
    with mesh_context(mesh):
        c = jax.jit(f).lower(
            jax.ShapeDtypeStruct((8, 8), jnp.float32)).compile()
    r = analyze_hlo(c.as_text())
    assert r["collective_total_bytes"] == 0


@pytest.mark.slow
def test_one_production_cell_compiles(tmp_path):
    """whisper-tiny x train_4k on the 256-chip mesh, in a subprocess (the
    512-device override must not leak into this test session)."""
    out = tmp_path / "dry"
    proc = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--arch",
         "whisper-tiny", "--shape", "train_4k", "--out", str(out)],
        env={**os.environ, "PYTHONPATH": os.path.join(REPO, "src")},
        capture_output=True, text=True, timeout=540, cwd=REPO)
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
    rec = json.loads((out / "whisper-tiny_train_4k_pod.json").read_text())
    assert not rec["skipped"]
    assert rec["flops_per_device"] > 0
    assert rec["collectives"]["total_bytes"] > 0
    assert jax.device_count() == 1  # no leak
