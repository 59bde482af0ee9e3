"""The main-path kernels compile with Mosaic for a TPU v5e at granite-3-2b
widths (head_dim 64, 8 KV heads, 4 query heads per KV head, d_model 2048,
d_ff 8192). Nothing runs: each kernel is compiled for one chip of a
described v5e:2x2 topology, and the compiled text must hold the Mosaic
custom call. The CPU tests run these kernels in the Pallas interpreter,
which accepts layouts the chip's compiler refuses."""
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.dual_plane_matmul import dual_plane_matmul_pallas
from repro.kernels.paged_kv_attention import (
    paged_kv_attention_pallas, paged_kv_attention_window_pallas)
from repro.kernels.quantize_pack_kv import quantize_pack_kv_pallas
from repro.kernels.ternary_matmul import ternary_matmul_pallas

B, KV, HG, D = 8, 8, 4, 64            # decode batch, granite attention
D_MODEL, D_FF = 2048, 8192
MAX_SEQ = 1024


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile for a described chip is written to the persistent cache
    # but can never be read back without one: keep the cache off here
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _compiled_text(sharding, fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding) for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def _paged_shapes(page, kv_bits, q_shape):
    max_p = MAX_SEQ // page
    n_pages = B * max_p + 1
    d_store, dt = (D // 2, jnp.uint8) if kv_bits == 4 else (D, jnp.int8)
    normal = ((n_pages, KV, page, D), jnp.bfloat16)
    packed = ((n_pages, KV, page, d_store), dt)
    scale = ((n_pages, KV, page), jnp.bfloat16)
    table = ((B, max_p), jnp.int32)
    return [(q_shape, jnp.bfloat16), normal, normal, packed, packed, scale,
            scale, ((B,), jnp.int32), table, table, table]


@pytest.mark.parametrize("page", [16, 32])
@pytest.mark.parametrize("kv_bits", [4, 8])
def test_paged_kv_attention_compiles(one_chip, kv_bits, page):
    fn = functools.partial(paged_kv_attention_pallas, page=page,
                           kv_bits=kv_bits)
    text = _compiled_text(one_chip, fn,
                          *_paged_shapes(page, kv_bits, (B, KV, HG, D)))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("kv_bits", [4, 8])
def test_paged_kv_attention_window_compiles(one_chip, kv_bits):
    page, W = 16, 4
    fn = functools.partial(paged_kv_attention_window_pallas, page=page,
                           kv_bits=kv_bits)
    text = _compiled_text(one_chip, fn,
                          *_paged_shapes(page, kv_bits, (B, KV, W, HG, D)))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("variant", ["plain", "masked", "integrity"])
def test_quantize_pack_kv_compiles(one_chip, variant):
    rows = B * 32 * KV                       # one 32-token prefill chunk
    shapes = [((rows, D), jnp.bfloat16)]
    if variant == "masked":
        shapes.append(((rows, 1), jnp.int32))
    fn = functools.partial(quantize_pack_kv_pallas,
                           with_integrity=variant == "integrity")
    assert "tpu_custom_call" in _compiled_text(one_chip, fn, *shapes)


@pytest.mark.parametrize("m", [B, 256])      # decode rows, prefill rows
def test_dual_plane_matmul_compiles(one_chip, m):
    fn = functools.partial(dual_plane_matmul_pallas, bm=min(m, 128))
    text = _compiled_text(one_chip, fn, ((m, D_MODEL), jnp.bfloat16),
                          ((D_MODEL, D_FF), jnp.uint8),
                          ((1, D_FF), jnp.float32), ((1, D_FF), jnp.float32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("m", [B, 256])
def test_ternary_matmul_compiles(one_chip, m):
    fn = functools.partial(ternary_matmul_pallas, bm=min(m, 128))
    text = _compiled_text(one_chip, fn, ((m, D_MODEL), jnp.bfloat16),
                          ((D_MODEL // 4, D_FF), jnp.uint8),
                          ((1, D_FF), jnp.float32))
    assert "tpu_custom_call" in text
