"""Pallas TPU kernel: dual-plane matmul (the 8T dual-bit augmented cell's
compute path).

ONE physical uint8 buffer holds TWO int4 weight matrices — high nibble =
static plane, low nibble = dynamic plane (e.g. the K-projection and
V-projection of an attention layer, written by the AugmentedStore under
its FILO ledger). The kernel reads each byte from HBM ONCE, splits the
planes in VMEM registers (each nibble is shifted to the top of an int32
lane and arithmetic-shifted back, which sign-extends it), and issues two
MXU dots per tile:

    y_hi = x @ dequant(hi(buf), hi_scale)
    y_lo = x @ dequant(lo(buf), lo_scale)

vs. two separate bf16 matmuls this moves 4x fewer weight bytes (and 2x
fewer than two separate int4 buffers' worth of scale/index traffic, since
the planes share one stream).

Blocks (bm, bk, bn) = (128, 256, 256): VMEM = bm*bk*2 + bk*bn*1 +
2*bm*bn*4 ~ 384 KiB.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.packed_kv_attention import as_bf16, nibbles

DEFAULT_BM = 128
DEFAULT_BK = 256
DEFAULT_BN = 256


def _split_planes(buf: jax.Array):
    """uint8 -> (hi int4, lo int4) as bf16, sign-extended."""
    hi, lo = nibbles(buf)
    return as_bf16(hi), as_bf16(lo)


def _dual_plane_kernel(x_ref, buf_ref, hs_ref, ls_ref, ohi_ref, olo_ref,
                       acc_hi, acc_lo):
    k_step = pl.program_id(2)

    @pl.when(k_step == 0)
    def _init():
        acc_hi[...] = jnp.zeros_like(acc_hi)
        acc_lo[...] = jnp.zeros_like(acc_lo)

    hi, lo = _split_planes(buf_ref[...])
    x = x_ref[...]
    acc_hi[...] += jnp.dot(x, hi, preferred_element_type=jnp.float32)
    acc_lo[...] += jnp.dot(x, lo, preferred_element_type=jnp.float32)

    @pl.when(k_step == pl.num_programs(2) - 1)
    def _done():
        ohi_ref[...] = (acc_hi[...] * hs_ref[...]).astype(ohi_ref.dtype)
        olo_ref[...] = (acc_lo[...] * ls_ref[...]).astype(olo_ref.dtype)


def dual_plane_matmul_pallas(x: jax.Array, buf: jax.Array,
                             hi_scale: jax.Array, lo_scale: jax.Array, *,
                             bm: int = DEFAULT_BM, bk: int = DEFAULT_BK,
                             bn: int = DEFAULT_BN, out_dtype=jnp.bfloat16,
                             interpret: bool = False):
    """x: (M, K) bf16; buf: (K, N) uint8 (two int4 planes);
    scales: (1, N) f32 per plane. Returns (y_hi, y_lo), each (M, N)."""
    M, K = x.shape
    K2, N = buf.shape
    assert K2 == K
    bm, bk, bn = min(bm, M), min(bk, K), min(bn, N)
    assert M % bm == 0 and K % bk == 0 and N % bn == 0
    grid = (M // bm, N // bn, K // bk)
    return pl.pallas_call(
        _dual_plane_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),
        ],
        out_specs=[pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
                   pl.BlockSpec((bm, bn), lambda i, j, k: (i, j))],
        out_shape=[jax.ShapeDtypeStruct((M, N), out_dtype),
                   jax.ShapeDtypeStruct((M, N), out_dtype)],
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32),
                        pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="dual_plane_matmul",
    )(x, buf, hi_scale, lo_scale)
