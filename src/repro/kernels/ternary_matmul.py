"""Pallas TPU kernel: packed-ternary weight matmul (the 7T augmented cell's
compute path).

Weights live in augmented storage: 2-bit trits, 4 per uint8 byte, packed
along the contraction (K) axis — an 8x capacity augmentation vs bf16.  The
kernel streams PACKED bytes HBM->VMEM (the full-precision weight matrix
never exists in HBM), unpacks trits in VMEM registers (shift/mask — VPU
friendly; base-3 would serialize on divmods), feeds the MXU in bf16, and
applies the per-output-channel TWN scale in the epilogue ("inverter-based
sensing").

Roofline effect (decode, memory-bound): weight bytes / 8 -> the dominant
memory term drops ~8x for weight-dominated steps.

Block sizes: (bm, bk, bn) = (128, 512, 256) by default — MXU-aligned
(multiples of 128 in M/N; bk covers 128 packed rows = 512 trits), VMEM
footprint = bm*bk*2 (x) + bk/4*bn (w) + bm*bn*4 (acc) ~ 292 KiB, well
under the ~16 MiB/core VMEM budget, leaving room for double buffering.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.packed_kv_attention import as_bf16


DEFAULT_BM = 128
DEFAULT_BK = 512   # trits of K per step = 128 packed bytes
DEFAULT_BN = 256


def _unpack_2bit(wp: jax.Array) -> jax.Array:
    """(bk//4, bn) uint8 -> (bk, bn) bf16 trits in {-1, 0, +1}, DIGIT-major:
    row t * bk//4 + i holds digit t of byte row i (K index 4 * i + t).

    The four digits are stacked along sublanes rather than interleaved
    (an interleaving reshape has no Mosaic layout); the wrapper permutes
    the activation columns to the same order, so the dot is unchanged."""
    b = wp.astype(jnp.int32)
    digits = [jnp.bitwise_and(jnp.right_shift(b, 2 * t), 3) - 1
              for t in range(4)]
    return as_bf16(jnp.concatenate(digits, axis=0))


def _digit_major_columns(x: jax.Array, bk: int) -> jax.Array:
    """Permute x's K columns inside each bk block to the digit-major order
    `_unpack_2bit` produces: column t * bk//4 + i <- column 4 * i + t."""
    M, K = x.shape
    return x.reshape(M, K // bk, bk // 4, 4).swapaxes(2, 3).reshape(M, K)


def _ternary_matmul_kernel(x_ref, wp_ref, scale_ref, o_ref, acc_ref):
    k_step = pl.program_id(2)

    @pl.when(k_step == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    w = _unpack_2bit(wp_ref[...])
    acc_ref[...] += jnp.dot(x_ref[...], w,
                            preferred_element_type=jnp.float32)

    @pl.when(k_step == pl.num_programs(2) - 1)
    def _done():
        o_ref[...] = (acc_ref[...] * scale_ref[...]).astype(o_ref.dtype)


def ternary_matmul_pallas(x: jax.Array, w_packed: jax.Array,
                          scale: jax.Array, *,
                          bm: int = DEFAULT_BM, bk: int = DEFAULT_BK,
                          bn: int = DEFAULT_BN,
                          out_dtype=jnp.bfloat16,
                          interpret: bool = False) -> jax.Array:
    """x: (M, K) bf16; w_packed: (K//4, N) uint8; scale: (1, N) f32.

    Returns (M, N) out_dtype. M % bm == 0, K % bk == 0, N % bn == 0.
    """
    M, K = x.shape
    Kp, N = w_packed.shape
    assert Kp * 4 == K, (Kp, K)
    bm, bk, bn = min(bm, M), min(bk, K), min(bn, N)
    assert M % bm == 0 and K % bk == 0 and N % bn == 0, (M, K, N, bm, bk, bn)
    grid = (M // bm, N // bn, K // bk)
    return pl.pallas_call(
        _ternary_matmul_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk // 4, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="ternary_matmul",
    )(_digit_major_columns(x, bk), w_packed, scale)
