"""Pallas TPU kernel: bit-serial in-memory-compute (IMC) dot product over
packed augmented storage.

The paper closes by noting the augmented bit-cells "can be seamlessly
combined with existing in-memory computing approaches"; this kernel is that
combination, reproducing the array semantics of "8T SRAM Cell as a
Multi-bit Dot Product Engine" (arXiv:1802.08601) and the reconfigurable
activation precision of "Bit Parallel 6T SRAM In-memory Computing"
(arXiv:2008.03378) on top of this repo's packed weight formats:

  * the weights stay IN THE ARRAY — consumed exactly as stored (2-bit
    ternary trits, dual-plane uint8, int4/int8), never dequantized in HBM;
  * activations are driven WORDLINE-SERIAL: quantized to `abits` bits
    (1/4/8 reconfigurable), then streamed one magnitude bit-plane per
    cycle — each cycle is one {-1,0,+1}-valued plane times the resident
    weights (the MXU dot plays the bitline-parallel analog accumulation);
  * partial sums are shift-added (x2^b) and the per-output-channel weight
    scale is applied in the epilogue (the ADC / sense stage).

Exactness: every bit-plane product and shift-add is integer-valued, and
for the ternary/dual/int4 formats the accumulated magnitudes stay well
under 2^24 at practical K, so the fp32 accumulation is EXACT — at full
activation precision the kernel is bit-identical to `ternary_matmul` /
`dual_plane_matmul` on the same packed bytes (golden-tested). int8
weights can exceed 2^24 beyond K ~ 1k (127*127*K), where parity vs the
oracle is near-exact rather than bit-exact (the oracle sums full-K
plane dots, the kernel per-bk blocks). The array-level event/energy model
for this access pattern (wordline pulses, bitline discharges, ADC
conversions) lives in `repro.imc.energy`.

Block sizes default to the ternary kernel's (128, 512, 256); VMEM adds one
(bm, bk) int8 activation tile + (bm, 1) scale over the packed-matmul
footprint, still far under budget.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BM = 128
DEFAULT_BK = 512
DEFAULT_BN = 256

# Weight storage formats consumed as stored (no dequantized HBM copy).
IMC_FORMATS = ("ternary", "dual", "int8", "int4")


def mag_bits(abits: int) -> int:
    """Bit-serial cycles per activation: magnitude bits of a signed
    `abits`-bit value (sign rides each plane, it is not a cycle)."""
    return 1 if abits == 1 else abits - 1


def qmax_for(abits: int) -> int:
    """Symmetric activation range: [-qmax, qmax]; abits=1 is binary
    {-1, 0, +1} (the BNN-style limit of arXiv:2008.03378)."""
    return 1 if abits == 1 else 2 ** (abits - 1) - 1


def quantize_activations(x: jax.Array, abits: int):
    """Per-row symmetric quantization of the activation operand (the DAC
    in front of the wordline drivers). x (M, K) -> (xq int8, xs (M,1) f32)
    with x ~= xq * xs. When a row's absmax equals qmax the scale is
    exactly 1.0 and the bit-serial path is exact (the parity goldens)."""
    xf = x.astype(jnp.float32)
    q = qmax_for(abits)
    amax = jnp.max(jnp.abs(xf), axis=-1, keepdims=True)
    xs = jnp.maximum(amax, 1e-8) / q
    xq = jnp.clip(jnp.round(xf / xs), -q, q).astype(jnp.int8)
    return xq, xs


# ---------------------------------------------------------------------------
# In-VMEM weight unpack (the resident array contents, by format)
# ---------------------------------------------------------------------------

def _unpack_ternary(wp: jax.Array, bk: int, bn: int) -> jax.Array:
    """(bk//4, bn) uint8 -> (bk, bn) bf16 trits (same as ternary_matmul)."""
    shifts = (jnp.arange(4, dtype=jnp.uint8) * 2)[None, :, None]
    d = jnp.bitwise_and(jnp.right_shift(wp[:, None, :], shifts),
                        jnp.uint8(0x3))
    return (d.astype(jnp.int8) - 1).reshape(bk, bn).astype(jnp.bfloat16)


def _unpack_int4_rows(wp: jax.Array, bk: int, bn: int) -> jax.Array:
    """(bk//2, bn) uint8 -> (bk, bn) bf16: two K-adjacent int4 rows per
    byte (hi nibble = even row, lo = odd row)."""
    hi = jnp.right_shift(wp.astype(jnp.int8), 4)
    lo = jnp.right_shift(
        jnp.left_shift(wp.astype(jnp.uint8), 4).astype(jnp.int8), 4)
    w = jnp.stack([hi, lo], axis=1)                  # (bk//2, 2, bn)
    return w.reshape(bk, bn).astype(jnp.bfloat16)


def _unpack_dual(buf: jax.Array):
    """(bk, bn) uint8 -> (hi, lo) bf16 planes (same as dual_plane_matmul)."""
    hi = jnp.right_shift(buf.astype(jnp.int8), 4)
    lo = jnp.right_shift(
        jnp.left_shift(buf.astype(jnp.uint8), 4).astype(jnp.int8), 4)
    return hi.astype(jnp.bfloat16), lo.astype(jnp.bfloat16)


def _weights_for(fmt: str, wp: jax.Array, bk: int, bn: int):
    if fmt == "ternary":
        return _unpack_ternary(wp, bk, bn)
    if fmt == "int4":
        return _unpack_int4_rows(wp, bk, bn)
    return wp.astype(jnp.bfloat16)                   # int8


def _bit_serial_acc(xq: jax.Array, w, acc_refs, abits: int) -> None:
    """The wordline-serial loop: one magnitude bit-plane per cycle, MXU dot
    per plane per resident weight plane, shift-added into fp32 scratch.
    `w`/`acc_refs` are matching tuples (1 for single-plane formats, 2 for
    dual — ONE wordline drive feeds BOTH planes' bitlines)."""
    xi = xq.astype(jnp.int32)
    sign = jnp.sign(xi)
    mag = jnp.abs(xi)
    for b in range(mag_bits(abits)):
        bit = jnp.bitwise_and(jnp.right_shift(mag, b), 1)
        plane = (sign * bit).astype(jnp.bfloat16)
        for wk, acc in zip(w, acc_refs):
            acc[...] += (2.0 ** b) * jnp.dot(
                plane, wk, preferred_element_type=jnp.float32)


def _imc_dot_kernel(xq_ref, xs_ref, wp_ref, ws_ref, o_ref, acc_ref, *,
                    fmt: str, bk: int, bn: int, abits: int):
    k_step = pl.program_id(2)

    @pl.when(k_step == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    w = _weights_for(fmt, wp_ref[...], bk, bn)
    _bit_serial_acc(xq_ref[...], (w,), (acc_ref,), abits)

    @pl.when(k_step == pl.num_programs(2) - 1)
    def _done():
        # ADC + sense epilogue: activation LSB size, then weight scale —
        # with xs == 1.0 this is bit-identical to the packed kernels'
        # (acc * scale) epilogue
        o_ref[...] = (acc_ref[...] * xs_ref[...]
                      * ws_ref[...]).astype(o_ref.dtype)


def _imc_dual_kernel(xq_ref, xs_ref, buf_ref, hs_ref, ls_ref, ohi_ref,
                     olo_ref, acc_hi, acc_lo, *, abits: int):
    k_step = pl.program_id(2)

    @pl.when(k_step == 0)
    def _init():
        acc_hi[...] = jnp.zeros_like(acc_hi)
        acc_lo[...] = jnp.zeros_like(acc_lo)

    hi, lo = _unpack_dual(buf_ref[...])
    _bit_serial_acc(xq_ref[...], (hi, lo), (acc_hi, acc_lo), abits)

    @pl.when(k_step == pl.num_programs(2) - 1)
    def _done():
        ohi_ref[...] = (acc_hi[...] * xs_ref[...]
                        * hs_ref[...]).astype(ohi_ref.dtype)
        olo_ref[...] = (acc_lo[...] * xs_ref[...]
                        * ls_ref[...]).astype(olo_ref.dtype)


# ---------------------------------------------------------------------------
# pallas_call drivers
# ---------------------------------------------------------------------------

def _k_pack(fmt: str) -> int:
    """Packed K rows per storage byte-row for each format."""
    return {"ternary": 4, "int4": 2, "int8": 1, "dual": 1}[fmt]


def imc_dot_pallas(xq: jax.Array, xs: jax.Array, wp: jax.Array,
                   scale: jax.Array, *, fmt: str, abits: int,
                   bm: int = DEFAULT_BM, bk: int = DEFAULT_BK,
                   bn: int = DEFAULT_BN, out_dtype=jnp.bfloat16,
                   interpret: bool = False) -> jax.Array:
    """xq (M, K) int8 + xs (M, 1) f32 activations; wp packed weights:
    (K//4, N) u8 trits / (K//2, N) u8 int4 rows / (K, N) i8; scale (1, N)
    f32. Returns (M, N) out_dtype. M % bm == K % bk == N % bn == 0."""
    assert fmt in ("ternary", "int4", "int8"), fmt
    M, K = xq.shape
    kp = _k_pack(fmt)
    Kp, N = wp.shape
    assert Kp * kp == K, (Kp, kp, K)
    bm, bk, bn = min(bm, M), min(bk, K), min(bn, N)
    assert M % bm == 0 and K % bk == 0 and N % bn == 0, (M, K, N, bm, bk, bn)
    assert bk % kp == 0, (bk, kp)
    grid = (M // bm, N // bn, K // bk)
    return pl.pallas_call(
        functools.partial(_imc_dot_kernel, fmt=fmt, bk=bk, bn=bn,
                          abits=abits),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bm, 1), lambda i, j, k: (i, 0)),
            pl.BlockSpec((bk // kp, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(xq, xs, wp, scale)


def imc_dual_dot_pallas(xq: jax.Array, xs: jax.Array, buf: jax.Array,
                        hi_scale: jax.Array, lo_scale: jax.Array, *,
                        abits: int, bm: int = DEFAULT_BM, bk: int = 256,
                        bn: int = DEFAULT_BN, out_dtype=jnp.bfloat16,
                        interpret: bool = False):
    """Dual-plane IMC dot: ONE wordline-serial activation stream drives
    BOTH int4 planes of the resident uint8 array. Returns (y_hi, y_lo)."""
    M, K = xq.shape
    K2, N = buf.shape
    assert K2 == K, (K2, K)
    bm, bk, bn = min(bm, M), min(bk, K), min(bn, N)
    assert M % bm == 0 and K % bk == 0 and N % bn == 0, (M, K, N, bm, bk, bn)
    grid = (M // bm, N // bn, K // bk)
    return pl.pallas_call(
        functools.partial(_imc_dual_kernel, abits=abits),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bm, 1), lambda i, j, k: (i, 0)),
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
    )(xq, xs, buf, hi_scale, lo_scale)
