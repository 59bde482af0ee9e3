"""Pallas TPU kernel: fused KV quantize-and-pack (the write driver of the
serving engine's augmented dynamic plane).

Prefill produces bf16 K/V chunks; the packed cache stores int4 nibbles +
per-token scales. Doing quantize -> nibble-pack as one kernel means the
bf16 chunk streams HBM->VMEM once and only packed bytes + scales go back —
no dequantized or int8 intermediate ever lands in HBM (the paper's "write
boosting": one array access per stored word, however many logical values
it encodes).

Per row (one token x head): scale = max|x| / 7, q = clip(round(x/scale)),
even lanes -> high nibble, odd lanes -> low nibble (same convention as
`quant.pack_int4_pair`, so the attention kernel's unpack is the inverse).

Grid: (N // bn,) over flattened token-head rows — embarrassingly parallel
(`dimension_semantics=("parallel",)`). Block (bn, D): with bn = 256,
D = 128 the VMEM term is bn*D*2 (in) + bn*D/2 (packed) + bn*4 (scale)
~ 81 KiB, far under budget; Mosaic double-buffers the row stream.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.quant import INT4_MAX

DEFAULT_BN = 256


def _bf16_ratio(num: jax.Array, den) -> jax.Array:
    """bf16(num / den) rounded to nearest, ties to even, for num >= 0 and
    den > 0 that are bf16 values held in f32 — the bf16 division of the
    host packer.

    The chip's f32 division is not correctly rounded, so rounding its
    quotient to bf16 can land one step off next to a rounding midpoint.
    The candidate is corrected by exact comparisons: a midpoint has 9
    significant bits and `den` at most 8, so midpoint * den is exact."""
    as_f32 = lambda b: jax.lax.bitcast_convert_type(b, jnp.float32)  # noqa
    q = (num / den).astype(jnp.bfloat16).astype(jnp.float32)
    bits = jax.lax.bitcast_convert_type(q, jnp.int32)
    step, half = 1 << 16, 1 << 15          # one bf16 step, half of it
    odd = jnp.bitwise_and(bits, step) != 0
    mid_up = as_f32(bits + half) * den
    mid_down = as_f32(bits - half) * den
    q = jnp.where((num > mid_up) | ((num == mid_up) & odd),
                  as_f32(bits + step), q)
    q = jnp.where((num < mid_down) | ((num == mid_down) & odd),
                  as_f32(bits - step), q)
    return jnp.where(num == 0, 0.0, q)


def _round_half_even(y: jax.Array) -> jax.Array:
    """jnp.round's rounding, spelled out in exact f32 ops (|y| small)."""
    f = jnp.floor(y)
    d = y - f
    odd = f - 2.0 * jnp.floor(f * 0.5) == 1.0
    return f + jnp.where((d > 0.5) | ((d == 0.5) & odd), 1.0, 0.0)


def _quantize_pack(x: jax.Array):
    """(bn, D) rows -> (packed (bn, D//2) int32 in [0, 255], scale (bn, 1)).

    The levels are bit-identical to the host packer, which computes in the
    input dtype (quant.quantize_int4 / pack_kv_int4: scale = max|x| / 7,
    level = round(x / scale)) — the engine's golden test depends on this
    parity. For bf16 rows (every KV write) the divisions are made exact
    by `_bf16_ratio`; f32 rows divide directly."""
    xf = x.astype(jnp.float32)
    ax = jnp.abs(xf)
    amax = jnp.max(ax, axis=1, keepdims=True)
    eps = jnp.asarray(1e-8, x.dtype).astype(jnp.float32)
    if x.dtype == jnp.bfloat16:
        scale = _bf16_ratio(jnp.maximum(amax, eps), float(INT4_MAX))
        y = _bf16_ratio(ax, scale)
        y = jnp.where(xf < 0, -y, y)
    else:
        scale = jnp.maximum(amax, eps) / INT4_MAX
        y = xf / scale
    q = jnp.clip(_round_half_even(y), -INT4_MAX, INT4_MAX).astype(
        jnp.bfloat16)
    # even lanes -> high nibble, odd lanes -> low nibble: Mosaic has no
    # lane-strided slice, so the lanes are split by two 0/1 selector
    # products on the MXU (exact: the levels are integers in [-7, 7])
    d = x.shape[1]
    row = jax.lax.broadcasted_iota(jnp.int32, (d, d // 2), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (d, d // 2), 1)

    def lanes(parity):
        sel = (row == 2 * col + parity).astype(jnp.bfloat16)
        v = jnp.dot(q, sel, preferred_element_type=jnp.float32)
        return jnp.bitwise_and(v.astype(jnp.int32), 0x0F)

    packed = jnp.bitwise_or(jnp.left_shift(lanes(0), 4), lanes(1))
    return packed, scale


def _qpack_kernel(x_ref, p_ref, s_ref):
    packed, scale = _quantize_pack(x_ref[...])
    p_ref[...] = packed.astype(p_ref.dtype)
    s_ref[...] = scale


def _qpack_integrity_kernel(x_ref, p_ref, s_ref, w_ref):
    # quantize-on-write with fused integrity words: the same pack as
    # _qpack_kernel plus a per-row byte-weighted checksum (word =
    # sum_j (j+1) * packed_byte_j mod 2**32, the formula of
    # core.faults.integrity_word) — computed while the packed bytes are
    # still in VMEM, so detection metadata costs no extra array read.
    # The sum runs in int32 (Mosaic reduces no unsigned type): it wraps
    # to the same 32 bits, which the wrapper reads back as uint32.
    packed, scale = _quantize_pack(x_ref[...])
    p_ref[...] = packed.astype(p_ref.dtype)
    s_ref[...] = scale
    lanes = jax.lax.broadcasted_iota(jnp.int32, packed.shape, 1) + 1
    w_ref[...] = jnp.sum(packed * lanes, axis=1, keepdims=True)


def _qpack_masked_kernel(x_ref, valid_ref, p_ref, s_ref):
    # the speculative store-back: rows whose token was REJECTED by the
    # verify pass commit zero bytes + unit scale instead of their values
    # (nothing of the draft window lands in the augmented plane)
    packed, scale = _quantize_pack(x_ref[...])
    keep = valid_ref[...] != 0                            # (bn, 1)
    p_ref[...] = jnp.where(keep, packed, 0).astype(p_ref.dtype)
    s_ref[...] = jnp.where(keep, scale, 1.0)


def quantize_pack_kv_pallas(kv: jax.Array, valid=None, *,
                            bn: int = DEFAULT_BN, interpret: bool = False,
                            with_integrity: bool = False):
    """kv: (N, D) bf16/f32, D even. Returns (packed (N, D//2) uint8,
    scale (N, 1) f32). N % bn == 0 (pad in the wrapper). `valid` (N, 1)
    int32, optional: rows with valid == 0 commit as zeros + unit scale
    (speculative decode commits only accepted tokens). With
    `with_integrity` (unmasked path only) a third (N, 1) uint32 output
    carries the per-row integrity word of `core.faults.integrity_word`
    over the packed bytes, fused with the pack — the detection metadata
    the fault-aware serving stores verify on gather/refresh."""
    N, D = kv.shape
    assert D % 2 == 0, D
    bn = min(bn, N)
    assert N % bn == 0, (N, bn)
    out_specs = [pl.BlockSpec((bn, D // 2), lambda i: (i, 0)),
                 pl.BlockSpec((bn, 1), lambda i: (i, 0))]
    out_shape = [jax.ShapeDtypeStruct((N, D // 2), jnp.uint8),
                 jax.ShapeDtypeStruct((N, 1), jnp.float32)]
    params = pltpu.CompilerParams(dimension_semantics=("parallel",))
    if with_integrity:
        assert valid is None, "with_integrity is for the unmasked write path"
        p, s, w = pl.pallas_call(
            _qpack_integrity_kernel,
            grid=(N // bn,),
            in_specs=[pl.BlockSpec((bn, D), lambda i: (i, 0))],
            out_specs=out_specs + [pl.BlockSpec((bn, 1), lambda i: (i, 0))],
            out_shape=out_shape + [jax.ShapeDtypeStruct((N, 1), jnp.int32)],
            compiler_params=params,
            interpret=interpret,
            name="quantize_pack_kv",
        )(kv)
        return p, s, jax.lax.bitcast_convert_type(w, jnp.uint32)
    if valid is None:
        return pl.pallas_call(
            _qpack_kernel,
            grid=(N // bn,),
            in_specs=[pl.BlockSpec((bn, D), lambda i: (i, 0))],
            out_specs=out_specs,
            out_shape=out_shape,
            compiler_params=params,
            interpret=interpret,
            name="quantize_pack_kv",
        )(kv)
    assert valid.shape == (N, 1), (valid.shape, N)
    return pl.pallas_call(
        _qpack_masked_kernel,
        grid=(N // bn,),
        in_specs=[pl.BlockSpec((bn, D), lambda i: (i, 0)),
                  pl.BlockSpec((bn, 1), lambda i: (i, 0))],
        out_specs=out_specs,
        out_shape=out_shape,
        compiler_params=params,
        interpret=interpret,
        name="quantize_pack_kv",
    )(kv, valid.astype(jnp.int32))
