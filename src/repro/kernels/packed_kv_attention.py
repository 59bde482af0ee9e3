"""Pallas TPU kernel: flash-decode attention over an int4-packed KV cache
(the serving engine's augmented dynamic plane).

Single-token GQA decode: q (B, KV, Hg, D) attends to a packed cache
k/v (B, S, KV, D//2) uint8 + per-(token, head) scales (B, S, KV).

The kernel never materializes the dequantized cache in HBM:
  * packed K blocks stream HBM->VMEM; scores = (q . k_int) * k_scale —
    the dequant scale is applied to score COLUMNS, not to K elements
    (D-fold cheaper than dequantizing K);
  * online softmax (running max m, denominator l, accumulator acc in VMEM
    scratch across sequence blocks — the innermost grid dim);
  * V blocks likewise stay int4: acc += (p * v_scale) @ v_int.

Length-aware pipelining: `lengths` is SCALAR-PREFETCHED
(`PrefetchScalarGridSpec`), so it is resident in SMEM before the grid
runs. Sequence blocks past a row's valid length are skipped entirely:
the block index maps clamp to the last valid block (the pipeline re-uses
the already-fetched block — no DMA is issued) and the kernel body is
`pl.when`-guarded off (no MXU/VPU work). Grid *work* is therefore
proportional to the actual cache length, not `max_seq` — a 12-token row
in a 64K-slot cache costs one block, not 128.

Memory term: S*D bytes/2 per head instead of S*D*2 (bf16) — 4x less HBM
traffic for the decode bottleneck, which is exactly the paper's augmented
capacity claim applied to the KV working set.

Grid: (B, KV, S//bs); block (bs, D//2) packed KV in VMEM — with bs = 512,
D = 128: 32 KiB packed KV + scratch (Hg x D acc, Hg stats) « VMEM.
B and KV are `parallel` dimension semantics (Mosaic may reorder /
parallelize them); the sequence dim is `arbitrary` (carries the online
softmax state).

The causal/validity mask inside the last valid block is handled via the
same `lengths` operand; fully invalid columns get -inf scores.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BS = 512
NEG_INF = -1e30


def nibbles(packed: jax.Array):
    """uint8 block -> (high, low) nibbles as sign-extended int32.

    Each nibble is shifted to the top of an int32 lane and arithmetic-
    shifted back: Mosaic has no int8 arithmetic shift."""
    b = packed.astype(jnp.int32)
    return (jnp.right_shift(jnp.left_shift(b, 24), 28),
            jnp.right_shift(jnp.left_shift(b, 28), 28))


def as_bf16(x: jax.Array) -> jax.Array:
    """Integer block -> bf16 (through f32: Mosaic converts integers to
    f32 only)."""
    return x.astype(jnp.float32).astype(jnp.bfloat16)


def _unpack_int4_pairs(packed: jax.Array) -> jax.Array:
    """(bs, D//2) uint8 -> (bs, D) bf16 int4 values (interleaved pairs).

    Mosaic has no lane-interleaving reshape, so the pairs are interleaved
    by two 0/1 selector products on the MXU: every operand is an integer
    in [-8, 7], so they are exact."""
    hi, lo = nibbles(packed)
    half = packed.shape[1]
    row = jax.lax.broadcasted_iota(jnp.int32, (half, 2 * half), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (half, 2 * half), 1)
    w = (jnp.dot(as_bf16(hi), (col == 2 * row).astype(jnp.bfloat16),
                 preferred_element_type=jnp.float32)
         + jnp.dot(as_bf16(lo), (col == 2 * row + 1).astype(jnp.bfloat16),
                   preferred_element_type=jnp.float32))
    return w.astype(jnp.bfloat16)


def _load_kv_block(ref_block: jax.Array, kv_bits: int) -> jax.Array:
    """Packed KV block -> (bs, D) bf16 integer levels.

    4-bit: two nibbles per byte, interleaved pairs. 8-bit: the int8 value
    itself — no unpack, the cast is the whole "sense amplifier"."""
    if kv_bits == 4:
        return _unpack_int4_pairs(ref_block)
    return as_bf16(ref_block)


def _num_valid_blocks(length, bs: int):
    """Blocks holding >= 1 valid slot; at least 1 so init/output fire."""
    return jnp.maximum(pl.cdiv(length, bs), 1)


def _kv_attn_kernel(lens_ref, q_ref, k_ref, v_ref, ks_ref, vs_ref, o_ref,
                    *rest, bs: int, scale: float, kv_bits: int,
                    debug_visits: bool):
    if debug_visits:
        visits_ref, acc_ref, m_ref, l_ref = rest
    else:
        acc_ref, m_ref, l_ref = rest
    s_step = pl.program_id(2)
    length = lens_ref[pl.program_id(0)]
    nvb = _num_valid_blocks(length, bs)
    visited = s_step < nvb

    @pl.when(s_step == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        if debug_visits:
            visits_ref[0, 0] = 0

    @pl.when(visited)
    def _compute():
        q = q_ref[0, 0]                          # (Hg, D) bf16
        k_int = _load_kv_block(k_ref[0, 0], kv_bits)  # (bs, D)
        v_int = _load_kv_block(v_ref[0, 0], kv_bits)
        k_scale = ks_ref[0, 0].astype(jnp.float32)  # (bs,)
        v_scale = vs_ref[0, 0].astype(jnp.float32)

        # scores with column-wise dequant
        s = jnp.dot(q, k_int.T, preferred_element_type=jnp.float32)
        s = s * (k_scale * scale)[None, :]       # (Hg, bs)
        # validity mask (ring caches rely on softmax permutation invariance)
        valid = (s_step * bs
                 + jax.lax.broadcasted_iota(jnp.int32, (1, bs), 1)) < length
        s = jnp.where(valid, s, NEG_INF)

        m_prev = m_ref[...]                      # (Hg, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)                   # (Hg, bs)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        pv = (p * v_scale[None, :]).astype(jnp.bfloat16)
        acc_ref[...] = (acc_ref[...] * alpha
                        + jnp.dot(pv, v_int,
                                  preferred_element_type=jnp.float32))
        m_ref[...] = m_new
        if debug_visits:
            visits_ref[0, 0] += 1

    @pl.when(s_step == nvb - 1)
    def _done():
        o_ref[0, 0] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


def packed_kv_attention_pallas(q: jax.Array, k_packed: jax.Array,
                               v_packed: jax.Array, k_scale: jax.Array,
                               v_scale: jax.Array, lengths: jax.Array, *,
                               bs: int = DEFAULT_BS,
                               kv_bits: int = 4,
                               debug_visits: bool = False,
                               interpret: bool = False):
    """q: (B, KV, Hg, D) bf16; k/v_packed: (B, KV, S, D//2) uint8 for
    kv_bits=4 or (B, KV, S, D) int8 for kv_bits=8;
    scales: (B, KV, S) bf16; lengths: (B,) int32 (valid slots per row).
    Returns (B, KV, Hg, D) bf16 [, visits (B, KV) int32 when
    `debug_visits` — the number of sequence blocks actually processed
    per (row, head), for asserting grid work ∝ length]."""
    B, KV, Hg, D = q.shape
    S = k_packed.shape[2]
    assert kv_bits in (4, 8), kv_bits
    d_store = D // 2 if kv_bits == 4 else D
    assert k_packed.shape[-1] == d_store, (k_packed.shape, D, kv_bits)
    bs = min(bs, S)
    assert S % bs == 0, (S, bs)
    scale = 1.0 / (D ** 0.5)
    # clamp: a ring-cache caller may pass position+1 past capacity, which
    # means "all S slots valid" — without this the last-valid-block index
    # lands past the grid and the output row is never written
    lengths = jnp.minimum(lengths.astype(jnp.int32), S)

    def _last_valid(lens, b):
        return jnp.maximum(_num_valid_blocks(lens[b], bs) - 1, 0)

    def _kv_map(b, h, s, lens):
        # clamp: past-length steps re-"fetch" the last valid block, which
        # the pipeline already holds -> no DMA issued for skipped blocks
        return (b, h, jnp.minimum(s, _last_valid(lens, b)), 0)

    def _scale_map(b, h, s, lens):
        return (b, h, jnp.minimum(s, _last_valid(lens, b)))

    in_specs = [
        pl.BlockSpec((1, 1, Hg, D), lambda b, h, s, lens: (b, h, 0, 0)),
        pl.BlockSpec((1, 1, bs, d_store), _kv_map),
        pl.BlockSpec((1, 1, bs, d_store), _kv_map),
        pl.BlockSpec((1, 1, bs), _scale_map),
        pl.BlockSpec((1, 1, bs), _scale_map),
    ]
    out_specs = pl.BlockSpec((1, 1, Hg, D), lambda b, h, s, lens: (b, h, 0, 0))
    out_shape = jax.ShapeDtypeStruct((B, KV, Hg, D), jnp.bfloat16)
    if debug_visits:
        out_specs = [out_specs,
                     pl.BlockSpec((1, 1), lambda b, h, s, lens: (b, h))]
        out_shape = [out_shape,
                     jax.ShapeDtypeStruct((B, KV), jnp.int32)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, KV, S // bs),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=[pltpu.VMEM((Hg, D), jnp.float32),
                        pltpu.VMEM((Hg, 1), jnp.float32),
                        pltpu.VMEM((Hg, 1), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_kv_attn_kernel, bs=bs, scale=scale,
                          kv_bits=kv_bits, debug_visits=debug_visits),
        grid_spec=grid_spec,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(lengths, q, k_packed, v_packed, k_scale, v_scale)
