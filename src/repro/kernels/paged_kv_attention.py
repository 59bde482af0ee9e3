"""Pallas TPU kernel: flash-decode attention over a PAGED, mode-switchable
augmented KV pool (the serving layer's page-table-indexed variant of
`packed_kv_attention`).

The pool stores fixed-size pages (page_size tokens) in one of two planes:

  Normal     bf16 arena  kn/vn: (Nn, KV, page, D)       — the 6T mode
  Augmented  packed arena kp/vp: (Np, KV, page, D//2|D)  — int4/int8 +
             per-(token, head) scales ks/vs: (Np, KV, page)

A sequence's logical cache is the concatenation of its page table entries
in logical order; each page carries a mode bit. The kernel walks logical
pages (innermost grid dim) and computes the online softmax exactly as the
contiguous `packed_kv_attention` does with bs == page_size — on a pool
whose pages are all Augmented this is BIT-IDENTICAL to the contiguous
kernel (same block walk, same op order), which is the golden anchor.

Scalar-prefetched page tables: `lengths` (B,), `modes` (B, maxP) and the
two HOLD-PREVIOUS gather index arrays `normal_idx` / `packed_idx`
(B, maxP) sit in SMEM before the grid runs. The host precomputes
hold-previous semantics: normal_idx[b, s] is the physical Normal page to
have resident at logical step s — the page itself when modes[b, s] == 0,
else the index already resident from the previous step, so the mode-
mismatched arena issues NO new DMA (the same pipeline-reuse trick the
contiguous kernel plays for skipped length blocks). Entries past a row's
valid page count are clamped to the last valid entry for the same reason.

Grid: (B, KV, maxP); B and KV are `parallel`, the page walk is
`arbitrary` (carries the softmax state). `pl.when` guards pages past
cdiv(length, page) — no MXU/VPU work for short rows, so grid work is
proportional to actual cache length exactly as in the contiguous kernel.

TPU note: page_size is the sequence-block size. Every block spans the
whole of its array's last two dims (a page's rows x head_dim, or a
page's (KV, page) scales, of which the kernel reads its head's row), so
Mosaic accepts any page size,
16 included, for the bf16 and the int8/uint8 arenas alike
(tests/test_tpu_compile.py compiles pages of 16 and 32 for a v5e).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.packed_kv_attention import NEG_INF, _load_kv_block


def _head_scales(ref, h) -> jax.Array:
    """Head `h`'s row of a (1, KV, page) scale block, as a (1, page) f32
    row vector: the score columns are scaled by it. The block holds all
    KV heads of the page so that it spans the arena's last two dims, as
    the TPU tiling rule asks, with no relayout of the arena. The row is
    picked by a masked sublane sum (exact: one term plus zeros), since
    Mosaic loads no dynamic sublane slice of a bf16 block."""
    x = ref[0].astype(jnp.float32)                        # (KV, page)
    rows = jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
    return jnp.sum(jnp.where(rows == h, x, 0.0), axis=0, keepdims=True)


def _paged_kernel(lens_ref, modes_ref, ni_ref, pi_ref, q_ref, kn_ref,
                  vn_ref, kp_ref, vp_ref, ks_ref, vs_ref, o_ref,
                  acc_ref, m_ref, l_ref, *, page: int, scale: float,
                  kv_bits: int):
    b, h = pl.program_id(0), pl.program_id(1)
    s_step = pl.program_id(2)
    length = lens_ref[b]
    nvp = jnp.maximum(pl.cdiv(length, page), 1)   # >=1 so init/output fire
    visited = s_step < nvp

    @pl.when(s_step == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(visited)
    def _compute():
        q = q_ref[0, 0]                               # (Hg, D) bf16
        is_aug = modes_ref[b, s_step] == 1
        # both candidate blocks are resident (the mismatched arena's index
        # map held its previous block -> no DMA was issued for it)
        k_aug = _load_kv_block(kp_ref[0, 0], kv_bits)  # (page, D) bf16
        v_aug = _load_kv_block(vp_ref[0, 0], kv_bits)
        k = jnp.where(is_aug, k_aug, kn_ref[0, 0])
        v_int = jnp.where(is_aug, v_aug, vn_ref[0, 0])
        # Normal pages are pre-scaled bf16: the "sense amplifier" scale
        # collapses to 1. Augmented pages dequantize on score COLUMNS.
        one = jnp.ones((1, page), jnp.float32)
        k_scale = jnp.where(is_aug, _head_scales(ks_ref, h), one)
        v_scale = jnp.where(is_aug, _head_scales(vs_ref, h), one)

        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)
        s = s * (k_scale * scale)                     # (Hg, page)
        valid = (s_step * page
                 + jax.lax.broadcasted_iota(jnp.int32, (1, page), 1)) < length
        s = jnp.where(valid, s, NEG_INF)

        m_prev = m_ref[...]                           # (Hg, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)                        # (Hg, page)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        pv = (p * v_scale).astype(jnp.bfloat16)
        acc_ref[...] = (acc_ref[...] * alpha
                        + jnp.dot(pv, v_int,
                                  preferred_element_type=jnp.float32))
        m_ref[...] = m_new

    @pl.when(s_step == nvp - 1)
    def _done():
        o_ref[0, 0] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


def paged_kv_attention_pallas(q: jax.Array, kn: jax.Array, vn: jax.Array,
                              kp: jax.Array, vp: jax.Array,
                              k_scale: jax.Array, v_scale: jax.Array,
                              lengths: jax.Array, modes: jax.Array,
                              normal_idx: jax.Array, packed_idx: jax.Array,
                              *, page: int, kv_bits: int = 4,
                              interpret: bool = False):
    """q: (B, KV, Hg, D) bf16; kn/vn: (Nn, KV, page, D) bf16;
    kp/vp: (Np, KV, page, D//2) uint8 (kv_bits=4) or (Np, KV, page, D)
    int8 (kv_bits=8); k/v_scale: (Np, KV, page) bf16; lengths: (B,) int32;
    modes / normal_idx / packed_idx: (B, maxP) int32 with HOLD-PREVIOUS
    gather semantics precomputed on the host (see module docstring).
    Returns (B, KV, Hg, D) bf16."""
    B, KV, Hg, D = q.shape
    maxP = modes.shape[1]
    assert kv_bits in (4, 8), kv_bits
    d_store = D // 2 if kv_bits == 4 else D
    assert kn.shape[2:] == (page, D), (kn.shape, page, D)
    assert kp.shape[2:] == (page, d_store), (kp.shape, page, d_store)
    scale = 1.0 / (D ** 0.5)
    lengths = jnp.minimum(lengths.astype(jnp.int32), maxP * page)

    def _nidx(b, h, s, lens, modes, ni, pi):
        return (ni[b, s], h, 0, 0)

    def _pidx(b, h, s, lens, modes, ni, pi):
        return (pi[b, s], h, 0, 0)

    def _pscale(b, h, s, lens, modes, ni, pi):
        return (pi[b, s], 0, 0)

    in_specs = [
        pl.BlockSpec((1, 1, Hg, D), lambda b, h, s, *_: (b, h, 0, 0)),
        pl.BlockSpec((1, 1, page, D), _nidx),
        pl.BlockSpec((1, 1, page, D), _nidx),
        pl.BlockSpec((1, 1, page, d_store), _pidx),
        pl.BlockSpec((1, 1, page, d_store), _pidx),
        pl.BlockSpec((1, KV, page), _pscale),
        pl.BlockSpec((1, KV, page), _pscale),
    ]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(B, KV, maxP),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, Hg, D), lambda b, h, s, *_: (b, h, 0, 0)),
        scratch_shapes=[pltpu.VMEM((Hg, D), jnp.float32),
                        pltpu.VMEM((Hg, 1), jnp.float32),
                        pltpu.VMEM((Hg, 1), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_paged_kernel, page=page, scale=scale,
                          kv_bits=kv_bits),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, Hg, D), jnp.bfloat16),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="paged_kv_attention",
    )(lengths, modes.astype(jnp.int32), normal_idx.astype(jnp.int32),
      packed_idx.astype(jnp.int32), q, kn, vn, kp, vp, k_scale, v_scale)


def _paged_window_kernel(starts_ref, modes_ref, ni_ref, pi_ref, q_ref,
                         kn_ref, vn_ref, kp_ref, vp_ref, ks_ref, vs_ref,
                         o_ref, acc_ref, m_ref, l_ref, *, page: int,
                         scale: float, kv_bits: int, win: int, hg: int):
    """W-query-token variant of `_paged_kernel` for speculative verify.

    The W window queries of a row share one page walk: queries are
    flattened onto the score rows ((win*Hg, page) per page) and every
    softmax-state op is row-independent, so each window slot w computes
    EXACTLY the single-token kernel's op sequence for length
    starts + w + 1 — pages wholly past a slot's horizon contribute
    exp(NEG_INF - m) == 0.0 in f32, a bit-exact no-op. That is the
    token-identity anchor the speculative engine's golden test pins."""
    b, h = pl.program_id(0), pl.program_id(1)
    s_step = pl.program_id(2)
    max_p = pl.num_programs(2)
    start = starts_ref[b]
    # horizon of the LAST window slot, clamped to the table's reach
    length = jnp.minimum(start + win, max_p * page)
    nvp = jnp.clip(pl.cdiv(length, page), 1, max_p)
    visited = s_step < nvp
    rows = win * hg

    @pl.when(s_step == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(visited)
    def _compute():
        q = q_ref[0, 0]                                    # (W*Hg, D) bf16
        is_aug = modes_ref[b, s_step] == 1
        k_aug = _load_kv_block(kp_ref[0, 0], kv_bits)      # (page, D) bf16
        v_aug = _load_kv_block(vp_ref[0, 0], kv_bits)
        k = jnp.where(is_aug, k_aug, kn_ref[0, 0])
        v_int = jnp.where(is_aug, v_aug, vn_ref[0, 0])
        one = jnp.ones((1, page), jnp.float32)
        k_scale = jnp.where(is_aug, _head_scales(ks_ref, h), one)
        v_scale = jnp.where(is_aug, _head_scales(vs_ref, h), one)

        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)
        s = s * (k_scale * scale)                          # (W*Hg, page)
        # causal-inside-the-window mask: score row r belongs to window
        # slot r // Hg, whose horizon is start + slot + 1 tokens, i.e.
        # col - start <= r // Hg, which for integers is
        # (col - start) * Hg <= r (no vector integer division)
        col = (s_step * page
               + jax.lax.broadcasted_iota(jnp.int32, (rows, page), 1))
        row = jax.lax.broadcasted_iota(jnp.int32, (rows, page), 0)
        s = jnp.where((col - start) * hg <= row, s, NEG_INF)

        m_prev = m_ref[...]                                # (W*Hg, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)                             # (W*Hg, page)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        pv = (p * v_scale).astype(jnp.bfloat16)
        acc_ref[...] = (acc_ref[...] * alpha
                        + jnp.dot(pv, v_int,
                                  preferred_element_type=jnp.float32))
        m_ref[...] = m_new

    @pl.when(s_step == nvp - 1)
    def _done():
        o_ref[0, 0] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


def paged_kv_attention_window_pallas(q: jax.Array, kn: jax.Array,
                                     vn: jax.Array, kp: jax.Array,
                                     vp: jax.Array, k_scale: jax.Array,
                                     v_scale: jax.Array, starts: jax.Array,
                                     modes: jax.Array, normal_idx: jax.Array,
                                     packed_idx: jax.Array, *, page: int,
                                     kv_bits: int = 4,
                                     interpret: bool = False):
    """q: (B, KV, W, Hg, D) bf16 — W speculative window queries per row at
    absolute positions starts + [0..W); arenas/scales/tables laid out as
    `paged_kv_attention_pallas`. Window slot w attends tokens
    < starts[b] + w + 1. Returns (B, KV, W, Hg, D) bf16."""
    B, KV, W, Hg, D = q.shape
    maxP = modes.shape[1]
    assert kv_bits in (4, 8), kv_bits
    d_store = D // 2 if kv_bits == 4 else D
    assert kn.shape[2:] == (page, D), (kn.shape, page, D)
    assert kp.shape[2:] == (page, d_store), (kp.shape, page, d_store)
    scale = 1.0 / (D ** 0.5)

    def _nidx(b, h, s, lens, modes, ni, pi):
        return (ni[b, s], h, 0, 0)

    def _pidx(b, h, s, lens, modes, ni, pi):
        return (pi[b, s], h, 0, 0)

    def _pscale(b, h, s, lens, modes, ni, pi):
        return (pi[b, s], 0, 0)

    in_specs = [
        pl.BlockSpec((1, 1, W * Hg, D), lambda b, h, s, *_: (b, h, 0, 0)),
        pl.BlockSpec((1, 1, page, D), _nidx),
        pl.BlockSpec((1, 1, page, D), _nidx),
        pl.BlockSpec((1, 1, page, d_store), _pidx),
        pl.BlockSpec((1, 1, page, d_store), _pidx),
        pl.BlockSpec((1, KV, page), _pscale),
        pl.BlockSpec((1, KV, page), _pscale),
    ]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(B, KV, maxP),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, W * Hg, D),
                               lambda b, h, s, *_: (b, h, 0, 0)),
        scratch_shapes=[pltpu.VMEM((W * Hg, D), jnp.float32),
                        pltpu.VMEM((W * Hg, 1), jnp.float32),
                        pltpu.VMEM((W * Hg, 1), jnp.float32)],
    )
    # the W window queries share one page walk as W*Hg score rows; the
    # flattening happens here (Mosaic cannot merge a sub-tile Hg dim)
    out = pl.pallas_call(
        functools.partial(_paged_window_kernel, page=page, scale=scale,
                          kv_bits=kv_bits, win=W, hg=Hg),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, W * Hg, D), jnp.bfloat16),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="paged_kv_attention_window",
    )(starts.astype(jnp.int32), modes.astype(jnp.int32),
      normal_idx.astype(jnp.int32), packed_idx.astype(jnp.int32),
      q.reshape(B, KV, W * Hg, D), kn, vn, kp, vp, k_scale, v_scale)
    return out.reshape(B, KV, W, Hg, D)
