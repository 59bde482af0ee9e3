"""Pallas TPU kernel: flash-decode attention over a PAGED, mode-switchable
augmented KV pool (the serving layer's page-table-indexed variant of
`packed_kv_attention`).

The pool stores fixed-size pages (page_size tokens) in one of two planes:

  Normal     bf16 arena  kn/vn: (Nn, KV, page, D)       — the 6T mode
  Augmented  packed arena kp/vp: (Np, KV, page, D//2|D)  — int4/int8 +
             per-(token, head) scales ks/vs: (Np, KV, page)

A sequence's logical cache is the concatenation of its page table entries
in logical order; each page carries a mode bit. Scalar-prefetched page
tables sit in SMEM before the grid runs: `starts` (B,), `modes` (B, maxP)
and the gather indices `normal_idx` / `packed_idx` (B, maxP), of which
the kernel uses a page's entry in the plane its mode names: the true
physical page (the hold-previous entries elsewhere go unused).

Walk. The grid is (B, n_blocks): one grid step takes a BLOCK of `pages`
consecutive logical pages of one row, all KV heads at once. Blocks past
cdiv(horizon, page) pages issue no DMA and no compute, so grid work is
proportional to each row's cache length. The arenas stay in HBM
(`memory_space=pl.ANY`); for each live page of a block the kernel issues
`make_async_copy`s of the page's whole slab from the one plane its mode
bit names — the packed K and V bytes and the page's scales, or the bf16
K and V — into a double-buffered VMEM block buffer. The next block's
copies (this row's next block, or the next row's first) start before the
current block is computed, so the gather overlaps the compute; both grid
axes are therefore sequential (`arbitrary`).

Operand form. Mosaic slices an HBM ref only where its minor dims are
whole tiles, which (page, D//2) is not, so the wrapper hands the kernel
token-major page slabs: (N, page, KV8, 128·k) packed bytes and bf16 rows
(each head's row padded to whole lanes, KV to a multiple of 8) and the
page's K and V scales as one (N, page, 2·KV → 128·k) bf16 slab. That is
the tiled layout XLA's scatter leaves an arena slice in, so each view
costs one local pad pass, with no transpose over the page index. A pool
whose Normal arena holds only the write-dump page (`always-augmented`)
has no Normal plane in the kernel at all; one whose packed arena does
(`normal-only`) has no packed plane.

Compute. A block is dequantised once with `_load_kv_block`'s arithmetic
(exact: integers in bf16) over all its heads' rows, the scales are
transposed onto lanes, and the block runs one batched score dot and one
batched PV dot over the KV axis (q (KV, R, D) against
k (KV, pages * page, D)), scales applied on the score COLUMNS, the mask
`col <= start + slot`, and one online-softmax update. Pages of a block
that a row does not reach are masked and their V rows weigh exactly
zero, whatever stale bytes the buffer holds.

Bit-identity anchors. Decode is the window walk at W = 1 with
start = length - 1, so the speculative window kernel and the
single-token kernel are one kernel body: window slot w is BIT-identical
to the single-token read at length start + w + 1 (blocks past a slot's
horizon add exp(NEG_INF - m) == 0.0 exactly). On an all-Augmented pool
the walk is BIT-identical to the contiguous `packed_kv_attention` with
bs == pages * page tokens: the same per-head op sequence per block.

Block size (`pages_per_block`) is derived from the shapes alone: about
`BLOCK_TOKENS` tokens a block, capped by maxP and by a VMEM estimate of
the block's double-buffered DMA targets and its unpack temporaries, so
granite's GQA shape (KV 8, Hg 4: 32 pages of 16) and minicpm's MHA shape
(KV 36, Hg 1: 8 pages) fit the default scoped VMEM limit, with or
without a Normal plane (tests/test_tpu_compile.py compiles them for a
v5e).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.packed_kv_attention import NEG_INF, _load_kv_block

BLOCK_TOKENS = 512          # tokens a block aims at
_VMEM_BUDGET = 12 << 20     # bytes a block may take of the 16 MiB scoped
_LANES = 128
_SUBLANES = 8


def pages_per_block(page: int, max_p: int, kv_heads: int, hg: int,
                    head_dim: int, kv_bits: int, normal: bool = False) -> int:
    """Logical pages one grid step walks: about BLOCK_TOKENS tokens, at
    most max_p, and few enough that the block fits _VMEM_BUDGET. A token
    holds there, per padded head row, its slab rows in the double-buffered
    DMA targets and Mosaic's 32-bit temporaries of the unpack (about
    1.2 KiB a 128-lane row of packed bytes, 1.5 KiB more with a Normal
    plane), about 10 KiB of per-token vectors, and the f32 scores,
    probabilities and PV operand of its column: calibrated against the
    v5e compiler at granite's and minicpm's shapes, with margin
    (tests/test_tpu_compile.py). Rounded down to a power of two, so the
    served tables (maxP 64, 128) split into whole blocks."""
    d_store = head_dim // 2 if kv_bits == 4 else head_dim
    rows = kv_heads + -kv_heads % _SUBLANES
    per_row = 1200 * pl.cdiv(d_store, _LANES)
    if normal:
        per_row += 1500 * pl.cdiv(head_dim, _LANES)
    per_token = rows * per_row + (10 << 10) + 12 * kv_heads * max(hg, 8)
    fit = max(1, _VMEM_BUDGET // (per_token * page))
    want = max(1, min(BLOCK_TOKENS // page, fit, max_p))
    return 1 << (want.bit_length() - 1)


def _heads(block: jax.Array, kv: int, width: int) -> jax.Array:
    """(pages, page, KV8, W) slab block -> (kv, pages * page, width) in a
    32-bit type: each head's tokens in logical order, its row cut to
    `width` lanes."""
    wide = jnp.float32 if block.dtype == jnp.bfloat16 else jnp.int32
    pages, page, kv8, w = block.shape
    x = block.astype(wide).reshape(pages * page, kv8, w)
    return jnp.swapaxes(x, 0, 1)[:kv, :, :width]


def _walk_kernel(starts_ref, modes_ref, ni_ref, pi_ref, q_ref, *refs,
                 page: int, pages: int, win: int, hg: int, d: int,
                 d_store: int, kv_bits: int, scale: float, packed: bool,
                 normal: bool):
    it = iter(refs)
    if packed:
        kp_hbm, vp_hbm, sc_hbm = next(it), next(it), next(it)
    if normal:
        kn_hbm, vn_hbm = next(it), next(it)
    o_ref = next(it)
    if packed:
        kp_buf, vp_buf, sc_buf = next(it), next(it), next(it)
    if normal:
        kn_buf, vn_buf = next(it), next(it)
    sem, slot_ref, acc_ref, m_ref, l_ref = it

    b, j = pl.program_id(0), pl.program_id(1)
    n_rows = pl.num_programs(0)
    max_p = modes_ref.shape[1]
    kv, rows = q_ref.shape[1], q_ref.shape[2]
    tokens = pages * page

    def n_pages(row):
        """Pages the row's walk reads: up to its last window slot."""
        horizon = jnp.minimum(starts_ref[row] + win, max_p * page)
        return jnp.clip(pl.cdiv(horizon, page), 0, max_p)

    def n_blocks(row):
        return jnp.maximum(pl.cdiv(n_pages(row), pages), 1)  # >= 1: init

    def for_copies(row, blk, slot, op):
        """`op` on the copy of every page slab of a block, each from its
        own plane, in a loop over the pages the row reaches."""
        def page(i, carry):
            pc = blk * pages + i
            aug = modes_ref[row, pc] == 1
            if packed:
                src = pi_ref[row, pc]
                for hbm, buf in ((kp_hbm, kp_buf), (vp_hbm, vp_buf),
                                 (sc_hbm, sc_buf)):
                    c = pltpu.make_async_copy(hbm.at[src], buf.at[slot, i],
                                              sem.at[slot])
                    pl.when(aug)(lambda c=c: op(c)) if normal else op(c)
            if normal:
                src = ni_ref[row, pc]
                for hbm, buf in ((kn_hbm, kn_buf), (vn_hbm, vn_buf)):
                    c = pltpu.make_async_copy(hbm.at[src], buf.at[slot, i],
                                              sem.at[slot])
                    pl.when(~aug)(lambda c=c: op(c)) if packed else op(c)
            return carry
        live = jnp.clip(n_pages(row) - blk * pages, 0, pages)
        jax.lax.fori_loop(0, live, page, 0)

    def start(row, blk, slot):
        for_copies(row, blk, slot, lambda c: c.start())

    def wait(row, blk, slot):
        for_copies(row, blk, slot, lambda c: c.wait())

    nb = n_blocks(b)

    @pl.when((b == 0) & (j == 0))
    def _first():
        slot_ref[0] = 0
        start(0, 0, 0)

    @pl.when(j < nb)
    def _visit():
        cur = slot_ref[0]
        last = j + 1 == nb
        nxt_row = jnp.where(last, b + 1, b)

        @pl.when(nxt_row < n_rows)
        def _prefetch():
            start(nxt_row, jnp.where(last, 0, j + 1), 1 - cur)

        wait(b, j, cur)

        @pl.when(j == 0)
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)
            m_ref[...] = jnp.full_like(m_ref, NEG_INF)
            l_ref[...] = jnp.zeros_like(l_ref)

        # pages the row reaches in this block; the rest of the block's
        # buffer holds stale bytes and is masked below
        n_live = jnp.clip(n_pages(b) - j * pages, 0, pages)
        tok = jax.lax.broadcasted_iota(jnp.int32, (tokens, 1), 0)
        reached = tok < n_live * page

        def packed_plane():
            def dequant(buf):
                raw = _heads(buf[cur], kv, d_store).reshape(kv * tokens, -1)
                return _load_kv_block(raw, kv_bits).reshape(kv, tokens, -1)
            k, v = dequant(kp_buf), dequant(vp_buf)        # (KV, T, D)
            # (T, lanes) -> (lanes, T): row h is K head h's scales, row
            # KV + h V head h's, each token on its own lane
            sc = sc_buf[cur].astype(jnp.float32).reshape(tokens, -1).T
            return (k, v, sc[:kv].reshape(kv, 1, tokens),
                    sc[kv:2 * kv].reshape(kv, 1, tokens))

        def normal_plane():
            return (_heads(buf[cur], kv, d).astype(jnp.bfloat16)
                    for buf in (kn_buf, vn_buf))

        if packed and normal:
            # per token the plane of its page; a page the row does not
            # reach reads the (finite) packed buffer
            tok_l = jax.lax.broadcasted_iota(jnp.int32, (1, tokens), 1)
            sel, sel_l = ~reached, tok_l >= n_live * page
            for i in range(pages):
                pc = jnp.minimum(j * pages + i, max_p - 1)
                aug = modes_ref[b, pc] == 1
                lo, hi = i * page, (i + 1) * page
                sel = sel | ((tok >= lo) & (tok < hi) & aug)
                sel_l = sel_l | ((tok_l >= lo) & (tok_l < hi) & aug)
            k, v, k_scale, v_scale = packed_plane()
            kn, vn = normal_plane()
            sel = sel.reshape(1, tokens, 1)
            sel_l = sel_l.reshape(1, 1, tokens)
            # Normal pages are pre-scaled bf16: their scale is 1
            k, v = jnp.where(sel, k, kn), jnp.where(sel, v, vn)
            k_scale = jnp.where(sel_l, k_scale, 1.0)
            v_scale = jnp.where(sel_l, v_scale, 1.0)
        elif packed:
            k, v, k_scale, v_scale = packed_plane()
        else:
            k, v = normal_plane()
            v = jnp.where(reached.reshape(1, tokens, 1), v, 0)
            k_scale = v_scale = jnp.ones((kv, 1, tokens), jnp.float32)

        q = q_ref[0]                                       # (KV, R, D)
        s = jnp.einsum("krd,ktd->krt", q, k,
                       preferred_element_type=jnp.float32)
        s = s * (k_scale * scale)                          # (KV, R, T)
        # score row r is window slot r // hg, whose horizon is
        # start + slot + 1 tokens: col - start <= r // hg, i.e.
        # (col - start) * hg <= r (no vector integer division)
        col = j * tokens + jax.lax.broadcasted_iota(
            jnp.int32, (rows, tokens), 1)
        r = jax.lax.broadcasted_iota(jnp.int32, (rows, tokens), 0)
        keep = ((col - starts_ref[b]) * hg <= r).reshape(1, rows, tokens)
        s = jnp.where(keep, s, NEG_INF)

        m_prev = m_ref[...]                                # (KV, R, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=2, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=2, keepdims=True)
        pv = jnp.where(keep, p * v_scale, 0.0).astype(jnp.bfloat16)
        acc_ref[...] = (acc_ref[...] * alpha
                        + jnp.einsum("krt,ktd->krd", pv, v,
                                     preferred_element_type=jnp.float32))
        m_ref[...] = m_new

        @pl.when(last)
        def _done():
            o_ref[0] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)

        slot_ref[0] = 1 - cur


def _slabs(arena: jax.Array) -> jax.Array:
    """(N, KV, page, w) arena -> (N, page, KV8, W) token-major page slabs,
    KV padded to a multiple of 8 rows and w to a multiple of 128 lanes.
    That is the tiled layout XLA's scatter leaves the
    arena slice in, so the view costs one local pass over it, and Mosaic
    can slice one page's slab out of it."""
    a = arena.transpose(0, 2, 1, 3)
    return jnp.pad(a, ((0, 0), (0, 0), (0, -a.shape[2] % _SUBLANES),
                       (0, -a.shape[3] % _LANES)))


def _walk(q, kn, vn, kp, vp, k_scale, v_scale, starts, modes, normal_idx,
          packed_idx, *, page: int, kv_bits: int, win: int, name: str,
          interpret: bool):
    """q: (B, KV, win * Hg, D) — score row r is window slot r // Hg.
    Returns (B, KV, win * Hg, D) bf16."""
    B, KV, R, D = q.shape
    hg = R // win
    max_p = modes.shape[1]
    assert kv_bits in (4, 8), kv_bits
    d_store = D // 2 if kv_bits == 4 else D
    assert kn.shape[1:] == (KV, page, D), (kn.shape, KV, page, D)
    assert kp.shape[1:] == (KV, page, d_store), (kp.shape, page, d_store)
    normal = kn.shape[0] > 1
    packed = kp.shape[0] > 1 or not normal
    pages = pages_per_block(page, max_p, KV, hg, D, kv_bits, normal)
    n_blocks = pl.cdiv(max_p, pages)

    hbm = pl.BlockSpec(memory_space=pl.ANY)
    operands = []
    if packed:
        # both planes' scales of a page as one (page, 2 * KV) bf16 slab
        sc = jnp.concatenate([k_scale, v_scale], axis=1).transpose(0, 2, 1)
        sc = jnp.pad(sc, ((0, 0), (0, 0), (0, -sc.shape[2] % _LANES)))
        operands += [_slabs(kp), _slabs(vp), sc]
    if normal:
        operands += [_slabs(kn), _slabs(vn)]
    scratch = [pltpu.VMEM((2, pages) + x.shape[1:], x.dtype)
               for x in operands]
    scratch += [pltpu.SemaphoreType.DMA((2,)), pltpu.SMEM((1,), jnp.int32),
                pltpu.VMEM((KV, R, D), jnp.float32),
                pltpu.VMEM((KV, R, 1), jnp.float32),
                pltpu.VMEM((KV, R, 1), jnp.float32)]
    row_block = pl.BlockSpec((1, KV, R, D), lambda b, j, *_: (b, 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(B, n_blocks),
        in_specs=[row_block] + [hbm] * len(operands),
        out_specs=row_block,
        scratch_shapes=scratch,
    )
    return pl.pallas_call(
        functools.partial(_walk_kernel, page=page, pages=pages, win=win,
                          hg=hg, d=D, d_store=d_store, kv_bits=kv_bits,
                          scale=1.0 / (D ** 0.5), packed=packed,
                          normal=normal),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, R, D), jnp.bfloat16),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name=name,
    )(starts.astype(jnp.int32), modes.astype(jnp.int32),
      normal_idx.astype(jnp.int32), packed_idx.astype(jnp.int32), q,
      *operands)


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
    modes / normal_idx / packed_idx: (B, maxP) int32 (see module
    docstring). The window walk at W = 1. Returns (B, KV, Hg, D) bf16."""
    max_len = modes.shape[1] * page
    starts = jnp.minimum(lengths.astype(jnp.int32), max_len) - 1
    return _walk(q, kn, vn, kp, vp, k_scale, v_scale, starts, modes,
                 normal_idx, packed_idx, page=page, kv_bits=kv_bits, win=1,
                 name="paged_kv_attention", interpret=interpret)


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
    # the W window queries share one walk as W*Hg score rows; the
    # flattening happens here (Mosaic cannot merge a sub-tile Hg dim)
    out = _walk(q.reshape(B, KV, W * Hg, D), kn, vn, kp, vp, k_scale,
                v_scale, starts, modes, normal_idx, packed_idx, page=page,
                kv_bits=kv_bits, win=W, name="paged_kv_attention_window",
                interpret=interpret)
    return out.reshape(B, KV, W, Hg, D)
