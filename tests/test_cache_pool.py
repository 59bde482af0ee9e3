"""Paged augmented KV pool: kernel golden (bit-identical to the
contiguous packed path on single-mode pools), mixed-mode oracle parity,
and the pool's byte-budget / mode-switch accounting."""
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch
from repro.configs.base import AMCConfig
from repro.kernels import ops as K
from repro.kernels.paged_kv_attention import pages_per_block
from repro.kernels.ref import rel_err
from repro.models import layers as L
from repro.serve.cache_pool import PagedKVPool


# ---------------------------------------------------------------------------
# kernel goldens
# ---------------------------------------------------------------------------

def _contiguous_packed(rng, B, KV, S, D, kv_bits):
    if kv_bits == 4:
        kp = jnp.asarray(rng.integers(0, 256, (B, KV, S, D // 2)), jnp.uint8)
        vp = jnp.asarray(rng.integers(0, 256, (B, KV, S, D // 2)), jnp.uint8)
    else:
        kp = jnp.asarray(rng.integers(-127, 128, (B, KV, S, D)), jnp.int8)
        vp = jnp.asarray(rng.integers(-127, 128, (B, KV, S, D)), jnp.int8)
    ks = jnp.asarray(rng.uniform(0.01, 0.1, (B, KV, S)), jnp.bfloat16)
    vs = jnp.asarray(rng.uniform(0.01, 0.1, (B, KV, S)), jnp.bfloat16)
    return kp, vp, ks, vs


def _page_out(contig, page, maxP, B):
    """Split a contiguous (B, KV, S, ·) operand into arena pages with an
    in-order page table (physical pages 1..B*maxP; 0 is the dump)."""
    KV = contig.shape[1]
    tail = contig.shape[3:]
    arena = jnp.zeros((B * maxP + 1, KV, page) + tail, contig.dtype)
    table = np.zeros((B, maxP), np.int32)
    phys = 1
    for b in range(B):
        for p in range(maxP):
            arena = arena.at[phys].set(contig[b, :, p * page:(p + 1) * page])
            table[b, p] = phys
            phys += 1
    return arena, table


@pytest.mark.parametrize("kv_bits", [4, 8])
def test_paged_kernel_bit_identical_to_contiguous_on_single_mode(kv_bits):
    """Acceptance golden: an all-Augmented paged pool walked in logical
    page order must be BIT-identical to `packed_kv_attention` with
    bs == page_size — same block walk, same op order."""
    rng = np.random.default_rng(0)
    B, KV, Hg, D, page, maxP = 2, 2, 4, 32, 8, 4
    S = maxP * page
    q = jnp.asarray(rng.standard_normal((B, KV, Hg, D)), jnp.bfloat16)
    kp_c, vp_c, ks_c, vs_c = _contiguous_packed(rng, B, KV, S, D, kv_bits)
    lengths = jnp.asarray([S, 13], jnp.int32)
    o_contig = K.packed_kv_attention(
        q, kp_c, vp_c, ks_c, vs_c, lengths,
        bs=page * pages_per_block(page, maxP, KV, Hg, D, kv_bits),
        kv_bits=kv_bits)

    kp, table = _page_out(kp_c, page, maxP, B)
    vp, _ = _page_out(vp_c, page, maxP, B)
    ks, _ = _page_out(ks_c, page, maxP, B)
    vs, _ = _page_out(vs_c, page, maxP, B)
    d_n = D
    kn = jnp.zeros((1, KV, page, d_n), jnp.bfloat16)
    vn = jnp.zeros((1, KV, page, d_n), jnp.bfloat16)
    modes = jnp.ones((B, maxP), jnp.int32)
    o_paged = K.paged_kv_attention(
        q, kn, vn, kp, vp, ks, vs, lengths, modes,
        jnp.zeros((B, maxP), jnp.int32), jnp.asarray(table),
        page=page, kv_bits=kv_bits)
    a = np.asarray(o_paged).view(np.uint16)
    b = np.asarray(o_contig).view(np.uint16)
    assert (a == b).all(), "paged walk diverged from contiguous kernel"


@pytest.mark.parametrize("kv_bits", [4, 8])
def test_paged_kernel_mixed_mode_matches_oracle(kv_bits):
    """Pages alternating Normal/Augmented (with the Normal plane holding
    the dequantized rows) must agree with the gather+dense oracle."""
    rng = np.random.default_rng(1)
    B, KV, Hg, D, page, maxP = 2, 2, 2, 32, 8, 4
    S = maxP * page
    q = jnp.asarray(rng.standard_normal((B, KV, Hg, D)), jnp.bfloat16)
    kp_c, vp_c, ks_c, vs_c = _contiguous_packed(rng, B, KV, S, D, kv_bits)
    kp, table = _page_out(kp_c, page, maxP, B)
    vp, _ = _page_out(vp_c, page, maxP, B)
    ks, _ = _page_out(ks_c, page, maxP, B)
    vs, _ = _page_out(vs_c, page, maxP, B)
    unpack = L.unpack_kv_int4 if kv_bits == 4 else L.unpack_kv_int8
    # even logical pages go Normal: dequantize them into the bf16 arena
    kn = jnp.zeros((B * maxP + 1, KV, page, D), jnp.bfloat16)
    vn = jnp.zeros((B * maxP + 1, KV, page, D), jnp.bfloat16)
    modes = np.ones((B, maxP), np.int32)
    for b in range(B):
        for p in range(0, maxP, 2):
            phys = table[b, p]
            kn = kn.at[phys].set(unpack(kp[phys], ks[phys][..., None]))
            vn = vn.at[phys].set(unpack(vp[phys], vs[phys][..., None]))
            modes[b, p] = 0
    nidx = np.zeros((B, maxP), np.int32)
    pidx = np.zeros((B, maxP), np.int32)
    lastn = np.zeros(B, np.int32)
    lastp = np.zeros(B, np.int32)
    for s in range(maxP):
        lastn = np.where(modes[:, s] == 0, table[:, s], lastn)
        lastp = np.where(modes[:, s] == 1, table[:, s], lastp)
        nidx[:, s], pidx[:, s] = lastn, lastp
    lengths = jnp.asarray([S, 21], jnp.int32)
    args = (q, kn, vn, kp, vp, ks, vs, lengths, jnp.asarray(modes),
            jnp.asarray(nidx), jnp.asarray(pidx))
    o = K.paged_kv_attention(*args, page=page, kv_bits=kv_bits)
    o_ref = K.paged_kv_attention(*args, page=page, kv_bits=kv_bits,
                                 use_ref=True)
    assert rel_err(o, o_ref) < 0.02


# Multi-block walks: with page 32 a block holds 16 pages (512 tokens), so
# maxP 20 walks two blocks a row, the second cut short by the table.
# (KV, Hg, maxP, row lengths, mixed Normal/Augmented pool)
_WALK_CASES = {
    "mha": (4, 1, 20, (640, 300), True),
    "maxp_not_block_multiple": (2, 2, 20, (640, 513), False),
    "len_1_block_multiple_max_seq": (2, 2, 20, (1, 512, 640), False),
    "mode_changes_inside_block": (2, 2, 20, (600, 200), True),
    "table_ends_mid_block": (2, 2, 20, (545, 37), True),
}


@pytest.mark.parametrize("case", sorted(_WALK_CASES))
def test_paged_kernel_block_walk_matches_oracle(case):
    """Block walks the small goldens above never take: two blocks a row,
    a last block that maxP cuts short, rows of one token / exactly one
    block / max_seq, pages whose mode changes inside a block, and
    hold-previous tables that end mid-block (entries past a row's pages
    hold its last page, as `PagedKVPool.device_tables` builds them).
    Every case must match the gather+dense oracle; on an all-Augmented
    pool the walk must also be BIT-identical to the contiguous kernel
    with bs == the block's tokens."""
    KV, Hg, maxP, lens, mixed = _WALK_CASES[case]
    rng = np.random.default_rng(3)
    B, D, page, kv_bits = len(lens), 32, 32, 4
    pages = pages_per_block(page, maxP, KV, Hg, D, kv_bits, mixed)
    assert pages < maxP and maxP % pages, "case must walk a short block"
    S = maxP * page
    q = jnp.asarray(rng.standard_normal((B, KV, Hg, D)), jnp.bfloat16)
    kp_c, vp_c, ks_c, vs_c = _contiguous_packed(rng, B, KV, S, D, kv_bits)
    kp, table = _page_out(kp_c, page, maxP, B)
    vp, _ = _page_out(vp_c, page, maxP, B)
    ks, _ = _page_out(ks_c, page, maxP, B)
    vs, _ = _page_out(vs_c, page, maxP, B)
    live = np.arange(maxP)[None, :] < -(-np.asarray(lens)[:, None] // page)
    modes = np.where(live, 1, 0).astype(np.int32)
    kn = vn = jnp.zeros((1, KV, page, D), jnp.bfloat16)
    if mixed:
        modes = np.where(live & (rng.random((B, maxP)) < 0.5), 0, modes)
        kn = jnp.zeros((B * maxP + 1, KV, page, D), jnp.bfloat16)
        vn = jnp.zeros((B * maxP + 1, KV, page, D), jnp.bfloat16)
        for b, p in zip(*np.nonzero(live & (modes == 0))):
            phys = table[b, p]
            kn = kn.at[phys].set(L.unpack_kv_int4(kp[phys],
                                                  ks[phys][..., None]))
            vn = vn.at[phys].set(L.unpack_kv_int4(vp[phys],
                                                  vs[phys][..., None]))
    nidx = np.zeros((B, maxP), np.int32)
    pidx = np.zeros((B, maxP), np.int32)
    lastn = np.zeros(B, np.int32)
    lastp = np.zeros(B, np.int32)
    for s in range(maxP):
        lastn = np.where(live[:, s] & (modes[:, s] == 0), table[:, s], lastn)
        lastp = np.where(live[:, s] & (modes[:, s] == 1), table[:, s], lastp)
        nidx[:, s], pidx[:, s] = lastn, lastp
    lengths = jnp.asarray(lens, jnp.int32)
    args = (q, kn, vn, kp, vp, ks, vs, lengths, jnp.asarray(modes),
            jnp.asarray(nidx), jnp.asarray(pidx))
    o = K.paged_kv_attention(*args, page=page, kv_bits=kv_bits)
    o_ref = K.paged_kv_attention(*args, page=page, kv_bits=kv_bits,
                                 use_ref=True)
    assert rel_err(o, o_ref) < 0.02
    if not mixed:
        # the contiguous kernel walks whole blocks: pad S to a multiple
        bs = pages * page
        pad = ((0, 0), (0, 0), (0, -S % bs))
        o_contig = K.packed_kv_attention(
            q, jnp.pad(kp_c, pad + ((0, 0),)), jnp.pad(vp_c, pad + ((0, 0),)),
            jnp.pad(ks_c, pad), jnp.pad(vs_c, pad), lengths, bs=bs,
            kv_bits=kv_bits)
        assert (np.asarray(o).view(np.uint16)
                == np.asarray(o_contig).view(np.uint16)).all()


# ---------------------------------------------------------------------------
# pool accounting and mode switches
# ---------------------------------------------------------------------------

def _pool(kv_mode="normal", pool_mode="augment-on-pressure", **kw):
    cfg = get_arch("qwen1.5-0.5b").reduced()
    cfg = dataclasses.replace(cfg, amc=AMCConfig(kv_mode=kv_mode,
                                                 pool_mode=pool_mode))
    return PagedKVPool(cfg, max_batch=2, max_seq=32, **kw)


def test_pool_budget_accounting_alloc_free():
    pool = _pool()
    pbn = pool.geom.page_bytes_normal
    assert pool.live_bytes == 0
    assert pool.alloc_page(0, 0, step=0)
    assert pool.live_bytes == pbn
    assert pool.page_mode[0, 0] == 0
    pool.free_row(0)
    assert pool.live_bytes == 0
    assert not pool.allocated.any()


def test_pool_augment_frees_budget_and_preserves_values():
    pool = _pool()
    g = pool.geom
    assert pool.alloc_page(0, 0, step=0)
    # write a recognizable page into the Normal plane
    rng = np.random.default_rng(2)
    phys = int(pool.page_table[0, 0])
    x = jnp.asarray(rng.standard_normal(
        pool.arenas["kn"].shape[:1] + pool.arenas["kn"].shape[2:]),
        jnp.bfloat16)
    pool.arenas["kn"] = pool.arenas["kn"].at[:, phys].set(x)
    before = pool.live_bytes
    pool.augment_page(0, 0, step=1)
    assert pool.page_mode[0, 0] == 1
    assert pool.live_bytes == before - (g.page_bytes_normal
                                        - g.page_bytes_aug)
    assert pool.stats["augment_events"] == 1
    assert (0, 0) in pool.policies          # retention clock started
    # round-trip: promote back and compare against the original rows
    assert pool.promote_page(0, 0, step=2)
    phys2 = int(pool.page_table[0, 0])
    y = pool.arenas["kn"][:, phys2]
    err = rel_err(y, x)
    tol = 0.2 if g.aug_bits == 4 else 0.02   # one quant step
    assert err < tol, err
    assert pool.live_bytes == before
    assert (0, 0) not in pool.policies


def test_pool_budget_rejects_when_exhausted_normal_only():
    g = _pool().geom
    pool = _pool(pool_mode="normal-only",      # exactly maxP pages: 1 seq
                 budget_bytes=2 * g.page_bytes_normal)
    assert pool.alloc_page(0, 0, 0) and pool.alloc_page(0, 1, 0)
    assert not pool.alloc_page(1, 0, 0)        # budget spent, no augmenting
    assert pool.stats["alloc_failures"] == 1
    assert not pool.can_admit_tokens(1)


def test_pool_pressure_augments_coldest_first():
    g = _pool().geom
    # budget fits 2 Normal pages, and a third page only after exactly one
    # augmentation (normal + 2*aug <= budget < 2*normal + aug)
    pool = _pool(budget_bytes=g.page_bytes_normal + 2 * g.page_bytes_aug)
    assert pool.alloc_page(0, 0, step=0)       # coldest (earliest write)
    assert pool.alloc_page(0, 1, step=5)
    # budget full: the next alloc must demote the step-0 page, not step-5
    assert pool.alloc_page(1, 0, step=6)
    assert pool.page_mode[0, 0] == 1           # cold page went Augmented
    assert pool.page_mode[0, 1] == 0           # hot page stayed Normal
    assert pool.page_mode[1, 0] == 1           # newcomer placed packed
    assert pool.stats["augment_events"] == 1


def test_pool_refresh_restamps_and_accounts_traffic():
    pool = _pool(kv_mode="int8", pool_mode="always-augmented",
                 retention_steps=2)
    assert pool.alloc_page(0, 0, step=0)
    assert pool.refresh_due(1) == []
    assert pool.refresh_due(2) == [(0, 0)]     # age == retention_steps
    pool.refresh_page(0, 0, step=2)
    assert pool.refresh_due(2) == []           # restamped
    assert pool.stats["refreshes"] == 1
    assert pool.stats["refresh_bytes"] == 2 * pool.geom.page_bytes_aug
    assert pool.max_augmented_age(3) == 1


def test_pool_single_sequence_must_fit_budget():
    g = _pool().geom
    with pytest.raises(ValueError, match="cannot hold one full sequence"):
        _pool(pool_mode="normal-only",          # 1 of the 2 pages needed
              budget_bytes=g.page_bytes_normal)


def test_device_tables_hold_previous_semantics():
    pool = _pool(kv_mode="int8")
    pool.alloc_page(0, 0, 0)
    pool.alloc_page(0, 1, 0)
    pool.augment_page(0, 1, step=1)            # page 1 -> packed plane
    t = pool.device_tables()
    md = np.asarray(t["page_modes"])
    ni, pi = np.asarray(t["normal_idx"]), np.asarray(t["packed_idx"])
    assert md[0, 0] == 0 and md[0, 1] == 1
    assert ni[0, 0] == pool.page_table[0, 0]
    assert ni[0, 1] == ni[0, 0]                # held: no DMA for normal
    assert pi[0, 0] == 0                       # dump until first aug page
    assert pi[0, 1] == pool.page_table[0, 1]
