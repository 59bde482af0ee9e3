"""Jitted public wrappers for the Pallas kernels.

On a TPU the kernels compile with Mosaic; on the CPU backend they run in
the Pallas interpreter (the kernel body runs in Python, for correctness
checks). `_auto_interpret` holds the rule.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.dual_plane_matmul import dual_plane_matmul_pallas
from repro.kernels.imc_dot import (imc_dot_pallas, imc_dual_dot_pallas,
                                   quantize_activations)
from repro.kernels.packed_kv_attention import packed_kv_attention_pallas
from repro.kernels.paged_kv_attention import (
    paged_kv_attention_pallas, paged_kv_attention_window_pallas)
from repro.kernels.quantize_pack_kv import quantize_pack_kv_pallas
from repro.kernels.ternary_matmul import ternary_matmul_pallas


def _auto_interpret(interpret):
    """Whether a kernel call runs in the Pallas interpreter.

    On a TPU every call compiles with Mosaic: interpret mode is refused
    there, even when asked for, so a chip run cannot quietly execute a
    kernel in Python. The CPU backend cannot run Mosaic at all; there a
    call that does not choose runs interpreted (how the tests check the
    kernels). Any other backend has to choose."""
    backend = jax.default_backend()
    if backend == "tpu":
        if interpret:
            raise ValueError("interpret mode was requested on a TPU; the "
                             "kernels compile with Mosaic there")
        return False
    if interpret is None:
        if backend != "cpu":
            raise RuntimeError(
                f"the Pallas kernels compile only for a TPU; on {backend!r} "
                f"pass interpret=True to run them in the interpreter")
        return True
    return interpret


@functools.partial(jax.jit, static_argnames=("bm", "bk", "bn", "interpret",
                                             "use_ref"))
def ternary_matmul(x, w_packed, scale, *, bm=128, bk=512, bn=256,
                   interpret=None, use_ref=False):
    """y = x @ unpack(w_packed) * scale — weights stay 2 bits/value in HBM."""
    if use_ref:
        return ref.ternary_matmul_ref(x, w_packed, scale)
    return ternary_matmul_pallas(x, w_packed, scale, bm=bm, bk=bk, bn=bn,
                                 interpret=_auto_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("bm", "bk", "bn", "interpret",
                                             "use_ref"))
def dual_plane_matmul(x, buf, hi_scale, lo_scale, *, bm=128, bk=256, bn=256,
                      interpret=None, use_ref=False):
    """(y_hi, y_lo) = x @ both int4 planes of ONE uint8 buffer."""
    if use_ref:
        return ref.dual_plane_matmul_ref(x, buf, hi_scale, lo_scale)
    return dual_plane_matmul_pallas(x, buf, hi_scale, lo_scale, bm=bm,
                                    bk=bk, bn=bn,
                                    interpret=_auto_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("fmt", "abits", "bm", "bk",
                                             "bn", "interpret", "use_ref"))
def imc_dot(x, wp, scale, *, fmt="ternary", abits=8, bm=128, bk=512, bn=256,
            interpret=None, use_ref=False):
    """Bit-serial IMC dot product over packed weights consumed as stored.

    `fmt` selects the resident storage: "ternary" (K//4, N) u8 trits,
    "int4" (K//2, N) u8 row pairs, "int8" (K, N) i8. Activations are
    quantized per-row to `abits` bits (1/4/8 — arXiv:2008.03378's
    reconfigurable precision) and streamed one magnitude bit-plane per
    cycle. At abits=8 with unit activation scale this is bit-exact with
    `ternary_matmul` on the same packed bytes."""
    if use_ref:
        return ref.imc_dot_ref(x, wp, scale, fmt=fmt, abits=abits)
    xq, xs = quantize_activations(x, abits)
    return imc_dot_pallas(xq, xs, wp, scale, fmt=fmt, abits=abits, bm=bm,
                          bk=bk, bn=bn, interpret=_auto_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("abits", "bm", "bk", "bn",
                                             "interpret", "use_ref"))
def imc_dual_dot(x, buf, hi_scale, lo_scale, *, abits=8, bm=128, bk=256,
                 bn=256, interpret=None, use_ref=False):
    """Bit-serial IMC dot over BOTH int4 planes of one uint8 array: a
    single wordline-serial activation stream, two bitline-parallel
    accumulations (the 8T dual-bit cell as a dot-product engine)."""
    if use_ref:
        return ref.imc_dual_dot_ref(x, buf, hi_scale, lo_scale, abits=abits)
    xq, xs = quantize_activations(x, abits)
    return imc_dual_dot_pallas(xq, xs, buf, hi_scale, lo_scale, abits=abits,
                               bm=bm, bk=bk, bn=bn,
                               interpret=_auto_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("bs", "kv_bits", "debug_visits",
                                             "interpret", "use_ref"))
def packed_kv_attention(q, k_packed, v_packed, k_scale, v_scale, lengths, *,
                        bs=512, kv_bits=4, debug_visits=False, interpret=None,
                        use_ref=False):
    """Flash-decode over a packed KV cache (never dequantized in HBM).

    `kv_bits` selects the storage format: 4 = two int4 nibbles per byte,
    8 = int8. `lengths` is scalar-prefetched: sequence blocks past a row's
    valid length are skipped (no DMA, no compute). With `debug_visits` also
    returns the per-(row, head) count of blocks actually processed."""
    if use_ref:
        assert not debug_visits, "visit counting is a kernel-path feature"
        return ref.packed_kv_attention_ref(q, k_packed, v_packed, k_scale,
                                           v_scale, lengths, kv_bits=kv_bits)
    return packed_kv_attention_pallas(q, k_packed, v_packed, k_scale,
                                      v_scale, lengths, bs=bs,
                                      kv_bits=kv_bits,
                                      debug_visits=debug_visits,
                                      interpret=_auto_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("page", "kv_bits", "interpret",
                                             "use_ref"))
def paged_kv_attention(q, kn, vn, kp, vp, k_scale, v_scale, lengths, modes,
                       normal_idx, packed_idx, *, page, kv_bits=4,
                       interpret=None, use_ref=False):
    """Flash-decode over the paged mode-switchable KV pool.

    Walks each row's page table (scalar-prefetched) in blocks of
    `pages_per_block` pages, all KV heads per grid step: each page slab is
    copied by DMA from the one plane its mode bit names into a
    double-buffered VMEM block, the next block's copies in flight while
    the current one computes; blocks past a row's length issue no DMA and
    no compute. On an all-Augmented pool this is bit-identical to
    `packed_kv_attention` with bs == the block's tokens (same block walk,
    same op order), and it is `paged_kv_attention_window` at W = 1."""
    if use_ref:
        # reconstruct the true page table: at mode==1 steps packed_idx
        # holds the real physical page, at mode==0 steps normal_idx does
        table = jnp.where(modes == 1, packed_idx, normal_idx)
        return ref.paged_kv_attention_ref(q, kn, vn, kp, vp, k_scale,
                                          v_scale, lengths, table, modes,
                                          kv_bits=kv_bits)
    return paged_kv_attention_pallas(q, kn, vn, kp, vp, k_scale, v_scale,
                                     lengths, modes, normal_idx, packed_idx,
                                     page=page, kv_bits=kv_bits,
                                     interpret=_auto_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("page", "kv_bits", "interpret",
                                             "use_ref"))
def paged_kv_attention_window(q, kn, vn, kp, vp, k_scale, v_scale, starts,
                              modes, normal_idx, packed_idx, *, page,
                              kv_bits=4, interpret=None, use_ref=False):
    """Speculative-verify window variant of `paged_kv_attention`.

    q: (B, KV, W, Hg, D) — the W-token draft window per row at absolute
    positions starts + [0..W); window slot w attends tokens
    < starts + w + 1 (causal inside the window). The same kernel body and
    block walk as `paged_kv_attention`, so per window slot this is
    BIT-IDENTICAL to it at lengths == starts + w + 1: the extra tokens a
    slot's shorter horizon masks off contribute exp(-inf) == 0.0 exactly
    in the f32 online softmax, which is what makes accept/rollback
    token-identical to step-by-step decode."""
    if use_ref:
        table = jnp.where(modes == 1, packed_idx, normal_idx)
        return ref.paged_kv_attention_window_ref(
            q, kn, vn, kp, vp, k_scale, v_scale, starts, table, modes,
            kv_bits=kv_bits)
    return paged_kv_attention_window_pallas(
        q, kn, vn, kp, vp, k_scale, v_scale, starts, modes, normal_idx,
        packed_idx, page=page, kv_bits=kv_bits,
        interpret=_auto_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("page", "kv_bits", "interpret",
                                             "use_ref"))
def paged_prefix_attention(q, kn, vn, kp, vp, k_scale, v_scale, lengths,
                           modes, normal_idx, packed_idx, *, page,
                           kv_bits=4, interpret=None, use_ref=False):
    """Cross-attention / STATIC-LENGTH variant of `paged_kv_attention`.

    Serves the encoder-decoder cross KV (and any other fixed-length
    prefix band): one un-roped query token per row attends non-causally
    over a page-table band whose valid length is pinned per row
    (`lengths` = prefix tokens, NOT positions + 1). For a single query
    a non-causal read over `lengths` tokens is exactly the causal
    kernel's masked walk, so the same walk and online softmax are reused
    — the page tables just come from the store's prefix band. Rows whose
    prefix is unallocated (length 0) read no page and give zeros; callers
    ignore their logits."""
    return paged_kv_attention(q, kn, vn, kp, vp, k_scale, v_scale,
                              lengths, modes, normal_idx, packed_idx,
                              page=page, kv_bits=kv_bits,
                              interpret=interpret, use_ref=use_ref)


@functools.partial(jax.jit, static_argnames=("bn", "interpret", "use_ref"))
def quantize_pack_kv(kv, valid=None, *, bn=256, interpret=None,
                     use_ref=False):
    """Fused bf16 -> int4-packed cache rows + per-token scales, one pass.

    kv: (..., D) with D even. Returns (packed (..., D//2) uint8,
    scale (..., 1) bf16) — the same layout `models.layers.pack_kv_int4`
    produces, with no dequantized/int8 intermediate in HBM. `valid`
    (optional, bool, broadcastable to kv.shape[:-1]) is the speculative
    store-back mask: rows whose token the verify pass REJECTED commit as
    zero bytes + unit scale, so only accepted tokens land in the
    augmented plane."""
    if use_ref:
        p, s = ref.quantize_pack_kv_ref(kv)
        if valid is not None:
            keep = jnp.broadcast_to(valid, kv.shape[:-1])[..., None]
            p = jnp.where(keep, p, jnp.uint8(0))
            s = jnp.where(keep, s, 1.0)
        return p, s.astype(jnp.bfloat16)
    lead = kv.shape[:-1]
    D = kv.shape[-1]
    flat = kv.reshape(-1, D)
    N = flat.shape[0]
    bn_eff = min(bn, N)
    pad = (-N) % bn_eff
    if pad:
        flat = jnp.concatenate(
            [flat, jnp.zeros((pad, D), flat.dtype)], axis=0)
    vflat = None
    if valid is not None:
        vflat = jnp.broadcast_to(valid, lead).reshape(-1, 1).astype(jnp.int32)
        if pad:
            vflat = jnp.concatenate(
                [vflat, jnp.zeros((pad, 1), jnp.int32)], axis=0)
    p, s = quantize_pack_kv_pallas(flat, vflat, bn=bn_eff,
                                   interpret=_auto_interpret(interpret))
    p = p[:N].reshape(*lead, D // 2)
    s = s[:N].reshape(*lead, 1).astype(jnp.bfloat16)
    return p, s
