"""Chip smoke test: granite-3-2b at its full published width (40 layers,
d_model 2048, GQA 32/8, d_ff 8192, vocab 49155; dual-plane weights, int4
KV), served on a TPU through the repo's own entry points.

    python chip_smoke.py            # one chip: kernels vs their oracles,
                                    # then 8 requests through make_serving
    python chip_smoke.py --chips 4  # four chips: an ArrayFleet of four
                                    # arrays, one per device, against one
                                    # array on the same requests — only

Everything runs in this one process, which holds the chip(s). Weights and
prompts are made from --seed. Without a TPU the script exits non-zero
and prints no result. Lines before the last report what was checked and
the set-up facts of the run (compile time, peak device memory, tokens
served — facts of this run, not benchmark metrics). The last line is one
JSON object: {"ok": true, "device": {"platform", "kind", "count"}}.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(REPO, "src")

ARCH = "granite-3-2b"
MAX_BATCH, MAX_SEQ = 8, 1024
N_REQUESTS, PROMPT_LENS, NEW_TOKENS = 8, (128, 512), 32
# the kernel tests' tolerances (tests/test_kernels.py, test_cache_pool.py,
# test_speculative.py): relative error vs the oracle; packing is exact
MATMUL_TOL, ATTN_TOL = 0.02, 0.02


def fail(msg: str) -> None:
    print(f"chip_smoke: FAILED: {msg}", file=sys.stderr)
    sys.exit(1)


def check(ok: bool, msg: str) -> None:
    if not ok:
        fail(msg)


class CompileClock:
    """Sums XLA backend-compile seconds (persistent-cache reads included)
    and counts persistent-cache hits, from JAX's monitoring events."""

    def __init__(self, jax):
        self.seconds, self.cache_hits = 0.0, 0

        def on_duration(name, secs, **_):
            if name == "/jax/core/compile/backend_compile_duration":
                self.seconds += secs

        def on_event(name, **_):
            if name == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)


# ---------------------------------------------------------------------------
# phase b: each main-path kernel once at granite widths vs its oracle
# ---------------------------------------------------------------------------

def _paged_pool(jax, jnp, np, key, *, B, KV, D, page, max_p, kv_bits):
    """A random two-plane pool with mixed page modes and its hold-previous
    gather tables (layouts of kernels/paged_kv_attention.py)."""
    n_pages = B * max_p + 1                       # page 0: the dump page
    ks = jax.random.split(key, 6)
    d_store = D // 2 if kv_bits == 4 else D
    shape_n, shape_p = (n_pages, KV, page, D), (n_pages, KV, page, d_store)
    kn = jax.random.normal(ks[0], shape_n, jnp.bfloat16)
    vn = jax.random.normal(ks[1], shape_n, jnp.bfloat16)
    if kv_bits == 4:
        kp = jax.random.randint(ks[2], shape_p, 0, 256).astype(jnp.uint8)
        vp = jax.random.randint(ks[3], shape_p, 0, 256).astype(jnp.uint8)
    else:
        kp = jax.random.randint(ks[2], shape_p, -127, 128).astype(jnp.int8)
        vp = jax.random.randint(ks[3], shape_p, -127, 128).astype(jnp.int8)
    scale = (n_pages, KV, page)
    k_s = jax.random.uniform(ks[4], scale, jnp.float32, 0.02, 0.2)
    v_s = jax.random.uniform(ks[5], scale, jnp.float32, 0.02, 0.2)
    rng = np.random.default_rng(0)
    table = (rng.permutation(n_pages - 1) + 1).reshape(B, max_p)
    modes = rng.integers(0, 2, size=(B, max_p)).astype(np.int32)
    nidx = np.zeros((B, max_p), np.int32)
    pidx = np.zeros((B, max_p), np.int32)
    last_n = np.zeros(B, np.int32)
    last_p = np.zeros(B, np.int32)
    for s in range(max_p):
        last_n = np.where(modes[:, s] == 0, table[:, s], last_n)
        last_p = np.where(modes[:, s] == 1, table[:, s], last_p)
        nidx[:, s], pidx[:, s] = last_n, last_p
    planes = (kn, vn, kp, vp, k_s.astype(jnp.bfloat16),
              v_s.astype(jnp.bfloat16))
    tables = tuple(jnp.asarray(t) for t in (modes, nidx, pidx))
    return planes, tables


def kernel_phase(jax, cfg, seed: int) -> None:
    import jax.numpy as jnp
    import numpy as np

    from repro.core import quant, ternary
    from repro.kernels import ops, ref
    from repro.kernels.quantize_pack_kv import quantize_pack_kv_pallas
    from repro.models import augment

    key = jax.random.PRNGKey(seed)
    KV, D = cfg.n_kv_heads, cfg.hd
    Hg = cfg.n_heads // KV
    page = cfg.amc.page_size
    max_p = -(-MAX_SEQ // page)
    B = MAX_BATCH

    def oracle(fn, *args, **kw):
        # the oracles are f32 programs: on a TPU, XLA's default matmul
        # precision would round their f32 operands to bf16
        with jax.default_matmul_precision("highest"):
            return fn(*args, **kw)

    def report(name, err, tol):
        print(f"kernel {name}: max_rel_err={err!r} (limit {tol})",
              flush=True)
        check(err < tol, f"{name} differs from its oracle: {err} >= {tol}")

    # paged flash-decode over a mixed Normal/Augmented pool, and its
    # speculative-verify window variant
    for kv_bits in (4, 8):
        planes, tables = _paged_pool(jax, jnp, np, jax.random.fold_in(
            key, kv_bits), B=B, KV=KV, D=D, page=page, max_p=max_p,
            kv_bits=kv_bits)
        rng = np.random.default_rng(kv_bits)
        q = jax.random.normal(jax.random.fold_in(key, 10 + kv_bits),
                              (B, KV, Hg, D), jnp.bfloat16)
        lengths = jnp.asarray(rng.integers(1, MAX_SEQ + 1, size=B),
                              jnp.int32)
        run = lambda use_ref: ops.paged_kv_attention(  # noqa: E731
            q, *planes, lengths, *tables, page=page, kv_bits=kv_bits,
            use_ref=use_ref)
        report(f"paged_kv_attention int{kv_bits} page {page}",
               ref.rel_err(run(False), oracle(run, True)), ATTN_TOL)
        W = 4
        qw = jax.random.normal(jax.random.fold_in(key, 20 + kv_bits),
                               (B, KV, W, Hg, D), jnp.bfloat16)
        starts = jnp.asarray(rng.integers(0, MAX_SEQ - W, size=B),
                             jnp.int32)
        run = lambda use_ref: ops.paged_kv_attention_window(  # noqa: E731
            qw, *planes, starts, *tables, page=page, kv_bits=kv_bits,
            use_ref=use_ref)
        report(f"paged_kv_attention_window int{kv_bits} page {page} W {W}",
               ref.rel_err(run(False), oracle(run, True)), ATTN_TOL)

    # fused quantize-pack: plain, masked (speculative store-back) and with
    # integrity words — bit-exact with the host packer, run on the host
    # (XLA's own division on the chip is not correctly rounded)
    kv = jax.random.normal(jax.random.fold_in(key, 30),
                           (B, 32, KV, D), jnp.bfloat16)   # a prefill chunk
    valid = jnp.asarray(np.random.default_rng(3).integers(0, 2, (B, 32, 1)),
                        bool)
    host = jax.devices("cpu")[0]
    on_host = lambda *a: jax.device_put(a, host)  # noqa: E731
    for name, args in (("plain", ()), ("masked", (valid,))):
        p, s = ops.quantize_pack_kv(kv, *args)
        pr, sr = ops.quantize_pack_kv(*on_host(kv, *args), use_ref=True)
        bad = int((np.asarray(p) != np.asarray(pr)).sum()) + int(
            (np.asarray(s, np.float32) != np.asarray(sr, np.float32)).sum())
        print(f"kernel quantize_pack_kv {name}: mismatched bytes and scales "
              f"{bad} of {p.size + s.size} (limit 0)", flush=True)
        check(bad == 0, f"quantize_pack_kv {name} is not bit-exact")
    flat = kv.reshape(-1, D)
    p, s, w = jax.jit(lambda x: quantize_pack_kv_pallas(
        x, with_integrity=True))(flat)
    pr, sr = ref.quantize_pack_kv_ref(*on_host(flat))
    wr = ref.integrity_words_ref(pr)
    bad = (int((np.asarray(p) != np.asarray(pr)).sum())
           + int((np.asarray(s) != np.asarray(sr)).sum())
           + int((np.asarray(w) != np.asarray(wr)).sum()))
    print(f"kernel quantize_pack_kv integrity: mismatched bytes, scales and "
          f"words {bad} of {p.size + s.size + w.size} (limit 0)", flush=True)
    check(bad == 0, "quantize_pack_kv with_integrity is not bit-exact")

    # the two weight matmuls at granite's MLP shape, through the same
    # blocking the served model uses (augment.*_apply), decode-sized M
    d, f = cfg.d_model, cfg.d_ff
    x = jax.random.normal(jax.random.fold_in(key, 40), (B, d), jnp.bfloat16)
    wg = jax.random.normal(jax.random.fold_in(key, 41), (d, f))
    wu = jax.random.normal(jax.random.fold_in(key, 42), (d, f))
    qg, sg = quant.quantize_int4(wg, axis=0)
    qu, su = quant.quantize_int4(wu, axis=0)
    buf = quant.pack_int4_pair(qg, qu)
    yh, yl = augment.dual_apply(x, buf, sg, su)
    rh, rl = oracle(ref.dual_plane_matmul_ref, x, buf, sg, su)
    report(f"dual_plane_matmul {B}x{d} @ {d}x{f}",
           max(ref.rel_err(yh, rh), ref.rel_err(yl, rl)), MATMUL_TOL)
    t, ts = ternary.ternarize(wg)
    wp = ternary.pack_ternary_2bit(t)
    report(f"ternary_matmul {B}x{d} @ {d}x{f}",
           ref.rel_err(augment.ternary_apply(x, wp, ts),
                       oracle(ref.ternary_matmul_ref, x, wp, ts)), MATMUL_TOL)


# ---------------------------------------------------------------------------
# phase c: granite-3-2b served through make_serving
# ---------------------------------------------------------------------------

def make_requests(cfg, seed: int):
    import numpy as np

    from repro.serve import Request
    rng = np.random.default_rng(seed)
    lo, hi = PROMPT_LENS
    return [Request(prompt=rng.integers(0, cfg.vocab, size=int(n_tok))
                    .astype(np.int32), max_new_tokens=NEW_TOKENS, id=i)
            for i, n_tok in enumerate(rng.integers(lo, hi + 1,
                                                   size=N_REQUESTS))]


def check_served(outs, reqs, vocab: int) -> int:
    """Every request returned all its tokens, each a vocab id."""
    check(sorted(outs) == [r.id for r in reqs],
          f"served ids {sorted(outs)} != requested")
    for r in reqs:
        toks = outs[r.id]
        check(len(toks) == r.max_new_tokens,
              f"request {r.id} returned {len(toks)} of "
              f"{r.max_new_tokens} tokens")
        check(all(0 <= t < vocab for t in toks),
              f"request {r.id} returned an id outside the vocab")
    return sum(len(outs[r.id]) for r in reqs)


def decode_hlo(jax, eng) -> str:
    """Compiled text of the engine's own jitted decode step."""
    import numpy as np
    B = eng.max_batch
    batch = {**eng.store.device_tables(),
             "tokens": np.zeros((B, 1), np.int32),
             "positions": np.zeros((B,), np.int32),
             "write_mask": np.zeros((B,), bool)}
    return eng._decode.lower(eng.params, eng.store.state,
                             batch).compile().as_text()


def serve_phase(jax, cfg, seed: int, clock: CompileClock) -> None:
    from repro.launch.mesh import make_local_mesh
    from repro.serve import make_serving

    t0 = time.perf_counter()
    eng = make_serving(cfg, make_local_mesh(), max_batch=MAX_BATCH,
                       max_seq=MAX_SEQ, seed=seed)
    jax.block_until_ready((eng.params, eng.store.state))
    build_s = time.perf_counter() - t0
    a = eng.cfg.amc
    print(f"setup: {cfg.name} built ({cfg.n_layers} layers, "
          f"d_model {cfg.d_model}, heads {cfg.n_heads}/{cfg.n_kv_heads}, "
          f"d_ff {cfg.d_ff}, vocab {cfg.vocab}; weights {a.weight_mode}, "
          f"kv {a.kv_mode}, matmul {a.matmul_impl}, attention "
          f"{a.kv_impl}, page {a.page_size}) in {build_s!r} s", flush=True)
    reqs = make_requests(cfg, seed)
    t0 = time.perf_counter()
    outs = eng.generate(reqs)
    serve_s = time.perf_counter() - t0
    served = check_served(outs, reqs, cfg.vocab)
    hlo = decode_hlo(jax, eng)
    n_kernels = hlo.count("tpu_custom_call")
    print(f"check: the compiled decode step holds {n_kernels} "
          f"tpu_custom_call (Mosaic kernel) sites", flush=True)
    check(n_kernels > 0, "the served decode step holds no Mosaic kernel")
    peak = jax.devices()[0].memory_stats()["peak_bytes_in_use"]
    lens = [len(r.prompt) for r in reqs]
    print(f"setup: served {len(reqs)} requests, prompts {lens} tokens, "
          f"{served} new tokens in all, in "
          f"{serve_s!r} s wall (compilation included)", flush=True)
    print(f"setup: compile seconds {clock.seconds!r} "
          f"(persistent cache hits {clock.cache_hits})", flush=True)
    print(f"setup: peak_bytes_in_use {peak}", flush=True)
    print(f"setup: first request's tokens {outs[reqs[0].id]}", flush=True)


# ---------------------------------------------------------------------------
# --chips 4: four arrays, one per device, against one array
# ---------------------------------------------------------------------------

def _leaf_devices(tree) -> set:
    import jax
    return {d for leaf in jax.tree.leaves(tree) for d in leaf.devices()}


def fleet_phase(jax, cfg, devices, seed: int) -> None:
    from repro.launch.mesh import make_local_mesh
    from repro.serve import ArrayFleet, make_serving

    eng_kw = {"max_batch": MAX_BATCH, "max_seq": MAX_SEQ}
    n = len(devices)

    single = make_serving(cfg, make_local_mesh(devices[:1]), seed=seed,
                          **eng_kw)
    reqs = make_requests(cfg, seed)
    t0 = time.perf_counter()
    want = single.generate(reqs)
    one_s = time.perf_counter() - t0
    check_served(want, reqs, cfg.vocab)
    del single
    gc.collect()

    fleet = make_serving(cfg, make_local_mesh(devices), num_arrays=n,
                         seed=seed, **eng_kw)
    check(isinstance(fleet, ArrayFleet), "make_serving built no ArrayFleet")
    owned = []            # each array's own device: its mesh's only one
    for aid, eng in enumerate(fleet.engines):
        own = set(eng.mesh.devices.flat)
        held = (_leaf_devices(eng.params), _leaf_devices(eng.store.state))
        print(f"check: array {aid} on mesh {sorted(map(str, own))}: weights "
              f"on {sorted(map(str, held[0]))}, store on "
              f"{sorted(map(str, held[1]))}", flush=True)
        check(len(own) == 1 and held[0] == held[1] == own,
              f"array {aid} is not wholly on its own device {own}")
        owned += own
    check(sorted(owned, key=str) == sorted(devices, key=str),
          f"the arrays' devices {owned} are not the {n} devices")
    reqs = make_requests(cfg, seed)
    t0 = time.perf_counter()
    got = fleet.generate(reqs)
    fleet_s = time.perf_counter() - t0
    served = check_served(got, reqs, cfg.vocab)
    per_array = [sum(1 for a in fleet.placements.values() if a == aid)
                 for aid in range(n)]
    print(f"check: {n} arrays on {n} distinct devices served requests "
          f"{per_array} per array; token-identical to one array: "
          f"{got == want}", flush=True)
    check(got == want, "fleet outputs differ from the single array's")
    print(f"setup: {served} new tokens in all; one array {one_s!r} s, "
          f"{n} arrays {fleet_s!r} s wall (compilation included)",
          flush=True)


def main() -> None:
    ap = argparse.ArgumentParser(
        description=__doc__.split("\n\n")[0],
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-array fleet phase")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the weights, kernel inputs and prompts")
    args = ap.parse_args()
    if not os.path.isdir(os.path.join(SRC, "repro")):
        fail(f"no repro package under {SRC}: run from the repository")
    sys.path.insert(0, SRC)

    from repro.launch.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        fail(f"no TPU: JAX found {devices[0].platform} devices only")
    check(len(devices) >= args.chips,
          f"--chips {args.chips} but JAX found {len(devices)} device(s)")
    clock = CompileClock(jax)
    print(f"setup: {len(devices)} x {devices[0].device_kind}; compile "
          f"cache {cache_dir}", flush=True)

    from repro.configs import get_arch
    cfg = get_arch(ARCH)
    if args.chips == 4:
        fleet_phase(jax, cfg, devices[:4], args.seed)
    else:
        kernel_phase(jax, cfg, args.seed)
        serve_phase(jax, cfg, args.seed, clock)
    d = devices[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
