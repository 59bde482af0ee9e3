"""Benchmark harness — one section per paper table + kernel and e2e benches.
Prints ``name,us_per_call,derived`` CSV (see DESIGN.md SS7 experiment index)
and writes BENCH_serve.json (prefill/decode throughput, the kv_mode x
weight_mode serving matrix + modeled HBM traffic), BENCH_kernels.json
(per-kernel modeled bytes + Pallas-interpret parity),
BENCH_scheduler.json (pool modes x offered load + the per-family arch
sweep), BENCH_paper_tables.json (the Tables I-VI analog rows, structured)
BENCH_imc.json (storage matrix x activation precision: modeled
energy/token + throughput), BENCH_fault.json (retention-fault chaos
sweep: injection rates x recovery outcomes, with token identity to the
fault-free run asserted), BENCH_obs.json (observability overhead vs
the disabled Null facade + trace/metrics cross-validation) and
BENCH_prefix.json (shared-prefix page reuse: prefill dispatches saved,
hit rate, bytes shared, with decode token identity to the
sharing-disabled run asserted) so the serving perf trajectory is
tracked across PRs. BENCH_manifest.json
records run provenance: jax version/backend, seed, git sha and
per-emitter wall time.

A failing emitter no longer takes the others down silently: every section
runs, tracebacks are printed, the surviving payloads are written, and the
process exits non-zero if ANY emitter threw — CI fails loudly instead of
uploading a quietly truncated artifact set.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time
import traceback


def _git_sha(root: str) -> str:
    try:
        return subprocess.run(
            ["git", "rev-parse", "HEAD"], cwd=root, capture_output=True,
            text=True, timeout=10, check=True).stdout.strip()
    except Exception:
        return "unknown"


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--seed", type=int, default=0,
                    help="base RNG seed threaded into every emitter "
                         "(prompt/request/weight randomness)")
    ap.add_argument("--tiny", action="store_true",
                    help="CI smoke mode: each emitter runs a minimal "
                         "subset (single cells instead of full sweeps) "
                         "so the whole harness finishes in minutes")
    ap.add_argument("--num-arrays", type=int, nargs="+",
                    default=[1, 2, 4],
                    help="fleet sizes the scheduler emitter sweeps "
                         "(recorded in BENCH_manifest.json provenance)")
    args = ap.parse_args()
    print("name,us_per_call,derived")
    import functools

    import jax

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    from benchmarks import e2e_bench, fault_bench, imc_bench, kernels_bench
    from benchmarks import obs_bench, paper_tables, prefix_bench
    from benchmarks import scheduler_bench
    scheduler_run = functools.partial(scheduler_bench.run_all,
                                      num_arrays=tuple(args.num_arrays))
    # the obs emitter measures a ~1% effect against run-to-run noise, so
    # it goes FIRST: after minutes of heavy sweeps the machine is hot
    # (frequency/cache state) and the measurement floor degrades
    sections = (
        ("BENCH_obs.json",
         "observability overhead + trace/metrics cross-validation",
         obs_bench.run_all),
        ("BENCH_paper_tables.json", "paper tables I-VI analogs",
         paper_tables.run_all),
        ("BENCH_kernels.json", "pallas kernels (bytes/roofline)",
         kernels_bench.run_all),
        ("BENCH_serve.json", "end-to-end (reduced configs, CPU)",
         e2e_bench.run_all),
        ("BENCH_scheduler.json",
         "continuous-batching scheduler (pool modes x load x arch "
         "x fleet size)",
         scheduler_run),
        ("BENCH_imc.json", "in-memory compute (storage x precision)",
         imc_bench.run_all),
        ("BENCH_fault.json",
         "retention-fault chaos (rates x recovery, token identity)",
         fault_bench.run_all),
        ("BENCH_prefix.json",
         "shared-prefix page reuse (multi-turn chat, COW + identity)",
         prefix_bench.run_all),
    )
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    failures: list[str] = []
    # run manifest: provenance + per-emitter wall time, written even when
    # emitters fail so a partial artifact set is still attributable
    # fleet/mesh provenance: the scheduler fleet sweep is only
    # reproducible given the array counts AND the device layout it
    # partitioned (one CPU device means arrays shared it)
    devices = jax.devices()
    manifest: dict = {
        "jax_version": jax.__version__,
        "backend": jax.default_backend(),
        "seed": args.seed,
        "tiny": args.tiny,
        "git_sha": _git_sha(root),
        "num_arrays": list(args.num_arrays),
        "mesh": {
            "device_count": len(devices),
            "devices": [str(d) for d in devices[:8]],
            "local_mesh_shape": {"data": len(devices), "model": 1},
            "axes": ["data", "model"],
        },
        "emitters": {},
    }
    t_total = time.perf_counter()
    for name, title, emit in sections:
        print(f"# -- {title} --")
        t0 = time.perf_counter()
        try:
            payload = emit(seed=args.seed, tiny=args.tiny)
        except Exception:
            failures.append(name)
            manifest["emitters"][name] = {
                "wall_s": time.perf_counter() - t0, "ok": False}
            print(f"# EMITTER FAILED: {name}", file=sys.stderr)
            traceback.print_exc()
            continue
        manifest["emitters"][name] = {
            "wall_s": time.perf_counter() - t0, "ok": True}
        out = os.path.join(root, name)
        with open(out, "w") as f:
            json.dump(payload, f, indent=2)
        print(f"# wrote {out}")
    manifest["wall_s_total"] = time.perf_counter() - t_total
    mpath = os.path.join(root, "BENCH_manifest.json")
    with open(mpath, "w") as f:
        json.dump(manifest, f, indent=2)
    print(f"# wrote {mpath}")
    if failures:
        print(f"# FAILED emitters: {', '.join(failures)}", file=sys.stderr)
        sys.exit(1)
    print("# done")


if __name__ == "__main__":
    main()
