"""Knee sweep of a cell with timed (Poisson) arrivals: the same engine
and traffic mix offered at each of several fixed rates, one window each.
For every rate it reports the admitted rate, the backlog left at the
window's close, and the TTFT and inter-token tails; the knee is the
highest rate whose backlog does not grow. The rate a cell offers is set
once from such a sweep and written into its traffic file.

    python benchmarks/chip/sweep.py --workload granite-3-2b.chat \
        --seconds 40 --rates 0.3 0.5 0.7 0.9 --seed 5

Between rates the engine drains what is left, untimed. One JSON line per
rate, on standard output.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(os.path.dirname(os.path.dirname(HERE)), "src")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    sys.path[:0] = [SRC, HERE]
    import jax

    import cell
    import harness
    import traffic
    if jax.devices()[0].platform != "tpu":
        sys.exit("sweep: no TPU")
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    c = cell.load(args.workload)
    if c.traffic["arrivals"]["kind"] != "poisson":
        sys.exit(f"sweep: {args.workload} has no timed arrivals")
    cfg = harness.model_config(c)
    eng = harness.build_engine(jax, cfg, c.shape, args.seed, c.chips)
    annotate = jax.profiler.TraceAnnotation
    warm = harness.Client(eng, traffic.Generator(c.traffic, args.seed,
                                                 cfg.vocab), annotate)
    harness.warm_up(warm, False, c.shape["max_batch"])
    for rate in args.rates:
        mix = dict(c.traffic, arrivals={"kind": "poisson",
                                        "rate_per_s": rate})
        eng.outputs.clear()                  # ids restart at 0 per rate
        client = harness.Client(eng, traffic.Generator(mix, args.seed,
                                                       cfg.vocab), annotate)
        t0 = time.perf_counter()
        harness.drive(client, False, c.shape["max_batch"], t0, args.seconds)
        t_end = t0 + args.seconds
        w = harness.window_numbers(client, t0, t_end)
        admitted = sum(1 for t in client.admitted.values()
                       if t0 <= t <= t_end)
        row = {"rate_per_s": rate, "window_s": w["window_s"],
               "offered": len(client.offers),
               "admitted_per_s": admitted / w["window_s"],
               "backlog_at_close": client.queued(),
               "running_at_close": int(eng.active.sum()),
               "ttft_p50_ms": harness.percentile(w["ttft"], 50) * 1e3,
               "ttft_p95_ms": harness.percentile(w["ttft"], 95) * 1e3,
               "itl_p95_ms": (harness.percentile(w["itl"], 95) * 1e3
                              if w["itl"] else None),
               "output_tok_s": w["tokens"] / w["window_s"],
               "late_max_s": max(w["late"], default=0.0)}
        print(json.dumps(row), flush=True)
        while client.busy():                 # drain, untimed
            client.step()


if __name__ == "__main__":
    main()
