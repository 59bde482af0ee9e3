"""Run one benchmark cell once, on the chip this process is started on.

    python benchmarks/chip/run_cell.py --workload granite-3-2b.decode \
        --seed 7 --seconds 51 --trace 0

The cell is `BENCHMARK.json`'s workload of that name, with the files under
benchmarks/chip/ that it names (cell.py). The run builds the engine
through `repro.serve.make_serving` with weights made on the device from
the seed, warms up the cell's programs, drives its traffic for
`--seconds`, and compares what the window served with the plain
reference that the cell's configuration names (cell.py).

The last line of standard output is one JSON object: `correct`,
`attempted`, `failed`, `metrics` (the cell's end-to-end metrics, or with
`--trace 1` its per-layer metrics read from a profiler trace of the
window), `device`, with `--trace 1` a `breakdown`, and last `checks`,
each number compared beside its limit. Everything else goes to standard
error, ending with the same checks. Without a TPU, or with fewer chips
than the cell asks for, the run exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(os.path.dirname(os.path.dirname(HERE)), "src")


def fail(msg: str) -> None:
    print(f"run_cell: {msg}", file=sys.stderr, flush=True)
    sys.exit(2)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(SRC, "repro")):
        fail(f"no system under test: {SRC}/repro is missing")
    sys.path.insert(0, HERE)
    sys.path.insert(0, SRC)
    import cell
    c = cell.load(args.workload)

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        fail(f"no TPU: JAX found {devices[0].platform} devices only")
    if len(devices) < c.chips:
        fail(f"{args.workload} asks for {c.chips} chips, JAX found "
             f"{len(devices)}")
    import harness
    result = harness.run(c, seed=args.seed, seconds=args.seconds,
                         trace=bool(args.trace), t_start=T_START)
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
