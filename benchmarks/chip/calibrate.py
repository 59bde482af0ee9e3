"""Readings that the limit of a cell's comparison is set from, each
judged by the rule that decides `correct` (harness.judge): for each seed,
one run of the cell's whole timed path (set-up, a window at the cell's
own load, the comparison). Without `--fault`, beside the program's
reading the control's: the reference computed a precision step lower
(float8 linear layers), at the same positions; the program has to read
correct and the control not. With `--fault <name>`, the timed path is
broken underneath by that fault of faults.py, and the run has to read
not correct.

    python benchmarks/chip/calibrate.py --workload granite-3-2b.decode \
        --seconds 10 --seeds 101 102 103 [--fault state-unchanged]

All runs share this one process and its chip. Each prints its checks
and its verdicts, and last one JSON line of {seed: readings}. The exit
code is 0 only if every verdict is as it has to be.
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
    sys.path[:0] = [SRC, HERE]
    import faults
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--fault", choices=sorted(faults.FAULTS))
    args = ap.parse_args()
    import jax

    import cell
    import harness
    if jax.devices()[0].platform != "tpu":
        sys.exit("calibrate: no TPU")
    c = cell.load(args.workload)
    limit = c.shape["limits"]["max_logit_gap"]
    fault = faults.FAULTS.get(args.fault)
    out, sound = {}, True
    for seed in args.seeds:
        r = harness.run(c, seed=seed, seconds=args.seconds, trace=False,
                        t_start=time.perf_counter(), control=fault is None,
                        fault=fault)
        ch = r["checks"]
        row = {"program": ch["max_logit_gap"]["value"],
               "program_correct": r["correct"]}
        if fault is None:
            row["control"] = ch["control_max_logit_gap"]["value"]
            row["control_correct"] = harness.judge(row["control"], limit,
                                                   r["failed"])
            sound &= r["correct"] and not row["control_correct"]
        else:
            row["fault"] = args.fault
            sound &= not r["correct"]
        out[seed] = row
        print(json.dumps({"seed": seed, **row, "limit": limit,
                          "metrics": r["metrics"]}), flush=True)
    print(json.dumps(out), flush=True)
    sys.exit(0 if sound else 1)


if __name__ == "__main__":
    main()
