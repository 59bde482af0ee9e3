"""Record the trace fixture `data/granite-decode-admit.xplane.pb.gz`: a
short profiled window of `granite-3-2b.decode` that holds one admission.

    python benchmarks/chip/tests/record_admission_trace.py --seed 7 \
        --out granite-decode-admit.xplane.pb.gz

The cell is set up as a run of it is (harness.py). The engine then steps,
unprofiled, until its next step finishes a row while the request at the
head of the queue has a prompt of at most `MAX_PROMPT` tokens (the
prefill's operations are most of a trace's size). The window holds that
step, the next one (whose admission pass prefills that request) and
`AFTER` more, the client keeping a batch queued as a run does.
"""
from __future__ import annotations

import argparse
import gzip
import os
import shutil
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
SRC = os.path.join(os.path.dirname(os.path.dirname(BENCH)), "src")
AFTER = 2          # plain steps after the admitting one
MAX_PROMPT = 200   # longest prompt the admitted request may have


def record(c, seed: int, out: str) -> None:
    import jax

    import harness
    import trace_reduce
    import traffic
    cfg = harness.model_config(c)
    eng = harness.build_engine(jax, cfg, c.shape, seed, c.chips)
    batch = c.shape["max_batch"]
    annotate = jax.profiler.TraceAnnotation
    client = harness.Client(
        eng, traffic.Generator(c.traffic, seed, cfg.vocab), annotate)
    harness.warm_up(client, True, batch)

    def top_up():
        while client.queued() < batch:
            client.enqueue(client.gen.offer(client.next),
                           time.perf_counter())
            client.next += 1

    while eng.remaining[eng.active].min() > 1 \
            or len(eng.scheduler.queue[0].prompt) > MAX_PROMPT:
        top_up()
        client.step()
    tmp = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(tmp, profiler_options=opts)
    with annotate("bench.window"):
        for _ in range(2 + AFTER):
            top_up()
            client.step()
    jax.profiler.stop_trace()
    with open(trace_reduce.find_xplane(tmp), "rb") as f, \
            gzip.open(out, "wb") as g:
        shutil.copyfileobj(f, g)
    shutil.rmtree(tmp, ignore_errors=True)
    print(f"{out}: {os.path.getsize(out)} bytes", flush=True)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--out", required=True)
    args = ap.parse_args()
    sys.path[:0] = [SRC, BENCH]
    import cell
    record(cell.load("granite-3-2b.decode"), args.seed, args.out)


if __name__ == "__main__":
    main()
