"""Find the knee of an open-loop cell: set it up once, then offer load at
each of a list of rates and report what the engine sustained.

    python3 benchmark/sweep.py --workload sift1m-flat.serve --seed 7 \
        --rates 1000,2000,3000,4000 --seconds 5

One line a rate on standard output: the rate offered and completed, the
50th/99th percentile latency, and how far the last request's answer
trailed its due time (a queue that grows all through the run shows
there). The knee is the highest rate whose completed rate keeps up and
whose p99 stays flat; a cell runs at four fifths of it.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

T_START = time.perf_counter()
sys.path[0] = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

from benchmark import harness  # noqa: E402
from benchmark.run import compile_cache  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", required=True, help="comma-separated requests/s")
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--cpu-rehearsal", action="store_true")
    args = ap.parse_args(argv)
    if args.cpu_rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
    compile_cache(harness.ROOT)
    c = harness.set_up(args.workload, args.seed, rehearsal=args.cpu_rehearsal, t_start=T_START)
    if c.traffic["kind"] != "open":
        print("sweep: the cell's traffic is not an open loop", file=sys.stderr)
        return 2
    import numpy as np

    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        win = harness.drive(c, args.seconds, args.seed + 10 + i, rate=rate)
        lat = np.asarray(win.latency_ms)
        late = np.asarray(win.lateness_s)
        print(json.dumps({
            "rate_offered": rate, "rate_completed": len(lat) / win.seconds,
            "window_s": win.seconds, "failed": win.failed,
            "p50_ms": float(np.percentile(lat, 50)), "p99_ms": float(np.percentile(lat, 99)),
            "tail_drain_s": win.seconds - args.seconds,
            "mean_batch_rows": float(np.mean([b[0] for b in win.batches])),
            "generator_late_p99_ms": float(np.percentile(late, 99) * 1e3),
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
