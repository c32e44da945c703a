"""The control of a configuration: the plain reference put in the
program's place, computed in the next precision down, and compared by
the same check that decides ``correct``. It has to come out not correct.

    python3 benchmark/control.py --config sift1m-flat --seeds 11,12,13

For each seed, one line on standard output with the control's numbers
beside their limits, and the agreement of the float32 reference with a
float64 numpy one on a sample of queries. The benchmark's own runs never
run this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path[0] = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

from benchmark import check, corpus, harness, reference  # noqa: E402
from benchmark.run import compile_cache  # noqa: E402


def spot_check(data, queries, ref_i, metric: str, k: int, n: int = 16) -> float:
    """Share of the reference's ids that a float64 numpy search over the
    whole corpus also returns, on the first ``n`` queries."""
    import numpy as np

    x = np.asarray(data, np.float64)
    q = np.asarray(queries[:n], np.float64)
    dot = q @ x.T
    score = -dot if metric == "inner_product" else np.sum(x * x, axis=1)[None, :] - 2.0 * dot
    want = np.argpartition(score, k - 1, axis=1)[:, :k]
    return check.recall(ref_i[:n], want)


def control_numbers(cfg: dict, seed: int) -> dict:
    import numpy as np

    k = int(cfg["k"])
    data, queries = corpus.make(cfg, seed)
    t0 = time.perf_counter()
    ref_d, ref_i = reference.topk(data, queries, k, cfg["metric"])
    t_ref = time.perf_counter() - t0
    ctl_d, ctl_i = reference.topk(data, queries, k, cfg["metric"], precision=cfg["control"])
    ans = check.Answers(np.arange(ctl_i.shape[0]), ctl_i, ctl_d)
    numbers = check.compare(ans, ref_i, ref_d, data, queries, cfg["metric"], cfg["limits"], 0)
    return {
        "seed": seed, "control": cfg["control"], "reference_s": t_ref,
        "correct": all(n.ok for n in numbers),
        "numbers": {n.name: {"value": n.value, "limit": n.limit} for n in numbers},
        "reference_vs_float64": spot_check(data, queries, ref_i, cfg["metric"], k),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--cpu-rehearsal", action="store_true")
    args = ap.parse_args(argv)
    if args.cpu_rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
    compile_cache(harness.ROOT)
    bench = harness.load_benchmark()
    cfg = harness.load_config(bench, args.config)
    if args.cpu_rehearsal:
        cfg = harness.overlay(cfg, cfg.get("rehearsal", {}))
    for seed in (int(s) for s in args.seeds.split(",")):
        print(json.dumps(control_numbers(cfg, seed)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
