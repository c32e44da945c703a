"""Dev sweep: CAGRA build (ivf_pq vs nn_descent path) + search configs at
1M x 128. Run EXCLUSIVELY on the TPU: python tools/sweep_cagra.py
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import time

import numpy as np

import jax
import jax.numpy as jnp

from raft_tpu.core.compile_cache import use_compile_cache  # noqa: E402

use_compile_cache()

from raft_tpu.neighbors import brute_force, cagra  # noqa: E402
from raft_tpu.ops.distance import DistanceType  # noqa: E402
from raft_tpu.stats import neighborhood_recall  # noqa: E402

N, D, NQ, K = 1_000_000, 128, 1024, 10


def timed(fn, nrep=3, inner=2):
    out = fn()
    float(jnp.sum(out[0]))
    best = float("inf")
    for _ in range(nrep):
        t0 = time.perf_counter()
        for _ in range(inner):
            out = fn()
        float(jnp.sum(out[0]))
        best = min(best, (time.perf_counter() - t0) / inner)
    return best, out


def main():
    key = jax.random.PRNGKey(1234)
    kc, ka, kb, kq1, kq2 = jax.random.split(key, 5)
    centers = jax.random.normal(kc, (1000, D), jnp.float32)
    dataset = centers[jax.random.randint(ka, (N,), 0, 1000)] + jax.random.normal(
        kb, (N, D), jnp.float32
    )
    queries = centers[jax.random.randint(kq1, (NQ,), 0, 1000)] + jax.random.normal(
        kq2, (NQ, D), jnp.float32
    )
    float(jnp.sum(dataset[0]))

    bf = brute_force.build(dataset, metric=DistanceType.L2Expanded)
    _, ei = brute_force.search(bf, queries, K, query_batch=NQ, dataset_tile=262144)
    gt = np.asarray(ei)
    print("# gt done", flush=True)

    t0 = time.perf_counter()
    cidx = cagra.build(
        dataset,
        cagra.CagraIndexParams(
            intermediate_graph_degree=32, graph_degree=16, build_algo=cagra.IVF_PQ
        ),
    )
    float(jnp.sum(cidx.graph[0].astype(jnp.float32)))
    build_s = round(time.perf_counter() - t0, 1)
    print(f"# ivf_pq-path build: {build_s}s", flush=True)

    from _artifact import Recorder

    art = Recorder("sweep_cagra", {"n": N, "dim": D, "nq": NQ, "k": K})
    print(f"# {'config':44s} {'qps':>10s} {'recall':>8s}")
    for itopk, w, dedup in [
        (128, 4, "sort"),
        (128, 4, "post"),
        (160, 4, "post"),
        (96, 4, "post"),
        (64, 4, "post"),
        (128, 8, "post"),
        (64, 2, "post"),
    ]:
        sp = cagra.CagraSearchParams(itopk_size=itopk, search_width=w, dedup=dedup)
        tag = f"itopk={itopk} w={w} dedup={dedup}"
        try:
            dt, (v, i) = timed(
                lambda sp=sp: cagra.search(cidx, queries, K, sp)
            )
        except Exception as e:  # noqa: BLE001
            print(f"# {tag:44s} FAILED {type(e).__name__}: {str(e)[:100]}", flush=True)
            continue
        rec = float(neighborhood_recall(np.asarray(i)[:, :K], gt))  # graft-lint: ignore[sync-transfer-in-loop] — post-timed recall readout
        print(f"# {tag:44s} {NQ/dt:>10,.0f} {rec:>8.4f}", flush=True)
        art.add({"config": tag, "qps": round(NQ / dt, 1), "recall": round(rec, 4)})

    # small-batch latency rows (plan_search_params schedule)
    for bq in (1, 10):
        sp = cagra.plan_search_params(bq, K, N, cagra.CagraSearchParams(itopk_size=128, dedup="post"))
        try:
            dt, (v, i) = timed(lambda sp=sp, bq=bq: cagra.search(cidx, queries[:bq], K, sp))
        except Exception as e:  # noqa: BLE001
            print(f"# latency batch={bq} FAILED {type(e).__name__}", flush=True)
            continue
        rec = float(neighborhood_recall(np.asarray(i)[:, :K], gt[:bq]))  # graft-lint: ignore[sync-transfer-in-loop] — post-timed recall readout
        print(f"# latency batch={bq:<3d} {dt*1e3:8.2f} ms  recall={rec:.4f}", flush=True)
        art.add({"config": f"latency batch={bq} w={sp.search_width}",
                 "latency_ms": round(dt * 1e3, 2), "recall": round(rec, 4)})

    art.set_context(build_seconds=build_s, device=str(jax.devices()[0]))


if __name__ == "__main__":
    main()
