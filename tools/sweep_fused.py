"""Dev sweep: fused IVF-Flat scan configs on the 1M x 128 bench shape.

Run EXCLUSIVELY on the TPU (no concurrent processes — tenancy skews
wall-times ~2x). Usage:

    python tools/sweep_fused.py [quick|full]

Prints a QPS/recall table per (merge, extract_every, col_chunk, qt, group,
nprobe) config. Uses the same synthetic clustered data as bench.py.
"""
import dataclasses
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import time

import numpy as np

import jax
import jax.numpy as jnp

from raft_tpu.core.compile_cache import use_compile_cache  # noqa: E402

use_compile_cache()

from raft_tpu.neighbors import brute_force, ivf_flat  # noqa: E402
from raft_tpu.ops.distance import DistanceType  # noqa: E402
from raft_tpu.stats import neighborhood_recall  # noqa: E402

N, D, NQ, K = 1_000_000, 128, 1024, 10


def timed(fn, nrep=3, inner=4):
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
    mode = sys.argv[1] if len(sys.argv) > 1 else "quick"
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

    t0 = time.perf_counter()
    bf = brute_force.build(dataset, metric=DistanceType.L2Expanded)
    _, ei = brute_force.search(bf, queries, K, query_batch=NQ, dataset_tile=262144)
    gt = np.asarray(ei)
    print(f"# gt in {time.perf_counter()-t0:.1f}s", flush=True)

    if mode == "quick":
        plans = {
            2.0: [
                # (npr, pf, G, qt, merge, ee, cc)   baseline first
                (20, 32, 4, 128, "seg4", 0, 0),
                (20, 32, 4, 128, "bank4", 0, 0),
                (20, 32, 4, 128, "bank8", 0, 1024),
                (20, 32, 8, 128, "bank8", 0, 1024),
            ],
            1.1: [
                (20, 32, 4, 128, "seg4", 0, 0),
                (20, 32, 4, 128, "bank4", 0, 0),
                (20, 32, 4, 128, "bank8", 0, 1024),
                (20, 32, 8, 128, "bank8", 0, 1024),
                (20, 64, 8, 256, "bank8", 0, 1024),
                (20, 32, 16, 128, "bank8", 0, 1024),
                (30, 32, 8, 128, "bank8", 0, 1024),
            ],
        }
    else:
        plans = {
            1.1: [
                (30, 32, 8, 128, "bank8", 0, 1024),
                (30, 24, 8, 256, "bank4", 0, 1024),
                (30, 16, 8, 256, "bank4", 0, 1024),
                (20, 24, 8, 256, "bank4", 0, 1024),
                (30, 24, 8, 512, "bank4", 0, 1024),
                (20, 32, 8, 512, "bank4", 0, 1024),
                (30, 16, 8, 512, "bank4", 0, 1024),
                (50, 16, 8, 256, "bank4", 0, 1024),
            ],
        }

    from _artifact import Recorder

    art = Recorder("sweep_fused", {"n": N, "dim": D, "nq": NQ, "k": K, "mode": mode})
    print(f"# {'config':60s} {'qps':>10s} {'recall':>8s}")
    for cap, configs in plans.items():
        t0 = time.perf_counter()
        fidx = ivf_flat.build(
            dataset,
            ivf_flat.IvfFlatIndexParams(
                n_lists=1024, kmeans_n_iters=10, kmeans_trainset_fraction=0.1,
                list_cap_factor=cap,
            ),
        )
        float(jnp.sum(fidx.list_sizes))
        print(
            f"# cap={cap} build in {time.perf_counter()-t0:.1f}s  max_list={fidx.max_list}",
            flush=True,
        )
        bf16_idx = dataclasses.replace(
            fidx, list_data=fidx.list_data.astype(jnp.bfloat16)
        )
        for npr, pf, g, qt, merge, ee, cc in configs:
            sp = ivf_flat.IvfFlatSearchParams(
                n_probes=npr, fused_qt=qt, fused_probe_factor=pf, fused_group=g,
                fused_merge=merge, fused_precision="default",
                fused_extract_every=ee, fused_col_chunk=cc,
            )
            tag = f"cap={cap} npr={npr} pf={pf} G={g} qt={qt} {merge} ee={ee} cc={cc}"
            try:
                dt, (v, i) = timed(
                    lambda sp=sp: ivf_flat.search(bf16_idx, queries, K, sp, mode="fused")
                )
            except Exception as e:  # noqa: BLE001
                print(f"# {tag:60s} FAILED {type(e).__name__}: {str(e)[:120]}", flush=True)
                continue
            rec = float(neighborhood_recall(np.asarray(i)[:, :K], gt))  # graft-lint: ignore[sync-transfer-in-loop] — post-timed recall readout
            print(f"# {tag:60s} {NQ/dt:>10,.0f} {rec:>8.4f}", flush=True)
            art.add({"config": tag, "qps": round(NQ / dt, 1), "recall": round(rec, 4)})

    art.set_context(device=str(jax.devices()[0]))


if __name__ == "__main__":
    main()
