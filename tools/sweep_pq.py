"""Dev sweep: fused IVF-PQ scan configs on the 1M x 128 bench shape.

Run EXCLUSIVELY on the TPU. Usage: python tools/sweep_pq.py
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

from raft_tpu.neighbors import brute_force, ivf_pq  # noqa: E402
from raft_tpu.neighbors.refine import refine  # noqa: E402
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

    variants = {
        "p4_d32": dict(pq_dim=32, pq_bits=4),
        "nib_d32": dict(pq_dim=32, pq_bits=8, pq_kind="nibble"),
        "p4_d64": dict(pq_dim=64, pq_bits=4),
    }
    idxs = {}
    for name, kw in variants.items():
        t0 = time.perf_counter()
        idxs[name] = ivf_pq.build(
            dataset,
            ivf_pq.IvfPqIndexParams(
                n_lists=1024, kmeans_n_iters=10, kmeans_trainset_fraction=0.1,
                list_cap_factor=1.1, **kw,
            ),
        )
        float(jnp.sum(idxs[name].list_sizes))
        code_mb = idxs[name].codes.size / 1e6
        print(f"# build {name}: {time.perf_counter()-t0:.1f}s  codes={code_mb:.0f}MB "
              f"max_list={idxs[name].max_list}", flush=True)

    from _artifact import Recorder

    art = Recorder("sweep_pq", {"n": N, "dim": D, "nq": NQ, "k": K})
    print(f"# {'config':52s} {'qps':>10s} {'recall':>8s}")
    for name, npr, pf, g, rr in [
        ("p4_d32", 30, 32, 8, 4),
        ("p4_d32", 30, 32, 8, 8),
        ("p4_d32", 30, 32, 16, 8),
        ("nib_d32", 30, 32, 8, 4),
        ("nib_d32", 30, 32, 8, 8),
        ("nib_d32", 20, 32, 8, 4),
        ("nib_d32", 30, 32, 16, 4),
        ("p4_d64", 30, 32, 8, 4),
        ("p4_d64", 30, 32, 16, 4),
    ]:
        idx = idxs[name]
        sp = ivf_pq.IvfPqSearchParams(
            n_probes=npr, fused_qt=128, fused_probe_factor=pf, fused_group=g
        )

        def run(sp=sp, idx=idx, rr=rr):
            _, cand = ivf_pq.search(idx, queries, rr * K, sp, mode="fused")
            return refine(dataset, queries, cand, K, metric=DistanceType.L2Expanded)

        tag = f"{name} npr={npr} pf={pf} G={g} refine={rr}x"
        try:
            dt, (v, i) = timed(run)
        except Exception as e:  # noqa: BLE001
            print(f"# {tag:52s} FAILED {type(e).__name__}: {str(e)[:100]}", flush=True)
            continue
        rec = float(neighborhood_recall(np.asarray(i)[:, :K], gt))  # graft-lint: ignore[sync-transfer-in-loop] — post-timed recall readout
        print(f"# {tag:52s} {NQ/dt:>10,.0f} {rec:>8.4f}", flush=True)
        art.add({"config": tag, "qps": round(NQ / dt, 1), "recall": round(rec, 4)})

    art.set_context(device=str(jax.devices()[0]))


if __name__ == "__main__":
    main()
