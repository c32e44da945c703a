"""Recall parity study: additive nibble codebooks vs kmeans-256 vs 4-bit
at equal pq_dim and n_probes (VERDICT r4 weak #4 — the nibble book's
recall-vs-default parity was unproven beyond smoke scale).

Runs anywhere (CPU ok — recall doesn't need the chip; only wall-times
do). Writes an incremental artifact under ``artifacts/tpu/``.

    python tools/compare_nibble.py [n_rows]
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

import jax

if os.environ.get("RAFT_TPU_FORCE_CPU"):
    # runs before the first backend use
    jax.config.update("jax_platforms", "cpu")
import jax.numpy as jnp

from raft_tpu.neighbors import brute_force, ivf_pq
from raft_tpu.neighbors.refine import refine
from raft_tpu.ops.distance import DistanceType
from raft_tpu.stats import neighborhood_recall

D, NQ, K = 64, 256, 10
N_CENTERS = 500


def main():
    n = int(sys.argv[1]) if len(sys.argv) > 1 else 300_000
    key = jax.random.PRNGKey(7)
    kc, ka, kb, kq1, kq2 = jax.random.split(key, 5)
    centers = jax.random.normal(kc, (N_CENTERS, D), jnp.float32)
    dataset = centers[jax.random.randint(ka, (n,), 0, N_CENTERS)] + jax.random.normal(
        kb, (n, D), jnp.float32
    )
    queries = centers[jax.random.randint(kq1, (NQ,), 0, N_CENTERS)] + jax.random.normal(
        kq2, (NQ, D), jnp.float32
    )
    bf = brute_force.build(dataset, metric=DistanceType.L2Expanded)
    _, ei = brute_force.search(bf, queries, K)
    gt = np.asarray(ei)
    print("# gt done", flush=True)

    from _artifact import Recorder

    art = Recorder(
        "nibble_vs_kmeans256",
        {"n": n, "dim": D, "nq": NQ, "k": K,
         "device": str(jax.devices()[0]),
         "note": "recall parity at equal pq_dim/n_probes; scan path (no kernel noise)"},
    )

    n_lists = max(64, int(n ** 0.5 / 2) // 64 * 64)
    variants = {
        "kmeans256": dict(pq_dim=16, pq_bits=8),
        "nibble": dict(pq_dim=16, pq_bits=8, pq_kind="nibble"),
        "pq4": dict(pq_dim=16, pq_bits=4),
    }
    idxs = {}
    for name, kw in variants.items():
        idxs[name] = ivf_pq.build(
            dataset,
            ivf_pq.IvfPqIndexParams(
                n_lists=n_lists, kmeans_n_iters=10, kmeans_trainset_fraction=0.2,
                list_cap_factor=1.1, **kw,
            ),
        )
        print(f"# built {name}", flush=True)

    for npr in (10, 20, 40):
        for name, idx in idxs.items():
            sp = ivf_pq.IvfPqSearchParams(n_probes=npr)
            _, i = ivf_pq.search(idx, queries, K, sp, mode="scan")
            rec = float(neighborhood_recall(np.asarray(i), gt))  # graft-lint: ignore[sync-transfer-in-loop] — recall measurement; throughput not at stake
            _, cand = ivf_pq.search(idx, queries, 4 * K, sp, mode="scan")
            _, ri = refine(dataset, queries, cand, K, metric=DistanceType.L2Expanded)
            rrec = float(neighborhood_recall(np.asarray(ri), gt))  # graft-lint: ignore[sync-transfer-in-loop] — recall measurement; throughput not at stake
            row = {"variant": name, "n_probes": npr,
                   "recall": round(rec, 4), "recall_refine4x": round(rrec, 4),
                   "code_bytes_per_row": int(idxs[name].codes.shape[-1])}
            art.add(row)
            print(f"# {name:10s} npr={npr:3d} recall={rec:.4f} refine4x={rrec:.4f}", flush=True)


if __name__ == "__main__":
    main()
