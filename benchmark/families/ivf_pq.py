"""IVF-PQ with exact refine, built by ``raft_tpu.neighbors.ivf_pq.build``
and served as the ``ivf_pq`` algo of ``ServingEngine`` with the raw
corpus on the device for the refine.

Configuration keys: ``index`` (``n_lists``, ``pq_dim``, ``pq_bits``,
``pq_kind``, ``kmeans_n_iters``, ``kmeans_trainset_fraction``,
``list_cap_factor``) and
``search`` (the fields of ``IvfPqSearchParams``).
"""
from __future__ import annotations

import numpy as np

#: the refine reads the raw rows, so the corpus stays on the device
KEEPS_CORPUS = True
SCAN_KERNELS = ("fused_pq_topk",)


def _metric(cfg):
    from raft_tpu.ops.distance import DistanceType

    return {"sqeuclidean": DistanceType.L2Expanded,
            "inner_product": DistanceType.InnerProduct}[cfg["metric"]]


def build(data, cfg):
    from raft_tpu.neighbors import ivf_pq

    ix = cfg["index"]
    return ivf_pq.build(data, ivf_pq.IvfPqIndexParams(
        n_lists=ix["n_lists"], metric=_metric(cfg), pq_dim=ix["pq_dim"], pq_bits=ix["pq_bits"],
        pq_kind=ix["pq_kind"], kmeans_n_iters=ix["kmeans_n_iters"],
        kmeans_trainset_fraction=ix["kmeans_trainset_fraction"],
        list_cap_factor=ix["list_cap_factor"]))


def register(engine, index_id: str, index, data, cfg) -> None:
    from raft_tpu.neighbors import ivf_pq

    engine.register(index_id, "ivf_pq", index,
                    params=ivf_pq.IvfPqSearchParams(**cfg["search"]), dataset=data)


def facts(index, cfg) -> dict:
    """What the roofline readers need once the index is gone."""
    return {
        "centers": np.asarray(index.centers, np.float32),
        "list_sizes": np.asarray(index.list_sizes, np.int64),
        "row_bytes": int(index.codes.shape[2]) * index.codes.dtype.itemsize,
        "pq_dim": int(index.codes.shape[2]) * (2 if index.packed else 1),
        "dim": int(index.dim),
        "stored_dtype": str(index.codes.dtype),
        "n_probes": int(cfg["search"]["n_probes"]),
    }
