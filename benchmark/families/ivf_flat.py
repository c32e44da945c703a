"""IVF-Flat, built by ``raft_tpu.neighbors.ivf_flat.build`` and served as
the ``ivf_flat`` algo of ``ServingEngine``.

Configuration keys: ``index`` (``n_lists``, ``kmeans_n_iters``,
``kmeans_trainset_fraction``, ``list_cap_factor``, ``list_dtype``) and
``search`` (the fields of ``IvfFlatSearchParams``).
"""
from __future__ import annotations

import dataclasses

import numpy as np

#: the served path reads only the lists, so the raw corpus is dropped
KEEPS_CORPUS = False
#: the Pallas kernel a traced run must show, by its kernel name
SCAN_KERNELS = ("fused_list_topk",)


def _metric(cfg):
    from raft_tpu.ops.distance import DistanceType

    return {"sqeuclidean": DistanceType.L2Expanded,
            "inner_product": DistanceType.InnerProduct}[cfg["metric"]]


def build(data, cfg):
    import jax.numpy as jnp

    from raft_tpu.neighbors import ivf_flat

    ix = cfg["index"]
    index = ivf_flat.build(data, ivf_flat.IvfFlatIndexParams(
        n_lists=ix["n_lists"], metric=_metric(cfg), kmeans_n_iters=ix["kmeans_n_iters"],
        kmeans_trainset_fraction=ix["kmeans_trainset_fraction"],
        list_cap_factor=ix["list_cap_factor"]))
    if ix.get("list_dtype"):
        index = dataclasses.replace(index, list_data=index.list_data.astype(jnp.dtype(ix["list_dtype"])))
    return index


def register(engine, index_id: str, index, data, cfg) -> None:
    from raft_tpu.neighbors import ivf_flat

    engine.register(index_id, "ivf_flat", index,
                    params=ivf_flat.IvfFlatSearchParams(**cfg["search"]))


def facts(index, cfg) -> dict:
    """What the roofline readers need once the index is gone."""
    row_bytes = index.dim * index.list_data.dtype.itemsize
    if index.list_norms is not None:
        row_bytes += 4
    return {
        "centers": np.asarray(index.centers, np.float32),
        "list_sizes": np.asarray(index.list_sizes, np.int64),
        "row_bytes": row_bytes,
        "dim": int(index.dim),
        "stored_dtype": str(index.list_data.dtype),
        "n_probes": int(cfg["search"]["n_probes"]),
    }
