"""ICI-sharded exact kNN: dataset rows sharded over a mesh axis, local
top-k per shard, ``all_gather`` + k-way merge.

The reference keeps multi-GPU ANN consumers downstream (cuML/cuGraph) and
ships only the comms layer (SURVEY.md §2.5); per the TPU-first design this
framework makes sharded search in-tree. The merge step is the
``knn_merge_parts`` pattern (``neighbors/detail/knn_merge_parts.cuh``)
applied across shards instead of streams.

Works on any 1-axis mesh (real TPU ICI or the 8-device CPU test mesh).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from jax import shard_map

from raft_tpu.core.errors import expects
from raft_tpu.neighbors.brute_force import _NORM_METRICS, _search_impl
from raft_tpu.ops import distance as _dist
from raft_tpu.ops.distance import DistanceType, is_min_close, resolve_metric
from raft_tpu.ops.select_k import merge_parts


def _knn_fn(mesh, axis, k, metric, metric_arg, per, dataset_tile, select_min,
            merge_mode):
    def local_search(ds_local, q):
        rank = jax.lax.axis_index(axis)
        vals, idx = _search_impl(
            ds_local,
            _dist.row_norms(ds_local) if metric in _NORM_METRICS else None,
            q,
            None,
            k=k,
            metric=metric,
            p=metric_arg,
            tile=min(dataset_tile, per),
            select_min=select_min,
            has_filter=False,
        )
        idx = jnp.where(idx >= 0, idx + rank * per, idx)
        if merge_mode == "fused_ring":
            # scan-fused ring: the local block enters the ring engine's
            # own fold (identical here where the block is already k wide,
            # but keeps one engine per merge_mode across the tree)
            from raft_tpu.ops.pallas.ring_topk import scan_ring_topk  # lazy: parallel <-> ops cycle

            return scan_ring_topk(vals, idx, k, select_min=select_min, axis=axis)
        if merge_mode == "ring":
            # stream each shard's [nq, k] block around the ring instead of
            # materialising all n_shards blocks on every shard
            from raft_tpu.ops.pallas.ring_topk import ring_topk  # lazy: parallel <-> ops cycle

            return ring_topk(vals, idx, k, select_min=select_min, axis=axis)
        # Gather each shard's [nq, k] block -> [n_shards, nq, k], flatten the
        # part axis into the candidate axis and merge (knn_merge_parts).
        all_vals = jax.lax.all_gather(vals, axis)  # graft-lint: ignore[gather-merge] — reference engine + ring/fused_ring fallback target
        all_idx = jax.lax.all_gather(idx, axis)
        nq = q.shape[0]
        cat_vals = jnp.moveaxis(all_vals, 0, 1).reshape(nq, -1)
        cat_idx = jnp.moveaxis(all_idx, 0, 1).reshape(nq, -1)
        return merge_parts(cat_vals, cat_idx, k, select_min=select_min)

    fn = shard_map(
        local_search,
        mesh=mesh,
        in_specs=(P(axis, None), P(None, None)),
        out_specs=(P(None, None), P(None, None)),
        check_vma=False,
    )
    return jax.jit(fn)


def sharded_knn(
    mesh: Mesh,
    dataset,
    queries,
    k: int,
    metric=DistanceType.L2SqrtExpanded,
    metric_arg: float = 2.0,
    axis: str = "data",
    dataset_tile: int = 2048,
    merge_mode: str = "auto",
) -> Tuple[jax.Array, jax.Array]:
    """Exact kNN with the dataset row-sharded across ``mesh`` axis ``axis``.

    ``dataset`` [n, d] is split into equal row blocks per device (n must be
    divisible by the axis size — pad upstream if needed); ``queries`` are
    replicated. Each shard computes a local top-k with *global* ids, then
    the per-shard candidates are exchanged and merged. ``merge_mode``
    picks the exchange: ``"ring"`` (ring top-k, O(k) wire per hop),
    ``"gather"`` (all-gather + ``knn_merge_parts``-style merge), or
    ``"auto"`` (ring when sharded, gather fallback on kernel failure).
    Returns replicated ``(distances [nq, k], indices [nq, k])`` identical
    to unsharded search under every engine.
    """
    from raft_tpu.parallel.sharded_ann import _resolve_merge_mode, _run_with_ring_fallback

    metric = resolve_metric(metric)
    dataset = jnp.asarray(dataset)
    queries = jnp.asarray(queries)
    n, d = dataset.shape
    n_shards = mesh.shape[axis]
    expects(n % n_shards == 0, "dataset rows %d not divisible by %d shards", n, n_shards)
    per = n // n_shards
    expects(k <= per, "k=%d larger than per-shard rows %d", k, per)
    select_min = is_min_close(metric)
    mode = _resolve_merge_mode(merge_mode, n_shards)

    ds_sharded = jax.device_put(dataset, NamedSharding(mesh, P(axis, None)))
    q_repl = jax.device_put(queries, NamedSharding(mesh, P(None, None)))
    build = lambda m: _knn_fn(
        mesh, axis, k, metric, metric_arg, per, dataset_tile, select_min, m
    )
    return _run_with_ring_fallback(build, (ds_sharded, q_repl), mode)
