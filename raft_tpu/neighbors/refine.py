"""Candidate re-ranking with exact distances — analog of
``raft::neighbors::refine`` (``neighbors/refine-inl.cuh:70,92``).

Given approximate candidate lists (e.g. from IVF-PQ or CAGRA), recompute
exact distances between each query and its candidates and keep the best k.
On TPU this is a batched gather + one small einsum per query block — XLA
turns the [n_queries, n_candidates, dim] contraction into MXU work. The
whole body runs under one ``jit`` so the gather feeds the distance matmul
and the top-k inside a single device program (eager dispatch per op costs
several HBM round-trips and a host dispatch per op).

Two gather tiers share one re-rank core (:func:`_exact_rerank`):

* device-resident ``dataset`` — the gather is ``dataset[ids]`` inside the
  jit (:func:`_refine_impl`), the original all-in-HBM path;
* host-resident ``dataset`` (a :class:`raft_tpu.tiered.HostVectorStore`)
  — the gather is an ``np.take`` on the host, the ``[batch, n_cand, dim]``
  slab is ``device_put`` and re-ranked by :func:`_refine_gathered_impl`.

Both paths run the identical f32 arithmetic on identical gathered values,
so tiered results are bit-identical to the all-resident ones (asserted in
``tests/test_tiered.py``).
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from raft_tpu import obs
from raft_tpu.core.errors import expects
from raft_tpu.neighbors.brute_force import _tile_distances, _NORM_METRICS
from raft_tpu.ops.distance import DistanceType, is_min_close, resolve_metric, row_norms
from raft_tpu.ops.select_k import select_k, worst_value


def is_host_dataset(dataset) -> bool:
    """True for host-tier vector stores (duck-typed so this module never
    imports :mod:`raft_tpu.tiered`, which imports it)."""
    return getattr(dataset, "is_host_tier", False)


def check_refine_dataset(dataset, index_size: int, algo: str = "index") -> None:
    """Validate a refine ``dataset`` against the index it re-ranks for —
    *before* any scan runs, so a short dataset fails up front with a
    typed :class:`~raft_tpu.core.errors.LogicError` naming the index
    size instead of deep inside the candidate gather."""
    shape = np.shape(dataset) if not hasattr(dataset, "shape") else tuple(dataset.shape)
    expects(
        len(shape) == 2,
        "%s refine dataset must be [n_rows, dim], got shape %s", algo, shape,
    )
    rows = int(shape[0])
    expects(
        rows >= index_size,
        "%s refine dataset has %d rows but the index holds %d vectors — "
        "every stored id must be gatherable; pass the full build dataset "
        "(or a HostVectorStore over it)",
        algo, rows, index_size,
    )


def _exact_rerank(
    cand_vecs, queries, candidates, valid, *, k: int, metric: DistanceType, metric_arg: float
) -> Tuple[jax.Array, jax.Array]:
    """Shared re-rank core: exact per-candidate distances + top-k.

    ``cand_vecs`` [nq, n_cand, d] is the gathered candidate slab —
    whichever tier produced it, the arithmetic from here on is identical,
    which is what makes tiered and resident results bit-equal."""
    qf = queries.astype(jnp.float32)
    cf = cand_vecs.astype(jnp.float32)

    select_min = is_min_close(metric)
    worst = jnp.float32(worst_value(jnp.float32, select_min))

    # Per-query exact distance to each candidate, via the same per-metric
    # bodies as brute force (vmapped over the query axis).
    q_sqnorm = row_norms(qf) if metric in _NORM_METRICS else None

    def one_query(q, cands, qn):
        qn_arr = None if qn is None else qn[None]
        d = _tile_distances(
            q[None, :],
            qn_arr,
            cands,
            None if qn is None else row_norms(cands),
            metric,
            metric_arg,
        )
        return d[0]

    if q_sqnorm is None:
        dists = jax.vmap(lambda q, c: one_query(q, c, None))(qf, cf)
    else:
        dists = jax.vmap(lambda q, c, n: one_query(q, c, n))(qf, cf, q_sqnorm)

    dists = jnp.where(valid, dists.astype(jnp.float32), worst)
    vals, pos = select_k(dists, k, select_min=select_min)
    idx = jnp.take_along_axis(candidates, pos, axis=1)
    # Restore -1 for slots that selected an invalid (padded) candidate.
    idx = jnp.where(jnp.take_along_axis(valid, pos, axis=1), idx, -1)
    return vals, idx


@functools.partial(jax.jit, static_argnames=("k", "metric", "metric_arg"))
def _refine_impl(
    dataset, queries, candidates, *, k: int, metric: DistanceType, metric_arg: float
) -> Tuple[jax.Array, jax.Array]:
    valid = candidates >= 0
    safe_ids = jnp.where(valid, candidates, 0)
    cand_vecs = dataset[safe_ids]  # [nq, n_cand, d]
    return _exact_rerank(
        cand_vecs, queries, candidates, valid, k=k, metric=metric, metric_arg=metric_arg
    )


@functools.partial(jax.jit, static_argnames=("k", "metric", "metric_arg"))
def _refine_gathered_impl(
    cand_vecs, queries, candidates, *, k: int, metric: DistanceType, metric_arg: float
) -> Tuple[jax.Array, jax.Array]:
    """Re-rank a pre-gathered slab (host-tier fetch): the gather already
    substituted row 0 for invalid slots exactly like :func:`_refine_impl`."""
    valid = candidates >= 0
    return _exact_rerank(
        cand_vecs, queries, candidates, valid, k=k, metric=metric, metric_arg=metric_arg
    )


def refine(
    dataset,
    queries,
    candidates,
    k: int,
    metric=DistanceType.L2SqrtExpanded,
    metric_arg: float = 2.0,
    query_batch: int = 0,
) -> Tuple[jax.Array, jax.Array]:
    """Re-rank ``candidates`` [n_queries, n_cand] (i32 ids into ``dataset``,
    -1 = invalid) down to the top ``k`` by exact distance.

    ``dataset`` may be a device array (all-in-HBM gather) or a
    :class:`raft_tpu.tiered.HostVectorStore` (host-tier ``np.take`` +
    ``device_put`` slab per batch); results are bit-identical.

    ``query_batch``: 0 = auto — cap the gathered [batch, n_cand, dim] f32
    temporary at ~1 GB (CAGRA's graph build refines the WHOLE dataset as
    queries; unbatched that would allocate n * n_cand * dim * 4 bytes).

    With :mod:`raft_tpu.obs` enabled the call is wrapped in a
    device-synced ``refine.refine`` span with call/query counters and a
    candidates-per-query histogram.

    Returns ``(distances [n_queries, k], indices [n_queries, k])``.
    """
    if not obs.is_enabled():
        return _refine_dispatch(
            dataset, queries, candidates, k, metric, metric_arg, query_batch
        )
    nq = int(np.shape(queries)[0])
    n_cand = int(np.shape(candidates)[1]) if np.ndim(candidates) == 2 else 0
    obs.inc("refine.refine.calls")
    obs.inc("refine.refine.queries", float(nq))
    obs.observe("refine.refine.candidates_per_query", float(n_cand))
    with obs.span("refine.refine", k=k, nq=nq, candidates=n_cand) as sp:
        return sp.sync(
            _refine_dispatch(
                dataset, queries, candidates, k, metric, metric_arg, query_batch
            )
        )


def _refine_dispatch(
    dataset,
    queries,
    candidates,
    k: int,
    metric,
    metric_arg: float,
    query_batch: int,
) -> Tuple[jax.Array, jax.Array]:
    metric = resolve_metric(metric)
    host_tier = is_host_dataset(dataset)
    if not host_tier:
        dataset = jnp.asarray(dataset)
    queries = jnp.asarray(queries)
    candidates = jnp.asarray(candidates, jnp.int32)
    expects(candidates.ndim == 2, "candidates must be [n_queries, n_candidates]")
    expects(candidates.shape[0] == queries.shape[0], "queries/candidates row mismatch")
    n_cand = candidates.shape[1]
    expects(0 < k <= n_cand, "k=%d out of range for %d candidates", k, n_cand)

    nq = queries.shape[0]
    if query_batch <= 0:
        per_q = max(1, n_cand * dataset.shape[1] * 4)
        query_batch = max(256, (1 << 30) // per_q)

    def one_batch(q, c):
        if host_tier:
            slab = dataset.gather(np.asarray(c))
            return _refine_gathered_impl(
                slab, q, c, k=k, metric=metric, metric_arg=metric_arg
            )
        return _refine_impl(dataset, q, c, k=k, metric=metric, metric_arg=metric_arg)

    if nq > query_batch:
        out_v, out_i = [], []
        for s in range(0, nq, query_batch):
            cnt = min(query_batch, nq - s)
            if cnt < query_batch:  # pad the tail to keep one compiled shape
                q = jnp.pad(queries[s : s + cnt], ((0, query_batch - cnt), (0, 0)))
                c = jnp.pad(
                    candidates[s : s + cnt],
                    ((0, query_batch - cnt), (0, 0)),
                    constant_values=-1,
                )
            else:
                q, c = queries[s : s + cnt], candidates[s : s + cnt]
            v, i = one_batch(q, c)
            out_v.append(v[:cnt])
            out_i.append(i[:cnt])
        return jnp.concatenate(out_v, axis=0), jnp.concatenate(out_i, axis=0)

    return one_batch(queries, candidates)
