"""Brute-force (exact) kNN index — analog of ``raft::neighbors::brute_force``.

The reference implements exact search as tiled pairwise distance + select_k
with a k>tile merge path (``neighbors/detail/knn_brute_force.cuh:60``
``tiled_brute_force_knn``, ``:326`` ``brute_force_knn_impl``) behind a
persistent index type holding the dataset and its precomputed norms
(``neighbors/brute_force_types.hpp:49``).

TPU design: the index is a pytree (dataset + f32 squared norms + static
metric), search is a single jitted function that ``lax.scan``s over dataset
tiles computing each [query_batch, tile] distance block on the MXU and
folding a running top-k carry (see :func:`raft_tpu.ops.select_k.running_merge`)
— so peak memory is O(batch * tile), never O(batch * n). Queries are batched
on the host like the reference's query iterator
(``knn_brute_force.cuh:440-480``). Prefiltering consumes
:class:`raft_tpu.core.Bitset` (``sample_filter_types.hpp:27`` analog).
"""
from __future__ import annotations

import dataclasses
import functools
import io
from typing import BinaryIO, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from raft_tpu import obs
from raft_tpu.core import serialize as ser
from raft_tpu.core.bitset import Bitset
from raft_tpu.core.errors import expects
from raft_tpu.core.resources import Resources, ensure_resources
from raft_tpu.ops.distance import (
    DistanceType,
    _EXPANDED,
    _accum_step,
    _expanded_distance,
    is_min_close,
    resolve_metric,
    row_norms,
)
from raft_tpu.ops.select_k import approx_select_k, running_merge, select_k, worst_value
from raft_tpu.utils.math import cdiv

_NORM_METRICS = frozenset(
    {
        DistanceType.L2Expanded,
        DistanceType.L2SqrtExpanded,
        DistanceType.CosineExpanded,
    }
)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class BruteForceIndex:
    """Persistent exact-kNN index (``brute_force_types.hpp:49`` analog)."""

    dataset: jax.Array  # [n_rows, dim]
    norms: Optional[jax.Array]  # [n_rows] f32 squared L2 norms, or None
    metric: DistanceType
    metric_arg: float

    def tree_flatten(self):
        return (self.dataset, self.norms), (self.metric, self.metric_arg)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(dataset=children[0], norms=children[1], metric=aux[0], metric_arg=aux[1])

    @property
    def size(self) -> int:
        return self.dataset.shape[0]

    @property
    def dim(self) -> int:
        return self.dataset.shape[1]


def build(
    dataset,
    metric=DistanceType.L2SqrtExpanded,
    metric_arg: float = 2.0,
    res: Optional[Resources] = None,
) -> BruteForceIndex:
    """Build the index: store the dataset and precompute squared row norms
    for expanded metrics (``brute_force_knn_impl``'s norm precompute,
    ``knn_brute_force.cuh:352-370``)."""
    ensure_resources(res)
    metric = resolve_metric(metric)
    dataset = jnp.asarray(dataset)
    expects(dataset.ndim == 2, "dataset must be [n_rows, dim]")
    norms = row_norms(dataset) if metric in _NORM_METRICS else None
    return BruteForceIndex(dataset=dataset, norms=norms, metric=metric, metric_arg=float(metric_arg))


def _tile_distances(q, q_sqnorm, y_tile, yn_tile, metric: DistanceType, p: float):
    """One [batch, tile] distance block. Expanded metrics ride the MXU with
    precomputed norms; accumulation metrics broadcast directly (the tile is
    small so m*tile*d temp is bounded by the tile size choice)."""
    if metric in _EXPANDED:
        return _expanded_distance(q, y_tile, metric, q_sqnorm, yn_tile)
    from raft_tpu.ops.distance import _accum_combine, _accum_finalize  # local: keep import surface small

    qf = q.astype(jnp.float32)
    yf = y_tile.astype(jnp.float32)
    acc = _accum_step(qf, yf, metric, p)
    return _accum_finalize(acc, metric, p, q.shape[1])


@functools.partial(
    jax.jit, static_argnames=("k", "metric", "p", "tile", "select_min", "has_filter")
)
def _search_impl(
    dataset,
    norms,
    queries,
    filter_mask,
    *,
    k: int,
    metric: DistanceType,
    p: float,
    tile: int,
    select_min: bool,
    has_filter: bool,
):
    n, d = dataset.shape
    qb = queries.shape[0]
    n_tiles = cdiv(n, tile)
    pad = n_tiles * tile - n

    ds = jnp.pad(dataset, ((0, pad), (0, 0))) if pad else dataset
    ds_tiles = ds.reshape(n_tiles, tile, d)
    if norms is not None:
        nm = jnp.pad(norms, (0, pad)) if pad else norms
        nm_tiles = nm.reshape(n_tiles, tile)
    else:
        nm_tiles = jnp.zeros((n_tiles, tile), jnp.float32)
    if has_filter:
        fm = jnp.pad(filter_mask, (0, pad)) if pad else filter_mask
        fm_tiles = fm.reshape(n_tiles, tile)
    else:
        fm_tiles = jnp.ones((n_tiles, tile), bool)

    q_sqnorm = row_norms(queries) if metric in _NORM_METRICS else None
    worst = jnp.float32(worst_value(jnp.float32, select_min))

    init = (
        jnp.full((qb, k), worst, jnp.float32),
        jnp.full((qb, k), -1, jnp.int32),
    )

    def body(carry, inputs):
        acc_v, acc_i = carry
        t, yt, ynt, fmt = inputs
        dist = _tile_distances(queries, q_sqnorm, yt, ynt, metric, p).astype(jnp.float32)
        ids = t * tile + jnp.arange(tile, dtype=jnp.int32)
        valid = (ids < n) & fmt
        dist = jnp.where(valid[None, :], dist, worst)
        tile_ids = jnp.broadcast_to(ids[None, :], dist.shape)
        acc_v, acc_i = running_merge(acc_v, acc_i, dist, tile_ids, select_min=select_min)
        return (acc_v, acc_i), None

    (vals, idx), _ = lax.scan(
        body, init, (jnp.arange(n_tiles, dtype=jnp.int32), ds_tiles, nm_tiles, fm_tiles)
    )
    # Rows knocked out by the filter keep id -1 and the worst sentinel,
    # matching the reference's behavior of returning invalid ids when fewer
    # than k candidates pass the filter.
    return vals, idx


@functools.partial(
    jax.jit,
    static_argnames=("k", "metric", "select_min", "has_filter", "recall_target"),
)
def _search_approx_impl(
    dataset,
    norms,
    queries_blocked,  # [n_blocks, block, d]
    filter_mask,
    *,
    k: int,
    metric: DistanceType,
    select_min: bool,
    has_filter: bool,
    recall_target: float,
):
    """Fused-scan fast path: per query block, one MXU matmul over the FULL
    dataset with the distance epilogue fused into an approximate top-k
    (PartialReduce). XLA never materializes the [block, n] distance matrix,
    so this runs at the matmul roofline — the TPU answer to the reference's
    tiled-GEMM + select_k pipeline (``knn_brute_force.cuh:60``). All query
    blocks ride one ``lax.scan`` inside one jit call: a single device
    dispatch regardless of n_queries."""
    worst = jnp.float32(worst_value(jnp.float32, select_min))

    def step(_, q):
        q_sqnorm = row_norms(q) if metric in _NORM_METRICS else None
        dist = _expanded_distance(q, dataset, metric, q_sqnorm, norms)
        if has_filter:
            dist = jnp.where(filter_mask[None, :], dist, worst)
        v, i = approx_select_k(
            dist, k, select_min=select_min, recall_target=recall_target
        )
        # slots that only found worst-sentinel values (fewer than k rows
        # pass the prefilter) return id -1, matching the exact path
        i = jnp.where(v == worst, -1, i.astype(jnp.int32))
        return None, (v, i)

    _, (vals, idx) = lax.scan(step, None, queries_blocked)
    return vals, idx


def search(
    index: BruteForceIndex,
    queries,
    k: int,
    prefilter: Optional[Bitset] = None,
    query_batch: int = 4096,
    dataset_tile: Optional[int] = None,
    mode: str = "exact",
    recall_target: float = 0.99,
    res: Optional[Resources] = None,
    dataset=None,
    refine_ratio: int = 1,
) -> Tuple[jax.Array, jax.Array]:
    """k-nearest-neighbor search.

    Analog of ``brute_force::search`` (``neighbors/brute_force-inl.cuh``).
    Returns ``(distances [n_queries, k] f32, indices [n_queries, k] i32)``,
    best-first. ``prefilter`` is a keep-bitset over dataset rows.

    ``mode="exact"`` (default) reproduces the reference's exact contract
    (tiled f32 scan + sort-based select). ``mode="approx"`` fuses the
    distance matmul with TPU approximate top-k (see
    :func:`raft_tpu.ops.select_k.approx_select_k`) — orders of magnitude
    faster on large n, returning each true neighbor with probability
    ``recall_target``; available for the expanded metrics
    (L2/IP/cosine).

    ``dataset`` + ``refine_ratio > 1`` adds the integrated refine (same
    contract as ivf_pq/ivf_flat): the scan keeps ``k * refine_ratio``
    candidates that an exact f32 re-rank against ``dataset`` — a device
    array or a tiered ``HostVectorStore`` — cuts back to ``k``. The
    natural pairing is ``mode="approx"`` (or a narrow-dtype index),
    where the re-rank recovers exactness the scan traded away.

    With :mod:`raft_tpu.obs` enabled the call is wrapped in a
    device-synced ``brute_force.search`` span with per-mode counters."""
    if dataset is not None and refine_ratio > 1:
        from raft_tpu.neighbors.refine import check_refine_dataset, refine

        check_refine_dataset(dataset, index.size, "brute_force")
        kk = min(k * refine_ratio, index.size)
        _, cand = search(
            index, queries, kk, prefilter=prefilter, query_batch=query_batch,
            dataset_tile=dataset_tile, mode=mode, recall_target=recall_target, res=res,
        )
        with obs.span("brute_force.search.refine", k=k, candidates=int(kk)) as sp:
            return sp.sync(
                refine(dataset, queries, cand, k, metric=index.metric,
                       metric_arg=index.metric_arg)
            )
    if not obs.active():
        return _search_dispatch(
            index, queries, k, prefilter, query_batch, dataset_tile, mode, recall_target, res
        )
    with obs.span("brute_force.search", k=k, nq=int(np.shape(queries)[0]), mode=mode) as sp:
        return sp.sync(
            _search_dispatch(
                index, queries, k, prefilter, query_batch, dataset_tile, mode, recall_target, res
            )
        )


def _search_dispatch(
    index: BruteForceIndex,
    queries,
    k: int,
    prefilter: Optional[Bitset],
    query_batch: int,
    dataset_tile: Optional[int],
    mode: str,
    recall_target: float,
    res: Optional[Resources],
) -> Tuple[jax.Array, jax.Array]:
    res = ensure_resources(res)
    queries = jnp.asarray(queries)
    expects(queries.ndim == 2, "queries must be [n_queries, dim]")
    expects(queries.shape[1] == index.dim, "query dim %d != index dim %d", queries.shape[1], index.dim)
    n = index.size
    expects(0 < k <= n, "k=%d out of range for index of size %d", k, n)
    if prefilter is not None:
        expects(prefilter.size == n, "prefilter size %d != index size %d", prefilter.size, n)

    metric = index.metric
    select_min = is_min_close(metric)
    nq = queries.shape[0]
    if obs.is_enabled():
        obs.inc("brute_force.search.calls", mode=mode)
        obs.inc("brute_force.search.queries", float(nq))

    if mode == "approx":
        expects(
            metric in _EXPANDED,
            "approx mode needs a matmul-shaped (expanded) metric, got %s",
            metric,
        )
        filter_mask = prefilter.to_mask() if prefilter is not None else None
        block = min(query_batch, nq)
        n_blocks = cdiv(nq, block)
        pad = n_blocks * block - nq
        qp = jnp.pad(queries, ((0, pad), (0, 0))) if pad else queries
        with obs.span("brute_force.search.approx", nq=nq, k=k) as sp:
            v, i = sp.sync(
                _search_approx_impl(
                    index.dataset,
                    index.norms,
                    qp.reshape(n_blocks, block, index.dim),
                    filter_mask,
                    k=k,
                    metric=metric,
                    select_min=select_min,
                    has_filter=filter_mask is not None,
                    recall_target=recall_target,
                )
            )
        v = v.reshape(n_blocks * block, k)[:nq]
        i = i.reshape(n_blocks * block, k)[:nq]
        return v, i
    expects(mode == "exact", "mode must be 'exact' or 'approx', got %r", mode)

    if dataset_tile is None:
        # Size tiles so per-tile temporaries stay within the workspace budget
        # (workspace heuristic analog of knn_brute_force.cuh:73-90
        # faiss::chooseTileSize). Expanded metrics materialize [batch, tile];
        # accumulation metrics broadcast [batch, tile, d] inside
        # _tile_distances, so their budget divides by d as well.
        qb = min(query_batch, nq)
        per_elem = 8 if metric in _EXPANDED else 8 * index.dim
        dataset_tile = max(512, min(n, res.workspace_bytes // (per_elem * max(qb, 1))))
    dataset_tile = int(min(dataset_tile, n))

    filter_mask = prefilter.to_mask() if prefilter is not None else None

    out_v, out_i = [], []
    for start in range(0, nq, query_batch):
        qchunk = queries[start : start + query_batch]
        # Pad the trailing batch so jit sees one batch shape (one compile).
        bpad = 0
        if qchunk.shape[0] < query_batch and nq > query_batch:
            bpad = query_batch - qchunk.shape[0]
            qchunk = jnp.pad(qchunk, ((0, bpad), (0, 0)))
        with obs.span(
            "brute_force.search.exact_batch", nq=qchunk.shape[0], k=k, tile=dataset_tile
        ) as sp:
            v, i = sp.sync(
                _search_impl(
                    index.dataset,
                    index.norms,
                    qchunk,
                    filter_mask,
                    k=k,
                    metric=metric,
                    p=index.metric_arg,
                    tile=dataset_tile,
                    select_min=select_min,
                    has_filter=filter_mask is not None,
                )
            )
        if bpad:
            v, i = v[:-bpad], i[:-bpad]
        out_v.append(v)
        out_i.append(i)
    if len(out_v) == 1:
        return out_v[0], out_i[0]
    return jnp.concatenate(out_v, axis=0), jnp.concatenate(out_i, axis=0)


def knn(
    dataset,
    queries,
    k: int,
    metric=DistanceType.L2SqrtExpanded,
    metric_arg: float = 2.0,
    res: Optional[Resources] = None,
) -> Tuple[jax.Array, jax.Array]:
    """One-shot build+search convenience (``brute_force::knn``,
    ``neighbors/brute_force-inl.cuh:224``)."""
    idx = build(dataset, metric=metric, metric_arg=metric_arg, res=res)
    return search(idx, queries, k, res=res)


# -- serialization ----------------------------------------------------------

_KIND = "brute_force"
_VERSION = 1


def _write_body(index: BruteForceIndex, stream: BinaryIO) -> None:
    ser.serialize_scalar(stream, int(index.metric), "int32")
    ser.serialize_scalar(stream, float(index.metric_arg), "float64")
    ser.serialize_scalar(stream, int(index.norms is not None), "int32")
    ser.serialize_array(stream, index.dataset)
    if index.norms is not None:
        ser.serialize_array(stream, index.norms)


def save(index: BruteForceIndex, stream: BinaryIO) -> None:
    """Serialize (``neighbors/brute_force_serialize.cuh`` analog) in the
    checksummed v4 envelope."""
    body = io.BytesIO()
    _write_body(index, body)
    ser.save_stream(stream, _KIND, _VERSION, body.getvalue())


def load(stream: BinaryIO, res: Optional[Resources] = None) -> BruteForceIndex:
    ensure_resources(res)
    _version, body = ser.load_stream(stream, _KIND)
    metric = DistanceType(ser.deserialize_scalar(body, "int32"))
    metric_arg = float(ser.deserialize_scalar(body, "float64"))
    has_norms = bool(ser.deserialize_scalar(body, "int32"))
    dataset = ser.deserialize_array(body)
    norms = ser.deserialize_array(body) if has_norms else None
    return BruteForceIndex(dataset=dataset, norms=norms, metric=metric, metric_arg=metric_arg)


def save_path(index: BruteForceIndex, path: str) -> str:
    """Atomic (temp-then-rename) checksummed snapshot at ``path``."""
    return ser.atomic_write(path, lambda f: save(index, f))


def load_path(path: str, res: Optional[Resources] = None) -> BruteForceIndex:
    with open(path, "rb") as f:
        return load(f, res=res)


class BatchKQuery:
    """Lazy batched-k query iterator — analog of
    ``neighbors/detail/knn_brute_force_batch_k_query.cuh`` /
    ``neighbors/brute_force-inl.cuh`` ``batch_k_query``: page through a
    query's neighbors ``batch_size`` at a time, searching lazily with a
    growing k (and over-fetching ahead like the reference's 1.5x growth)
    so cheap "first page" consumers never pay for deep ks.

    >>> for batch in BatchKQuery(index, queries, batch_size=32):
    ...     ids, dists = batch.indices, batch.distances   # [nq, 32] each
    """

    class Batch:
        def __init__(self, distances, indices, offset):
            self.distances = distances
            self.indices = indices
            self.offset = offset

    def __init__(self, index: BruteForceIndex, queries, batch_size: int, mode: str = "exact"):
        expects(batch_size >= 1, "batch_size must be >= 1")
        self.index = index
        self.queries = jnp.asarray(queries)
        self.batch_size = int(batch_size)
        self.mode = mode
        self._k = 0  # neighbors fetched so far
        self._dists = None
        self._ids = None

    def _ensure(self, k: int) -> None:
        if k <= self._k:
            return
        # over-fetch 1.5x ahead (the reference grows the same way) but
        # never past the index size
        k_fetch = min(self.index.size, max(k, int(1.5 * k)))
        self._dists, self._ids = search(
            self.index, self.queries, k_fetch, mode=self.mode
        )
        self._k = k_fetch

    def batch(self, i: int) -> "BatchKQuery.Batch":
        """The i-th page of neighbors: ranks [i*bs, (i+1)*bs)."""
        lo = i * self.batch_size
        hi = min(lo + self.batch_size, self.index.size)
        expects(lo < self.index.size, "batch %d past index size", i)
        self._ensure(hi)
        return BatchKQuery.Batch(self._dists[:, lo:hi], self._ids[:, lo:hi], lo)

    def __iter__(self):
        i = 0
        while i * self.batch_size < self.index.size:
            yield self.batch(i)
            i += 1
