"""IVF-Flat index — analog of ``raft::neighbors::ivf_flat``.

Reference: index layout ``neighbors/ivf_flat_types.hpp:44-164`` (per-list
interleaved groups of 32 rows x veclen chunks), build
``neighbors/detail/ivf_flat_build.cuh:382-460``, search
``neighbors/detail/ivf_flat_search-inl.cuh:271`` (coarse select at ``:145``),
fused scan+top-k kernel ``detail/ivf_flat_interleaved_scan-inl.cuh:687``.

TPU-first redesign (SURVEY.md §7 hard part (b) — ragged lists vs dense
tiles):

* Lists live in ONE dense padded tensor ``list_data [n_lists, max_list, d]``
  with parallel ``list_indices [n_lists, max_list]`` (-1 pads) and
  ``list_sizes [n_lists]`` — the CUDA 32-row interleave is replaced by
  sublane-padded dense tiles XLA can tile onto the MXU/VPU directly, and the
  gather of a probed list is one dynamic-slice.
* Coarse quantization = pairwise distance to centers + select_k, exactly the
  reference's ``select_clusters`` structure.
* Fine search ``lax.scan``s over the ``n_probes`` axis: each step gathers
  one probed list per query, computes the [batch, max_list] distance block
  (dot via einsum on the MXU; norms pre-stored), masks padded slots /
  filtered ids, and folds a running top-k — the interleaved_scan + fused
  top-k kernel expressed as scan + merge.
* Balanced k-means training keeps ``max_list`` close to the mean list size,
  bounding the padding waste the dense layout costs.

Supported metrics: L2Expanded, L2SqrtExpanded, InnerProduct, CosineExpanded
(the set the reference's IVF-Flat accepts).
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
from raft_tpu.cluster import kmeans_balanced
from raft_tpu.cluster.kmeans_balanced import BalancedKMeansParams
from raft_tpu.core import serialize as ser
from raft_tpu.core.bitset import Bitset
from raft_tpu.core.errors import expects
from raft_tpu.core.resources import Resources, ensure_resources
from raft_tpu.neighbors.ivf_common import pack_rows as _pack, topk_labels as _topk_labels
from raft_tpu.ops.distance import DistanceType, is_min_close, resolve_metric, row_norms
from raft_tpu.ops.fused_1nn import min_cluster_and_distance
from raft_tpu.ops.select_k import running_merge, select_k, worst_value

_SUPPORTED = (
    DistanceType.L2Expanded,
    DistanceType.L2SqrtExpanded,
    DistanceType.InnerProduct,
    DistanceType.CosineExpanded,
)


@dataclasses.dataclass
class IvfFlatIndexParams:
    """``ivf_flat::index_params`` analog (``neighbors/ivf_flat_types.hpp:44``)."""

    n_lists: int = 1024
    metric: DistanceType = DistanceType.L2Expanded
    kmeans_n_iters: int = 20
    kmeans_trainset_fraction: float = 0.5
    seed: int = 0
    # Dense-layout list capacity: lists are capped at
    # ``cap_factor * n / n_lists`` rows; overflow spills to the row's
    # next-nearest lists (see ``ivf_common.assign_slots``). 0 disables
    # capping (max_list = largest cluster, as the ragged reference layout).
    list_cap_factor: float = 2.0


@dataclasses.dataclass
class IvfFlatSearchParams:
    """``ivf_flat::search_params`` analog (``ivf_flat_types.hpp:155``).

    The ``fused_*`` knobs tune the Pallas fused scan (``mode="fused"``):
    query-tile height, tile probe-table size (``fused_probe_factor *
    n_probes`` lists per tile), top-k merge strategy (``"seg"``/``"seg1"``/``"seg4"``
    banked lane-group PartialReduce, ``"bank"``/``"bankN"`` persistent
    min-merge buffer with periodic extraction — the fast path — or
    ``"exact"``), and MXU precision for the distance matmul
    (``"highest"`` = f32-exact passes, ``"default"`` = fast)."""

    n_probes: int = 20
    # qt/probe_factor/group/merge = the measured 1M x 128 operating point
    # on TPU v5e (see docs/tpu_design.md); group rounds down to a divisor
    # of n_lists and the probe table caps at the unit count, so they
    # degrade gracefully on small indexes. precision stays "highest"
    # (f32-exact distances) by default — the bench trades it for speed
    # explicitly with "default". bank8 + col_chunk=1024 replaced seg4 in
    # round 4: per-step min-merge into a persistent 8x128-lane buffer with
    # one extraction per tile is both faster and slightly higher-recall
    # than per-step extraction at these shapes.
    fused_qt: int = 128
    fused_probe_factor: int = 32
    fused_group: int = 8  # lists per DMA block / probe-table entry
    fused_merge: str = "bank8"
    fused_precision: str = "highest"
    # bank-merge extras: extraction period (0 = once per tile) and score
    # column-chunk rows (0 = whole DMA block at once)
    fused_extract_every: int = 0
    fused_col_chunk: int = 1024
    # Exact re-rank depth: search keeps k * refine_ratio candidates and
    # re-scores them against the raw dataset (refine.refine) when search()
    # is given one — the escape hatch that recovers exactness when
    # list_data is stored in a narrow dtype (bf16/int8) or the scan ran
    # an approximate top-k. 1 = off (the all-resident default).
    refine_ratio: int = 1


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class IvfFlatIndex:
    """Dense-padded inverted-file index (``ivf_flat_types.hpp:129`` analog)."""

    centers: jax.Array  # [n_lists, d] f32
    list_data: jax.Array  # [n_lists, max_list, d] (dataset dtype)
    list_indices: jax.Array  # [n_lists, max_list] i32, -1 = empty slot
    list_sizes: jax.Array  # [n_lists] i32
    list_norms: Optional[jax.Array]  # [n_lists, max_list] f32 sq norms (L2/cos)
    metric: DistanceType
    size: int  # total indexed rows
    list_cap_factor: float = 2.0  # build-time cap; honored by extend()
    # PCA-bisection spatial rank of the centers (see
    # raft_tpu.ops.pallas.spatial_center_rank); used by the fused Pallas
    # search path to form probe-coherent query tiles. Optional: computed at
    # build, regenerated on demand for indexes loaded from old files.
    center_rank: Optional[jax.Array] = None

    def tree_flatten(self):
        return (
            (
                self.centers,
                self.list_data,
                self.list_indices,
                self.list_sizes,
                self.list_norms,
                self.center_rank,
            ),
            (self.metric, self.size, self.list_cap_factor),
        )

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(
            *children[:5],
            metric=aux[0],
            size=aux[1],
            list_cap_factor=aux[2],
            center_rank=children[5],
        )

    @property
    def n_lists(self) -> int:
        return self.centers.shape[0]

    @property
    def dim(self) -> int:
        return self.centers.shape[1]

    @property
    def max_list(self) -> int:
        return self.list_data.shape[1]


def build(
    dataset,
    params: Optional[IvfFlatIndexParams] = None,
    res: Optional[Resources] = None,
    **kwargs,
) -> IvfFlatIndex:
    """Train centers with balanced k-means and pack the inverted lists
    (``ivf_flat::build``, ``detail/ivf_flat_build.cuh:382``)."""
    res = ensure_resources(res)
    if params is None:
        params = IvfFlatIndexParams(**kwargs)
    metric = resolve_metric(params.metric)
    expects(metric in _SUPPORTED, "IVF-Flat does not support metric %s", metric)
    dataset = jnp.asarray(dataset)
    expects(dataset.ndim == 2, "dataset must be [n_rows, dim]")
    n, d = dataset.shape
    n_lists = min(params.n_lists, n)

    train_n = max(n_lists, int(n * params.kmeans_trainset_fraction))
    ds_f32 = dataset.astype(jnp.float32)
    trainset = ds_f32
    if train_n < n:
        rng = np.random.default_rng(params.seed)
        trainset = ds_f32[jnp.asarray(rng.permutation(n)[:train_n])]

    assign_data = ds_f32
    if metric == DistanceType.CosineExpanded:
        trainset = trainset / jnp.maximum(jnp.linalg.norm(trainset, axis=1, keepdims=True), 1e-12)
        assign_data = ds_f32 / jnp.maximum(jnp.linalg.norm(ds_f32, axis=1, keepdims=True), 1e-12)

    centers = kmeans_balanced.fit(
        trainset,
        BalancedKMeansParams(
            n_clusters=n_lists,
            n_iters=params.kmeans_n_iters,
            metric=DistanceType.L2Expanded,
            seed=params.seed,
        ),
    )
    # Physically order the lists by the PCA-bisection spatial rank of their
    # centers, so spatially nearby lists get nearby indices. The fused
    # Pallas path depends on this: probe-coherent query tiles and
    # group-granular probe tables both assume neighbor lists sit next to
    # each other in the layout. (List order is meaningless to every other
    # path, so this is free.)
    from raft_tpu.ops.pallas import spatial_center_rank

    rank = spatial_center_rank(np.asarray(centers))
    centers = jnp.asarray(np.asarray(centers)[np.argsort(rank)])
    cand = _topk_labels(assign_data, centers, k=8)
    list_data, list_indices, list_sizes, _ = _pack(
        dataset, jnp.arange(n, dtype=jnp.int32), cand, n_lists, params.list_cap_factor
    )
    list_norms = None
    if metric in (DistanceType.L2Expanded, DistanceType.L2SqrtExpanded, DistanceType.CosineExpanded):
        list_norms = row_norms(list_data.reshape(-1, d)).reshape(list_data.shape[:2])
    # lists are stored in spatial order, so the rank is the identity
    center_rank = jnp.arange(n_lists, dtype=jnp.int32)
    return IvfFlatIndex(
        centers=centers,
        list_data=list_data,
        list_indices=list_indices,
        list_sizes=list_sizes,
        list_norms=list_norms,
        metric=metric,
        size=n,
        list_cap_factor=params.list_cap_factor,
        center_rank=center_rank,
    )


def extend(
    index: IvfFlatIndex, new_vectors, new_ids=None, cap_factor: Optional[float] = None
) -> IvfFlatIndex:
    """Add vectors to an existing index (``ivf_flat::extend``,
    ``detail/ivf_flat_build.cuh:163``): assign to nearest centers and repack
    on device (centers are kept fixed, as in the reference). Unlike the
    round-2 implementation there is no device→host→device round trip — the
    valid rows are gathered, concatenated with the new ones, and
    re-scattered entirely on the accelerator. ``cap_factor=None`` uses the
    index's build-time ``list_cap_factor``."""
    if cap_factor is None:
        cap_factor = index.list_cap_factor
    new_vectors = jnp.asarray(new_vectors)
    expects(new_vectors.ndim == 2 and new_vectors.shape[1] == index.dim, "bad extend shape")
    n_new = new_vectors.shape[0]
    if new_ids is None:
        new_ids = jnp.arange(index.size, index.size + n_new, dtype=jnp.int32)
    else:
        new_ids = jnp.asarray(new_ids, jnp.int32)

    d = index.dim
    # Compact existing valid rows to the front (on device): argsort on the
    # invalid flag keeps list order among valid rows.
    flat_ids = index.list_indices.reshape(-1)
    n_old = int(index.size)
    keep_order = jnp.argsort(flat_ids < 0)[:n_old]
    old_data = index.list_data.reshape(-1, d)[keep_order]
    old_ids = flat_ids[keep_order]

    all_data = jnp.concatenate([old_data, new_vectors.astype(index.list_data.dtype)], axis=0)
    all_ids = jnp.concatenate([old_ids, new_ids])
    assign = all_data.astype(jnp.float32)
    if index.metric == DistanceType.CosineExpanded:
        assign = assign / jnp.maximum(jnp.linalg.norm(assign, axis=1, keepdims=True), 1e-12)
    cand = _topk_labels(assign, index.centers, k=8)

    list_data, list_indices, list_sizes, _ = _pack(
        all_data, all_ids, cand, index.n_lists, cap_factor
    )
    list_norms = None
    if index.list_norms is not None:
        list_norms = row_norms(list_data.reshape(-1, d)).reshape(list_data.shape[:2])
    return IvfFlatIndex(
        centers=index.centers,
        list_data=list_data,
        list_indices=list_indices,
        list_sizes=list_sizes,
        list_norms=list_norms,
        metric=index.metric,
        size=index.size + n_new,
        list_cap_factor=cap_factor,
        center_rank=index.center_rank,
    )


@functools.partial(
    jax.jit,
    static_argnames=("k", "n_probes", "metric", "has_filter", "chunk_lists"),
)
def _ivf_flat_scan_impl(
    centers,
    list_data,
    list_indices,
    list_norms,
    queries,
    filter_bits,
    *,
    k: int,
    n_probes: int,
    metric: DistanceType,
    has_filter: bool,
    chunk_lists: int,
):
    """Dense masked scan — the TPU answer to the reference's fused
    interleaved-scan kernel (``ivf_flat_interleaved_scan-inl.cuh:687``)
    for batched queries.

    Rather than gathering each query's probed lists (a per-(query,probe)
    HBM gather that runs far off the roofline on TPU), the whole padded
    index is streamed chunk-of-lists at a time through ONE dense MXU
    matmul per chunk; rows in lists a query did not probe are masked with
    an elementwise predicate that XLA fuses into the matmul epilogue, and
    the selection is the fused approximate top-k. The candidate set is
    exactly the probe path's. Wins whenever the query batch is large
    enough that most lists are probed by someone (the usual
    throughput-mode regime); ``search`` keeps the gather path for small
    batches."""
    qf = queries.astype(jnp.float32)
    if metric == DistanceType.CosineExpanded:
        qf = qf / jnp.maximum(jnp.linalg.norm(qf, axis=1, keepdims=True), 1e-12)
    probed = probe_mask(centers, qf, n_probes, metric)
    return flat_scan_core(
        list_data,
        list_indices,
        list_norms,
        qf,
        probed,
        filter_bits,
        k=k,
        metric=metric,
        has_filter=has_filter,
        chunk_lists=chunk_lists,
    )


def scan_chunk_lists(n_lists: int, max_list: int) -> int:
    """Chunk-of-lists size for the dense scan: ~512k rows per chunk (the
    measured fusion sweet spot), constrained to divide n_lists."""
    g = max(1, 524288 // max(max_list, 1))
    while n_lists % g:
        g -= 1
    return g


def probe_mask(centers, qf, n_probes: int, metric: DistanceType) -> jax.Array:
    """[nq, n_lists] bool — which lists each query probes (the coarse
    ``select_clusters`` step as a mask). For cosine, ``qf`` must already be
    unit-normalized."""
    from raft_tpu.neighbors.ivf_common import probe_selection

    return probe_selection(centers, qf, n_probes, metric)[1]


def flat_scan_core(
    list_data,
    list_indices,
    list_norms,
    qf,
    probed,
    filter_bits,
    *,
    k: int,
    metric: DistanceType,
    has_filter: bool,
    chunk_lists: int,
):
    """Masked dense scan over (a shard of) the padded lists. ``probed`` is
    [nq, n_lists_local]; ``list_indices`` carry global row ids, so per-shard
    results merge directly (used by ``parallel.sharded_ann``)."""
    nq = qf.shape[0]
    n_lists, max_list = list_indices.shape
    d = list_data.shape[-1]
    G, M = chunk_lists, max_list
    n_chunks = n_lists // G
    data_c = list_data.reshape(n_chunks, G * M, d)
    ids_c = list_indices.reshape(n_chunks, G * M)
    if list_norms is not None:
        norms_c = list_norms.reshape(n_chunks, G * M)
    else:
        norms_c = jnp.zeros((n_chunks, G * M), jnp.float32)
    probed_cm = jnp.moveaxis(probed.reshape(nq, n_chunks, G), 1, 0)

    init = (
        jnp.full((nq, k), -jnp.inf, jnp.float32),
        jnp.zeros((nq, k), jnp.int32),  # flat slots
    )

    def body(carry, inp):
        acc_v, acc_i = carry
        rows, ids, nrm, pmask, ci = inp
        dots = (qf @ rows.astype(jnp.float32).T).astype(jnp.float32)
        if metric == DistanceType.InnerProduct:
            score = dots
        elif metric == DistanceType.CosineExpanded:
            score = dots * lax.rsqrt(jnp.maximum(nrm, 1e-24))[None, :]
        else:
            score = 2.0 * dots - nrm[None, :]  # max == min L2
        # Masking is ADDITIVE on the small axes (a [G*M] pad penalty and an
        # [nq, G] probe penalty broadcast into the epilogue) — a boolean
        # [nq, G*M] keep-mask defeats XLA's matmul fusion and costs ~10x.
        pad_pen = jnp.where(ids >= 0, 0.0, -jnp.inf)  # [G*M]
        if has_filter:
            word = filter_bits[jnp.clip(ids, 0, None) // 32]
            bit = (word >> (jnp.clip(ids, 0, None) % 32).astype(jnp.uint32)) & 1
            pad_pen = jnp.where(bit == 1, pad_pen, -jnp.inf)
        probe_pen = jnp.where(pmask, 0.0, -jnp.inf)  # [nq, G]
        score = (
            score
            + pad_pen[None, :]
            + jnp.broadcast_to(probe_pen[:, :, None], (nq, G, M)).reshape(nq, G * M)
        )
        # shortlist 2k per chunk: each true top-k member lands in the
        # approximate top-2k with much higher probability than in the
        # top-k, lifting end-to-end recall toward the probe path's
        kk = min(max(2 * k, 16), G * M)
        v, i = lax.approx_max_k(score, kk, recall_target=0.99)
        nv, ni = lax.top_k(jnp.concatenate([acc_v, v], axis=1), k)
        na = jnp.take_along_axis(
            jnp.concatenate([acc_i, i + ci * (G * M)], axis=1), ni, axis=1
        )
        return (nv, na), None

    (vals, slots), _ = lax.scan(
        body,
        init,
        (data_c, ids_c, norms_c, probed_cm, jnp.arange(n_chunks, dtype=jnp.int32)),
    )

    idx = list_indices.reshape(-1)[slots.reshape(-1)].reshape(nq, k)
    idx = jnp.where(jnp.isfinite(vals), idx, -1)
    if metric == DistanceType.InnerProduct:
        out = vals
    elif metric == DistanceType.CosineExpanded:
        out = 1.0 - vals
        out = jnp.where(idx >= 0, out, jnp.inf)
    else:
        qn = jnp.sum(qf * qf, axis=1)
        out = jnp.maximum(qn[:, None] - vals, 0.0)
        if metric == DistanceType.L2SqrtExpanded:
            out = jnp.sqrt(out)
        out = jnp.where(idx >= 0, out, jnp.inf)
    return out, idx


@functools.partial(
    jax.jit,
    static_argnames=("k", "n_probes", "metric", "has_filter"),
)
def _ivf_search_impl(
    centers,
    list_data,
    list_indices,
    list_norms,
    queries,
    filter_bits,
    *,
    k: int,
    n_probes: int,
    metric: DistanceType,
    has_filter: bool,
):
    nq, d = queries.shape
    n_lists, max_list = list_indices.shape
    qf = queries.astype(jnp.float32)
    if metric == DistanceType.CosineExpanded:
        qf = qf / jnp.maximum(jnp.linalg.norm(qf, axis=1, keepdims=True), 1e-12)

    # -- coarse: nearest centers (select_clusters, ivf_flat_search-inl.cuh:145)
    q_dot_c = qf @ centers.T  # [nq, n_lists] (MXU)
    if metric == DistanceType.InnerProduct:
        coarse = -q_dot_c
    else:
        c_norm = jnp.sum(centers * centers, axis=1)
        coarse = c_norm[None, :] - 2.0 * q_dot_c  # rankwise == L2 distance
    _, probes = select_k(coarse, n_probes, select_min=True)  # [nq, n_probes]

    q_sqnorm = jnp.sum(qf * qf, axis=1)
    select_min = metric != DistanceType.InnerProduct
    worst = jnp.float32(worst_value(jnp.float32, select_min))

    init = (
        jnp.full((nq, k), worst, jnp.float32),
        jnp.full((nq, k), -1, jnp.int32),
    )

    def body(carry, p):
        acc_v, acc_i = carry
        list_id = probes[:, p]  # [nq]
        data_p = list_data[list_id]  # [nq, max_list, d] gather
        ids_p = list_indices[list_id]  # [nq, max_list]
        dots = jnp.einsum(
            "qd,qmd->qm",
            qf,
            data_p.astype(jnp.float32),
            preferred_element_type=jnp.float32,
            # full-precision passes: in-list ranking must match the exact
            # distances the reference computes (see cagra.py note on the
            # TPU default bf16 matmul)
            precision=lax.Precision.HIGHEST,
        )
        if metric == DistanceType.InnerProduct:
            dist = dots
        elif metric == DistanceType.CosineExpanded:
            # qf is unit-normalized; stored rows are raw, so
            # 1 - cos = 1 - (q̂·x)/||x||.
            norms_p = list_norms[list_id]
            dist = 1.0 - dots * lax.rsqrt(jnp.maximum(norms_p, 1e-24))
        else:
            norms_p = list_norms[list_id]
            dist = q_sqnorm[:, None] + norms_p - 2.0 * dots
            dist = jnp.maximum(dist, 0.0)
        valid = ids_p >= 0
        if has_filter:
            word = filter_bits[jnp.clip(ids_p, 0, None) // 32]
            bit = (word >> (jnp.clip(ids_p, 0, None) % 32).astype(jnp.uint32)) & 1
            valid = valid & (bit == 1)
        dist = jnp.where(valid, dist, worst)
        ids_masked = jnp.where(valid, ids_p, -1)
        return running_merge(acc_v, acc_i, dist, ids_masked, select_min=select_min), None

    (vals, idx), _ = lax.scan(body, init, jnp.arange(n_probes))

    if metric == DistanceType.L2SqrtExpanded:
        vals = jnp.where(idx >= 0, jnp.sqrt(jnp.maximum(vals, 0.0)), vals)
    return vals, idx


def _batched_search(run, queries, query_batch: int):
    """Shared query-batching: pad the tail batch, call ``run`` per batch,
    trim, concatenate. One home for the loop the fused/scan/probe modes
    all need."""
    nq = queries.shape[0]
    out_v, out_i = [], []
    for start in range(0, nq, query_batch):
        qc = queries[start : start + query_batch]
        bpad = 0
        if qc.shape[0] < query_batch and nq > query_batch:
            bpad = query_batch - qc.shape[0]
            qc = jnp.pad(qc, ((0, bpad), (0, 0)))
        v, i = run(qc)
        if bpad:
            v, i = v[:-bpad], i[:-bpad]
        out_v.append(v)
        out_i.append(i)
    if len(out_v) == 1:
        return out_v[0], out_i[0]
    return jnp.concatenate(out_v, axis=0), jnp.concatenate(out_i, axis=0)


# Rank cache for legacy (pre-v3) indexes, keyed on the identity of the
# centers array: computing the PCA-bisection rank is a host-side walk we
# don't want per search call, and caching ON the index object (as an
# attribute) is a mutation of user-owned state that doesn't survive
# serialization or pytree transforms. Weak refs let index arrays die;
# arrays that refuse weakrefs would otherwise pin themselves forever, so
# the cache is also capped (FIFO evict) — a long-running server loading
# many legacy indexes must not grow without bound.
_RANK_CACHE: dict = {}
_RANK_CACHE_MAX = 64


def _rank_cache_put(key, ref, value):
    _RANK_CACHE[key] = (ref, value)
    while len(_RANK_CACHE) > _RANK_CACHE_MAX:
        _RANK_CACHE.pop(next(iter(_RANK_CACHE)))


def _legacy_rank_cache(centers) -> jax.Array:
    import weakref

    key = id(centers)
    hit = _RANK_CACHE.get(key)
    if hit is not None and hit[0]() is centers:
        return hit[1]
    from raft_tpu.ops.pallas.ivf_scan import spatial_center_rank

    rank = jnp.asarray(spatial_center_rank(np.asarray(centers)))
    try:
        ref = weakref.ref(centers, lambda _: _RANK_CACHE.pop(key, None))
    except TypeError:  # some array types refuse weakrefs; FIFO cap evicts
        ref = lambda: centers  # noqa: E731
    _rank_cache_put(key, ref, rank)
    return rank


def _rank_is_identity(rank) -> bool:
    key = id(rank)
    hit = _RANK_CACHE.get(("ident", key))
    if hit is not None and hit[0]() is rank:
        return hit[1]
    import weakref

    r = np.asarray(rank)
    ident = bool((r == np.arange(r.shape[0], dtype=r.dtype)).all())
    try:
        ref = weakref.ref(rank, lambda _: _RANK_CACHE.pop(("ident", key), None))
    except TypeError:
        ref = lambda: rank  # noqa: E731
    _rank_cache_put(("ident", key), ref, ident)
    return ident


def search(
    index: IvfFlatIndex,
    queries,
    k: int,
    params: Optional[IvfFlatSearchParams] = None,
    prefilter: Optional[Bitset] = None,
    query_batch: int = 1024,
    mode: str = "auto",
    res: Optional[Resources] = None,
    dataset=None,
    **kwargs,
) -> Tuple[jax.Array, jax.Array]:
    """ANN search over probed lists (``ivf_flat::search``,
    ``detail/ivf_flat_search-inl.cuh:271``). Returns best-first
    ``(distances [nq, k] f32, indices [nq, k] i32)``; unfilled slots get
    id -1.

    ``mode``: ``"fused"`` = the Pallas fused probed-list scan (DMAs only
    the probed lists — the big-batch TPU fast path, see
    :mod:`raft_tpu.ops.pallas.ivf_scan`); ``"scan"`` = dense masked scan
    over list chunks (:func:`_ivf_flat_scan_impl`); ``"probe"`` = per-probe
    gather (latency path for small batches); ``"auto"`` picks fused on TPU
    for batches >= 128 (when the metric/dtype qualify and there is no
    prefilter fallback issue), else scan for batches >= 128, else probe.
    All draw from the same probed candidate set; fused/scan select with an
    approximate top-k (lane-group PartialReduce), so results can differ
    slightly from the deterministic probe path. Fused accepts
    ``params.fused_*`` tuning knobs and runs in interpret mode off-TPU.

    With observability on (:mod:`raft_tpu.obs`, ``RAFT_TPU_OBS=1``) the
    call records a sync-aware ``ivf_flat.search`` span with ``put`` and
    ``refine`` children; under a profiler session the same spans are
    annotations that never block; with neither (the default) it costs
    one check."""
    if not obs.active():
        return _search_dispatch(
            index, queries, k, params, prefilter, query_batch, mode, res, dataset, **kwargs
        )
    with obs.span("ivf_flat.search", k=k, nq=int(np.shape(queries)[0])) as sp:
        return sp.sync(
            _search_dispatch(
                index, queries, k, params, prefilter, query_batch, mode, res, dataset, **kwargs
            )
        )


def _search_dispatch(
    index: IvfFlatIndex,
    queries,
    k: int,
    params: Optional[IvfFlatSearchParams],
    prefilter: Optional[Bitset],
    query_batch: int,
    mode: str,
    res: Optional[Resources],
    dataset,
    **kwargs,
) -> Tuple[jax.Array, jax.Array]:
    """Mode routing + query batching behind :func:`search` (split out so
    the span-off path costs a single check)."""
    ensure_resources(res)
    if params is None:
        params = IvfFlatSearchParams(**kwargs)
    with obs.span("ivf_flat.search.put"):
        queries = jnp.asarray(queries)
    expects(queries.ndim == 2 and queries.shape[1] == index.dim, "bad query shape")
    expects(k >= 1, "k must be >= 1")
    if dataset is not None and params.refine_ratio > 1:
        # Integrated refine (same contract as ivf_pq.search): scan for
        # k * refine_ratio candidates, then exact re-rank against the raw
        # dataset — a device array or a tiered HostVectorStore.
        from raft_tpu.neighbors.refine import check_refine_dataset, refine

        check_refine_dataset(dataset, index.size, "ivf_flat")
        inner = dataclasses.replace(params, refine_ratio=1)
        kk = min(k * params.refine_ratio, index.size)
        _, cand = search(
            index, queries, kk, inner,
            prefilter=prefilter, query_batch=query_batch, mode=mode, res=res,
        )
        if obs.is_enabled():
            obs.observe("ivf_flat.search.refine_candidates_per_query", float(kk))
        with obs.span("ivf_flat.search.refine", k=k, candidates=int(kk)) as sp:
            return sp.sync(
                refine(dataset, queries, cand, k, metric=resolve_metric(index.metric))
            )
    if prefilter is not None:
        expects(prefilter.size >= index.size, "prefilter smaller than index")
    n_probes = min(params.n_probes, index.n_lists)
    nq = queries.shape[0]

    filter_bits = prefilter.bits if prefilter is not None else None

    if mode == "auto":
        from raft_tpu import plan as _plan
        from raft_tpu.ops.pallas.ivf_scan import supported_metric

        on_tpu = jax.default_backend() == "tpu"
        if _plan.is_enabled():
            mode = _plan.plan_search_mode(
                "ivf_flat", nq, on_tpu=on_tpu,
                fused_ok=supported_metric(index.metric),
            ).choice
        elif nq >= 128 and on_tpu and supported_metric(index.metric):
            mode = "fused"
        else:
            mode = "scan" if nq >= 128 else "probe"
    expects(
        mode in ("scan", "probe", "fused"), "mode must be auto|scan|probe|fused, got %r", mode
    )
    if obs.is_enabled():
        obs.inc("ivf_flat.search.calls", mode=mode)
    if mode == "fused":
        from raft_tpu.ops.pallas.ivf_scan import (
            ivf_flat_fused_search,
            spatial_center_rank,
            supported_metric,
        )

        expects(supported_metric(index.metric), "fused mode: unsupported metric")
        rank = index.center_rank
        if rank is None:
            # legacy (pre-v3) index: compute once and cache OUTSIDE the
            # index (keyed on the centers array) — mutating a user-owned
            # index here would leak an unserializable side channel
            rank = _legacy_rank_cache(index.centers)
        # Lists are physically stored in spatial order only when the v3
        # build produced them: that build reorders list storage and leaves
        # center_rank == identity. A rank regenerated for a legacy file is
        # a genuine PCA-bisection permutation (never identity), so this
        # check is derived from the data — it survives serialization and
        # pytree round-trips, unlike an in-memory flag.
        legacy_order = not _rank_is_identity(rank)
        # Clamp the DMA group to the VMEM budget: two double-buffered list
        # blocks, plus the in-kernel f32 copy that int8/uint8 lists get
        # (f32 is identity, bf16 rides the MXU natively). Empirical limit:
        # 2 x 8 MB f32 blocks overflow the ~16 MB scoped budget, 2 x 4 MB
        # bf16 blocks fit with room.
        itemsize = index.list_data.dtype.itemsize
        cast_bytes = 4 if itemsize < 2 else 0
        per_group = index.max_list * index.dim * (2 * itemsize + cast_bytes)
        vmem_group_cap = max(1, (12 * 1024 * 1024) // max(1, per_group))
        group = max(1, min(params.fused_group, index.n_lists, vmem_group_cap))
        if legacy_order:
            # pre-v3 files store lists in arbitrary k-means order; grouping
            # assumes spatially adjacent lists, so fall back to single-list
            # DMA blocks rather than silently losing probe coverage
            group = 1
        while index.n_lists % group:
            group -= 1

        def run(qc):
            return ivf_flat_fused_search(
                index.centers,
                rank,
                index.list_data,
                index.list_indices,
                index.list_norms,
                qc,
                filter_bits,
                k=k,
                n_probes=n_probes,
                metric=index.metric,
                qt=params.fused_qt,
                probe_factor=params.fused_probe_factor,
                group=group,
                has_filter=filter_bits is not None,
                merge=params.fused_merge,
                precision=params.fused_precision,
                extract_every=params.fused_extract_every,
                col_chunk=params.fused_col_chunk,
                interpret=jax.default_backend() != "tpu",
            )

        return _batched_search(run, queries, query_batch)
    if mode == "scan":
        g = scan_chunk_lists(index.n_lists, index.max_list)

        def run_scan(qc):
            return _ivf_flat_scan_impl(
                index.centers,
                index.list_data,
                index.list_indices,
                index.list_norms,
                qc,
                filter_bits,
                k=k,
                n_probes=n_probes,
                metric=index.metric,
                has_filter=filter_bits is not None,
                chunk_lists=g,
            )

        return _batched_search(run_scan, queries, query_batch)

    def run_probe(qc):
        return _ivf_search_impl(
            index.centers,
            index.list_data,
            index.list_indices,
            index.list_norms,
            qc,
            filter_bits,
            k=k,
            n_probes=n_probes,
            metric=index.metric,
            has_filter=filter_bits is not None,
        )

    return _batched_search(run_probe, queries, query_batch)


# -- serialization (neighbors/ivf_flat_serialize.cuh analog) ----------------

_KIND = "ivf_flat"
_VERSION = 3


def _write_body(index: IvfFlatIndex, stream: BinaryIO) -> None:
    ser.serialize_scalar(stream, int(index.metric), "int32")
    ser.serialize_scalar(stream, int(index.size), "int64")
    ser.serialize_scalar(stream, float(index.list_cap_factor), "float64")
    ser.serialize_scalar(stream, int(index.list_norms is not None), "int32")
    ser.serialize_scalar(stream, int(index.center_rank is not None), "int32")
    ser.serialize_array(stream, index.centers)
    ser.serialize_array(stream, index.list_data)
    ser.serialize_array(stream, index.list_indices)
    ser.serialize_array(stream, index.list_sizes)
    if index.list_norms is not None:
        ser.serialize_array(stream, index.list_norms)
    if index.center_rank is not None:
        ser.serialize_array(stream, index.center_rank)


def save(index: IvfFlatIndex, stream: BinaryIO) -> None:
    body = io.BytesIO()
    _write_body(index, body)
    ser.save_stream(stream, _KIND, _VERSION, body.getvalue())


def load(stream: BinaryIO, res: Optional[Resources] = None) -> IvfFlatIndex:
    ensure_resources(res)
    version, stream = ser.load_stream(stream, _KIND)
    metric = DistanceType(ser.deserialize_scalar(stream, "int32"))
    size = int(ser.deserialize_scalar(stream, "int64"))
    cap_factor = float(ser.deserialize_scalar(stream, "float64")) if version >= 2 else 2.0
    has_norms = bool(ser.deserialize_scalar(stream, "int32"))
    has_rank = bool(ser.deserialize_scalar(stream, "int32")) if version >= 3 else False
    centers = ser.deserialize_array(stream)
    list_data = ser.deserialize_array(stream)
    list_indices = ser.deserialize_array(stream)
    list_sizes = ser.deserialize_array(stream)
    list_norms = ser.deserialize_array(stream) if has_norms else None
    center_rank = ser.deserialize_array(stream) if has_rank else None
    return IvfFlatIndex(
        centers=centers,
        list_data=list_data,
        list_indices=list_indices,
        list_sizes=list_sizes,
        list_norms=list_norms,
        metric=metric,
        size=size,
        list_cap_factor=cap_factor,
        center_rank=center_rank,
    )


def save_path(index: IvfFlatIndex, path: str) -> str:
    """Atomic (temp-then-rename) checksummed snapshot at ``path``."""
    return ser.atomic_write(path, lambda f: save(index, f))


def load_path(path: str, res: Optional[Resources] = None) -> IvfFlatIndex:
    with open(path, "rb") as f:
        return load(f, res=res)
