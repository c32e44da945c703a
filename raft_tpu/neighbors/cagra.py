"""CAGRA graph index — analog of ``raft::neighbors::cagra``.

Reference: build ``neighbors/detail/cagra/cagra_build.cuh:47,238,263``
(kNN graph via IVF-PQ search or NN-descent), 2-hop detour pruning
``detail/cagra/graph_core.cuh:130`` (``kern_prune``) + reverse-edge merge
(``graph_core.cuh:440-560``), search plan ``detail/cagra/search_plan.cuh:81``
and single-CTA greedy beam search
``detail/cagra/search_single_cta_kernel-inl.cuh:467``
(``pickup_next_parents:54``, bitonic topk ``:97,200``, visited hashmap
``detail/cagra/hashmap.hpp``). Index type ``neighbors/cagra_types.hpp:142``.

TPU-first redesign:

* **Pruning** is a dense batched computation: the detour count of edge
  A->B_rank_b — #{a < b : B ∈ G[G[A,a]]} — comes from a two-hop gather plus
  an equality-reduction scan over the higher-ranked neighbor axis; edges are
  then re-ranked by (detour_count, original rank) with one argsort. No
  atomics, no per-node kernels.
* **Reverse-edge merge** keeps the first ``degree/2`` forward edges
  protected and fills the tail with rank-limited reverse edges followed by
  the remaining forward edges, deduplicated with a sort-based keep-first
  compaction — the vectorized equivalent of the reference's shift-insert
  loop.
* **Search** is a fixed-iteration batched beam search under ``jit``: an
  ``itopk``-slot candidate buffer per query carries (distance, id, visited)
  — the visited *hashmap* becomes a visited *flag lane* merged by a
  sort-dedup (TPUs prefer sorted lanes over random scatter). Each step
  expands ``search_width`` best unvisited parents, gathers their fixed-
  degree adjacency rows, scores them with one MXU einsum, and re-selects
  the buffer. Data-dependent termination is replaced by a static iteration
  count (SURVEY.md §7 hard part (c)).

Supported metrics: L2Expanded, L2SqrtExpanded, InnerProduct.
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
from raft_tpu.ops.distance import DistanceType, resolve_metric
from raft_tpu.ops.select_k import running_merge_unique, select_k, worst_value
from raft_tpu.random.rng import as_key
from raft_tpu.robust import fallback as _fallback, faults as _faults
from raft_tpu.utils.graph import reverse_edges

_SUPPORTED = (
    DistanceType.L2Expanded,
    DistanceType.L2SqrtExpanded,
    DistanceType.InnerProduct,
)

IVF_PQ = "ivf_pq"
NN_DESCENT = "nn_descent"


@dataclasses.dataclass
class CagraIndexParams:
    """``cagra::index_params`` analog (``neighbors/cagra_types.hpp:62``)."""

    intermediate_graph_degree: int = 128
    graph_degree: int = 64
    build_algo: str = NN_DESCENT
    metric: DistanceType = DistanceType.L2Expanded
    nn_descent_niter: int = 20
    seed: int = 0


@dataclasses.dataclass
class CagraSearchParams:
    """``cagra::search_params`` analog (``neighbors/cagra_types.hpp:85``).

    ``init_sample``: seed the beam from the best-scoring of this many
    evenly strided dataset rows (scored exactly with ONE [nq, S] MXU
    matmul) instead of purely random ids — the in-tree analog of the
    reference's optional seed points (``search_plan.cuh:100`` ``dev_seed``
    + ``num_random_samplings``). On clustered data random inits rarely
    land near the query's cluster and the pruned fixed-degree graph has
    few long-range edges to recover, so sampled seeding is the difference
    between ~0.2 and ~0.9 recall at 1M scale. 0 = legacy random init.

    ``seed`` only affects the legacy random init (``init_sample=0``): the
    default strided-sample path is deterministic and ignores it."""

    itopk_size: int = 64
    search_width: int = 1
    max_iterations: int = 0  # 0 = auto (search_plan.cuh:136 adjust)
    seed: int = 0
    init_sample: int = 4096
    # fused (Pallas) path knobs — see ops/pallas/cagra_search.py. ``qt``
    # is the per-grid-step query tile (VMEM-modeled at 32);
    # ``fused_table_dtype`` trades table HBM footprint for score
    # precision (bf16 halves the deg-x table; use float32 for
    # bit-faithful parity runs).
    fused_qt: int = 32
    fused_table_dtype: str = "bfloat16"
    # Candidate deduplication strategy per iteration:
    #   "sort" — id-sort + adjacent-compare + re-select (two sorts; the
    #            round-3 default, exact).
    #   "post" — single value-sort merge, then adjacent-id kill on the
    #            RESULT: duplicates of one id carry the same distance, so
    #            a stable value sort makes them adjacent, and the stable
    #            tie order guarantees the visited (buffered) copy
    #            survives. Half the sort work of "sort"; dup copies decay
    #            to dead slots instead of re-selectable ghosts. Default.
    #   "none" — no dedup. NOT recommended: unflagged duplicates of
    #            already-expanded nodes get re-picked as parents forever
    #            and the beam stalls (measured: recall 0.97 -> 0.39).
    # True/False are accepted as aliases of "sort"/"none".
    dedup: str = "post"


@dataclasses.dataclass
class VpqParams:
    """``vpq_params`` analog (``neighbors/dataset.hpp:210-235``): coarse
    vector quantization + product quantization of the residual."""

    vq_n_centers: int = 0  # 0 = auto (~sqrt(n))
    pq_dim: int = 0  # 0 = auto (dim / 4, min 1)
    pq_bits: int = 8
    kmeans_n_iters: int = 15
    seed: int = 0


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class VpqDataset:
    """VQ+PQ compressed dataset (``vpq_dataset``,
    ``neighbors/dataset.hpp:236-259``): each row is a coarse VQ center
    plus PQ-coded residual, ~pq_dim bytes/row instead of 4*dim — the
    beyond-HBM story for large CAGRA datasets. Decoding during beam
    search is a one-hot MXU matmul (TPUs have no fast per-lane gather)."""

    vq_centers: jax.Array  # [vq_n, d] f32
    vq_labels: jax.Array  # [n] i32
    pq_centers: jax.Array  # [pq_dim, ksub, pq_len] f32
    codes: jax.Array  # [n, pq_dim] u8
    sqnorms: jax.Array  # [n] f32 — ||decoded row||^2, precomputed

    def tree_flatten(self):
        return (self.vq_centers, self.vq_labels, self.pq_centers, self.codes, self.sqnorms), ()

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    @property
    def pq_dim(self) -> int:
        return self.codes.shape[1]

    @property
    def ksub(self) -> int:
        return self.pq_centers.shape[1]


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class CagraIndex:
    """Fixed-degree graph + dataset (``cagra_types.hpp:142``). The dataset
    is either raw rows or a :class:`VpqDataset` (``neighbors/dataset.hpp:37``
    strided vs ``:259`` vpq dataset variants)."""

    dataset: Optional[jax.Array]  # [n, d], or None when vpq is set
    sqnorms: Optional[jax.Array]  # [n] f32 (L2 metrics)
    graph: jax.Array  # [n, graph_degree] i32
    metric: DistanceType
    size: int
    vpq: Optional[VpqDataset] = None
    dim_hint: int = 0  # feature dim when dataset is compressed away

    def tree_flatten(self):
        return (self.dataset, self.sqnorms, self.graph, self.vpq), (
            self.metric,
            self.size,
            self.dim_hint,
        )

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(
            dataset=children[0],
            sqnorms=children[1],
            graph=children[2],
            vpq=children[3],
            metric=aux[0],
            size=aux[1],
            dim_hint=aux[2],
        )

    @property
    def dim(self) -> int:
        if self.dataset is not None:
            return self.dataset.shape[1]
        return self.dim_hint or self.vpq.vq_centers.shape[1]

    @property
    def graph_degree(self) -> int:
        return self.graph.shape[1]


# ---------------------------------------------------------------------------
# graph optimization (prune + reverse merge)
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("kout",))
def _detour_rerank_chunk(graph, chunk_ids, *, kout: int):
    """Detour counts for a chunk of nodes + re-rank (``kern_prune``,
    ``graph_core.cuh:130`` and the rank-ordered rebuild at ``:425-442``).

    For node A with ranked neighbors G[A]: detour(A, b) =
    #{a < b : G[A, b] ∈ G[G[A, a]]}. Edges are kept ordered by
    (detour count, original rank), truncated to ``kout``.

    Membership is a SORTED two-hop adjacency + batched binary search
    (O(kin² log kin) per node instead of the O(kin³) equality scan —
    TPUs have no hash sets, but vmapped searchsorted vectorizes cleanly).
    """
    kin = graph.shape[1]
    rows = graph[chunk_ids]  # [c, kin]
    # rows may hold -1 padding (e.g. the IVF-PQ build path's short kNN
    # rows); a raw gather would wrap to the last node's adjacency and
    # pollute detour counts, so gather clipped and mask the contribution.
    rows_valid = rows >= 0  # [c, kin]
    two_hop = graph[jnp.maximum(rows, 0)]  # [c, kin, kin]
    th_sorted = jnp.sort(two_hop, axis=-1)

    def member(th_a, targets):  # th_a [kin] sorted, targets [kin]
        pos = jnp.clip(jnp.searchsorted(th_a, targets), 0, kin - 1)
        return th_a[pos] == targets

    # hit[c, a, b] = G[A, b] ∈ G[G[A, a]]
    hit = jax.vmap(jax.vmap(member, in_axes=(0, None)))(th_sorted, rows)
    hit = hit & rows_valid[:, :, None]  # invalid rank-a edge: no 2-hop
    a_lt_b = jnp.arange(kin)[:, None] < jnp.arange(kin)[None, :]
    counts = jnp.sum(hit & a_lt_b[None, :, :], axis=1).astype(jnp.int32)
    # invalid (padded) edges sort last; order by (detour, rank) via one
    # composite-integer argsort
    counts = jnp.where(rows < 0, kin + 1, counts)
    key = counts * kin + jnp.arange(kin)[None, :]
    order = jnp.argsort(key, axis=1)
    return jnp.take_along_axis(rows, order[:, :kout], axis=1)


@functools.partial(jax.jit, static_argnames=("kout",))
def _merge_reverse(fwd, rev, *, kout: int):
    """Protected-head merge (``graph_core.cuh:525-555``): keep the first
    ``kout/2`` forward edges, fill the tail with reverse edges then the
    remaining forward edges, keep-first dedup, truncate to ``kout``."""
    n = fwd.shape[0]
    protected = kout // 2
    cand = jnp.concatenate([fwd[:, :protected], rev, fwd[:, protected:]], axis=1)
    m = cand.shape[1]
    # keep-first dedup: sort by (id, position); a sorted entry is a dup if
    # its predecessor holds the same id (an earlier position wins).
    pos = jnp.broadcast_to(jnp.arange(m, dtype=jnp.int32), cand.shape)
    # int32 composite requires n * (2*graph_degree) < 2^31; invalid ids all
    # tie at INT32_MAX (stable argsort keeps their relative order).
    composite = jnp.where(cand < 0, jnp.iinfo(jnp.int32).max, cand * m + pos)
    order = jnp.argsort(composite, axis=1, stable=True)
    ids_s = jnp.take_along_axis(cand, order, axis=1)
    pos_s = jnp.take_along_axis(pos, order, axis=1)
    prev = jnp.concatenate([jnp.full_like(ids_s[:, :1], -2), ids_s[:, :-1]], axis=1)
    dup = (ids_s == prev) | (ids_s < 0)
    # compact survivors back into original order, take first kout
    key2 = jnp.where(dup, m + pos_s, pos_s)
    order2 = jnp.argsort(key2, axis=1)
    merged = jnp.take_along_axis(ids_s, order2[:, :kout], axis=1)
    dup_k = jnp.take_along_axis(dup, order2[:, :kout], axis=1)
    return jnp.where(dup_k, -1, merged)


def optimize(knn_graph: jax.Array, graph_degree: int, node_chunk: int = 2048) -> jax.Array:
    """Prune an intermediate kNN graph to a fixed-degree CAGRA graph
    (``cagra::optimize``, ``cagra_build.cuh:263``)."""
    knn_graph = jnp.asarray(knn_graph, jnp.int32)
    n, kin = knn_graph.shape
    kout = min(graph_degree, kin)
    parts = []
    for s in range(0, n, node_chunk):
        ids = jnp.arange(s, min(s + node_chunk, n), dtype=jnp.int32)
        parts.append(_detour_rerank_chunk(knn_graph, ids, kout=kout))
    fwd = jnp.concatenate(parts, axis=0)
    # reverse lists ordered by forward rank: the reference's k-major
    # insertion order (kern_make_rev_graph, graph_core.cuh:480-500)
    rev = reverse_edges(fwd, n, kout, order_by_rank=True)
    return _merge_reverse(fwd, rev, kout=kout)


# ---------------------------------------------------------------------------
# build
# ---------------------------------------------------------------------------


def build(
    dataset,
    params: Optional[CagraIndexParams] = None,
    res: Optional[Resources] = None,
    pq_index=None,
    **kwargs,
) -> CagraIndex:
    """Build the CAGRA index (``cagra::build``, ``cagra_build.cuh:293``):
    intermediate kNN graph via NN-descent or IVF-PQ+refine, then
    :func:`optimize`. ``pq_index``: an already-built
    :class:`~raft_tpu.neighbors.ivf_pq.IvfPqIndex` over this dataset to
    reuse for the ``build_algo="ivf_pq"`` path (skips the internal PQ
    build — callers that serve both indexes build once)."""
    res = ensure_resources(res)
    if params is None:
        params = CagraIndexParams(**kwargs)
    metric = resolve_metric(params.metric)
    expects(metric in _SUPPORTED, "CAGRA does not support metric %s", metric)
    dataset = jnp.asarray(dataset)
    expects(dataset.ndim == 2, "dataset must be [n_rows, dim]")
    n, d = dataset.shape
    kin = min(params.intermediate_graph_degree, n - 1)
    kout = min(params.graph_degree, kin)

    if params.build_algo == NN_DESCENT:
        from raft_tpu.neighbors import nn_descent

        out = nn_descent.build(
            dataset,
            nn_descent.NNDescentParams(
                graph_degree=kin,
                intermediate_graph_degree=min(max(kin + kin // 2, kin + 8), n - 1),
                max_iterations=params.nn_descent_niter,
                metric=metric,
                seed=params.seed,
            ),
        )
        knn_graph = out.graph
    else:
        expects(params.build_algo == IVF_PQ, "unknown build_algo %s", params.build_algo)
        from raft_tpu.neighbors import ivf_pq as ivf_pq_mod
        from raft_tpu.neighbors.refine import refine as refine_fn

        # build_knn_graph via IVF-PQ search over the dataset itself + exact
        # re-rank (cagra_build.cuh:47-146). Additive-nibble codebooks make
        # the index eligible for the fused Pallas scan, which is what
        # makes this path the fast 1M-scale default (vs ~16 min of
        # NN-descent local joins on the same hardware).
        import time as _time

        from raft_tpu.core.logging import logger

        t0 = _time.perf_counter()
        with obs.span("cagra.build.pq_build", n=n):
            if pq_index is not None:
                expects(pq_index.size == n, "pq_index covers %d rows, dataset has %d", pq_index.size, n)
                pq = pq_index
            else:
                pq = ivf_pq_mod.build(
                    dataset,
                    ivf_pq_mod.IvfPqIndexParams(
                        n_lists=max(1, min(1024, n // 128)),
                        metric=metric,
                        seed=params.seed,
                        # pq_dim 32 keeps the fused decode LUT small (K = 32*32
                        # columns); graph-build shortlists only need coarse
                        # ranking, the exact refine below restores order
                        pq_dim=32 if d >= 64 and d % 32 == 0 else 0,
                        pq_kind="nibble",
                        kmeans_n_iters=10,
                        kmeans_trainset_fraction=min(1.0, max(0.05, 100_000 / max(n, 1))),
                        list_cap_factor=1.1,
                    ),
                )
            jax.block_until_ready(pq.codes)
        t1 = _time.perf_counter()
        top = kin + 1
        with obs.span("cagra.build.self_search", n=n):
            _, cand = ivf_pq_mod.search(
                pq, dataset, min(2 * top, pq.size), n_probes=24, query_batch=4096
            )
            jax.block_until_ready(cand)
        t2 = _time.perf_counter()
        with obs.span("cagra.build.refine", n=n):
            _, nbrs = refine_fn(dataset, dataset, cand, top, metric=metric)
            jax.block_until_ready(nbrs)
        logger.info(
            "cagra ivf_pq graph build: pq_build %.1fs, self-search %.1fs, refine %.1fs",
            t1 - t0, t2 - t1, _time.perf_counter() - t2,
        )
        # drop self-edges, keep kin per row: stable argsort pushes the (at
        # most one) self-edge per row to the end — on device (shipping the
        # [n, kin] graph through the host link costs minutes at 1M rows)
        rows = jnp.arange(n, dtype=jnp.int32)[:, None]
        mask = nbrs != rows
        pos = jnp.argsort(~mask, axis=1, stable=True)[:, :kin]
        knn = jnp.take_along_axis(nbrs, pos, axis=1).astype(jnp.int32)
        knn_graph = jnp.where(jnp.take_along_axis(mask, pos, axis=1), knn, -1)

    with obs.span("cagra.build.optimize", n=n) as sp:
        graph = sp.sync(optimize(knn_graph, kout))
    data_f32 = dataset.astype(jnp.float32)
    sqnorms = jnp.sum(data_f32 * data_f32, axis=1)
    return CagraIndex(dataset=dataset, sqnorms=sqnorms, graph=graph, metric=metric, size=n)


def from_graph(dataset, graph, metric=DistanceType.L2Expanded) -> CagraIndex:
    """Assemble an index from a pre-built graph (``cagra::index`` ctor from
    existing dataset+graph views, ``cagra_types.hpp:253``)."""
    dataset = jnp.asarray(dataset)
    graph = jnp.asarray(graph, jnp.int32)
    expects(dataset.shape[0] == graph.shape[0], "dataset/graph row mismatch")
    data_f32 = dataset.astype(jnp.float32)
    return CagraIndex(
        dataset=dataset,
        sqnorms=jnp.sum(data_f32 * data_f32, axis=1),
        graph=graph,
        metric=resolve_metric(metric),
        size=dataset.shape[0],
    )


# ---------------------------------------------------------------------------
# search
# ---------------------------------------------------------------------------


def _pick_positions(vals, w: int, worst):
    """Positions of the ``w`` best entries per row via w rounds of
    min-extract — VPU compare/select passes instead of the full sort
    ``lax.top_k`` lowers to (the beam only needs 1-4 parents out of
    itopk, so a sort is ~10x overkill per iteration)."""
    cols = lax.broadcasted_iota(jnp.int32, vals.shape, 1)
    big = jnp.int32(2**30)
    poss, valids = [], []
    for _ in range(w):
        mv = jnp.min(vals, axis=1, keepdims=True)
        sel = jnp.min(jnp.where(vals == mv, cols, big), axis=1, keepdims=True)
        poss.append(sel)
        valids.append(mv != worst)
        vals = jnp.where(cols == sel, worst, vals)
    return jnp.concatenate(poss, axis=1), jnp.concatenate(valids, axis=1)


def _seed_select(qf, q_sqnorm, vecs, vsq, init_ids, *, itopk, select_min, worst,
                 filter_bits, has_filter):
    """Score the shared strided seed rows (one [nq, S] MXU matmul — the
    ``dev_seed`` analog) and select the initial ``itopk`` beam. Shared
    by the XLA and fused search paths so both start from an IDENTICAL
    beam: (values, ids) with ``worst``/-1 in unfilled slots."""
    s = init_ids.shape[0]
    dots = jnp.dot(
        qf, vecs.T, preferred_element_type=jnp.float32, precision=lax.Precision.HIGHEST
    )
    if select_min:
        sample_d = jnp.maximum(q_sqnorm[:, None] + vsq[None, :] - 2.0 * dots, 0.0)
    else:
        sample_d = dots
    if has_filter:
        word = filter_bits[init_ids // 32]
        bit = (word >> (init_ids % 32).astype(jnp.uint32)) & 1
        sample_d = jnp.where((bit == 1)[None, :], sample_d, worst)
    kk = min(itopk, s)
    v0, pos = select_k(sample_d, kk, select_min=select_min)
    i0 = jnp.where(v0 != worst, init_ids[pos], -1)
    if kk < itopk:
        v0 = jnp.pad(v0, ((0, 0), (0, itopk - kk)), constant_values=worst)
        i0 = jnp.pad(i0, ((0, 0), (0, itopk - kk)), constant_values=-1)
    return v0, i0


@functools.partial(
    jax.jit,
    static_argnames=(
        "k", "itopk", "width", "iters", "metric", "has_filter", "use_vpq", "dedup"
    ),
)
def _cagra_search_impl(
    dataset,
    sqnorms,
    graph,
    queries,
    init_ids,
    filter_bits,
    vpq_arrays=None,  # (vq_centers, vq_labels, pq_centers, codes) or None
    *,
    k: int,
    itopk: int,
    width: int,
    iters: int,
    metric: DistanceType,
    has_filter: bool,
    use_vpq: bool = False,
    dedup: str = "post",
):
    nq, d = queries.shape
    n, deg = graph.shape
    qf = queries.astype(jnp.float32)
    select_min = metric != DistanceType.InnerProduct
    worst = jnp.asarray(worst_value(jnp.float32, select_min), jnp.float32)
    q_sqnorm = jnp.sum(qf * qf, axis=1)

    def gather_vecs(safe):
        if not use_vpq:
            return dataset[safe].astype(jnp.float32)  # [nq, c, d]
        # VPQ decode (dataset.hpp:259 vpq_dataset): coarse VQ center +
        # one-hot-matmul PQ residual — the TPU substitute for the CUDA
        # per-lane LUT gather
        vq_centers, vq_labels, pq_centers, codes = vpq_arrays
        ksub = pq_centers.shape[1]
        b, c = safe.shape  # b == nq for beam rows, 1 for the shared seed row
        base = vq_centers[vq_labels[safe]]  # [b, c, d]
        cod = codes[safe].astype(jnp.int32)  # [b, c, pq_dim]
        onehot = (
            cod[..., None] == jnp.arange(ksub, dtype=jnp.int32)
        ).astype(jnp.float32)
        resid = jnp.einsum(
            "qcjs,jst->qcjt", onehot, pq_centers, preferred_element_type=jnp.float32
        )
        return base + resid.reshape(b, c, d)

    def score(cand):  # cand: [nq, c] ids, -1 invalid
        safe = jnp.clip(cand, 0, None)
        vecs = gather_vecs(safe)
        # HIGHEST: single-pass bf16 MXU rounding visibly degrades beam
        # ranking (measured ~6 recall points on TPU); these matmuls are tiny
        # and HBM-bound, so full-precision passes cost ~nothing.
        dots = jnp.einsum(
            "qd,qcd->qc",
            qf,
            vecs,
            preferred_element_type=jnp.float32,
            precision=lax.Precision.HIGHEST,
        )
        if select_min:
            dist = q_sqnorm[:, None] + sqnorms[safe] - 2.0 * dots
            dist = jnp.maximum(dist, 0.0)
        else:
            dist = dots
        invalid = cand < 0
        if has_filter:
            # filter at insertion (the reference's sample-filter hook inside
            # the search kernel): banned ids never occupy buffer slots, so
            # valid candidates keep competing even under selective filters
            word = filter_bits[jnp.clip(cand, 0, None) // 32]
            bit = (word >> (jnp.clip(cand, 0, None) % 32).astype(jnp.uint32)) & 1
            invalid = invalid | (bit == 0)
        return jnp.where(invalid, worst, dist)

    # -- init: seed candidates ----------------------------------------------
    # The visited-flag lane through running_merge_unique is the sort-based
    # stand-in for the CUDA visited hashmap + bitonic merge
    # (search_single_cta_kernel-inl.cuh:97-200).
    if init_ids.ndim == 1:
        # shared strided sample (dev_seed analog): all queries score the
        # same S rows, so the gather is [S, d] once and the scoring is one
        # MXU matmul — no [nq, S, d] blowup
        v0, i0 = _seed_select(
            qf, q_sqnorm, gather_vecs(init_ids[None, :])[0], sqnorms[init_ids],
            init_ids, itopk=itopk, select_min=select_min, worst=worst,
            filter_bits=filter_bits, has_filter=has_filter,
        )
        buf_v, buf_i, buf_f = v0, i0, jnp.zeros((nq, itopk), bool)
    else:
        init_d = score(init_ids)
        buf_v, buf_i, buf_f = running_merge_unique(
            jnp.full((nq, itopk), worst, jnp.float32),
            jnp.full((nq, itopk), -1, jnp.int32),
            init_d,
            init_ids,
            select_min=select_min,
            acc_flags=jnp.zeros((nq, itopk), bool),
        )

    def _expand_parents(masked, ids_at):
        """Shared pickup_next_parents (:54) → adjacency → score prologue:
        the best ``width`` unvisited buffer entries (width rounds of
        min-extract, not a full sort) parent a fixed-degree expansion.
        ``ids_at(ppos)`` reads parent ids from the carry's own
        representation; returns (ppos, rows, nbrs, dist)."""
        ppos, pvalid = _pick_positions(
            masked if select_min else -masked, width, jnp.inf
        )
        parents = jnp.where(pvalid, ids_at(ppos), -1)  # [nq, width]
        rows = jnp.arange(nq)[:, None]
        nbrs = graph[jnp.clip(parents, 0, None)]  # [nq, width, deg]
        nbrs = jnp.where(parents[:, :, None] >= 0, nbrs, -1).reshape(nq, width * deg)
        return ppos, rows, nbrs, score(nbrs)

    def body_sort(_, carry):
        buf_v, buf_i, buf_f = carry
        masked = jnp.where(buf_f | (buf_i < 0), worst, buf_v)
        ppos, rows, nbrs, dist = _expand_parents(
            masked, lambda p: jnp.take_along_axis(buf_i, p, axis=1)
        )
        buf_f = buf_f.at[rows, ppos].set(True)
        return running_merge_unique(
            buf_v, buf_i, dist, nbrs, select_min=select_min, acc_flags=buf_f
        )

    def body_packed(_, carry):
        # "post"/"none" fast path: the (id, visited) pair rides as ONE
        # int32 lane ``idf = id * 2 + flag`` through the value-sorted
        # merge — one take_along_axis instead of three per iteration
        # (measured ~20% of the per-iteration cost). id = -1 decodes
        # from both packings: -2 >> 1 == -1 (flag 0), -1 >> 1 == -1
        # (flag 1); requires ids < 2^30 like running_merge_unique.
        buf_v, buf_idf = carry
        masked = jnp.where(((buf_idf & 1) == 1) | (buf_idf < 0), worst, buf_v)
        ppos, rows, nbrs, dist = _expand_parents(
            masked, lambda p: jnp.take_along_axis(buf_idf >> 1, p, axis=1)
        )
        buf_idf = buf_idf.at[rows, ppos].set(
            jnp.take_along_axis(buf_idf, ppos, axis=1) | 1
        )
        # one value-sorted selection; "post" then kills adjacent duplicate
        # ids on the result (equal ids carry equal distances, and stable
        # tie order keeps the buffered/visited copy first)
        vals = jnp.concatenate([buf_v, jnp.where(nbrs < 0, worst, dist)], axis=1)
        idfs = jnp.concatenate([buf_idf, nbrs * 2], axis=1)
        out_v, pos = select_k(vals, itopk, select_min=select_min)
        out_idf = jnp.take_along_axis(idfs, pos, axis=1)
        out_idf = jnp.where(out_v == worst, -1, out_idf)
        if dedup == "post":
            out_i = out_idf >> 1
            prev = jnp.concatenate([jnp.full_like(out_i[:, :1], -2), out_i[:, :-1]], axis=1)
            dup = (out_i == prev) & (out_i >= 0)
            out_v = jnp.where(dup, worst, out_v)
            out_idf = jnp.where(dup, -1, out_idf)  # -1 = id -1, flagged: never parents
        return out_v, out_idf

    if dedup == "sort":
        buf_v, buf_i, buf_f = lax.fori_loop(0, iters, body_sort, (buf_v, buf_i, buf_f))
    else:
        buf_idf = buf_i * 2 + buf_f.astype(jnp.int32)
        buf_idf = jnp.where(buf_i < 0, -1, buf_idf)  # invalid slots stay non-parents
        buf_v, buf_idf = lax.fori_loop(0, iters, body_packed, (buf_v, buf_idf))
        buf_i = buf_idf >> 1
        buf_f = (buf_idf & 1) == 1
    if dedup in ("none", "post"):
        # one final sort-dedup so duplicate ids cannot occupy several of
        # the returned top-k slots. Needed for "post" too: the shared-seed
        # init scores via a [nq,s] dot while loop expansions use score()'s
        # einsum, and the two contractions can round differently — an
        # init-seeded node re-proposed during expansion then isn't
        # value-adjacent to its buffered copy, so the per-iteration
        # adjacent-id kill misses it
        buf_v, buf_i, buf_f = running_merge_unique(
            buf_v, buf_i,
            jnp.full((nq, 1), worst, jnp.float32), jnp.full((nq, 1), -1, jnp.int32),
            select_min=select_min, acc_flags=buf_f,
        )

    vals, idx = buf_v[:, :k], buf_i[:, :k]
    if metric == DistanceType.L2SqrtExpanded:
        vals = jnp.where(idx >= 0, jnp.sqrt(jnp.maximum(vals, 0.0)), vals)
    return vals, idx


def strided_seed_ids(size: int, sample: int) -> jnp.ndarray:
    """``min(sample, size)`` DISTINCT evenly spaced seed ids:
    ``floor(i * size / sample)`` — covers the whole id range whatever the
    build order groups (a fixed integer stride either truncates coverage
    or collapses onto a subgroup when it divides ``size``). Shared by the
    local and sharded search paths (``dev_seed`` analog,
    ``search_plan.cuh:100``)."""
    s = min(sample, size)
    # host-side int64: jnp.arange(int64) silently downgrades to int32 when
    # jax_enable_x64 is off, and i * size overflows int32 at ~2k seeds on
    # a 1M-row index
    return jnp.asarray((np.arange(s, dtype=np.int64) * size) // s, jnp.int32)


def plan_search_params(
    nq: int, k: int, size: int, base: Optional["CagraSearchParams"] = None
) -> "CagraSearchParams":
    """Pick the search schedule from the query-batch shape — the
    ``search_plan.cuh:81-164`` plan-selection analog. The reference
    chooses among three kernel schedules (single-CTA for big batches,
    multi-CTA / multi-kernel to keep one GPU busy on few queries); on TPU
    a single fused batched schedule serves every shape, so the plan
    moves the latency/throughput trade through
    ``(search_width, init_sample)``:

    * **every default-width call** gets the wide (width-8) beam: the
      fixed per-iteration cost (buffer merge, flag bookkeeping, host
      dispatch) is batch-size independent, so cutting the auto iteration
      count ``~itopk/width`` by the width factor wins in every regime
      (measured: +40-50% QPS at equal itopk/recall at batch 1024,
      ``artifacts/tpu/cagra_width_sweep_*``).
    * **tiny batches** (the multi-CTA / multi-kernel regime) additionally
      seed from a larger strided sample (one cheap matmul) so fewer hops
      are needed while the chip is otherwise idle.

    Explicit non-default ``base`` values are respected — the plan only
    raises knobs the caller left at their defaults."""
    base = base or CagraSearchParams()
    width = base.search_width
    init = base.init_sample
    if width == CagraSearchParams.search_width:
        # Measured (artifacts/tpu/cagra_width_sweep_*): at equal itopk a
        # width-8 beam matches width-4 recall with ~40% more QPS — the
        # auto iteration count drops ~width-fold while the fixed per-
        # iteration cost (buffer merge, flag bookkeeping, host dispatch)
        # does not grow with width. That overhead is batch-size-
        # independent, so the wide beam wins in EVERY regime.
        width = 8
    if nq <= 32 and init == CagraSearchParams.init_sample:
        # multi-CTA/multi-kernel regime: seed from a larger strided
        # sample (one cheap matmul) so fewer hops are needed
        init = min(size, 4 * CagraSearchParams.init_sample)
    return dataclasses.replace(
        base, itopk_size=max(base.itopk_size, k), search_width=width, init_sample=init
    )


def derive_search_config(params: "CagraSearchParams", k: int, size: int):
    """(itopk, width, iters, n_init) from search params — the
    ``search_plan.cuh:136`` adjust step, shared with the sharded path."""
    itopk = max(params.itopk_size, k)
    width = max(1, params.search_width)
    # search_plan.cuh:138-144: 1 + min((itopk/width)*1.1, itopk/width + 10),
    # floored at the reference's min_iterations default
    ratio = itopk // max(1, width)
    iters = params.max_iterations or max(10, 1 + min(int(ratio * 1.1), ratio + 10))
    return itopk, width, iters, min(itopk, size)


def fused_ineligible_reason(
    index: CagraIndex,
    params: "CagraSearchParams",
    prefilter: Optional[Bitset] = None,
) -> str:
    """Why the Pallas fused beam kernel
    (:mod:`raft_tpu.ops.pallas.cagra_search`) cannot serve this search,
    or ``""`` when it can. It needs a raw (uncompressed) dataset, shared
    strided seeding, ``"post"`` dedup semantics (the kernel's merge
    implements exactly those), no prefilter, ids within the packed
    base-256 encoding, id rows that fit the vector lanes
    (``graph_degree <= dim``), and room in HBM for the packed neighbor
    table it reads (``deg + 3`` rows per node) — an index whose table
    cannot fit plans the XLA path up front instead of failing on the
    allocation."""
    from raft_tpu.ops.pallas import cagra_search, hbm_model

    if not (
        index.dataset is not None
        and prefilter is None
        and params.init_sample > 0
        and params.dedup == "post"
        and index.metric in _SUPPORTED
        and index.graph_degree <= index.dim
        and index.size <= cagra_search.MAX_TABLE_IDS
    ):
        return ("needs raw dataset, init_sample>0, dedup='post', no prefilter, "
                "graph_degree<=dim")
    dtype = jnp.dtype(params.fused_table_dtype)
    cached = getattr(index, "_fused_table_cache", None)
    if cached is not None and cached[0] == dtype:
        return ""
    need = cagra_search.table_bytes(index.size, index.graph_degree, index.dim, dtype)
    free = hbm_model.device_free_bytes()
    if need > free * hbm_model.HBM_HEADROOM:
        return (f"needs {need} B of HBM for the packed neighbor table, "
                f"{free} B free (x{hbm_model.HBM_HEADROOM} headroom)")
    return ""


def fused_eligible(
    index: CagraIndex,
    params: "CagraSearchParams",
    prefilter: Optional[Bitset] = None,
) -> bool:
    """Whether the fused beam kernel can serve this search (see
    :func:`fused_ineligible_reason`)."""
    return not fused_ineligible_reason(index, params, prefilter)


def _fused_table(index: CagraIndex, dtype) -> jax.Array:
    """Build (once) and cache the packed ``[n, deg + 3, d]`` neighbor
    table on the index. Plain attribute, not a pytree leaf — transforms
    never see it, and a rebuilt index starts with a cold cache."""
    from raft_tpu.ops.pallas.cagra_search import build_neighbor_table

    dtype = jnp.dtype(dtype)
    cached = getattr(index, "_fused_table_cache", None)
    if cached is None or cached[0] != dtype:
        table = build_neighbor_table(index.dataset, index.graph, dtype=dtype)
        cached = (dtype, table)
        index._fused_table_cache = cached
    return cached[1]


@functools.partial(
    jax.jit,
    static_argnames=("k", "deg", "itopk", "width", "iters", "metric", "qt", "interpret"),
)
def _cagra_fused_impl(
    table,
    dataset,
    sqnorms,
    queries,
    init_ids,
    *,
    k: int,
    deg: int,
    itopk: int,
    width: int,
    iters: int,
    metric: DistanceType,
    qt: int,
    interpret: bool,
):
    """Fused-path wrapper: identical seed beam to the XLA path (shared
    :func:`_seed_select`), the Pallas beam loop, then the same final
    unique-merge + metric epilogue as ``_cagra_search_impl``. The final
    merge also collapses the one dup class the in-kernel adjacent kill
    cannot see: a seed node rescored during expansion carries the
    kernel's arithmetic, not the init matmul's, so the two copies are
    not value-adjacent."""
    from raft_tpu.ops.pallas.cagra_search import WORST as KWORST
    from raft_tpu.ops.pallas.cagra_search import cagra_fused_search

    nq, _ = queries.shape
    qf = queries.astype(jnp.float32)
    select_min = metric != DistanceType.InnerProduct
    worst = jnp.asarray(worst_value(jnp.float32, select_min), jnp.float32)
    q_sqnorm = jnp.sum(qf * qf, axis=1)
    v0, i0 = _seed_select(
        qf, q_sqnorm, dataset[init_ids].astype(jnp.float32), sqnorms[init_ids],
        init_ids, itopk=itopk, select_min=select_min, worst=worst,
        filter_bits=None, has_filter=False,
    )
    # kernel beam is min-ordered with a finite worst: negate IP dots,
    # map empty slots, pack (id, visited=0) into one lane
    kv0 = jnp.where(i0 < 0, KWORST, v0 if select_min else -v0)
    kidf0 = jnp.where(i0 < 0, -1, i0 * 2)
    bv, bidf = cagra_fused_search(
        table, qf, kv0, kidf0,
        deg=deg, itopk=itopk, width=width, iters=iters, qt=qt,
        ip=not select_min, interpret=interpret,
    )
    buf_i = bidf >> 1
    buf_f = (bidf & 1) == 1
    buf_v = jnp.where(buf_i < 0, worst, bv if select_min else -bv)
    buf_v, buf_i, buf_f = running_merge_unique(
        buf_v, buf_i,
        jnp.full((nq, 1), worst, jnp.float32), jnp.full((nq, 1), -1, jnp.int32),
        select_min=select_min, acc_flags=buf_f,
    )
    vals, idx = buf_v[:, :k], buf_i[:, :k]
    if metric == DistanceType.L2SqrtExpanded:
        vals = jnp.where(idx >= 0, jnp.sqrt(jnp.maximum(vals, 0.0)), vals)
    return vals, idx


def search(
    index: CagraIndex,
    queries,
    k: int,
    params: Optional[CagraSearchParams] = None,
    prefilter: Optional[Bitset] = None,
    query_batch: int = 1024,
    res: Optional[Resources] = None,
    mode: str = "auto",
    **kwargs,
) -> Tuple[jax.Array, jax.Array]:
    """Greedy beam search over the graph (``cagra::search``,
    ``detail/cagra/cagra_search.cuh:249``). Returns best-first
    ``(distances [nq, k], indices [nq, k])``; unfilled slots get id -1.

    ``mode``: ``"fused"`` = the Pallas DMA-fed beam kernel
    (:mod:`raft_tpu.ops.pallas.cagra_search`) — beam state VMEM-resident
    across iterations, parents' packed neighbor rows streamed HBM->VMEM;
    ``"xla"`` = the gather/einsum/select loop (the fallback and the
    recall oracle the fused path is tested against); ``"auto"`` picks
    fused on TPU when :func:`fused_eligible`, else xla.

    With observability on (:mod:`raft_tpu.obs`, ``RAFT_TPU_OBS=1``) the
    call records a sync-aware ``cagra.search`` span with per-batch
    children, the mode chosen (fused vs xla), iterations executed, and
    beam occupancy; under a profiler session the spans are annotations
    that never block; with neither (the default) it costs one check."""
    if not obs.active():
        return _search_dispatch(
            index, queries, k, params, prefilter, query_batch, res, mode, **kwargs
        )
    with obs.span("cagra.search", k=k, nq=int(np.shape(queries)[0])) as sp:
        return sp.sync(
            _search_dispatch(
                index, queries, k, params, prefilter, query_batch, res, mode, **kwargs
            )
        )


def _search_dispatch(
    index: CagraIndex,
    queries,
    k: int,
    params: Optional[CagraSearchParams],
    prefilter: Optional[Bitset],
    query_batch: int,
    res: Optional[Resources],
    mode: str,
    **kwargs,
) -> Tuple[jax.Array, jax.Array]:
    """Mode routing + query batching behind :func:`search` (split out so
    the observability-off path costs a single flag check)."""
    ensure_resources(res)
    if params is None:
        params = CagraSearchParams(**kwargs)
    queries = jnp.asarray(queries)
    expects(queries.ndim == 2 and queries.shape[1] == index.dim, "bad query shape")
    expects(k >= 1, "k must be >= 1")
    expects(
        params.dedup in ("sort", "post", "none", True, False),
        "dedup must be sort|post|none, got %r", params.dedup,
    )
    # auto iteration count (search_plan.cuh:136 adjust_search_params)
    itopk, width, iters, n_init = derive_search_config(params, k, index.size)
    if prefilter is not None:
        expects(prefilter.size >= index.size, "prefilter smaller than index")
    filter_bits = prefilter.bits if prefilter is not None else None

    requested_mode = mode
    if mode in ("auto", "fused"):
        why_not_fused = fused_ineligible_reason(index, params, prefilter)
    if mode == "auto":
        from raft_tpu import plan as _plan

        mode = _plan.plan_cagra_mode(
            on_tpu=jax.default_backend() == "tpu", fused_ok=not why_not_fused
        )
    expects(mode in ("xla", "fused"), "mode must be auto|xla|fused, got %r", mode)
    if mode == "fused":
        expects(not why_not_fused, "fused mode %s (use mode='xla')", why_not_fused)
    if obs.is_enabled():
        obs.inc("cagra.search.calls", mode=mode)
        obs.inc("cagra.search.queries", float(queries.shape[0]))
        obs.observe("cagra.search.iterations", float(iters))
        obs.set_gauge("cagra.search.itopk", float(itopk))
        obs.set_gauge("cagra.search.width", float(width))

    nq = queries.shape[0]
    key = as_key(params.seed)

    out_v, out_i = [], []
    for start in range(0, nq, query_batch):
        qc = queries[start : start + query_batch]
        bpad = 0
        if qc.shape[0] < query_batch and nq > query_batch:
            bpad = query_batch - qc.shape[0]
            qc = jnp.pad(qc, ((0, bpad), (0, 0)))
        if params.init_sample > 0:
            init_ids = strided_seed_ids(index.size, params.init_sample)
        else:
            key, kb = jax.random.split(key)
            init_ids = jax.random.randint(kb, (qc.shape[0], n_init), 0, index.size, jnp.int32)
        if mode == "fused":
            try:
                # host-level fault point: fires per batch even when the
                # jitted program below is cache-hit
                _faults.fire("pallas.cagra_search", nq=int(qc.shape[0]))
                table = _fused_table(index, params.fused_table_dtype)
                with obs.span(
                    "cagra.search.fused_batch", nq=qc.shape[0], iters=iters, width=width
                ) as sp:
                    v, i = sp.sync(
                        _cagra_fused_impl(
                            table,
                            index.dataset,
                            index.sqnorms,
                            qc,
                            init_ids,
                            k=k,
                            deg=index.graph_degree,
                            itopk=itopk,
                            width=width,
                            iters=iters,
                            metric=index.metric,
                            qt=max(8, min(params.fused_qt, -(-qc.shape[0] // 8) * 8)),
                            interpret=jax.default_backend() != "tpu",
                        )
                    )
                if bpad:
                    v, i = v[:-bpad], i[:-bpad]
                if obs.is_enabled():
                    obs.observe(
                        "cagra.search.beam_occupancy", float(jnp.mean(i >= 0)), mode="fused"
                    )
                out_v.append(v)
                out_i.append(i)
                continue
            except _fallback.FALLBACK_ERRORS as e:
                if requested_mode == "fused":
                    raise  # the caller pinned the engine; do not mask
                _fallback.record_fallback("cagra", e)
                mode = "xla"  # this batch and the rest take the XLA path
        use_vpq = index.dataset is None
        vpq_arrays = None
        sqnorms = index.sqnorms
        if use_vpq:
            expects(index.vpq is not None, "index has neither dataset nor vpq data")
            vpq_arrays = (
                index.vpq.vq_centers,
                index.vpq.vq_labels,
                index.vpq.pq_centers,
                index.vpq.codes,
            )
            sqnorms = index.vpq.sqnorms
        with obs.span(
            "cagra.search.xla_batch", nq=qc.shape[0], iters=iters, width=width
        ) as sp:
            v, i = sp.sync(
                _cagra_search_impl(
                    index.dataset,
                    sqnorms,
                    index.graph,
                    qc,
                    init_ids,
                    filter_bits,
                    vpq_arrays,
                    k=k,
                    itopk=itopk,
                    width=width,
                    iters=iters,
                    metric=index.metric,
                    has_filter=filter_bits is not None,
                    use_vpq=use_vpq,
                    dedup={True: "sort", False: "none"}.get(params.dedup, params.dedup),
                )
            )
        if bpad:
            v, i = v[:-bpad], i[:-bpad]
        if obs.is_enabled():
            obs.observe(
                "cagra.search.beam_occupancy", float(jnp.mean(i >= 0)), mode="xla"
            )
        out_v.append(v)
        out_i.append(i)
    if len(out_v) == 1:
        return out_v[0], out_i[0]
    return jnp.concatenate(out_v, axis=0), jnp.concatenate(out_i, axis=0)


# ---------------------------------------------------------------------------
# VPQ compression (neighbors/dataset.hpp:210-259 vpq_dataset)
# ---------------------------------------------------------------------------


def _default_vpq_pq_dim(d: int) -> int:
    for cand in (d // 4, d // 2, d):
        if cand >= 1 and d % cand == 0:
            return cand
    return d


def compress(index: CagraIndex, params: Optional[VpqParams] = None, **kwargs) -> CagraIndex:
    """Replace the raw dataset with a VQ+PQ compressed one
    (``cagra::compress`` / ``vpq_build``, ``neighbors/dataset.hpp:210``):
    coarse VQ centers + per-subspace PQ codebooks over the VQ residuals.
    Search decodes candidates on the fly; memory drops from ``4*dim``
    to ``~pq_dim`` bytes per row."""
    from raft_tpu.cluster import kmeans_balanced
    from raft_tpu.cluster.kmeans_balanced import BalancedKMeansParams
    from raft_tpu.neighbors.ivf_pq import _batched_lloyd
    from raft_tpu.ops.fused_1nn import min_cluster_and_distance

    if params is None:
        params = VpqParams(**kwargs)
    expects(index.dataset is not None, "index already compressed")
    ds = index.dataset.astype(jnp.float32)
    n, d = ds.shape
    vq_n = params.vq_n_centers or max(8, min(1024, int(round(n ** 0.5))))
    pq_dim = params.pq_dim or _default_vpq_pq_dim(d)
    expects(d % pq_dim == 0, "dim %d must be divisible by pq_dim %d", d, pq_dim)
    pq_len = d // pq_dim
    ksub = 1 << params.pq_bits

    key, k_sub, k_init = jax.random.split(as_key(params.seed), 3)
    vq_centers = kmeans_balanced.fit(
        ds,
        BalancedKMeansParams(
            n_clusters=vq_n, n_iters=params.kmeans_n_iters, seed=params.seed
        ),
    )
    vq_labels, _ = min_cluster_and_distance(ds, vq_centers)
    resid = (ds - vq_centers[vq_labels]).reshape(n, pq_dim, pq_len)

    # per-subspace codebooks on (a subsample of) the residuals
    nt = min(n, ksub * 256)
    sub = jax.random.permutation(k_sub, n)[:nt]
    Xs = jnp.transpose(resid[sub], (1, 0, 2))  # [pq_dim, nt, pq_len]
    init_idx = jax.random.permutation(k_init, nt)[: min(ksub, nt)]
    init = Xs[:, init_idx, :]
    if init.shape[1] < ksub:
        reps = -(-ksub // init.shape[1])
        init = jnp.tile(init, (1, reps, 1))[:, :ksub, :]
    pq_centers = _batched_lloyd(
        Xs, jnp.ones((pq_dim, nt), jnp.float32), init, k=ksub, n_iters=params.kmeans_n_iters
    )

    # encode: nearest sub-center per subspace (chunked)
    cn = jnp.sum(pq_centers * pq_centers, axis=-1)  # [pq_dim, ksub]
    codes_parts = []
    sq_parts = []
    chunk = 131072
    for s in range(0, n, chunk):
        rr = resid[s : s + chunk]  # [c, pq_dim, pq_len]
        dots = jnp.einsum("cjl,jkl->cjk", rr, pq_centers, preferred_element_type=jnp.float32)
        code = jnp.argmax(2.0 * dots - cn[None, :, :], axis=-1).astype(jnp.uint8)
        codes_parts.append(code)
        # decoded sqnorm for the score epilogue
        dec = jnp.take_along_axis(
            pq_centers[None], code[:, :, None, None].astype(jnp.int32), axis=2
        )[:, :, 0, :].reshape(-1, d) + vq_centers[vq_labels[s : s + chunk]]
        sq_parts.append(jnp.sum(dec * dec, axis=1))
    codes = codes_parts[0] if len(codes_parts) == 1 else jnp.concatenate(codes_parts)
    sqnorms = sq_parts[0] if len(sq_parts) == 1 else jnp.concatenate(sq_parts)

    vpq = VpqDataset(
        vq_centers=vq_centers,
        vq_labels=vq_labels.astype(jnp.int32),
        pq_centers=pq_centers,
        codes=codes,
        sqnorms=sqnorms,
    )
    return dataclasses.replace(
        index, dataset=None, sqnorms=None, vpq=vpq, dim_hint=d
    )


# ---------------------------------------------------------------------------
# serialization (neighbors/cagra_serialize.cuh analog)
# ---------------------------------------------------------------------------

_KIND = "cagra"
_VERSION = 2


def _write_body(index: CagraIndex, stream: BinaryIO, include_dataset: bool = True) -> None:
    ser.serialize_scalar(stream, int(index.metric), "int32")
    ser.serialize_scalar(stream, int(index.size), "int64")
    has_raw = index.dataset is not None and include_dataset
    has_vpq = index.vpq is not None
    ser.serialize_scalar(stream, int(has_raw), "int32")
    ser.serialize_scalar(stream, int(has_vpq), "int32")
    ser.serialize_scalar(stream, int(index.dim), "int32")
    ser.serialize_array(stream, index.graph)
    if has_raw:
        ser.serialize_array(stream, index.dataset)
    if has_vpq:
        ser.serialize_array(stream, index.vpq.vq_centers)
        ser.serialize_array(stream, index.vpq.vq_labels)
        ser.serialize_array(stream, index.vpq.pq_centers)
        ser.serialize_array(stream, index.vpq.codes)
        ser.serialize_array(stream, index.vpq.sqnorms)


def save(index: CagraIndex, stream: BinaryIO, include_dataset: bool = True) -> None:
    body = io.BytesIO()
    _write_body(index, body, include_dataset=include_dataset)
    ser.save_stream(stream, _KIND, _VERSION, body.getvalue())


def load(stream: BinaryIO, dataset=None, res: Optional[Resources] = None) -> CagraIndex:
    """Load an index; if it was saved without the dataset, one must be
    supplied (mirrors the reference's dataset-less serialize mode,
    ``cagra_serialize.cuh``)."""
    ensure_resources(res)
    version, stream = ser.load_stream(stream, _KIND)
    metric = DistanceType(ser.deserialize_scalar(stream, "int32"))
    size = int(ser.deserialize_scalar(stream, "int64"))
    has_ds = bool(ser.deserialize_scalar(stream, "int32"))
    has_vpq = bool(ser.deserialize_scalar(stream, "int32")) if version >= 2 else False
    dim = int(ser.deserialize_scalar(stream, "int32")) if version >= 2 else 0
    graph = ser.deserialize_array(stream)
    vpq = None
    if has_ds:
        data = ser.deserialize_array(stream)
    if has_vpq:
        vpq = VpqDataset(
            vq_centers=ser.deserialize_array(stream),
            vq_labels=ser.deserialize_array(stream),
            pq_centers=ser.deserialize_array(stream),
            codes=ser.deserialize_array(stream),
            sqnorms=ser.deserialize_array(stream),
        )
    if not has_ds:
        if vpq is not None and dataset is None:
            return CagraIndex(
                dataset=None, sqnorms=None, graph=graph, metric=metric,
                size=size, vpq=vpq, dim_hint=dim,
            )
        expects(dataset is not None, "index was saved without dataset; pass one")
        data = jnp.asarray(dataset)
    expects(data.shape[0] == size, "dataset rows != index size")
    out = from_graph(data, graph, metric)
    return dataclasses.replace(out, vpq=vpq, dim_hint=dim)


def save_path(index: CagraIndex, path: str, include_dataset: bool = True) -> str:
    """Atomic (temp-then-rename) checksummed snapshot at ``path``."""
    return ser.atomic_write(path, lambda f: save(index, f, include_dataset=include_dataset))


def load_path(path: str, dataset=None, res: Optional[Resources] = None) -> CagraIndex:
    with open(path, "rb") as f:
        return load(f, dataset=dataset, res=res)
