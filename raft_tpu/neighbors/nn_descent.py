"""NN-descent (GNND) kNN-graph construction — analog of
``raft::neighbors::experimental::nn_descent``.

Reference: ``neighbors/detail/nn_descent.cuh:342`` (``class GNND``), the
per-iteration ``local_join`` (``:1191``), ``build`` (``:1215``), params in
``neighbors/nn_descent_types.hpp``.

TPU-first redesign of the local join. The CUDA version samples "new"/"old"
neighbor lists per node, plus reverse edges, and runs a warp-level join
kernel with bloom-filter sampling and shared-memory insertion sort. Here the
same neighborhood-expansion fixed point is reached with dense, static-shape
ops:

1. **Sample** a pool ``P(u)`` of ``max_samples`` forward neighbors per node
   (preferring not-yet-visited "new" entries, which are then marked old —
   GNND's new/old split) plus up to ``max_samples`` *reverse* neighbors,
   built by sorting the sampled edge list by destination and rank-limiting
   (the static-shape substitute for the CUDA scatter into ragged reverse
   lists).
2. **Expand**: candidates(u) = P(P(u)) — because ``a ∈ P(u)`` implies the
   hosts of ``a`` are exactly ``P(a)``, the pairwise local join over every
   pool collapses into one two-hop gather over the symmetrized sample graph.
3. **Score** candidates with one batched MXU matmul per node chunk and
   **merge** into the running top-k with id-dedup
   (:func:`raft_tpu.ops.select_k.running_merge_unique`).

Iteration stops when the fraction of changed graph entries drops below
``termination_threshold`` (GNND's update-rate test) — a host-side check at
build time only.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from raft_tpu import obs
from raft_tpu.core.errors import expects
from raft_tpu.core.resources import Resources, ensure_resources
from raft_tpu.ops.distance import DistanceType, resolve_metric
from raft_tpu.ops.select_k import running_merge_unique, worst_value
from raft_tpu.random.rng import as_key
from raft_tpu.utils.graph import reverse_edges

_SUPPORTED = (
    DistanceType.L2Expanded,
    DistanceType.L2SqrtExpanded,
    DistanceType.InnerProduct,
    DistanceType.CosineExpanded,
)


@dataclasses.dataclass
class NNDescentParams:
    """``nn_descent::index_params`` analog (``nn_descent_types.hpp``)."""

    graph_degree: int = 64
    intermediate_graph_degree: int = 128
    max_iterations: int = 20
    termination_threshold: float = 0.0001
    max_samples: int = 16  # pool size per direction per iteration
    metric: DistanceType = DistanceType.L2Expanded
    seed: int = 0
    node_chunk: int = 4096  # rows scored per device step (memory knob)


@dataclasses.dataclass
class NNDescentOutput:
    """The built kNN graph (``nn_descent::index`` analog): best-first
    neighbor ids and distances per row."""

    graph: jax.Array  # [n, graph_degree] i32
    distances: jax.Array  # [n, graph_degree] f32
    metric: DistanceType


@functools.partial(jax.jit, static_argnames=("k", "select_min"))
def _score_and_merge(data, sqnorms, cand, acc_v, acc_i, acc_f, row0, *, k: int, select_min: bool):
    """Score a chunk of rows against their candidate ids and merge.

    ``cand``: [c, C] candidate ids (-1 invalid). One einsum puts the
    distance work on the MXU (the local join's distance computations,
    ``nn_descent.cuh:1191``). The "already sampled" flag lane rides through
    the merge (GNND's new/old bookkeeping); fresh candidates enter
    unsampled.
    """
    c, C = cand.shape
    rows = row0 + jnp.arange(c, dtype=jnp.int32)
    q = data[rows]  # [c, d]
    safe = jnp.clip(cand, 0, None)
    vecs = data[safe]  # [c, C, d]
    # HIGHEST precision: graph quality is sensitive to distance-rank errors
    # from the TPU's default single-pass bf16 matmul (see cagra.py).
    dots = jnp.einsum(
        "cd,cCd->cC",
        q,
        vecs,
        preferred_element_type=jnp.float32,
        precision=lax.Precision.HIGHEST,
    )
    if select_min:
        dist = sqnorms[rows][:, None] + sqnorms[safe] - 2.0 * dots
        dist = jnp.maximum(dist, 0.0)
    else:
        dist = dots
    worst = jnp.asarray(worst_value(jnp.float32, select_min), jnp.float32)
    invalid = (cand < 0) | (cand == rows[:, None])  # padding + self-loops
    dist = jnp.where(invalid, worst, dist)
    cand = jnp.where(invalid, -1, cand)
    return running_merge_unique(
        acc_v, acc_i, dist, cand, select_min=select_min, acc_flags=acc_f
    )


@functools.partial(jax.jit, static_argnames=("half",))
def _sample_pool(key, ids, sampled, *, half: int):
    """Sample ``half`` new (never-sampled) + ``half`` old neighbors per node
    via Gumbel top-k over the flag-partitioned lists (GNND's new/old
    sampling, ``nn_descent.cuh`` sample_graph); returns
    (pool [n, 2*half], updated flags with the drawn new entries marked
    sampled)."""
    n, k = ids.shape
    g = jax.random.gumbel(key, (n, k))
    valid = ids >= 0
    new_logit = jnp.where(valid & ~sampled, g, -jnp.inf)
    old_logit = jnp.where(valid & sampled, g, -jnp.inf)
    _, new_pos = lax.top_k(new_logit, half)
    _, old_pos = lax.top_k(old_logit, half)
    new_sel = jnp.take_along_axis(ids, new_pos, axis=1)
    old_sel = jnp.take_along_axis(ids, old_pos, axis=1)
    # Positions whose logit was -inf were invalid picks.
    new_sel = jnp.where(jnp.take_along_axis(new_logit, new_pos, axis=1) == -jnp.inf, -1, new_sel)
    old_sel = jnp.where(jnp.take_along_axis(old_logit, old_pos, axis=1) == -jnp.inf, -1, old_sel)
    # Mark the drawn new entries as sampled.
    rows = jnp.arange(n, dtype=jnp.int32)[:, None]
    sampled = sampled.at[rows, new_pos].set(True)
    return jnp.concatenate([new_sel, old_sel], axis=1), sampled


def build(
    dataset,
    params: Optional[NNDescentParams] = None,
    res: Optional[Resources] = None,
    **kwargs,
) -> NNDescentOutput:
    """Build an approximate kNN graph (``nn_descent::build``,
    ``detail/nn_descent.cuh:1215``).

    With :mod:`raft_tpu.obs` enabled the build is wrapped in a
    device-synced ``nn_descent.build`` span with call/row counters and
    an iterations-to-convergence histogram."""
    if params is None:
        params = NNDescentParams(**kwargs)
    if not obs.active():
        return _build_impl(dataset, params, res)
    n = int(np.shape(dataset)[0])
    obs.inc("nn_descent.build.calls")
    obs.inc("nn_descent.build.rows", float(n))
    with obs.span(
        "nn_descent.build", n=n, graph_degree=params.graph_degree,
        intermediate=params.intermediate_graph_degree,
    ) as sp:
        out = _build_impl(dataset, params, res)
        sp.sync((out.graph, out.distances))
        return out


def _build_impl(
    dataset, params: NNDescentParams, res: Optional[Resources]
) -> NNDescentOutput:
    res = ensure_resources(res)
    metric = resolve_metric(params.metric)
    expects(metric in _SUPPORTED, "nn_descent does not support metric %s", metric)
    dataset = jnp.asarray(dataset)
    expects(dataset.ndim == 2, "dataset must be [n_rows, dim]")
    n, d = dataset.shape
    gd = params.graph_degree
    k = max(params.intermediate_graph_degree, gd)
    expects(gd >= 1, "graph_degree must be >= 1")
    expects(k < n, "graph degree %d must be < n_rows %d", k, n)

    data = dataset.astype(jnp.float32)
    if metric == DistanceType.CosineExpanded:
        # cosine ranking == L2 ranking on unit vectors; distances converted
        # at the end (1 - cos = L2^2 / 2 on the unit sphere).
        data = data / jnp.maximum(jnp.linalg.norm(data, axis=1, keepdims=True), 1e-12)
    select_min = metric != DistanceType.InnerProduct
    sqnorms = jnp.sum(data * data, axis=1)

    key = as_key(params.seed)
    key, k_init = jax.random.split(key)

    # -- random initial graph (GNND's random init) --------------------------
    init_ids = jax.random.randint(k_init, (n, k), 0, n, dtype=jnp.int32)
    rows = jnp.arange(n, dtype=jnp.int32)[:, None]
    init_ids = jnp.where(init_ids == rows, (init_ids + 1) % n, init_ids)

    worst = jnp.asarray(worst_value(jnp.float32, select_min), jnp.float32)
    chunk = max(256, params.node_chunk)

    def merge_candidates(acc_v, acc_i, acc_f, cand_of_chunk):
        out_v, out_i, out_f = [], [], []
        for s in range(0, n, chunk):
            c = cand_of_chunk(s)
            v, i, f = _score_and_merge(
                data, sqnorms, c,
                acc_v[s : s + chunk], acc_i[s : s + chunk], acc_f[s : s + chunk],
                jnp.int32(s), k=k, select_min=select_min,
            )
            out_v.append(v)
            out_i.append(i)
            out_f.append(f)
        return (
            jnp.concatenate(out_v, axis=0),
            jnp.concatenate(out_i, axis=0),
            jnp.concatenate(out_f, axis=0),
        )

    acc_v = jnp.full((n, k), worst, jnp.float32)
    acc_i = jnp.full((n, k), -1, jnp.int32)
    sampled = jnp.zeros((n, k), bool)  # everything new (never sampled)
    acc_v, acc_i, sampled = merge_candidates(
        acc_v, acc_i, sampled, lambda s: init_ids[s : s + chunk]
    )

    half = max(1, min(params.max_samples // 2, k))

    @functools.partial(jax.jit, static_argnames=())
    def _two_hop_chunk(sym, sym_c):
        # candidates(u) = P(P(u)) for one row chunk — expanding per chunk
        # keeps the [chunk, 4h, 4h] gather small (the full [n, 4h, 4h]
        # tensor is 4*n*half^2 ints and blows HBM at 1M rows)
        safe_c = jnp.clip(sym_c, 0, None)
        cand = jnp.where(sym_c[:, :, None] >= 0, sym[safe_c], -1)
        cand = cand.reshape(sym_c.shape[0], -1)
        return jnp.concatenate([cand, sym_c], axis=1)  # include one-hop too

    for it in range(params.max_iterations):
        key, k_sample = jax.random.split(key)
        pool, sampled = _sample_pool(k_sample, acc_i, sampled, half=half)
        rev = reverse_edges(pool, n, 2 * half)
        sym = jnp.concatenate([pool, rev], axis=1)  # [n, 4*half]

        prev_i = acc_i
        acc_v, acc_i, sampled = merge_candidates(
            acc_v, acc_i, sampled, lambda s: _two_hop_chunk(sym, sym[s : s + chunk])
        )

        # update rate = fraction of entries not present before (sorted lookup)
        prev_sorted = jnp.sort(prev_i, axis=1)
        pos = jax.vmap(lambda ps, ai: jnp.searchsorted(ps, ai))(prev_sorted, acc_i)
        found = jnp.take_along_axis(prev_sorted, jnp.clip(pos, 0, k - 1), axis=1) == acc_i
        new_mask = (~found) & (acc_i >= 0)
        update_rate = float(jnp.mean(new_mask.astype(jnp.float32)))
        if update_rate < params.termination_threshold:
            break

    if obs.is_enabled() and params.max_iterations > 0:
        obs.observe("nn_descent.build.iterations", float(it + 1))
    graph = acc_i[:, :gd]
    dists = acc_v[:, :gd]
    if metric == DistanceType.L2SqrtExpanded:
        dists = jnp.where(graph >= 0, jnp.sqrt(jnp.maximum(dists, 0.0)), dists)
    elif metric == DistanceType.CosineExpanded:
        dists = jnp.where(graph >= 0, 0.5 * dists, dists)
    return NNDescentOutput(graph=graph, distances=dists, metric=metric)
