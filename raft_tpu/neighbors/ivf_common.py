"""Shared IVF list machinery — analog of the reference's shared per-list
storage helpers (``neighbors/ivf_list.hpp``, ``ivf_list_types.hpp``,
``neighbors/ivf_flat_codepacker.hpp``), used by both IVF-Flat and IVF-PQ.

TPU-first layout: every list lives in ONE dense padded tensor
``[n_lists, max_list, ...]`` (the CUDA 32-row interleave dissolves into
sublane-padded dense tiles XLA can feed the MXU directly). The pieces here
solve the two problems that layout creates:

* **Capacity-capped assignment** (:func:`assign_slots`): one crowded
  cluster must not inflate ``max_list`` (and with it every list's stride).
  Rows overflowing their nearest list spill to their second-nearest and,
  in the rare case that is also full, to any free slot — bounding padding
  waste at ``cap_factor``× the mean list size.
* **On-device packing** (:func:`scatter_rows`): packing is sorts +
  scatters on the accelerator; the only host sync is one scalar (the
  ``max_list`` shape decision). Round 2 packed on host, which cost
  minutes of dataset transfer per build on tethered-TPU links.
"""
from __future__ import annotations

import functools
import math
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from raft_tpu.ops.distance import DistanceType
from raft_tpu.utils.math import round_up


def coarse_scores(centers, qf, metric, row_norm: float = 0.0) -> jax.Array:
    """[nq, n_lists] coarse scores, smaller = better — the shared
    ``select_clusters`` ranking (``ivf_pq_search.cuh:67``) used by the
    probe mask, the fused Pallas path, and IVF-PQ. For cosine, ``qf`` must
    already be unit-normalized (centers trained on normalized data).
    ``row_norm`` is the index's :func:`one_norm` (see
    :func:`coarse_from_dots`)."""
    return coarse_from_dots(qf @ centers.T, centers, qf, metric, row_norm)


def coarse_from_dots(q_dot_c, centers, qf, metric, row_norm: float = 0.0) -> jax.Array:
    """:func:`coarse_scores` from the ``[nq, n_lists]`` products ``q.c``.

    L2 and cosine rank by ``|c|^2 - 2 q.c`` (rankwise the L2 distance to
    the center) and inner product by ``-q.c``, as ``select_clusters``
    does. One case departs from it: an inner-product index whose rows all
    have one norm ``s = row_norm > 0``. The build places every row by L2
    (:func:`topk_labels`), and the inner-product order of rows of norm
    ``s`` is their L2 order from the point ``s q / |q|``; so such an index ranks
    its lists by the L2 distance from that point, ``|c|^2 - 2 s q.c /
    |q|``, and a query probes the lists its best rows were placed in.
    Ranked by ``q.c``, it passes over the low-norm centers that hold the
    rows that overflowed their own list. Rows of differing norms keep
    ``-q.c``: L2 placement says little about their inner products, and
    the L2 score would favour low-norm lists."""
    if metric == DistanceType.InnerProduct:
        if not row_norm:
            return -q_dot_c
        qn = jnp.linalg.norm(qf, axis=1, keepdims=True)
        q_dot_c = q_dot_c * (row_norm / jnp.maximum(qn, 1e-30))
    return jnp.sum(centers * centers, axis=1)[None, :] - 2.0 * q_dot_c


#: widest relative spread of row norms, ``(max - min) / max``, under which
#: :func:`one_norm` counts a dataset's rows as of one norm
ONE_NORM_RTOL = 1e-2


def one_norm(data) -> float:
    """The norm every row of ``data`` has, to :data:`ONE_NORM_RTOL`
    (their largest), or 0.0 when the rows differ in norm. One host sync
    of two scalars."""
    sq = jnp.sum(jnp.square(data.astype(jnp.float32)), axis=1)
    lo, hi = (float(v) for v in jax.device_get((jnp.min(sq), jnp.max(sq))))
    if hi <= 0.0 or math.sqrt(hi) - math.sqrt(lo) > ONE_NORM_RTOL * math.sqrt(hi):
        return 0.0
    return math.sqrt(hi)


def probe_selection(centers, qf, n_probes: int, metric,
                    row_norm: float = 0.0) -> Tuple[jax.Array, jax.Array]:
    """``(coarse [nq, n_lists], probed [nq, n_lists] bool)`` — the shared
    coarse ranking plus the per-query probe mask (``select_clusters``,
    ``ivf_flat_search-inl.cuh:145``). Single home for probe selection so
    the scan, probe, and fused paths cannot diverge."""
    nq = qf.shape[0]
    n_lists = centers.shape[0]
    coarse = coarse_scores(centers, qf, metric, row_norm)
    if n_probes < n_lists:
        probes = probe_lists(coarse, n_probes)
        probed = jnp.zeros((nq, n_lists), bool).at[jnp.arange(nq)[:, None], probes].set(True)
    else:
        probed = jnp.ones((nq, n_lists), bool)
    return coarse, probed


def probe_lists(coarse, n_probes: int) -> jax.Array:
    """``[nq, n_probes]`` the lists each query probes, nearest first, from
    :func:`coarse_scores` — the selection :func:`probe_selection` masks;
    every list when ``n_probes`` covers them all."""
    from raft_tpu.ops.select_k import select_k

    nq, n_lists = coarse.shape
    if n_probes < n_lists:
        return select_k(coarse, n_probes, select_min=True)[1]
    return jnp.broadcast_to(jnp.arange(n_lists, dtype=jnp.int32), (nq, n_lists))


#: elements of the ``[rows, n_lists]`` score block :func:`topk_labels`
#: holds at once (512 MiB of f32): 131,072 rows at 1,024 lists, 16,384 at
#: 8,192
TOPK_BLOCK_ELEMS = 131072 * 1024


@functools.partial(jax.jit, static_argnames=("k", "block"))
def _topk_labels(x, centers, *, k: int, block: int):
    """One program over ``block``-row slices of ``x``; the last slice
    ends at the last row (overlapping its predecessor), so every slice
    has the same shape and no padded copy of ``x`` is made."""
    n = x.shape[0]
    cn = jnp.sum(centers * centers, axis=1)

    def body(b, out):
        s = jnp.minimum(b * block, n - block)
        xb = lax.dynamic_slice_in_dim(x, s, block)
        score = 2.0 * (xb @ centers.T) - cn[None, :]  # max == nearest
        _, idx = lax.top_k(score, k)
        return lax.dynamic_update_slice_in_dim(out, idx.astype(jnp.int32), s, axis=0)

    return lax.fori_loop(0, -(-n // block), body, jnp.zeros((n, k), jnp.int32))


def topk_labels(ds_f32: jax.Array, centers: jax.Array, k: int = 4) -> jax.Array:
    """Per-row k nearest center ids ``[n, k]`` — rankwise L2 via the norm
    trick, in row blocks of ``TOPK_BLOCK_ELEMS // n_lists`` rows, so the
    ``[block, n_lists]`` scores are the peak temporary whatever the
    number of lists."""
    n, n_lists = ds_f32.shape[0], centers.shape[0]
    k = min(k, n_lists)
    block = max(1, min(n, TOPK_BLOCK_ELEMS // n_lists))
    return _topk_labels(ds_f32, centers, k=k, block=block)


def assign_slots(cand_labels, *, n_lists: int, max_list: int) -> jax.Array:
    """Flat destination slot per row in the padded layout (list-major).

    ``cand_labels [n, c]`` ranks each row's candidate lists nearest-first.
    One pass per candidate column (nearest list while it has room, then the
    next candidate, ...), then a final pass dropping stragglers into any
    free slot. A pass sorts only the rows still unplaced, and the passes
    stop once every row is placed. Returns ``slot [n] int32`` with every
    row placed (requires ``n <= n_lists * max_list``); the final list of
    a row is ``slot // max_list``.

    Runs in numpy on the host (one ``[n, c]`` fetch). The same passes as
    one XLA program took 83 s to compile for a v5e at 1M rows and 1086 s
    at 4M (the sorts and scans compile in time that grows with ``n``),
    past the four-chip smoke's whole time limit (PERF.md, PR 21); here
    they take under half a second per million rows.
    """
    cand = np.asarray(cand_labels)
    n, n_cand = cand.shape
    total = n_lists * max_list

    def rank_within(lab):
        """Stable rank of each entry of ``lab`` within its label group."""
        order = np.argsort(lab, kind="stable")
        counts = np.bincount(lab, minlength=n_lists)
        start = np.cumsum(counts) - counts
        rank = np.empty(lab.size, np.int64)
        rank[order] = np.arange(lab.size) - start[lab[order]]
        return rank

    slot = np.full(n, total, np.int64)
    placed = np.zeros(n, bool)
    used = np.zeros(n_lists, np.int64)
    for c in range(n_cand):
        rows = np.flatnonzero(~placed)
        if rows.size == 0:
            break
        lc = cand[rows, c]
        dest = used[lc] + rank_within(lc)
        fits = dest < max_list
        slot[rows[fits]] = lc[fits] * max_list + dest[fits]
        used += np.bincount(lc[fits], minlength=n_lists)
        placed[rows[fits]] = True

    # final pass: leftovers, in row order, into the free slots in order
    filled = np.zeros(total, np.int8)
    filled[slot[placed]] = 1
    free_slots = np.flatnonzero(filled == 0)
    left = np.flatnonzero(~placed)
    slot[left] = free_slots[:left.size]
    return jnp.asarray(slot.astype(np.int32))


def choose_max_list(l1, n: int, n_lists: int, cap_factor: float) -> int:
    """Pick the static ``max_list`` (ONE scalar device→host fetch)."""
    counts = jnp.zeros((n_lists,), jnp.int32).at[l1].add(1)
    max_count = int(jnp.max(counts))
    cap = max_count
    if cap_factor > 0:
        cap = min(cap, int(math.ceil(cap_factor * n / n_lists)))
    cap = max(cap, int(math.ceil(n / n_lists)))  # capacity for every row
    if cap >= 512:
        # Lane-align big lists: the fused Pallas scan compresses scores in
        # 128-lane groups, and a non-multiple max_list forces a full
        # score-matrix pad copy EVERY probe step (measured ~25% of step
        # time on v5e). +>=6% padding rows is cheap next to that.
        return round_up(cap, 128)
    return max(8, round_up(cap, 8))


def pack_rows(
    rows, ids, cand_labels, n_lists: int, cap_factor: float
) -> Tuple[jax.Array, jax.Array, jax.Array, int]:
    """assign_slots + scatter_rows with the max_list decision in between."""
    max_list = choose_max_list(cand_labels[:, 0], rows.shape[0], n_lists, cap_factor)
    slot = assign_slots(cand_labels, n_lists=n_lists, max_list=max_list)
    data, idx, sizes = scatter_rows(rows, ids, slot, n_lists=n_lists, max_list=max_list)
    return data, idx, sizes, max_list


@functools.partial(jax.jit, static_argnames=("n_lists", "max_list"))
def scatter_rows(rows, ids, slot, *, n_lists: int, max_list: int):
    """Scatter per-row payloads + ids into the padded layout. Returns
    ``(data [n_lists, max_list, d], indices [n_lists, max_list],
    sizes [n_lists])``."""
    d = rows.shape[1]
    total = n_lists * max_list
    flat_data = (jnp.zeros((total + 1, d), rows.dtype).at[slot].set(rows, mode="drop"))[:total]
    flat_ids = (jnp.full((total + 1,), -1, jnp.int32).at[slot].set(ids, mode="drop"))[:total]
    flat_ids = flat_ids.reshape(n_lists, max_list)
    sizes = jnp.sum((flat_ids >= 0).astype(jnp.int32), axis=1)
    return flat_data.reshape(n_lists, max_list, d), flat_ids, sizes
