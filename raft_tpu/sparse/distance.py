"""Sparse pairwise distances + sparse brute-force kNN — analog of
``raft/sparse/distance/distance.cuh:69`` (``pairwiseDistance``) and
``raft/sparse/neighbors/brute_force.cuh``.

TPU-first, two regimes:

* **Block densification** (moderate ``n_cols``): densify row *blocks* into
  VPU/MXU tiles and reuse the dense engine — HBM traffic is the same order
  once rows are touched, and the MXU does the rest.
* **Native CSR** (``n_cols`` too wide to densify — the genuinely-sparse
  regime the reference's CSR walkers target): the expanded-form metrics
  (inner product, cosine, L2, hellinger, jaccard, dice) only need the
  sparse-sparse gram ``X @ Y^T`` plus per-row statistics. The gram is a
  **padded-row sort-merge**: rows padded to the max nnz/row, and each
  (x-row, y-row) intersection found with a vmapped ``searchsorted`` over
  the y row's (sorted) column ids — O(r log r) per pair instead of O(d),
  entirely gather/compare VPU work, memory bounded by the pair-block
  size. This replaces the reference's hash-table/bloom load-balanced CSR
  kernels (``sparse/distance/detail/lp_distance.cuh``): TPUs have no
  cheap random scatter, but batched binary search vectorizes perfectly.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from raft_tpu.core.errors import expects
from raft_tpu.ops.distance import (
    DistanceType,
    js_term,
    kl_term,
    pairwise_distance,
    resolve_metric,
)
from raft_tpu.ops.select_k import running_merge, select_k, worst_value
from raft_tpu.sparse.types import CSR

# metrics expressible as f(gram, row stats) — the gram native-CSR set
_NATIVE_GRAM = frozenset(
    {
        DistanceType.InnerProduct,
        DistanceType.CosineExpanded,
        DistanceType.L2Expanded,
        DistanceType.L2SqrtExpanded,
        DistanceType.HellingerExpanded,
        DistanceType.JaccardExpanded,
        DistanceType.DiceExpanded,
    }
)
# metrics needing the UNION of nonzero columns (|a-b| family) — covered
# by the same padded-row sort-merge, accumulating elementwise terms over
# x-side matches plus unmatched y-side entries (the reference computes
# these with its load-balanced CSR walkers, sparse/distance/detail/
# lp_distance.cuh / l2_distance.cuh)
_NATIVE_UNION = frozenset(
    {
        DistanceType.L1,
        DistanceType.Linf,
        DistanceType.Canberra,
        DistanceType.LpUnexpanded,
        DistanceType.L2Unexpanded,
        DistanceType.L2SqrtUnexpanded,
        DistanceType.HammingUnexpanded,
        DistanceType.BrayCurtis,
        DistanceType.KLDivergence,
        DistanceType.JensenShannon,
    }
)
_NATIVE = _NATIVE_GRAM | _NATIVE_UNION


def _plan_sparse(n_cols: int, metric) -> str:
    """Resolve ``mode="auto"``: densify vs native-CSR by
    :func:`raft_tpu.plan.plan_sparse_mode`."""
    from raft_tpu import plan as _plan

    return _plan.plan_sparse_mode(n_cols, native_ok=metric in _NATIVE)


def _densify_rows(a: CSR, start: int, count: int, rows=None) -> jax.Array:
    """Dense [count, n_cols] block of CSR rows [start, start+count);
    ``rows`` is the precomputed ``a.row_ids()`` (hoist it out of block
    loops — it is a searchsorted over the full nnz axis)."""
    n_rows, n_cols = a.shape
    if rows is None:
        rows = a.row_ids()
    within = rows - start
    keep = (within >= 0) & (within < count)
    r = jnp.where(keep, within, count)  # OOB -> dropped
    c = jnp.where(keep, a.indices, 0)
    out = jnp.zeros((count, n_cols), a.vals.dtype)
    return out.at[r, c].add(jnp.where(keep, a.vals, 0), mode="drop")


def _csr_padded_rows(a: CSR, pad_sentinel: int):
    """CSR -> (col_ids [m, r], vals [m, r]) padded to the max row nnz;
    padding columns get ``pad_sentinel`` (beyond any real column id, so
    sorted order is preserved and sentinels never match)."""
    m = a.shape[0]
    indptr = np.asarray(a.indptr)
    counts = np.diff(indptr)
    r = max(1, int(counts.max()) if m else 1)
    rows = a.row_ids()
    within = jnp.arange(a.nnz, dtype=jnp.int32) - a.indptr[rows]
    idx = jnp.full((m, r), pad_sentinel, jnp.int32)
    val = jnp.zeros((m, r), jnp.float32)
    idx = idx.at[rows, within].set(a.indices.astype(jnp.int32))
    val = val.at[rows, within].set(a.vals.astype(jnp.float32))
    return idx, val


@jax.jit
def _gram_block(xi, xv, yi, yv):
    """Sparse-sparse gram of padded row blocks: ``[mi, nj]`` of
    ``sum_a xv[i,a] * yv[j, pos]`` where pos = the binary-search match of
    x's column in y's sorted columns."""

    def one_y(yrow_i, yrow_v):
        pos = jnp.clip(jnp.searchsorted(yrow_i, xi), 0, yrow_i.shape[0] - 1)  # [mi, r1]
        hit = yrow_i[pos] == xi
        return jnp.sum(jnp.where(hit, xv * yrow_v[pos], 0.0), axis=1)  # [mi]

    return jnp.transpose(jax.vmap(one_y)(yi, yv))  # [mi, nj]


@functools.partial(jax.jit, static_argnames=("kind", "use_max"))
def _union_block(xi, xv, yi, yv, kind, use_max, p):
    """Union-of-nonzeros accumulation over padded row blocks: ``[mi, nj]``
    of ``reduce_c term(x[i,c], y[j,c])`` over every column where either
    row is nonzero. Terms vanish at (0, 0), so the union decomposes as
    (x entries, matched-or-zero y) + (unmatched y entries, zero x) — both
    sides found with batched binary search; padding sentinels never match
    and their (0, 0) terms are guarded to 0."""

    def term(a, b):
        ad = jnp.abs(a - b)
        if kind == "l1" or kind == "linf":
            return ad
        if kind == "lp":
            return ad**p
        if kind == "canberra":
            den = jnp.abs(a) + jnp.abs(b)
            return jnp.where(den > 0.0, ad / jnp.where(den > 0.0, den, 1.0), 0.0)
        if kind == "kl":
            # (0, b) terms vanish, so the union's y-only side is free
            return kl_term(a, b)
        if kind == "js":
            return js_term(a, b)
        return (a != b).astype(jnp.float32)  # hamming

    def one_y(yrow_i, yrow_v):
        r2 = yrow_i.shape[0]
        pos = jnp.clip(jnp.searchsorted(yrow_i, xi), 0, r2 - 1)  # [mi, r1]
        hit = yrow_i[pos] == xi
        b = jnp.where(hit, yrow_v[pos], 0.0)
        # y entries with no x match: one searchsorted per x row
        pos2 = jax.vmap(lambda xrow: jnp.searchsorted(xrow, yrow_i))(xi)  # [mi, r2]
        pos2 = jnp.clip(pos2, 0, xi.shape[1] - 1)
        hit2 = jnp.take_along_axis(xi, pos2, axis=1) == yrow_i[None, :]
        if kind == "bc":
            # braycurtis needs sum|a-b| AND sum|a+b| — one merge, two
            # channels (the match work dominates; don't do it twice)
            num = jnp.sum(jnp.abs(xv - b), axis=1) + jnp.sum(
                jnp.where(hit2, 0.0, jnp.abs(yrow_v)[None, :]), axis=1
            )
            den = jnp.sum(jnp.abs(xv + b), axis=1) + jnp.sum(
                jnp.where(hit2, 0.0, jnp.abs(yrow_v)[None, :]), axis=1
            )
            return jnp.stack([num, den])  # [2, mi]
        left = term(xv, b)  # [mi, r1]; padding x rows give term(0,0)=0
        right = jnp.where(hit2, 0.0, term(0.0, yrow_v)[None, :])  # [mi, r2]
        if use_max:
            return jnp.maximum(jnp.max(left, axis=1), jnp.max(right, axis=1))
        return jnp.sum(left, axis=1) + jnp.sum(right, axis=1)

    out = jax.vmap(one_y)(yi, yv)  # [nj, mi] or [nj, 2, mi]
    if kind == "bc":
        return jnp.transpose(out, (2, 0, 1))  # [mi, nj, 2]
    return jnp.transpose(out)  # [mi, nj]


def _union_accumulate(
    x: CSR, y: CSR, kind: str, use_max: bool = False, p: float = 2.0, pair_block: int = 512
) -> jax.Array:
    """Blocked [m, n] union accumulation (see :func:`_union_block`)."""
    expects(x.shape[1] == y.shape[1], "feature dim mismatch")
    xi, xv = _csr_padded_rows(x, x.shape[1] + 2)  # distinct sentinels never match
    yi, yv = _csr_padded_rows(y, x.shape[1] + 1)
    m, n = x.shape[0], y.shape[0]
    p = jnp.float32(p)
    outs = []
    for s in range(0, m, pair_block):
        row = []
        for t in range(0, n, pair_block):
            row.append(
                _union_block(
                    xi[s : s + pair_block], xv[s : s + pair_block],
                    yi[t : t + pair_block], yv[t : t + pair_block],
                    kind, use_max, p,
                )
            )
        outs.append(jnp.concatenate(row, axis=1) if len(row) > 1 else row[0])
    return jnp.concatenate(outs, axis=0) if len(outs) > 1 else outs[0]


def sparse_gram(x: CSR, y: CSR, transform=None, pair_block: int = 512) -> jax.Array:
    """Dense [m, n] gram ``X @ Y^T`` of two CSR matrices WITHOUT
    densifying the feature axis. ``transform`` optionally maps values
    (e.g. ``jnp.sqrt`` for hellinger, ``lambda v: (v != 0)`` for binary
    metrics) before the products."""
    expects(x.shape[1] == y.shape[1], "feature dim mismatch")
    sent_y = x.shape[1] + 1
    xi, xv = _csr_padded_rows(x, x.shape[1] + 2)  # distinct sentinels never match
    yi, yv = _csr_padded_rows(y, sent_y)
    if transform is not None:
        xv, yv = transform(xv), transform(yv)
    m, n = x.shape[0], y.shape[0]
    outs = []
    for s in range(0, m, pair_block):
        row = []
        for t in range(0, n, pair_block):
            row.append(
                _gram_block(
                    xi[s : s + pair_block], xv[s : s + pair_block],
                    yi[t : t + pair_block], yv[t : t + pair_block],
                )
            )
        outs.append(jnp.concatenate(row, axis=1) if len(row) > 1 else row[0])
    return jnp.concatenate(outs, axis=0) if len(outs) > 1 else outs[0]


def _row_stat(a: CSR, fn) -> jax.Array:
    """Per-row reduction over CSR values (no densify)."""
    return jax.ops.segment_sum(fn(a.vals.astype(jnp.float32)), a.row_ids(), num_segments=a.shape[0])


def pairwise_distance_sparse_native(
    x: CSR,
    y: CSR,
    metric=DistanceType.L2Expanded,
    pair_block: int = 512,
    metric_arg: float = 2.0,
) -> jax.Array:
    """Native-CSR metrics (``sparse/distance/distance.cuh:69``) — never
    materializes a dense feature axis, so arbitrarily wide matrices work.
    The gram family (inner product, cosine, L2, hellinger, jaccard, dice)
    reduces to the sort-merge gram + row stats; the |a-b| family (L1,
    Linf, Canberra, Lp, unexpanded L2, Hamming, BrayCurtis) uses the same
    machinery with a union-of-nonzeros accumulation (the reference's
    load-balanced CSR walkers, ``detail/lp_distance.cuh``)."""
    metric = resolve_metric(metric)
    expects(metric in _NATIVE, "metric %s has no native CSR path", metric)
    if metric in _NATIVE_UNION:
        d_cols = x.shape[1]
        if metric == DistanceType.L1:
            return _union_accumulate(x, y, "l1", pair_block=pair_block)
        if metric == DistanceType.Linf:
            return _union_accumulate(x, y, "linf", use_max=True, pair_block=pair_block)
        if metric == DistanceType.Canberra:
            return _union_accumulate(x, y, "canberra", pair_block=pair_block)
        if metric == DistanceType.LpUnexpanded:
            acc = _union_accumulate(x, y, "lp", p=metric_arg, pair_block=pair_block)
            return acc ** (1.0 / metric_arg)
        if metric in (DistanceType.L2Unexpanded, DistanceType.L2SqrtUnexpanded):
            acc = _union_accumulate(x, y, "lp", p=2.0, pair_block=pair_block)
            return jnp.sqrt(acc) if metric == DistanceType.L2SqrtUnexpanded else acc
        if metric == DistanceType.HammingUnexpanded:
            return _union_accumulate(x, y, "hamming", pair_block=pair_block) / d_cols
        if metric == DistanceType.KLDivergence:
            return _union_accumulate(x, y, "kl", pair_block=pair_block)
        if metric == DistanceType.JensenShannon:
            acc = _union_accumulate(x, y, "js", pair_block=pair_block)
            return jnp.sqrt(jnp.maximum(0.5 * acc, 0.0))
        bc = _union_accumulate(x, y, "bc", pair_block=pair_block)  # braycurtis
        num, den = bc[..., 0], bc[..., 1]
        return jnp.where(den == 0.0, 0.0, num / jnp.where(den == 0.0, 1.0, den))
    if metric == DistanceType.HellingerExpanded:
        g = sparse_gram(x, y, transform=jnp.sqrt, pair_block=pair_block)
        return jnp.sqrt(jnp.maximum(1.0 - g, 0.0))
    dot = sparse_gram(x, y, pair_block=pair_block)
    if metric == DistanceType.InnerProduct:
        return dot
    if metric in (DistanceType.L2Expanded, DistanceType.L2SqrtExpanded):
        xn = _row_stat(x, jnp.square)
        yn = _row_stat(y, jnp.square)
        d2 = jnp.maximum(xn[:, None] + yn[None, :] - 2.0 * dot, 0.0)
        return jnp.sqrt(d2) if metric == DistanceType.L2SqrtExpanded else d2
    if metric == DistanceType.CosineExpanded:
        xn = jnp.sqrt(_row_stat(x, jnp.square))
        yn = jnp.sqrt(_row_stat(y, jnp.square))
        denom = xn[:, None] * yn[None, :]
        return 1.0 - dot / jnp.where(denom == 0.0, 1.0, denom)
    sx = _row_stat(x, lambda v: v)
    sy = _row_stat(y, lambda v: v)
    if metric == DistanceType.JaccardExpanded:
        union = sx[:, None] + sy[None, :] - dot
        sim = jnp.where(union == 0.0, 0.0, dot / jnp.where(union == 0.0, 1.0, union))
        return 1.0 - sim
    denom = sx[:, None] + sy[None, :]  # dice
    sim = jnp.where(denom == 0.0, 0.0, 2.0 * dot / jnp.where(denom == 0.0, 1.0, denom))
    return 1.0 - sim


def pairwise_distance_sparse(
    x: CSR,
    y: CSR,
    metric=DistanceType.L2Expanded,
    metric_arg: float = 2.0,
    block: int = 1024,
    mode: str = "auto",
) -> jax.Array:
    """Full [m, n] distance matrix between CSR row sets
    (``sparse/distance/distance.cuh:69``); every metric of the dense
    engine via block densification, plus a native-CSR path for the
    expanded (gram-based) metrics. ``mode``: ``"auto"`` picks native when
    the feature axis is too wide to densify sanely (> 2^18 columns) and
    the metric supports it; ``"densify"`` / ``"native"`` force a path."""
    metric = resolve_metric(metric)
    expects(x.shape[1] == y.shape[1], "feature dim mismatch")
    expects(mode in ("auto", "densify", "native"), "bad mode %r", mode)
    if mode == "auto":
        mode = _plan_sparse(x.shape[1], metric)
    if mode == "native":
        return pairwise_distance_sparse_native(x, y, metric, metric_arg=metric_arg)
    m = x.shape[0]
    x_rows = x.row_ids()
    y_rows = y.row_ids()
    yd = _densify_rows(y, 0, y.shape[0], y_rows) if y.shape[0] <= block else None
    outs = []
    for s in range(0, m, block):
        cnt = min(block, m - s)
        xb = _densify_rows(x, s, cnt, x_rows)
        if yd is not None:
            outs.append(pairwise_distance(xb, yd, metric, metric_arg))
        else:
            row_parts = []
            for t in range(0, y.shape[0], block):
                ycnt = min(block, y.shape[0] - t)
                row_parts.append(
                    pairwise_distance(xb, _densify_rows(y, t, ycnt, y_rows), metric, metric_arg)
                )
            outs.append(jnp.concatenate(row_parts, axis=1))
    return jnp.concatenate(outs, axis=0)


def knn_sparse(
    x: CSR,
    y: CSR,
    k: int,
    metric=DistanceType.L2Expanded,
    metric_arg: float = 2.0,
    block: int = 1024,
    mode: str = "auto",
) -> Tuple[jax.Array, jax.Array]:
    """Sparse brute-force kNN (``sparse/neighbors/brute_force.cuh``):
    block distances + running top-k merge. Returns (dists, ids) of y-rows
    nearest to each x-row. ``mode`` as in :func:`pairwise_distance_sparse`
    — ``"native"`` (or auto on very wide matrices) computes distances from
    the sort-merge gram without densifying the feature axis."""
    metric = resolve_metric(metric)
    from raft_tpu.ops.distance import is_min_close

    select_min = is_min_close(metric)
    n = y.shape[0]
    m = x.shape[0]
    expects(0 < k <= n, "k out of range")
    worst = jnp.float32(worst_value(jnp.float32, select_min))

    expects(mode in ("auto", "densify", "native"), "bad mode %r", mode)
    if mode == "auto":
        mode = _plan_sparse(x.shape[1], metric)
    if mode == "native":
        d = pairwise_distance_sparse_native(x, y, metric, metric_arg=metric_arg)
        return select_k(d, k, select_min=select_min)

    x_rows = x.row_ids()
    y_rows = y.row_ids()
    out_v, out_i = [], []
    for s in range(0, m, block):
        cnt = min(block, m - s)
        xb = _densify_rows(x, s, cnt, x_rows)
        acc_v = jnp.full((cnt, k), worst, jnp.float32)
        acc_i = jnp.full((cnt, k), -1, jnp.int32)
        for t in range(0, n, block):
            ycnt = min(block, n - t)
            d = pairwise_distance(xb, _densify_rows(y, t, ycnt, y_rows), metric, metric_arg)
            ids = t + jnp.arange(ycnt, dtype=jnp.int32)[None, :].repeat(cnt, axis=0)
            if ycnt >= k:
                dv, di = select_k(d, k, select_min=select_min, indices=ids)
            else:
                dv, di = d, ids
            acc_v, acc_i = running_merge(acc_v, acc_i, dv, di, select_min=select_min)
        out_v.append(acc_v)
        out_i.append(acc_i)
    return jnp.concatenate(out_v, axis=0), jnp.concatenate(out_i, axis=0)
