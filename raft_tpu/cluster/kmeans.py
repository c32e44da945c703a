"""K-means (Lloyd) with k-means++ init — analog of ``raft::cluster::kmeans``.

Reference: ``cluster/kmeans.cuh:89`` (``kmeans::fit``), params struct
``cluster/kmeans_types.hpp:38-70``, EM loop ``cluster/detail/kmeans.cuh:362``
(``kmeans_fit_main``), ``kmeansPlusPlus`` (``:91``), ``update_centroids``
(``:288``).

TPU design notes:

* The EM loop runs entirely on-device in ``lax.while_loop`` — the reference
  pays a device→host sync per iteration for its convergence check
  (``kmeans.cuh:440-455``); here the inertia/shift test is part of the loop
  carry, so there is no per-iteration ping-pong.
* The E step is the fused distance+argmin scan
  (:func:`raft_tpu.ops.fused_1nn.min_cluster_and_distance`) — [n, k]
  distances are never materialized.
* The M step is a ``segment_sum`` (XLA scatter-add), the
  ``reduce_rows_by_key`` analog.
* k-means++ seeding draws one center per ``fori_loop`` step via the
  categorical-from-min-distance trick, all on-device.
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
from raft_tpu.ops.distance import DistanceType, resolve_metric, row_norms
from raft_tpu.ops.fused_1nn import min_cluster_and_distance
from raft_tpu.random.rng import as_key
from raft_tpu.utils.math import cdiv


@dataclasses.dataclass
class KMeansParams:
    """``cluster/kmeans_types.hpp:38-70`` analog."""

    n_clusters: int = 8
    max_iter: int = 300
    tol: float = 1e-4
    init: str = "kmeans++"  # "kmeans++" | "random" | "array"
    n_init: int = 1
    metric: DistanceType = DistanceType.L2Expanded
    seed: int = 0
    oversampling_factor: float = 2.0  # kept for param parity; unused by Lloyd
    batch_samples: int = 1 << 15  # kept for param parity; the E step is
    #   already memory-bounded by the fused argmin scan, so no batching knob
    algorithm: str = "lloyd"  # "lloyd" | "flash" (Flash-KMeans exact E step)


@dataclasses.dataclass
class KMeansOutput:
    centroids: jax.Array  # [k, d] f32
    labels: jax.Array  # [n] i32
    inertia: jax.Array  # scalar f32
    n_iter: jax.Array  # scalar i32


def kmeans_plus_plus(key, X: jax.Array, k: int, sample_weights=None) -> jax.Array:
    """Greedy k-means++ seeding (``cluster/detail/kmeans.cuh:91``
    kmeansPlusPlus): the first center sampled by weight; each next one the
    best of ``2 + log(k)`` candidates sampled with probability proportional
    to (weighted) squared distance to the nearest chosen center — the
    candidate that lowers the total potential most. One sample per step
    (plain k-means++) leaves a centroid between two blobs for some seeds
    of the random stream; the local trials make the seeding robust to
    the stream, as in the reference."""
    n = X.shape[0]
    w = jnp.ones((n,), jnp.float32) if sample_weights is None else jnp.asarray(sample_weights, jnp.float32)
    return _kmeans_plus_plus(key, jnp.asarray(X, jnp.float32), w, k=k)


@functools.partial(jax.jit, static_argnames=("k",))
def _kmeans_plus_plus(key, X, w, *, k: int):
    n, d = X.shape
    n_trials = 2 + int(np.log(max(k, 1)))
    x_sq = jnp.sum(X * X, axis=1)
    k0, kloop = jax.random.split(key)
    first = jax.random.categorical(k0, jnp.log(jnp.maximum(w, 1e-30)))
    centers = jnp.zeros((k, d), jnp.float32).at[0].set(X[first])
    min_d2 = jnp.sum((X - X[first]) ** 2, axis=1)

    def body(i, carry):
        centers, min_d2, kk = carry
        kk, ksel = jax.random.split(kk)
        # Sample proportional to w * min_d2 (log-categorical; zero-safe).
        logits = jnp.log(jnp.maximum(w * min_d2, 1e-30))
        idx = jax.random.categorical(ksel, logits, shape=(n_trials,))
        cand = X[idx]  # [t, d]
        dots = jnp.dot(cand, X.T, precision=lax.Precision.HIGHEST)
        d2 = jnp.maximum(x_sq[idx][:, None] + x_sq[None, :] - 2.0 * dots, 0.0)
        trial = jnp.minimum(min_d2[None, :], d2)  # [t, n]
        best = jnp.argmin(jnp.sum(w[None, :] * trial, axis=1))
        centers = centers.at[i].set(cand[best])
        return centers, trial[best], kk

    centers, _, _ = lax.fori_loop(1, k, body, (centers, min_d2, kloop))
    return centers


def _update_centroids(X, labels, k: int, old_centroids, weights):
    """M step (``cluster/detail/kmeans.cuh:288`` update_centroids): weighted
    mean of assigned points; empty clusters keep their previous centroid (the
    reference copies the old center for weight-0 clusters)."""
    sums = jax.ops.segment_sum(X * weights[:, None], labels, num_segments=k)
    counts = jax.ops.segment_sum(weights, labels, num_segments=k)
    means = sums / jnp.maximum(counts[:, None], 1e-9)
    return jnp.where(counts[:, None] > 0, means, old_centroids), counts


# -- Flash-KMeans exact E step ----------------------------------------------
# "Flash-KMeans: Fast and Memory-Efficient Exact K-Means" (PAPERS.md): three
# changes to the assignment step, none of which alter a single bit of the
# result relative to :func:`min_cluster_and_distance`:
#
# 1. **norm caching** — ``||x||^2`` (and for cosine the unit rows) are
#    computed once per fit and reused every EM iteration; the fused scan
#    recomputes them inside the ``while_loop`` body each time.
# 2. **blocked assignment** — rows are processed in MXU-sized blocks against
#    center tiles, one ``[block, tile]`` matmul per step.
# 3. **norm-difference bounds** — ``d(x, c) >= | ||x|| - ||c|| |`` lets a
#    whole center tile be skipped via ``lax.cond`` (the matmul truly does
#    not run) when no row in the block can improve on its running best.
#
# The bound is deflated by a worst-case f32 rounding margin so it only
# suppresses tiles whose *computed* distances provably cannot win, and
# replacement stays strict-(</>) with first-seen ties — so labels,
# distances, and the convergence trajectory are bit-identical to the
# default path ("bit-compatible convergence").

_F32_EPS = float(np.finfo(np.float32).eps)


@functools.partial(jax.jit, static_argnames=("tile", "sqrt"))
def _flash_assign_l2(Xb, xnb, sxb, ct, cnt, sct, *, tile: int, sqrt: bool):
    """Blocked bound-skipping L2 assignment over pre-tiled inputs.

    ``Xb [nb, block, d]``, ``xnb/sxb [nb, block]`` (squared norms / norms,
    zero on row padding); ``ct [nt, tile, d]``, ``cnt/sct [nt, tile]`` with
    ``inf`` norms marking center padding. Returns ``(labels, dists)`` each
    ``[nb * block]``, matching :func:`fused_l2_nn` bit-for-bit."""
    n_tiles, _, d = ct.shape
    # |computed d2 - true d2| <= eps * O(d) * (||x|| + ||c||)^2 covers both
    # the dot's length-d accumulation and the xn + cn - 2dot cancellation.
    margin_scale = jnp.float32(_F32_EPS * (d + 8.0))

    def per_block(blk):
        xb, xn, sx = blk

        def body(carry, inputs):
            t, yt, ynt, syt = inputs
            bv0, _ = carry
            pad = ynt == jnp.inf
            lb = (sx[:, None] - syt[None, :]) ** 2
            lb = lb - margin_scale * (sx[:, None] + syt[None, :]) ** 2
            lb = jnp.where(pad[None, :], jnp.inf, lb)
            can_skip = jnp.all(jnp.min(lb, axis=1) >= bv0)

            def compute(c):
                bv, bi = c
                dot = lax.dot_general(
                    xb, yt, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
                )
                d2 = xn[:, None] + ynt[None, :] - 2.0 * dot
                d2 = jnp.maximum(d2, 0.0)
                d2 = jnp.where(pad[None, :], jnp.inf, d2)
                tile_val = jnp.min(d2, axis=1)
                tile_arg = jnp.argmin(d2, axis=1).astype(jnp.int32) + t * tile
                take_new = tile_val < bv
                return (
                    jnp.where(take_new, tile_val, bv),
                    jnp.where(take_new, tile_arg, bi),
                )

            carry = lax.cond(can_skip, lambda c: c, compute, carry)
            return carry, None

        init = (
            jnp.full(xb.shape[:1], jnp.inf, jnp.float32),
            jnp.zeros(xb.shape[:1], jnp.int32),
        )
        (bv, bi), _ = lax.scan(body, init, (jnp.arange(n_tiles), ct, cnt, sct))
        return bv, bi

    vals, idxs = lax.map(per_block, (Xb, xnb, sxb))
    vals = vals.reshape(-1)
    if sqrt:
        vals = jnp.sqrt(vals)
    return idxs.reshape(-1), vals


@functools.partial(jax.jit, static_argnames=("tile",))
def _flash_assign_ip(Xb, sxb, ct, sct, vt, *, tile: int):
    """Blocked max-inner-product assignment with a Cauchy-Schwarz skip:
    ``dot(x, c) <= ||x|| * ||c||`` (inflated by the rounding margin), so a
    tile whose upper bound cannot beat the running best never runs its
    matmul. Matches :func:`_fused_ip_nn_impl` bit-for-bit."""
    n_tiles, _, d = ct.shape
    margin_scale = jnp.float32(_F32_EPS * (d + 8.0))

    def per_block(blk):
        xb, sx = blk

        def body(carry, inputs):
            t, yt, syt, vtt = inputs
            bv0, _ = carry
            ub = sx[:, None] * syt[None, :]
            ub = jnp.where(vtt[None, :], ub + margin_scale * ub, -jnp.inf)
            can_skip = jnp.all(jnp.max(ub, axis=1) <= bv0)

            def compute(c):
                bv, bi = c
                dot = lax.dot_general(
                    xb, yt, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
                )
                dot = jnp.where(vtt[None, :], dot, -jnp.inf)
                tile_val = jnp.max(dot, axis=1)
                tile_arg = jnp.argmax(dot, axis=1).astype(jnp.int32) + t * tile
                take_new = tile_val > bv
                return (
                    jnp.where(take_new, tile_val, bv),
                    jnp.where(take_new, tile_arg, bi),
                )

            carry = lax.cond(can_skip, lambda c: c, compute, carry)
            return carry, None

        init = (
            jnp.full(xb.shape[:1], -jnp.inf, jnp.float32),
            jnp.zeros(xb.shape[:1], jnp.int32),
        )
        (bv, bi), _ = lax.scan(body, init, (jnp.arange(n_tiles), ct, sct, vt))
        return bv, bi

    vals, idxs = lax.map(per_block, (Xb, sxb))
    return idxs.reshape(-1), vals.reshape(-1)


def flash_norm_cache(X, metric=DistanceType.L2Expanded):
    """Precompute the per-dataset arrays the flash E step reuses across EM
    iterations: for cosine the unit rows (plus their norms), otherwise the
    squared norms and norms of ``X``. Pass the result to
    :func:`flash_min_cluster_and_distance` as ``cache=``."""
    metric = resolve_metric(metric)
    X = jnp.asarray(X, jnp.float32)
    if metric == DistanceType.CosineExpanded:
        xu = X / jnp.maximum(jnp.linalg.norm(X, axis=1, keepdims=True), 1e-12)
        xn = row_norms(xu)
        return (xu, xn, jnp.sqrt(xn))
    xn = row_norms(X)
    return (X, xn, jnp.sqrt(xn))


def flash_min_cluster_and_distance(
    X,
    centroids,
    metric=DistanceType.L2Expanded,
    cache=None,
    row_block: int = 1024,
    center_tile: int = 512,
):
    """Drop-in, bit-identical replacement for
    :func:`min_cluster_and_distance` built on the flash blocked/bounded
    assignment. ``cache`` (from :func:`flash_norm_cache`) amortizes the
    sample-side norms across repeated calls on the same ``X``."""
    metric = resolve_metric(metric)
    if cache is None:
        cache = flash_norm_cache(X, metric)
    Xc, xn, sx = cache
    n, d = Xc.shape
    c = jnp.asarray(centroids, jnp.float32)
    k = c.shape[0]

    block = int(min(row_block, max(8, n)))
    nb = cdiv(n, block)
    rpad = nb * block - n
    if rpad:
        Xc = jnp.pad(Xc, ((0, rpad), (0, 0)))
        xn = jnp.pad(xn, (0, rpad))
        sx = jnp.pad(sx, (0, rpad))
    Xb = Xc.reshape(nb, block, d)
    xnb = xn.reshape(nb, block)
    sxb = sx.reshape(nb, block)

    tile = int(min(center_tile, max(128, k)))
    nt = cdiv(k, tile)
    cpad = nt * tile - k
    cp = jnp.pad(c, ((0, cpad), (0, 0))) if cpad else c
    ct = cp.reshape(nt, tile, d)

    if metric == DistanceType.InnerProduct:
        sct = jnp.sqrt(row_norms(cp)).reshape(nt, tile)
        valid = (jnp.arange(nt * tile) < k).reshape(nt, tile)
        labels, vals = _flash_assign_ip(Xb, sxb, ct, sct, valid, tile=tile)
        return labels[:n], vals[:n]

    if metric == DistanceType.CosineExpanded:
        cu = cp / jnp.maximum(jnp.linalg.norm(cp, axis=1, keepdims=True), 1e-12)
        cn = row_norms(cu)
        cn = jnp.where(jnp.arange(nt * tile) < k, cn, jnp.inf)
        labels, vals = _flash_assign_l2(
            Xb, xnb, sxb, cu.reshape(nt, tile, d), cn.reshape(nt, tile),
            jnp.sqrt(cn).reshape(nt, tile), tile=tile, sqrt=False,
        )
        return labels[:n], 0.5 * vals[:n]  # ||x̂-ĉ||²/2 == 1 - cos

    cn = row_norms(cp)
    cn = jnp.where(jnp.arange(nt * tile) < k, cn, jnp.inf)
    sqrt = metric in (DistanceType.L2SqrtExpanded, DistanceType.L2SqrtUnexpanded)
    labels, vals = _flash_assign_l2(
        Xb, xnb, sxb, ct, cn.reshape(nt, tile), jnp.sqrt(cn).reshape(nt, tile),
        tile=tile, sqrt=sqrt,
    )
    return labels[:n], vals[:n]


def _flash_lloyd(X, init_centers, k: int, metric, max_iter: int, tol: float, weights) -> KMeansOutput:
    """Flash-KMeans Lloyd: same ``while_loop`` cond/body semantics as
    :func:`_lloyd` with the E step swapped for the cached/blocked/bounded
    assignment — bit-compatible convergence, less work per iteration."""
    n = X.shape[0]
    tol2 = jnp.float32(tol * tol)
    cache = flash_norm_cache(X, metric)  # hoisted out of the EM loop

    def assign(centers):
        return flash_min_cluster_and_distance(X, centers, metric=metric, cache=cache)

    def cond(carry):
        _, _, it, shift2, _ = carry
        return (it < max_iter) & (shift2 > tol2)

    def body(carry):
        centers, _, it, _, _ = carry
        labels, dists = assign(centers)
        new_centers, _ = _update_centroids(X, labels, k, centers, weights)
        shift2 = jnp.sum((new_centers - centers) ** 2)
        inertia = jnp.sum(weights * dists)
        return new_centers, labels, it + 1, shift2, inertia

    init = (
        init_centers,
        jnp.zeros((n,), jnp.int32),
        jnp.int32(0),
        jnp.float32(jnp.inf),
        jnp.float32(jnp.inf),
    )
    centers, labels, n_iter, _, _ = lax.while_loop(cond, body, init)
    labels, dists = assign(centers)
    return KMeansOutput(
        centroids=centers, labels=labels, inertia=jnp.sum(weights * dists), n_iter=n_iter
    )


def fit(
    X,
    params: Optional[KMeansParams] = None,
    centroids: Optional[jax.Array] = None,
    sample_weights: Optional[jax.Array] = None,
    res: Optional[Resources] = None,
    **kwargs,
) -> KMeansOutput:
    """Lloyd EM (``kmeans::fit``, ``cluster/kmeans.cuh:89``).

    ``kwargs`` are convenience overrides for :class:`KMeansParams` fields
    (e.g. ``fit(X, n_clusters=16)``).
    """
    res = ensure_resources(res)
    if params is None:
        params = KMeansParams(**kwargs)
    metric = resolve_metric(params.metric)
    X = jnp.asarray(X, jnp.float32)
    expects(X.ndim == 2, "X must be [n_samples, n_features]")
    n, d = X.shape
    k = params.n_clusters
    expects(0 < k <= n, "n_clusters=%d out of range for %d samples", k, n)

    expects(
        params.init != "array" or centroids is not None,
        "init='array' requires an explicit centroids argument",
    )
    expects(
        params.algorithm in ("lloyd", "flash"),
        "algorithm must be 'lloyd' or 'flash', got %s",
        params.algorithm,
    )
    lloyd_fn = _flash_lloyd if params.algorithm == "flash" else _lloyd
    weights = (
        jnp.ones((n,), jnp.float32)
        if sample_weights is None
        else jnp.asarray(sample_weights, jnp.float32)
    )
    expects(weights.shape == (n,), "sample_weights must be [n_samples]")

    # Whether a smaller "inertia" is better depends on the metric direction
    # (InnerProduct assignment scores are similarities, larger = better).
    from raft_tpu.ops.distance import is_min_close

    min_close = is_min_close(metric)

    if obs.is_enabled():
        obs.inc("kmeans.fit.calls", init=str(params.init if centroids is None else "array"))
        obs.inc("kmeans.fit.samples", float(n))

    key = as_key(params.seed)
    best = None
    for trial in range(max(1, params.n_init)):
        key, kinit = jax.random.split(key)
        with obs.span("kmeans.fit.init", k=k, n=n, trial=trial) as sp:
            if centroids is not None:
                init_centers = jnp.asarray(centroids, jnp.float32)
                expects(init_centers.shape == (k, d), "explicit centroids shape mismatch")
            elif params.init == "random":
                idx = jax.random.permutation(kinit, n)[:k]
                init_centers = X[idx]
            else:
                init_centers = kmeans_plus_plus(kinit, X, k, sample_weights)
            sp.sync(init_centers)

        with obs.span(
            "kmeans.fit.lloyd", k=k, n=n, trial=trial, algorithm=params.algorithm
        ) as sp:
            out = sp.sync(
                lloyd_fn(X, init_centers, k, metric, params.max_iter, params.tol, weights)
            )
        if obs.is_enabled():
            obs.observe("kmeans.fit.n_iter", float(out.n_iter))
        better = best is None or (
            float(out.inertia) < float(best.inertia)
            if min_close
            else float(out.inertia) > float(best.inertia)
        )
        if better:
            best = out
        if centroids is not None:
            break
    return best


def _lloyd(X, init_centers, k: int, metric, max_iter: int, tol: float, weights) -> KMeansOutput:
    n = X.shape[0]
    tol2 = jnp.float32(tol * tol)

    def cond(carry):
        _, _, it, shift2, _ = carry
        return (it < max_iter) & (shift2 > tol2)

    def body(carry):
        centers, _, it, _, _ = carry
        labels, dists = min_cluster_and_distance(X, centers, metric=metric)
        new_centers, _ = _update_centroids(X, labels, k, centers, weights)
        shift2 = jnp.sum((new_centers - centers) ** 2)
        inertia = jnp.sum(weights * dists)
        return new_centers, labels, it + 1, shift2, inertia

    init = (
        init_centers,
        jnp.zeros((n,), jnp.int32),
        jnp.int32(0),
        jnp.float32(jnp.inf),
        jnp.float32(jnp.inf),
    )
    centers, labels, n_iter, _, _ = lax.while_loop(cond, body, init)
    # Final E step so labels/inertia match the returned centroids.
    labels, dists = min_cluster_and_distance(X, centers, metric=metric)
    return KMeansOutput(
        centroids=centers, labels=labels, inertia=jnp.sum(weights * dists), n_iter=n_iter
    )


def predict(X, centroids, metric=DistanceType.L2Expanded) -> Tuple[jax.Array, jax.Array]:
    """Assign samples to nearest centroids (``kmeans::predict``). Returns
    (labels, distances)."""
    labels, dists = min_cluster_and_distance(jnp.asarray(X, jnp.float32), centroids, metric=metric)
    return labels, dists


def fit_predict(X, params: Optional[KMeansParams] = None, **kwargs) -> Tuple[KMeansOutput, jax.Array]:
    out = fit(X, params, **kwargs)
    return out, out.labels


def transform(X, centroids, metric=DistanceType.L2Expanded) -> jax.Array:
    """Distances to every centroid (``kmeans::transform``) — [n, k]."""
    from raft_tpu.ops.distance import pairwise_distance

    return pairwise_distance(jnp.asarray(X, jnp.float32), centroids, metric=metric)


def inertia(X, centroids, metric=DistanceType.L2Expanded) -> jax.Array:
    _, dists = predict(X, centroids, metric)
    return jnp.sum(dists)


def cluster_dispersion(centroids, cluster_sizes) -> jax.Array:
    """Cluster dispersion metric (``stats/dispersion.cuh:85``): sqrt of the
    weighted sum of squared distances between centroids and the global
    (size-weighted) centroid."""
    c = jnp.asarray(centroids, jnp.float32)
    w = jnp.asarray(cluster_sizes, jnp.float32)
    total = jnp.maximum(jnp.sum(w), 1.0)
    g = jnp.sum(c * w[:, None], axis=0) / total
    return jnp.sqrt(jnp.sum(w * jnp.sum((c - g) ** 2, axis=1)))


def find_k(
    X,
    kmax: int,
    kmin: int = 1,
    max_iter: int = 100,
    tol: float = 1e-2,
    seed: int = 0,
) -> Tuple[int, jax.Array, jax.Array]:
    """Auto-select k — ``kmeans::find_k`` (``cluster/kmeans.cuh:291-308``,
    ``detail/kmeans_auto_find_k.cuh:67``).

    Binary search over k maximizing the Calinski-Harabasz-style objective
    ``(n - k) / (k - 1) * dispersion(k) / inertia(k)`` exactly as the
    reference's bisection does (slope test on the objective at
    left/mid/right). Returns ``(best_k, inertia, n_iter)``.
    """
    X = jnp.asarray(X, jnp.float32)
    n = X.shape[0]
    expects(1 <= kmin <= kmax <= n, "need 1 <= kmin <= kmax <= n")
    params = lambda k: KMeansParams(n_clusters=k, max_iter=max_iter, tol=tol, seed=seed)

    cache = {}

    def objective(k):
        if k not in cache:
            out = fit(X, params(k))
            sizes = jnp.zeros((k,), jnp.int32).at[out.labels].add(1)
            disp = cluster_dispersion(out.centroids, sizes)
            inert = jnp.maximum(out.inertia, 1e-20)
            obj = (n - k) / max(k - 1, 1) * float(disp) / float(inert)
            cache[k] = (obj, out)
        return cache[k]

    left = max(2, kmin)
    right = kmax
    if left >= right:
        _, out = objective(right)
        return right, out.inertia, out.n_iter
    if right - left <= 24:
        # small range: evaluate exhaustively (each fit is cached; the
        # reference's slope-sign bisection walks the wrong way when the
        # objective is monotone, e.g. true k at kmin)
        best = max(range(left, right + 1), key=lambda k: objective(k)[0])
        _, out = objective(best)
        return best, out.inertia, out.n_iter
    while right - left > 2:
        m1 = left + (right - left) // 3
        m2 = right - (right - left) // 3
        if objective(m1)[0] < objective(m2)[0]:
            left = m1 + 1
        else:
            right = m2 - 1
    best = max(range(left, right + 1), key=lambda k: objective(k)[0])
    _, out = objective(best)
    return best, out.inertia, out.n_iter


def fit_minibatch(
    X,
    params: Optional[KMeansParams] = None,
    n_epochs: int = 10,
    res: Optional[Resources] = None,
    **kwargs,
) -> KMeansOutput:
    """Mini-batch Lloyd — the ``batch_samples`` tiling of
    ``kmeans_types.hpp:102-106`` taken to its stochastic conclusion: each
    step assigns one ``batch_samples``-sized sample and moves its centers
    by the running-count learning rate (centers never see a full [n, k]
    anything; peak memory is O(batch * d + batch * k_tile)).

    Use for n >> HBM; plain :func:`fit` already tiles its E step and is
    preferred when the data fits."""
    res = ensure_resources(res)
    if params is None:
        params = KMeansParams(**kwargs)
    metric = resolve_metric(params.metric)
    X = jnp.asarray(X, jnp.float32)
    n, d = X.shape
    k = params.n_clusters
    b = int(min(params.batch_samples, n))
    expects(0 < k <= b, "n_clusters=%d must be <= batch_samples=%d", k, b)

    key = as_key(params.seed)
    key, kinit = jax.random.split(key)
    init_idx = jax.random.permutation(kinit, n)[:b]
    centers = kmeans_plus_plus(kinit, X[init_idx], k)

    steps = max(1, n_epochs * (n // b))

    @functools.partial(jax.jit, static_argnames=())
    def step(carry, kk):
        centers, counts = carry
        idx = jax.random.randint(kk, (b,), 0, n)
        batch = X[idx]
        labels, _ = min_cluster_and_distance(batch, centers, metric=metric)
        bsum = jax.ops.segment_sum(batch, labels, num_segments=k)
        bcnt = jax.ops.segment_sum(jnp.ones((b,), jnp.float32), labels, num_segments=k)
        new_counts = counts + bcnt
        # per-center learning rate = batch count / total count (sklearn's
        # MiniBatchKMeans update; equivalent to a running weighted mean)
        lr = jnp.where(new_counts > 0, bcnt / jnp.maximum(new_counts, 1.0), 0.0)
        bmean = bsum / jnp.maximum(bcnt[:, None], 1e-9)
        centers = jnp.where(
            (bcnt > 0)[:, None], centers + lr[:, None] * (bmean - centers), centers
        )
        return (centers, new_counts), None

    keys = jax.random.split(key, steps)
    (centers, _), _ = lax.scan(step, (centers, jnp.zeros((k,), jnp.float32)), keys)

    labels, dists = min_cluster_and_distance(X, centers, metric=metric)
    return KMeansOutput(
        centroids=centers,
        labels=labels,
        inertia=jnp.sum(dists),
        n_iter=jnp.int32(steps),
    )
