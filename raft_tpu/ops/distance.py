"""Pairwise distance engine — TPU-native analog of ``raft::distance``.

The reference implements pairwise distances as a tiled GEMM-like CUDA kernel
with per-metric accumulate/epilogue functors
(``distance/detail/pairwise_distance_base.cuh:69``,
``distance/detail/distance_ops/*.cuh``), dispatched over
``DistanceType`` (``distance/distance_types.hpp:23-68``,
``distance/distance-inl.cuh:239``).

The TPU design splits metrics into two families instead of one kernel:

* **Expanded (GEMM) metrics** — L2Expanded, Cosine, InnerProduct,
  Correlation, Hellinger, Jaccard, Dice, RusselRao: one MXU matmul
  (``x @ y.T`` with dtype-appropriate accumulation) plus a cheap vectorized
  epilogue using precomputed row statistics. This is exactly where the FLOPs
  belong on TPU; XLA fuses the epilogue into the matmul output.
* **Accumulation metrics** — L1, L2Unexpanded, Linf, Canberra, Lp,
  Hamming, KLDivergence, JensenShannon, BrayCurtis: no matmul form exists,
  so they are computed by scanning feature chunks with a per-metric
  elementwise combine + reduce, keeping peak memory at
  ``m*n*chunk`` instead of ``m*n*d`` (the analog of the reference's
  register-tiled accumulation loop).

All functions are jit-compatible with static shapes; the metric is a static
argument (trace-time dispatch, mirroring the reference's compile-time functor
dispatch at ``distance/detail/pairwise_matrix/dispatch-inl.cuh:58``).
"""
from __future__ import annotations

import enum
import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from raft_tpu.core.errors import expects


class DistanceType(enum.IntEnum):
    """Metric enum; values match the reference ``DistanceType``
    (``distance/distance_types.hpp:23-68``)."""

    L2Expanded = 0
    L2SqrtExpanded = 1
    CosineExpanded = 2
    L1 = 3
    L2Unexpanded = 4
    L2SqrtUnexpanded = 5
    InnerProduct = 6
    Linf = 7
    Canberra = 8
    LpUnexpanded = 9
    CorrelationExpanded = 10
    JaccardExpanded = 11
    HellingerExpanded = 12
    Haversine = 13
    BrayCurtis = 14
    JensenShannon = 15
    HammingUnexpanded = 16
    KLDivergence = 17
    RusselRaoExpanded = 18
    DiceExpanded = 19
    Precomputed = 100


# Aliases accepted by the string API (mirrors pylibraft's
# ``pairwise_distance(..., metric="euclidean")`` surface,
# ``pylibraft/distance/pairwise_distance.pyx``).
_METRIC_ALIASES = {
    "euclidean": DistanceType.L2SqrtExpanded,
    "l2": DistanceType.L2SqrtExpanded,
    "sqeuclidean": DistanceType.L2Expanded,
    "l2_expanded": DistanceType.L2Expanded,
    "l2_unexpanded": DistanceType.L2Unexpanded,
    "cosine": DistanceType.CosineExpanded,
    "inner_product": DistanceType.InnerProduct,
    "dot": DistanceType.InnerProduct,
    "l1": DistanceType.L1,
    "cityblock": DistanceType.L1,
    "manhattan": DistanceType.L1,
    "chebyshev": DistanceType.Linf,
    "linf": DistanceType.Linf,
    "canberra": DistanceType.Canberra,
    "minkowski": DistanceType.LpUnexpanded,
    "lp": DistanceType.LpUnexpanded,
    "correlation": DistanceType.CorrelationExpanded,
    "jaccard": DistanceType.JaccardExpanded,
    "hellinger": DistanceType.HellingerExpanded,
    "haversine": DistanceType.Haversine,
    "braycurtis": DistanceType.BrayCurtis,
    "jensenshannon": DistanceType.JensenShannon,
    "hamming": DistanceType.HammingUnexpanded,
    "kl_divergence": DistanceType.KLDivergence,
    "kldivergence": DistanceType.KLDivergence,
    "russellrao": DistanceType.RusselRaoExpanded,
    "dice": DistanceType.DiceExpanded,
}

_EXPANDED = frozenset(
    {
        DistanceType.L2Expanded,
        DistanceType.L2SqrtExpanded,
        DistanceType.CosineExpanded,
        DistanceType.InnerProduct,
        DistanceType.CorrelationExpanded,
        DistanceType.JaccardExpanded,
        DistanceType.HellingerExpanded,
        DistanceType.RusselRaoExpanded,
        DistanceType.DiceExpanded,
    }
)


def resolve_metric(metric) -> DistanceType:
    """Resolve a ``DistanceType``, int, or string alias to the enum."""
    if isinstance(metric, DistanceType):
        return metric
    if isinstance(metric, str):
        key = metric.lower()
        expects(key in _METRIC_ALIASES, "unknown metric name %s", metric)
        return _METRIC_ALIASES[key]
    return DistanceType(metric)


def is_min_close(metric) -> bool:
    """Whether smaller distance means more similar
    (``distance/distance_types.hpp:72-85``)."""
    return resolve_metric(metric) != DistanceType.InnerProduct


def _acc_dtype(dtype) -> jnp.dtype:
    """Accumulation dtype for a given input dtype: integers accumulate in
    int32 (MXU int8 path), everything else in float32."""
    if jnp.issubdtype(dtype, jnp.integer):
        return jnp.dtype(jnp.int32)
    return jnp.dtype(jnp.float32)


def _dot(x: jax.Array, y: jax.Array) -> jax.Array:
    """``x @ y.T`` with accumulation in f32/i32 (MXU-friendly: bf16 and int8
    inputs keep their narrow storage type through the matmul). Two f32
    operands multiply at full f32 precision: the TPU's default rounds
    them to bf16, which reorders near neighbors in an "exact" search."""
    f32 = jnp.dtype(jnp.float32)
    out = lax.dot_general(
        x,
        y,
        dimension_numbers=(((1,), (1,)), ((), ())),
        precision=(lax.Precision.HIGHEST
                   if jnp.dtype(x.dtype) == f32 and jnp.dtype(y.dtype) == f32 else None),
        preferred_element_type=_acc_dtype(x.dtype),
    )
    return out.astype(jnp.float32)


def row_norms(x: jax.Array, squared: bool = True) -> jax.Array:
    """Squared (or plain) L2 row norms in f32 — the precomputed-norms input
    of the reference's expanded-form epilogues (``distance/detail/
    distance_ops/l2_exp.cuh``)."""
    xf = x.astype(jnp.float32) if not jnp.issubdtype(x.dtype, jnp.integer) else x.astype(jnp.int32)
    sq = jnp.sum((xf * xf).astype(jnp.float32), axis=-1)
    return sq if squared else jnp.sqrt(sq)


# ---------------------------------------------------------------------------
# Expanded (matmul) family
# ---------------------------------------------------------------------------


def _expanded_distance(
    x: jax.Array,
    y: jax.Array,
    metric: DistanceType,
    x_sqnorm: Optional[jax.Array] = None,
    y_sqnorm: Optional[jax.Array] = None,
) -> jax.Array:
    """Matmul + epilogue path. ``x``: [m, d], ``y``: [n, d] → [m, n] f32.

    ``x_sqnorm``/``y_sqnorm`` allow index types to pass precomputed squared
    norms (the reference passes them into the epilogue the same way,
    ``neighbors/detail/knn_brute_force.cuh:126-181``).
    """
    m, d = x.shape
    if metric == DistanceType.HellingerExpanded:
        # dist = sqrt(1 - sum_k sqrt(x_k * y_k)); computed as an MXU matmul
        # of elementwise square roots (distance_ops/hellinger.cuh).
        xs = jnp.sqrt(x.astype(jnp.float32))
        ys = jnp.sqrt(y.astype(jnp.float32))
        acc = _dot(xs, ys)
        inner = 1.0 - acc
        # rectify negatives introduced by rounding before the sqrt
        return jnp.sqrt(jnp.maximum(inner, 0.0))

    dot = _dot(x, y)

    if metric == DistanceType.InnerProduct:
        return dot

    if metric in (DistanceType.L2Expanded, DistanceType.L2SqrtExpanded):
        xn = row_norms(x) if x_sqnorm is None else x_sqnorm.astype(jnp.float32)
        yn = row_norms(y) if y_sqnorm is None else y_sqnorm.astype(jnp.float32)
        d2 = xn[:, None] + yn[None, :] - 2.0 * dot
        d2 = jnp.maximum(d2, 0.0)  # clamp fp cancellation (l2_exp.cuh epilogue)
        return jnp.sqrt(d2) if metric == DistanceType.L2SqrtExpanded else d2

    if metric == DistanceType.CosineExpanded:
        xn = row_norms(x, squared=False) if x_sqnorm is None else jnp.sqrt(x_sqnorm.astype(jnp.float32))
        yn = row_norms(y, squared=False) if y_sqnorm is None else jnp.sqrt(y_sqnorm.astype(jnp.float32))
        denom = xn[:, None] * yn[None, :]
        sim = dot / jnp.where(denom == 0.0, 1.0, denom)
        return 1.0 - sim

    if metric == DistanceType.CorrelationExpanded:
        # 1 - (k*dot - sx*sy) / sqrt((k*x2 - sx^2)(k*y2 - sy^2))
        # (distance_ops/correlation.cuh epilogue)
        xf = x.astype(jnp.float32)
        yf = y.astype(jnp.float32)
        sx = jnp.sum(xf, axis=1)
        sy = jnp.sum(yf, axis=1)
        x2 = row_norms(x)
        y2 = row_norms(y)
        numer = d * dot - sx[:, None] * sy[None, :]
        q = d * x2 - sx * sx
        r = d * y2 - sy * sy
        denom = jnp.sqrt(jnp.maximum(q[:, None] * r[None, :], 0.0))
        return 1.0 - numer / jnp.where(denom == 0.0, 1.0, denom)

    if metric == DistanceType.JaccardExpanded:
        # 1 - dot / (|x| + |y| - dot) with 0/0 -> 0 guard
        # (sparse/distance/detail/bin_distance.cuh jaccard functor)
        sx = jnp.sum(x.astype(jnp.float32), axis=1)
        sy = jnp.sum(y.astype(jnp.float32), axis=1)
        union = sx[:, None] + sy[None, :] - dot
        sim = jnp.where(union == 0.0, 0.0, dot / jnp.where(union == 0.0, 1.0, union))
        return 1.0 - sim

    if metric == DistanceType.DiceExpanded:
        # 1 - 2*dot / (|x| + |y|) (bin_distance.cuh dice functor)
        sx = jnp.sum(x.astype(jnp.float32), axis=1)
        sy = jnp.sum(y.astype(jnp.float32), axis=1)
        denom = sx[:, None] + sy[None, :]
        sim = jnp.where(denom == 0.0, 0.0, 2.0 * dot / jnp.where(denom == 0.0, 1.0, denom))
        return 1.0 - sim

    if metric == DistanceType.RusselRaoExpanded:
        # (k - dot) / k (distance_ops/russel_rao.cuh)
        return (d - dot) / d

    raise AssertionError(f"not an expanded metric: {metric}")


# ---------------------------------------------------------------------------
# Accumulation family
# ---------------------------------------------------------------------------


def _accum_step(xc: jax.Array, yc: jax.Array, metric: DistanceType, p: float):
    """Per-feature-chunk contribution, [m, 1, dc] vs [1, n, dc] → [m, n].

    The elementwise combine bodies mirror the reference's per-metric
    ``core()`` functors (``distance/detail/distance_ops/*.cuh``).
    """
    xb = xc[:, None, :]
    yb = yc[None, :, :]
    if metric == DistanceType.L1:
        return jnp.sum(jnp.abs(xb - yb), axis=-1)
    if metric in (DistanceType.L2Unexpanded, DistanceType.L2SqrtUnexpanded):
        diff = xb - yb
        return jnp.sum(diff * diff, axis=-1)
    if metric == DistanceType.Linf:
        return jnp.max(jnp.abs(xb - yb), axis=-1)
    if metric == DistanceType.Canberra:
        diff = jnp.abs(xb - yb)
        add = jnp.abs(xb) + jnp.abs(yb)
        # 0/0 -> 0 (distance_ops/canberra.cuh)
        return jnp.sum(jnp.where(add == 0.0, 0.0, diff / jnp.where(add == 0.0, 1.0, add)), axis=-1)
    if metric == DistanceType.LpUnexpanded:
        return jnp.sum(jnp.abs(xb - yb) ** p, axis=-1)
    if metric == DistanceType.BrayCurtis:
        # sum |x-y| and sum |x+y| accumulated together; packed as complex
        # would be cute but two stacked channels are clearer.
        num = jnp.sum(jnp.abs(xb - yb), axis=-1)
        den = jnp.sum(jnp.abs(xb + yb), axis=-1)
        return jnp.stack([num, den], axis=0)
    if metric == DistanceType.HammingUnexpanded:
        return jnp.sum((xb != yb).astype(jnp.float32), axis=-1)
    if metric == DistanceType.KLDivergence:
        return jnp.sum(kl_term(xb, yb), axis=-1)
    if metric == DistanceType.JensenShannon:
        return jnp.sum(js_term(xb, yb), axis=-1)
    raise AssertionError(f"not an accumulation metric: {metric}")


def _accum_combine(acc, contrib, metric: DistanceType):
    if metric == DistanceType.Linf:
        return jnp.maximum(acc, contrib)
    return acc + contrib


def _accum_finalize(acc, metric: DistanceType, p: float, d: int):
    if metric == DistanceType.L2SqrtUnexpanded:
        return jnp.sqrt(acc)
    if metric == DistanceType.LpUnexpanded:
        return acc ** (1.0 / p)
    if metric == DistanceType.HammingUnexpanded:
        return acc / d
    if metric == DistanceType.JensenShannon:
        return jnp.sqrt(jnp.maximum(0.5 * acc, 0.0))
    if metric == DistanceType.BrayCurtis:
        num, den = acc[0], acc[1]
        return jnp.where(den == 0.0, 0.0, num / jnp.where(den == 0.0, 1.0, den))
    return acc


def _accum_distance(x: jax.Array, y: jax.Array, metric: DistanceType, p: float) -> jax.Array:
    """Feature-chunked accumulation engine for non-GEMM metrics.

    Scans ``d`` in chunks so peak temp memory is ``m*n*chunk`` (the analog of
    the reference's k-tiled accumulation in
    ``pairwise_distance_base.cuh:127``). Chunk size is chosen at trace time
    from static shapes.
    """
    m, d = x.shape
    n = y.shape[0]
    xf = x.astype(jnp.float32)
    yf = y.astype(jnp.float32)

    # Keep the m*n*chunk broadcast temp under ~256 MiB of f32.
    budget_elems = (256 << 20) // 4
    chunk = max(1, min(d, budget_elems // max(1, m * n)))
    n_chunks = -(-d // chunk)
    if n_chunks <= 1:
        acc = _accum_step(xf, yf, metric, p)
        return _accum_finalize(acc, metric, p, d)

    pad = n_chunks * chunk - d
    if pad:
        # Pad features with zeros; for every accumulation metric a (0, 0)
        # feature pair contributes the identity (0 for sums, 0 for max).
        xf = jnp.pad(xf, ((0, 0), (0, pad)))
        yf = jnp.pad(yf, ((0, 0), (0, pad)))
    xcs = xf.reshape(m, n_chunks, chunk).transpose(1, 0, 2)
    ycs = yf.reshape(n, n_chunks, chunk).transpose(1, 0, 2)

    acc_shape = (2, m, n) if metric == DistanceType.BrayCurtis else (m, n)
    init = jnp.zeros(acc_shape, jnp.float32)

    def body(acc, chunks):
        xc, yc = chunks
        return _accum_combine(acc, _accum_step(xc, yc, metric, p), metric), None

    acc, _ = lax.scan(body, init, (xcs, ycs))
    return _accum_finalize(acc, metric, p, d)


def kl_term(a, b) -> jax.Array:
    """Elementwise ``a * (log a - log b)``, zero-guarded exactly as the
    reference's functor (``distance_ops/kl_divergence.cuh``): a==0 terms
    vanish, b==0 drops the log-b contribution. Shared by the dense
    accumulation engine and the sparse union path — keep the guards in
    exactly one place."""
    la = jnp.log(jnp.where(a == 0.0, 1.0, a))
    lb = jnp.where(b == 0.0, 0.0, jnp.log(jnp.where(b == 0.0, 1.0, b)))
    return a * (la - lb)


def js_term(a, b) -> jax.Array:
    """Elementwise Jensen-Shannon contribution ``-a*(log m - log a) -
    b*(log m - log b)`` with ``m = (a+b)/2``, zero-guarded
    (``distance_ops/jensen_shannon.cuh``). Finalize with
    ``sqrt(max(0.5 * sum, 0))``. Shared like :func:`kl_term`."""
    m = 0.5 * (a + b)
    lm = jnp.where(m == 0.0, 0.0, jnp.log(jnp.where(m == 0.0, 1.0, m)))
    la = jnp.log(jnp.where(a == 0.0, 1.0, a))
    lb = jnp.log(jnp.where(b == 0.0, 1.0, b))
    return -a * (lm - la) - b * (lm - lb)


def haversine_core(lat1, lon1, lat2, lon2) -> jax.Array:
    """Great-circle distance from broadcast-compatible (lat, lon in
    radians) components (``spatial/knn/detail/haversine_distance.cuh``).
    Shared by the pairwise engine and the ball-cover gathered path — keep
    the formula in exactly one place."""
    sin_0 = jnp.sin(0.5 * (lat1 - lat2))
    sin_1 = jnp.sin(0.5 * (lon1 - lon2))
    rdist = sin_0 * sin_0 + jnp.cos(lat1) * jnp.cos(lat2) * sin_1 * sin_1
    return 2.0 * jnp.arcsin(jnp.sqrt(jnp.clip(rdist, 0.0, 1.0)))


def _haversine(x: jax.Array, y: jax.Array) -> jax.Array:
    """[m, n] pairwise haversine."""
    return haversine_core(x[:, 0:1], x[:, 1:2], y[None, :, 0], y[None, :, 1])


# ---------------------------------------------------------------------------
# Public API
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("metric", "p"))
def _pairwise_impl(x, y, x_sqnorm, y_sqnorm, *, metric: DistanceType, p: float):
    if metric == DistanceType.Haversine:
        return _haversine(x.astype(jnp.float32), y.astype(jnp.float32))
    if metric in _EXPANDED:
        return _expanded_distance(x, y, metric, x_sqnorm, y_sqnorm)
    return _accum_distance(x, y, metric, p)


def pairwise_distance(
    x,
    y,
    metric=DistanceType.L2SqrtExpanded,
    metric_arg: float = 2.0,
    x_sqnorm: Optional[jax.Array] = None,
    y_sqnorm: Optional[jax.Array] = None,
) -> jax.Array:
    """Compute the full [m, n] pairwise distance matrix.

    Analog of ``raft::distance::pairwise_distance``
    (``distance/distance-inl.cuh:239``). ``metric`` may be a
    :class:`DistanceType`, its integer value, or a string alias
    ("euclidean", "cosine", ...). ``metric_arg`` is the Minkowski ``p``.
    """
    metric = resolve_metric(metric)
    expects(metric != DistanceType.Precomputed, "Precomputed is not a computable metric")
    x = jnp.asarray(x)
    y = jnp.asarray(y)
    expects(x.ndim == 2 and y.ndim == 2, "pairwise_distance expects 2-D inputs")
    expects(x.shape[1] == y.shape[1], "feature dims differ: %d vs %d", x.shape[1], y.shape[1])
    if metric == DistanceType.Haversine:
        expects(x.shape[1] == 2, "Haversine requires 2-D (lat, lon) points")
    return _pairwise_impl(x, y, x_sqnorm, y_sqnorm, metric=metric, p=float(metric_arg))
