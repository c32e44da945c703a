"""The plain reference: exact k-nearest neighbours in ``jax.numpy``, and
the control, the same search in the next precision down.

Nothing here imports the program. ``metric`` is ``sqeuclidean`` (the
squared L2 distance, smaller is nearer) or ``inner_product`` (larger is
nearer), as a configuration states it.
"""
from __future__ import annotations

import functools

import numpy as np

METRICS = ("sqeuclidean", "inner_product")
#: corpus rows per reference block: [q_block, chunk] f32 scores at a time
CHUNK = 65536
Q_BLOCK = 1024


def select_min(metric: str) -> bool:
    if metric not in METRICS:
        raise ValueError(f"unknown metric {metric!r}")
    return metric == "sqeuclidean"


def _scores(q, x, metric: str, precision: str):
    """[nq, n] scores, smaller is nearer, in the named precision:
    ``highest`` (f32 at full precision) or ``bfloat16`` (inputs rounded
    to bf16, f32 accumulation)."""
    import jax
    import jax.numpy as jnp

    if precision == "highest":
        dot = jnp.dot(q, x.T, precision=jax.lax.Precision.HIGHEST)
        xn = jnp.sum(x * x, axis=1)
        qn = jnp.sum(q * q, axis=1)
    elif precision == "bfloat16":
        qb, xb = q.astype(jnp.bfloat16), x.astype(jnp.bfloat16)
        dot = jnp.dot(qb, xb.T, preferred_element_type=jnp.float32)
        xf, qf = xb.astype(jnp.float32), qb.astype(jnp.float32)
        xn, qn = jnp.sum(xf * xf, axis=1), jnp.sum(qf * qf, axis=1)
    else:
        raise ValueError(f"unknown precision {precision!r}")
    if metric == "inner_product":
        return -dot
    return jnp.maximum(qn[:, None] + xn[None, :] - 2.0 * dot, 0.0)


def _int8_scores(qi, q_scale, xi, x_scale, metric: str):
    """As ``_scores`` from int8 codes and their per-tensor scales: int32
    accumulation, then scaled back."""
    import jax.numpy as jnp

    dot = jnp.dot(qi, xi.T, preferred_element_type=jnp.int32).astype(jnp.float32)
    dot = dot * (q_scale * x_scale)
    if metric == "inner_product":
        return -dot
    xf = xi.astype(jnp.float32) * x_scale
    qf = qi.astype(jnp.float32) * q_scale
    xn, qn = jnp.sum(xf * xf, axis=1), jnp.sum(qf * qf, axis=1)
    return jnp.maximum(qn[:, None] + xn[None, :] - 2.0 * dot, 0.0)


@functools.lru_cache(maxsize=None)
def _chunk_topk_fn(k: int, metric: str, precision: str):
    import jax
    import jax.numpy as jnp

    def run(q, x, base, n_valid, *scales):
        if precision == "int8":
            s = _int8_scores(q, scales[0], x, scales[1], metric)
        else:
            s = _scores(q, x, metric, precision)
        col = base + jnp.arange(x.shape[0], dtype=jnp.int32)
        s = jnp.where(col[None, :] < n_valid, s, jnp.inf)
        v, i = jax.lax.top_k(-s, k)
        return -v, col[i]

    return jax.jit(run)


@functools.lru_cache(maxsize=None)
def _merge_fn(k: int):
    import jax
    import jax.numpy as jnp

    def run(v0, i0, v1, i1):
        v = jnp.concatenate([v0, v1], axis=1)
        i = jnp.concatenate([i0, i1], axis=1)
        nv, pos = jax.lax.top_k(-v, k)
        return -nv, jnp.take_along_axis(i, pos, axis=1)

    return jax.jit(run)


def quantize_int8(x):
    """Per-tensor symmetric int8: ``(codes, scale)``."""
    import jax.numpy as jnp

    scale = jnp.max(jnp.abs(x)) / 127.0
    return jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8), scale


def topk(data, queries, k: int, metric: str, precision: str = "highest"):
    """Exact top-``k`` of every query over every row: ``(distances
    [nq, k], ids [nq, k])`` as numpy, nearest first, distances in the
    metric's own sign (an inner product is returned as the product).

    The corpus is cut into fixed chunks, so one program serves every
    chunk; only the last pads, masked by its row count."""
    import jax.numpy as jnp

    select_min(metric)
    n, nq = data.shape[0], queries.shape[0]
    scales = ()
    if precision == "int8":
        data, xs = quantize_int8(data)
        queries, qs = quantize_int8(queries)
        scales = (qs, xs)
    n_chunks = -(-n // CHUNK)
    pad = n_chunks * CHUNK - n
    if pad:
        data = jnp.pad(data, ((0, pad), (0, 0)))
    qpad = -(-nq // Q_BLOCK) * Q_BLOCK - nq
    if qpad:
        queries = jnp.pad(queries, ((0, qpad), (0, 0)))
    chunk_fn, merge = _chunk_topk_fn(k, metric, precision), _merge_fn(k)
    out_d, out_i = [], []
    for s in range(0, nq + qpad, Q_BLOCK):
        qb = queries[s:s + Q_BLOCK]
        best = None
        for c in range(n_chunks):
            part = chunk_fn(qb, data[c * CHUNK:(c + 1) * CHUNK], c * CHUNK, n, *scales)
            best = part if best is None else merge(*best, *part)
        out_d.append(best[0])
        out_i.append(best[1])
    d = np.asarray(jnp.concatenate(out_d))[:nq]
    i = np.asarray(jnp.concatenate(out_i))[:nq]
    return (-d if metric == "inner_product" else d), i


@functools.lru_cache(maxsize=None)
def _pair_fn(metric: str):
    import jax
    import jax.numpy as jnp

    def run(data, queries, q_rows, ids):
        x = data[ids]
        q = queries[q_rows]
        if metric == "inner_product":
            return jnp.sum(x * q, axis=1)
        return jnp.sum((x - q) * (x - q), axis=1)

    return jax.jit(run)


def pair_distances(data, queries, q_rows: np.ndarray, ids: np.ndarray, metric: str,
                   block: int = 1 << 18) -> np.ndarray:
    """Exact distance of each ``(queries[q_rows[j]], data[ids[j]])``,
    elementwise in f32 (no matrix unit, so no precision setting), in
    fixed blocks so one program serves them all."""
    import jax.numpy as jnp

    n = q_rows.shape[0]
    out = np.empty(n, np.float32)
    fn = _pair_fn(metric)
    for s in range(0, n, block):
        qr = np.zeros(block, np.int32)
        ir = np.zeros(block, np.int32)
        m = min(block, n - s)
        qr[:m], ir[:m] = q_rows[s:s + m], ids[s:s + m]
        out[s:s + m] = np.asarray(fn(data, queries, jnp.asarray(qr), jnp.asarray(ir)))[:m]
    return out
