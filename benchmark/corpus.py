"""Corpus and query pool, made on the device from ``--seed``.

A configuration's ``data`` block names the generator: ``blobs`` is
``n_centers`` unit-variance Gaussian blobs around N(0, 1) centers, and
``normalize`` puts every row on the unit sphere (an angular dataset).
The same seed gives the same arrays, and every seed gives arrays of the
same shapes: a seed changes the values, never the work.
"""
from __future__ import annotations


def seed_key(seed: int):
    """A PRNG key from any whole number, 32 bits or more."""
    import jax

    seed = int(seed)
    key = jax.random.PRNGKey(seed % (1 << 31))
    return jax.random.fold_in(key, (seed >> 31) % (1 << 31))


def _blobs(key, rows: int, centers, normalize: bool):
    import jax
    import jax.numpy as jnp

    k1, k2 = jax.random.split(key)
    x = centers[jax.random.randint(k1, (rows,), 0, centers.shape[0])]
    x = x + jax.random.normal(k2, (rows, centers.shape[1]), jnp.float32)
    if normalize:
        x = x / jnp.linalg.norm(x, axis=1, keepdims=True)
    return x


def make(cfg: dict, seed: int):
    """``(data [rows, dim], queries [n_queries, dim])`` f32 on the device,
    each made by one jitted call."""
    import jax
    import jax.numpy as jnp

    spec = cfg["data"]
    if spec["kind"] != "blobs":
        raise ValueError(f"unknown data kind {spec['kind']!r}")
    k_c, k_d, k_q = jax.random.split(seed_key(seed), 3)
    dim, n_centers, norm = cfg["dim"], spec["n_centers"], bool(spec.get("normalize", False))

    @jax.jit
    def centers(key):
        return jax.random.normal(key, (n_centers, dim), jnp.float32)

    c = centers(k_c)
    gen = jax.jit(_blobs, static_argnums=(1, 3))
    data = gen(k_d, cfg["rows"], c, norm)
    queries = gen(k_q, cfg["n_queries"], c, norm)
    return data, queries
