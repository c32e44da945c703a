"""IVF-PQ tests — mirror the reference's recall-threshold pattern
(``cpp/test/neighbors/ann_ivf_pq.cuh``): compare ANN results against exact
brute-force kNN and assert recall above a threshold, not exact equality.
"""
import io

import numpy as np
import pytest

from raft_tpu.neighbors import brute_force, ivf_pq, refine
from raft_tpu.neighbors.ivf_pq import (
    IvfPqIndexParams,
    IvfPqSearchParams,
    PER_CLUSTER,
    PER_SUBSPACE,
)
from raft_tpu.ops.distance import DistanceType
from raft_tpu.stats import neighborhood_recall


def _clustered(rng, n, d, n_centers=32, scale=0.15):
    centers = rng.standard_normal((n_centers, d)).astype(np.float32)
    labels = rng.integers(0, n_centers, n)
    return (centers[labels] + scale * rng.standard_normal((n, d))).astype(np.float32)


def _exact(dataset, queries, k, metric=DistanceType.L2Expanded):
    idx = brute_force.build(dataset, metric=metric)
    return brute_force.search(idx, queries, k)



@pytest.fixture(scope="module")
def pq8_index():
    """Shared (X, index) built at n_lists=8 / pq_dim=8 for the filter /
    extend / serialize tests — the build dominates each of them and
    extend/save return new objects, leaving this one untouched."""
    rng = np.random.default_rng(55)
    X = _clustered(rng, 2000, 16)
    index = ivf_pq.build(X, IvfPqIndexParams(kmeans_n_iters=5, n_lists=8, pq_dim=8, seed=7))
    return X, index


class TestIvfPqBuild:
    def test_shapes_and_packing(self, rng):
        n, d = 2000, 32
        X = _clustered(rng, n, d)
        index = ivf_pq.build(X, IvfPqIndexParams(kmeans_n_iters=5, n_lists=16, pq_dim=8, seed=1))
        assert index.pq_dim == 8
        assert index.ksub == 256
        assert index.rot_dim == 32
        assert index.codes.shape[0] == 16
        assert index.codes.shape[2] == 8
        # every row lands in exactly one list slot
        ids = np.asarray(index.list_indices)
        valid = ids[ids >= 0]
        assert len(valid) == n
        assert sorted(valid.tolist()) == list(range(n))
        assert int(np.asarray(index.list_sizes).sum()) == n

    def test_default_pq_dim_heuristic(self):
        # matches ivf_pq_types.hpp:588 calculate_pq_dim behavior
        assert ivf_pq._default_pq_dim(128) == 64
        assert ivf_pq._default_pq_dim(256) == 128
        assert ivf_pq._default_pq_dim(96) == 96
        assert ivf_pq._default_pq_dim(20) == 16

    def test_rotation_orthonormal_when_padding(self, rng):
        n, d = 500, 30  # 30 not divisible by pq_dim=8 -> rot_dim=32, random R
        X = _clustered(rng, n, d)
        index = ivf_pq.build(X, IvfPqIndexParams(kmeans_n_iters=5, n_lists=8, pq_dim=8, seed=0))
        R = np.asarray(index.rotation)
        assert R.shape == (32, 30)
        # isometry on the input space: ||R x|| == ||x|| for all x in R^30
        np.testing.assert_allclose(R.T @ R, np.eye(30), atol=1e-4)


class TestIvfPqSearch:
    # both codebook kinds stay in the fast tier: this is the only
    # recall coverage of the PER_CLUSTER layout
    @pytest.mark.parametrize("codebook_kind", [PER_SUBSPACE, PER_CLUSTER])
    def test_recall_l2(self, rng, codebook_kind):
        n, d, nq, k = 6000, 32, 64, 10
        X = _clustered(rng, n, d)
        Q = _clustered(rng, nq, d)
        index = ivf_pq.build(
            X, IvfPqIndexParams(kmeans_n_iters=5, n_lists=32, pq_dim=16, codebook_kind=codebook_kind, seed=2)
        )
        _, ref_i = _exact(X, Q, k)
        _, ann_i = ivf_pq.search(index, Q, k, IvfPqSearchParams(n_probes=16))
        recall = float(neighborhood_recall(np.asarray(ann_i), np.asarray(ref_i)))
        # observed 0.792 (per_subspace) / 0.816 (per_cluster) at this
        # operating point with greedy k-means++ codebook seeds under the
        # installed JAX's random stream; floor set one regression-width
        # below
        assert recall >= 0.78, f"recall {recall}"

    def test_recall_with_refine(self, rng):
        n, d, nq, k = 6000, 32, 64, 10
        X = _clustered(rng, n, d)
        Q = _clustered(rng, nq, d)
        index = ivf_pq.build(X, IvfPqIndexParams(kmeans_n_iters=5, n_lists=32, pq_dim=8, seed=3))
        _, ref_i = _exact(X, Q, k)
        # integrated refine: search(dataset=) over-fetches k * refine_ratio
        # (default 8x) and exact re-ranks — the out-of-box Pareto config
        _, ann_i = ivf_pq.search(index, Q, k, IvfPqSearchParams(n_probes=32), dataset=X)
        recall = float(neighborhood_recall(np.asarray(ann_i), np.asarray(ref_i)))
        assert recall >= 0.95, f"refined recall {recall}"
        # the standalone refine entry point agrees with the integrated path
        _, cand = ivf_pq.search(index, Q, 8 * k, IvfPqSearchParams(n_probes=32))
        _, man_i = refine(X, Q, cand, k, metric=DistanceType.L2Expanded)
        assert np.array_equal(np.asarray(man_i), np.asarray(ann_i))

    def test_inner_product(self, rng):
        n, d, nq, k = 4000, 32, 32, 10
        X = _clustered(rng, n, d)
        X /= np.linalg.norm(X, axis=1, keepdims=True)
        Q = _clustered(rng, nq, d)
        index = ivf_pq.build(
            X, IvfPqIndexParams(kmeans_n_iters=5, n_lists=16, pq_dim=16, metric=DistanceType.InnerProduct, seed=4)
        )
        _, ref_i = _exact(X, Q, k, metric=DistanceType.InnerProduct)
        # raw ADC ordering sanity (default auto->nibble codes blur a bit
        # more than kmeans-256; the refine default recovers it below)
        _, ann_i = ivf_pq.search(index, Q, k, IvfPqSearchParams(n_probes=12))
        recall = float(neighborhood_recall(np.asarray(ann_i), np.asarray(ref_i)))
        assert recall >= 0.6, f"IP recall {recall}"
        _, ref_i8 = ivf_pq.search(index, Q, k, IvfPqSearchParams(n_probes=12), dataset=X)
        recall8 = float(neighborhood_recall(np.asarray(ref_i8), np.asarray(ref_i)))
        assert recall8 >= 0.9, f"refined IP recall {recall8}"

    def test_l2sqrt_matches_l2_ranking(self, rng):
        n, d, nq, k = 2000, 16, 16, 5
        X = _clustered(rng, n, d)
        Q = _clustered(rng, nq, d)
        i1 = ivf_pq.build(X, IvfPqIndexParams(kmeans_n_iters=5, n_lists=8, pq_dim=8, seed=5))
        i2 = ivf_pq.build(
            X, IvfPqIndexParams(kmeans_n_iters=5, n_lists=8, pq_dim=8, metric=DistanceType.L2SqrtExpanded, seed=5)
        )
        v1, idx1 = ivf_pq.search(i1, Q, k, n_probes=8)
        v2, idx2 = ivf_pq.search(i2, Q, k, n_probes=8)
        np.testing.assert_array_equal(np.asarray(idx1), np.asarray(idx2))
        np.testing.assert_allclose(
            np.sqrt(np.maximum(np.asarray(v1), 0)), np.asarray(v2), atol=1e-3
        )

    def test_bf16_lut_mode(self, rng):
        import jax.numpy as jnp

        n, d, nq, k = 3000, 32, 32, 10
        X = _clustered(rng, n, d)
        Q = _clustered(rng, nq, d)
        index = ivf_pq.build(X, IvfPqIndexParams(kmeans_n_iters=5, n_lists=16, pq_dim=16, seed=6))
        _, ref_i = _exact(X, Q, k)
        _, ann_i = ivf_pq.search(
            index, Q, k, IvfPqSearchParams(n_probes=16, lut_dtype=jnp.bfloat16)
        )
        recall = float(neighborhood_recall(np.asarray(ann_i), np.asarray(ref_i)))
        # observed 0.775 with the bf16 LUT (vs ~0.82 f32): floor catches a
        # ranking regression, not LUT-rounding noise
        assert recall >= 0.72, f"bf16-LUT recall {recall}"

    def test_prefilter(self, rng, pq8_index):
        from raft_tpu.core.bitset import Bitset

        X, index = pq8_index
        n, k = len(X), 5
        Q = _clustered(rng, 16, 16)
        banned = np.arange(0, n, 2, dtype=np.int32)  # ban all even ids
        bs = Bitset.create(n, default=True).unset(banned)
        _, idx = ivf_pq.search(index, Q, k, n_probes=8, prefilter=bs)
        idx = np.asarray(idx)
        assert ((idx % 2 == 1) | (idx < 0)).all()

    @pytest.mark.slow
    def test_nearly_exact_when_uncompressed(self, rng):
        # pq_dim == dim with 8-bit codebooks on a small set: ADC error tiny.
        n, d, nq, k = 1500, 16, 24, 5
        X = _clustered(rng, n, d, n_centers=8)
        Q = _clustered(rng, nq, d, n_centers=8)
        index = ivf_pq.build(X, IvfPqIndexParams(kmeans_n_iters=5, n_lists=4, pq_dim=16, seed=8))
        _, ref_i = _exact(X, Q, k)
        _, ann_i = ivf_pq.search(index, Q, k, n_probes=4)
        recall = float(neighborhood_recall(np.asarray(ann_i), np.asarray(ref_i)))
        assert recall >= 0.9, f"uncompressed recall {recall}"


class TestIvfPqExtendSerialize:
    def test_extend(self, rng, pq8_index):
        X, index = pq8_index
        n, d = X.shape
        X2 = _clustered(rng, 500, d)
        bigger = ivf_pq.extend(index, X2)
        assert bigger.size == n + 500
        ids = np.asarray(bigger.list_indices)
        assert (ids[ids >= 0] < n + 500).all()
        assert len(ids[ids >= 0]) == n + 500
        # extended rows are findable
        _, idx = ivf_pq.search(bigger, X2[:8], 3, n_probes=8)
        hits = (np.asarray(idx) >= n).any(axis=1)
        assert hits.mean() >= 0.75

    def test_serialize_roundtrip(self, rng, pq8_index):
        k = 5
        X, index = pq8_index
        Q = _clustered(rng, 8, 16)
        buf = io.BytesIO()
        ivf_pq.save(index, buf)
        buf.seek(0)
        loaded = ivf_pq.load(buf)
        v1, i1 = ivf_pq.search(index, Q, k, n_probes=8)
        v2, i2 = ivf_pq.search(loaded, Q, k, n_probes=8)
        np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))
        np.testing.assert_allclose(np.asarray(v1), np.asarray(v2), rtol=1e-6)
        assert loaded.pq_bits == index.pq_bits
        assert loaded.codebook_kind == index.codebook_kind


def _ip_blobs(norms, d=64, n_blobs=500, n=10000, nq=300, seed=0):
    """Rows and queries drawn from unit-variance blobs around N(0, 1)
    centers, scaled to unit length (``"one"``: an angular dataset served
    as inner product) and then, for ``"mixed"``, to log-normal norms."""
    rng = np.random.default_rng(seed)
    c = rng.standard_normal((n_blobs, d))
    x = c[rng.integers(0, n_blobs, n + nq)] + rng.standard_normal((n + nq, d))
    x /= np.linalg.norm(x, axis=1, keepdims=True)
    if norms == "mixed":
        x *= np.exp(0.5 * rng.standard_normal(n + nq))[:, None]
    x = x.astype(np.float32)
    return x[:n], x[n:]


@pytest.mark.parametrize("norms", ["one", "mixed"])
def test_ip_coarse_ranking_follows_row_norms(norms):
    """An inner-product index ranks its lists by the L2 placement score
    only when its rows have one norm (``ivf_common.coarse_from_dots``).
    Recall@10 after exact refine, under the index's own ranking and
    under the other one, at 8 of 128 lists with lists capped at 1.1x:
    rows of one norm find more under the placement score than under
    ``q.c``; rows of log-normal norms find far less under it, so their
    index keeps ``q.c``."""
    import dataclasses

    x, q = _ip_blobs(norms)
    index = ivf_pq.build(x, IvfPqIndexParams(
        n_lists=128, metric=DistanceType.InnerProduct, list_cap_factor=1.1,
        kmeans_n_iters=10, pq_dim=16, seed=0))
    if norms == "one":
        assert index.row_norm == pytest.approx(1.0, rel=1e-5)
        other = dataclasses.replace(index, row_norm=0.0)
    else:
        assert index.row_norm == 0.0
        other = dataclasses.replace(index, row_norm=float(np.sqrt(np.mean(np.sum(x * x, 1)))))
    _, truth = _exact(x, q, 10, DistanceType.InnerProduct)
    sp = IvfPqSearchParams(n_probes=8)
    recall = [float(neighborhood_recall(
        np.asarray(ivf_pq.search(ix, q, 10, sp, mode="scan", dataset=x)[1]),
        np.asarray(truth))) for ix in (index, other)]
    assert recall[0] > recall[1] + (0.015 if norms == "one" else 0.08), recall


def test_one_norm_rows_probe_where_they_were_placed():
    """For rows of one norm, the ranking puts first the lists the build
    placed the row in (``topk_labels``: L2 to the center), whatever the
    query's own scale; with centers of unequal norms ``q.c`` alone would
    send many rows' own queries elsewhere."""
    import jax.numpy as jnp

    from raft_tpu.neighbors import ivf_common

    rng = np.random.default_rng(5)
    x = rng.standard_normal((400, 16)).astype(np.float32)
    x /= np.linalg.norm(x, axis=1, keepdims=True)
    c = rng.standard_normal((32, 16)).astype(np.float32)
    c *= np.linspace(0.2, 1.0, 32, dtype=np.float32)[:, None] / np.linalg.norm(c, axis=1, keepdims=True)
    assert ivf_common.one_norm(jnp.asarray(x)) == pytest.approx(1.0, rel=1e-5)
    x, c = jnp.asarray(x), jnp.asarray(c)
    placed = np.asarray(ivf_common.topk_labels(x, c, k=3))
    ip = DistanceType.InnerProduct
    for scale in (1.0, 3.0):
        coarse = ivf_common.coarse_scores(c, scale * x, ip, row_norm=1.0)
        np.testing.assert_array_equal(np.asarray(ivf_common.probe_lists(coarse, 3)), placed)
    by_dot = np.asarray(ivf_common.probe_lists(ivf_common.coarse_scores(c, x, ip), 1))
    assert (by_dot[:, 0] != placed[:, 0]).mean() > 0.1


@pytest.mark.parametrize("case", ["unit", "scaled", "mixed", "zero"])
def test_one_norm(case):
    """``one_norm`` gives the rows' common norm, and 0.0 when they differ
    or are all zero."""
    import jax.numpy as jnp

    from raft_tpu.neighbors import ivf_common

    rng = np.random.default_rng(3)
    x = rng.standard_normal((500, 24)).astype(np.float32)
    x /= np.linalg.norm(x, axis=1, keepdims=True)
    want = {"unit": 1.0, "scaled": 2.5, "mixed": 0.0, "zero": 0.0}[case]
    if case == "scaled":
        x *= 2.5
    elif case == "mixed":
        x[::7] *= 1.1
    elif case == "zero":
        x[:] = 0.0
    assert ivf_common.one_norm(jnp.asarray(x)) == pytest.approx(want, rel=1e-5)


@pytest.mark.parametrize("step", ["save_load", "extend_same", "extend_other"])
def test_row_norm_survives_save_and_extend(step):
    """The one-norm flag of an inner-product index is saved and loaded,
    kept by an extend with rows of the same norm, and cleared by one
    with rows of another."""
    x, _ = _ip_blobs("one", d=16, n_blobs=20, n=1200, nq=0)
    index = ivf_pq.build(x[:1000], IvfPqIndexParams(
        n_lists=8, metric=DistanceType.InnerProduct, kmeans_n_iters=5, pq_dim=8, seed=1))
    assert index.row_norm == pytest.approx(1.0, rel=1e-5)
    if step == "save_load":
        buf = io.BytesIO()
        ivf_pq.save(index, buf)
        buf.seek(0)
        assert ivf_pq.load(buf).row_norm == index.row_norm
    elif step == "extend_same":
        assert ivf_pq.extend(index, x[1000:]).row_norm == index.row_norm
    else:
        assert ivf_pq.extend(index, 2.0 * x[1000:]).row_norm == 0.0
