"""IVF-Flat tests: recall-threshold vs exact kNN (``cpp/test/neighbors/
ann_ivf_flat.cuh`` pattern), extend, filters, serialization, metrics."""
import io

import numpy as np
import pytest

import jax.numpy as jnp

from raft_tpu.core.bitset import Bitset
from raft_tpu.neighbors import brute_force, ivf_flat
from raft_tpu.neighbors.ivf_flat import IvfFlatIndexParams, IvfFlatSearchParams
from raft_tpu.ops import DistanceType
from raft_tpu.stats import neighborhood_recall

N, D, NQ, K = 20_000, 32, 200, 10


@pytest.fixture(scope="module")
def data():
    rng = np.random.default_rng(3)
    dataset = rng.standard_normal((N, D)).astype(np.float32)
    queries = rng.standard_normal((NQ, D)).astype(np.float32)
    return dataset, queries


@pytest.fixture(scope="module")
def index(data):
    dataset, _ = data
    return ivf_flat.build(dataset, IvfFlatIndexParams(kmeans_n_iters=5, n_lists=64, metric=DistanceType.L2Expanded, seed=0))


def exact(dataset, queries, k, metric=DistanceType.L2Expanded):
    bf = brute_force.build(dataset, metric=metric)
    return brute_force.search(bf, queries, k)


def test_recall_at_probes(data, index):
    dataset, queries = data
    _, ref_idx = exact(dataset, queries, K)
    dist, idx = ivf_flat.search(index, queries, K, IvfFlatSearchParams(n_probes=40))
    recall = float(neighborhood_recall(np.asarray(idx), np.asarray(ref_idx)))
    assert recall >= 0.95, recall


def test_recall_improves_with_probes(data, index):
    dataset, queries = data
    _, ref_idx = exact(dataset, queries, K)
    recalls = []
    for np_ in (1, 8, 64):
        _, idx = ivf_flat.search(index, queries, K, n_probes=np_)
        recalls.append(float(neighborhood_recall(np.asarray(idx), np.asarray(ref_idx))))
    assert recalls[0] < recalls[2]
    assert recalls[2] >= 0.99, recalls


def test_all_probes_equals_exact(data, index):
    # Probing every list must return exactly the brute-force answer.
    dataset, queries = data
    ref_dist, ref_idx = exact(dataset, queries, K)
    dist, idx = ivf_flat.search(index, queries, K, n_probes=64)
    recall = float(neighborhood_recall(np.asarray(idx), np.asarray(ref_idx),
                                       np.asarray(dist), np.asarray(ref_dist)))
    assert recall >= 0.9999, recall


def test_distances_are_exact_for_found(data, index):
    # IVF-Flat stores raw vectors: distances of returned ids must equal the
    # true L2^2 to those rows.
    dataset, queries = data
    dist, idx = ivf_flat.search(index, queries, K, n_probes=16)
    dist, idx = np.asarray(dist), np.asarray(idx)
    for q in range(0, NQ, 37):
        for j in range(K):
            if idx[q, j] >= 0:
                true = ((queries[q] - dataset[idx[q, j]]) ** 2).sum()
                np.testing.assert_allclose(dist[q, j], true, rtol=1e-3, atol=1e-2)


# fast tier: the only coverage of ivf_flat's max-similarity scan branch
def test_inner_product(data):
    dataset, queries = data
    idx_ip = ivf_flat.build(dataset, n_lists=64, metric=DistanceType.InnerProduct, seed=0)
    _, ref_idx = exact(dataset, queries, K, metric=DistanceType.InnerProduct)
    _, idx = ivf_flat.search(idx_ip, queries, K, n_probes=32)
    recall = float(neighborhood_recall(np.asarray(idx), np.asarray(ref_idx)))
    assert recall >= 0.9, recall


def test_cosine(data):
    dataset, queries = data
    idx_cos = ivf_flat.build(dataset, n_lists=64, metric=DistanceType.CosineExpanded, seed=0)
    _, ref_idx = exact(dataset, queries, K, metric=DistanceType.CosineExpanded)
    dist, idx = ivf_flat.search(idx_cos, queries, K, n_probes=32)
    recall = float(neighborhood_recall(np.asarray(idx), np.asarray(ref_idx)))
    assert recall >= 0.9, recall
    # cosine distances live in [0, 2]
    d = np.asarray(dist)
    assert d[np.asarray(idx) >= 0].min() >= -1e-4
    assert d[np.asarray(idx) >= 0].max() <= 2.0 + 1e-4


def test_l2sqrt_distances(data, index):
    dataset, queries = data
    idx_sqrt = ivf_flat.build(dataset, n_lists=64, metric=DistanceType.L2SqrtExpanded, seed=0)
    d1, i1 = ivf_flat.search(idx_sqrt, queries[:20], K, n_probes=64)
    ref_d, ref_i = exact(dataset, queries[:20], K, metric=DistanceType.L2SqrtExpanded)
    np.testing.assert_allclose(np.sort(np.asarray(d1)), np.sort(np.asarray(ref_d)), rtol=1e-3, atol=1e-3)


def test_prefilter(data, index):
    dataset, queries = data
    _, base = ivf_flat.search(index, queries, 1, n_probes=64)
    banned = np.unique(np.asarray(base).ravel())
    keep = np.ones(N, bool)
    keep[banned] = False
    bs = Bitset.from_mask(jnp.asarray(keep))
    _, idx = ivf_flat.search(index, queries, K, n_probes=64, prefilter=bs)
    assert not np.isin(np.asarray(idx), banned).any()


def test_extend(data, index):
    dataset, queries = data
    rng = np.random.default_rng(9)
    extra = rng.standard_normal((3000, D)).astype(np.float32)
    bigger = ivf_flat.extend(index, extra)
    assert bigger.size == N + 3000
    full = np.concatenate([dataset, extra], axis=0)
    _, ref_idx = exact(full, queries, K)
    # n_probes=48 (not 32): extend assigns new rows to the EXISTING
    # centroids, so on uniform data the extended index needs a few more
    # probes for the same recall — this test is about extend semantics,
    # the probes/recall tradeoff is test_recall_at_probes' job
    _, idx = ivf_flat.search(bigger, queries, K, n_probes=48)
    recall = float(neighborhood_recall(np.asarray(idx), np.asarray(ref_idx)))
    assert recall >= 0.95, recall
    # ids of extended rows must appear (some queries' neighbors are new rows)
    assert (np.asarray(idx) >= N).any()


def test_serialize_roundtrip(data, index):
    _, queries = data
    buf = io.BytesIO()
    ivf_flat.save(index, buf)
    buf.seek(0)
    loaded = ivf_flat.load(buf)
    d1, i1 = ivf_flat.search(index, queries, K, n_probes=16)
    d2, i2 = ivf_flat.search(loaded, queries, K, n_probes=16)
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))
    assert loaded.metric == index.metric and loaded.size == index.size


def test_list_sizes_balanced(index):
    sizes = np.asarray(index.list_sizes)
    assert sizes.sum() == N
    assert sizes.min() > 0
    avg = N / 64
    assert sizes.max() < avg * 4, sizes.max()


def _slots_one_row_at_a_time(cand, n_lists, max_list):
    """The capacity-capped assignment, written plainly: each pass walks
    the unplaced rows in order into their pass-``c`` list while it has
    room; the leftovers then take the free slots in order."""
    n, n_cand = cand.shape
    slot, used = [None] * n, [0] * n_lists
    for c in range(n_cand):
        for r in range(n):
            lc = int(cand[r, c])
            if slot[r] is None and used[lc] < max_list:
                slot[r] = lc * max_list + used[lc]
                used[lc] += 1
    free = iter(sorted(set(range(n_lists * max_list)) - set(s for s in slot if s is not None)))
    return np.array([next(free) if s is None else s for s in slot])


@pytest.mark.parametrize("n,n_lists,n_cand,max_list", [
    (600, 8, 3, 80),    # crowded: spills down the candidates and into the final pass
    (2000, 32, 8, 64),  # every row fits by its candidates
])
def test_assign_slots_matches_plain_reference(n, n_lists, n_cand, max_list):
    from raft_tpu.neighbors import ivf_common

    rng = np.random.default_rng(n)
    cand = (rng.zipf(1.3, (n, n_cand)) % n_lists).astype(np.int32)
    got = np.asarray(ivf_common.assign_slots(jnp.asarray(cand), n_lists=n_lists,
                                             max_list=max_list))
    assert got.dtype == np.int32
    np.testing.assert_array_equal(got, _slots_one_row_at_a_time(cand, n_lists, max_list))


@pytest.mark.parametrize("n,n_lists,block_rows", [
    (1000, 64, 7),    # the last block overlaps its predecessor
    (1000, 64, 250),  # blocks tile the rows exactly
    (5, 16, 2),       # fewer rows than one default block
])
def test_topk_labels_blocked_matches_unblocked(n, n_lists, block_rows, monkeypatch):
    """``topk_labels`` under a tiny score budget (``block_rows`` rows of
    ``n_lists`` scores at once) returns the one-block result, and that
    holds each row's nearest centers by squared L2."""
    from raft_tpu.neighbors import ivf_common

    rng = np.random.default_rng(n_lists)
    x = jnp.asarray(rng.standard_normal((n, 12)), jnp.float32)
    c = jnp.asarray(rng.standard_normal((n_lists, 12)), jnp.float32)
    whole = np.asarray(ivf_common.topk_labels(x, c, k=8))  # one block: n <= the default
    monkeypatch.setattr(ivf_common, "TOPK_BLOCK_ELEMS", block_rows * n_lists)
    got = np.asarray(ivf_common.topk_labels(x, c, k=8))
    assert got.shape == (n, 8) and got.dtype == np.int32
    np.testing.assert_array_equal(got, whole)
    d2 = ((np.asarray(x, np.float64)[:, None, :] - np.asarray(c, np.float64)[None]) ** 2).sum(-1)
    np.testing.assert_allclose(np.take_along_axis(d2, got, 1), np.sort(d2, 1)[:, :8], rtol=1e-4)


def test_query_batching(data, index):
    _, queries = data
    d1, i1 = ivf_flat.search(index, queries, K, n_probes=8, query_batch=64)
    d2, i2 = ivf_flat.search(index, queries, K, n_probes=8, query_batch=NQ)
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))


@pytest.mark.parametrize("dtype", [np.int8, np.uint8])
def test_ivf_flat_integer_dtypes(rng, dtype):
    """int8/uint8 list storage — the reference ships per-dtype IVF scan
    kernels (``ivf_flat_interleaved_scan-inl.cuh:106-650``); here the
    narrow dtype flows through packing and both search paths."""
    n, d, nq, k = 3000, 16, 64, 5
    lo, hi = (0, 60) if dtype == np.uint8 else (-30, 30)
    X = rng.integers(lo, hi, (n, d)).astype(dtype)
    Q = rng.integers(lo, hi, (nq, d)).astype(dtype)
    index = ivf_flat.build(X, IvfFlatIndexParams(kmeans_n_iters=5, n_lists=32, seed=1))
    assert index.list_data.dtype == dtype
    from raft_tpu.neighbors import brute_force as bf_mod

    _, ref = bf_mod.search(bf_mod.build(X.astype(np.float32), metric=DistanceType.L2Expanded), Q.astype(np.float32), k)
    for mode in ("scan", "probe"):
        _, i = ivf_flat.search(index, Q, k, n_probes=16, mode=mode)
        rec = float(neighborhood_recall(np.asarray(i), np.asarray(ref)))
        assert rec >= 0.95, (mode, rec)


@pytest.mark.parametrize("dtype", [np.uint8, np.int8])
def test_native_integer_datasets(rng, dtype):
    """int8/uint8 datasets build and search natively — list storage keeps
    the dataset dtype (1 B/element, half of bf16's DMA) and both the scan
    and fused paths cast per block in-kernel. Reference parity: the
    float/half/int8/uint8 dtype set of ``ivf_flat_types.hpp:44`` /
    ``ivf_flat_interleaved_scan-inl.cuh:106-650``."""
    centers = rng.integers(30, 220, (16, 32))
    lo, hi = (0, 255) if dtype == np.uint8 else (-128, 127)
    off = 0 if dtype == np.uint8 else -128
    X = np.clip(centers[rng.integers(0, 16, 3000)] + rng.normal(0, 12, (3000, 32)) + off, lo, hi).astype(dtype)
    Q = np.clip(centers[rng.integers(0, 16, 48)] + rng.normal(0, 12, (48, 32)) + off, lo, hi).astype(dtype)

    bf = brute_force.build(X.astype(np.float32))
    _, gt = brute_force.search(bf, Q.astype(np.float32), 10)

    index = ivf_flat.build(jnp.asarray(X), IvfFlatIndexParams(n_lists=16, kmeans_n_iters=5, seed=0))
    assert index.list_data.dtype == dtype
    for mode in ("scan", "fused"):
        _, i = ivf_flat.search(
            index, jnp.asarray(Q), 10,
            IvfFlatSearchParams(n_probes=8, fused_qt=16, fused_probe_factor=16, fused_group=4),
            mode=mode,
        )
        rec = float(neighborhood_recall(np.asarray(i), np.asarray(gt)))
        assert rec >= 0.95, (dtype, mode, rec)

    # serialization keeps the integer storage
    buf = io.BytesIO()
    ivf_flat.save(index, buf)
    buf.seek(0)
    loaded = ivf_flat.load(buf)
    assert loaded.list_data.dtype == dtype
    _, i1 = ivf_flat.search(index, jnp.asarray(Q), 5, n_probes=8)
    _, i2 = ivf_flat.search(loaded, jnp.asarray(Q), 5, n_probes=8)
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))
