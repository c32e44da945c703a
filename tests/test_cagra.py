"""CAGRA tests — recall-threshold vs exact kNN (reference pattern:
``cpp/test/neighbors/ann_cagra.cuh``) plus unit checks on the graph
optimizer (prune + reverse merge)."""
import dataclasses
import io

import numpy as np
import pytest

from raft_tpu.neighbors import brute_force, cagra
from raft_tpu.neighbors.cagra import CagraIndexParams, CagraSearchParams
from raft_tpu.ops.distance import DistanceType
from raft_tpu.stats import neighborhood_recall


def _data(rng, n, d, n_centers=16, scale=0.25):
    centers = rng.standard_normal((n_centers, d)).astype(np.float32)
    labels = rng.integers(0, n_centers, n)
    return (centers[labels] + scale * rng.standard_normal((n, d))).astype(np.float32)



@pytest.fixture(scope="module")
def nn_index():
    """One shared nn-descent index (n=2000, d=16) for the filter /
    serialize / VPQ tests — building it is the dominant cost of each of
    those tests and none of them mutates it."""
    rng = np.random.default_rng(33)
    X = _data(rng, 2000, 16)
    index = cagra.build(
        X, CagraIndexParams(intermediate_graph_degree=32, graph_degree=16, nn_descent_niter=8, seed=3)
    )
    return X, index


class TestOptimize:
    def test_degree_and_validity(self, rng):
        n, kin, kout = 500, 16, 8
        # random well-formed knn graph (no self loops)
        g = rng.integers(0, n - 1, (n, kin)).astype(np.int32)
        g = g + (g >= np.arange(n)[:, None])
        out = np.asarray(cagra.optimize(g, kout))
        assert out.shape == (n, kout)
        assert (out < n).all()
        # no duplicate ids within a row (ignoring -1 pads)
        for i in range(0, n, 37):
            row = out[i][out[i] >= 0]
            assert len(set(row.tolist())) == len(row)

    def test_detour_pruning_prefers_no_detour_edges(self):
        # Node 0's neighbors ranked [1, 2]; 1's list contains 2, so edge
        # 0->2 has a detour via 1 and must be pruned when kout=1.
        g = np.array(
            [
                [1, 2],
                [2, 3],
                [3, 0],
                [0, 1],
            ],
            np.int32,
        )
        fwd = np.asarray(cagra._detour_rerank_chunk(g, np.arange(4, dtype=np.int32), kout=1))
        assert fwd[0, 0] == 1  # rank-0 edge kept, detour edge 0->2 dropped

    def test_detour_ignores_invalid_padding_edges(self):
        # Regression (round-2 advisor): a -1 pad in a row used to wrap to
        # the LAST node's adjacency, so its edges accrued phantom detour
        # counts and valid edges got demoted. Node 0's row is [-1, 1, 2, 3]
        # and node 4 (the wrap target) lists 1 — under the bug edge 0->1
        # picked up a phantom detour and sorted after 2 and 3.
        g = np.array(
            [
                [-1, 1, 2, 3],
                [-1, -1, -1, -1],
                [-1, -1, -1, -1],
                [-1, -1, -1, -1],
                [1, -1, -1, -1],
            ],
            np.int32,
        )
        fwd = np.asarray(cagra._detour_rerank_chunk(g, np.array([0], np.int32), kout=2))
        np.testing.assert_array_equal(fwd[0], [1, 2])

    def test_reverse_merge_keeps_protected_head(self, rng):
        n, kout = 200, 8
        # rows must be duplicate-free (true of any real kNN graph)
        g = np.empty((n, kout), np.int32)
        for i in range(n):
            choices = rng.permutation(n - 1)[:kout]
            g[i] = choices + (choices >= i)
        out = np.asarray(cagra.optimize(g, kout))
        # reverse merge never disturbs the first kout/2 pruned-forward edges:
        # recompute the pure-forward pruning and compare heads
        fwd = np.asarray(
            cagra._detour_rerank_chunk(g, np.arange(n, dtype=np.int32), kout=kout)
        )
        np.testing.assert_array_equal(out[:, : kout // 2], fwd[:, : kout // 2])


class TestCagraSearch:
    @pytest.mark.slow
    def test_recall_nn_descent_build(self, rng):
        n, d, nq, k = 2500, 32, 64, 10
        X = _data(rng, n, d)
        Q = _data(rng, nq, d)
        index = cagra.build(
            X, CagraIndexParams(intermediate_graph_degree=48, graph_degree=24, nn_descent_niter=8, seed=0)
        )
        _, ref = brute_force.search(brute_force.build(X), Q, k)
        _, ann = cagra.search(index, Q, k, CagraSearchParams(itopk_size=64, search_width=2))
        recall = float(neighborhood_recall(np.asarray(ann), np.asarray(ref)))
        assert recall >= 0.9, f"recall {recall}"

    def test_recall_ivf_pq_build(self, rng):
        n, d, nq, k = 2000, 32, 48, 10
        X = _data(rng, n, d)
        Q = _data(rng, nq, d)
        index = cagra.build(
            X,
            CagraIndexParams(
                intermediate_graph_degree=32,
                graph_degree=16,
                build_algo=cagra.IVF_PQ,
                seed=1,
            ),
        )
        _, ref = brute_force.search(brute_force.build(X), Q, k)
        _, ann = cagra.search(index, Q, k, CagraSearchParams(itopk_size=64, search_width=2))
        recall = float(neighborhood_recall(np.asarray(ann), np.asarray(ref)))
        assert recall >= 0.85, f"recall {recall}"

    def test_recall_planned_width8_default_itopk(self, rng):
        """The width-8 beam `plan_search_params` hands every
        default-width caller must hold recall at the DEFAULT itopk (64)
        — the plan's claim is that widening the beam only cuts the
        iteration count, not result quality."""
        n, d, nq, k = 2000, 32, 48, 10
        X = _data(rng, n, d)
        Q = _data(rng, nq, d)
        index = cagra.build(
            X,
            CagraIndexParams(
                intermediate_graph_degree=32,
                graph_degree=16,
                build_algo=cagra.IVF_PQ,
                seed=1,
            ),
        )
        sp = cagra.plan_search_params(nq, k, n)
        assert sp.itopk_size == CagraSearchParams.itopk_size == 64
        assert sp.search_width == 8  # the plan's wide-beam promotion
        _, ref = brute_force.search(brute_force.build(X), Q, k)
        _, ann = cagra.search(index, Q, k, sp)
        recall = float(neighborhood_recall(np.asarray(ann), np.asarray(ref)))
        assert recall >= 0.85, f"recall {recall}"

    @pytest.mark.slow
    def test_inner_product(self, rng):
        n, d, nq, k = 2000, 32, 48, 10
        X = _data(rng, n, d)
        X /= np.linalg.norm(X, axis=1, keepdims=True)
        Q = _data(rng, nq, d)
        index = cagra.build(
            X,
            CagraIndexParams(
                intermediate_graph_degree=48,
                graph_degree=24,
                metric=DistanceType.InnerProduct,
                seed=2,
            ),
        )
        _, ref = brute_force.search(
            brute_force.build(X, metric=DistanceType.InnerProduct), Q, k
        )
        _, ann = cagra.search(index, Q, k, CagraSearchParams(itopk_size=64, search_width=2))
        recall = float(neighborhood_recall(np.asarray(ann), np.asarray(ref)))
        assert recall >= 0.8, f"IP recall {recall}"

    def test_prefilter(self, rng, nn_index):
        from raft_tpu.core.bitset import Bitset

        X, index = nn_index
        n, k = len(X), 5
        Q = _data(rng, 16, 16)
        banned = np.arange(0, n, 2, dtype=np.int32)
        bs = Bitset.create(n, default=True).unset(banned)
        _, idx = cagra.search(
            index, Q, k, CagraSearchParams(itopk_size=64, search_width=2), prefilter=bs
        )
        idx = np.asarray(idx)
        assert ((idx % 2 == 1) | (idx < 0)).all()

    def test_selective_prefilter_still_returns_k(self, rng, nn_index):
        # 95% of ids banned: insertion-time filtering must keep valid
        # candidates competing for buffer slots (post-hoc filtering would
        # return mostly -1 here)
        from raft_tpu.core.bitset import Bitset

        X, index = nn_index
        n, k = len(X), 5
        Q = _data(rng, 16, 16)
        allowed = np.arange(0, n, 20, dtype=np.int32)  # 5% allowed
        bs = Bitset.create(n, default=False).set(allowed)
        _, idx = cagra.search(
            index, Q, k, CagraSearchParams(itopk_size=64, search_width=4), prefilter=bs
        )
        idx = np.asarray(idx)
        assert (idx % 20 == 0).all() or ((idx < 0) | (idx % 20 == 0)).all()
        # most slots should actually be filled with allowed ids
        assert (idx >= 0).mean() >= 0.8

    def test_from_graph_and_serialize(self, rng, nn_index):
        k = 5
        X, index = nn_index
        Q = _data(rng, 16, 16)
        # round trip with dataset
        buf = io.BytesIO()
        cagra.save(index, buf)
        buf.seek(0)
        loaded = cagra.load(buf)
        p = CagraSearchParams(itopk_size=32, seed=7)
        v1, i1 = cagra.search(index, Q, k, p)
        v2, i2 = cagra.search(loaded, Q, k, p)
        np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))
        # round trip without dataset (graph-only file + external dataset)
        buf2 = io.BytesIO()
        cagra.save(index, buf2, include_dataset=False)
        buf2.seek(0)
        loaded2 = cagra.load(buf2, dataset=X)
        v3, i3 = cagra.search(loaded2, Q, k, p)
        np.testing.assert_array_equal(np.asarray(i1), np.asarray(i3))


class TestVpq:
    """VPQ-compressed dataset (``neighbors/dataset.hpp:210-259``)."""

    @pytest.mark.slow
    def test_compressed_search_recall(self, rng):
        n, d, nq, k = 3000, 32, 64, 10
        X = _data(rng, n, d, n_centers=16, scale=0.2)
        Q = _data(rng, nq, d, n_centers=16, scale=0.2)
        index = cagra.build(
            X, cagra.CagraIndexParams(intermediate_graph_degree=32, graph_degree=16, nn_descent_niter=8, seed=0)
        )
        comp = cagra.compress(index, cagra.VpqParams(pq_dim=8, pq_bits=6, kmeans_n_iters=6, seed=1))
        assert comp.dataset is None and comp.vpq is not None
        assert comp.vpq.codes.shape == (n, 8)
        _, ref = brute_force.search(
            brute_force.build(X, metric=DistanceType.L2Expanded), Q, k
        )
        _, ci = cagra.search(comp, Q, k, CagraSearchParams(itopk_size=64, search_width=2))
        rec = float(neighborhood_recall(np.asarray(ci), np.asarray(ref)))
        # PQ-quantized scoring costs recall vs exact; must stay useful
        assert rec >= 0.6, rec
        # and must roughly track the uncompressed search
        _, ui = cagra.search(index, Q, k, CagraSearchParams(itopk_size=64, search_width=2))
        urec = float(neighborhood_recall(np.asarray(ui), np.asarray(ref)))
        assert rec >= urec - 0.3, (rec, urec)

    def test_vpq_serialize_roundtrip(self, rng, nn_index):
        # the suite's ONLY VPQ serialize coverage — fast tier, reuses the
        # shared module index (d=16, pq_dim=4 divides it)
        import io as _io

        X, index = nn_index
        comp = cagra.compress(index, cagra.VpqParams(pq_dim=4, pq_bits=5, kmeans_n_iters=4, seed=1))
        buf = _io.BytesIO()
        cagra.save(comp, buf)
        buf.seek(0)
        loaded = cagra.load(buf)
        assert loaded.vpq is not None and loaded.dataset is None
        np.testing.assert_array_equal(np.asarray(loaded.vpq.codes), np.asarray(comp.vpq.codes))
        Q = _data(rng, 16, 16, n_centers=8)
        v1, i1 = cagra.search(comp, Q, 5)
        v2, i2 = cagra.search(loaded, Q, 5)
        np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))


def test_plan_search_params_by_batch_shape():
    """search_plan.cuh:81-164 analog: every default-width plan takes the
    measured-dominant wide beam (fewer sequential iterations), tiny
    batches additionally seed from a larger sample, explicit overrides
    are respected."""
    p1 = cagra.plan_search_params(1, 10, 1_000_000)
    pbig = cagra.plan_search_params(1024, 10, 1_000_000)
    assert p1.search_width >= 8
    assert pbig.search_width >= 8
    assert p1.init_sample > pbig.init_sample  # latency regime seeds wider
    pexp = cagra.plan_search_params(
        1, 10, 100, CagraSearchParams(search_width=16, init_sample=64)
    )
    assert pexp.search_width == 16 and pexp.init_sample == 64
    # an explicitly NARROW beam must survive too (only defaults are raised)
    pnarrow = cagra.plan_search_params(1, 10, 100, CagraSearchParams(search_width=2))
    assert pnarrow.search_width == 2


def test_plan_latency_search_works(rng=None):
    rng = np.random.default_rng(5)
    X = _data(rng, 1500, 16, n_centers=10)
    Q = _data(rng, 4, 16, n_centers=10)
    index = cagra.build(
        X, cagra.CagraIndexParams(intermediate_graph_degree=16, graph_degree=8, nn_descent_niter=6, seed=0)
    )
    sp = cagra.plan_search_params(Q.shape[0], 5, 1500)
    v, i = cagra.search(index, Q, 5, sp)
    bf = brute_force.build(X, metric=DistanceType.L2Expanded)
    _, gi = brute_force.search(bf, Q, 5)
    rec = float(neighborhood_recall(np.asarray(i), np.asarray(gi)))
    assert rec >= 0.8, rec


class TestFusedSearch:
    """The Pallas beam kernel (mode="fused") against the XLA oracle —
    same graph, same seeded beam, so with a float32 table the two paths
    must agree (interpret mode on CPU)."""

    def test_fused_matches_xla_parity(self, rng, nn_index):
        X, index = nn_index
        Q = _data(rng, 48, 16)
        k = 10
        sp = CagraSearchParams(
            itopk_size=64, search_width=4, dedup="post", fused_table_dtype="float32"
        )
        assert cagra.fused_eligible(index, sp)
        vx, ix = cagra.search(index, Q, k, sp, mode="xla")
        vf, fi = cagra.search(index, Q, k, sp, mode="fused")
        # identical top-1 and (with a bit-faithful f32 table) identical
        # top-k: the kernel's rank merge reproduces select_k's stable
        # tie-breaking
        np.testing.assert_array_equal(np.asarray(ix)[:, 0], np.asarray(fi)[:, 0])
        rec = float(neighborhood_recall(np.asarray(fi), np.asarray(ix)))
        assert rec >= 0.99, f"fused-vs-xla agreement {rec}"
        np.testing.assert_allclose(np.asarray(vf), np.asarray(vx), rtol=1e-5, atol=1e-5)

    def test_fused_bf16_table_recall(self, rng, nn_index):
        """The default bf16 table trades score precision for half the
        DMA bytes — recall vs the XLA oracle stays within epsilon."""
        X, index = nn_index
        Q = _data(rng, 48, 16)
        k = 10
        sp = CagraSearchParams(itopk_size=64, search_width=4, dedup="post")
        vx, ix = cagra.search(index, Q, k, sp, mode="xla")
        _, fi = cagra.search(index, Q, k, sp, mode="fused")
        rec = float(neighborhood_recall(np.asarray(fi), np.asarray(ix)))
        assert rec >= 0.95, f"bf16 fused-vs-xla agreement {rec}"

    def test_fused_batch1_smoke(self, rng, nn_index):
        X, index = nn_index
        q = _data(rng, 1, 16)
        k = 10
        sp = cagra.plan_search_params(1, k, index.size, CagraSearchParams(dedup="post"))
        v, i = cagra.search(index, q, k, sp, mode="fused")
        assert v.shape == (1, k) and i.shape == (1, k)
        ids = np.asarray(i)[0]
        assert ((ids >= 0) & (ids < index.size)).all()
        assert len(set(ids.tolist())) == k  # dedup'd
        vals = np.asarray(v)[0]
        assert (np.diff(vals) >= 0).all()  # best-first
        # agrees with the exact nearest neighbor
        _, ref = brute_force.search(brute_force.build(X), q, 1)
        assert ids[0] == int(np.asarray(ref)[0, 0])

    def test_fused_requires_eligibility(self, rng, nn_index):
        X, index = nn_index
        Q = _data(rng, 8, 16)
        with pytest.raises(Exception, match="fused mode needs"):
            cagra.search(
                index, Q, 10, CagraSearchParams(dedup="sort"), mode="fused"
            )

    @pytest.mark.parametrize("row_chunk", [128, 700, 4096])
    def test_neighbor_table_layout(self, nn_index, row_chunk):
        """The in-place chunked fill (last chunk clamped to the end)
        packs every node the same way at any chunk size: ``deg``
        neighbor vectors, base-256 id digits of ``id + 1``, zero rows to
        the tile boundary."""
        from raft_tpu.ops.pallas.cagra_search import ID_ROWS, build_neighbor_table, table_rows

        X, index = nn_index
        g = np.asarray(index.graph)
        deg = g.shape[1]
        t = np.asarray(build_neighbor_table(X, g, dtype=np.float32, row_chunk=row_chunk))
        assert t.shape == (X.shape[0], table_rows(deg), X.shape[1])
        np.testing.assert_array_equal(t[:, :deg], X[np.clip(g, 0, None)])
        digits = t[:, deg:deg + ID_ROWS, :deg].astype(np.int64)
        ids = digits[:, 0] + 256 * digits[:, 1] + 65536 * digits[:, 2] - 1
        np.testing.assert_array_equal(ids, g)
        assert not t[:, deg + ID_ROWS:].any() and not t[:, deg:, deg:].any()

    def test_table_that_cannot_fit_plans_xla(self, rng, nn_index, monkeypatch):
        """An index whose packed table exceeds free HBM is not fused-
        eligible: auto's rule picks XLA, the reason names the table, and
        an explicit fused request fails up front — no allocation attempted."""
        from raft_tpu import plan
        from raft_tpu.ops.pallas import hbm_model

        X, index = nn_index
        sp = CagraSearchParams(itopk_size=64, search_width=4, dedup="post")
        fresh = dataclasses.replace(index)  # no table cached on the copy
        monkeypatch.setattr(hbm_model, "device_free_bytes", lambda device=None: 1024)
        why = cagra.fused_ineligible_reason(fresh, sp)
        assert "packed neighbor table" in why
        assert plan.plan_cagra_mode(on_tpu=True, fused_ok=not why) == "xla"
        with pytest.raises(Exception, match="fused mode needs"):
            cagra.search(fresh, _data(rng, 4, 16), 5, sp, mode="fused")
        assert getattr(fresh, "_fused_table_cache", None) is None

    def test_vmem_model_matches_kernel_scratch_shapes(self):
        import jax.numpy as jnp

        from raft_tpu.ops.pallas import cagra_search, vmem_model

        res = vmem_model.cagra_search_residency()
        budget = vmem_model.VMEM_HEADROOM * 16 * 2**20
        assert res.total_bytes <= budget, res.table()
        # the float32 parity table also fits
        assert vmem_model.cagra_search_residency(table_itemsize=4).total_bytes <= budget
        decls = cagra_search.kernel_scratch_shapes(32, 8, 16, 128, jnp.bfloat16)
        scratch = [r for r in res.residents if r.kind == "scratch"]
        assert len(scratch) == len(decls)
        for r, decl in zip(scratch, decls):
            assert tuple(decl.shape) == r.shape, r.name
            assert jnp.dtype(decl.dtype).itemsize == r.itemsize, r.name
