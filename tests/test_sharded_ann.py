"""Multi-device sharded ANN search on the 8-device CPU mesh.

Mirrors the reference's single-node multi-GPU test strategy (SURVEY.md §4,
``raft_dask/test/test_comms.py`` on LocalCUDACluster): per-index sharded
search must reproduce the single-device result (sets may differ only where
distances tie or the scan path's approximate selection differs, so recall
against the unsharded result is the assertion, as in
``cpp/test/neighbors/ann_utils.cuh``).
"""
import numpy as np
import pytest

from raft_tpu.neighbors import brute_force, cagra, ivf_flat, ivf_pq
from raft_tpu.ops.distance import DistanceType
from raft_tpu.parallel import (
    make_mesh,
    sharded_cagra_search,
    sharded_ivf_flat_search,
    sharded_ivf_pq_search,
)
from raft_tpu.stats import neighborhood_recall


def _data(rng, n, d, nc=32, scale=0.25):
    c = rng.standard_normal((nc, d)).astype(np.float32)
    return (c[rng.integers(0, nc, n)] + scale * rng.standard_normal((n, d))).astype(np.float32)


@pytest.fixture(scope="module")
def setup(eight_devices):
    rng = np.random.default_rng(3)
    n, d, nq = 2048, 32, 64
    X = _data(rng, n, d)
    Q = _data(rng, nq, d)
    mesh = make_mesh(eight_devices)
    return mesh, X, Q


class TestShardedIvfFlat:
    def test_matches_unsharded(self, setup):
        mesh, X, Q = setup
        k = 10
        index = ivf_flat.build(X, ivf_flat.IvfFlatIndexParams(n_lists=64, seed=1))
        sv, si = sharded_ivf_flat_search(mesh, index, Q, k, n_probes=16)
        uv, ui = ivf_flat.search(index, Q, k, n_probes=16, mode="scan")
        rec = float(neighborhood_recall(np.asarray(si), np.asarray(ui)))
        assert rec >= 0.99, rec
        np.testing.assert_allclose(
            np.sort(np.asarray(sv), 1), np.sort(np.asarray(uv), 1), rtol=1e-4, atol=1e-4
        )

    def test_recall_vs_exact(self, setup):
        mesh, X, Q = setup
        k = 10
        index = ivf_flat.build(X, ivf_flat.IvfFlatIndexParams(n_lists=64, seed=1))
        _, si = sharded_ivf_flat_search(mesh, index, Q, k, n_probes=32)
        _, ref = brute_force.search(brute_force.build(X, metric=DistanceType.L2Expanded), Q, k)
        assert float(neighborhood_recall(np.asarray(si), np.asarray(ref))) >= 0.95


class TestShardedCagra:
    @pytest.mark.slow
    def test_matches_unsharded(self, setup):
        mesh, X, Q = setup
        k = 8
        index = cagra.build(
            X, cagra.CagraIndexParams(intermediate_graph_degree=32, graph_degree=16, nn_descent_niter=8, seed=0)
        )
        sv, si = sharded_cagra_search(
            mesh, index, Q, k, cagra.CagraSearchParams(itopk_size=64, search_width=2)
        )
        _, ref = brute_force.search(brute_force.build(X, metric=DistanceType.L2Expanded), Q, k)
        rec = float(neighborhood_recall(np.asarray(si), np.asarray(ref)))
        # query-sharded beam search must track the single-device quality
        _, ui = cagra.search(index, Q, k, cagra.CagraSearchParams(itopk_size=64, search_width=2))
        rec_u = float(neighborhood_recall(np.asarray(ui), np.asarray(ref)))
        # margin covers seed variance of the random beam-search init
        assert rec >= rec_u - 0.1, (rec, rec_u)
        assert si.shape == (Q.shape[0], k)


class TestShardedIvfPq:
    def test_recall(self, setup):
        mesh, X, Q = setup
        k = 10
        index = ivf_pq.build(X, ivf_pq.IvfPqIndexParams(n_lists=64, pq_dim=8, seed=2))
        sv, si = sharded_ivf_pq_search(mesh, index, Q, k, n_probes=32)
        uv, ui = ivf_pq.search(index, Q, k, ivf_pq.IvfPqSearchParams(n_probes=32), mode="scan")
        rec = float(neighborhood_recall(np.asarray(si), np.asarray(ui)))
        assert rec >= 0.99, rec

    def test_lists_sharded_matches_unsharded(self, setup):
        """VERDICT r3 item 6: inverted code lists sharded across the mesh
        (per-shard HBM holds 1/n of the codes), replicated quantizers."""
        from raft_tpu.parallel.sharded_ann import sharded_ivf_pq_lists_search

        mesh, X, Q = setup
        k = 10
        index = ivf_pq.build(X, ivf_pq.IvfPqIndexParams(n_lists=64, pq_dim=8, seed=2))
        sv, si = sharded_ivf_pq_lists_search(mesh, index, Q, k, n_probes=32)
        uv, ui = ivf_pq.search(index, Q, k, ivf_pq.IvfPqSearchParams(n_probes=32), mode="scan")
        rec = float(neighborhood_recall(np.asarray(si), np.asarray(ui)))
        assert rec >= 0.97, rec

    def test_lists_sharded_packed_codes(self, setup):
        from raft_tpu.parallel.sharded_ann import sharded_ivf_pq_lists_search

        mesh, X, Q = setup
        k = 5
        index = ivf_pq.build(X, ivf_pq.IvfPqIndexParams(n_lists=64, pq_dim=8, pq_bits=4, seed=2))
        assert index.packed
        _, si = sharded_ivf_pq_lists_search(mesh, index, Q, k, n_probes=32)
        _, ui = ivf_pq.search(index, Q, k, ivf_pq.IvfPqSearchParams(n_probes=32), mode="scan")
        rec = float(neighborhood_recall(np.asarray(si), np.asarray(ui)))
        assert rec >= 0.95, rec

    @pytest.mark.slow
    def test_distributed_build_sketch(self, setup):
        """psum-Lloyd coarse + codebook training over row-sharded data."""
        from raft_tpu.parallel.sharded_ann import sharded_ivf_pq_build

        mesh, X, Q = setup
        k = 5
        index = sharded_ivf_pq_build(
            mesh, X, ivf_pq.IvfPqIndexParams(n_lists=32, pq_dim=8, kmeans_n_iters=5, seed=2)
        )
        _, si = ivf_pq.search(index, Q, k, ivf_pq.IvfPqSearchParams(n_probes=16), mode="scan")
        bf = brute_force.build(X, metric=DistanceType.L2Expanded)
        _, gt = brute_force.search(bf, Q, k)
        rec = float(neighborhood_recall(np.asarray(si), np.asarray(gt)))
        assert rec >= 0.5, rec  # quantized ADC on a sketch build: loose floor


class TestShardedCagraVpq:
    @pytest.mark.slow
    def test_vpq_index_works_sharded(self, setup):
        mesh, X, Q = setup
        k = 8
        index = cagra.build(
            X, cagra.CagraIndexParams(intermediate_graph_degree=32, graph_degree=16, nn_descent_niter=8, seed=0)
        )
        comp = cagra.compress(index, cagra.VpqParams(pq_dim=8, kmeans_n_iters=6, seed=1))
        sv, si = sharded_cagra_search(
            mesh, comp, Q, k, cagra.CagraSearchParams(itopk_size=64, search_width=2)
        )
        assert si.shape == (Q.shape[0], k)
        assert (np.asarray(si) >= 0).mean() > 0.95


class TestDistKMeansCommFusion:
    """Satellite of the ring-exchange PR: the distributed Lloyd step's
    per-iteration allreduce PAIR (centroid sums + counts) is fused into
    one concatenated psum. psum is elementwise, so the packed reduction
    must leave the Lloyd trajectory bit-identical; the win is one
    collective launch per iteration instead of two (payload unchanged)."""

    ITERS = 5
    N_LISTS = 16

    def _trajectory(self, mesh, X, fuse):
        import functools

        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P

        from raft_tpu.cluster.kmeans import flash_norm_cache
        from jax import shard_map
        from raft_tpu.parallel.sharded_ann import dist_lloyd_step

        init = jnp.asarray(X[: self.N_LISTS])
        n_lists, iters = self.N_LISTS, self.ITERS

        @functools.partial(
            shard_map, mesh=mesh, in_specs=(P(), P("data")), out_specs=P(),
            check_vma=False,
        )
        def run(c0, xl):
            cache = flash_norm_cache(xl, DistanceType.L2Expanded)
            c, outs = c0, []
            for _ in range(iters):
                c, _ = dist_lloyd_step(
                    c, xl, n_lists, "data", cache=cache, fuse_comms=fuse
                )
                outs.append(c)
            return jnp.stack(outs)

        return np.asarray(jax.jit(run)(init, jnp.asarray(X)))

    def test_trajectory_bit_identical(self, setup):
        mesh, X, _Q = setup
        np.testing.assert_array_equal(
            self._trajectory(mesh, X, fuse=True),
            self._trajectory(mesh, X, fuse=False),
        )

    def test_fused_halves_collective_launches(self, setup):
        """comms.bytes before/after: the fused step moves the same bytes
        (sums+counts payload is unchanged) in HALF the allreduce calls."""
        from raft_tpu import obs

        mesh, X, _Q = setup
        reg = obs.registry()

        def measure(fuse):
            reg.reset()
            obs.enable()
            try:
                self._trajectory(mesh, X, fuse=fuse)
                snap = reg.as_dict()
            finally:
                obs.disable()
                reg.reset()
            return (
                snap["counters"]['comms.allreduce.calls{axis="data"}'],
                snap["counters"]['comms.allreduce.bytes{axis="data"}'],
            )

        fused_calls, fused_bytes = measure(True)
        plain_calls, plain_bytes = measure(False)
        assert fused_calls == self.ITERS
        assert plain_calls == 2 * self.ITERS
        assert fused_bytes == plain_bytes


class TestDistKMeansCommAvoiding:
    """Tentpole of the communication-avoiding-builds PR: the Lloyd
    exchange carries the global accumulator across iterations and moves
    only the rows whose assignments churned (``comm_mode="ca"``). The
    contract is exactness-or-bounded-drift: with the cap at full width
    the trajectory is bit-identical to the fused full allreduce (an
    unchanged row's local partial is bit-identical across iterations,
    so patching churned rows reconstructs the full exchange); at the
    default quarter-width cap the steady-state wire drops >=2x and the
    built index's recall must hold within a small drift bound."""

    ITERS = 5
    N_LISTS = 32

    def _trajectory(self, mesh, X, comm_mode, ca_cap=None):
        import functools

        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P

        from raft_tpu.cluster.kmeans import flash_norm_cache
        from jax import shard_map
        from raft_tpu.parallel.sharded_ann import dist_lloyd_step

        init = jnp.asarray(X[: self.N_LISTS])
        n_lists, iters = self.N_LISTS, self.ITERS

        @functools.partial(
            shard_map, mesh=mesh, in_specs=(P(), P("data")), out_specs=P(),
            check_vma=False,
        )
        def run(c0, xl):
            cache = flash_norm_cache(xl, DistanceType.L2Expanded)
            c, carry, outs = c0, None, []
            for _ in range(iters):
                if comm_mode == "full":
                    c, _ = dist_lloyd_step(
                        c, xl, n_lists, "data", cache=cache, fuse_comms=True
                    )
                else:
                    c, _lab, carry = dist_lloyd_step(
                        c, xl, n_lists, "data", cache=cache,
                        comm_mode="ca", carry=carry, ca_cap=ca_cap,
                    )
                outs.append(c)
            return jnp.stack(outs)

        return np.asarray(jax.jit(run)(init, jnp.asarray(X)))

    def test_ca_trajectory_bit_identical_at_full_cap(self, setup):
        """cap = n_lists admits every churned row, so the CA exchange
        must reconstruct the full allreduce bit-for-bit — iteration by
        iteration, not just at convergence."""
        mesh, X, _Q = setup
        np.testing.assert_array_equal(
            self._trajectory(mesh, X, "full"),
            self._trajectory(mesh, X, "ca", ca_cap=self.N_LISTS),
        )

    def test_ca_trajectory_bounded_drift_at_default_cap(self, setup):
        """The capped exchange may drift (churn past the cap patches
        late), but the final centers must stay close to the full
        trajectory's — drift is bounded, not runaway."""
        mesh, X, _Q = setup
        full = self._trajectory(mesh, X, "full")[-1]
        ca = self._trajectory(mesh, X, "ca")[-1]
        scale = float(np.abs(full).mean())
        assert float(np.abs(ca - full).mean()) <= 0.25 * scale

    def test_wire_model_reduction_at_8(self):
        from raft_tpu.parallel.sharded_ann import (
            codebook_wire_bytes_per_iter,
            lloyd_wire_bytes_per_iter,
        )

        full = lloyd_wire_bytes_per_iter(32, 16, 8, comm_mode="full")
        ca = lloyd_wire_bytes_per_iter(32, 16, 8, comm_mode="ca")
        assert full / ca >= 2.0
        # one shard moves nothing under either schedule
        assert lloyd_wire_bytes_per_iter(32, 16, 1, comm_mode="ca") == 0.0
        # explicit cap: full width restores the full payload plus the
        # (tiny) changed-count vector
        capped = lloyd_wire_bytes_per_iter(32, 16, 8, comm_mode="ca", ca_cap=32)
        assert capped > full
        cb_full = codebook_wire_bytes_per_iter(8, 256, 4, 8, comm_mode="full")
        cb_ca = codebook_wire_bytes_per_iter(8, 256, 4, 8, comm_mode="ca")
        assert cb_full / cb_ca >= 2.0

    def test_ca_build_halves_steady_state_bytes_and_holds_recall(self, setup):
        """End-to-end build under both schedules with the build-comms
        counters on: the steady-state per-iteration bytes (phase
        ``kmeans_ca`` / ``pq_codebook_ca``) must undercut the full
        schedule's per-iteration bytes by >=2x, and the CA-built index's
        recall must track the full-built index."""
        from raft_tpu import obs
        from raft_tpu.parallel.sharded_ann import sharded_ivf_pq_build

        mesh, X, Q = setup
        k = 5
        params = ivf_pq.IvfPqIndexParams(
            n_lists=32, pq_dim=8, kmeans_n_iters=self.ITERS, seed=2
        )
        reg = obs.registry()

        def build(mode):
            reg.reset()
            obs.enable()
            try:
                index = sharded_ivf_pq_build(mesh, X, params, comm_mode=mode)
                snap = reg.as_dict()["counters"]
            finally:
                obs.disable()
                reg.reset()
            per_iter = {}
            for phase in ("kmeans_full", "kmeans_ca",
                          "pq_codebook_full", "pq_codebook_ca"):
                b = snap.get('comms.build.bytes{phase="%s"}' % phase, 0.0)
                launches = snap.get(
                    'comms.build.launches{phase="%s"}' % phase, 0.0)
                # CA pays two collective launches per iteration (counts +
                # selected rows); full pays one fused allreduce
                iters = launches / (2.0 if phase.endswith("_ca") else 1.0)
                per_iter[phase] = b / iters if iters else 0.0
            return index, per_iter, snap

        full_idx, full_iter, _ = build("full")
        ca_idx, ca_iter, ca_snap = build("ca")
        assert full_iter["kmeans_full"] >= 2.0 * ca_iter["kmeans_ca"], (
            full_iter, ca_iter)
        assert full_iter["pq_codebook_full"] >= 2.0 * ca_iter["pq_codebook_ca"], (
            full_iter, ca_iter)
        # the warmup exchanges are full-width and counted as such
        assert ca_snap['comms.build.launches{phase="kmeans_full"}'] == 2.0
        # one init-only seed-pool allgather per build
        assert ca_snap['comms.build.launches{phase="seed"}'] == 1.0

        bf = brute_force.build(X, metric=DistanceType.L2Expanded)
        _, gt = brute_force.search(bf, Q, k)

        def rec(index):
            _, si = ivf_pq.search(
                index, Q, k, ivf_pq.IvfPqSearchParams(n_probes=16), mode="scan"
            )
            return float(neighborhood_recall(np.asarray(si), np.asarray(gt)))

        rec_full, rec_ca = rec(full_idx), rec(ca_idx)
        # measured 0.713 / 0.678 at this (deliberately hard) shape — the
        # bound is the drift contract, not an absolute quality floor
        assert rec_ca >= rec_full - 0.05, (rec_full, rec_ca)

    @pytest.mark.slow
    def test_distributed_build_gap_vs_single_chip(self, setup):
        """Regression pin for the cross-shard codebook seed: the
        distributed build (either schedule) must stay within 0.05 recall
        of the single-chip build on identical params — the rank-0-pool
        seed this replaces left ~0.02-0.03 on the table and would trip
        this on harder shapes."""
        from raft_tpu.parallel.sharded_ann import sharded_ivf_pq_build

        mesh, X, Q = setup
        k = 5
        params = ivf_pq.IvfPqIndexParams(
            n_lists=32, pq_dim=8, kmeans_n_iters=self.ITERS, seed=2
        )
        bf = brute_force.build(X, metric=DistanceType.L2Expanded)
        _, gt = brute_force.search(bf, Q, k)

        def rec(index):
            _, si = ivf_pq.search(
                index, Q, k, ivf_pq.IvfPqSearchParams(n_probes=16), mode="scan"
            )
            return float(neighborhood_recall(np.asarray(si), np.asarray(gt)))

        rec_sc = rec(ivf_pq.build(X, params))
        rec_full = rec(sharded_ivf_pq_build(mesh, X, params, comm_mode="full"))
        rec_ca = rec(sharded_ivf_pq_build(mesh, X, params, comm_mode="ca"))
        assert rec_full >= rec_sc - 0.05, (rec_sc, rec_full)
        assert rec_ca >= rec_sc - 0.05, (rec_sc, rec_ca)
