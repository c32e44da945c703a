"""Fused scan→ring top-k (``ops/pallas/ring_topk.scan_ring_topk``).

The fused engine takes the per-shard scan's WIDE candidate tile
``[nq, kc]`` (kc = k·refine_ratio candidates, not yet reduced to k) and
runs the local top-k fold inside the ring engine, so the acceptance
contract has two layers: the in-engine scan fold must bit-match the
sort-truncate local top-k at every ragged width and tie pattern, and the
end-to-end result must stay id-for-id equal to the gather reference —
a stable top-k over the shard-major concatenation — at every device
count, select direction, and demoted-shard mask. Plus the fused-path
fallback seam (``comms.ring_topk`` chaos with ``kind="scan"`` → gather
results, ``fallbacks{algo="scan_ring_topk"}``, the plain ring
untouched), the scratch-shape ↔ vmem-model drift guard at the lint
binding shape, and the wire model (fused_ring moves ring bytes — the
fusion saves HBM round-trips, not wire).
"""
import functools
import warnings

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from raft_tpu import obs
from raft_tpu.core.errors import KernelFailure, LogicError
from raft_tpu.neighbors import ivf_flat
from raft_tpu.ops.pallas import ring_topk as rt
from raft_tpu.ops.select_k import merge_parts
from raft_tpu.parallel import make_mesh, sharded_ivf_flat_search
from jax import shard_map
from raft_tpu.robust import faults, reset_warned


@pytest.fixture(autouse=True)
def _pristine():
    faults.disable()
    faults.clear()
    obs.disable()
    obs.registry().reset()
    reset_warned()
    yield
    faults.disable()
    faults.clear()
    obs.disable()
    obs.registry().reset()
    reset_warned()


def _shard_candidates(rng, n_shards, nq, kc, *, ties=False, demote=()):
    """Per-shard WIDE candidate tiles ``[n_shards, nq, kc]`` — sorted
    within each row like a real scan output, integer-valued when
    ``ties=True`` so cross-shard AND cross-column equal values exercise
    the (value, position) tie-break, worst-value/-1 rows for shards in
    ``demote`` (the degraded-mode masking contract)."""
    if ties:
        v = rng.integers(0, 7, (n_shards, nq, kc)).astype(np.float32)
    else:
        v = rng.standard_normal((n_shards, nq, kc)).astype(np.float32)
    v = np.sort(v, axis=2)
    i = np.empty((n_shards, nq, kc), np.int32)
    for s in range(n_shards):
        i[s] = s * 10_000 + np.arange(kc, dtype=np.int32)[None, :]
    for s in demote:
        v[s] = np.inf
        i[s] = -1
    return jnp.asarray(v), jnp.asarray(i)


def _run_scan(mesh, vs, ins, k, select_min):
    """Run ``scan_ring_topk`` inside shard_map, one wide tile per shard."""

    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(P("data"), P("data")), out_specs=(P(), P()), check_vma=False,
    )
    def prog(vb, ib):
        return rt.scan_ring_topk(vb[0], ib[0], k, select_min=select_min, axis="data")

    return jax.jit(prog)(vs, ins)


def _gather_reference(vs, ins, k, select_min):
    """The gather path's merge: stable top-k over the shard-major concat
    of the FULL wide tiles (kc columns each, not pre-reduced)."""
    n, nq, kc = vs.shape
    cat_v = jnp.moveaxis(vs, 0, 1).reshape(nq, n * kc)
    cat_i = jnp.moveaxis(ins, 0, 1).reshape(nq, n * kc)
    return merge_parts(cat_v, cat_i, k, select_min=select_min)


class TestScanRingParity:
    @pytest.mark.parametrize("n_shards", [2, 4, 8])
    @pytest.mark.parametrize("select_min", [True, False])
    @pytest.mark.parametrize("kc", [4, 10, 16])
    def test_bit_parity_with_gather(
        self, eight_devices, n_shards, select_min, kc
    ):
        """kc=k (no local fold), kc=2.5k (ragged last fold slice), and
        kc=4k (full fold) must all reproduce the gathered wide merge."""
        mesh = make_mesh(eight_devices[:n_shards])
        rng = np.random.default_rng(n_shards * 100 + kc)
        nq, k = 37, 4  # nq deliberately not a multiple of any ring size
        vs, ins = _shard_candidates(rng, n_shards, nq, kc)
        if not select_min:
            vs = -vs
        rv, ri = _run_scan(mesh, vs, ins, k, select_min)
        gv, gi = _gather_reference(vs, ins, k, select_min)
        np.testing.assert_array_equal(np.asarray(ri), np.asarray(gi))
        np.testing.assert_allclose(np.asarray(rv), np.asarray(gv), atol=1e-6)

    def test_tie_break_matches_gather_order(self, eight_devices):
        """Integer-valued wide tiles: exact ties across shards AND
        across the fold slices within one shard — the (value, concat
        position) lane must reproduce the gather path's stable
        shard-major, column-minor preference exactly."""
        mesh = make_mesh(eight_devices)
        rng = np.random.default_rng(0)
        vs, ins = _shard_candidates(rng, 8, 32, 20, ties=True)
        rv, ri = _run_scan(mesh, vs, ins, 8, True)
        gv, gi = _gather_reference(vs, ins, 8, True)
        np.testing.assert_array_equal(np.asarray(ri), np.asarray(gi))
        np.testing.assert_array_equal(np.asarray(rv), np.asarray(gv))

    @pytest.mark.parametrize("demote", [(1,), (0, 3)])
    def test_demoted_shards_lose_every_fold(self, eight_devices, demote):
        mesh = make_mesh(eight_devices[:4])
        rng = np.random.default_rng(42)
        vs, ins = _shard_candidates(rng, 4, 24, 25, demote=demote)
        rv, ri = _run_scan(mesh, vs, ins, 10, True)
        gv, gi = _gather_reference(vs, ins, 10, True)
        np.testing.assert_array_equal(np.asarray(ri), np.asarray(gi))
        np.testing.assert_allclose(np.asarray(rv), np.asarray(gv), atol=1e-6)
        dead = {s * 10_000 + c for s in demote for c in range(25)}
        assert not dead.intersection(np.asarray(ri).ravel().tolist())

    def test_single_shard_folds_locally(self, eight_devices):
        """n=1 skips the ring entirely; the scan fold alone must equal
        the stable local top-k of the wide tile."""
        mesh = make_mesh(eight_devices[:1])
        rng = np.random.default_rng(9)
        vs, ins = _shard_candidates(rng, 1, 16, 40, ties=True)
        rv, ri = _run_scan(mesh, vs, ins, 10, True)
        gv, gi = _gather_reference(vs, ins, 10, True)
        np.testing.assert_array_equal(np.asarray(ri), np.asarray(gi))
        np.testing.assert_array_equal(np.asarray(rv), np.asarray(gv))


class TestScanFold:
    """The incremental local fold vs the sort-truncate it replaces —
    ``_scan_fold`` must be bit-identical to the 2-key sort + truncate
    (same keys, same tie-break lane), including the ragged last slice."""

    @pytest.mark.parametrize("kc,k", [(7, 4), (16, 4), (41, 8)])
    @pytest.mark.parametrize("ties", [False, True])
    @pytest.mark.parametrize("select_min", [True, False])
    def test_scan_fold_bit_matches_sort_truncate(self, kc, k, ties, select_min):
        from jax import lax

        rng = np.random.default_rng(kc * k + ties)
        if ties:
            v = np.sort(rng.integers(0, 5, (13, kc)), axis=1).astype(np.float32)
        else:
            v = np.sort(rng.standard_normal((13, kc)), axis=1).astype(np.float32)
        if not select_min:
            v = -v
        v = jnp.asarray(v)
        i = jnp.asarray(rng.permutation(13 * kc).reshape(13, kc), jnp.int32)
        pos = jnp.asarray(rng.permutation(13 * kc).reshape(13, kc), jnp.int32)
        key = v if select_min else -v
        got = rt._scan_fold(key, pos, v, i, k, select_min)
        sk, sp, sv, si = lax.sort((key, pos, v, i), dimension=1, num_keys=2)
        want = (sk[:, :k], sp[:, :k], sv[:, :k], si[:, :k])
        for g, x in zip(got, want):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(x))


class TestScanRingFaultsAndFallback:
    def _search(self, mesh, X, Q, merge_mode):
        index = ivf_flat.build(X, ivf_flat.IvfFlatIndexParams(n_lists=32, seed=1))
        return sharded_ivf_flat_search(
            mesh, index, Q, 10, n_probes=16, merge_mode=merge_mode
        )

    def test_kind_scoped_fault_fires_scan_only(self, eight_devices):
        """The shared ``comms.ring_topk`` seam with ``kind="scan"`` must
        kill the fused engine and leave the plain ring alone."""
        mesh = make_mesh(eight_devices[:2])
        rng = np.random.default_rng(2)
        vs, ins = _shard_candidates(rng, 2, 8, 12)
        with faults.injected("comms.ring_topk", KernelFailure("chaos"),
                             match={"kind": "scan"}):
            with pytest.raises(KernelFailure):
                _run_scan(mesh, vs, ins, 4, True)

    def test_injected_scan_failure_falls_back_to_gather(self, eight_devices):
        """A failing fused program must not fail the query: the dispatch
        re-runs on the gather engine (identical ids — the parity tests
        above are what make this safe), counts the fallback under the
        fused engine's own algo label, and warns once; the plain ring
        keeps running through the same injection."""
        mesh = make_mesh(eight_devices[:4])
        rng = np.random.default_rng(5)
        X = rng.standard_normal((512, 16)).astype(np.float32)
        Q = rng.standard_normal((16, 16)).astype(np.float32)
        want = self._search(mesh, X, Q, "gather")
        reg = obs.registry()
        reg.reset()
        obs.enable()
        try:
            with faults.injected("comms.ring_topk", KernelFailure("chaos"),
                                 match={"kind": "scan"}):
                with warnings.catch_warnings(record=True) as wlog:
                    warnings.simplefilter("always")
                    got = self._search(mesh, X, Q, "fused_ring")
                    again = self._search(mesh, X, Q, "fused_ring")
                    ring = self._search(mesh, X, Q, "ring")
            snap = reg.as_dict()
        finally:
            obs.disable()
            reg.reset()
        np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(want[1]))
        np.testing.assert_array_equal(np.asarray(again[1]), np.asarray(want[1]))
        np.testing.assert_array_equal(np.asarray(ring[1]), np.asarray(want[1]))
        key = 'fallbacks{algo="scan_ring_topk",reason="KernelFailure"}'
        assert snap["counters"][key] == 2.0
        assert 'fallbacks{algo="ring_topk",reason="KernelFailure"}' not in snap["counters"]
        scan_warns = [w for w in wlog if "scan_ring_topk" in str(w.message)]
        assert len(scan_warns) == 1  # warn-once per (algo, reason)

    def test_healthy_fused_ring_matches_gather_end_to_end(self, eight_devices):
        mesh = make_mesh(eight_devices)
        rng = np.random.default_rng(6)
        X = rng.standard_normal((1024, 16)).astype(np.float32)
        Q = rng.standard_normal((32, 16)).astype(np.float32)
        fv, fi = self._search(mesh, X, Q, "fused_ring")
        gv, gi = self._search(mesh, X, Q, "gather")
        np.testing.assert_array_equal(np.asarray(fi), np.asarray(gi))
        np.testing.assert_allclose(np.asarray(fv), np.asarray(gv), atol=1e-6)


class TestScanResidencyModel:
    def test_scratch_shapes_match_vmem_model(self):
        """Drift guard at the lint binding shape: the fused kernel's
        declared scratch must be exactly the buffers the lint-checked
        residency model accounts for."""
        from raft_tpu.ops.pallas.vmem_model import scan_ring_topk_residency

        n, B, w, kc = 8, 128, 128, 256
        res = scan_ring_topk_residency(n=n, B=B, w=w, kc=kc)
        modeled = [r for r in res.residents if r.kind == "scratch"]
        declared = rt.scan_kernel_scratch_shapes(n, B, w, kc)
        vmem = [s for s in declared if str(s.memory_space) == "vmem"]
        assert len(vmem) == len(modeled)
        for spec, r in zip(vmem, modeled):
            assert tuple(spec.shape) == tuple(r.shape), r.name
            assert jnp.dtype(spec.dtype).itemsize == r.itemsize, r.name
        assert len(declared) - len(vmem) == 3  # DMA semaphore pairs + credits
        # kc=256 lands exactly on the 12 MiB plan (the wide input refs
        # dominate); kc=512 breaches — the binding pins the safe shape
        assert res.total_bytes <= int(16 * 2**20 * 0.75)
        wide = scan_ring_topk_residency(n=n, B=B, w=w, kc=512)
        assert wide.total_bytes > int(16 * 2**20 * 0.75)

    def test_scan_scratch_requires_aligned_width(self):
        with pytest.raises(LogicError):
            rt.scan_kernel_scratch_shapes(8, 128, 128, 200)  # kc % w != 0

    def test_wire_model_fused_equals_ring(self):
        for n in (2, 4, 8, 16):
            assert rt.wire_bytes_per_query(n, 10, "fused_ring") == (
                rt.wire_bytes_per_query(n, 10, "ring")
            )
        assert rt.wire_bytes_per_query(1, 10, "fused_ring") == 0.0
