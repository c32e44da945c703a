"""ICI ring top-k exchange (``ops/pallas/ring_topk``) on the CPU mesh.

The acceptance contract is **bit-parity**: the ring engine must reproduce
the gather path's merge — a stable ``top_k`` over the shard-major
concatenation — id-for-id at every device count, select direction, odd
shape, tie pattern, and degraded-health mask. Plus the fallback seam
(injected ``comms.ring_topk`` chaos → gather results, warn-once,
``fallbacks{algo="ring_topk"}``), interpret-mode parity of the in-kernel
Pallas fold against the XLA fold, the scratch-shape ↔ vmem-model drift
guard, and the wire-byte model behind the ≥2x-at-8-devices claim.
"""
import functools
import warnings

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from raft_tpu import obs
from raft_tpu.core.errors import KernelFailure, LogicError
from raft_tpu.neighbors import ivf_flat
from raft_tpu.ops.pallas import ring_topk as rt
from raft_tpu.ops.select_k import merge_parts
from raft_tpu.parallel import make_mesh, sharded_ann, sharded_ivf_flat_search
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
    """Per-shard local top-k candidate sets ``[n_shards, nq, kc]``.

    Values ascend within each shard row (a real local top-k is sorted);
    ``ties=True`` draws integer-valued floats so cross-shard equal values
    exercise the (value, position) tie-break; shards in ``demote`` carry
    worst-value/-1 candidates (the degraded-mode masking contract)."""
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


def _run_ring(mesh, vs, ins, k, select_min):
    """Run ``ring_topk`` inside shard_map, one candidate set per shard."""

    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(P("data"), P("data")), out_specs=(P(), P()), check_vma=False,
    )
    def prog(vb, ib):
        return rt.ring_topk(vb[0], ib[0], k, select_min=select_min, axis="data")

    return jax.jit(prog)(vs, ins)


def _gather_reference(vs, ins, k, select_min):
    """The gather path's merge: stable top-k over the shard-major concat."""
    n, nq, kc = vs.shape
    cat_v = jnp.moveaxis(vs, 0, 1).reshape(nq, n * kc)
    cat_i = jnp.moveaxis(ins, 0, 1).reshape(nq, n * kc)
    return merge_parts(cat_v, cat_i, k, select_min=select_min)


class TestRingParity:
    @pytest.mark.parametrize("n_shards", [2, 4, 8])
    @pytest.mark.parametrize("select_min", [True, False])
    def test_bit_parity_with_gather(self, eight_devices, n_shards, select_min):
        mesh = make_mesh(eight_devices[:n_shards])
        rng = np.random.default_rng(n_shards)
        nq, k = 64, 10
        vs, ins = _shard_candidates(rng, n_shards, nq, k)
        if not select_min:
            vs = -vs
        rv, ri = _run_ring(mesh, vs, ins, k, select_min)
        gv, gi = _gather_reference(vs, ins, k, select_min)
        np.testing.assert_array_equal(np.asarray(ri), np.asarray(gi))
        np.testing.assert_allclose(np.asarray(rv), np.asarray(gv), atol=1e-6)

    @pytest.mark.parametrize("nq,k,kc", [(13, 7, 7), (5, 16, 16), (64, 10, 6)])
    def test_odd_shapes_and_width_padding(self, eight_devices, nq, k, kc):
        """Query counts not divisible by the ring size and local widths
        below the requested k (padded with losing sentinels)."""
        mesh = make_mesh(eight_devices[:4])
        rng = np.random.default_rng(nq * k)
        vs, ins = _shard_candidates(rng, 4, nq, kc)
        rv, ri = _run_ring(mesh, vs, ins, k, True)
        gv, gi = _gather_reference(vs, ins, k, True)
        np.testing.assert_array_equal(np.asarray(ri), np.asarray(gi))
        np.testing.assert_allclose(np.asarray(rv), np.asarray(gv), atol=1e-6)

    def test_tie_break_matches_gather_order(self, eight_devices):
        """Integer-valued candidates: many exact cross-shard ties — the
        (value, concat position) lane must reproduce the gather path's
        stable shard-major preference exactly."""
        mesh = make_mesh(eight_devices)
        rng = np.random.default_rng(0)
        vs, ins = _shard_candidates(rng, 8, 32, 8, ties=True)
        rv, ri = _run_ring(mesh, vs, ins, 8, True)
        gv, gi = _gather_reference(vs, ins, 8, True)
        np.testing.assert_array_equal(np.asarray(ri), np.asarray(gi))
        np.testing.assert_array_equal(np.asarray(rv), np.asarray(gv))

    @pytest.mark.parametrize("demote", [(1,), (0, 3)])
    def test_demoted_shards_lose_every_fold(self, eight_devices, demote):
        """Masked (degraded) shards forward worst-value/-1 candidates:
        they must vanish from the merged result exactly as they vanish
        from the gathered merge, and surviving ids stay bit-identical."""
        mesh = make_mesh(eight_devices[:4])
        rng = np.random.default_rng(42)
        vs, ins = _shard_candidates(rng, 4, 24, 10, demote=demote)
        rv, ri = _run_ring(mesh, vs, ins, 10, True)
        gv, gi = _gather_reference(vs, ins, 10, True)
        np.testing.assert_array_equal(np.asarray(ri), np.asarray(gi))
        np.testing.assert_allclose(np.asarray(rv), np.asarray(gv), atol=1e-6)
        dead = {s * 10_000 + c for s in demote for c in range(10)}
        assert not dead.intersection(np.asarray(ri).ravel().tolist())

    def test_single_shard_is_trivial(self, eight_devices):
        mesh = make_mesh(eight_devices[:1])
        rng = np.random.default_rng(9)
        vs, ins = _shard_candidates(rng, 1, 16, 10)
        rv, ri = _run_ring(mesh, vs, ins, 10, True)
        np.testing.assert_array_equal(np.asarray(ri), np.asarray(ins[0]))


class TestRingObsAndFaults:
    def test_span_and_counters(self, eight_devices):
        mesh = make_mesh(eight_devices[:4])
        rng = np.random.default_rng(1)
        vs, ins = _shard_candidates(rng, 4, 16, 8)
        reg = obs.registry()
        reg.reset()
        obs.enable()
        try:
            _run_ring(mesh, vs, ins, 8, True)
            snap = reg.as_dict()
        finally:
            obs.disable()
            reg.reset()
        assert snap["counters"]['comms.ring.hops{axis="data"}'] == 6.0
        sent = snap["counters"]['comms.ring.bytes{axis="data",direction="send"}']
        recvd = snap["counters"]['comms.ring.bytes{axis="data",direction="recv"}']
        # 3 RS hops x B=4 rows x k=8 x 12B + 3 AG hops x 4 x 8 x 8B
        assert sent == recvd == 3 * 4 * 8 * (rt.RS_ENTRY_BYTES + rt.AG_ENTRY_BYTES)
        # on the wire the XLA engine ships the sort-key lane too (16B/entry)
        wire = snap["counters"]['comms.ring.wire_bytes{axis="data",direction="send"}']
        assert wire == 3 * 4 * 8 * (16 + rt.AG_ENTRY_BYTES)

    def test_fault_point_registered_and_fires(self, eight_devices):
        assert "comms.ring_topk" in faults.FAULT_POINTS
        mesh = make_mesh(eight_devices[:2])
        rng = np.random.default_rng(2)
        vs, ins = _shard_candidates(rng, 2, 8, 4)
        with faults.injected("comms.ring_topk", KernelFailure("chaos")):
            with pytest.raises(KernelFailure):
                _run_ring(mesh, vs, ins, 4, True)


class TestRingFallback:
    def _search(self, mesh, X, Q, merge_mode):
        index = ivf_flat.build(X, ivf_flat.IvfFlatIndexParams(n_lists=32, seed=1))
        return sharded_ivf_flat_search(
            mesh, index, Q, 10, n_probes=16, merge_mode=merge_mode
        )

    @pytest.mark.parametrize("merge_mode", ["auto", "ring"])
    def test_injected_ring_failure_falls_back_to_gather(
        self, eight_devices, merge_mode
    ):
        """A failing ring program must not fail the query: the dispatch
        re-runs on the gather engine, counts the fallback, and warns once
        — for auto AND for explicitly requested ring (the ring is a
        transport, parity is exact, so falling back is always safe)."""
        mesh = make_mesh(eight_devices[:4])
        rng = np.random.default_rng(5)
        X = rng.standard_normal((512, 16)).astype(np.float32)
        Q = rng.standard_normal((16, 16)).astype(np.float32)
        want = self._search(mesh, X, Q, "gather")
        # the seam fires at trace time: a ring program another test traced
        # at this shape would be reused and never reach it
        sharded_ann._ivf_flat_fn.cache_clear()
        reg = obs.registry()
        reg.reset()
        obs.enable()
        try:
            with faults.injected("comms.ring_topk", KernelFailure("chaos")):
                with warnings.catch_warnings(record=True) as wlog:
                    warnings.simplefilter("always")
                    got = self._search(mesh, X, Q, merge_mode)
                    again = self._search(mesh, X, Q, merge_mode)
            snap = reg.as_dict()
        finally:
            obs.disable()
            reg.reset()
        np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(want[1]))
        np.testing.assert_array_equal(np.asarray(again[1]), np.asarray(want[1]))
        key = 'fallbacks{algo="ring_topk",reason="KernelFailure"}'
        assert snap["counters"][key] == 2.0
        ring_warns = [w for w in wlog if "ring_topk" in str(w.message)]
        assert len(ring_warns) == 1  # warn-once per (algo, reason)

    def test_healthy_ring_matches_gather_end_to_end(self, eight_devices):
        mesh = make_mesh(eight_devices)
        rng = np.random.default_rng(6)
        X = rng.standard_normal((1024, 16)).astype(np.float32)
        Q = rng.standard_normal((32, 16)).astype(np.float32)
        rv, ri = self._search(mesh, X, Q, "ring")
        gv, gi = self._search(mesh, X, Q, "gather")
        np.testing.assert_array_equal(np.asarray(ri), np.asarray(gi))
        np.testing.assert_allclose(np.asarray(rv), np.asarray(gv), atol=1e-6)


class TestFusedFold:
    """Interpret-mode coverage of the Pallas fold — the per-hop compute
    of the remote-DMA kernel (the ring schedule itself needs real ICI)."""

    def _tuples(self, rng, rows, w, ties=False):
        if ties:
            k1 = rng.integers(0, 5, (rows, w)).astype(np.float32)
            k2 = rng.integers(0, 5, (rows, w)).astype(np.float32)
        else:
            k1 = rng.standard_normal((rows, w)).astype(np.float32)
            k2 = rng.standard_normal((rows, w)).astype(np.float32)
        p1 = rng.permutation(rows * 2 * w)[: rows * w].reshape(rows, w)
        p2 = rng.permutation(rows * 2 * w)[rows * w:].reshape(rows, w)
        mk = lambda kk, pp: (  # noqa: E731
            jnp.asarray(kk), jnp.asarray(pp, jnp.int32),
            jnp.asarray(kk * 2.0), jnp.asarray(pp % 997, jnp.int32),
        )
        return mk(k1, p1), mk(k2, p2)

    @pytest.mark.parametrize("rows,w,ties", [(32, 16, False), (64, 8, True)])
    def test_hop_merge_bit_matches_xla_fold(self, rows, w, ties):
        rng = np.random.default_rng(rows + w)
        a, b = self._tuples(rng, rows, w, ties)
        got = rt.hop_merge(a, b, qt=32, interpret=True)
        want = rt._fold(a, b, w)
        for g, x in zip(got, want):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(x))

    def test_hop_merge_rejects_ragged_tiles(self):
        rng = np.random.default_rng(3)
        a, b = self._tuples(rng, 33, 8)
        with pytest.raises(LogicError):
            rt.hop_merge(a, b, qt=32, interpret=True)


class TestFusedRingInterpret:
    """The remote-DMA ring kernels themselves, run by Pallas' TPU
    interpreter on the CPU mesh with its race detector on: ids and values
    must equal the ``ppermute`` engine's, and no DMA may land in a slot
    its reader has not released."""

    @pytest.mark.parametrize("n", [2, 4])
    @pytest.mark.parametrize("engine", ["ring", "scan_ring"])
    def test_matches_xla_engine_without_races(self, rng, monkeypatch, n, engine):
        from jax.experimental import pallas as pl
        from jax.experimental.pallas import tpu as pltpu
        from jax._src.pallas.mosaic.interpret import interpret_pallas_call as ipc

        pallas_call = pl.pallas_call

        def interpreted(*args, **kwargs):
            kwargs["interpret"] = pltpu.InterpretParams(detect_races=True)
            return pallas_call(*args, **kwargs)

        monkeypatch.setattr(rt.pl, "pallas_call", interpreted)
        k, nq = 10, 37
        kc = k if engine == "ring" else 3 * k
        v, i = _shard_candidates(rng, n, nq, kc, demote=(1,))
        fn = rt.ring_topk if engine == "ring" else rt.scan_ring_topk
        mesh = make_mesh(jax.devices()[:n])
        outs = []
        for fused in (False, True):
            step = jax.jit(shard_map(
                lambda a, b, fused=fused: fn(a[0], b[0], k, axis="data", use_fused=fused),
                mesh=mesh, in_specs=(P("data"), P("data")), out_specs=(P(), P()),
                check_vma=False))
            outs.append(jax.device_get(step(v, i)))
        np.testing.assert_array_equal(outs[1][1], outs[0][1])
        np.testing.assert_array_equal(outs[1][0], outs[0][0])
        assert not ipc.races.races_found


class TestResidencyModel:
    def test_scratch_shapes_match_vmem_model(self):
        """Drift guard: the kernel's declared scratch must be exactly the
        buffers the lint-checked residency model accounts for."""
        from raft_tpu.ops.pallas.vmem_model import ring_topk_residency

        n, B, w = 8, 128, 128
        res = ring_topk_residency(n=n, B=B, w=w)
        modeled = [
            r for r in res.residents if r.kind == "scratch"
        ]
        declared = rt.kernel_scratch_shapes(n, B, w)
        vmem = [s for s in declared if str(s.memory_space) == "vmem"]
        assert len(vmem) == len(modeled)
        for spec, r in zip(vmem, modeled):
            assert tuple(spec.shape) == tuple(r.shape), r.name
            assert jnp.dtype(spec.dtype).itemsize == r.itemsize, r.name
        # the non-VMEM entries are the DMA semaphore pairs and the credits
        assert len(declared) - len(vmem) == 3
        # and the whole kernel fits the plan comfortably
        assert res.total_bytes < 12 * 2**20

    def test_fused_slots_count_on_the_wire(self):
        # the fused kernel's DMA slots are a lane tile wide: at k=10 the
        # wire carries 128 columns, 12.8x the payload's 10 (x 16/12 on
        # the reduce-scatter's sort-key lane)
        assert rt.lanes(10) == 128 and rt.lanes(129) == 256
        n, B, k = 4, 32, 10
        payload = (n - 1) * B * k * (rt.RS_ENTRY_BYTES + rt.AG_ENTRY_BYTES)
        fused = rt.ring_wire_bytes(n, B, rt.lanes(k))
        assert fused == (n - 1) * B * 128 * 24
        assert fused / payload == pytest.approx(12.8 * 24 / 20)
        assert rt.ring_wire_bytes(1, B, rt.lanes(k)) == 0.0

    def test_wire_model_reduction_at_8(self):
        ring = rt.wire_bytes_per_query(8, 10, "ring")
        gather = rt.wire_bytes_per_query(8, 10, "gather")
        assert gather / ring >= 2.0
        assert rt.wire_bytes_per_query(1, 10, "ring") == 0.0
        # ring advantage grows ~0.4n
        assert (
            rt.wire_bytes_per_query(16, 10, "gather")
            / rt.wire_bytes_per_query(16, 10, "ring")
            > gather / ring
        )
