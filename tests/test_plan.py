"""Dispatch rules (``raft_tpu.plan``) and what the serving engine makes
of them.

Every ``"auto"`` engine choice is one plain rule returning the engine's
name (docs/planner.md). Pinned here:

* **truth tables** — each rule against its written-out condition over
  the inputs its call sites pass;
* **auto equals the rule's engine** — a search with ``mode="auto"`` is
  bit-identical to the same search with the rule's engine pinned, and
  the serving engine dispatches, per shape bucket, exactly the engine
  the rule picks for that bucket;
* **the engine does not plan** — a pinned mode dispatches the pinned
  engine, and the maintenance tick compiles nothing.
"""
import numpy as np
import pytest

from raft_tpu import obs
from raft_tpu import plan as planlib
from raft_tpu.mutable import MutableIndex
from raft_tpu.neighbors import ivf_flat, ivf_pq
from raft_tpu.serve.bucketing import bucket_sizes
from raft_tpu.serve.engine import ServingEngine


@pytest.fixture
def serve_obs():
    reg = obs.registry()
    reg.reset()
    obs.enable()
    yield reg
    obs.disable()
    reg.reset()


def _counter(registry, name, **labels):
    """Sum of every counter sample matching ``name`` and ``labels``."""
    snap = registry.as_dict()["counters"]
    total = 0.0
    for key, value in snap.items():
        if not key.startswith(name + "{") and key != name:
            continue
        if all(f'{k}="{v}"' in key for k, v in labels.items()):
            total += value
    return total


# -- rule truth tables -------------------------------------------------------


NQ_SWEEP = list(range(1, 16)) + [63, 64, 126, 127, 128, 129, 192, 256, 1024]


class TestRules:
    @pytest.mark.parametrize("on_tpu", [False, True])
    @pytest.mark.parametrize("fused_ok", [False, True])
    @pytest.mark.parametrize("wants_f32_lut", [False, True])
    def test_ivf_search_mode(self, on_tpu, fused_ok, wants_f32_lut):
        """ivf_pq/ivf_flat/rabitq mode="auto": the probe/scan/fused three-way."""
        for nq in NQ_SWEEP:
            if nq < 128:
                want = "probe"
            elif on_tpu and fused_ok and not wants_f32_lut:
                want = "fused"
            else:
                want = "scan"
            got = planlib.plan_search_mode(nq, on_tpu=on_tpu, fused_ok=fused_ok,
                                           wants_f32_lut=wants_f32_lut)
            assert isinstance(got, str)
            assert got == want, (nq, on_tpu, fused_ok, wants_f32_lut)

    @pytest.mark.parametrize("on_tpu", [False, True])
    @pytest.mark.parametrize("fused_ok", [False, True])
    def test_cagra_mode(self, on_tpu, fused_ok):
        want = "fused" if on_tpu and fused_ok else "xla"
        assert planlib.plan_cagra_mode(on_tpu=on_tpu, fused_ok=fused_ok) == want

    def test_merge_mode(self):
        for n_shards in (1, 2, 3, 4, 8, 16):
            want = "ring" if n_shards > 1 else "gather"
            assert planlib.plan_merge_mode(n_shards) == want, n_shards

    def test_comm_mode(self):
        # ca on every real accumulator shape across shards (row cap below
        # the full rows), full on one shard; without the shape, the
        # shard count alone decides
        for n_shards in (2, 4, 8):
            for n_rows in (32, 256, 4096):
                for d in (8, 64, 768):
                    got = planlib.plan_comm_mode(n_shards, n_rows=n_rows, d=d)
                    assert got == "ca", (n_rows, d, n_shards)
            assert planlib.plan_comm_mode(n_shards) == "ca"
        assert planlib.plan_comm_mode(1, n_rows=4096, d=64) == "full"
        assert planlib.plan_comm_mode(1) == "full"

    def test_comm_mode_degenerate_cap_keeps_full(self):
        """When the CA row cap cannot undercut the full exchange, the
        byte comparison keeps full — and the wire model's bytes say so."""
        from raft_tpu.parallel.wire_model import lloyd_wire_bytes_per_iter

        assert planlib.plan_comm_mode(2, n_rows=4, d=8, ca_cap=4) == "full"  # cap == rows
        ca = lloyd_wire_bytes_per_iter(4, 8, 2, comm_mode="ca", ca_cap=4)
        full = lloyd_wire_bytes_per_iter(4, 8, 2, comm_mode="full")
        assert ca + planlib.CA_SELECT_BYTES >= full

    @pytest.mark.parametrize("eligible", [False, True])
    @pytest.mark.parametrize("on_tpu", [False, True])
    def test_delta_mode(self, eligible, on_tpu):
        want = "fused" if eligible and on_tpu else "exact"
        assert planlib.plan_delta_mode(eligible=eligible, on_tpu=on_tpu) == want

    @pytest.mark.parametrize("per_subspace", [False, True])
    def test_pq_kind(self, per_subspace):
        for pq_bits in range(1, 9):
            if pq_bits == 1:
                want = "rabitq"
            else:
                want = "nibble" if pq_bits == 8 and per_subspace else "kmeans"
            assert planlib.plan_pq_kind(pq_bits, per_subspace) == want, pq_bits

    def test_sparse_mode(self):
        B = 1 << 18
        for n_cols in (16, B - 1, B, B + 1, B * 4):
            for native_ok in (False, True):
                want = "native" if n_cols > B and native_ok else "densify"
                got = planlib.plan_sparse_mode(n_cols, native_ok=native_ok)
                assert got == want, (n_cols, native_ok)


# -- auto equals the rule's explicit engine, bit for bit ---------------------


@pytest.fixture(scope="module")
def small_corpus():
    rng = np.random.default_rng(11)
    X = rng.standard_normal((512, 16)).astype(np.float32)
    Q = rng.standard_normal((256, 16)).astype(np.float32)
    return X, Q


@pytest.fixture(scope="module")
def flat_index(small_corpus):
    X, _ = small_corpus
    return ivf_flat.build(X, ivf_flat.IvfFlatIndexParams(n_lists=8, seed=3))


FLAT_PARAMS = ivf_flat.IvfFlatSearchParams(n_probes=4)


def _rule_mode(nq):
    return planlib.plan_search_mode(nq, on_tpu=False, fused_ok=True)


def _assert_same(a, b):
    np.testing.assert_array_equal(np.asarray(a[1]), np.asarray(b[1]))
    np.testing.assert_array_equal(np.asarray(a[0]), np.asarray(b[0]))


class TestBitParity:
    def test_ivf_pq_auto_search(self, small_corpus):
        X, Q = small_corpus
        idx = ivf_pq.build(X, ivf_pq.IvfPqIndexParams(n_lists=8, pq_dim=8, seed=3))
        sp = ivf_pq.IvfPqSearchParams(n_probes=4)
        nq = 130
        _assert_same(ivf_pq.search(idx, Q[:nq], 10, sp, mode="auto"),
                     ivf_pq.search(idx, Q[:nq], 10, sp, mode=_rule_mode(nq)))

    def test_ivf_flat_auto_search_both_sides_of_128(self, small_corpus, flat_index):
        _, Q = small_corpus
        for nq in (4, 130):  # probe side and scan side of the rule
            _assert_same(
                ivf_flat.search(flat_index, Q[:nq], 10, FLAT_PARAMS, mode="auto"),
                ivf_flat.search(flat_index, Q[:nq], 10, FLAT_PARAMS, mode=_rule_mode(nq)))

    def test_engine_serving_bit_identical(self, small_corpus, flat_index):
        _, Q = small_corpus
        eng = ServingEngine(max_batch=16, max_wait_ms=0.0)
        eng.register("t", "ivf_flat", flat_index, params=FLAT_PARAMS)
        fut = eng.submit("t", Q[:8], k=5)
        eng.run_until_idle()
        r = fut.result()
        _assert_same((r.distances, r.indices),
                     ivf_flat.search(flat_index, Q[:8], 5, FLAT_PARAMS, query_batch=8))


@pytest.mark.parametrize("bucket", bucket_sizes(256))
def test_engine_bucket_dispatches_rule_engine(small_corpus, flat_index, serve_obs, bucket):
    """A bucket's engine is what the search's auto resolves there: the
    dispatch counter shows the rule's engine, and the served ids equal a
    direct search with that engine pinned."""
    _, Q = small_corpus
    rule = planlib.plan_search_mode(bucket, on_tpu=False, fused_ok=True)
    eng = ServingEngine(max_batch=256, max_wait_ms=0.0)
    eng.register("b", "ivf_flat", flat_index, params=FLAT_PARAMS)
    fut = eng.submit("b", Q[:bucket], k=5)
    eng.run_until_idle()
    r = fut.result()
    assert r.bucket == bucket
    assert _counter(serve_obs, "ivf_flat.search.calls", mode=rule) == 1
    assert _counter(serve_obs, "ivf_flat.search.calls") == 1
    d, i = ivf_flat.search(flat_index, Q[:bucket], 5, FLAT_PARAMS, mode=rule,
                           query_batch=bucket)
    np.testing.assert_array_equal(np.asarray(r.indices), np.asarray(i))
    np.testing.assert_array_equal(np.asarray(r.distances), np.asarray(d))


# -- explain and the engine's hands-off dispatch -----------------------------


def test_registration_plan_explain(flat_index):
    eng = ServingEngine(max_batch=16, max_wait_ms=0.0)
    eng.register("exp", "ivf_flat", flat_index, params=FLAT_PARAMS)
    text = eng.plan_explain("exp")
    assert text is not None and "plan[exp]" in text
    lines = [ln.strip() for ln in text.splitlines() if "bucket modes:" in ln]
    assert len(lines) == 1
    entries = lines[0].split("bucket modes:")[1].split()
    assert entries == [f"{b}→{_rule_mode(b)}" for b in bucket_sizes(16)]


def test_pinned_mode_never_planned(small_corpus, flat_index, serve_obs):
    """A pinned mode dispatches the pinned engine (scan at a bucket whose
    rule says probe), and there is no bucket plan to explain."""
    _, Q = small_corpus
    eng = ServingEngine(max_batch=16, max_wait_ms=0.0)
    eng.register("pinned", "ivf_flat", flat_index, mode="scan", params=FLAT_PARAMS)
    assert eng.plan_explain("pinned") is None
    fut = eng.submit("pinned", Q[:6], k=5)
    eng.run_until_idle()
    fut.result()
    assert _rule_mode(8) == "probe"
    assert _counter(serve_obs, "ivf_flat.search.calls", mode="scan") == 1
    assert _counter(serve_obs, "ivf_flat.search.calls") == 1


def test_maintenance_tick_compiles_nothing(small_corpus, flat_index):
    """The tick runs compactors and the recorder only: with a mutable
    registration that grew 2x and an auto-mode registration that served
    traffic, it builds no program."""
    _, Q = small_corpus
    rng = np.random.default_rng(5)
    mi = MutableIndex("brute_force", 16)
    mi.insert(rng.standard_normal((64, 16)).astype(np.float32))
    eng = ServingEngine(max_batch=16, max_wait_ms=0.0)
    eng.register_mutable("grow", mi)
    eng.register("flat", "ivf_flat", flat_index, params=FLAT_PARAMS)
    for index_id in ("grow", "flat"):
        for _ in range(3):
            fut = eng.submit(index_id, Q[:7], k=5)
            eng.run_until_idle()
            fut.result()
    mi.insert(rng.standard_normal((64, 16)).astype(np.float32))
    assert mi.size == 128
    before = eng.cache.stats()
    eng.maintenance_tick()
    after = eng.cache.stats()
    assert (after.misses, after.size) == (before.misses, before.size)
