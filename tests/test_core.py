"""Core-layer tests: resources, serialize, bitset, interruptible, errors.

Mirrors the reference's ``cpp/test/core`` coverage (serialize round-trips,
bitset semantics, interruptible cancellation).
"""
import io
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from raft_tpu.core import (
    Bitmap,
    Bitset,
    LogicError,
    Resources,
    as_array,
    default_resources,
    expects,
    interruptible,
    serialize,
)


class TestResources:
    def test_default(self):
        res = default_resources()
        assert res.device is not None

    def test_key_stream_distinct(self):
        res = Resources(seed=7)
        k1, k2 = res.next_key(), res.next_key()
        assert not np.array_equal(jax.random.key_data(k1), jax.random.key_data(k2))

    def test_key_batch(self):
        res = Resources(seed=3)
        ks = res.next_key(5)
        assert ks.shape[0] == 5

    def test_registry(self):
        res = Resources()
        assert res.get_resource("x", lambda: 42) == 42
        res.set_resource("x", 43)
        assert res.get_resource("x") == 43

    def test_mesh_missing_raises(self):
        with pytest.raises(ValueError):
            Resources().get_mesh()


class TestSerialize:
    @pytest.mark.parametrize("dtype", ["float32", "int32", "uint8"])
    def test_array_roundtrip(self, rng, dtype):
        x = rng.standard_normal((17, 9)).astype(dtype)
        buf = io.BytesIO()
        serialize.serialize_array(buf, jnp.asarray(x))
        buf.seek(0)
        y = serialize.deserialize_array(buf)
        np.testing.assert_array_equal(np.asarray(y), x)

    def test_scalar_and_string_roundtrip(self):
        buf = io.BytesIO()
        serialize.serialize_scalar(buf, 123, "int64")
        serialize.serialize_scalar(buf, 0.5, "float32")
        serialize.serialize_string(buf, "metric=L2Expanded")
        buf.seek(0)
        assert serialize.deserialize_scalar(buf, "int64") == 123
        assert serialize.deserialize_scalar(buf, "float32") == 0.5
        assert serialize.deserialize_string(buf) == "metric=L2Expanded"

    def test_header_roundtrip(self):
        buf = io.BytesIO()
        serialize.dump_header(buf, "ivf_flat")
        buf.seek(0)
        assert serialize.check_header(buf, "ivf_flat") == serialize.SERIALIZATION_VERSION

    def test_header_kind_mismatch(self):
        buf = io.BytesIO()
        serialize.dump_header(buf, "ivf_flat")
        buf.seek(0)
        with pytest.raises(ValueError):
            serialize.check_header(buf, "cagra")


class TestBitset:
    def test_create_count(self):
        bs = Bitset.create(100, default=True)
        assert int(bs.count()) == 100
        assert int(Bitset.create(100, default=False).count()) == 0

    def test_roundtrip_mask(self, rng):
        mask = rng.random(77) < 0.5
        bs = Bitset.from_mask(jnp.asarray(mask))
        np.testing.assert_array_equal(np.asarray(bs.to_mask()), mask)
        assert int(bs.count()) == mask.sum()

    def test_set_unset_test(self):
        bs = Bitset.create(64, default=False)
        bs = bs.set(jnp.array([0, 5, 33]))
        got = bs.test(jnp.array([0, 1, 5, 33, 63]))
        np.testing.assert_array_equal(np.asarray(got), [True, False, True, True, False])
        bs = bs.unset(jnp.array([5]))
        assert not bool(bs.test(jnp.array([5]))[0])

    def test_deleted_rows_ctor(self):
        bs = Bitset.from_unset_indices(40, jnp.array([3, 17]))
        assert int(bs.count()) == 38

    def test_flip(self, rng):
        mask = rng.random(50) < 0.3
        bs = Bitset.from_mask(jnp.asarray(mask)).flip()
        np.testing.assert_array_equal(np.asarray(bs.to_mask()), ~mask)

    def test_jit_test(self):
        bs = Bitset.from_mask(jnp.asarray(np.array([True, False, True])))
        f = jax.jit(lambda b, i: b.test(i))
        assert bool(f(bs, jnp.array([2]))[0])

    def test_bitmap(self, rng):
        m = rng.random((5, 9)) < 0.5
        bm = Bitmap.from_mask(jnp.asarray(m))
        np.testing.assert_array_equal(np.asarray(bm.to_mask()), m)
        assert bool(bm.test(jnp.array(1), jnp.array(2))) == m[1, 2]


class TestBitsetUnderJit:
    """Tombstone-mask semantics under jit — the in-scan delete path of
    the mutable layer (`raft_tpu/mutable/segments.py`) relies on these
    holding inside compiled programs, not just eagerly."""

    def test_set_unset_count_jitted(self):
        @jax.jit
        def mutate(bs, on, off):
            return bs.set(on).unset(off)

        bs = Bitset.create(130, default=False)
        bs = mutate(bs, jnp.array([0, 64, 129]), jnp.array([64]))
        assert int(jax.jit(lambda b: b.count())(bs)) == 2
        got = bs.test(jnp.array([0, 64, 129]))
        np.testing.assert_array_equal(np.asarray(got), [True, False, True])

    def test_count_matches_mask_sum_jitted(self, rng):
        mask = rng.random(257) < 0.4
        bs = Bitset.from_mask(jnp.asarray(mask))
        count = jax.jit(lambda b: b.count())(bs)
        assert int(count) == int(mask.sum())

    def test_mask_then_topk_equals_filter_then_topk(self, rng):
        # the delete correctness identity: masking distances to +inf
        # inside the scan (what prefilter does) must select exactly the
        # rows a host-side filter-then-top-k selects
        n, k = 96, 8
        dist = rng.random(n).astype(np.float32)
        dist += np.arange(n, dtype=np.float32) * 1e-4  # break ties
        keep = rng.random(n) < 0.6
        bs = Bitset.from_mask(jnp.asarray(keep))

        @jax.jit
        def mask_then_topk(b, d):
            masked = jnp.where(b.to_mask(), d, jnp.inf)
            return jax.lax.top_k(-masked, k)[1]

        got = np.sort(np.asarray(mask_then_topk(bs, jnp.asarray(dist))))
        want = np.sort(np.argsort(np.where(keep, dist, np.inf))[:k])
        np.testing.assert_array_equal(got, want)

    def test_prefilter_in_scan_matches_host_filter(self, rng):
        # end-to-end over a real index: brute-force search with a
        # tombstone prefilter == search over the physically filtered set
        from raft_tpu.neighbors import brute_force

        data = rng.standard_normal((120, 8)).astype(np.float32)
        q = rng.standard_normal((3, 8)).astype(np.float32)
        keep = rng.random(120) < 0.7
        bs = Bitset.from_mask(jnp.asarray(keep))
        idx = brute_force.build(data)
        d_mask, i_mask = brute_force.search(idx, q, 5, prefilter=bs, mode="exact")
        kept = np.flatnonzero(keep)
        idx_f = brute_force.build(data[kept])
        d_filt, i_filt = brute_force.search(idx_f, q, 5, mode="exact")
        np.testing.assert_array_equal(
            kept[np.asarray(i_filt)], np.asarray(i_mask)
        )
        np.testing.assert_allclose(
            np.asarray(d_mask), np.asarray(d_filt), rtol=1e-5, atol=1e-5
        )


class TestInterruptible:
    def test_yield_no_throw(self):
        assert not interruptible.yield_no_throw()

    def test_cancel_other_thread(self):
        caught = []

        def worker():
            ev.wait()
            try:
                interruptible.synchronize()
            except interruptible.InterruptedException:
                caught.append(True)

        ev = threading.Event()
        t = threading.Thread(target=worker)
        t.start()
        interruptible.cancel(t.ident)
        ev.set()
        t.join()
        assert caught == [True]


class TestErrors:
    def test_expects(self):
        expects(True, "fine")
        with pytest.raises(LogicError):
            expects(False, "bad value %d", 3)


class TestAsArray:
    def test_numpy(self):
        a = as_array(np.ones((2, 3)), dtype=jnp.float32, ndim=2)
        assert a.dtype == jnp.float32

    def test_ndim_check(self):
        with pytest.raises(LogicError):
            as_array(np.ones(3), ndim=2)

    def test_torch_cpu(self):
        torch = pytest.importorskip("torch")
        t = torch.arange(6, dtype=torch.float32).reshape(2, 3)
        a = as_array(t, ndim=2)
        np.testing.assert_allclose(np.asarray(a), t.numpy())


class TestCompileCache:
    """``$JAX_COMPILATION_CACHE_DIR`` wins untouched; otherwise the cache
    goes to one fixed path inside the checkout."""

    @pytest.fixture(autouse=True)
    def _restore(self):
        was = jax.config.jax_compilation_cache_dir
        yield
        jax.config.update("jax_compilation_cache_dir", was)

    def test_env_var_wins(self, monkeypatch, tmp_path):
        from raft_tpu.core.compile_cache import use_compile_cache

        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        jax.config.update("jax_compilation_cache_dir", "/unchanged")
        assert use_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == "/unchanged"

    def test_checkout_path_otherwise(self, monkeypatch):
        import os

        from raft_tpu.core.compile_cache import CHECKOUT_CACHE_DIR, use_compile_cache

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        assert use_compile_cache() == CHECKOUT_CACHE_DIR
        assert jax.config.jax_compilation_cache_dir == CHECKOUT_CACHE_DIR
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert CHECKOUT_CACHE_DIR == os.path.join(root, ".jax_cache")
