"""Compile the fused kernels of the serving path for a described TPU v5e.

No chip is attached here: ``jax.experimental.topologies`` describes one,
and the TPU compiler compiles for it. What it refuses (unaligned slices,
too much VMEM, an API the installed JAX dropped) would cost a chip run
to find. The shapes are ``chip_smoke.py``'s: a 1M x 128 corpus in 1024
lists, k=10, a 128-query serving bucket. The kernels are called with
``interpret=False`` themselves, since ``jax.default_backend()`` reads
``cpu`` here and the library would pick interpret mode.
"""
import os

import pytest

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

from raft_tpu.ops.distance import DistanceType

N_LISTS, MAX_LIST, DIM, NQ, K = 1024, 1152, 128, 128, 10
N_ROWS = 1_000_000
L2 = DistanceType.L2Expanded


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep it out
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(lowered):
    text = lowered.compile().as_text()
    assert "tpu_custom_call" in text


def _tiny_pq(pq_kind):
    """A small index of the smoke's kind, for the shapes that do not
    scale with the corpus (codebooks, rotation, bytes per row)."""
    from raft_tpu.neighbors import ivf_pq

    x = np.random.default_rng(0).standard_normal((2048, DIM)).astype(np.float32)
    return ivf_pq.build(jnp.asarray(x), ivf_pq.IvfPqIndexParams(
        n_lists=8, pq_dim=32, pq_bits=8, pq_kind=pq_kind, kmeans_n_iters=2))


def _list_specs(one_chip, bpr=None, dtype=jnp.float32):
    lists = (N_LISTS, MAX_LIST) if bpr is None else (N_LISTS, MAX_LIST, bpr)
    return (
        _spec((N_LISTS, DIM), jnp.float32, one_chip),   # centers
        _spec(lists, dtype, one_chip),
        _spec((N_LISTS, MAX_LIST), jnp.int32, one_chip),  # list ids
    )


def test_ivf_scan_compiles(one_chip):
    from raft_tpu.ops.pallas.ivf_scan import ivf_flat_fused_search

    centers, data, ids = _list_specs(one_chip, bpr=DIM, dtype=jnp.bfloat16)
    _assert_kernel(ivf_flat_fused_search.lower(
        centers, _spec((N_LISTS,), jnp.int32, one_chip), data, ids,
        _spec((N_LISTS, MAX_LIST), jnp.float32, one_chip),
        _spec((NQ, DIM), jnp.float32, one_chip), None,
        k=K, n_probes=30, metric=L2, qt=128, probe_factor=32, group=8,
        merge="bank8", precision="default", col_chunk=1024, interpret=False))


def test_pq_scan_compiles(one_chip):
    from raft_tpu.neighbors.ivf_pq import _fused_code_layout, nibble_books
    from raft_tpu.ops.pallas.pq_scan import ivf_pq_fused_search

    idx = _tiny_pq("nibble")
    code_mode, ksub = _fused_code_layout(idx)
    books = nibble_books(idx.pq_centers)
    bpr, rot = idx.codes.shape[2], idx.rotation.shape[0]
    centers, codes, ids = _list_specs(one_chip, bpr=bpr, dtype=jnp.uint8)
    _assert_kernel(ivf_pq_fused_search.lower(
        centers, _spec((N_LISTS, rot), jnp.float32, one_chip),
        _spec(idx.rotation.shape, jnp.float32, one_chip),
        _spec(books.shape, jnp.float32, one_chip), codes, ids,
        _spec((N_LISTS, MAX_LIST), jnp.float32, one_chip),
        _spec((NQ, DIM), jnp.float32, one_chip), None,
        k=8 * K, n_probes=30, metric=L2, qt=128, group=8,
        merge="bank8", code_mode=code_mode, ksub=ksub, decode_cols=2048,
        interpret=False))


def _pq_bucket(one_chip, *, d, m, nq, k, n_lists, n_probes):
    """Lower the fused PQ search of a benchmark cell's bucket: inner
    product, nibble codes of 64 bytes a row, groups of 8 lists."""
    from raft_tpu.neighbors import ivf_pq
    from raft_tpu.neighbors.ivf_pq import _fused_code_layout, nibble_books
    from raft_tpu.ops.pallas.pq_scan import ivf_pq_fused_search

    x = np.random.default_rng(0).standard_normal((2048, d)).astype(np.float32)
    idx = ivf_pq.build(jnp.asarray(x), ivf_pq.IvfPqIndexParams(
        n_lists=8, pq_dim=64, pq_bits=8, pq_kind="nibble", kmeans_n_iters=2,
        metric=DistanceType.InnerProduct))
    code_mode, ksub = _fused_code_layout(idx)
    books = nibble_books(idx.pq_centers)
    bpr, rot = idx.codes.shape[2], idx.rotation.shape[0]
    assert (code_mode, bpr) == ("nib8", 64)
    return idx, ivf_pq_fused_search.lower(
        _spec((n_lists, d), jnp.float32, one_chip),
        _spec((n_lists, rot), jnp.float32, one_chip),
        _spec(idx.rotation.shape, jnp.float32, one_chip),
        _spec(books.shape, jnp.float32, one_chip),
        _spec((n_lists, m, bpr), jnp.uint8, one_chip),
        _spec((n_lists, m), jnp.int32, one_chip),
        _spec((n_lists, m), jnp.float32, one_chip),
        _spec((nq, d), jnp.float32, one_chip), None,
        k=k, n_probes=n_probes, metric=DistanceType.InnerProduct, qt=128,
        group=8, merge="bank8", code_mode=code_mode, ksub=ksub,
        decode_cols=2048, interpret=False)


def test_pq_scan_compiles_glove_bucket(one_chip):
    """The glove200-pq benchmark cell's shape: 1,183,514 x 200 by inner
    product in 1,024 lists capped at 1,280 rows, nibble codes of 64 bytes
    a row, k 80 (10 x refine 8), a 1,024-query bucket of 30 probes: its
    30,720 (query, probe) pairs make 240 tiles of 128, and the walk's
    scalar-prefetch tables have 240 + 128 - 1 steps."""
    _, lowered = _pq_bucket(one_chip, d=200, m=1280, nq=1024, k=80, n_lists=1024,
                            n_probes=30)
    assert "tensor<367xi32>" in lowered.as_text()  # the pair walk's prefetch tables
    _assert_kernel(lowered)


def test_pq_scan_compiles_deep_bucket(one_chip):
    """The deep10m-pq benchmark cell's shape: 9,990,000 x 96 by inner
    product in 8,192 lists capped at 1,408 rows, nibble codes of 64 bytes
    a row (pq_len 2, rot_dim 128), k 80, a 1,024-query bucket of 64
    probes: 65,536 pairs in 512 tiles, a walk of at most 512 + 1,024 - 1
    (tile, unit) steps."""
    idx, lowered = _pq_bucket(one_chip, d=96, m=1408, nq=1024, k=80, n_lists=8192,
                              n_probes=64)
    assert (idx.rotation.shape[0], idx.pq_len) == (128, 2)
    assert "tensor<1535xi32>" in lowered.as_text()  # the pair walk's prefetch tables
    _assert_kernel(lowered)


def test_rabitq_scan_compiles(one_chip):
    from raft_tpu.ops.pallas.rabitq_scan import ivf_rabitq_fused_search, vmem_decode_rows

    idx = _tiny_pq("rabitq")
    bpr, rot = idx.codes.shape[2], idx.rotation.shape[0]
    centers, codes, ids = _list_specs(one_chip, bpr=bpr, dtype=jnp.uint8)
    per_row = _spec((N_LISTS, MAX_LIST), jnp.float32, one_chip)
    _assert_kernel(ivf_rabitq_fused_search.lower(
        centers, _spec((N_LISTS, rot), jnp.float32, one_chip),
        _spec((N_LISTS,), jnp.int32, one_chip),
        _spec(idx.rotation.shape, jnp.float32, one_chip), codes, ids, per_row, per_row,
        _spec((NQ, DIM), jnp.float32, one_chip), None,
        k=8 * K, n_probes=30, metric=L2, qt=128, probe_factor=32, group=8,
        merge="bank8",
        decode_rows=vmem_decode_rows(m=MAX_LIST, bpr=bpr, qt=128, k=8 * K, g_lists=8,
                                     rot_dim=rot, merge="bank8"),
        interpret=False))


def test_cagra_search_compiles(one_chip):
    from raft_tpu.neighbors import cagra
    from raft_tpu.ops.pallas.cagra_search import table_rows

    params = cagra.CagraSearchParams(itopk_size=128, search_width=8, dedup="post")
    itopk, width, iters, _ = cagra.derive_search_config(params, K, N_ROWS)
    deg = 32
    _assert_kernel(cagra._cagra_fused_impl.lower(
        _spec((N_ROWS, table_rows(deg), DIM), jnp.bfloat16, one_chip),
        _spec((N_ROWS, DIM), jnp.float32, one_chip),
        _spec((N_ROWS,), jnp.float32, one_chip),
        _spec((NQ, DIM), jnp.float32, one_chip),
        _spec((params.init_sample,), jnp.int32, one_chip),
        k=K, deg=deg, itopk=itopk, width=width, iters=iters, metric=L2, qt=params.fused_qt,
        interpret=False))


@pytest.mark.parametrize("engine", ["ring", "scan_ring"])
def test_fused_ring_compiles(topo, engine):
    from raft_tpu.ops.pallas.ring_topk import fused_ring_topk, fused_scan_ring_topk

    mesh = Mesh(topo.devices, ("data",))
    n = len(topo.devices)
    fn, width = (fused_ring_topk, K) if engine == "ring" else (fused_scan_ring_topk, 8 * K)

    def local(v, i):
        return fn(v[0], i[0], K, True, "data")

    step = jax.jit(jax.shard_map(local, mesh=mesh, in_specs=(P("data"), P("data")),
                                 out_specs=(P(), P()), check_vma=False))
    sharded = NamedSharding(mesh, P("data"))
    _assert_kernel(step.lower(_spec((n, NQ, width), jnp.float32, sharded),
                              _spec((n, NQ, width), jnp.int32, sharded)))


def test_sharded_pq_build_fits(topo):
    """The four-chip smoke's distributed IVF-PQ training at 1M rows a
    shard: its codebook pass once held a [rows, pq_dim, ksub] f32 array
    (65 GB), which the compiler refuses; chunked it fits one chip."""
    from raft_tpu.neighbors import ivf_pq
    from raft_tpu.parallel.sharded_ann import _dist_pq_train_fn

    mesh = Mesh(topo.devices, ("data",))
    n = len(topo.devices)
    rotation = ivf_pq._make_rotation(jax.random.PRNGKey(0), DIM, DIM, False)
    fn = _dist_pq_train_fn(mesh, "data", rotation, n_lists=N_LISTS, pq_dim=64, ksub=256,
                           n_iters=5, mode="ca", fuse_comms=True, ca_cap=None, ca_warmup=2)
    compiled = fn.lower(
        _spec((n * N_ROWS, DIM), jnp.float32, NamedSharding(mesh, P("data"))),
        _spec((N_LISTS, DIM), jnp.float32, NamedSharding(mesh, P()))).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 4 * 2**30
