"""Comms verb-set self-tests on the 8-device virtual CPU mesh.

Port of the reference's header-only comms correctness checks
(``comms/comms_test.hpp:117-155`` — test_collective_allreduce et al.,
invoked there from pytest through LocalCUDACluster; here through
``shard_map`` on ``xla_force_host_platform_device_count=8``).
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from jax import shard_map

from raft_tpu.parallel import comms
from raft_tpu.parallel.sharded_knn import sharded_knn
from raft_tpu.ops import DistanceType
from raft_tpu.neighbors import brute_force


@pytest.fixture(scope="module")
def mesh():
    devs = jax.devices()
    if len(devs) < 8:
        pytest.skip("needs 8 virtual devices")
    return comms.make_mesh(devs[:8])


def run_spmd(mesh, fn, *args, in_specs=None, out_specs=P()):
    n = mesh.shape["data"]
    if in_specs is None:
        in_specs = (P("data"),) * len(args)
    g = shard_map(fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False)
    return jax.jit(g)(*args)


def test_allreduce_sum(mesh):
    # Each rank contributes 1; allreduce must equal world size
    # (comms_test.hpp:117 test_collective_allreduce).
    x = jnp.ones((8,), jnp.float32)

    def body(xs):
        return comms.allreduce(xs.sum(), op="sum")[None]

    out = run_spmd(mesh, body, x, out_specs=P("data"))
    np.testing.assert_array_equal(np.asarray(out), np.full(8, 8.0, np.float32))


@pytest.mark.parametrize("op,expected", [("max", 7.0), ("min", 0.0)])
def test_allreduce_minmax(mesh, op, expected):
    x = jnp.arange(8, dtype=jnp.float32)

    def body(xs):
        return comms.allreduce(xs[0], op=op)[None]

    out = run_spmd(mesh, body, x, out_specs=P("data"))
    np.testing.assert_array_equal(np.asarray(out), np.full(8, expected, np.float32))


def test_allgather(mesh):
    # comms_test.hpp test_collective_allgather: rank r contributes r.
    x = jnp.arange(8, dtype=jnp.float32)

    def body(xs):
        return comms.allgather(xs)  # [8, 1]

    out = run_spmd(mesh, body, x, out_specs=P(None, "data"))
    got = np.asarray(out).reshape(8, 8)
    for col in range(8):
        np.testing.assert_array_equal(got[:, col], np.arange(8, dtype=np.float32))


def test_allgather_fault_seam_aborts_trace(mesh):
    # chaos drill for the comms.all_gather seam: the fault fires at
    # trace time (verbs run while shard_map traces), so an injected
    # failure aborts program construction before any collective is
    # issued — the SPMD analog of a lost participant.
    from raft_tpu.core.errors import KernelFailure
    from raft_tpu.robust import faults

    assert "comms.all_gather" in faults.FAULT_POINTS
    x = jnp.arange(8, dtype=jnp.float32)

    def body(xs):
        return comms.allgather(xs)

    with faults.injected("comms.all_gather", KernelFailure("chaos")):
        with pytest.raises(KernelFailure):
            run_spmd(mesh, body, x, out_specs=P(None, "data"))


def test_reducescatter(mesh):
    # comms_test.hpp test_collective_reducescatter: every rank sends ones;
    # each receives sum over ranks of its chunk.
    x = jnp.ones((8 * 8,), jnp.float32)

    def body(xs):
        # xs is [8] per shard; reducescatter over ranks -> [1] per shard
        return comms.reducescatter(xs, op="sum")

    out = run_spmd(mesh, body, x, out_specs=P("data"))
    np.testing.assert_array_equal(np.asarray(out), np.full(8, 8.0, np.float32))


def test_bcast(mesh):
    # comms_test.hpp test_collective_broadcast: root value reaches all.
    x = (jnp.arange(8, dtype=jnp.float32) + 1) * 10

    def body(xs):
        return comms.bcast(xs, root=3)

    out = run_spmd(mesh, body, x, out_specs=P("data"))
    np.testing.assert_array_equal(np.asarray(out), np.full(8, 40.0, np.float32))


def test_reduce_to_root(mesh):
    x = jnp.ones((8,), jnp.float32)

    def body(xs):
        return comms.reduce(xs, root=2, op="sum")

    out = np.asarray(run_spmd(mesh, body, x, out_specs=P("data")))
    expected = np.zeros(8, np.float32)
    expected[2] = 8.0
    np.testing.assert_array_equal(out, expected)


def test_ppermute_ring(mesh):
    # device_sendrecv analog (comms_test.hpp test_pointToPoint_device_sendrecv):
    # ring shift by one.
    x = jnp.arange(8, dtype=jnp.float32)
    perm = [(i, (i + 1) % 8) for i in range(8)]

    def body(xs):
        return comms.ppermute(xs, perm)

    out = np.asarray(run_spmd(mesh, body, x, out_specs=P("data")))
    np.testing.assert_array_equal(out, np.roll(np.arange(8, dtype=np.float32), 1))


def test_rank_and_size(mesh):
    x = jnp.zeros((8,), jnp.float32)

    def body(xs):
        r = comms.comm_rank()
        s = comms.comm_size()
        return (r * 100 + s)[None].astype(jnp.float32)

    out = np.asarray(run_spmd(mesh, body, x, out_specs=P("data")))
    np.testing.assert_array_equal(out, np.arange(8) * 100.0 + 8)


def test_barrier(mesh):
    x = jnp.zeros((8,), jnp.float32)

    def body(xs):
        tok = comms.barrier()
        return (xs[0] + tok.astype(jnp.float32))[None]

    out = np.asarray(run_spmd(mesh, body, x, out_specs=P("data")))
    np.testing.assert_array_equal(out, np.full(8, 8.0, np.float32))


def test_comm_split(mesh):
    sub = comms.comm_split(mesh, "data")
    assert sub == {"axis": "data", "size": 8}


def test_mesh_2d_subcomms():
    devs = jax.devices()
    if len(devs) < 8:
        pytest.skip("needs 8 devices")
    mesh2 = comms.make_mesh(devs[:8], shape=(2, 4), axis_names=("rows", "cols"))

    def body(xs):
        row_sum = comms.allreduce(xs.sum(), axis="rows")
        col_sum = comms.allreduce(xs.sum(), axis="cols")
        return jnp.stack([row_sum, col_sum])[None]

    g = shard_map(body, mesh=mesh2, in_specs=(P("rows", "cols"),), out_specs=P("rows", "cols"), check_vma=False)
    x = jnp.ones((2, 4), jnp.float32)
    out = np.asarray(jax.jit(g)(x))
    # each shard holds 1 element: row-axis sum = 2, col-axis sum = 4
    np.testing.assert_array_equal(out.reshape(-1, 2), np.tile([2.0, 4.0], (8, 1)))


def test_init_comms_installs_mesh():
    from raft_tpu.core.resources import Resources

    res = Resources()
    m = comms.init_comms(res)
    assert res.get_mesh() is m


# -- sharded search ---------------------------------------------------------


def test_sharded_knn_matches_unsharded(mesh, rng):
    n, d, nq, k = 1024, 24, 32, 8
    dataset = rng.standard_normal((n, d)).astype(np.float32)
    queries = rng.standard_normal((nq, d)).astype(np.float32)

    sv, si = sharded_knn(mesh, dataset, queries, k, metric=DistanceType.L2Expanded)
    index = brute_force.build(dataset, metric=DistanceType.L2Expanded)
    uv, ui = brute_force.search(index, queries, k)

    np.testing.assert_array_equal(np.asarray(si), np.asarray(ui))
    np.testing.assert_allclose(np.asarray(sv), np.asarray(uv), rtol=1e-5, atol=1e-5)


def test_sharded_knn_inner_product(mesh, rng):
    n, d, nq, k = 512, 16, 16, 5
    dataset = rng.standard_normal((n, d)).astype(np.float32)
    queries = rng.standard_normal((nq, d)).astype(np.float32)
    sv, si = sharded_knn(mesh, dataset, queries, k, metric=DistanceType.InnerProduct)
    sims = queries @ dataset.T
    ref_idx = np.argsort(-sims, axis=1)[:, :k]
    np.testing.assert_array_equal(np.asarray(si), ref_idx)


def test_allreduce_prod_shape_and_value(mesh):
    # prod must return the same shape as sum/max/min (regression: extra
    # leading axis from all_gather(x[None])).
    x = jnp.arange(1, 9, dtype=jnp.float32)

    def body(xs):
        return comms.allreduce(xs[0], op="prod")[None]

    out = np.asarray(run_spmd(mesh, body, x, out_specs=P("data")))
    np.testing.assert_array_equal(out, np.full(8, float(np.prod(np.arange(1, 9)))))


class TestBootstrap:
    """Multi-host bootstrap (raft_dask Comms analog) — single-host
    degenerate path (``raft_dask/common/comms.py:172`` init semantics)."""

    def test_init_single_host_noop(self):
        from raft_tpu.parallel import bootstrap

        assert bootstrap.init_distributed() is False  # nothing to bootstrap

    def test_global_and_local_mesh(self, mesh):
        from raft_tpu.parallel import bootstrap

        g = bootstrap.global_mesh()
        assert g.devices.size == len(jax.devices())
        l = bootstrap.local_mesh()
        assert l.devices.size == len(jax.local_devices())

    def test_comms_self_test(self, mesh):
        from raft_tpu.parallel import bootstrap

        assert bootstrap.run_comms_self_test(mesh) is True


# ---------------------------------------------------------------------------
# gather / gatherv / scatter / p2p pair (comms_test.hpp:156-230 analogs)
# ---------------------------------------------------------------------------


def test_gather_to_root(mesh):
    # test_collective_gather: rank r contributes r; root receives [0..7],
    # everyone else zeros.
    x = jnp.arange(8, dtype=jnp.float32)

    def body(xs):
        return comms.gather(xs, root=2)  # [8, 1] per rank

    out = np.asarray(run_spmd(mesh, body, x, out_specs=P(None, "data"))).reshape(8, 8)
    np.testing.assert_array_equal(out[:, 2], np.arange(8, dtype=np.float32))
    for col in [c for c in range(8) if c != 2]:
        np.testing.assert_array_equal(out[:, col], np.zeros(8, np.float32))


def test_gatherv_variable_sizes(mesh):
    # test_collective_gatherv: rank r contributes r+1 valid rows (value r)
    # inside a capacity-4 padded block; root reconstructs the ragged
    # concatenation from (blocks, sizes).
    cap = 4
    x = jnp.repeat(jnp.arange(8, dtype=jnp.float32)[:, None], cap, axis=1).reshape(-1)

    def body(xs):
        r = comms.comm_rank()
        valid = jnp.minimum(r + 1, cap)
        blocks, sizes = comms.gatherv(xs, valid, root=0)
        return blocks.reshape(1, -1), sizes.reshape(1, -1)

    blocks, sizes = run_spmd(
        mesh, body, x,
        in_specs=(P("data"),), out_specs=(P("data", None), P("data", None)),
    )
    blocks = np.asarray(blocks)  # [8 ranks, 8*cap]
    sizes = np.asarray(sizes)  # [8 ranks, 8]
    np.testing.assert_array_equal(sizes[0], np.minimum(np.arange(8) + 1, cap))
    root_blocks = blocks[0].reshape(8, cap)
    for r in range(8):
        n_valid = min(r + 1, cap)
        np.testing.assert_array_equal(root_blocks[r, :n_valid], np.full(n_valid, float(r)))
    assert (blocks[1:] == 0).all() and (sizes[1:] == 0).all()


def test_scatter_from_root(mesh):
    # root holds [10, 20, ..., 80]; rank r receives 10*(r+1)
    x = jnp.tile((jnp.arange(8, dtype=jnp.float32) + 1) * 10, 8)

    def body(xs):
        # xs is this rank's [8] copy of the root buffer
        return comms.scatter(xs, root=0)[None]

    out = np.asarray(run_spmd(mesh, body, x, out_specs=P("data")))
    np.testing.assert_array_equal(out, (np.arange(8) + 1) * 10.0)


def test_send_recv_single_pair(mesh):
    # test_pointToPoint_simple_send_recv: rank 1 sends its value to rank 5;
    # only rank 5 receives it.
    x = (jnp.arange(8, dtype=jnp.float32) + 1) * 100

    def body(xs):
        return comms.send_recv(xs, src=1, dst=5)

    out = np.asarray(run_spmd(mesh, body, x, out_specs=P("data")))
    expected = np.zeros(8, np.float32)
    expected[5] = 200.0
    np.testing.assert_array_equal(out, expected)


def test_device_sendrecv_exchange(mesh):
    # test_pointToPoint_device_sendrecv: pairs (0,1) (2,3) ... swap values.
    x = jnp.arange(8, dtype=jnp.float32)

    def body(xs):
        return comms.device_sendrecv(xs, [(0, 1), (2, 3), (4, 5), (6, 7)])

    out = np.asarray(run_spmd(mesh, body, x, out_specs=P("data")))
    np.testing.assert_array_equal(out, np.array([1, 0, 3, 2, 5, 4, 7, 6], np.float32))


def test_multicast_sendrecv(mesh):
    # test_pointToPoint_device_multicast_sendrecv: rank 0 multicasts to
    # 1, 2, 3 via three permute edges.
    x = (jnp.arange(8, dtype=jnp.float32) + 1) * 7

    def body(xs):
        return comms.multicast_sendrecv(xs, [(0, 1), (0, 2), (0, 3)])

    out = np.asarray(run_spmd(mesh, body, x, out_specs=P("data")))
    expected = np.zeros(8, np.float32)
    expected[1:4] = 7.0
    np.testing.assert_array_equal(out, expected)
