"""Fused Pallas IVF-PQ scan: correctness vs brute force + the scan path,
nibble/packed code layouts, serialization round-trip.

Reference test analog: ``cpp/test/neighbors/ann_ivf_pq.cuh`` recall-
threshold pattern (compare against exact kNN, assert recall floor).
Runs in interpret mode on CPU.
"""
import io

import numpy as np
import pytest

import jax.numpy as jnp

from raft_tpu.neighbors import brute_force, ivf_pq
from raft_tpu.ops.distance import DistanceType
from raft_tpu.stats import neighborhood_recall


def _data(seed=0, n=2500, d=32, nq=128):
    rng = np.random.default_rng(seed)
    centers = rng.standard_normal((20, d)).astype(np.float32) * 3
    ds = centers[rng.integers(0, 20, n)] + rng.standard_normal((n, d)).astype(np.float32)
    qs = centers[rng.integers(0, 20, nq)] + rng.standard_normal((nq, d)).astype(np.float32)
    return ds, qs


def _gt(ds, qs, k, metric=DistanceType.L2Expanded):
    bf = brute_force.build(ds, metric=metric)
    _, bi = brute_force.search(bf, qs, k)
    return np.asarray(bi)


@pytest.mark.parametrize("pq_bits", [4, pytest.param(5, marks=pytest.mark.slow), pytest.param(6, marks=pytest.mark.slow)])
def test_fused_matches_brute_force_small_ksub(pq_bits):
    ds, qs = _data(seed=1)
    k = 10
    idx = ivf_pq.build(
        ds,
        ivf_pq.IvfPqIndexParams(kmeans_n_iters=5, n_lists=16, pq_dim=16, pq_bits=pq_bits, seed=3),
    )
    assert idx.packed  # pq_dim=16: every width 4/5/6 is byte-aligned
    v, i = ivf_pq.search(
        idx, qs, k,
        ivf_pq.IvfPqSearchParams(n_probes=16, fused_qt=16, fused_probe_factor=16, fused_group=4),
        mode="fused",
    )
    rec = float(neighborhood_recall(np.asarray(i), _gt(ds, qs, k)))
    # ADC with small codebooks on 2-dim subspaces: recall floor from the
    # measured operating point (0.55 / 0.69 / 0.77) minus slack
    assert rec > 0.48 + 0.06 * (pq_bits - 4), rec
    # fused and scan paths share the candidate set: near-identical recall
    v2, i2 = ivf_pq.search(idx, qs, k, ivf_pq.IvfPqSearchParams(n_probes=16), mode="scan")
    rec2 = float(neighborhood_recall(np.asarray(i2), _gt(ds, qs, k)))
    assert abs(rec - rec2) < 0.08, (rec, rec2)


@pytest.mark.parametrize("pq_bits", [3, 5, 6, 7])
def test_bit_packed_roundtrip_and_size(pq_bits):
    """Spanning bit-pack layouts (VERDICT r4 item 6): exact round-trip,
    codes measurably smaller than one byte per code."""
    rng = np.random.default_rng(0)
    codes = rng.integers(0, 1 << pq_bits, (3, 7, 16), dtype=np.uint8)
    packed = ivf_pq.pack_codes_bits(jnp.asarray(codes), pq_bits)
    assert packed.shape[-1] == 16 * pq_bits // 8  # 6 / 10 / 12 / 14 bytes
    out = ivf_pq.unpack_codes_bits(packed, pq_bits, 16)
    np.testing.assert_array_equal(np.asarray(out), codes)


@pytest.mark.parametrize("pq_bits", [pytest.param(3, marks=pytest.mark.slow), pytest.param(5, marks=pytest.mark.slow), 6])
def test_bit_packed_fused_matches_unpacked(pq_bits):
    """The b3/b5/b6 kernel unpack decodes the same one-hots as u8 on the
    unpacked bytes — results must be identical, index pq_bits/8 the
    size."""
    import dataclasses

    ds, qs = _data(seed=7)
    k = 10
    idx = ivf_pq.build(
        ds, ivf_pq.IvfPqIndexParams(kmeans_n_iters=5, n_lists=16, pq_dim=16, pq_bits=pq_bits, seed=3)
    )
    assert idx.packed and idx.codes.shape[-1] == 16 * pq_bits // 8
    unpacked = dataclasses.replace(idx, codes=idx.codes_unpacked(), packed=False)
    sp = ivf_pq.IvfPqSearchParams(n_probes=16, fused_qt=16, fused_probe_factor=16, fused_group=4)
    v, i = ivf_pq.search(idx, qs, k, sp, mode="fused")
    v2, i2 = ivf_pq.search(unpacked, qs, k, sp, mode="fused")
    np.testing.assert_array_equal(np.asarray(i), np.asarray(i2))
    np.testing.assert_allclose(np.asarray(v), np.asarray(v2), rtol=1e-5, atol=1e-5)


def test_bit_packed_serialize_roundtrip():
    ds, qs = _data(seed=8, n=1200, nq=16)
    idx = ivf_pq.build(
        ds, ivf_pq.IvfPqIndexParams(kmeans_n_iters=5, n_lists=8, pq_dim=16, pq_bits=5, seed=3)
    )
    assert idx.packed
    buf = io.BytesIO()
    ivf_pq.save(idx, buf)
    buf.seek(0)
    idx2 = ivf_pq.load(buf)
    assert idx2.packed and idx2.pq_bits == 5 and idx2.pq_dim == 16
    sp = ivf_pq.IvfPqSearchParams(n_probes=8, fused_qt=16, fused_probe_factor=8, fused_group=2)
    v, i = ivf_pq.search(idx, qs, 5, sp, mode="fused")
    v2, i2 = ivf_pq.search(idx2, qs, 5, sp, mode="fused")
    np.testing.assert_array_equal(np.asarray(i), np.asarray(i2))


@pytest.mark.slow
def test_bit_packed_extend_repacks():
    ds, qs = _data(seed=9, n=1500, nq=16)
    idx = ivf_pq.build(
        ds[:1000], ivf_pq.IvfPqIndexParams(kmeans_n_iters=5, n_lists=8, pq_dim=16, pq_bits=6, seed=3)
    )
    assert idx.packed
    idx2 = ivf_pq.extend(idx, ds[1000:])
    assert idx2.packed and idx2.size == 1500
    assert idx2.codes.shape[-1] == 16 * 6 // 8
    v, i = ivf_pq.search(idx2, qs, 5, ivf_pq.IvfPqSearchParams(n_probes=8), mode="scan")
    rec = float(neighborhood_recall(np.asarray(i), _gt(ds, qs, 5)))
    assert rec > 0.5, rec


def test_fused_default_ksub256_matches_scan():
    """The reference's pq_bits=8 kmeans-256 config takes the fused path
    via column-chunked decode (VERDICT r4 item 3). pq_kind is explicit:
    the repo default now auto-resolves to nibble."""
    ds, qs = _data(seed=11)
    k = 10
    idx = ivf_pq.build(
        ds,
        ivf_pq.IvfPqIndexParams(
            kmeans_n_iters=5, n_lists=16, pq_dim=16, pq_bits=8, pq_kind="kmeans", seed=3
        ),
    )
    assert not idx.packed and not idx.additive and idx.ksub == 256
    sp = ivf_pq.IvfPqSearchParams(
        n_probes=16, fused_qt=16, fused_probe_factor=16, fused_group=4,
        fused_decode_cols=512,  # force several chunks (K = 16*256 = 4096)
    )
    v, i = ivf_pq.search(idx, qs, k, sp, mode="fused")
    v2, i2 = ivf_pq.search(idx, qs, k, ivf_pq.IvfPqSearchParams(n_probes=16), mode="scan")
    gt = _gt(ds, qs, k)
    rec = float(neighborhood_recall(np.asarray(i), gt))
    rec2 = float(neighborhood_recall(np.asarray(i2), gt))
    assert abs(rec - rec2) < 0.08, (rec, rec2)
    assert rec > 0.7, rec


@pytest.mark.slow
def test_bit_packed_b7_fused_matches_unpacked():
    """7-bit spanning layout + ksub=128 chunked decode."""
    import dataclasses

    ds, qs = _data(seed=12)
    k = 8
    idx = ivf_pq.build(
        ds, ivf_pq.IvfPqIndexParams(kmeans_n_iters=5, n_lists=16, pq_dim=16, pq_bits=7, seed=3)
    )
    assert idx.packed and idx.codes.shape[-1] == 14 and idx.ksub == 128
    unpacked = dataclasses.replace(idx, codes=idx.codes_unpacked(), packed=False)
    sp = ivf_pq.IvfPqSearchParams(n_probes=16, fused_qt=16, fused_probe_factor=16, fused_group=4)
    v, i = ivf_pq.search(idx, qs, k, sp, mode="fused")
    v2, i2 = ivf_pq.search(unpacked, qs, k, sp, mode="fused")
    np.testing.assert_array_equal(np.asarray(i), np.asarray(i2))


@pytest.mark.slow
def test_fused_nibble_beats_pq4():
    ds, qs = _data(seed=2)
    k = 10
    common = dict(n_lists=16, pq_dim=16, seed=3)
    idx4 = ivf_pq.build(ds, ivf_pq.IvfPqIndexParams(kmeans_n_iters=5, pq_bits=4, **common))
    idx_nib = ivf_pq.build(ds, ivf_pq.IvfPqIndexParams(kmeans_n_iters=5, pq_bits=8, pq_kind="nibble", **common))
    assert idx_nib.additive and not idx_nib.packed
    sp = ivf_pq.IvfPqSearchParams(n_probes=16, fused_qt=16, fused_probe_factor=16, fused_group=4)
    _, i4 = ivf_pq.search(idx4, qs, k, sp, mode="fused")
    _, inib = ivf_pq.search(idx_nib, qs, k, sp, mode="fused")
    gt = _gt(ds, qs, k)
    r4 = float(neighborhood_recall(np.asarray(i4), gt))
    rnib = float(neighborhood_recall(np.asarray(inib), gt))
    # 256 additive centers must beat 16 plain centers per subspace
    assert rnib > r4 + 0.02, (rnib, r4)


def test_fused_inner_product():
    ds, qs = _data(seed=4)
    k = 8
    idx = ivf_pq.build(
        ds,
        ivf_pq.IvfPqIndexParams(kmeans_n_iters=5,
            n_lists=16, pq_dim=16, pq_bits=8, pq_kind="nibble",
            metric=DistanceType.InnerProduct, seed=5,
        ),
    )
    v, i = ivf_pq.search(
        idx, qs, k,
        ivf_pq.IvfPqSearchParams(n_probes=16, fused_qt=16, fused_probe_factor=16, fused_group=4),
        mode="fused",
    )
    rec = float(neighborhood_recall(np.asarray(i), _gt(ds, qs, k, DistanceType.InnerProduct)))
    assert rec > 0.6, rec


def test_fused_prefilter():
    from raft_tpu.core.bitset import Bitset

    ds, qs = _data(seed=6)
    k = 5
    idx = ivf_pq.build(ds, ivf_pq.IvfPqIndexParams(kmeans_n_iters=5, n_lists=8, pq_dim=16, pq_bits=6, seed=7))
    banned = np.arange(0, ds.shape[0], 2)
    bs = Bitset.from_unset_indices(ds.shape[0], jnp.asarray(banned, jnp.int32))
    _, i = ivf_pq.search(
        idx, qs, k,
        ivf_pq.IvfPqSearchParams(n_probes=8, fused_qt=16, fused_probe_factor=8, fused_group=2),
        prefilter=bs,
        mode="fused",
    )
    out = np.asarray(i)
    assert (out[out >= 0] % 2 == 1).all()  # only odd ids survive


def test_packed_codes_round_trip():
    ds, _ = _data(seed=8)
    idx = ivf_pq.build(ds, ivf_pq.IvfPqIndexParams(kmeans_n_iters=5, n_lists=8, pq_dim=16, pq_bits=4, seed=9))
    assert idx.packed
    assert idx.codes.shape[2] == 8  # pq_dim/2 bytes per row
    up = ivf_pq.unpack_codes(idx.codes)
    assert up.shape[2] == 16
    assert (np.asarray(ivf_pq.pack_codes(up)) == np.asarray(idx.codes)).all()
    assert int(np.asarray(up).max()) < 16


def test_packed_index_smaller_than_8bit():
    ds, _ = _data(seed=8)
    idx4 = ivf_pq.build(ds, ivf_pq.IvfPqIndexParams(kmeans_n_iters=5, n_lists=8, pq_dim=16, pq_bits=4, seed=9))
    idx8 = ivf_pq.build(ds, ivf_pq.IvfPqIndexParams(kmeans_n_iters=5, n_lists=8, pq_dim=16, pq_bits=8, seed=9))
    b4 = io.BytesIO()
    b8 = io.BytesIO()
    ivf_pq.save(idx4, b4)
    ivf_pq.save(idx8, b8)
    # code storage halves; codebook shrinks 16x — the serialized file must
    # show the memory win (VERDICT r3 item 5)
    assert len(b4.getvalue()) < 0.7 * len(b8.getvalue())


def test_serialize_v3_round_trip_nibble():
    ds, qs = _data(seed=10)
    k = 5
    idx = ivf_pq.build(
        ds, ivf_pq.IvfPqIndexParams(kmeans_n_iters=5, n_lists=8, pq_dim=16, pq_bits=8, pq_kind="nibble", seed=11)
    )
    buf = io.BytesIO()
    ivf_pq.save(idx, buf)
    buf.seek(0)
    idx2 = ivf_pq.load(buf)
    assert idx2.additive and idx2.center_rank is not None
    sp = ivf_pq.IvfPqSearchParams(n_probes=8, fused_qt=16, fused_probe_factor=8, fused_group=2)
    _, i1 = ivf_pq.search(idx, qs, k, sp, mode="fused")
    _, i2 = ivf_pq.search(idx2, qs, k, sp, mode="fused")
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))


def test_extend_packed():
    ds, qs = _data(seed=12)
    idx = ivf_pq.build(ds[:2000], ivf_pq.IvfPqIndexParams(kmeans_n_iters=5, n_lists=8, pq_dim=16, pq_bits=4, seed=13))
    idx2 = ivf_pq.extend(idx, ds[2000:])
    assert idx2.size == ds.shape[0]
    assert idx2.packed and idx2.codes.shape[2] == 8
    _, i = ivf_pq.search(
        idx2, qs, 5,
        ivf_pq.IvfPqSearchParams(n_probes=8, fused_qt=16, fused_probe_factor=8, fused_group=2),
        mode="fused",
    )
    assert int(np.asarray(i).max()) >= 2000  # extended rows are findable


@pytest.mark.slow
def test_multi_hot_decode_every_width():
    """Fast kernel-math coverage for ALL code layouts (u8, p4, nib8,
    b3/b5/b6/b7): _multi_hot's decode must reproduce the one-hot of the
    unpacked codes — this guards the spanning bit arithmetic without an
    index build, so the heavy end-to-end variants can sit behind -m slow."""
    from raft_tpu.ops.pallas.pq_scan import _code_groups, _multi_hot

    rng = np.random.default_rng(3)
    m, pq_dim = 6, 16
    for bits in (3, 5, 6, 7):
        ksub = 1 << bits
        codes = rng.integers(0, ksub, (m, pq_dim), dtype=np.uint8)
        packed = np.asarray(ivf_pq.pack_codes_bits(jnp.asarray(codes), bits))
        bpr = packed.shape[-1]
        mode = f"b{bits}"
        n_groups, gw = _code_groups(mode, ksub, bpr)
        assert (n_groups, gw) == (pq_dim, ksub)
        s = np.asarray(
            _multi_hot(jnp.asarray(packed), code_mode=mode, ksub=ksub, m=m, bpr=bpr)
        )
        expect = np.zeros((m, pq_dim * ksub), np.float32)
        for r in range(m):
            for j in range(pq_dim):
                expect[r, j * ksub + int(codes[r, j])] = 1.0
        np.testing.assert_array_equal(s.astype(np.float32), expect, err_msg=mode)
        # chunked decode (the ksub-256-style path) agrees column-for-column
        half = pq_dim // 2
        s0 = np.asarray(
            _multi_hot(jnp.asarray(packed), code_mode=mode, ksub=ksub, m=m, bpr=bpr,
                       g0=half, ng=half)
        )
        np.testing.assert_array_equal(s0, s[:, half * ksub:], err_msg=mode + " chunk")
    # u8 / p4 / nib8 byte layouts
    codes = rng.integers(0, 64, (m, pq_dim), dtype=np.uint8)
    s = np.asarray(_multi_hot(jnp.asarray(codes), code_mode="u8", ksub=64, m=m, bpr=pq_dim))
    expect = np.zeros((m, pq_dim * 64), np.float32)
    for r in range(m):
        for j in range(pq_dim):
            expect[r, j * 64 + int(codes[r, j])] = 1.0
    np.testing.assert_array_equal(s.astype(np.float32), expect, err_msg="u8")
    codes4 = rng.integers(0, 16, (m, pq_dim), dtype=np.uint8)
    p4 = np.asarray(ivf_pq.pack_codes(jnp.asarray(codes4)))
    s = np.asarray(_multi_hot(jnp.asarray(p4), code_mode="p4", ksub=16, m=m, bpr=pq_dim // 2))
    expect = np.zeros((m, pq_dim * 16), np.float32)
    for r in range(m):
        for j in range(pq_dim):
            expect[r, j * 16 + int(codes4[r, j])] = 1.0
    np.testing.assert_array_equal(s.astype(np.float32), expect, err_msg="p4")


def test_vmem_decode_cols_cap():
    """The VMEM model keeps the decode chunk under budget for any list
    length, in whole lane-aligned code groups, beside the one-list
    multi-hot scratch and the banks of every sub-tile sharing it."""
    from raft_tpu.ops.pallas import vmem_model
    from raft_tpu.ops.pallas.pq_scan import vmem_decode_cols

    # bench shape (K = 32 x 256 = 8192 columns): the chunk is whole
    # 256-column groups and fits what the fixed residents leave
    dc = vmem_decode_cols(8192, m=1152, code_mode="u8", ksub=256, bpr=32)
    assert dc % 256 == 0 and dc < 8192
    budget = vmem_model.pq_decode_chunk_budget(m=1152, code_mode="u8", ksub=256, bpr=32)
    assert 6 * 1152 * dc <= budget < 6 * 1152 * (dc + 256)
    # more sub-tiles sharing one decode leave less room for the chunk
    assert vmem_decode_cols(8192, m=1152, code_mode="u8", ksub=256, bpr=32,
                            n_sub=4) < dc
    # short lists keep the request
    assert vmem_decode_cols(2048, m=256, code_mode="u8", ksub=256, bpr=32) == 2048
    # 0 = "single pass" still resolves to a bounded chunk
    dc0 = vmem_decode_cols(0, m=1152, code_mode="u8", ksub=256, bpr=32)
    assert dc0 == dc
    # lists too long for the multi-hot scratch are infeasible: flagged up
    # front (ivf_pq.search auto-routes those to the scan path) and
    # refused here
    from raft_tpu.core.errors import RaftError
    from raft_tpu.ops.pallas.pq_scan import decode_feasible

    assert not decode_feasible(m=100_000, code_mode="u8", ksub=256, bpr=32)
    with pytest.raises(RaftError):
        vmem_decode_cols(2048, m=100_000, code_mode="u8", ksub=256, bpr=32)
    # narrow layouts (nib8: 32 cols/group) are usually uncapped
    assert vmem_decode_cols(1024, m=1152, code_mode="nib8", ksub=16, bpr=32) == 1024
    # a request off the 128-lane grid rounds down onto it
    assert vmem_decode_cols(1000, m=1152, code_mode="nib8", ksub=16, bpr=32) == 896
    # spanning bit layouts carry a heavier per-cell footprint (two f32
    # byte-spreads + peel temps), so their cap is tighter than u8's
    assert vmem_decode_cols(4096, m=4000, code_mode="b5", ksub=32, bpr=20) < \
        vmem_decode_cols(4096, m=4000, code_mode="u8", ksub=32, bpr=32)


@pytest.mark.parametrize(
    "shape, measured_mib",
    [
        # the 1M-row bench shape, one 128-query sub-tile: the 18 MiB
        # multi-hot of 256-entry books dominates
        (dict(m=1152, code_mode="u8", ksub=256, bpr=32, qt=128, k=10), 24.28),
        # the glove200-pq cell: eight sub-tiles share each decoded list
        (dict(m=1280, code_mode="nib8", ksub=16, bpr=64, qt=128, k=80,
              rot_dim=256, n_sub=8), 23.44),
    ],
    ids=["bench_u8_256", "glove_nib8_8sub"],
)
def test_vmem_model_reproduces_measured_residency(shape, measured_mib):
    """The residency model must land within 5% of the scoped-VMEM
    allocation the TPU compiler reports for ``fused_pq_topk`` compiled
    for a v5e at these shapes (the size in its refusal at any lower
    limit; the compile passes at 25 and 24 MiB). The decode chunk's
    temporaries do not enter that allocation (the same at 512 and 2048
    columns); the model plans them on top, as a reserve."""
    from raft_tpu.ops.pallas import vmem_model

    res = vmem_model.pq_scan_residency(**shape)
    measured = measured_mib * 2**20
    err = abs(res.fixed_bytes - measured) / measured
    assert err < 0.05, f"{res.fixed_bytes} B vs measured {measured_mib} MiB " \
        f"({err:.1%}):\n{res.table()}"
    planned = vmem_model.pq_scan_residency(decode_cols=2048, **shape)
    assert planned.total_bytes > measured
    # the scratch that persists across the grid dominates
    scratch = sum(r.nbytes for r in res.residents if r.kind == "scratch")
    assert scratch > res.fixed_bytes / 2


def test_vmem_model_matches_kernel_scratch_shapes():
    """The model's scratch entries must mirror the shapes/dtypes the
    kernel actually declares (``kernel_scratch_shapes``) — this is the
    drift guard: changing the kernel's scratch without updating the
    model fails here, not in a Mosaic compile on TPU."""
    from raft_tpu.ops.pallas import vmem_model
    from raft_tpu.ops.pallas.ivf_scan import _eff_banks
    from raft_tpu.ops.pallas.pq_scan import kernel_scratch_shapes

    for m, merge, qt, k, n_sub, mode, ksub, bpr in [
        (1152, "bank8", 128, 10, 1, "u8", 256, 32),
        (256, "bank8", 128, 128, 4, "u8", 256, 32),
        (1152, "bank4", 64, 10, 2, "nib8", 16, 64),
        (100, "bank8", 128, 10, 8, "u8", 16, 20),  # K = 320 pads to 384
    ]:
        banks = _eff_banks(merge, m, 0)
        assert vmem_model.merge_banks(merge, m) == banks, (merge, m)
        res = vmem_model.pq_scan_residency(
            m=m, code_mode=mode, ksub=ksub, bpr=bpr, qt=qt, k=k, merge=merge,
            n_sub=n_sub,
        )
        model_scratch = [r for r in res.residents if r.kind == "scratch"]
        Kp = vmem_model.padded_cols(mode, ksub, bpr)
        assert Kp % 128 == 0 and Kp >= vmem_model.code_groups(mode, ksub, bpr)[0] * \
            vmem_model.code_groups(mode, ksub, bpr)[1]
        decls = kernel_scratch_shapes(n_sub * qt, k, banks, m, Kp)
        assert len(model_scratch) == len(decls)
        for r, decl in zip(model_scratch, decls):
            assert tuple(decl.shape) == r.shape, r.name
            assert jnp.dtype(decl.dtype).itemsize == r.itemsize, r.name


def test_decode_budget_is_derived_not_hardcoded():
    """The hand-calibrated 8 MB ``_DECODE_CHUNK_BUDGET`` constant is
    gone; the budget comes from the residency model (headroom x the
    scoped limit the kernel compiles under, minus fixed residents) and
    therefore moves with shape."""
    from raft_tpu.ops.pallas import pq_scan, vmem_model

    assert not hasattr(pq_scan, "_DECODE_CHUNK_BUDGET")
    shape = dict(m=1152, code_mode="u8", ksub=256, bpr=32, k=10)
    budget = vmem_model.pq_decode_chunk_budget(**shape)
    fixed = vmem_model.pq_scan_residency(**shape).fixed_bytes
    # the kernel's raised scope, not the 16 MiB default
    assert budget == int(0.75 * vmem_model.PQ_SCAN_VMEM_LIMIT_BYTES) - fixed
    assert vmem_model.PQ_SCAN_VMEM_LIMIT_BYTES > vmem_model.VMEM_LIMIT_BYTES
    # under the default scope the one-list multi-hot alone overflows
    assert vmem_model.pq_decode_chunk_budget(
        limit=vmem_model.VMEM_LIMIT_BYTES, **shape) < 0
    # the budget shrinks as fixed residents grow: longer lists (bigger
    # multi-hot, dot accumulator, code DMA buffers) and more sub-tiles
    # sharing a decode (their LUT rows and banks)
    assert vmem_model.pq_decode_chunk_budget(**dict(shape, m=2304)) < budget
    assert vmem_model.pq_decode_chunk_budget(n_sub=2, **shape) < budget
    # and the kernel-side wrapper agrees with the model
    assert pq_scan._decode_chunk_budget(**shape) == budget


@pytest.mark.parametrize("nq, n_sub", [(1024, 8), (128, 1), (300, 3)])
def test_glove_shape_shares_one_decode_per_bucket(nq, n_sub):
    """At the glove200-pq cell's shape (m 1,280, nib8 with 64 bytes a
    row, k 80, group 8) one decode serves the whole bucket under the
    raised limit; a bucket of one sub-tile keeps the one-tile schedule."""
    from raft_tpu.ops.pallas import pq_scan, vmem_model

    shape = dict(m=1280, code_mode="nib8", ksub=16, bpr=64, qt=128, k=80,
                 g_lists=8, rot_dim=256, merge="bank8")
    assert pq_scan.fused_subtiles(nq, **shape) == n_sub
    # ... with the whole 2,048-column multi-hot decoded in one chunk
    assert pq_scan.vmem_decode_cols(2048, n_sub=n_sub, **shape) == 2048
    res = vmem_model.pq_scan_residency(n_sub=n_sub, decode_cols=2048, **shape)
    assert res.total_bytes <= 0.75 * vmem_model.PQ_SCAN_VMEM_LIMIT_BYTES


def _subtile_reference(idx, qs, k, *, n_probes, probe_factor, group, qt, keep=None):
    """Exact top-k of each query over exactly the lists of its own
    ``qt``-query sub-tile's probe table (``build_tile_probe_tables``),
    scored by ADC from the materialized books, in float64. The LUT
    entries are rounded to bfloat16 as the fused kernel's LUT is (for
    nibble books, each half of the additive pair on its own), so ids can
    be compared exactly. Returns ``(ids, dists, dists_f32_books, cand)``
    with ``cand[q]`` the query's candidate row ids."""
    import jax
    from raft_tpu.neighbors.ivf_common import probe_selection
    from raft_tpu.ops.pallas.ivf_scan import build_tile_probe_tables

    nq = qs.shape[0]
    qf = jnp.asarray(qs, jnp.float32)
    coarse, probed = probe_selection(idx.centers, qf, n_probes, idx.metric)
    order_pad, tp, pv = build_tile_probe_tables(
        coarse, probed, idx.center_rank, nq=nq, qt=qt, n_lists=idx.n_lists,
        group=group, n_probes=n_probes, probe_factor=probe_factor)
    order, tp, pv = np.asarray(order_pad)[:nq], np.asarray(tp), np.asarray(pv)

    rot = np.asarray(idx.rotation, np.float64)
    q_rot = np.asarray(qs, np.float64) @ rot.T
    grid = np.asarray(idx.pq_centers, np.float64)  # [pq_dim, 256, pq_len]
    pq_dim, n_codes, pq_len = grid.shape
    q_sub = q_rot.reshape(nq, pq_dim, pq_len)
    bf16 = lambda x: np.asarray(jnp.asarray(x, jnp.float32).astype(jnp.bfloat16), np.float64)
    lut_f32 = np.einsum("npl,pcl->npc", q_sub, grid)  # the books' exact ADC terms
    if idx.additive:  # grid[j, hi*16+lo] = A[hi] + B[lo]
        a = np.einsum("npl,phl->nph", q_sub, grid[:, 0::16, :])
        b = np.einsum("npl,pbl->npb", q_sub, grid[:, 0:16, :] - grid[:, 0:1, :])
        lut = (bf16(a)[:, :, :, None] + bf16(b)[:, :, None, :]).reshape(nq, pq_dim, n_codes)
    else:
        lut = bf16(lut_f32)
    codes = np.asarray(idx.codes_unpacked()).astype(np.int64)  # [n_lists, m, pq_dim]
    ids = np.asarray(idx.list_indices)
    sqn = np.asarray(idx.rot_sqnorms, np.float64)
    crot = np.asarray(idx.centers_rot, np.float64)
    ip = idx.metric == DistanceType.InnerProduct
    out_i = np.full((nq, k), -1, np.int64)
    out_d = np.zeros((nq, k))
    out_f = np.zeros((nq, k))
    cand = []
    row_of = np.empty(nq, np.int64)
    row_of[order] = np.arange(nq)
    for q in range(nq):
        t = row_of[q] // qt
        lists = (tp[t][pv[t] > 0][:, None] * group + np.arange(group)).ravel()
        li = ids[lists].ravel()
        ok = li >= 0
        if keep is not None:
            ok &= keep[np.clip(li, 0, None)]
        cod = codes[lists].reshape(-1, pq_dim)[ok]
        cq = np.repeat(q_rot[q] @ crot[lists].T, codes.shape[1])[ok]
        cand.append(set(li[ok].tolist()))
        j = np.arange(pq_dim)
        adc = lut[q, j, cod].sum(1) + cq
        adc_f = lut_f32[q, j, cod].sum(1) + cq
        if ip:
            d, d_f = adc, adc_f
            top = np.argsort(-d, kind="stable")[:k]
        else:
            base = q_rot[q] @ q_rot[q] + sqn[lists].ravel()[ok]
            d, d_f = np.maximum(base - 2 * adc, 0.0), base - 2 * adc_f
            top = np.argsort(d, kind="stable")[:k]
        out_i[q, : len(top)] = li[ok][top]
        out_d[q, : len(top)] = d[top]
        out_f[q, : len(top)] = d_f[top]
    return out_i, out_d, out_f, cand


@pytest.fixture(scope="module")
def subtile_indexes():
    """One index per (code layout, metric), built once for the parity
    cases: 32 lists, so a sub-tile's 8-unit table leaves lists out."""
    ds, qs = _data(seed=31, n=3000, nq=300)
    out = {}
    for kind in ("nibble", "kmeans"):
        for metric in (DistanceType.L2Expanded, DistanceType.InnerProduct):
            out[kind, metric] = ivf_pq.build(ds, ivf_pq.IvfPqIndexParams(
                kmeans_n_iters=5, n_lists=32, pq_dim=16, pq_bits=8, pq_kind=kind,
                metric=metric, seed=5))
    return ds, qs, out


@pytest.mark.parametrize("filtered", [False, True], ids=["all", "prefilter"])
@pytest.mark.parametrize("metric", [DistanceType.L2Expanded, DistanceType.InnerProduct],
                         ids=["l2", "ip"])
@pytest.mark.parametrize("kind, code_mode", [("nibble", "nib8"), ("kmeans", "u8")])
def test_fused_matches_subtile_reference(subtile_indexes, kind, code_mode, metric, filtered):
    """300 queries span three 128-query sub-tiles (the last partial), all
    sharing each decoded list. Every query must be scored against exactly
    the lists of its own sub-tile's table: the fused ids equal the
    per-sub-tile reference's, never a list of a neighbouring sub-tile's
    table. ``extract_every=1`` with one-list units keeps every candidate
    of a step in its own bank slot, so the kernel's top-k is exact; with
    the default schedule (bank merges over the whole walk) the ids must
    still come from the query's own candidate set."""
    from raft_tpu import obs
    from raft_tpu.core.bitset import Bitset

    ds, qs, indexes = subtile_indexes
    idx = indexes[kind, metric]
    from raft_tpu.neighbors.ivf_pq import _fused_code_layout

    assert _fused_code_layout(idx)[0] == code_mode
    k, n_probes, pf = 10, 4, 2
    keep = prefilter = None
    if filtered:
        keep = np.ones(ds.shape[0], bool)
        keep[::3] = False
        prefilter = Bitset.from_unset_indices(ds.shape[0], jnp.asarray(np.flatnonzero(~keep), jnp.int32))
    sp = ivf_pq.IvfPqSearchParams(n_probes=n_probes, fused_qt=128, fused_probe_factor=pf,
                                  fused_group=1, fused_extract_every=1)
    reg = obs.registry()
    reg.reset()
    obs.enable()
    try:
        d, i = ivf_pq.search(idx, qs, k, sp, prefilter=prefilter, mode="fused")
        hist = obs.registry().as_dict()["histograms"]["ivf_pq.search.fused_subtiles"]
    finally:
        obs.disable()
        reg.reset()
    assert hist["count"] == 1 and hist["sum"] == 3.0  # 300 queries: 3 sub-tiles, one decode
    ref_i, ref_d, ref_f, cand = _subtile_reference(
        idx, qs, k, n_probes=n_probes, probe_factor=pf, group=1, qt=128, keep=keep)
    i, d = np.asarray(i), np.asarray(d)
    np.testing.assert_array_equal(i, ref_i)
    # a LUT entry on a bfloat16 rounding boundary may round the other way
    # from float64 than from the kernel's float32: one ulp of one term
    np.testing.assert_allclose(d, ref_d, rtol=1e-3, atol=1e-3)
    # the bf16 LUT stays close to the materialized books' exact ADC
    scale = np.median(np.abs(ref_f))
    np.testing.assert_allclose(d, ref_f, rtol=0.02, atol=0.02 * scale)
    if keep is not None:
        assert keep[i[i >= 0]].all()
    # the default schedule: one bank walk over the whole union, 4-list
    # units; still no query sees a list outside its own sub-tile's table
    sp0 = ivf_pq.IvfPqSearchParams(n_probes=n_probes, fused_qt=128, fused_probe_factor=pf,
                                   fused_group=4)
    _, i0 = ivf_pq.search(idx, qs, k, sp0, prefilter=prefilter, mode="fused")
    _, _, _, cand4 = _subtile_reference(
        idx, qs, k, n_probes=n_probes, probe_factor=pf, group=4, qt=128, keep=keep)
    for q, row in enumerate(np.asarray(i0)):
        assert set(row[row >= 0].tolist()) <= cand4[q], q


@pytest.mark.parametrize("extract_every", [0, 2])
def test_subtile_sharing_keeps_ids(subtile_indexes, extract_every, monkeypatch):
    """How many sub-tiles share a decode changes the schedule, never the
    result: one sub-tile a grid row (the one-tile schedule) and all three
    of a 300-query call give identical ids and distances, banks and
    their merge order included."""
    from raft_tpu.neighbors.ivf_pq import _fused_code_layout, nibble_books
    from raft_tpu.ops.pallas import pq_scan

    ds, qs, indexes = subtile_indexes
    idx = indexes["nibble", DistanceType.L2Expanded]
    code_mode, ksub = _fused_code_layout(idx)
    args = (idx.centers, idx.centers_rot, idx.center_rank, idx.rotation,
            nibble_books(idx.pq_centers), idx.codes, idx.list_indices,
            idx.rot_sqnorms, jnp.asarray(qs), None)
    kw = dict(k=10, n_probes=4, metric=idx.metric, qt=128, probe_factor=2, group=4,
              code_mode=code_mode, ksub=ksub, extract_every=extract_every,
              decode_cols=256, interpret=True)
    out = {}
    for n_sub in (1, 3):
        monkeypatch.setattr(pq_scan, "fused_subtiles", lambda nq, n=n_sub, **_: n)
        d, i = pq_scan.ivf_pq_fused_search.__wrapped__(*args, **kw)
        out[n_sub] = np.asarray(d), np.asarray(i)
    np.testing.assert_array_equal(out[1][1], out[3][1])
    np.testing.assert_array_equal(out[1][0], out[3][0])


def test_explicit_fused_f32_lut_warns():
    """An explicit mode="fused" + lut_dtype=float32 is a precision request
    the bf16 kernel cannot honor — it must warn, not silently ignore it.
    mode="auto" honors the request by routing to the scan path, silently."""
    import warnings

    ds, qs = _data(seed=21)
    idx = ivf_pq.build(
        ds, ivf_pq.IvfPqIndexParams(kmeans_n_iters=5, n_lists=16, pq_dim=16, seed=3)
    )
    sp = ivf_pq.IvfPqSearchParams(
        n_probes=16, fused_qt=16, fused_probe_factor=16, fused_group=4,
        lut_dtype=jnp.float32,
    )
    with pytest.warns(UserWarning, match="bf16 by construction"):
        ivf_pq.search(idx, qs, 10, sp, mode="fused")
    with warnings.catch_warnings():
        warnings.simplefilter("error")  # any warning -> test failure
        ivf_pq.search(idx, qs, 10, sp, mode="auto")
