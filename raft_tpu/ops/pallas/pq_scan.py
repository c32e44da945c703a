"""Pallas fused probed-list scan for IVF-PQ search.

Reference analog: the shared-memory LUT similarity kernel
(``neighbors/detail/ivf_pq_compute_similarity-inl.cuh:252-457``) with its
fp8/half LUTs (``detail/ivf_pq_fp_8bit.cuh``) — one CUDA kernel per
(query, probe) that builds a per-subspace lookup table in shared memory
and accumulates ``sum_j LUT[j, code_j]`` over the probed list's codes.

TPU design
----------
TPUs have no fast per-lane gather, so the LUT lookup becomes a **multi-hot
matmul**: per query tile, the LUT ``W[q, (j, c)] = <q_sub[j], books[j, c]>``
is computed ONCE outside the kernel ([nq, K] bf16, K = pq_dim * ksub) and
the kernel scores a code block by expanding its codes to a multi-hot
``S [rows, K]`` (pq_dim ones per row, built with VPU compares) and taking
``W @ S^T`` on the MXU. With ksub <= 64 the decode FLOPs stay a small
multiple of the raw-vector scan's — and the DMA drops to the CODE bytes
(16-64 B/row instead of 256-512 B/row), which is the entire point of PQ:
on bandwidth-bound hardware the compressed index scans faster than raw
vectors and an order of magnitude beyond what fits in HBM raw.

Probe scheduling, tile-coherent query ordering, scalar-prefetch DMA of
only the probed code blocks, and the bank-merge running top-k are shared
with the IVF-Flat fused scan (:mod:`raft_tpu.ops.pallas.ivf_scan`).

Code layouts (``code_mode``):

* ``"u8"``  — one byte per sub-quantizer code, ``ksub = 2^pq_bits <= 64``.
* ``"nib8"`` — additive nibble pairs: byte j holds ``(hi, lo)`` indexing
  two 16-entry codebooks ``A[j], B[j]`` whose SUM quantizes subspace j
  (256 effective centers from 32 columns of W — 8-bit quality at 4-bit
  decode cost). The TPU-native substitute for the reference's fp8 LUTs.
* ``"p4"``  — packed 4-bit codes: byte b holds codes ``2b`` (low nibble)
  and ``2b+1`` (high nibble), ``ksub = 16``
  (``ivf_pq_types.hpp:129-164`` / ``detail/ivf_pq_codepacking.cuh``
  analog; here simple pairwise packing, not 16-byte interleave — TPU DMA
  wants plain contiguous bytes).

Supported metrics: L2Expanded / L2SqrtExpanded / InnerProduct (the
reference's PQ metric set).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from raft_tpu.core.errors import expects
from raft_tpu.ops.distance import DistanceType
from raft_tpu.ops.pallas import vmem_model
from raft_tpu.ops.pallas.ivf_scan import (
    _eff_banks,
    _extract_topk,
    _seg_compress,
    build_tile_probe_tables,
)

_SUPPORTED = frozenset(
    {
        DistanceType.L2Expanded,
        DistanceType.L2SqrtExpanded,
        DistanceType.InnerProduct,
    }
)


def supported_metric(metric: DistanceType) -> bool:
    return metric in _SUPPORTED


# (n_groups, gw) of the multi-hot column space — shared with the VMEM
# residency model so the decode-chunk budget and the kernel agree on the
# column layout by construction.
_code_groups = vmem_model.code_groups


def _multi_hot(cod, *, code_mode: str, ksub: int, m: int, bpr: int,
               g0: int = 0, ng: int = 0):
    """Expand a [m, bpr] uint8 code block to the multi-hot ``S [m, Kc]``
    bf16 the decode matmul consumes — the column chunk covering groups
    ``[g0, g0 + ng)`` of the full K-column space (``ng=0`` = all groups;
    chunking keeps S inside VMEM for 256-entry codebooks, where the full
    K = pq_dim * 256 would be tens of MB). Column order must match the W
    layout built in :func:`pq_lut`.

    Built entirely in 2D (Mosaic rejects collapsing a 3D one-hot's minor
    dims): a tiny "spread" matmul broadcasts byte j across its K-column
    group (code values <= 255 are exact in bf16/f32), nibbles are peeled
    arithmetically, and one lane-iota compare yields the one-hots.

    ``"b3"``/``"b5"``/``"b6"``/``"b7"`` (spanning little-endian bitstreams) use
    TWO spread matmuls — code j's low byte ``(j*b)//8`` and high byte one
    past it — then peel the value with power-of-two floor arithmetic
    (shifts <= 7 of bytes <= 255: every intermediate is an exact f32
    integer)."""
    n_groups, gw = _code_groups(code_mode, ksub, bpr)
    if not ng:
        ng = n_groups
    Kc = ng * gw
    # u8 -> f32 via i32 (Mosaic has no direct u8 -> float cast)
    codf = cod.astype(jnp.int32).astype(jnp.float32)  # [m, bpr]
    ej = lax.broadcasted_iota(jnp.int32, (bpr, Kc), 0)
    ec = lax.broadcasted_iota(jnp.int32, (bpr, Kc), 1)
    lane = lax.broadcasted_iota(jnp.int32, (m, Kc), 1)
    if code_mode in ("b3", "b5", "b6", "b7"):
        b = int(code_mode[1:])
        jb = (g0 + ec // ksub) * b  # code j's first global bit, per column
        s_lo = (ej == jb // 8).astype(jnp.float32)
        s_hi = (ej == jb // 8 + 1).astype(jnp.float32)  # all-zero col when
        #   the code ends inside its low byte OR at the row's last byte
        bl = lax.dot_general(
            codf, s_lo, dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [m, Kc]
        bh = lax.dot_general(
            codf, s_hi, dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        off = ((g0 + lane // ksub) * b) % 8
        lo_bits = jnp.minimum(8 - off, b)
        p_off = jnp.exp2(-off.astype(jnp.float32))
        p_lob = jnp.exp2(lo_bits.astype(jnp.float32))
        p_hib = jnp.exp2((b - lo_bits).astype(jnp.float32))
        t = jnp.floor(bl * p_off)  # low byte >> off
        v_lo = t - jnp.floor(t / p_lob) * p_lob  # ... & (2^lo_bits - 1)
        v_hi = (bh - jnp.floor(bh / p_hib) * p_hib) * p_lob
        sub = (lane % ksub).astype(jnp.float32)
        return (v_lo + v_hi == sub).astype(jnp.bfloat16)
    spread = (g0 + ec // gw == ej).astype(jnp.float32)  # [bpr, Kc] block-const
    byte_lane = lax.dot_general(
        codf, spread, dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # [m, Kc] — byte g0+j's value on each of its gw lanes
    if code_mode == "u8":
        sub = (lane % gw).astype(jnp.float32)
        return (byte_lane == sub).astype(jnp.bfloat16)
    sub16 = (lane % 16).astype(jnp.float32)
    hi = jnp.floor(byte_lane * 0.0625)  # byte >> 4, exact in f32
    lo = byte_lane - 16.0 * hi
    if code_mode == "nib8":
        # per byte: [A-one-hot (hi) | B-one-hot (lo)]
        val = jnp.where(lane % 32 < 16, hi, lo)
    else:  # p4: byte b = (code 2b in low nibble, code 2b+1 in high)
        val = jnp.where(lane % 32 < 16, lo, hi)
    return (val == sub16).astype(jnp.bfloat16)


# per-cell decode footprint — shared with the VMEM residency model
_decode_cell_bytes = vmem_model.decode_cell_bytes


def _decode_chunk_budget(*, m: int, code_mode: str, ksub: int, bpr: int,
                         **model_kwargs) -> int:
    """Bytes of scoped VMEM one decode chunk may use at this shape:
    ``VMEM_HEADROOM x VMEM_LIMIT`` minus the kernel's fixed residents
    (W tile, q_rot, bank/acc scratch, double-buffered code+epilogue
    DMA, dot accumulator) as accounted by
    :func:`raft_tpu.ops.pallas.vmem_model.pq_decode_chunk_budget`.
    Replaces the historical hand-calibrated 8 MB constant, which this
    derivation reproduces within 2% at its calibration shape
    (m=1152, ksub=256) while adapting to every other shape."""
    return vmem_model.pq_decode_chunk_budget(
        m=m, code_mode=code_mode, ksub=ksub, bpr=bpr, **model_kwargs
    )


def decode_feasible(*, m: int, code_mode: str, ksub: int, bpr: int,
                    **model_kwargs) -> bool:
    """Whether even a single-group decode chunk fits the derived VMEM
    budget — false for very long lists with wide codebooks (e.g.
    ksub=256 with max_list > ~3400), where the fused kernel cannot
    compile and callers must use the scan path instead."""
    _, gw = _code_groups(code_mode, ksub, bpr)
    budget = _decode_chunk_budget(
        m=m, code_mode=code_mode, ksub=ksub, bpr=bpr, **model_kwargs
    )
    return _decode_cell_bytes(code_mode) * m * gw <= budget


def vmem_decode_cols(requested: int, *, m: int, code_mode: str, ksub: int,
                     bpr: int, **model_kwargs) -> int:
    """Cap the decode column chunk so the kernel's scoped-VMEM stack fits
    the TPU's ~16 MB limit.

    A chunk materializes the multi-hot ``S [m, Kc]`` bf16 plus f32
    byte-spread intermediates (see :func:`_decode_cell_bytes`). Measured
    at the 1M-row bench shape (m=1152, ksub=256, Kc=2048) the kernel
    needs 17.19 MiB and the Mosaic compile dies at 16 MiB; capping the
    chunk to the per-shape budget :func:`_decode_chunk_budget` derives
    from the kernel's fixed residents keeps the whole stack inside the
    limit with margin. Chunks cover whole code groups, so the cap rounds
    down to a multiple of the group width. Raises when even one group
    cannot fit (use :func:`decode_feasible` to route such shapes to the
    scan path up front). ``model_kwargs`` (``qt``/``k``/``g_lists``/
    ``rot_dim``/``merge``) refine the resident accounting; omitted ones
    fall back to conservative defaults."""
    n_groups, gw = _code_groups(code_mode, ksub, bpr)
    K = n_groups * gw
    if not requested:
        requested = K
    expects(
        decode_feasible(m=m, code_mode=code_mode, ksub=ksub, bpr=bpr,
                        **model_kwargs),
        "fused PQ decode infeasible: one %d-column group over %d rows "
        "exceeds the VMEM chunk budget — use mode='scan' or more lists",
        gw, m,
    )
    budget = _decode_chunk_budget(
        m=m, code_mode=code_mode, ksub=ksub, bpr=bpr, **model_kwargs
    )
    cap = int(budget // (_decode_cell_bytes(code_mode) * max(m, 1)))
    cap = max(gw, (cap // gw) * gw)
    return min(requested, cap, K)


def _make_pq_kernel(*, k, metric, merge, qt, m, g_lists, n_steps, K,
                    code_mode, ksub, bpr, extract_every, decode_cols):
    banks = _eff_banks(merge, m, 0)
    n_groups, gw = _code_groups(code_mode, ksub, bpr)
    # decode in column chunks so S stays VMEM-resident even for 256-entry
    # codebooks (K = pq_dim * 256); a chunk covers whole groups
    chunk_groups = n_groups if not decode_cols else max(1, decode_cols // gw)
    chunk_groups = min(chunk_groups, n_groups)

    def kernel(pr_ref, pv_ref, w_ref, qrot_ref, crot_ref, cod_ref, ln_ref,
               outv_ref, outi_ref, accv, acci, bankv, banki):
        i = pl.program_id(0)
        j = pl.program_id(1)

        @pl.when(j == 0)
        def _():
            accv[...] = jnp.full((qt, k), jnp.inf, jnp.float32)
            acci[...] = jnp.full((qt, k), -1, jnp.int32)
            bankv[...] = jnp.full((qt, banks * 128), jnp.inf, jnp.float32)
            banki[...] = jnp.full((qt, banks * 128), -1, jnp.int32)

        @pl.when(pv_ref[i, j] > 0)
        def _():
            w = w_ref[...]  # [qt, K] bf16
            base = pr_ref[i, j] * (g_lists * m)
            # coarse q.c term for the DMA'd lists (q_rot.c_rot == q.c under
            # the orthonormal rotation): one tiny [qt, G] matmul per step
            qdc = lax.dot_general(
                qrot_ref[...],
                crot_ref[0],
                dimension_numbers=(((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )  # [qt, G]
            # one column chunk per list: the q.c coarse term is constant
            # within a list, so it folds into the chunk epilogue as a
            # scalar column instead of a [qt, m] pass
            for g in range(g_lists):
                cod = cod_ref[0, g * m : (g + 1) * m, :]  # [m, bpr] u8
                dot = jnp.zeros((qt, m), jnp.float32)
                for g0 in range(0, n_groups, chunk_groups):
                    ngc = min(chunk_groups, n_groups - g0)
                    s = _multi_hot(
                        cod, code_mode=code_mode, ksub=ksub, m=m, bpr=bpr,
                        g0=g0, ng=ngc,
                    )
                    dot = dot + lax.dot_general(
                        w[:, g0 * gw : (g0 + ngc) * gw],
                        s,
                        dimension_numbers=(((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32,
                    )  # [qt, m]
                ln = ln_ref[0, 0, g * m : (g + 1) * m]  # prepared epilogue
                if metric == DistanceType.InnerProduct:
                    score = ln[None, :] - dot - qdc[:, g][:, None]
                else:
                    score = ln[None, :] - 2.0 * (dot + qdc[:, g][:, None])
                v, sl = _seg_compress(score, base + g * m, qt, m, banks)
                take = v < bankv[...]
                bankv[...] = jnp.where(take, v, bankv[...])
                banki[...] = jnp.where(take, sl, banki[...])

        if extract_every and extract_every < n_steps:
            do_extract = ((j + 1) % extract_every == 0) | (j == n_steps - 1)
        else:
            do_extract = j == n_steps - 1

        @pl.when(do_extract)
        def _():
            cv = jnp.concatenate([accv[...], bankv[...]], axis=1)
            ci = jnp.concatenate([acci[...], banki[...]], axis=1)
            nv, ni = _extract_topk(cv, ci, k)
            accv[...] = nv
            acci[...] = ni
            bankv[...] = jnp.full((qt, banks * 128), jnp.inf, jnp.float32)
            banki[...] = jnp.full((qt, banks * 128), -1, jnp.int32)

        @pl.when(j == n_steps - 1)
        def _():
            outv_ref[...] = accv[...]
            outi_ref[...] = acci[...]

    return kernel


def kernel_scratch_shapes(qt: int, k: int, banks: int):
    """The fused PQ kernel's scratch declarations: running top-k
    accumulator pair + bank-merge pair. Split out so tests can assert
    the VMEM residency model against the shapes the kernel actually
    allocates (``vmem_model.pq_scan_residency`` mirrors these)."""
    return [
        pltpu.VMEM((qt, k), jnp.float32),
        pltpu.VMEM((qt, k), jnp.int32),
        pltpu.VMEM((qt, banks * 128), jnp.float32),
        pltpu.VMEM((qt, banks * 128), jnp.int32),
    ]


def pq_lut(q_rot, books) -> jax.Array:
    """Per-query LUT ``W [nq, K]`` bf16: ``W[n, (j, c)] = <q_sub[n, j],
    books[j, c]>`` (the ``compute_similarity`` smem LUT, built once per
    query batch outside the kernel). ``books [pq_dim_eff, ksub_eff,
    pq_len]``; for nib8/p4 layouts the (j, c) flattening of ``books``
    must already match the kernel's multi-hot column order."""
    nq = q_rot.shape[0]
    pq_dim_eff, ksub_eff, pq_len = books.shape
    q_sub = q_rot.reshape(nq, pq_dim_eff, pq_len)
    w = jnp.einsum(
        "npl,pkl->npk", q_sub, books, preferred_element_type=jnp.float32
    )
    return w.reshape(nq, pq_dim_eff * ksub_eff).astype(jnp.bfloat16)


@functools.partial(
    jax.jit,
    static_argnames=(
        "k", "metric", "qt", "merge", "code_mode", "ksub", "extract_every",
        "decode_cols", "interpret",
    ),
)
def fused_pq_topk(
    codes,        # [n_units, gm, bpr] u8
    ln,           # [n_units, 1, gm] f32 prepared epilogue (sqn/pen, +inf invalid)
    w,            # [nq_pad, K] bf16 per-query LUT rows (tile-sorted)
    q_rot,        # [nq_pad, rot_dim] f32 rotated queries (tile-sorted)
    centers_rot,  # [n_units, G, rot_dim] f32 rotated coarse centers
    tile_probes,
    probe_valid,
    *,
    k: int,
    metric: DistanceType,
    qt: int,
    merge: str = "bank8",
    code_mode: str = "u8",
    ksub: int = 16,
    extract_every: int = 0,
    decode_cols: int = 2048,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """Run the fused probed-list PQ scan; returns ``(scores [nq_pad, k]
    asc, slots [nq_pad, k])`` with slot = unit * (G * max_list) + row."""
    n_units, gm, bpr = codes.shape
    nq_pad, K = w.shape
    rot_dim = q_rot.shape[1]
    n_qt, n_steps = tile_probes.shape
    g_lists = centers_rot.shape[1]
    m = gm // g_lists
    expects(nq_pad == n_qt * qt, "query rows %d != tiles*qt %d", nq_pad, n_qt * qt)
    expects(merge.startswith("bank"), "pq fused scan requires a bank merge mode")

    kernel = _make_pq_kernel(
        k=k, metric=metric, merge=merge, qt=qt, m=m, g_lists=g_lists,
        n_steps=n_steps, K=K, code_mode=code_mode, ksub=ksub, bpr=bpr,
        extract_every=extract_every, decode_cols=decode_cols,
    )
    banks = _eff_banks(merge, m, 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n_qt, n_steps),
        in_specs=[
            pl.BlockSpec((qt, K), lambda i, j, pr, pv: (i, 0)),
            pl.BlockSpec((qt, rot_dim), lambda i, j, pr, pv: (i, 0)),
            pl.BlockSpec((1, g_lists, rot_dim), lambda i, j, pr, pv: (pr[i, j], 0, 0)),
            # codes rows are deliberately narrow (bpr = 16-64 B/row is
            # the whole point of PQ): the lane padding the linter sees
            # costs VMEM but the HBM DMA moves only the real code bytes
            pl.BlockSpec((1, gm, bpr), lambda i, j, pr, pv: (pr[i, j], 0, 0)),  # graft-lint: ignore[tile-align]
            pl.BlockSpec((1, 1, gm), lambda i, j, pr, pv: (pr[i, j], 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((qt, k), lambda i, j, pr, pv: (i, 0)),
            pl.BlockSpec((qt, k), lambda i, j, pr, pv: (i, 0)),
        ],
        scratch_shapes=kernel_scratch_shapes(qt, k, banks),
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((nq_pad, k), jnp.float32),
            jax.ShapeDtypeStruct((nq_pad, k), jnp.int32),
        ],
        interpret=interpret,
        # the op's name in a profile, which the benchmark's kernel
        # readers match: stable whatever the wrapper is called
        name="fused_pq_topk",
    )(tile_probes, probe_valid, w, q_rot, centers_rot, codes, ln)


@functools.partial(
    jax.jit,
    static_argnames=(
        "k", "n_probes", "metric", "qt", "probe_factor", "group",
        "has_filter", "merge", "code_mode", "ksub", "extract_every",
        "decode_cols", "interpret",
    ),
)
def ivf_pq_fused_search(
    centers,
    centers_rot,
    center_rank,
    rotation,
    books,        # [pq_dim_eff, ksub_eff, pq_len] f32, W column order
    codes,        # [n_lists, max_list, bpr] u8
    list_indices,
    rot_sqnorms,
    queries,
    filter_bits,
    *,
    k: int,
    n_probes: int,
    metric: DistanceType,
    qt: int = 128,
    probe_factor: int = 32,
    group: int = 8,
    has_filter: bool = False,
    merge: str = "bank8",
    code_mode: str = "u8",
    ksub: int = 16,
    extract_every: int = 0,
    decode_cols: int = 2048,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """IVF-PQ search through the Pallas fused scan. Candidate-set
    semantics match the probe path whenever the tile probe union fits the
    table (see :func:`ivf_scan.ivf_flat_fused_search`); scores are exact
    ADC distances of the (possibly additive-nibble) codebooks, so pairing
    with :func:`raft_tpu.neighbors.refine.refine` mirrors the reference's
    refinement ratio workflow."""
    nq, d = queries.shape
    n_lists, m, bpr = codes.shape
    qf = queries.astype(jnp.float32)

    from raft_tpu.neighbors.ivf_common import probe_selection

    coarse, probed = probe_selection(centers, qf, n_probes, metric)
    order_pad, tile_probes, probe_valid = build_tile_probe_tables(
        coarse, probed, center_rank, nq=nq, qt=qt, n_lists=n_lists,
        group=group, n_probes=n_probes, probe_factor=probe_factor,
    )
    nq_pad = order_pad.shape[0]
    qs = qf[order_pad]

    # per-query LUT, in tile order (the q.c coarse term is computed
    # in-kernel from q_rot x centers_rot — rotation-invariant)
    q_rot = qs @ rotation.T
    w = pq_lut(q_rot, books)
    n_units = n_lists // group
    rot_dim = rotation.shape[0]

    # prepared epilogue: sqn (+inf invalid) for L2, 0/+inf penalty for IP,
    # with the prefilter folded in
    valid = list_indices >= 0
    if has_filter:
        ids = jnp.clip(list_indices, 0, None)
        word = filter_bits[ids // 32]
        bit = (word >> (ids % 32).astype(jnp.uint32)) & 1
        valid = valid & (bit == 1)
    if metric == DistanceType.InnerProduct:
        ln = jnp.where(valid, 0.0, jnp.inf).astype(jnp.float32)
    else:
        ln = jnp.where(valid, rot_sqnorms, jnp.inf)

    from raft_tpu.ops.pallas._guard import kernel_guard

    gm = group * m
    with kernel_guard("ivf_pq_fused_search"):
        vals, slots = fused_pq_topk(
            codes.reshape(n_units, gm, bpr),
            ln.reshape(n_units, 1, gm),
            w,
            q_rot,
            centers_rot.reshape(n_units, group, rot_dim),
            tile_probes,
            probe_valid,
            k=k,
            metric=metric,
            qt=qt,
            merge=merge,
            code_mode=code_mode,
            ksub=ksub,
            extract_every=extract_every,
            decode_cols=decode_cols,
            interpret=interpret,
        )

    # postprocess (mirrors _ivf_pq_scan_impl's tail)
    flat_ids = list_indices.reshape(-1)
    idx = jnp.where(slots >= 0, flat_ids[jnp.clip(slots, 0, None)], -1)
    if metric == DistanceType.InnerProduct:
        out = -vals
    else:
        qn = jnp.sum(q_rot * q_rot, axis=1)
        out = jnp.maximum(qn[:, None] + vals, 0.0)
        if metric == DistanceType.L2SqrtExpanded:
            out = jnp.sqrt(out)
        out = jnp.where(idx >= 0, out, jnp.inf)

    order = order_pad[:nq]
    dist = jnp.zeros((nq, k), jnp.float32).at[order].set(out[:nq])
    ind = jnp.full((nq, k), -1, jnp.int32).at[order].set(idx[:nq])
    return dist, ind
