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

``S`` depends on the list alone, so the grid is unit-major: for each
group of ``n_sub`` query sub-tiles it walks the units any of their
per-sub-tile tables holds, decodes each list once into a VMEM scratch,
and scores it for the member sub-tiles only (:func:`shared_unit_tables`).
A query's candidates stay its own sub-tile's lists; ``n_sub`` comes from
the VMEM model (:func:`fused_subtiles`), and ``n_sub = 1`` is the
one-tile schedule.

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
    ``VMEM_HEADROOM x PQ_SCAN_VMEM_LIMIT_BYTES`` minus the kernel's
    fixed residents (LUT rows, queries, multi-hot scratch, bank/acc
    scratch of every sub-tile, double-buffered code+epilogue DMA, dot
    accumulator) as accounted by
    :func:`raft_tpu.ops.pallas.vmem_model.pq_decode_chunk_budget`."""
    return vmem_model.pq_decode_chunk_budget(
        m=m, code_mode=code_mode, ksub=ksub, bpr=bpr, **model_kwargs
    )


def decode_feasible(*, m: int, code_mode: str, ksub: int, bpr: int,
                    **model_kwargs) -> bool:
    """Whether the one-list multi-hot scratch plus one lane-aligned
    decode chunk fit the derived VMEM budget at one sub-tile — false for
    very long lists with wide codebooks, where the fused kernel cannot
    compile and callers must use the scan path instead."""
    budget = _decode_chunk_budget(
        m=m, code_mode=code_mode, ksub=ksub, bpr=bpr, **model_kwargs
    )
    lanes = vmem_model.decode_lanes(code_mode, ksub, bpr)
    return _decode_cell_bytes(code_mode) * m * lanes <= budget


def vmem_decode_cols(requested: int, *, m: int, code_mode: str, ksub: int,
                     bpr: int, **model_kwargs) -> int:
    """Cap the decode column chunk so the kernel's scoped-VMEM stack fits
    the limit it compiles under.

    A chunk holds the f32 byte-spread and peel temporaries of its
    columns before they land in the multi-hot scratch (see
    :func:`_decode_cell_bytes`); capping it to the per-shape budget
    :func:`_decode_chunk_budget` derives from the kernel's fixed
    residents keeps the whole stack inside the limit with margin.
    Chunks cover whole code groups and whole 128-lane tiles, so the cap
    rounds down to a multiple of :func:`vmem_model.decode_lanes`. Raises
    when even one such chunk cannot fit (use :func:`decode_feasible` to
    route such shapes to the scan path up front). ``model_kwargs``
    (``qt``/``k``/``g_lists``/``rot_dim``/``merge``/``n_sub``) refine
    the resident accounting; omitted ones fall back to conservative
    defaults."""
    Kp = vmem_model.padded_cols(code_mode, ksub, bpr)
    lanes = vmem_model.decode_lanes(code_mode, ksub, bpr)
    expects(
        decode_feasible(m=m, code_mode=code_mode, ksub=ksub, bpr=bpr,
                        **model_kwargs),
        "fused PQ decode infeasible: the %d-column multi-hot of %d rows "
        "exceeds the VMEM budget — use mode='scan' or more lists",
        Kp, m,
    )
    budget = _decode_chunk_budget(
        m=m, code_mode=code_mode, ksub=ksub, bpr=bpr, **model_kwargs
    )
    cap = int(budget // (_decode_cell_bytes(code_mode) * max(m, 1)))
    requested = min(requested or Kp, cap, Kp)
    return max(lanes, requested // lanes * lanes)


def fused_subtiles(nq: int, *, m: int, code_mode: str, ksub: int, bpr: int,
                   qt: int = 128, **model_kwargs) -> int:
    """How many ``qt``-query sub-tiles of an ``nq``-query call share each
    decoded list (:func:`vmem_model.pq_scan_subtiles`): a function of the
    static shapes alone, so a caller can observe it without a sync."""
    return vmem_model.pq_scan_subtiles(
        -(-nq // qt), m=m, code_mode=code_mode, ksub=ksub, bpr=bpr, qt=qt,
        **model_kwargs)


def _make_pq_kernel(*, k, metric, merge, qt, n_sub, m, g_lists, n_steps, Kp,
                    code_mode, ksub, bpr, extract_every, decode_cols):
    banks = _eff_banks(merge, m, 0)
    _, gw = _code_groups(code_mode, ksub, bpr)
    rows = n_sub * qt
    # decode in lane-aligned column chunks, so the byte-spread temps of
    # 256-entry codebooks (Kp = pq_dim * 256) stay inside VMEM
    lanes = vmem_model.decode_lanes(code_mode, ksub, bpr)
    chunk = max(lanes, min(decode_cols or Kp, Kp) // lanes * lanes)

    def sub_rows(t):
        """Rows of sub-tile ``t`` in the grid row's LUT/bank blocks."""
        return pl.ds(pl.multiple_of(t * qt, qt), qt)

    def kernel(pr_ref, pv_ref, mb_ref, w_ref, qrot_ref, crot_ref, cod_ref,
               ln_ref, outv_ref, outi_ref, accv, acci, bankv, banki, s_ref):
        i = pl.program_id(0)
        j = pl.program_id(1)

        def extract_subtile(t):
            """Merge sub-tile ``t``'s banks into its running top-k."""
            r = sub_rows(t)
            cv = jnp.concatenate([accv[r, :], bankv[r, :]], axis=1)
            ci = jnp.concatenate([acci[r, :], banki[r, :]], axis=1)
            nv, ni = _extract_topk(cv, ci, k)
            accv[r, :] = nv
            acci[r, :] = ni
            bankv[r, :] = jnp.full((qt, banks * 128), jnp.inf, jnp.float32)
            banki[r, :] = jnp.full((qt, banks * 128), -1, jnp.int32)

        @pl.when(j == 0)
        def _():
            accv[...] = jnp.full((rows, k), jnp.inf, jnp.float32)
            acci[...] = jnp.full((rows, k), -1, jnp.int32)
            bankv[...] = jnp.full((rows, banks * 128), jnp.inf, jnp.float32)
            banki[...] = jnp.full((rows, banks * 128), -1, jnp.int32)

        @pl.when(pv_ref[i, j] > 0)
        def _():
            base = pr_ref[i, j] * (g_lists * m)
            for g in range(g_lists):
                # decode list g once; every sub-tile whose table holds
                # this unit scores its own LUT rows against it
                cod = cod_ref[0, g * m : (g + 1) * m, :]  # [m, bpr] u8
                for c0 in range(0, Kp, chunk):
                    c1 = min(c0 + chunk, Kp)
                    s_ref[:, c0:c1] = _multi_hot(
                        cod, code_mode=code_mode, ksub=ksub, m=m, bpr=bpr,
                        g0=c0 // gw, ng=(c1 - c0) // gw,
                    )
                ln = ln_ref[0, 0, g * m : (g + 1) * m]  # prepared epilogue

                def score_subtile(t, carry):
                    @pl.when(mb_ref[i * n_sub + t, j] > 0)
                    def _():
                        r = sub_rows(t)
                        # coarse q.c term (q_rot.c_rot == q.c under the
                        # orthonormal rotation): constant within a list,
                        # so it folds into the epilogue as one column
                        qdc = lax.dot_general(
                            qrot_ref[r, :],
                            crot_ref[0],
                            dimension_numbers=(((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32,
                        )[:, g][:, None]  # [qt, 1]
                        dot = lax.dot_general(
                            w_ref[r, :],
                            s_ref[...],
                            dimension_numbers=(((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32,
                        )  # [qt, m]
                        if metric == DistanceType.InnerProduct:
                            score = ln[None, :] - dot - qdc
                        else:
                            score = ln[None, :] - 2.0 * (dot + qdc)
                        v, sl = _seg_compress(score, base + g * m, qt, m, banks)
                        take = v < bankv[r, :]
                        bankv[r, :] = jnp.where(take, v, bankv[r, :])
                        banki[r, :] = jnp.where(take, sl, banki[r, :])

                    return carry

                lax.fori_loop(0, n_sub, score_subtile, 0)

            if extract_every:
                # a sub-tile's banks merge after every extract_every-th
                # unit of its own table (member flag 2), as they would
                # on a walk of that table alone
                def extract_due(t, carry):
                    @pl.when(mb_ref[i * n_sub + t, j] > 1)
                    def _():
                        extract_subtile(t)

                    return carry

                lax.fori_loop(0, n_sub, extract_due, 0)

        @pl.when(j == n_steps - 1)
        def _():
            def extract_last(t, carry):
                extract_subtile(t)
                return carry

            lax.fori_loop(0, n_sub, extract_last, 0)
            outv_ref[...] = accv[...]
            outi_ref[...] = acci[...]

    return kernel


def kernel_scratch_shapes(rows: int, k: int, banks: int, m: int, Kp: int):
    """The fused PQ kernel's scratch declarations: running top-k
    accumulator pair + bank-merge pair for the ``rows`` queries of every
    sub-tile of a grid row, and the one-list multi-hot ``[m, Kp]``.
    Split out so tests can assert the VMEM residency model against the
    shapes the kernel actually allocates (``vmem_model.pq_scan_residency``
    mirrors these)."""
    return [
        pltpu.VMEM((rows, k), jnp.float32),
        pltpu.VMEM((rows, k), jnp.int32),
        pltpu.VMEM((rows, banks * 128), jnp.float32),
        pltpu.VMEM((rows, banks * 128), jnp.int32),
        pltpu.VMEM((m, Kp), jnp.bfloat16),
    ]


def shared_unit_tables(tile_probes, probe_valid, *, n_units: int, n_sub: int,
                       extract_every: int = 0):
    """Per-group-of-``n_sub``-sub-tiles walk of the probe units, from the
    per-sub-tile tables of :func:`ivf_scan.build_tile_probe_tables`.

    Returns ``(unit_probes [n_st, P], unit_valid [n_st, P], member
    [n_qt, P])``: the ascending units any sub-tile of a group holds
    (invalid slots re-address the row's last valid unit, as in the
    per-sub-tile tables), and whether sub-tile ``t``'s own table holds
    the unit of step ``j`` of its group: 2 where that unit is the
    ``extract_every``-th of the sub-tile's own table since its last
    merge. ``P = min(n_units, n_sub * p)``. With ``n_sub == 1`` this is
    the per-sub-tile table itself."""
    n_qt, p = tile_probes.shape
    n_st = n_qt // n_sub
    P = min(n_units, n_sub * p)
    held = jnp.zeros((n_qt, n_units), jnp.int32).at[
        jnp.arange(n_qt)[:, None], tile_probes
    ].max(probe_valid).reshape(n_st, n_sub, n_units)
    units = jnp.arange(n_units, dtype=jnp.int32)
    key = jnp.where(jnp.max(held, axis=1) > 0, units, n_units)
    unit_probes = jnp.sort(key, axis=1)[:, :P]
    unit_valid = (unit_probes < n_units).astype(jnp.int32)
    last = jnp.max(jnp.where(unit_valid > 0, unit_probes, 0), axis=1, keepdims=True)
    unit_probes = jnp.where(unit_valid > 0, unit_probes, last)
    member = jnp.take_along_axis(held, unit_probes[:, None, :], axis=2)
    member = (member * unit_valid[:, None, :]).reshape(n_qt, P)
    if extract_every:
        due = (jnp.cumsum(member, axis=1) % extract_every == 0) & (member > 0)
        member = member + due.astype(jnp.int32)
    return unit_probes, unit_valid, member


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
        "k", "metric", "qt", "n_sub", "merge", "code_mode", "ksub",
        "extract_every", "decode_cols", "interpret",
    ),
)
def fused_pq_topk(
    codes,        # [n_units, gm, bpr] u8
    ln,           # [n_units, 1, gm] f32 prepared epilogue (sqn/pen, +inf invalid)
    w,            # [nq_pad, K] bf16 per-query LUT rows (tile-sorted)
    q_rot,        # [nq_pad, rot_dim] f32 rotated queries (tile-sorted)
    centers_rot,  # [n_units, G, rot_dim] f32 rotated coarse centers
    unit_probes,  # [n_st, P] i32 ascending units of each sub-tile group
    unit_valid,   # [n_st, P] i32
    member,       # [n_st * n_sub, P] i32: sub-tile's own table holds the unit
    #               (2: and its banks merge after this step)
    *,
    k: int,
    metric: DistanceType,
    qt: int,
    n_sub: int = 1,
    merge: str = "bank8",
    code_mode: str = "u8",
    ksub: int = 16,
    extract_every: int = 0,
    decode_cols: int = 2048,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """Run the fused probed-list PQ scan; returns ``(scores [nq_pad, k]
    asc, slots [nq_pad, k])`` with slot = unit * (G * max_list) + row.

    The grid walks, for each group of ``n_sub`` query sub-tiles of
    ``qt`` rows, the units any of them probes in ascending order
    (:func:`shared_unit_tables`); each list of a unit is decoded once
    into a VMEM multi-hot and scored by the member sub-tiles only, so
    every query sees exactly the lists of its own sub-tile's table."""
    n_units, gm, bpr = codes.shape
    nq_pad, K = w.shape
    rot_dim = q_rot.shape[1]
    n_st, n_steps = unit_probes.shape
    g_lists = centers_rot.shape[1]
    m = gm // g_lists
    rows = n_sub * qt
    expects(nq_pad == n_st * rows, "query rows %d != groups*n_sub*qt %d",
            nq_pad, n_st * rows)
    expects(merge.startswith("bank"), "pq fused scan requires a bank merge mode")
    Kp = vmem_model.padded_cols(code_mode, ksub, bpr)
    if Kp != K:
        w = jnp.pad(w, ((0, 0), (0, Kp - K)))  # zero LUT columns score 0

    kernel = _make_pq_kernel(
        k=k, metric=metric, merge=merge, qt=qt, n_sub=n_sub, m=m,
        g_lists=g_lists, n_steps=n_steps, Kp=Kp, code_mode=code_mode,
        ksub=ksub, bpr=bpr, extract_every=extract_every,
        decode_cols=decode_cols,
    )
    banks = _eff_banks(merge, m, 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(n_st, n_steps),
        in_specs=[
            pl.BlockSpec((rows, Kp), lambda i, j, pr, pv, mb: (i, 0)),
            pl.BlockSpec((rows, rot_dim), lambda i, j, pr, pv, mb: (i, 0)),
            pl.BlockSpec((1, g_lists, rot_dim), lambda i, j, pr, pv, mb: (pr[i, j], 0, 0)),
            # codes rows are deliberately narrow (bpr = 16-64 B/row is
            # the whole point of PQ): the lane padding the linter sees
            # costs VMEM but the HBM DMA moves only the real code bytes
            pl.BlockSpec((1, gm, bpr), lambda i, j, pr, pv, mb: (pr[i, j], 0, 0)),  # graft-lint: ignore[tile-align]
            pl.BlockSpec((1, 1, gm), lambda i, j, pr, pv, mb: (pr[i, j], 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((rows, k), lambda i, j, pr, pv, mb: (i, 0)),
            pl.BlockSpec((rows, k), lambda i, j, pr, pv, mb: (i, 0)),
        ],
        scratch_shapes=kernel_scratch_shapes(rows, k, banks, m, Kp),
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((nq_pad, k), jnp.float32),
            jax.ShapeDtypeStruct((nq_pad, k), jnp.int32),
        ],
        # the multi-hot scratch and every sub-tile's banks stay resident:
        # past the 16 MiB default scope, inside v5e's 128 MiB of VMEM
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=vmem_model.PQ_SCAN_VMEM_LIMIT_BYTES),
        interpret=interpret,
        # the op's name in a profile, which the benchmark's kernel
        # readers match: stable whatever the wrapper is called
        name="fused_pq_topk",
    )(unit_probes, unit_valid, member, w, q_rot, centers_rot, codes, ln)


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

    # sub-tiles that share each decoded list, and the decode chunk that
    # fits beside their residents: both from the VMEM model at this shape
    model = dict(m=m, code_mode=code_mode, ksub=ksub, bpr=bpr, qt=qt, k=k,
                 g_lists=group, rot_dim=rot_dim, merge=merge)
    n_sub = fused_subtiles(nq, **model)
    unit_probes, unit_valid, member = shared_unit_tables(
        tile_probes, probe_valid, n_units=n_units, n_sub=n_sub,
        extract_every=extract_every)
    gm = group * m
    with kernel_guard("ivf_pq_fused_search"):
        vals, slots = fused_pq_topk(
            codes.reshape(n_units, gm, bpr),
            ln.reshape(n_units, 1, gm),
            w,
            q_rot,
            centers_rot.reshape(n_units, group, rot_dim),
            unit_probes,
            unit_valid,
            member,
            k=k,
            metric=metric,
            qt=qt,
            n_sub=n_sub,
            merge=merge,
            code_mode=code_mode,
            ksub=ksub,
            extract_every=extract_every,
            decode_cols=vmem_decode_cols(decode_cols, n_sub=n_sub, **model),
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
