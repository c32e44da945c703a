"""Pallas fused probed-list scan for IVF-PQ search.

Reference analog: the shared-memory LUT similarity kernel
(``neighbors/detail/ivf_pq_compute_similarity-inl.cuh:252-457``) with its
fp8/half LUTs (``detail/ivf_pq_fp_8bit.cuh``) — one CUDA kernel per
(query, probe) that builds a per-subspace lookup table in shared memory
and accumulates ``sum_j LUT[j, code_j]`` over the probed list's codes.

TPU design
----------
TPUs have no fast per-lane gather, so the LUT lookup becomes a **multi-hot
matmul**: per query batch, the LUT ``W[q, (j, c)] = <q_sub[j], books[j, c]>``
is computed ONCE outside the kernel ([nq, K] bf16, K = pq_dim * ksub) and
the kernel scores a code block by expanding its codes to a multi-hot
``S [rows, K]`` (pq_dim ones per row, built with VPU compares) and taking
``W @ S^T`` on the MXU. With ksub <= 64 the decode FLOPs stay a small
multiple of the raw-vector scan's — and the DMA drops to the CODE bytes
(16-64 B/row instead of 256-512 B/row), which is the entire point of PQ:
on bandwidth-bound hardware the compressed index scans faster than raw
vectors and an order of magnitude beyond what fits in HBM raw.

The kernel walks (query, probe) **pairs**, not queries. The wrapper
sorts a call's ``nq * n_probes`` pairs by list and cuts them into tiles
of ``qt`` pairs; each pair row carries its query's LUT row and the
coarse ``q.c`` term of its list. ``S`` depends on the list alone, and a
tile's lists form one ascending run, so the 1-D grid walks the (tile,
unit) steps in ascending list order (:func:`pair_walk`): each list a
tile holds is decoded once into a VMEM scratch and scored by one
``[qt, K] @ S^T`` matmul, and only the rows of its own pairs keep a
finite score. Each query sees exactly its own ``n_probes`` lists, and
the MXU makes one pass a list plus one at each tile boundary. The
scalar-prefetched unit ids drive the DMA of only the probed code
blocks; on a tile's last step the bank merge of
:mod:`raft_tpu.ops.pallas.ivf_scan` gives each pair its top ``k``, and
the wrapper takes each query's top ``k`` of its pairs' in XLA.

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
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from raft_tpu.core.errors import expects
from raft_tpu.ops.distance import DistanceType
from raft_tpu.ops.pallas import vmem_model
from raft_tpu.ops.pallas.ivf_scan import _eff_banks, _extract_topk, _seg_compress
from raft_tpu.utils.math import cdiv

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
    fixed residents (pair LUT rows, multi-hot scratch, bank scratch,
    double-buffered code+epilogue DMA, dot accumulator) as accounted by
    :func:`raft_tpu.ops.pallas.vmem_model.pq_decode_chunk_budget`."""
    return vmem_model.pq_decode_chunk_budget(
        m=m, code_mode=code_mode, ksub=ksub, bpr=bpr, **model_kwargs
    )


def decode_feasible(*, m: int, code_mode: str, ksub: int, bpr: int,
                    **model_kwargs) -> bool:
    """Whether the one-list multi-hot scratch plus one lane-aligned
    decode chunk fit the derived VMEM budget — false for
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
    (``qt``/``k``/``g_lists``/``merge``) refine
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


def _make_pq_kernel(*, k, metric, qt, m, g_lists, Kp, banks, code_mode, ksub,
                    bpr, decode_cols):
    _, gw = _code_groups(code_mode, ksub, bpr)
    # decode in lane-aligned column chunks, so the byte-spread temps of
    # 256-entry codebooks (Kp = pq_dim * 256) stay inside VMEM
    lanes = vmem_model.decode_lanes(code_mode, ksub, bpr)
    chunk = max(lanes, min(decode_cols or Kp, Kp) // lanes * lanes)
    first_bit, last_bit = 1 << g_lists, 1 << (g_lists + 1)

    def kernel(tile_ref, unit_ref, flags_ref, w_ref, plist_ref, pterm_ref,
               cod_ref, ln_ref, outv_ref, outi_ref, bankv, banki, s_ref):
        s = pl.program_id(0)
        flags = flags_ref[s]

        @pl.when((flags & first_bit) != 0)
        def _():
            bankv[...] = jnp.full((qt, banks * 128), jnp.inf, jnp.float32)
            banki[...] = jnp.full((qt, banks * 128), -1, jnp.int32)

        for g in range(g_lists):
            # decode each list of the unit that a pair of the tile
            # probes, once, and score the whole tile against it
            @pl.when(((flags >> g) & 1) != 0)
            def _(g=g):
                cod = cod_ref[0, g * m : (g + 1) * m, :]  # [m, bpr] u8
                for c0 in range(0, Kp, chunk):
                    c1 = min(c0 + chunk, Kp)
                    s_ref[:, c0:c1] = _multi_hot(
                        cod, code_mode=code_mode, ksub=ksub, m=m, bpr=bpr,
                        g0=c0 // gw, ng=(c1 - c0) // gw,
                    )
                lst = unit_ref[s] * g_lists + g
                # a row whose pair probes another list scores +inf; the
                # pair's coarse q.c term is constant within its list
                term = jnp.where(plist_ref[...] == lst, pterm_ref[...], jnp.inf)
                dot = lax.dot_general(
                    w_ref[...],
                    s_ref[...],
                    dimension_numbers=(((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )  # [qt, m]
                ln = ln_ref[0, 0, g * m : (g + 1) * m]  # prepared epilogue
                if metric == DistanceType.InnerProduct:
                    score = ln[None, :] - dot + term
                else:
                    score = ln[None, :] - 2.0 * dot + term
                v, sl = _seg_compress(score, lst * m, qt, m, banks)
                take = v < bankv[...]
                bankv[...] = jnp.where(take, v, bankv[...])
                banki[...] = jnp.where(take, sl, banki[...])

        @pl.when((flags & last_bit) != 0)
        def _():
            # a pair's candidates all come from its one list: one
            # extraction of the banks gives its top k
            outv_ref[...], outi_ref[...] = _extract_topk(bankv[...], banki[...], k)

    return kernel


def kernel_scratch_shapes(qt: int, banks: int, m: int, Kp: int):
    """The fused PQ kernel's scratch declarations: the bank-merge pair of
    one ``qt``-pair tile and the one-list multi-hot ``[m, Kp]``. Split
    out so tests can assert the VMEM residency model against the shapes
    the kernel actually allocates (``vmem_model.pq_scan_residency``
    mirrors these)."""
    return [
        pltpu.VMEM((qt, banks * 128), jnp.float32),
        pltpu.VMEM((qt, banks * 128), jnp.int32),
        pltpu.VMEM((m, Kp), jnp.bfloat16),
    ]


class PairWalk(NamedTuple):
    """A call's (query, probe) pairs sorted by list, cut into tiles of
    ``qt`` pairs, and the kernel's walk over them (:func:`pair_walk`).

    Per sorted pair (``n_pad = n_tiles * qt``): ``pair_query`` and
    ``pair_list`` (ascending; ``n_lists`` on padding pairs, which match
    no list). ``inverse [nq * n_probes]``: the sorted position of pair
    ``q * n_probes + j``. Per walk step: ``step_tile``, ``step_unit``
    and ``step_flags`` (bit ``g`` set where the tile holds list ``g`` of
    the unit; bits ``group`` and ``group + 1`` mark the tile's first and
    last step). ``list_passes``: the (tile, list) decodes and matmuls of
    the walk; ``tile_units [n_tiles]``: the units each tile holds."""

    pair_query: jax.Array
    pair_list: jax.Array
    inverse: jax.Array
    step_tile: jax.Array
    step_unit: jax.Array
    step_flags: jax.Array
    list_passes: jax.Array
    tile_units: jax.Array


def pair_walk(probes, *, n_lists: int, group: int, qt: int) -> PairWalk:
    """Sort the ``[nq, n_probes]`` (query, list) pairs of ``probes`` by
    list, cut them into tiles of ``qt`` pairs, and lay out the walk of
    (tile, unit) steps, ``unit`` = ``group`` adjacent lists, in
    ascending list order.

    A tile's lists form one ascending run and neighbouring tiles share
    at most one, so the walk has at most ``n_tiles + n_units - 1`` steps
    (and no more than there are pairs); the rest of the static length
    are padding steps that hold no list and re-address the last blocks.
    Each list is scored once, plus once more for each tile boundary that
    falls inside its pairs."""
    nq, n_probes = probes.shape
    n_pairs = nq * n_probes
    n_tiles = cdiv(n_pairs, qt)
    n_pad = n_tiles * qt
    n_units = n_lists // group
    expects(n_lists % group == 0, "n_lists %d not divisible by group %d", n_lists, group)
    expects(group <= 29, "a unit of %d lists overflows the step flags", group)
    flat = jnp.pad(probes.reshape(-1).astype(jnp.int32), (0, n_pad - n_pairs),
                   constant_values=n_lists)
    perm = jnp.argsort(flat, stable=True).astype(jnp.int32)
    plist = flat[perm]
    valid = perm < n_pairs
    pquery = jnp.where(valid, perm // n_probes, 0)
    inverse = jnp.zeros((n_pad,), jnp.int32).at[perm].set(
        jnp.arange(n_pad, dtype=jnp.int32))[:n_pairs]

    pos = jnp.arange(n_pad, dtype=jnp.int32)
    tile = pos // qt
    starts = pos % qt == 0
    prev = jnp.concatenate([jnp.full((1,), -1, jnp.int32), plist[:-1]])
    unit = plist // group
    new_list = valid & (starts | (plist != prev))  # a (tile, list) pass
    new_unit = valid & (starts | (unit != prev // group))  # a walk step
    item = jnp.cumsum(new_unit.astype(jnp.int32)) - 1

    n_steps = min(n_tiles + n_units - 1, n_pairs)
    at = jnp.where(new_unit, item, n_steps)  # out of range: dropped
    step_tile = jnp.full((n_steps,), n_tiles - 1, jnp.int32).at[at].set(tile, mode="drop")
    step_unit = jnp.full((n_steps,), unit[n_pairs - 1], jnp.int32).at[at].set(
        unit, mode="drop")
    held = jnp.zeros((n_steps,), jnp.int32).at[jnp.where(new_list, item, n_steps)].add(
        jnp.left_shift(1, plist % group), mode="drop")
    edge = jnp.full((1,), -1, jnp.int32)
    first = step_tile != jnp.concatenate([edge, step_tile[:-1]])
    last = step_tile != jnp.concatenate([step_tile[1:], edge])
    flags = (held | (first.astype(jnp.int32) << group)
             | (last.astype(jnp.int32) << (group + 1)))
    return PairWalk(
        pquery, plist, inverse, step_tile, step_unit, flags,
        jnp.sum(new_list.astype(jnp.int32)),
        jnp.sum(new_unit.reshape(n_tiles, qt).astype(jnp.int32), axis=1),
    )


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
        "k", "metric", "qt", "group", "merge", "code_mode", "ksub",
        "decode_cols", "interpret",
    ),
)
def fused_pq_topk(
    codes,        # [n_units, group * m, bpr] u8
    ln,           # [n_units, 1, group * m] f32 prepared epilogue (+inf invalid)
    w,            # [n_pad, K] bf16: each sorted pair's LUT row
    pair_list,    # [n_pad, 1] i32: each sorted pair's list
    pair_term,    # [n_pad, 1] f32: each sorted pair's coarse epilogue term
    step_tile,    # [n_steps] i32 }
    step_unit,    # [n_steps] i32 } the walk of :func:`pair_walk`
    step_flags,   # [n_steps] i32 }
    *,
    k: int,
    metric: DistanceType,
    qt: int,
    group: int,
    merge: str = "bank8",
    code_mode: str = "u8",
    ksub: int = 16,
    decode_cols: int = 2048,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """Run the fused PQ scan over a pair walk; returns ``(scores [n_pad,
    k] asc, slots [n_pad, k])`` of each sorted pair, slot = list *
    max_list + row.

    The 1-D grid walks the (tile, unit) steps in ascending list order;
    each list of a step that the tile holds is decoded once into a VMEM
    multi-hot and scored for the tile's ``qt`` rows, of which only the
    rows of its own pairs keep a finite score, so every pair sees
    exactly its own list."""
    n_units, gm, bpr = codes.shape
    n_pad, K = w.shape
    (n_steps,) = step_tile.shape
    m = gm // group
    expects(n_pad % qt == 0, "pair rows %d not a multiple of the tile %d", n_pad, qt)
    expects(merge.startswith("bank"), "pq fused scan requires a bank merge mode")
    Kp = vmem_model.padded_cols(code_mode, ksub, bpr)
    if Kp != K:
        w = jnp.pad(w, ((0, 0), (0, Kp - K)))  # zero LUT columns score 0
    banks = _eff_banks(merge, m, 0)

    kernel = _make_pq_kernel(
        k=k, metric=metric, qt=qt, m=m, g_lists=group, Kp=Kp, banks=banks,
        code_mode=code_mode, ksub=ksub, bpr=bpr, decode_cols=decode_cols,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(n_steps,),
        in_specs=[
            pl.BlockSpec((qt, Kp), lambda s, tile, unit, flags: (tile[s], 0)),
            pl.BlockSpec((qt, 1), lambda s, tile, unit, flags: (tile[s], 0)),
            pl.BlockSpec((qt, 1), lambda s, tile, unit, flags: (tile[s], 0)),
            # codes rows are deliberately narrow (bpr = 16-64 B/row is
            # the whole point of PQ): the lane padding the linter sees
            # costs VMEM but the HBM DMA moves only the real code bytes
            pl.BlockSpec((1, gm, bpr), lambda s, tile, unit, flags: (unit[s], 0, 0)),  # graft-lint: ignore[tile-align]
            pl.BlockSpec((1, 1, gm), lambda s, tile, unit, flags: (unit[s], 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((qt, k), lambda s, tile, unit, flags: (tile[s], 0)),
            pl.BlockSpec((qt, k), lambda s, tile, unit, flags: (tile[s], 0)),
        ],
        scratch_shapes=kernel_scratch_shapes(qt, banks, m, Kp),
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((n_pad, k), jnp.float32),
            jax.ShapeDtypeStruct((n_pad, k), jnp.int32),
        ],
        # the one-list multi-hot scratch is past the 16 MiB default scope
        # for 256-entry books; inside v5e's 128 MiB of VMEM
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=vmem_model.PQ_SCAN_VMEM_LIMIT_BYTES),
        interpret=interpret,
        # the op's name in a profile, which the benchmark's kernel
        # readers match: stable whatever the wrapper is called
        name="fused_pq_topk",
    )(step_tile, step_unit, step_flags, w, pair_list, pair_term, codes, ln)


@functools.partial(
    jax.jit,
    static_argnames=(
        "k", "n_probes", "metric", "qt", "group", "has_filter", "merge",
        "code_mode", "ksub", "decode_cols", "interpret", "stats", "row_norm",
    ),
)
def ivf_pq_fused_search(
    centers,
    centers_rot,
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
    group: int = 8,
    has_filter: bool = False,
    merge: str = "bank8",
    code_mode: str = "u8",
    ksub: int = 16,
    decode_cols: int = 2048,
    interpret: bool = False,
    stats: bool = False,
    row_norm: float = 0.0,
):
    """IVF-PQ search through the Pallas fused scan. Each query is scored
    against exactly its own ``n_probes`` lists, the candidate set of the
    probe path (ranked by :func:`ivf_common.coarse_scores` with the
    index's ``row_norm``); scores are exact ADC distances of the (possibly
    additive-nibble) codebooks over a bf16 LUT, so pairing with
    :func:`raft_tpu.neighbors.refine.refine` mirrors the reference's
    refinement ratio workflow.

    The kernel gives each (query, probe) pair its top ``k``
    (:func:`fused_pq_topk` over :func:`pair_walk`); each query's exact
    top ``k`` of its ``n_probes * k`` pair candidates is taken here.
    Returns ``(dist [nq, k], ind [nq, k])``; with ``stats`` also the
    walk's ``(list_passes, tile_units [n_tiles])``, computed in the same
    program."""
    nq, d = queries.shape
    n_lists, m, bpr = codes.shape
    qf = queries.astype(jnp.float32)

    from raft_tpu.neighbors.ivf_common import coarse_scores, probe_lists

    probes = probe_lists(coarse_scores(centers, qf, metric, row_norm), n_probes)
    walk = pair_walk(probes, n_lists=n_lists, group=group, qt=qt)

    # per-query LUT, zero-padded to the multi-hot's columns, then one
    # row for each sorted pair
    q_rot = qf @ rotation.T
    w = pq_lut(q_rot, books)
    Kp = vmem_model.padded_cols(code_mode, ksub, bpr)
    w = jnp.pad(w, ((0, 0), (0, Kp - w.shape[1])))
    # each pair's coarse term q_rot.c_rot (== q.c under the orthonormal
    # rotation), summed in f32 for the pairs alone, in the form the
    # epilogue adds
    pair_center = centers_rot[jnp.minimum(walk.pair_list, n_lists - 1)]
    qdc = jnp.sum(q_rot[walk.pair_query] * pair_center, axis=1)
    term = -qdc if metric == DistanceType.InnerProduct else -2.0 * qdc

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

    n_units = n_lists // group
    gm = group * m
    with kernel_guard("ivf_pq_fused_search"):
        vals, slots = fused_pq_topk(
            codes.reshape(n_units, gm, bpr),
            ln.reshape(n_units, 1, gm),
            w[walk.pair_query],
            walk.pair_list[:, None],
            term[:, None],
            walk.step_tile,
            walk.step_unit,
            walk.step_flags,
            k=k,
            metric=metric,
            qt=qt,
            group=group,
            merge=merge,
            code_mode=code_mode,
            ksub=ksub,
            # capped to what the VMEM model leaves at this shape
            decode_cols=vmem_decode_cols(
                decode_cols, m=m, code_mode=code_mode, ksub=ksub, bpr=bpr,
                qt=qt, k=k, g_lists=group, merge=merge),
            interpret=interpret,
        )

    # each query's exact top k of its n_probes x k pair candidates
    vals = vals[walk.inverse].reshape(nq, n_probes * k)
    slots = slots[walk.inverse].reshape(nq, n_probes * k)
    neg, best = lax.top_k(-vals, k)
    vals = -neg
    slots = jnp.take_along_axis(slots, best, axis=1)

    # postprocess (mirrors _ivf_pq_scan_impl's tail)
    flat_ids = list_indices.reshape(-1)
    ind = jnp.where(slots >= 0, flat_ids[jnp.clip(slots, 0, None)], -1)
    if metric == DistanceType.InnerProduct:
        dist = -vals
    else:
        qn = jnp.sum(q_rot * q_rot, axis=1)
        dist = jnp.maximum(qn[:, None] + vals, 0.0)
        if metric == DistanceType.L2SqrtExpanded:
            dist = jnp.sqrt(dist)
        dist = jnp.where(ind >= 0, dist, jnp.inf)
    if stats:
        return dist, ind, walk.list_passes, walk.tile_units
    return dist, ind
