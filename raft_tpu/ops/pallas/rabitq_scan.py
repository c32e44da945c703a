"""Pallas fused probed-list scan for IVF-RaBitQ search.

Reference analog: the bitwise IVF-RaBitQ scan of "GPU-Native Approximate
Nearest Neighbor Search with IVF-RaBitQ" (PAPERS.md) — one bit per
rotated-residual dimension plus two per-vector scalar corrections, scored
with the unbiased estimator and rescored through ``refine``.

TPU design
----------
The estimator needs one number per scanned row: the sign-bit dot
``b . q_rot``. On TPU that is a plain matmul against the unpacked bit
plane — no LUT, no per-lane gather. Per probed list the kernel

1. unpacks the ``[m, bpr]`` u8 codes to a ``[rows, D]`` f32 0/1 plane
   (byte-spread matmul + power-of-two floor peel, all exact in f32;
   row-chunked under the VMEM budget of
   :func:`raft_tpu.ops.pallas.vmem_model.rabitq_decode_rows_budget`),
2. takes ``dot = q_rot @ bits^T`` on the MXU ([qt, m] f32), and
3. applies the elementwise epilogue with the two prepared per-slot
   channels — ``ln`` (the center-dependent constant ``C1``, +inf for
   invalid/filtered slots) and ``corr`` (the estimator scale ``g``):

       score = ln - coef * (q . c_l) - g * (dot - sum(q_rot) / 2)

   (min-score convention; ``coef`` = 2 for L2, 1 for IP — the encode side
   in :mod:`raft_tpu.neighbors.ivf_pq` folds every other estimator term
   into ``ln``/``g`` so ONE kernel formula serves both metrics).

Versus the PQ fused scan the DMA per row is identical at d=128 (16 B)
but the decode matmul shrinks from ``pq_dim * ksub`` multi-hot columns
to D sign columns — the per-row FLOP drop the paper banks on.

Probe scheduling, tile-coherent query ordering, scalar-prefetch DMA of
only the probed code blocks, and the bank-merge running top-k are shared
with :mod:`raft_tpu.ops.pallas.ivf_scan` / :mod:`~.pq_scan`.

Supported metrics: L2Expanded / L2SqrtExpanded / InnerProduct.
"""
from __future__ import annotations

import functools
from typing import Tuple

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


def _sign_bits(cod, *, rows: int, bpr: int, rot_dim: int):
    """Unpack a ``[rows, bpr]`` u8 code block to its ``[rows, rot_dim]``
    f32 0/1 sign plane (little-endian bit t of byte s = dimension
    ``s*8 + t``, matching ``ivf_pq.pack_codes_bits``). Built entirely in
    2D for Mosaic: a spread matmul broadcasts byte ``t // 8`` onto lane
    t (bytes <= 255 are exact in f32), then a power-of-two floor peel
    extracts bit ``t % 8`` (shifts <= 7 of exact integers — every
    intermediate is an exact f32 integer)."""
    # u8 -> f32 via i32 (Mosaic has no direct u8 -> float cast)
    codf = cod.astype(jnp.int32).astype(jnp.float32)  # [rows, bpr]
    ej = lax.broadcasted_iota(jnp.int32, (bpr, rot_dim), 0)
    et = lax.broadcasted_iota(jnp.int32, (bpr, rot_dim), 1)
    spread = (ej == et // 8).astype(jnp.float32)  # [bpr, rot_dim]
    byte_lane = lax.dot_general(
        codf, spread, dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # [rows, rot_dim] — dimension t's byte value on lane t
    tib = lax.broadcasted_iota(jnp.int32, (rows, rot_dim), 1) % 8
    t = jnp.floor(byte_lane * jnp.exp2(-tib.astype(jnp.float32)))  # >> t%8
    return t - 2.0 * jnp.floor(t * 0.5)  # ... & 1


def _decode_rows_budget(*, m: int, bpr: int, **model_kwargs) -> int:
    """Bytes of scoped VMEM one sign-plane row chunk may use at this
    shape (see :func:`vmem_model.rabitq_decode_rows_budget`)."""
    return vmem_model.rabitq_decode_rows_budget(m=m, bpr=bpr, **model_kwargs)


def vmem_decode_rows(
    *,
    m: int,
    bpr: int,
    qt: int = 128,
    k: int = 128,
    g_lists: int = 8,
    rot_dim: int = 128,
    merge: str = "bank8",
) -> int:
    """Row-chunk size for the in-kernel sign-bit unpack so the scoped
    VMEM stack fits the TPU's ~16 MB limit: the per-shape budget divided
    by :data:`vmem_model.RABITQ_DECODE_CELL_BYTES` per (row, dim) cell,
    rounded down to a multiple of 128 rows (sublane-friendly chunks).
    Returns ``m`` when the whole list fits in one chunk and 0 when not
    even a 128-row chunk fits (fused-infeasible — see
    :func:`rabitq_feasible`)."""
    budget = _decode_rows_budget(
        m=m, bpr=bpr, qt=qt, k=k, g_lists=g_lists, rot_dim=rot_dim,
        merge=merge,
    )
    per_row = vmem_model.RABITQ_DECODE_CELL_BYTES * rot_dim
    cap = max(0, budget) // per_row
    if cap >= m:
        return m
    return (cap // 128) * 128


def rabitq_feasible(
    *,
    m: int,
    bpr: int,
    qt: int = 128,
    k: int = 128,
    g_lists: int = 8,
    rot_dim: int = 128,
    merge: str = "bank8",
) -> bool:
    """Whether the fused rabitq kernel fits VMEM at this shape — false
    for very long lists (the full ``[qt, m]`` dot accumulator plus one
    row chunk exceed the budget), where callers must use the scan path
    instead."""
    return (
        vmem_decode_rows(
            m=m, bpr=bpr, qt=qt, k=k, g_lists=g_lists, rot_dim=rot_dim,
            merge=merge,
        )
        > 0
    )


def _make_rabitq_kernel(*, k, metric, merge, qt, m, g_lists, n_steps,
                        rot_dim, bpr, extract_every, decode_rows):
    banks = _eff_banks(merge, m, 0)
    chunk_rows = m if not decode_rows else min(decode_rows, m)

    def kernel(pr_ref, pv_ref, qrot_ref, crot_ref, cod_ref, ln_ref,
               corr_ref, outv_ref, outi_ref, accv, acci, bankv, banki):
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
            qr = qrot_ref[...]  # [qt, rot_dim]
            sq = jnp.sum(qr, axis=1)  # [qt] — the estimator's sum(q_rot)
            base = pr_ref[i, j] * (g_lists * m)
            # coarse q.c term for the DMA'd lists (q_rot.c_rot == q.c under
            # the orthonormal rotation): one tiny [qt, G] matmul per step
            qdc = lax.dot_general(
                qr,
                crot_ref[0],
                dimension_numbers=(((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )  # [qt, G]
            for g in range(g_lists):
                cod = cod_ref[0, g * m : (g + 1) * m, :]  # [m, bpr] u8
                # row-chunked sign unpack: only one [rows, rot_dim] bit
                # plane is live at a time; the dots concatenate back to
                # the full [qt, m] accumulator (static chunk bounds)
                parts = []
                for r0 in range(0, m, chunk_rows):
                    rc = min(chunk_rows, m - r0)
                    bits = _sign_bits(
                        cod[r0 : r0 + rc, :], rows=rc, bpr=bpr,
                        rot_dim=rot_dim,
                    )
                    parts.append(
                        lax.dot_general(
                            qr, bits,
                            dimension_numbers=(((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32,
                        )
                    )  # [qt, rc]
                dot = parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=1)
                ln = ln_ref[0, 0, g * m : (g + 1) * m]  # prepared C1 (+inf invalid)
                gc = corr_ref[0, 0, g * m : (g + 1) * m]  # prepared g
                if metric == DistanceType.InnerProduct:
                    coef = 1.0
                else:
                    coef = 2.0
                score = (
                    ln[None, :]
                    - coef * qdc[:, g][:, None]
                    - gc[None, :] * (dot - 0.5 * sq[:, None])
                )
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
    """The fused rabitq kernel's scratch declarations: running top-k
    accumulator pair + bank-merge pair (identical to pq_scan's). Split
    out so tests can assert the VMEM residency model against the shapes
    the kernel actually allocates (``vmem_model.rabitq_scan_residency``
    mirrors these)."""
    return [
        pltpu.VMEM((qt, k), jnp.float32),
        pltpu.VMEM((qt, k), jnp.int32),
        pltpu.VMEM((qt, banks * 128), jnp.float32),
        pltpu.VMEM((qt, banks * 128), jnp.int32),
    ]


@functools.partial(
    jax.jit,
    static_argnames=("k", "metric", "qt", "merge", "extract_every",
                     "decode_rows", "interpret"),
)
def fused_rabitq_topk(
    codes,        # [n_units, gm, bpr] u8 packed sign bits
    ln,           # [n_units, 1, gm] f32 prepared C1 (+inf invalid)
    corr,         # [n_units, 1, gm] f32 prepared g (0 at pad slots)
    q_rot,        # [nq_pad, rot_dim] f32 rotated queries (tile-sorted)
    centers_rot,  # [n_units, G, rot_dim] f32 rotated coarse centers
    tile_probes,
    probe_valid,
    *,
    k: int,
    metric: DistanceType,
    qt: int,
    merge: str = "bank8",
    extract_every: int = 0,
    decode_rows: int = 0,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """Run the fused probed-list rabitq scan; returns ``(scores [nq_pad,
    k] asc, slots [nq_pad, k])`` with slot = unit * (G * max_list) + row."""
    n_units, gm, bpr = codes.shape
    nq_pad, rot_dim = q_rot.shape
    n_qt, n_steps = tile_probes.shape
    g_lists = centers_rot.shape[1]
    m = gm // g_lists
    expects(nq_pad == n_qt * qt, "query rows %d != tiles*qt %d", nq_pad, n_qt * qt)
    expects(merge.startswith("bank"), "rabitq fused scan requires a bank merge mode")
    expects(bpr * 8 == rot_dim, "rabitq codes carry %d bits/row but rot_dim=%d",
            bpr * 8, rot_dim)

    kernel = _make_rabitq_kernel(
        k=k, metric=metric, merge=merge, qt=qt, m=m, g_lists=g_lists,
        n_steps=n_steps, rot_dim=rot_dim, bpr=bpr,
        extract_every=extract_every, decode_rows=decode_rows,
    )
    banks = _eff_banks(merge, m, 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n_qt, n_steps),
        in_specs=[
            pl.BlockSpec((qt, rot_dim), lambda i, j, pr, pv: (i, 0)),
            pl.BlockSpec((1, g_lists, rot_dim), lambda i, j, pr, pv: (pr[i, j], 0, 0)),
            # codes rows are deliberately narrow (bpr = D/8 bytes/row is
            # the whole point of RaBitQ): the lane padding the linter
            # sees costs VMEM but the HBM DMA moves only real code bytes
            pl.BlockSpec((1, gm, bpr), lambda i, j, pr, pv: (pr[i, j], 0, 0)),  # graft-lint: ignore[tile-align]
            pl.BlockSpec((1, 1, gm), lambda i, j, pr, pv: (pr[i, j], 0, 0)),
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
    )(tile_probes, probe_valid, q_rot, centers_rot, codes, ln, corr)


@functools.partial(
    jax.jit,
    static_argnames=(
        "k", "n_probes", "metric", "qt", "probe_factor", "group",
        "has_filter", "merge", "extract_every", "decode_rows", "interpret",
        "row_norm",
    ),
)
def ivf_rabitq_fused_search(
    centers,
    centers_rot,
    center_rank,
    rotation,
    codes,        # [n_lists, max_list, bpr] u8 packed sign bits
    list_indices,
    rot_sqnorms,  # [n_lists, max_list] f32 — the estimator constant C1
    corrections,  # [n_lists, max_list] f32 — the estimator scale g
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
    extract_every: int = 0,
    decode_rows: int = 0,
    interpret: bool = False,
    row_norm: float = 0.0,
) -> Tuple[jax.Array, jax.Array]:
    """IVF-RaBitQ search through the Pallas fused scan. Candidate-set
    semantics match the probe path whenever the tile probe union fits the
    table (see :func:`ivf_scan.ivf_flat_fused_search`); scores are the
    unbiased rabitq estimates, so pairing with
    :func:`raft_tpu.neighbors.refine.refine` recovers exact-rank results
    the way the paper's rescoring pass does."""
    nq, d = queries.shape
    n_lists, m, bpr = codes.shape
    qf = queries.astype(jnp.float32)

    from raft_tpu.neighbors.ivf_common import probe_selection

    coarse, probed = probe_selection(centers, qf, n_probes, metric, row_norm)
    order_pad, tile_probes, probe_valid = build_tile_probe_tables(
        coarse, probed, center_rank, nq=nq, qt=qt, n_lists=n_lists,
        group=group, n_probes=n_probes, probe_factor=probe_factor,
    )
    nq_pad = order_pad.shape[0]
    qs = qf[order_pad]
    q_rot = qs @ rotation.T
    n_units = n_lists // group
    rot_dim = rotation.shape[0]

    # prepared epilogue: the estimator constant C1 (stored in rot_sqnorms;
    # identically 0 for IP) with invalid/filtered slots pushed to +inf, and
    # the scale g (0 at pad slots, so inf - 0*dot stays inf, never NaN)
    valid = list_indices >= 0
    if has_filter:
        ids = jnp.clip(list_indices, 0, None)
        word = filter_bits[ids // 32]
        bit = (word >> (ids % 32).astype(jnp.uint32)) & 1
        valid = valid & (bit == 1)
    ln = jnp.where(valid, rot_sqnorms, jnp.inf)
    corr = jnp.where(valid, corrections, 0.0)

    from raft_tpu.ops.pallas._guard import kernel_guard

    gm = group * m
    with kernel_guard("ivf_rabitq_fused_search"):
        vals, slots = fused_rabitq_topk(
            codes.reshape(n_units, gm, bpr),
            ln.reshape(n_units, 1, gm),
            corr.reshape(n_units, 1, gm),
            q_rot,
            centers_rot.reshape(n_units, group, rot_dim),
            tile_probes,
            probe_valid,
            k=k,
            metric=metric,
            qt=qt,
            merge=merge,
            extract_every=extract_every,
            decode_rows=decode_rows,
            interpret=interpret,
        )

    # postprocess (mirrors rabitq_scan_core's tail: est = ||q||^2 + score
    # for L2, est = -score for IP)
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
