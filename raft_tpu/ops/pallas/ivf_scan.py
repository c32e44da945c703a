"""Pallas fused probed-list scan for IVF-Flat search.

Reference analog: the fused interleaved-scan kernel
(``neighbors/detail/ivf_flat_interleaved_scan-inl.cuh:687``) — one CUDA
kernel that walks each query's probed lists, computes distances, and keeps
a per-query top-k, never materializing the full distance matrix.

TPU design
----------
The dense-scan XLA path (:func:`raft_tpu.neighbors.ivf_flat.flat_scan_core`)
streams the WHOLE padded index through the MXU and masks unprobed lists,
because XLA has no efficient data-dependent gather. That costs brute-force
FLOPs/bandwidth regardless of ``n_probes``. This kernel restores the IVF
work savings with three pieces:

1. **Scalar-prefetch DMA**: the grid is ``(query_tile, probe_slot)`` and the
   list-data block index map reads a prefetched probe table, so Mosaic's
   DMA engine streams exactly the probed ``[max_list, d]`` blocks from HBM
   into VMEM (double-buffered) — lists nobody probes are never touched.
2. **Tile-coherent queries**: probing is per query, DMA is per query-*tile*.
   Queries are sorted by the *spatial rank* of their nearest center (a
   PCA-bisection ordering of the coarse centroids, computed at build), so
   the ``QT`` queries of a tile probe nearly the same lists and the
   tile-union probe table stays small. Extra lists a tile scans beyond one
   query's own probes only *add* candidates (scored exactly), so per-query
   recall is >= the probe path's whenever the union fits the table; the
   table keeps the most-shared lists when it does not.
3. **In-kernel running top-k**: a VMEM accumulator merged per probe step,
   either exactly (``merge="exact"``: k rounds of min-extract over the full
   ``max_list`` width) or via a banked lane-group pre-compression
   (``merge="seg"``/``"seg1"``/``"seg4"``: per-(lane, bank) min over
   sublane groups first — the same PartialReduce idea as
   ``lax.approx_max_k``; ``seg`` = 2 banks, more banks = fewer
   same-lane collisions between candidates, slightly wider extract).

   ``merge="bank"``/``"bankN"`` goes one step further: the per-step
   compressed candidates are **min-merged elementwise** into a persistent
   ``[qt, N*128]`` (value, slot) buffer — 3 VPU selects per step — and the
   k-round extraction runs only every ``extract_every`` steps (0 = once at
   the end). The per-step cost drops from "compress + concat + k
   min-extract rounds" (the round-3 bottleneck: ~3-4x the matmul time) to
   "compress + 3 selects". The price is cross-step lane collisions: two
   candidates from different probe steps sharing a (lane, bank) slot keep
   only the better one. With N*128 slots and the true top-k spread
   uniformly over lanes, the expected loss is ~C(k,2)/(N*128) of one
   candidate per query (<0.5% recall@10 at N=8); ``extract_every`` bounds
   the collision window when that matters.

4. **Column-chunked scoring** (``col_chunk``): the [qt, m] score tile is
   computed in column slices so the f32 intermediate stays small enough to
   raise ``qt`` (bigger query tiles amortize the per-tile DMA of shared
   lists). Only supported with bank merge (slices merge into the
   persistent buffer; no per-slice extraction needed).

The kernel supports L2Expanded / L2SqrtExpanded / InnerProduct /
CosineExpanded, prefilters (folded into ``list_indices`` outside), and runs
in interpret mode on CPU for tests.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from raft_tpu.core.errors import expects
from raft_tpu.ops.distance import DistanceType
from raft_tpu.utils.math import cdiv

_SUPPORTED = frozenset(
    {
        DistanceType.L2Expanded,
        DistanceType.L2SqrtExpanded,
        DistanceType.InnerProduct,
        DistanceType.CosineExpanded,
    }
)


def supported_metric(metric: DistanceType) -> bool:
    return metric in _SUPPORTED


# ---------------------------------------------------------------------------
# spatial ordering of the coarse centers (build-time, host)
# ---------------------------------------------------------------------------


def spatial_center_rank(centers: np.ndarray, leaf: int = 8) -> np.ndarray:
    """PCA-bisection rank of the coarse centers: recursively split along
    the local principal direction at the median, so lists with nearby ranks
    are nearby in space. Sorting queries by ``rank[top1_center]`` makes
    query tiles probe-coherent (piece 2 of the kernel design). Host-side,
    one-time at build; O(n_lists * d * log n_lists)."""
    centers = np.asarray(centers, np.float64)
    n = centers.shape[0]
    rank = np.empty((n,), np.int32)
    pos = 0

    stack = [np.arange(n)]
    out = []
    while stack:
        idx = stack.pop()
        if len(idx) <= leaf:
            out.append(idx)
            continue
        x = centers[idx]
        x = x - x.mean(axis=0)
        # principal direction of the small [len, d] block via the d x d gram
        cov = x.T @ x
        # power iteration: cheap + deterministic, avoids full eigh cost
        v = np.ones((cov.shape[0],)) / np.sqrt(cov.shape[0])
        for _ in range(16):
            v = cov @ v
            v = v / max(np.linalg.norm(v), 1e-30)
        proj = x @ v
        order = np.argsort(proj, kind="stable")
        half = len(idx) // 2
        # push right first so left pops first -> in-order traversal
        stack.append(idx[order[half:]])
        stack.append(idx[order[:half]])
    for idx in out:
        rank[idx] = np.arange(pos, pos + len(idx), dtype=np.int32)
        pos += len(idx)
    return rank


# ---------------------------------------------------------------------------
# kernel
# ---------------------------------------------------------------------------


def _extract_topk(cv, ci, k: int):
    """k rounds of (min, first-argmin, mask) over the candidate width.
    All VPU-friendly ops: compare/select/reduce — no gathers, no sorts."""
    cols = lax.broadcasted_iota(jnp.int32, cv.shape, 1)
    big_col = jnp.int32(2**30)
    vs, ids = [], []
    for _ in range(k):
        mv = jnp.min(cv, axis=1, keepdims=True)
        sel = jnp.min(jnp.where(cv == mv, cols, big_col), axis=1, keepdims=True)
        mid = jnp.sum(jnp.where(cols == sel, ci, 0), axis=1, keepdims=True)
        mid = jnp.where(jnp.isinf(mv), -1, mid)
        vs.append(mv)
        ids.append(mid)
        cv = jnp.where(cols == sel, jnp.inf, cv)
    return jnp.concatenate(vs, axis=1), jnp.concatenate(ids, axis=1)


def _seg_compress(score, base, qt: int, m: int, banks: int):
    """Lane-group pre-compression: [qt, m] -> [qt, banks * 128] keeping
    per-(lane, bank) minima over the sublane groups (the PartialReduce
    shape of ``lax.approx_max_k``), group ``g`` assigned to bank
    ``g % banks``. More banks -> fewer collisions between same-lane
    candidates (two true top-k rows of one list collide only when they
    share BOTH lane and bank parity), at linear extract-width cost.
    Tracks only the winning group index per lane — the full [qt, m] slot
    iota never materializes — and reconstructs
    ``slot = base + g * 128 + lane`` at the end."""
    mg = cdiv(m, 128)
    mpad = mg * 128
    if mpad != m:
        score = jnp.pad(score, ((0, 0), (0, mpad - m)), constant_values=jnp.inf)
    lane = lax.broadcasted_iota(jnp.int32, (qt, 128), 1)
    out_v, out_s = [], []
    for b in range(banks):
        groups = list(range(b, mg, banks))
        if not groups:
            continue
        best_v = score[:, groups[0] * 128 : (groups[0] + 1) * 128]
        best_g = jnp.full((qt, 128), groups[0], jnp.int32)
        for g in groups[1:]:
            v = score[:, g * 128 : (g + 1) * 128]
            take = v < best_v
            best_v = jnp.where(take, v, best_v)
            best_g = jnp.where(take, g, best_g)
        out_v.append(best_v)
        out_s.append(jnp.where(jnp.isinf(best_v), -1, base + best_g * 128 + lane))
    return jnp.concatenate(out_v, axis=1), jnp.concatenate(out_s, axis=1)


def _bank_count(merge: str) -> int:
    import re

    m = re.search(r"(\d+)$", merge)
    n = int(m.group(1)) if m else 0
    if merge.startswith("bank"):
        return n or 4
    if merge.startswith("seg"):
        return n or 2
    return 0


def _eff_banks(merge: str, m: int, col_chunk: int) -> int:
    """Banks clamped to the lane-group count of one compress call (a block
    slice narrower than banks*128 fills fewer banks)."""
    mc = col_chunk if col_chunk else m
    return max(1, min(_bank_count(merge), cdiv(mc, 128)))


def _make_kernel(*, k, metric, merge, qt, m, n_steps, precision, extract_every, col_chunk):
    bank_mode = merge.startswith("bank")
    banks = _eff_banks(merge, m, col_chunk) if bank_mode else _bank_count(merge)
    mc = col_chunk if (bank_mode and col_chunk) else m
    n_cc = m // mc

    def score_slice(q, ld_ref, ln_ref, li_ref, lo: int):
        """One [qt, mc] score slice: matmul + prepared epilogue."""
        y = ld_ref[0, lo : lo + mc, :]
        if y.dtype == jnp.bfloat16:
            # bf16 lists ride the native bf16 MXU path with f32 accum
            q = q.astype(jnp.bfloat16)
        else:
            y = y.astype(jnp.float32)  # int8 lists cast per block
        dot = lax.dot_general(
            q,
            y,
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=precision,
        )  # [qt, mc]
        # ln_ref carries the PREPARED epilogue term (see the wrapper):
        # L2 -> norms with +inf folded in for invalid slots, IP -> a
        # 0/+inf penalty, cosine -> precomputed rsqrt norm scales — so
        # validity and normalization cost no extra [qt, m] passes
        ln = ln_ref[0, 0, lo : lo + mc]
        if metric in (DistanceType.L2Expanded, DistanceType.L2SqrtExpanded):
            return ln[None, :] - 2.0 * dot
        if metric == DistanceType.InnerProduct:
            return ln[None, :] - dot
        # CosineExpanded; queries pre-normalized by the wrapper
        return jnp.where(
            (li_ref[0, 0, lo : lo + mc] >= 0)[None, :], -dot * ln[None, :], jnp.inf
        )

    def kernel(pr_ref, pv_ref, q_ref, ld_ref, ln_ref, li_ref, outv_ref, outi_ref,
               accv, acci, bankv=None, banki=None):
        i = pl.program_id(0)
        j = pl.program_id(1)

        @pl.when(j == 0)
        def _():
            accv[...] = jnp.full((qt, k), jnp.inf, jnp.float32)
            acci[...] = jnp.full((qt, k), -1, jnp.int32)
            if bank_mode:
                bankv[...] = jnp.full((qt, banks * 128), jnp.inf, jnp.float32)
                banki[...] = jnp.full((qt, banks * 128), -1, jnp.int32)

        @pl.when(pv_ref[i, j] > 0)
        def _():
            q = q_ref[...]
            base = pr_ref[i, j] * m
            if bank_mode:
                # compress each column slice, min-merge into the bank buffer
                for cc in range(n_cc):
                    score = score_slice(q, ld_ref, ln_ref, li_ref, cc * mc)
                    if merge.startswith("bankraw"):  # perf probe: no compress
                        bankv[...] = score[:, : banks * 128]
                        banki[...] = jnp.full((qt, banks * 128), 1, jnp.int32)
                        continue
                    v, s = _seg_compress(score, base + cc * mc, qt, mc, banks)
                    if merge.startswith("bankover"):  # perf probe: no min-merge
                        bankv[...] = v
                        banki[...] = s
                    else:
                        take = v < bankv[...]
                        bankv[...] = jnp.where(take, v, bankv[...])
                        banki[...] = jnp.where(take, s, banki[...])
            else:
                score = score_slice(q, ld_ref, ln_ref, li_ref, 0)
                if merge.startswith("seg"):
                    score, slot = _seg_compress(score, base, qt, m, banks)
                else:
                    valid = jnp.isfinite(score)
                    slot = jnp.where(
                        valid, base + lax.broadcasted_iota(jnp.int32, (qt, m), 1), -1
                    )
                cv = jnp.concatenate([accv[...], score], axis=1)
                ci = jnp.concatenate([acci[...], slot], axis=1)
                nv, ni = _extract_topk(cv, ci, k)
                accv[...] = nv
                acci[...] = ni

        if bank_mode:
            # periodic + final extraction of the bank buffer into the top-k
            # accumulator; resetting bounds the cross-step collision window
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


@functools.partial(
    jax.jit,
    static_argnames=(
        "k", "metric", "qt", "merge", "precision", "extract_every", "col_chunk", "interpret"
    ),
)
def fused_list_topk(
    list_data,
    list_norms,
    list_indices,
    queries_sorted,
    tile_probes,
    probe_valid,
    *,
    k: int,
    metric: DistanceType,
    qt: int,
    merge: str = "seg",
    precision: str = "highest",
    extract_every: int = 0,
    col_chunk: int = 0,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """Run the fused probed-list scan.

    ``queries_sorted [nq_pad, d]`` (nq_pad % qt == 0, f32, tile-coherent
    order), ``tile_probes/probe_valid [nq_pad//qt, P]`` int32. Returns
    ``(scores [nq_pad, k] asc, slots [nq_pad, k])`` where slot =
    ``list_id * max_list + row`` (or -1).
    """
    n_lists, m, d = list_data.shape
    nq_pad = queries_sorted.shape[0]
    n_qt, n_steps = tile_probes.shape
    assert nq_pad == n_qt * qt
    if col_chunk:
        expects(merge.startswith("bank"), "col_chunk requires bank merge")
        expects(m % col_chunk == 0, "col_chunk %d must divide block rows %d", col_chunk, m)

    prec = dict(
        highest=lax.Precision.HIGHEST,
        default=lax.Precision.DEFAULT,
    )[precision]
    kernel = _make_kernel(
        k=k, metric=metric, merge=merge, qt=qt, m=m, n_steps=n_steps, precision=prec,
        extract_every=extract_every, col_chunk=col_chunk,
    )
    scratch_shapes = [
        pltpu.VMEM((qt, k), jnp.float32),
        pltpu.VMEM((qt, k), jnp.int32),
    ]
    if merge.startswith("bank"):
        w = _eff_banks(merge, m, col_chunk) * 128
        scratch_shapes += [
            pltpu.VMEM((qt, w), jnp.float32),
            pltpu.VMEM((qt, w), jnp.int32),
        ]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n_qt, n_steps),
        in_specs=[
            pl.BlockSpec((qt, d), lambda i, j, pr, pv: (i, 0)),
            pl.BlockSpec((1, m, d), lambda i, j, pr, pv: (pr[i, j], 0, 0)),
            pl.BlockSpec((1, 1, m), lambda i, j, pr, pv: (pr[i, j], 0, 0)),
            pl.BlockSpec((1, 1, m), lambda i, j, pr, pv: (pr[i, j], 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((qt, k), lambda i, j, pr, pv: (i, 0)),
            pl.BlockSpec((qt, k), lambda i, j, pr, pv: (i, 0)),
        ],
        scratch_shapes=scratch_shapes,
    )
    # prepare the per-slot epilogue term the kernel folds into the matmul
    # output (one pass here instead of one per (tile, probe) step inside):
    # L2 -> norm with +inf on invalid slots; IP -> 0/+inf penalty;
    # cosine -> rsqrt norm scale (validity handled via list_indices inside)
    valid = list_indices >= 0
    if metric in (DistanceType.L2Expanded, DistanceType.L2SqrtExpanded):
        raw = list_norms if list_norms is not None else jnp.zeros((n_lists, m), jnp.float32)
        ln = jnp.where(valid, raw, jnp.inf)
    elif metric == DistanceType.InnerProduct:
        ln = jnp.where(valid, 0.0, jnp.inf).astype(jnp.float32)
    else:
        raw = list_norms if list_norms is not None else jnp.zeros((n_lists, m), jnp.float32)
        ln = lax.rsqrt(jnp.maximum(raw, 1e-24))
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
        name="fused_list_topk",
    )(
        tile_probes,
        probe_valid,
        queries_sorted.astype(jnp.float32),
        list_data,
        ln[:, None, :],
        list_indices[:, None, :],
    )


# ---------------------------------------------------------------------------
# shared probe-table construction (used by the IVF-Flat and IVF-PQ wrappers)
# ---------------------------------------------------------------------------


def build_tile_probe_tables(
    coarse, probed, center_rank, *, nq: int, qt: int, n_lists: int,
    group: int, n_probes: int, probe_factor: int
):
    """Tile-coherent query ordering + per-tile union probe tables.

    ``coarse [nq, n_lists]`` coarse scores (smaller = closer),
    ``probed [nq, n_lists]`` bool. Returns ``(order_pad [nq_pad],
    tile_probes [n_qt, P], probe_valid [n_qt, P])`` where probe units are
    ``group`` adjacent lists and invalid slots re-address the row's last
    valid unit (DMA-friendly ascending order)."""
    top1 = jnp.argmin(coarse, axis=1)
    order = jnp.argsort(center_rank[top1], stable=True).astype(jnp.int32)

    n_qt = cdiv(nq, qt)
    nq_pad = n_qt * qt
    order_pad = jnp.concatenate(
        [order, jnp.broadcast_to(order[:1], (nq_pad - nq,))]
    ) if nq_pad != nq else order
    row_real = (jnp.arange(nq_pad) < nq)[:, None]
    probed_sorted = probed[order_pad] & row_real

    expects(n_lists % group == 0, "n_lists %d not divisible by group %d", n_lists, group)
    n_units = n_lists // group
    probed_u = probed_sorted.reshape(nq_pad, n_units, group).any(axis=2)
    p = min(n_units, max(cdiv(probe_factor * n_probes, group), cdiv(n_probes, group)))
    counts = jnp.sum(probed_u.reshape(n_qt, qt, n_units).astype(jnp.int32), axis=1)
    cvals, tile_probes = lax.top_k(counts, p)
    probe_valid = (cvals > 0).astype(jnp.int32)
    # Ascending probe order per tile: the DMA engine pipelines far better
    # over monotonically increasing block indices (measured ~30% on v5e).
    # Invalid slots get the row's last valid id so their (skipped) steps
    # re-address an already-resident block instead of fetching a new one.
    sort_key = jnp.where(probe_valid > 0, tile_probes, n_units)
    probe_order = jnp.argsort(sort_key, axis=1)
    tile_probes = jnp.take_along_axis(tile_probes, probe_order, axis=1)
    probe_valid = jnp.take_along_axis(probe_valid, probe_order, axis=1)
    last_valid = jnp.max(jnp.where(probe_valid > 0, tile_probes, 0), axis=1, keepdims=True)
    tile_probes = jnp.where(probe_valid > 0, tile_probes, last_valid).astype(jnp.int32)
    return order_pad, tile_probes, probe_valid


# ---------------------------------------------------------------------------
# full search wrapper
# ---------------------------------------------------------------------------


@functools.partial(
    jax.jit,
    static_argnames=(
        "k",
        "n_probes",
        "metric",
        "qt",
        "probe_factor",
        "group",
        "has_filter",
        "merge",
        "precision",
        "extract_every",
        "col_chunk",
        "interpret",
    ),
)
def ivf_flat_fused_search(
    centers,
    center_rank,
    list_data,
    list_indices,
    list_norms,
    queries,
    filter_bits,
    *,
    k: int,
    n_probes: int,
    metric: DistanceType,
    qt: int = 64,
    probe_factor: int = 4,
    group: int = 1,
    has_filter: bool = False,
    merge: str = "seg",
    precision: str = "highest",
    extract_every: int = 0,
    col_chunk: int = 0,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """IVF-Flat search through the Pallas fused scan. Same candidate-set
    semantics as the probe path whenever each tile's probe union fits the
    ``probe_factor * n_probes`` table (extra tile-mates only add exactly
    scored candidates); distances/post-processing match
    :func:`raft_tpu.neighbors.ivf_flat.flat_scan_core`.

    ``group``: DMA unit in lists. Lists are stored in spatial order (build
    reorders them by PCA-bisection rank), so ``group`` adjacent lists form
    one probe-table entry and one ``[group * max_list, d]`` DMA block —
    bigger streams for the DMA engine and ``group``x the list coverage per
    table slot, at the cost of scoring a probed group's spatial neighbors
    too (usually probed anyway). Requires ``n_lists % group == 0``."""
    nq, d = queries.shape
    n_lists, m, _ = list_data.shape
    qf = queries.astype(jnp.float32)
    if metric == DistanceType.CosineExpanded:
        qf = qf / jnp.maximum(jnp.linalg.norm(qf, axis=1, keepdims=True), 1e-12)

    # ---- coarse scores, per-query probes, tile-coherent ordering ---------
    from raft_tpu.neighbors.ivf_common import probe_selection

    coarse, probed = probe_selection(centers, qf, n_probes, metric)
    order_pad, tile_probes, probe_valid = build_tile_probe_tables(
        coarse, probed, center_rank, nq=nq, qt=qt, n_lists=n_lists,
        group=group, n_probes=n_probes, probe_factor=probe_factor,
    )
    nq_pad = order_pad.shape[0]
    qs = qf[order_pad]

    # ---- prefilter folds into the per-slot validity ----------------------
    li_eff = list_indices
    if has_filter:
        ids = jnp.clip(list_indices, 0, None)
        word = filter_bits[ids // 32]
        bit = (word >> (ids % 32).astype(jnp.uint32)) & 1
        li_eff = jnp.where((bit == 1) & (list_indices >= 0), list_indices, -1)

    # The DMA/scoring unit is `group` adjacent lists: reshaping keeps the
    # flat slot order, so slots map straight back to list_indices.
    n_units = n_lists // group
    gm = group * m
    if col_chunk and not merge.startswith("bank"):
        col_chunk = 0  # chunked scoring only exists for the bank merge
    if col_chunk:
        # round down to a divisor of the block rows (0 disables chunking)
        cc = min(col_chunk, gm)
        while gm % cc:
            cc -= 1
        col_chunk = 0 if cc >= gm else cc
    vals, slots = fused_list_topk(
        list_data.reshape(n_units, gm, d),
        list_norms.reshape(n_units, gm) if list_norms is not None else None,
        li_eff.reshape(n_units, gm),
        qs,
        tile_probes,
        probe_valid,
        k=k,
        metric=metric,
        qt=qt,
        merge=merge,
        precision=precision,
        extract_every=extract_every,
        col_chunk=col_chunk,
        interpret=interpret,
    )

    # ---- postprocess (mirrors flat_scan_core's tail) ---------------------
    flat_ids = list_indices.reshape(-1)
    idx = jnp.where(slots >= 0, flat_ids[jnp.clip(slots, 0, None)], -1)
    if metric == DistanceType.InnerProduct:
        out = -vals
    elif metric == DistanceType.CosineExpanded:
        out = 1.0 + vals
        out = jnp.where(idx >= 0, out, jnp.inf)
    else:
        qn = jnp.sum(qs * qs, axis=1)
        out = jnp.maximum(qn[:, None] + vals, 0.0)
        if metric == DistanceType.L2SqrtExpanded:
            out = jnp.sqrt(out)
        out = jnp.where(idx >= 0, out, jnp.inf)

    # ---- unsort ----------------------------------------------------------
    order = order_pad[:nq]
    dist = jnp.zeros((nq, k), jnp.float32).at[order].set(out[:nq])
    ind = jnp.full((nq, k), -1, jnp.int32).at[order].set(idx[:nq])
    return dist, ind
