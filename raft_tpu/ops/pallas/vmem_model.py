"""VMEM residency model for the fused Pallas scan kernels.

The reference ships correctness tooling alongside its kernels
(compile-time template checks, sanitizer CI); this module is the TPU
analog for the resource axis: a byte-accurate model of what one grid
step of a fused scan keeps live in VMEM, used three ways —

* :mod:`raft_tpu.ops.pallas.pq_scan` derives its decode-chunk budget
  from the model's fixed residents instead of a hand-calibrated
  constant, so scratch-shape drift moves the cap instead of silently
  reintroducing Mosaic compile failures;
* tests assert the model against the kernel's actual scratch/BlockSpec
  shapes and against the scoped allocation the TPU compiler reports
  for the fused PQ scan at two shapes;
* ``tools/graft_lint`` cross-checks the shapes it parses out of the
  kernel source against the same accounting.

Accounting rules (see ``docs/static_analysis.md`` for the rationale):

* every in/out tile contributes ``block_bytes x buffers`` where
  ``buffers = 2`` when the tile's block index varies along the
  *innermost* grid axis (the DMA pipeline double-buffers it) and 1 when
  it only changes at outer-axis boundaries, where the pipeline drains
  anyway;
* scratch buffers contribute their full size once — they persist across
  the whole grid;
* kernel-body intermediates contribute their peak: for the PQ scan
  the ``[qt, m]`` f32 dot accumulator, and, planned as a reserve on
  top, one decode column chunk's f32 byte-spread temps
  (:func:`decode_cell_bytes`).

Sub-(8, 128) tiles are modeled at logical size, except in the PQ scan's
model, which counts every buffer at its physical (sublane, 128-lane)
size: its narrow code rows (64 B of a 128-lane row) and k-wide
accumulators pad by a few MiB at the benchmark shapes.
"""
from __future__ import annotations

import dataclasses
import math
import re
from typing import Optional, Tuple

#: Mosaic's default scoped-VMEM limit on v4/v5e: 16 MiB. This is the
#: default scope, not the chip's VMEM (v5e has 128 MiB): Mosaic rejects
#: a kernel whose scoped allocation exceeds its limit, and a kernel may
#: raise its own through ``pltpu.CompilerParams(vmem_limit_bytes=...)``.
VMEM_LIMIT_BYTES = 16 * 1024 * 1024

#: The scoped limit the fused PQ scan compiles under: one list's decoded
#: multi-hot and the top-k banks of every query sub-tile that shares it
#: stay resident across the grid. Half of v5e's 128 MiB.
PQ_SCAN_VMEM_LIMIT_BYTES = 64 * 1024 * 1024

#: Fraction of VMEM the model lets a kernel plan for. The remainder
#: absorbs what the model cannot see: Mosaic spill slots, semaphores,
#: and compiler-scheduled copies. 0.75 reproduces (within 2%) the 8 MB
#: decode budget that was hand-calibrated against the 1M-row bench
#: shape before this model existed.
VMEM_HEADROOM = 0.75


def code_groups(code_mode: str, ksub: int, bpr: int) -> Tuple[int, int]:
    """(n_groups, gw): the PQ multi-hot column space is ``n_groups``
    groups of ``gw`` columns — one group per stored byte for u8/nib8/p4,
    one per CODE for the spanning b3/b5/b6/b7 bit layouts."""
    if code_mode in ("b3", "b5", "b6", "b7"):
        b = int(code_mode[1:])
        return bpr * 8 // b, ksub
    return bpr, (ksub if code_mode == "u8" else 32)


def decode_lanes(code_mode: str, ksub: int, bpr: int) -> int:
    """Column step of a PQ decode chunk: whole code groups AND whole
    128-lane tiles, so chunk stores and the LUT's column slices stay
    aligned."""
    return math.lcm(code_groups(code_mode, ksub, bpr)[1], 128)


def padded_cols(code_mode: str, ksub: int, bpr: int) -> int:
    """The multi-hot's column count rounded up to :func:`decode_lanes`
    (the LUT is zero-padded to match)."""
    n_groups, gw = code_groups(code_mode, ksub, bpr)
    step = decode_lanes(code_mode, ksub, bpr)
    return -(-n_groups * gw // step) * step


def decode_cell_bytes(code_mode: str) -> int:
    """Peak live bytes per (row, column) of one PQ decode chunk. u8/
    nib8/p4 hold the f32 byte-spread + the bf16 multi-hot (~6 B); the
    spanning bit layouts keep TWO f32 byte-spreads (low/high byte) plus
    f32 peel temps live at once (~14 B)."""
    return 14 if code_mode.startswith("b") and code_mode[1:].isdigit() else 6


def merge_banks(merge: str, m: int) -> int:
    """Bank count of the running top-k scratch for a ``bank<N>`` merge
    mode, clamped to the lane-group count of one compress call (mirrors
    ``ivf_scan._eff_banks`` at col_chunk=0)."""
    g = re.search(r"(\d+)$", merge)
    n = int(g.group(1)) if g else 0
    if merge.startswith("bank"):
        n = n or 4
    elif merge.startswith("seg"):
        n = n or 2
    return max(1, min(n, math.ceil(m / 128)))


@dataclasses.dataclass(frozen=True)
class Resident:
    """One VMEM-resident buffer of a kernel grid step.

    ``kind`` is ``"tile"`` (BlockSpec in/out block), ``"scratch"``
    (``pltpu.VMEM`` scratch), ``"body"`` (peak kernel-body
    intermediate), or ``"chunk"`` (the sizeable *scalable* body
    intermediate the budget is solved for). ``tiled`` counts the
    buffer at its physical (sublane, 128-lane) layout, as Mosaic
    allocates it."""

    name: str
    shape: Tuple[int, ...]
    itemsize: int
    buffers: int = 1
    kind: str = "tile"
    tiled: bool = False

    @property
    def nbytes(self) -> int:
        dims = list(self.shape)
        if self.tiled:
            dims[-1] = -(-dims[-1] // 128) * 128
            sublanes = max(8, 32 // self.itemsize)
            if len(dims) >= 2 and dims[-2] > 1:
                dims[-2] = -(-dims[-2] // sublanes) * sublanes
        return int(math.prod(dims)) * self.itemsize * self.buffers


@dataclasses.dataclass(frozen=True)
class KernelResidency:
    """The model's full accounting for one kernel configuration."""

    kernel: str
    residents: Tuple[Resident, ...]

    @property
    def total_bytes(self) -> int:
        return sum(r.nbytes for r in self.residents)

    @property
    def fixed_bytes(self) -> int:
        """Bytes that do not scale with the decode chunk size."""
        return sum(r.nbytes for r in self.residents if r.kind != "chunk")

    def by_name(self, name: str) -> Resident:
        for r in self.residents:
            if r.name == name:
                return r
        raise KeyError(name)

    def table(self) -> str:
        rows = [
            "%-14s %-18s x%d %10d B  [%s]"
            % (r.name, "x".join(map(str, r.shape)), r.buffers, r.nbytes, r.kind)
            for r in self.residents
        ]
        rows.append("total: %d B (%.2f MiB)" % (self.total_bytes, self.total_bytes / 2**20))
        return "\n".join(rows)


def pq_scan_residency(
    *,
    m: int,
    code_mode: str,
    ksub: int,
    bpr: int,
    qt: int = 128,
    k: int = 128,
    g_lists: int = 8,
    rot_dim: int = 128,
    merge: str = "bank8",
    decode_cols: int = 0,
    n_sub: int = 1,
) -> KernelResidency:
    """Model ``pq_scan.fused_pq_topk``'s VMEM residency for one grid
    step. Mirrors the kernel's grid spec exactly — the shapes here are
    asserted against the literal BlockSpec/scratch declarations in
    tests (``test_pq_fused.py``), so the two cannot drift apart
    silently.

    ``n_sub`` query sub-tiles of ``qt`` rows share each decoded list:
    their LUT rows, queries, outputs, accumulators and banks are
    resident together, beside the one-list multi-hot scratch.
    ``decode_cols=0`` omits the decode chunk (useful for computing the
    fixed residents the chunk budget is solved against); defaults for
    ``qt``/``k``/``g_lists``/``merge`` match ``IvfPqSearchParams``
    (``k=128`` is a conservative stand-in when the caller's k is
    unknown — the k-sized residents are <3% of the stack)."""
    Kp = padded_cols(code_mode, ksub, bpr)
    gm = g_lists * m
    rows = n_sub * qt
    banks = merge_banks(merge, m)
    T = dict(tiled=True)  # the narrow code rows and k columns pad lanes
    residents = [
        # in tiles, in fused_pq_topk's in_specs order. Index maps that
        # reference the inner grid axis j (the probe step) are
        # double-buffered by the DMA pipeline; w/q_rot/outs only move
        # with the outer (query) axis.
        Resident("w_rows", (rows, Kp), 2, **T),              # bf16 LUT rows
        Resident("q_rot", (rows, rot_dim), 4, **T),
        Resident("centers_rot", (1, g_lists, rot_dim), 4, buffers=2, **T),
        Resident("codes", (1, gm, bpr), 1, buffers=2, **T),
        Resident("ln", (1, 1, gm), 4, buffers=2, **T),
        Resident("out_vals", (rows, k), 4, **T),
        Resident("out_idx", (rows, k), 4, **T),
        # scratch_shapes, in declaration order
        Resident("acc_vals", (rows, k), 4, kind="scratch", **T),
        Resident("acc_idx", (rows, k), 4, kind="scratch", **T),
        Resident("bank_vals", (rows, banks * 128), 4, kind="scratch", **T),
        Resident("bank_idx", (rows, banks * 128), 4, kind="scratch", **T),
        Resident("multi_hot", (m, Kp), 2, kind="scratch", **T),
        # peak kernel-body intermediates
        Resident("dot_acc", (qt, m), 4, kind="body", **T),
    ]
    if decode_cols:
        # the chunk's byte-spread temps stream into the multi-hot scratch
        # and do not show in the scoped allocation the compiler reports
        # (equal at 512 and 2048 columns); planned all the same, as the
        # reserve that keeps a chunk's peak off the limit
        residents.append(
            Resident(
                "decode_chunk", (m, decode_cols), decode_cell_bytes(code_mode),
                kind="chunk",
            )
        )
    return KernelResidency("pq_scan.fused_pq_topk", tuple(residents))


def pq_decode_chunk_budget(
    *,
    m: int,
    code_mode: str,
    ksub: int,
    bpr: int,
    qt: int = 128,
    k: int = 128,
    g_lists: int = 8,
    rot_dim: int = 128,
    merge: str = "bank8",
    n_sub: int = 1,
    limit: int = PQ_SCAN_VMEM_LIMIT_BYTES,
    headroom: float = VMEM_HEADROOM,
) -> int:
    """Bytes one PQ decode chunk may occupy: ``headroom x limit`` minus
    the kernel's fixed residents at this shape, the scoped limit being
    the one the kernel compiles under. Replaces the historical
    hand-calibrated 8 MB ``_DECODE_CHUNK_BUDGET``: the budget shrinks
    for longer lists, wider code rows and more sub-tiles a decode, whose
    fixed residents (multi-hot scratch, banks, code DMA buffers) grow.
    May be <= 0: no chunk fits, the shape is fused-infeasible."""
    fixed = pq_scan_residency(
        m=m, code_mode=code_mode, ksub=ksub, bpr=bpr, qt=qt, k=k,
        g_lists=g_lists, rot_dim=rot_dim, merge=merge, decode_cols=0,
        n_sub=n_sub,
    ).fixed_bytes
    return int(limit * headroom) - fixed


def pq_scan_subtiles(n_qt: int, *, m: int, code_mode: str, ksub: int,
                     bpr: int, **model_kwargs) -> int:
    """How many of a call's ``n_qt`` query sub-tiles share one decoded
    list in ``fused_pq_topk``: the whole call when its residents leave
    room for one lane-aligned decode chunk, else the largest divisor of
    ``n_qt`` that does (1, the one-tile schedule, at worst)."""
    need = decode_cell_bytes(code_mode) * m * decode_lanes(code_mode, ksub, bpr)
    for n in range(n_qt, 1, -1):
        if n_qt % n == 0 and pq_decode_chunk_budget(
            m=m, code_mode=code_mode, ksub=ksub, bpr=bpr, n_sub=n, **model_kwargs
        ) >= need:
            return n
    return 1


#: Peak live bytes per (row, rot_dim-column) cell of one RaBitQ decode
#: chunk: the f32 byte-spread lanes, the f32 shift temp, and the f32
#: sign-bit plane live at once (3 x 4 B).
RABITQ_DECODE_CELL_BYTES = 12


def rabitq_scan_residency(
    *,
    m: int,
    bpr: int,
    qt: int = 128,
    k: int = 128,
    g_lists: int = 8,
    rot_dim: int = 128,
    merge: str = "bank8",
    decode_rows: int = 0,
) -> KernelResidency:
    """Model ``rabitq_scan.fused_rabitq_topk``'s VMEM residency for one
    grid step. Same accounting discipline as :func:`pq_scan_residency`
    (tests assert these shapes against the kernel's literal BlockSpec /
    scratch declarations); the LUT tile is replaced by the per-slot
    correction channel, and the scalable body intermediate is a ROW
    chunk of unpacked sign bits (``[rows, rot_dim]`` f32 planes,
    :data:`RABITQ_DECODE_CELL_BYTES`/cell) — the bit-dot accumulates
    into the same full ``[qt, m]`` body buffer pq_scan keeps.

    ``decode_rows=0`` omits the chunk (for computing the fixed
    residents the row budget is solved against)."""
    gm = g_lists * m
    banks = merge_banks(merge, m)
    residents = [
        # in tiles, in fused_rabitq_topk's in_specs order
        Resident("q_rot", (qt, rot_dim), 4),
        Resident("centers_rot", (1, g_lists, rot_dim), 4, buffers=2),
        Resident("codes", (1, gm, bpr), 1, buffers=2),
        Resident("ln", (1, 1, gm), 4, buffers=2),
        Resident("corr", (1, 1, gm), 4, buffers=2),
        Resident("out_vals", (qt, k), 4),
        Resident("out_idx", (qt, k), 4),
        # scratch_shapes, in declaration order
        Resident("acc_vals", (qt, k), 4, kind="scratch"),
        Resident("acc_idx", (qt, k), 4, kind="scratch"),
        Resident("bank_vals", (qt, banks * 128), 4, kind="scratch"),
        Resident("bank_idx", (qt, banks * 128), 4, kind="scratch"),
        # peak non-chunk body intermediates: the bit-dot accumulator, the
        # per-step coarse q.c tile, and the [bpr, rot_dim] byte-spread
        Resident("dot_acc", (qt, m), 4, kind="body"),
        Resident("qdc", (qt, g_lists), 4, kind="body"),
        Resident("spread", (bpr, rot_dim), 4, kind="body"),
    ]
    if decode_rows:
        residents.append(
            Resident(
                "decode_chunk", (decode_rows, rot_dim), RABITQ_DECODE_CELL_BYTES,
                kind="chunk",
            )
        )
    return KernelResidency("rabitq_scan.fused_rabitq_topk", tuple(residents))


def rabitq_decode_rows_budget(
    *,
    m: int,
    bpr: int,
    qt: int = 128,
    k: int = 128,
    g_lists: int = 8,
    rot_dim: int = 128,
    merge: str = "bank8",
    limit: int = VMEM_LIMIT_BYTES,
    headroom: float = VMEM_HEADROOM,
) -> int:
    """Bytes one RaBitQ decode row-chunk may occupy: ``headroom x
    limit`` minus the kernel's fixed residents at this shape. Per row
    the chunk costs ``RABITQ_DECODE_CELL_BYTES * rot_dim`` bytes of
    sign-bit planes; may be <= 0 when the shape is fused-infeasible."""
    fixed = rabitq_scan_residency(
        m=m, bpr=bpr, qt=qt, k=k, g_lists=g_lists, rot_dim=rot_dim,
        merge=merge, decode_rows=0,
    ).fixed_bytes
    return int(limit * headroom) - fixed


def cagra_search_residency(
    *,
    itopk: int = 160,
    width: int = 8,
    deg: int = 16,
    d: int = 128,
    qt: int = 32,
    table_itemsize: int = 2,
) -> KernelResidency:
    """Model ``cagra_search._beam_kernel``'s residency for one grid
    step (one ``qt``-query tile; the grid is 1-D over query tiles, so
    every tile moves with the innermost axis and double-buffers).
    Defaults are the 1M-row bench shape (itopk<=160, width 8, the
    bf16 packed table). The SMEM parent-id staging buffer
    (``[qt, width]`` i32) is not VMEM and is excluded.

    ``table_itemsize`` follows ``CagraSearchParams.fused_table_dtype``
    (2 = bf16 default, 4 = the float32 parity table)."""
    m = itopk + width * deg
    residents = [
        # in tiles (queries, init beam) + out tiles (final beam); the
        # out tiles double as the across-iteration beam state
        Resident("q_tile", (qt, d), 4, buffers=2),
        Resident("init_v", (qt, itopk), 4, buffers=2),
        Resident("init_idf", (qt, itopk), 4, buffers=2),
        Resident("out_v", (qt, itopk), 4, buffers=2),
        Resident("out_idf", (qt, itopk), 4, buffers=2),
        # scratch_shapes, in declaration order (table stays in HBM/ANY
        # and is streamed by explicit per-parent DMAs into nbr)
        Resident("nbr", (qt, width, -(-(deg + 3) // 8) * 8, d), table_itemsize,
                 kind="scratch"),
        Resident("parents", (qt, width), 4, kind="scratch"),
        Resident("cand_v", (qt, width * deg), 4, kind="scratch"),
        Resident("cand_id", (qt, width * deg), 4, kind="scratch"),
        # peak kernel-body intermediates: one pairwise rank/placement
        # column chunk (two i32 [qt, m, chunk] temps live at the peak)
        # and one parent block's f32 score diff
        Resident("rank_chunk", (qt, m, 64), 4, buffers=2, kind="body"),
        Resident("score_blk", (width * deg, d), 4, kind="body"),
    ]
    return KernelResidency("cagra_search._beam_kernel", tuple(residents))


def ring_topk_residency(
    *,
    n: int,
    B: int,
    w: int,
    fold_rows: int = 32,
    rank_chunk: int = 64,
) -> KernelResidency:
    """Model ``ring_topk._ring_kernel``'s residency. The kernel has no
    grid — the whole prepped candidate set ([n*B, w] per lane) sits in
    VMEM — so every in/out ref is single-buffered; the ring state
    ([n, B, w] per lane) plus the double-buffered send/recv DMA slots
    ([2, B, w] per lane, padded to whole 128-lane tiles for the DMA) are
    scratch, asserted against
    ``ring_topk.kernel_scratch_shapes`` (DMA semaphores are not VMEM and
    are excluded); the body peak is one (key, pos) pairwise-rank chunk
    of the fold (two i32 ``[fold_rows, 2w, rank_chunk]`` temps live).

    ``n`` = ring size (devices), ``B`` = query-block rows per hop,
    ``w`` = merge width (k). At the serving shape (n=8, B=128, w=128)
    the total is ~5.6 MiB — comfortably inside the 0.75 x 16 MiB plan."""
    residents = [
        # in refs (prepped key/pos/val/id lanes), then out refs
        Resident("in_key", (n * B, w), 4),
        Resident("in_pos", (n * B, w), 4),
        Resident("in_val", (n * B, w), 4),
        Resident("in_id", (n * B, w), 4),
        Resident("out_v", (n * B, w), 4),
        Resident("out_i", (n * B, w), 4),
        # scratch_shapes, in declaration order (= kernel_scratch_shapes)
        Resident("state_key", (n, B, w), 4, kind="scratch"),
        Resident("state_pos", (n, B, w), 4, kind="scratch"),
        Resident("state_val", (n, B, w), 4, kind="scratch"),
        Resident("state_id", (n, B, w), 4, kind="scratch"),
        Resident("send_key", (2, B, -(-w // 128) * 128), 4, kind="scratch"),
        Resident("send_pos", (2, B, -(-w // 128) * 128), 4, kind="scratch"),
        Resident("send_val", (2, B, -(-w // 128) * 128), 4, kind="scratch"),
        Resident("send_id", (2, B, -(-w // 128) * 128), 4, kind="scratch"),
        Resident("recv_key", (2, B, -(-w // 128) * 128), 4, kind="scratch"),
        Resident("recv_pos", (2, B, -(-w // 128) * 128), 4, kind="scratch"),
        Resident("recv_val", (2, B, -(-w // 128) * 128), 4, kind="scratch"),
        Resident("recv_id", (2, B, -(-w // 128) * 128), 4, kind="scratch"),
        # peak body intermediate: less + tie of one rank chunk
        Resident("rank_chunk", (fold_rows, 2 * w, rank_chunk), 4, buffers=2,
                 kind="body"),
    ]
    return KernelResidency("ring_topk._ring_kernel", tuple(residents))


def scan_ring_topk_residency(
    *,
    n: int,
    B: int,
    w: int,
    kc: int,
    fold_rows: int = 32,
    rank_chunk: int = 64,
) -> KernelResidency:
    """Model ``ring_topk._scan_ring_kernel``'s residency — the
    scan-fused ring. Relative to :func:`ring_topk_residency` only the
    four INPUT refs widen to the scan's ``kc``-column candidate tile
    (``kc`` a multiple of ``w``, e.g. ``k·refine_ratio``); the staging
    fold writes straight into the same ring state, so scratch is
    byte-identical (asserted against
    ``ring_topk.scan_kernel_scratch_shapes``) and the body peak is the
    same pairwise-rank chunk — the staging fold and the per-hop fold
    share ``_rank_merge_pos`` at the same ``(fold_rows, 2w)`` union
    shape. At kc = 2k the lint binding (n=8, B=128, w=128, kc=256)
    totals exactly the 12 MiB (75% x 16 MiB) plan; kc = 4k (512, 16
    MiB) breaches it — wider scans must pre-fold toward 2k upstream or
    shrink the query block."""
    residents = [
        # in refs: the full scan candidate tile, kc wide
        Resident("in_key", (n * B, kc), 4),
        Resident("in_pos", (n * B, kc), 4),
        Resident("in_val", (n * B, kc), 4),
        Resident("in_id", (n * B, kc), 4),
        Resident("out_v", (n * B, w), 4),
        Resident("out_i", (n * B, w), 4),
        # scratch_shapes, in declaration order (= scan_kernel_scratch_shapes)
        Resident("state_key", (n, B, w), 4, kind="scratch"),
        Resident("state_pos", (n, B, w), 4, kind="scratch"),
        Resident("state_val", (n, B, w), 4, kind="scratch"),
        Resident("state_id", (n, B, w), 4, kind="scratch"),
        Resident("send_key", (2, B, -(-w // 128) * 128), 4, kind="scratch"),
        Resident("send_pos", (2, B, -(-w // 128) * 128), 4, kind="scratch"),
        Resident("send_val", (2, B, -(-w // 128) * 128), 4, kind="scratch"),
        Resident("send_id", (2, B, -(-w // 128) * 128), 4, kind="scratch"),
        Resident("recv_key", (2, B, -(-w // 128) * 128), 4, kind="scratch"),
        Resident("recv_pos", (2, B, -(-w // 128) * 128), 4, kind="scratch"),
        Resident("recv_val", (2, B, -(-w // 128) * 128), 4, kind="scratch"),
        Resident("recv_id", (2, B, -(-w // 128) * 128), 4, kind="scratch"),
        # peak body intermediate: less + tie of one rank chunk (shared
        # by the staging fold and the per-hop fold)
        Resident("rank_chunk", (fold_rows, 2 * w, rank_chunk), 4, buffers=2,
                 kind="body"),
    ]
    return KernelResidency("ring_topk._scan_ring_kernel", tuple(residents))


def ivf_scan_residency(
    *,
    m: int,
    d: int,
    qt: int = 128,
    k: int = 128,
    merge: str = "bank8",
    itemsize: int = 4,
) -> KernelResidency:
    """Model ``ivf_scan.fused_list_topk``'s residency (col_chunk=0):
    one query tile, one double-buffered list block + prepared epilogue
    and id rows, the top-k accumulator and bank scratch, and the
    ``[qt, m]`` f32 score block."""
    banks = merge_banks(merge, m)
    residents = [
        Resident("q_tile", (qt, d), 4),
        Resident("list_data", (1, m, d), itemsize, buffers=2),
        Resident("ln", (1, 1, m), 4, buffers=2),
        Resident("list_idx", (1, 1, m), 4, buffers=2),
        Resident("out_vals", (qt, k), 4),
        Resident("out_idx", (qt, k), 4),
        Resident("acc_vals", (qt, k), 4, kind="scratch"),
        Resident("acc_idx", (qt, k), 4, kind="scratch"),
        Resident("bank_vals", (qt, banks * 128), 4, kind="scratch"),
        Resident("bank_idx", (qt, banks * 128), 4, kind="scratch"),
        Resident("score", (qt, m), 4, kind="body"),
    ]
    return KernelResidency("ivf_scan.fused_list_topk", tuple(residents))
