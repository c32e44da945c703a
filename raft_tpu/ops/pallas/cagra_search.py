"""Fused Pallas beam search for CAGRA — the TPU analog of the
reference's single-CTA kernel
(``detail/cagra/search_single_cta_kernel-inl.cuh:467``).

The XLA search loop (:mod:`raft_tpu.neighbors.cagra`) round-trips HBM
every iteration: a ``dataset[...]`` gather materializes the candidate
vectors, an einsum scores them, and a full ``select_k`` re-sorts the
beam — three dispatches per hop with no control over data movement.
This kernel keeps the whole traversal on-chip:

* the **beam buffer** — ``itopk`` slots of (distance, packed id|visited
  flag) per query — lives in VMEM across all iterations (the output
  tiles double as the loop state), like the reference's
  shared-memory ``itopk`` list;
* each iteration DMAs the ``search_width`` parents' **packed neighbor
  rows** straight from HBM into a ``[qt, width]``-deep VMEM buffer with
  one async copy per (query, parent) — all copies are issued up front
  and waited per query, so the scoring of query ``q`` overlaps the
  in-flight fetches of queries ``q+1..`` (the deep buffer is the
  multi-buffered pipeline; there is no XLA gather round trip);
* candidates are scored on the VPU as ``sum((q - v)^2)`` — one fused
  subtract/multiply/reduce per parent block, no MXU batching hazards —
  and merged with a **rank-based stable re-sort**: pairwise-comparison
  ranks place every union element into its sorted slot via one-hot
  accumulation, reproducing the XLA path's stable value sort, so the
  ``dedup="post"`` adjacent-id kill applies verbatim (equal ids carry
  bit-identical in-kernel distances, and stable ties keep the
  buffered/visited copy first — the visited *hashmap* of the reference
  stays a visited *flag lane*, ``hashmap.hpp`` analog).

Graph traversal is data-dependent, so the adjacency fetch cannot be a
scalar-prefetch ``index_map`` (those are fixed before the kernel runs,
``ivf_scan.py`` style); instead parent ids are staged VMEM -> SMEM each
iteration and drive guarded ``pltpu.make_async_copy`` slices of the
HBM-resident table.

**Packed neighbor table** (:func:`build_neighbor_table`): per node,
``deg`` neighbor vectors plus 3 id rows — base-256 digits of
``neighbor_id + 1`` in lanes ``0..deg-1`` (0 decodes to the -1 pad) —
giving ``[n, deg + 3, d]``. One contiguous ~5 KB DMA per parent fetches
vectors *and* ids; digits <= 255 are exact in bf16, so ids up to
``2^24 - 2`` survive the narrow dtype. The table costs ``deg x`` the
dataset in HBM (bf16 halves it) — the classic bandwidth-for-latency
trade, bought back by never touching the ``[n, d]`` dataset during
the loop.

VMEM residency is modeled in
:func:`raft_tpu.ops.pallas.vmem_model.cagra_search_residency` and
checked by ``tools/graft_lint`` under the ``cagra_search`` bindings.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from raft_tpu.core.errors import expects
from raft_tpu.utils.math import cdiv

#: id rows appended to each node's vector rows: base-256 digits of
#: ``id + 1`` (lane j of row ``deg + t`` holds digit t of neighbor j).
ID_ROWS = 3

#: Largest node count the packed id encoding supports: three 8-bit
#: digits of ``id + 1``.
MAX_TABLE_IDS = (1 << 24) - 2

#: Finite in-kernel "worst" distance. The rank-merge places elements
#: with masked one-hot sums, and ``inf * 0`` would poison them with
#: NaNs; a finite sentinel keeps every lane arithmetic-safe. Mapped
#: back to the XLA path's ``worst_value`` outside the kernel.
WORST = 3.0e38

#: Scoped-VMEM limit the beam kernel compiles under (see its call).
VMEM_LIMIT_BYTES = 48 * 1024 * 1024

#: Column chunk of the pairwise rank / one-hot placement passes — bounds
#: the [qt, m, chunk] body intermediates to ~1 MiB at the bench shape.
_RANK_CHUNK = 64


def table_rows(deg: int) -> int:
    """Rows per node of the packed table: ``deg`` vectors, the
    ``ID_ROWS`` id rows, then zero rows up to a whole 8-row tile — a DMA
    may only move whole tiles, and HBM pads the rows to them anyway."""
    return -(-(deg + ID_ROWS) // 8) * 8


def table_bytes(n: int, deg: int, d: int, dtype=jnp.bfloat16) -> int:
    """HBM bytes of the packed ``[n, table_rows(deg), d]`` neighbor table."""
    return n * table_rows(deg) * d * jnp.dtype(dtype).itemsize


def build_neighbor_table(dataset, graph, *, dtype=jnp.bfloat16, row_chunk: int = 65536):
    """Pack ``[n, table_rows(deg), d]`` neighbor rows: node ``v``'s rows
    are its ``deg`` neighbors' vectors followed by 3 id-digit rows
    (base-256 of ``id + 1`` in lanes ``0..deg-1``; lane 0-fill decodes
    to -1) and zero rows to the tile boundary.

    The table is filled in place one ``row_chunk`` of nodes at a time, so
    the build holds one table plus one chunk: at SIFT-1M the table alone
    is ~9 GB of a v5e's 16 GB, and chunks concatenated at the end would
    need it twice."""
    n, d = dataset.shape
    deg = graph.shape[1]
    expects(deg <= d, "packed id rows need graph_degree (%d) <= dim (%d)", deg, d)
    expects(n <= MAX_TABLE_IDS, "packed ids support <= %d rows, got %d", MAX_TABLE_IDS, n)
    return _pack_table(jnp.asarray(dataset), jnp.asarray(graph, jnp.int32),
                       dtype=jnp.dtype(dtype), row_chunk=min(row_chunk, n))


@functools.partial(jax.jit, static_argnames=("dtype", "row_chunk"))
def _pack_table(dataset, graph, *, dtype, row_chunk: int):
    n, d = dataset.shape
    deg = graph.shape[1]

    def fill(c, table):
        # the last chunk is clamped to end at row n: it rewrites a few
        # rows of its predecessor with the same values
        start = jnp.minimum(c * row_chunk, n - row_chunk)
        g = lax.dynamic_slice_in_dim(graph, start, row_chunk, 0)
        vecs = dataset[jnp.clip(g, 0, None)].astype(dtype)  # [c, deg, d]
        gp1 = g + 1  # -1 pad -> 0
        digits = jnp.stack([gp1 & 255, (gp1 >> 8) & 255, (gp1 >> 16) & 255], axis=1)
        id_rows = jnp.zeros((row_chunk, table_rows(deg) - deg, d), dtype).at[
            :, :ID_ROWS, :deg].set(digits.astype(dtype))
        block = jnp.concatenate([vecs, id_rows], axis=1)
        return lax.dynamic_update_slice_in_dim(table, block, start, 0)

    table = jnp.zeros((n, table_rows(deg), d), dtype)
    return lax.fori_loop(0, cdiv(n, row_chunk), fill, table)


def _pick_columns(vals, width: int):
    """``width`` rounds of min-extract over ``[qt, itopk]`` (the
    ``pickup_next_parents`` analog): per round, the ``[qt, 1]`` position
    and validity columns, which the kernel consumes one at a time."""
    cols = lax.broadcasted_iota(jnp.int32, vals.shape, 1)
    big = jnp.int32(2**30)
    out = []
    for _ in range(width):
        mv = jnp.min(vals, axis=1, keepdims=True)
        sel = jnp.min(jnp.where(vals == mv, cols, big), axis=1, keepdims=True)
        out.append((sel, mv < WORST))
        vals = jnp.where(cols == sel, WORST, vals)
    return out


def _rank_merge(uv, uidf, itopk: int):
    """Stable value-sorted top-``itopk`` of the union ``[qt, m]`` via
    pairwise ranks + one-hot placement. ``rank(i) = #{j : v_j < v_i or
    (v_j == v_i and j < i)}`` is a permutation of ``0..m-1``; keeping
    ranks ``< itopk`` reproduces the XLA path's stable ``select_k``
    (beam entries precede candidates, so the visited copy of a
    duplicate wins the tie)."""
    qt, m = uv.shape
    jj = lax.broadcasted_iota(jnp.int32, (1, m, 1), 1)
    parts = []
    for i0 in range(0, m, _RANK_CHUNK):
        i1 = min(i0 + _RANK_CHUNK, m)
        vi = jnp.expand_dims(uv[:, i0:i1], 1)  # a None index here lowers to a gather
        ii = lax.broadcasted_iota(jnp.int32, (1, 1, i1 - i0), 2) + i0
        less = (uv[:, :, None] < vi).astype(jnp.int32)
        tie = ((uv[:, :, None] == vi) & (jj < ii)).astype(jnp.int32)
        parts.append(jnp.sum(less + tie, axis=1))
    rank = jnp.concatenate(parts, axis=1)  # [qt, m]
    nv_parts, ni_parts = [], []
    for p0 in range(0, itopk, _RANK_CHUNK):
        p1 = min(p0 + _RANK_CHUNK, itopk)
        pidx = lax.broadcasted_iota(jnp.int32, (1, 1, p1 - p0), 2) + p0
        oh = rank[:, :, None] == pidx  # [qt, m, chunk]
        nv_parts.append(jnp.sum(jnp.where(oh, uv[:, :, None], 0.0), axis=1))
        ni_parts.append(jnp.sum(jnp.where(oh, uidf[:, :, None], 0), axis=1))
    nv = jnp.concatenate(nv_parts, axis=1)
    nidf = jnp.concatenate(ni_parts, axis=1)
    return nv, jnp.where(nv >= WORST, -1, nidf)


def _beam_kernel(
    q_ref, iv_ref, ii_ref, table_ref, ov_ref, oi_ref,
    nbr, pv, cv, ci, ps, semp, semn,
    *, itopk: int, width: int, deg: int, d: int, qt: int, iters: int, ip: bool,
):
    # beam state = the output tiles, VMEM-resident across all iterations
    ov_ref[...] = iv_ref[...]
    oi_ref[...] = ii_ref[...]
    cols = lax.broadcasted_iota(jnp.int32, (qt, itopk), 1)

    def step(_, carry):
        beam_v = ov_ref[...]
        beam_idf = oi_ref[...]
        # -- pick parents: best `width` unvisited, valid slots ------------
        masked = jnp.where(((beam_idf & 1) == 1) | (beam_idf < 0), WORST, beam_v)
        # one parent column at a time: 2-D [qt, itopk] masks only (Mosaic
        # cannot lay out the [qt, width, 1] casts of a 3-D one-hot, nor
        # concatenate mask columns)
        picked = jnp.zeros((qt, itopk), jnp.bool_)
        for w, (ppos, pvalid) in enumerate(_pick_columns(masked, width)):
            ohv = (cols == ppos) & pvalid  # [qt, itopk]
            pidf = jnp.sum(jnp.where(ohv, beam_idf, 0), axis=1, keepdims=True)
            pv[:, w:w + 1] = jnp.where(pvalid, pidf >> 1, -1)  # parent ids, -1 invalid
            picked = picked | ohv
        # mark the picked slots visited before the merge sees them
        oi_ref[...] = jnp.where(picked, beam_idf | 1, beam_idf)

        # -- stage parent ids to SMEM, then issue every DMA up front ------
        stage = pltpu.make_async_copy(pv, ps, semp)
        stage.start()
        stage.wait()

        def issue(j, c):
            qq, ww = j // width, j % width
            pid = ps[qq, ww]

            @pl.when(pid >= 0)
            def _():
                pltpu.make_async_copy(
                    table_ref.at[pid], nbr.at[qq, ww],
                    semn.at[qq, ww],
                ).start()

            return c

        lax.fori_loop(0, qt * width, issue, 0)

        # -- score query q while later queries' fetches are in flight -----
        def score_q(qq, c):
            def waitw(ww, c2):
                pid = ps[qq, ww]

                @pl.when(pid >= 0)
                def _():
                    pltpu.make_async_copy(
                        table_ref.at[pid], nbr.at[qq, ww],
                        semn.at[qq, ww],
                    ).wait()

                return c2

            lax.fori_loop(0, width, waitw, 0)
            qv = q_ref[pl.ds(qq, 1), :]  # [1, d]
            ones = jnp.ones((1, d), jnp.float32)
            # one parent at a time, all 2-D: distances come out as a lane
            # row [1, deg] (a lane reduction would give a column), the
            # sum over d taken by the MXU at full f32 precision
            vrow, irow = [], []
            for w in range(width):
                blk = nbr[qq, w]  # [table_rows, d]: deg vectors + 3 id rows
                vecs = blk[:deg].astype(jnp.float32)  # [deg, d]
                if ip:
                    terms = vecs * qv
                else:
                    diff = vecs - qv
                    terms = diff * diff
                dist = lax.dot_general(
                    ones, terms, (((1,), (1,)), ((), ())),
                    precision=lax.Precision.HIGHEST,
                    preferred_element_type=jnp.float32,
                )  # [1, deg]
                if ip:
                    dist = -dist
                # decode ids: base-256 digit rows, exact in the table dtype
                dg = blk[deg:deg + ID_ROWS].astype(jnp.float32)  # [3, d]
                cid = (dg[0:1] + 256.0 * dg[1:2] + 65536.0 * dg[2:3])[:, :deg]
                cid = cid.astype(jnp.int32) - 1
                # a skipped (invalid-parent) DMA leaves stale lanes: mask them
                cid = jnp.where(ps[qq, w] >= 0, cid, -1)
                vrow.append(jnp.where(cid >= 0, dist, WORST))
                irow.append(cid)
            cv[pl.ds(qq, 1), :] = jnp.concatenate(vrow, axis=1)
            ci[pl.ds(qq, 1), :] = jnp.concatenate(irow, axis=1)
            return c

        lax.fori_loop(0, qt, score_q, 0)

        # -- merge + post-sort adjacent dedup (body_packed semantics) -----
        beam_idf = oi_ref[...]
        uv = jnp.concatenate([ov_ref[...], cv[...]], axis=1)
        uidf = jnp.concatenate([beam_idf, ci[...] * 2], axis=1)
        nv, nidf = _rank_merge(uv, uidf, itopk)
        ids_new = nidf >> 1
        prev = jnp.concatenate(
            [jnp.full((qt, 1), -2, jnp.int32), ids_new[:, :-1]], axis=1
        )
        dup = (ids_new == prev) & (ids_new >= 0)
        ov_ref[...] = jnp.where(dup, WORST, nv)
        oi_ref[...] = jnp.where(dup, -1, nidf)
        return carry

    lax.fori_loop(0, iters, step, 0)


def kernel_scratch_shapes(qt: int, width: int, deg: int, d: int, table_dtype=jnp.bfloat16):
    """The kernel's VMEM scratch declarations, in order — exposed so
    ``vmem_model.cagra_search_residency`` can be asserted against the
    literal shapes (the SMEM staging buffer and DMA semaphores are not
    VMEM and are appended separately at the call site)."""
    return [
        # one [table_rows, d] slot per parent: a DMA may not cut a tile
        pltpu.VMEM((qt, width, table_rows(deg), d), table_dtype),  # nbr rows
        pltpu.VMEM((qt, width), jnp.int32),  # parent ids
        pltpu.VMEM((qt, width * deg), jnp.float32),  # candidate dists
        pltpu.VMEM((qt, width * deg), jnp.int32),  # candidate ids
    ]


@functools.partial(
    jax.jit,
    static_argnames=("deg", "itopk", "width", "iters", "qt", "ip", "interpret"),
)
def cagra_fused_search(
    table,
    queries,
    init_v,
    init_idf,
    *,
    deg: int,
    itopk: int,
    width: int,
    iters: int,
    qt: int = 32,
    ip: bool = False,
    interpret: bool = False,
):
    """Run the fused beam loop. ``queries [nq, d]`` f32, ``init_v``/
    ``init_idf [nq, itopk]`` the seeded beam (min-ordered distances —
    negate for InnerProduct — with :data:`WORST` in empty slots; ids
    packed ``id * 2 + flag``, -1 invalid). Returns the final beam
    ``(values [nq, itopk] f32, packed idf [nq, itopk] i32)``; the caller
    unpacks, runs the final unique-merge and metric epilogue."""
    nq, d = queries.shape
    expects(table.shape[1] == table_rows(deg), "table has %d rows per node, want %d",
            table.shape[1], table_rows(deg))
    nqp = cdiv(nq, qt) * qt
    if nqp != nq:
        pad = nqp - nq
        queries = jnp.pad(queries, ((0, pad), (0, 0)))
        init_v = jnp.pad(init_v, ((0, pad), (0, 0)), constant_values=WORST)
        init_idf = jnp.pad(init_idf, ((0, pad), (0, 0)), constant_values=-1)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=0,
        grid=(nqp // qt,),
        in_specs=[
            pl.BlockSpec((qt, d), lambda i: (i, 0)),
            pl.BlockSpec((qt, itopk), lambda i: (i, 0)),
            pl.BlockSpec((qt, itopk), lambda i: (i, 0)),
            pl.BlockSpec(memory_space=pl.ANY),  # table stays in HBM
        ],
        out_specs=[
            pl.BlockSpec((qt, itopk), lambda i: (i, 0)),
            pl.BlockSpec((qt, itopk), lambda i: (i, 0)),
        ],
        scratch_shapes=[
            *kernel_scratch_shapes(qt, width, deg, d, table.dtype),
            pltpu.SMEM((qt, width), jnp.int32),  # scalar parent ids
            pltpu.SemaphoreType.DMA,
            pltpu.SemaphoreType.DMA((qt, width)),
        ],
    )
    kern = functools.partial(
        _beam_kernel,
        itopk=itopk, width=width, deg=deg, d=d, qt=qt, iters=iters, ip=ip,
    )
    from raft_tpu.ops.pallas._guard import kernel_guard

    with kernel_guard("cagra_fused_search"):
        out_v, out_idf = pl.pallas_call(
            kern,
            grid_spec=grid_spec,
            out_shape=[
                jax.ShapeDtypeStruct((nqp, itopk), jnp.float32),
                jax.ShapeDtypeStruct((nqp, itopk), jnp.int32),
            ],
            # the pairwise-rank merge temporaries of a 32-query tile need
            # ~21 MiB: past the 16 MiB default scope, well inside v5e's
            # 128 MiB of VMEM
            compiler_params=pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT_BYTES),
            interpret=interpret,
        )(queries, init_v, init_idf, table)
    return out_v[:nq], out_idf[:nq]
