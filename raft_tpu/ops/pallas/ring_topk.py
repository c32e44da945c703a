"""ICI ring top-k merge for sharded search — the communication-avoiding
replacement for the ``all_gather`` + k-way merge candidate exchange.

The gather path (``parallel/sharded_ann.py`` / ``sharded_knn.py``) ships
every shard's ``[nq, k]`` candidates to every chip and re-selects over the
``n_shards x k`` concatenation: per-rank wire traffic is
``8k(n-1)`` bytes/query and every chip materializes the full candidate
matrix in HBM. This module runs the same merge as a **ring
reduce-scatter + ring all-gather over query blocks** — the
communication-optimal schedule for an associative reduction:

* queries are split into ``n`` blocks; at reduce-scatter hop ``s`` chip
  ``r`` sends its running partial of block ``(r - s) mod n`` to its right
  neighbor and folds the block arriving from the left into its own
  partial — after ``n - 1`` hops chip ``r`` owns the *finished* top-k of
  block ``(r + 1) mod n``;
* an all-gather ring then replicates the finished blocks (values + ids
  only; the merge tie-break lane is no longer needed).

Per-rank wire is ``~20k(n-1)/n`` bytes/query (12 B/candidate while the
tie-break lane rides along, 8 B after) versus the gather path's
``8k(n-1)``: a ``0.4 n`` reduction — 3.2x at 8 chips — and peak memory
stays ``O(k)`` per query instead of ``O(n k)``.

**Bit-parity contract.** The gather path's merge is a stable
``lax.top_k`` over the shard-major concatenation, i.e. a sort by
``(value, concat position)``. Each candidate here carries its concat
position explicitly — ``pos = rank * k + slot`` (unique, total order) —
and every 2k -> k fold merges by ``(value, pos)``. A merge under a total
order is associative and schedule-independent, so the ring reproduces
the gather ids **bit-exactly** at every device count, hop order, and
degraded-health mask (demoted shards' candidates carry worst-value
sentinels and their true ``pos``, losing every fold exactly as they lose
the gather merge — a dead shard degenerates to a pass-through that
forwards its neighbor's buffer unchanged). Values are carried, never
recomputed, so distances are bit-identical too.

Two engines share that schedule:

* :func:`_ring_topk_xla` — ``lax.ppermute`` hops + a 2-key
  ``lax.sort`` fold. Runs everywhere (this is the engine the 8-device
  CPU test mesh exercises for parity) and is what ``merge_mode="ring"``
  means off-TPU.
* :func:`fused_ring_topk` — a Pallas kernel holding the per-block
  partials in VMEM and driving each hop with
  ``pltpu.make_async_remote_copy`` into the right neighbor's scratch,
  double-buffered send/recv slots with deferred send-semaphore waits so
  hop ``s``'s outgoing DMA drains while the hop-``s`` fold runs on the
  VPU. TPU-only: jax 0.4.x cannot interpret remote DMAs on CPU, so the
  dispatch gates on the real backend and the fold kernel is covered by
  an interpret-mode parity test instead
  (:func:`hop_merge` — the same rank-based placement proven bit-exact
  in ``cagra_search._rank_merge``, extended with the ``pos`` tie lane).

:func:`scan_ring_topk` (``merge_mode="fused_ring"``) is the scan-fused
variant of the same schedule: it takes the scan's full ``[nq,
k·refine_ratio]`` candidate tile and folds it to the merge width INSIDE
the ring engine (``_scan_ring_kernel`` stages the fold's winners
directly into the ring's VMEM state; the XLA mirror ``_scan_fold``
consumes the tile slice-wise), so the per-shard top-k never
round-trips through HBM between the scan and the exchange. Wire bytes
are unchanged vs ``ring_topk`` — only winners ride the ring.

Failure semantics: :func:`ring_topk` fires the ``comms.ring_topk``
fault point at trace time (the collective analog of a lost ring
participant — same placement as the ``comms.all_gather`` seam); callers
in ``parallel/`` catch :class:`~raft_tpu.core.errors.KernelFailure` /
runtime errors through ``_guard.kernel_guard`` and fall back to the
gather merge (warn-once, ``fallbacks{algo="ring_topk"}``). Per-ring obs:
``comms.ring.hops``, ``comms.ring.bytes{direction}`` (the payload
model) and ``comms.ring.wire_bytes{direction}`` (what the engine ships:
the fused kernel's DMA slots are lane-padded) counters and a traced
``ring_topk`` span.

VMEM residency of the fused kernel is modeled in
:func:`raft_tpu.ops.pallas.vmem_model.ring_topk_residency` and checked
by ``tools/graft_lint`` under the ``ring_topk`` bindings;
:func:`kernel_scratch_shapes` is asserted against the model in tests.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from raft_tpu import obs
from raft_tpu.core.errors import expects
from jax.lax import axis_size
from raft_tpu.robust import faults

#: Finite in-kernel "worst" value (see ``cagra_search.WORST``): the
#: rank-based fold places elements with masked one-hot sums, and
#: ``inf * 0`` would poison them with NaNs. The XLA engine keeps true
#: ``+/-inf`` sentinels (no masked arithmetic there).
WORST = 3.0e38

#: Sort-key pos for padding entries: must lose every tie against a real
#: candidate (real pos < n_shards * k << _PAD_POS).
_PAD_POS = jnp.iinfo(jnp.int32).max

#: Query-row chunk of the in-kernel fold — bounds the pairwise-rank
#: body intermediates to ~4 MiB at the serving shape (B=128, w=128).
_FOLD_ROWS = 32

#: Column chunk of the pairwise rank / one-hot placement passes
#: (``cagra_search._RANK_CHUNK``).
_RANK_CHUNK = 64

#: Lane tile of a VMEM buffer: a remote DMA must move whole lane tiles,
#: so the send/recv slots are ``lanes(w)`` wide and carry the merge
#: width ``w`` in their leading columns.
_LANES = 128


def lanes(w: int) -> int:
    """Width of a DMA slot that carries ``w`` columns."""
    return -(-w // _LANES) * _LANES

# The per-query merge wire model moved to the consolidated
# raft_tpu.parallel.wire_model; re-exported at this original home, where
# the engines' byte counters and older consumers import it from.
from raft_tpu.parallel.wire_model import (  # noqa: F401  (re-export)
    AG_ENTRY_BYTES,
    RS_ENTRY_BYTES,
    ring_wire_bytes,
    wire_bytes_per_query,
)


# ---------------------------------------------------------------------------
# shared schedule helpers
# ---------------------------------------------------------------------------


def _pad_cols(key, pos, v, i, width: int, select_min: bool):
    """Right-pad the candidate lanes to ``width`` columns with losing
    sentinels (inf key, ``_PAD_POS`` tie-break, -1 id)."""
    pad = ((0, 0), (0, width - key.shape[1]))
    return (
        jnp.pad(key, pad, constant_values=jnp.inf),
        jnp.pad(pos, pad, constant_values=_PAD_POS),
        jnp.pad(v, pad, constant_values=jnp.inf if select_min else -jnp.inf),
        jnp.pad(i, pad, constant_values=-1),
    )


def _scan_fold(key, pos, v, i, k: int, select_min: bool):
    """Streaming local top-k: fold the ``[nq, kc]`` candidate tile into
    ``[nq, k]`` one ``k``-wide slice at a time through :func:`_fold`
    instead of one monolithic width-``kc`` sort. Bit-identical to the
    sort-truncate (the (key, pos) total order makes every fold schedule
    associative) — this is the XLA mirror of the fused kernel's in-VMEM
    scan fold, shaped so the wide tile is consumed slice-wise rather
    than re-materialized sorted."""
    kc = key.shape[1]
    acc = (key[:, :k], pos[:, :k], v[:, :k], i[:, :k])
    for c0 in range(k, kc, k):
        c1 = min(c0 + k, kc)
        sl = tuple(x[:, c0:c1] for x in (key, pos, v, i))
        if c1 - c0 < k:
            sl = _pad_cols(*sl, k, select_min)
        acc = _fold(acc, sl, k)
    return acc


def _prep(v, i, k: int, select_min: bool, axis: str, scan_fold: bool = False):
    """Normalize local candidates to the ring's working layout.

    Returns ``(key, pos, v, i, n, B, nq)`` where the first four are
    ``[n * B, w]`` with ``w = k``: the sort key (``v`` for min-select,
    ``-v`` for max), the global concat position tie-break, and the
    carried value/id payloads. Width is padded (losing sentinels) or
    truncated (a local 2-key top-k — entries past local rank ``k`` can
    never enter the global top-k; ``scan_fold=True`` folds slice-wise
    via :func:`_scan_fold`, bit-identically) to ``k``; query rows are
    padded to a multiple of the axis size."""
    n = axis_size(axis)
    r = lax.axis_index(axis)
    nq, kc = v.shape
    v = v.astype(jnp.float32)
    i = i.astype(jnp.int32)
    pos = (r * kc + lax.broadcasted_iota(jnp.int32, (nq, kc), 1)).astype(jnp.int32)
    key = v if select_min else -v
    if kc > k:
        if scan_fold:
            key, pos, v, i = _scan_fold(key, pos, v, i, k, select_min)
        else:
            key, pos, v, i = lax.sort((key, pos, v, i), dimension=1, num_keys=2)
            key, pos, v, i = key[:, :k], pos[:, :k], v[:, :k], i[:, :k]
    elif kc < k:
        pad = ((0, 0), (0, k - kc))
        key = jnp.pad(key, pad, constant_values=jnp.inf)
        v = jnp.pad(v, pad, constant_values=jnp.inf if select_min else -jnp.inf)
        pos = jnp.pad(pos, pad, constant_values=_PAD_POS)
        i = jnp.pad(i, pad, constant_values=-1)
    B = -(-nq // n)
    rpad = n * B - nq
    if rpad:
        pad = ((0, rpad), (0, 0))
        key = jnp.pad(key, pad, constant_values=jnp.inf)
        v = jnp.pad(v, pad, constant_values=jnp.inf if select_min else -jnp.inf)
        pos = jnp.pad(pos, pad, constant_values=_PAD_POS)
        i = jnp.pad(i, pad, constant_values=-1)
    return key, pos, v, i, n, B, nq


def _fold(a, b, w: int):
    """One 2w -> w merge under the (key, pos) total order. ``a``/``b``
    are (key, pos, val, id) tuples of ``[B, w]`` arrays; pos uniqueness
    makes the fold associative and schedule-independent — the parity
    contract with the gather path's stable ``top_k``."""
    cat = tuple(jnp.concatenate([x, y], axis=1) for x, y in zip(a, b))
    key, pos, v, i = lax.sort(cat, dimension=1, num_keys=2)
    return key[:, :w], pos[:, :w], v[:, :w], i[:, :w]


# ---------------------------------------------------------------------------
# XLA engine: ppermute hops (runs everywhere; the CPU-mesh parity engine)
# ---------------------------------------------------------------------------


def _ring_topk_xla(v, i, k: int, select_min: bool, axis: str, scan_fold: bool = False):
    key, pos, v, i, n, B, nq = _prep(v, i, k, select_min, axis, scan_fold=scan_fold)
    r = lax.axis_index(axis)
    state = tuple(x.reshape(n, B, k) for x in (key, pos, v, i))
    if n == 1:
        _, _, ov, oi = tuple(x[0] for x in state)
        return ov[:nq], oi[:nq]
    perm = [(j, (j + 1) % n) for j in range(n)]
    take = lambda t, b: tuple(  # noqa: E731
        lax.dynamic_index_in_dim(x, b, 0, keepdims=False) for x in t
    )
    put = lambda t, blk, b: tuple(  # noqa: E731
        lax.dynamic_update_index_in_dim(x, y, b, 0) for x, y in zip(t, blk)
    )
    # -- reduce-scatter: after hop s, my partial of block (r-s-1)%n has
    # folded in every rank <= me's candidates; after n-1 hops block
    # (r+1)%n is finished on rank r.
    for s in range(n - 1):
        send = take(state, (r - s) % n)
        recv = tuple(lax.ppermute(x, axis, perm) for x in send)
        b = (r - s - 1) % n
        state = put(state, _fold(take(state, b), recv, k), b)
    # -- all-gather of the finished blocks (key/pos lanes done their job)
    out_v, out_i = state[2], state[3]
    for s in range(n - 1):
        send = take((out_v, out_i), (r + 1 - s) % n)
        rv, ri = (lax.ppermute(x, axis, perm) for x in send)
        b = (r - s) % n
        out_v = lax.dynamic_update_index_in_dim(out_v, rv, b, 0)
        out_i = lax.dynamic_update_index_in_dim(out_i, ri, b, 0)
    return out_v.reshape(n * B, k)[:nq], out_i.reshape(n * B, k)[:nq]


# ---------------------------------------------------------------------------
# fused engine: Pallas async-remote-copy ring (real TPU ICI only)
# ---------------------------------------------------------------------------


def kernel_scratch_shapes(n: int, B: int, w: int):
    """The fused kernel's scratch declarations, exposed so tests can
    assert them against ``vmem_model.ring_topk_residency`` (the drift
    guard every fused kernel in this tree carries)."""
    return [
        pltpu.VMEM((n, B, w), jnp.float32),   # state_key
        pltpu.VMEM((n, B, w), jnp.int32),     # state_pos
        pltpu.VMEM((n, B, w), jnp.float32),   # state_val
        pltpu.VMEM((n, B, w), jnp.int32),     # state_id
        pltpu.VMEM((2, B, lanes(w)), jnp.float32),   # send_key
        pltpu.VMEM((2, B, lanes(w)), jnp.int32),     # send_pos
        pltpu.VMEM((2, B, lanes(w)), jnp.float32),   # send_val
        pltpu.VMEM((2, B, lanes(w)), jnp.int32),     # send_id
        pltpu.VMEM((2, B, lanes(w)), jnp.float32),   # recv_key
        pltpu.VMEM((2, B, lanes(w)), jnp.int32),     # recv_pos
        pltpu.VMEM((2, B, lanes(w)), jnp.float32),   # recv_val
        pltpu.VMEM((2, B, lanes(w)), jnp.int32),     # recv_id
        pltpu.SemaphoreType.DMA((2, 4)),      # send sems [slot, lane]
        pltpu.SemaphoreType.DMA((2, 4)),      # recv sems [slot, lane]
        pltpu.SemaphoreType.REGULAR((2,)),    # credits: my right's recv slot is free
    ]


def scan_kernel_scratch_shapes(n: int, B: int, w: int, kc: int):
    """Scratch declarations of the scan-fused ring kernel — identical to
    :func:`kernel_scratch_shapes` (the scan fold reuses the state
    buffers as its accumulator target; only the *input* refs widen to
    ``kc`` columns). Exposed for the same vmem_model drift guard."""
    expects(kc % w == 0 and kc >= w,
            "scan width %d must be a positive multiple of merge width %d", kc, w)
    return kernel_scratch_shapes(n, B, w)


def _rank_merge_pos(uk, up, uv, ui, w: int):
    """Stable (key, pos)-ordered top-``w`` of the union ``[rows, 2w]``
    via pairwise ranks + one-hot placement — ``cagra_search._rank_merge``
    with the value tie broken by the unique concat position instead of
    the local column, which is what makes the fold order-independent.
    ``rank(i) = #{j : k_j < k_i or (k_j == k_i and p_j < p_i)}`` is a
    permutation of ``0..2w-1`` (pos unique); ranks ``< w`` survive."""
    rows, m = uk.shape
    parts = []
    for i0 in range(0, m, _RANK_CHUNK):
        i1 = min(i0 + _RANK_CHUNK, m)
        ki = jnp.expand_dims(uk[:, i0:i1], 1)  # a None index here lowers to a gather
        pi = jnp.expand_dims(up[:, i0:i1], 1)
        less = (uk[:, :, None] < ki).astype(jnp.int32)
        tie = ((uk[:, :, None] == ki) & (up[:, :, None] < pi)).astype(jnp.int32)
        parts.append(jnp.sum(less + tie, axis=1))
    rank = jnp.concatenate(parts, axis=1)  # [rows, 2w]
    outs = [[] for _ in range(4)]
    for p0 in range(0, w, _RANK_CHUNK):
        p1 = min(p0 + _RANK_CHUNK, w)
        pidx = lax.broadcasted_iota(jnp.int32, (1, 1, p1 - p0), 2) + p0
        oh = rank[:, :, None] == pidx  # [rows, 2w, chunk]
        for o, u in zip(outs, (uk, up, uv, ui)):
            z = jnp.zeros((), u.dtype)
            o.append(jnp.sum(jnp.where(oh, u[:, :, None], z), axis=1))
    return tuple(jnp.concatenate(o, axis=1) for o in outs)


def _hop_merge_kernel(ak, ap, av, ai, bk, bp, bv, bi, ok, op, ov, oi):
    """Single-device fold kernel: merge two [qt, w] candidate tiles into
    the (key, pos)-ordered top-w. This is the exact fold the ring kernel
    runs per hop; factored out so interpret-mode tests can pin it
    against the XLA ``_fold`` bit-for-bit."""
    w = ak.shape[1]
    uk = jnp.concatenate([ak[:], bk[:]], axis=1)
    up = jnp.concatenate([ap[:], bp[:]], axis=1)
    uv = jnp.concatenate([av[:], bv[:]], axis=1)
    ui = jnp.concatenate([ai[:], bi[:]], axis=1)
    rk, rp, rv, ri = _rank_merge_pos(uk, up, uv, ui, w)
    ok[:], op[:], ov[:], oi[:] = rk, rp, rv, ri


@functools.partial(jax.jit, static_argnames=("qt", "interpret"))
def hop_merge(a, b, qt: int = _FOLD_ROWS, interpret: bool = True):
    """Run one 2w -> w fold through the Pallas kernel (grid over
    ``qt``-row tiles). ``a``/``b`` are (key, pos, val, id) tuples of
    ``[rows, w]`` arrays. Used by tests (interpret mode on CPU) to prove
    the in-kernel fold bit-matches the XLA fold; the ring kernel inlines
    the same ``_rank_merge_pos``."""
    rows, w = a[0].shape
    expects(rows % qt == 0, "fold rows %d not divisible by tile %d", rows, qt)
    grid = (rows // qt,)
    tile = lambda: pl.BlockSpec((qt, w), lambda g: (g, 0))  # noqa: E731
    dts = (jnp.float32, jnp.int32, jnp.float32, jnp.int32)
    return pl.pallas_call(
        _hop_merge_kernel,
        grid=grid,
        in_specs=[tile() for _ in range(8)],
        out_specs=tuple(tile() for _ in range(4)),
        out_shape=tuple(jax.ShapeDtypeStruct((rows, w), d) for d in dts),
        interpret=interpret,
    )(*a, *b)


def _ring_body(n: int, B: int, w: int, axis: str, ov, oi, state, send, recv,
               send_sem, recv_sem, credit):
    """The shared ring schedule: reduce-scatter then all-gather, one
    ``make_async_remote_copy`` bundle per hop into the right neighbor's
    recv slot, fold on the VPU while the outgoing DMA drains (its
    send-semaphore wait is deferred until the slot is restaged two hops
    later — the double-buffer discipline of the guide's ring
    all-gather). ``state`` must already hold the staged ``[n, B, w]``
    per-block partials; :func:`_ring_kernel` stages a straight copy of
    the inputs, :func:`_scan_ring_kernel` stages the scan fold's
    winners.

    Hops are numbered ``t = 0 .. 2(n-1)-1`` across both phases and use
    slot ``t % 2``. A recv slot is written again two hops later, so the
    receiver hands a credit back to its left neighbor once it has read
    the slot, and the sender waits for that credit before the DMA — the
    right neighbor may still be folding the slot's previous contents
    (Pallas' interpret-mode race detector finds that race without it)."""
    me = lax.axis_index(axis)
    right = lax.rem(me + 1, n)
    left = lax.rem(me + n - 1, n)
    n_hops = 2 * (n - 1)

    # neighbor rendezvous: nobody DMAs into a peer still setting up
    # (staging touches only local state, never a recv slot, so running
    # it before the barrier is safe — peers cannot DMA until we signal)
    barrier = pltpu.get_barrier_semaphore()
    pltpu.semaphore_signal(barrier, inc=1, device_id=(left,))
    pltpu.semaphore_signal(barrier, inc=1, device_id=(right,))
    pltpu.semaphore_wait(barrier, 2)

    def copy(slot, ln):
        """The lane-``ln`` remote copy of send slot ``slot`` into the
        right neighbor's recv slot. DMA semaphores count bytes, so every
        wait goes through the descriptor, which knows the byte count."""
        return pltpu.make_async_remote_copy(
            src_ref=send[ln].at[slot],
            dst_ref=recv[ln].at[slot],
            send_sem=send_sem.at[slot, ln],
            recv_sem=recv_sem.at[slot, ln],
            device_id=(right,),
        )

    def free_send(t, lanes):
        """Before staging hop ``t``: my send of hop ``t-2`` (same slot)
        has drained, and my right neighbor has read that hop's data."""
        if t >= 2:
            for ln in lanes:
                copy(t % 2, ln).wait_send()
            pltpu.semaphore_wait(credit.at[t % 2], 1)

    def release(t):
        """My recv slot of hop ``t`` has been read: credit the sender."""
        pltpu.semaphore_signal(credit.at[t % 2], 1, device_id=(left,))

    lanes_rs = (0, 1, 2, 3)
    lanes_ag = (2, 3)  # finished blocks travel as (val, id) only

    # -- reduce-scatter hops ------------------------------------------------
    for s in range(n - 1):
        t, slot = s, s % 2
        free_send(t, lanes_rs)
        src = lax.rem(me + n - s, n) if s else me
        for ln in lanes_rs:
            send[ln][slot, :, 0:w] = state[ln][pl.ds(src, 1)][0]
            copy(slot, ln).start()
        for ln in lanes_rs:
            copy(slot, ln).wait_recv()
        dst = lax.rem(me + n - s - 1 + n, n)
        cur = tuple(st[pl.ds(dst, 1)][0] for st in state)
        got = tuple(rcv[slot, :, 0:w] for rcv in recv)
        for q0 in range(0, B, _FOLD_ROWS):
            q1 = min(q0 + _FOLD_ROWS, B)
            uk = jnp.concatenate([cur[0][q0:q1], got[0][q0:q1]], axis=1)
            up = jnp.concatenate([cur[1][q0:q1], got[1][q0:q1]], axis=1)
            uv = jnp.concatenate([cur[2][q0:q1], got[2][q0:q1]], axis=1)
            ui = jnp.concatenate([cur[3][q0:q1], got[3][q0:q1]], axis=1)
            fk, fp, fv, fi = _rank_merge_pos(uk, up, uv, ui, w)
            for st, f in zip(state, (fk, fp, fv, fi)):
                st[pl.ds(dst, 1), pl.ds(q0, q1 - q0)] = f[None]
        release(t)

    # rank r owns finished block (r+1)%n; write it to the output
    own = lax.rem(me + 1, n)
    for dst_ref, ln in ((ov, 2), (oi, 3)):
        dst_ref[pl.ds(own * B, B)] = state[ln][pl.ds(own, 1)][0]

    # -- all-gather hops: forward the newest finished block rightward -------
    for s in range(n - 1):
        t = n - 1 + s
        slot = t % 2
        # the lanes the reduce-scatter sent but this phase does not must
        # drain too before the slot is reused (hop t-2 may be an RS hop)
        free_send(t, lanes_rs if t - 2 < n - 1 else lanes_ag)
        # the block being forwarded is already in the output; stage from
        # state (first hop: own block) or from the previous hop's recv
        # slot, which is then read and goes back to the sender
        for ln in lanes_ag:
            if s == 0:
                send[ln][slot, :, 0:w] = state[ln][pl.ds(own, 1)][0]
            else:
                send[ln][slot] = recv[ln][1 - slot]
            copy(slot, ln).start()
        if s:
            release(t - 1)
        for ln in lanes_ag:
            copy(slot, ln).wait_recv()
        dst = lax.rem(me + n - s, n)
        ov[pl.ds(dst * B, B)] = recv[2][slot, :, 0:w]
        oi[pl.ds(dst * B, B)] = recv[3][slot, :, 0:w]
    release(n_hops - 1)
    # drain: the last two sends, and the right neighbor's last two credits
    for t in range(max(0, n_hops - 2), n_hops):
        for ln in (lanes_rs if t < n - 1 else lanes_ag):
            copy(t % 2, ln).wait_send()
        pltpu.semaphore_wait(credit.at[t % 2], 1)


def _ring_kernel(
    n: int, B: int, w: int, axis: str,
    ink, inp, inv, ini, ov, oi,
    sk, sp, sv, si,          # state [n, B, w]
    tk, tp, tv, ti,          # send slots [2, B, w]
    rk, rp, rv, ri,          # recv slots [2, B, w]
    send_sem, recv_sem, credit,
):
    """Width-``w`` inputs: stage a straight copy of each query block
    into the state buffers, then run the shared :func:`_ring_body`."""
    for b in range(n):
        sk[b], sp[b] = ink[b * B:(b + 1) * B], inp[b * B:(b + 1) * B]
        sv[b], si[b] = inv[b * B:(b + 1) * B], ini[b * B:(b + 1) * B]
    _ring_body(n, B, w, axis, ov, oi, (sk, sp, sv, si), (tk, tp, tv, ti),
               (rk, rp, rv, ri), send_sem, recv_sem, credit)


def _scan_ring_kernel(
    n: int, B: int, w: int, kc: int, axis: str,
    ink, inp, inv, ini, ov, oi,
    sk, sp, sv, si,          # state [n, B, w]
    tk, tp, tv, ti,          # send slots [2, B, w]
    rk, rp, rv, ri,          # recv slots [2, B, w]
    send_sem, recv_sem, credit,
):
    """Scan-fused staging: the inputs are the scan's FULL ``[n*B, kc]``
    candidate tile (``kc`` a multiple of ``w``; e.g. ``k·refine_ratio``
    wide). Each query block is folded ``w`` columns at a time through
    :func:`_rank_merge_pos` straight into the state buffers — the local
    top-``w`` never exists as an HBM array between the scan and the ring
    — and the shared :func:`_ring_body` takes over. Bit-identical to
    staging a pre-sorted top-``w``: every fold is under the (key, pos)
    total order."""
    state = (sk, sp, sv, si)
    ins = (ink, inp, inv, ini)
    for b in range(n):
        for q0 in range(0, B, _FOLD_ROWS):
            q1 = min(q0 + _FOLD_ROWS, B)
            acc = tuple(x[b * B + q0:b * B + q1, 0:w] for x in ins)
            for c0 in range(w, kc, w):
                sl = tuple(x[b * B + q0:b * B + q1, c0:c0 + w] for x in ins)
                uk = jnp.concatenate([acc[0], sl[0]], axis=1)
                up = jnp.concatenate([acc[1], sl[1]], axis=1)
                uv = jnp.concatenate([acc[2], sl[2]], axis=1)
                ui = jnp.concatenate([acc[3], sl[3]], axis=1)
                acc = _rank_merge_pos(uk, up, uv, ui, w)
            for st, f in zip(state, acc):
                st[pl.ds(b, 1), pl.ds(q0, q1 - q0)] = f[None]
    _ring_body(n, B, w, axis, ov, oi, state, (tk, tp, tv, ti),
               (rk, rp, rv, ri), send_sem, recv_sem, credit)


def fused_ring_topk(v, i, k: int, select_min: bool, axis: str, collective_id: int = 7):
    """Pallas async-remote-copy ring (inside ``shard_map``). Same
    schedule and (key, pos) fold as :func:`_ring_topk_xla`; real-TPU
    only — remote DMAs have no CPU interpreter on this jax release."""
    key, pos, vv, ii, n, B, nq = _prep(v, i, k, select_min, axis)
    # in-kernel fold arithmetic needs finite sentinels (inf * 0 = NaN)
    key = jnp.clip(key, -WORST, WORST)
    vals = jnp.clip(vv, -WORST, WORST)
    if n == 1:
        return vv[:nq], ii[:nq]
    w = k
    dts = (jnp.float32, jnp.int32)
    out_v, out_i = pl.pallas_call(
        functools.partial(_ring_kernel, n, B, w, axis),
        out_shape=tuple(jax.ShapeDtypeStruct((n * B, w), d) for d in dts),
        scratch_shapes=kernel_scratch_shapes(n, B, w),
        compiler_params=pltpu.CompilerParams(collective_id=collective_id),
    )(key, pos, vals, ii)
    # restore the inf sentinels the XLA/gather paths report
    worst = jnp.float32(WORST if select_min else -WORST)
    inf = jnp.float32(jnp.inf if select_min else -jnp.inf)
    out_v = jnp.where((out_v == worst) & (out_i < 0), inf, out_v)
    return out_v[:nq], out_i[:nq]


def fused_scan_ring_topk(v, i, k: int, select_min: bool, axis: str,
                         collective_id: int = 8):
    """Scan-fused Pallas ring (inside ``shard_map``): hands the scan's
    full ``[nq, kc]`` candidate tile to :func:`_scan_ring_kernel`, which
    folds it to the merge width in VMEM and runs the same ring as
    :func:`fused_ring_topk` (distinct ``collective_id`` — the two rings
    may coexist in one program). Real-TPU only, like the plain fused
    ring."""
    n = axis_size(axis)
    r = lax.axis_index(axis)
    nq, kc = v.shape
    if n == 1 or kc <= k:
        # nothing to fuse: no wide local tile (or no ring at all)
        return _ring_topk_xla(v, i, k, select_min, axis, scan_fold=True)
    vals = v.astype(jnp.float32)
    ids = i.astype(jnp.int32)
    pos = (r * kc + lax.broadcasted_iota(jnp.int32, (nq, kc), 1)).astype(jnp.int32)
    key = vals if select_min else -vals
    w = k
    kcp = -(-kc // w) * w
    if kcp > kc:
        key, pos, vals, ids = _pad_cols(key, pos, vals, ids, kcp, select_min)
    B = -(-nq // n)
    rpad = n * B - nq
    if rpad:
        pad = ((0, rpad), (0, 0))
        key = jnp.pad(key, pad, constant_values=jnp.inf)
        vals = jnp.pad(vals, pad, constant_values=jnp.inf if select_min else -jnp.inf)
        pos = jnp.pad(pos, pad, constant_values=_PAD_POS)
        ids = jnp.pad(ids, pad, constant_values=-1)
    # in-kernel fold arithmetic needs finite sentinels (inf * 0 = NaN)
    key = jnp.clip(key, -WORST, WORST)
    vals = jnp.clip(vals, -WORST, WORST)
    dts = (jnp.float32, jnp.int32)
    out_v, out_i = pl.pallas_call(
        functools.partial(_scan_ring_kernel, n, B, w, kcp, axis),
        out_shape=tuple(jax.ShapeDtypeStruct((n * B, w), d) for d in dts),
        scratch_shapes=scan_kernel_scratch_shapes(n, B, w, kcp),
        compiler_params=pltpu.CompilerParams(collective_id=collective_id),
    )(key, pos, vals, ids)
    worst = jnp.float32(WORST if select_min else -WORST)
    inf = jnp.float32(jnp.inf if select_min else -jnp.inf)
    out_v = jnp.where((out_v == worst) & (out_i < 0), inf, out_v)
    return out_v[:nq], out_i[:nq]


# ---------------------------------------------------------------------------
# dispatch
# ---------------------------------------------------------------------------


def _count_ring(n: int, nq: int, k: int, axis: str, fused: bool) -> None:
    """Trace-time ring counters. ``comms.ring.bytes`` is the payload
    model (:func:`wire_bytes_per_query`: the ``k`` entries that must
    cross); ``comms.ring.wire_bytes`` is what the engine puts on the
    wire (:func:`ring_wire_bytes`: the sort-key lane too, and the fused
    kernel's ``lanes(k)``-wide DMA slots)."""
    B = -(-nq // n)
    payload = max(0, n - 1) * B * k * (RS_ENTRY_BYTES + AG_ENTRY_BYTES)
    wire = ring_wire_bytes(n, B, lanes(k) if fused else k)
    obs.inc("comms.ring.hops", 2 * max(0, n - 1), axis=str(axis))
    for direction in ("send", "recv"):
        obs.inc("comms.ring.bytes", float(payload), axis=str(axis), direction=direction)
        obs.inc("comms.ring.wire_bytes", wire, axis=str(axis), direction=direction)


def ring_topk(
    v, i, k: int, *, select_min: bool = True, axis: str = "data",
    use_fused: bool | None = None,
) -> Tuple[jax.Array, jax.Array]:
    """Ring merge of per-shard candidates — call inside a ``shard_map``
    body where the gather path would ``all_gather`` + ``merge_parts``.

    ``v``/``i`` are the shard-local ``[nq, k_local]`` top-k (ids already
    global); returns replicated ``(vals [nq, k], ids [nq, k])``
    bit-identical to ``merge_parts`` over the shard-major concatenation.
    ``use_fused=None`` picks the Pallas remote-DMA kernel on real TPU
    and the ``ppermute`` engine elsewhere; failures escape to the
    caller's ``kernel_guard`` -> gather fallback.
    """
    n = axis_size(axis)
    # trace-time seam: the collective analog of a lost ring participant
    # (same placement as the comms.all_gather fault point)
    faults.fire("comms.ring_topk", axis=str(axis), n_shards=int(n))
    if use_fused is None:
        use_fused = jax.default_backend() == "tpu"
    if obs.is_enabled():
        _count_ring(n, v.shape[0], k, axis, use_fused)
        with obs.span(
            "ring_topk", axis=str(axis), n_shards=int(n), k=int(k),
            engine="fused" if use_fused else "xla", traced=True,
        ):
            if use_fused:
                return fused_ring_topk(v, i, k, select_min, axis)
            return _ring_topk_xla(v, i, k, select_min, axis)
    if use_fused:
        return fused_ring_topk(v, i, k, select_min, axis)
    return _ring_topk_xla(v, i, k, select_min, axis)


def scan_ring_topk(
    v, i, k: int, *, select_min: bool = True, axis: str = "data",
    use_fused: bool | None = None,
) -> Tuple[jax.Array, jax.Array]:
    """Scan-fused ring merge: like :func:`ring_topk` but takes the
    scan's FULL ``[nq, k_candidates]`` tile (any width ≥ ``k``, ids
    already global) and runs the local top-``k`` fold inside the ring
    engine, so the per-shard winners never materialize in HBM between
    the scan and the exchange (``merge_mode="fused_ring"``).

    Same (value, position) total order as the gather path's stable merge
    over the shard-major width-``k_candidates`` concatenation — the
    global top-k is a subset of the per-shard top-k, so folding locally
    first is bit-exact. Wire bytes equal ``ring_topk``'s; obs
    counters land under the same ``comms.ring.*`` names and the shared
    ``ring_topk`` span (``engine="scan_fused"/"scan_xla"``). Failures
    escape to the caller's ``kernel_guard`` → gather fallback
    (``fallbacks{algo="scan_ring_topk"}``)."""
    n = axis_size(axis)
    # same seam as ring_topk (a lost participant kills either ring);
    # kind="scan" lets chaos drills target just the fused path
    faults.fire("comms.ring_topk", axis=str(axis), n_shards=int(n), kind="scan")
    if use_fused is None:
        use_fused = jax.default_backend() == "tpu"
    # a tile no wider than k has nothing to fold: fused_scan_ring_topk
    # hands it to the XLA ring
    fused = use_fused and n > 1 and v.shape[1] > k

    def run():
        if use_fused:
            return fused_scan_ring_topk(v, i, k, select_min, axis)
        return _ring_topk_xla(v, i, k, select_min, axis, scan_fold=True)

    if obs.is_enabled():
        _count_ring(n, v.shape[0], k, axis, fused)
        with obs.span(
            "ring_topk", axis=str(axis), n_shards=int(n), k=int(k),
            engine="scan_fused" if fused else "scan_xla", traced=True,
        ):
            return run()
    return run()
