"""One importable wire model for every fabric-byte estimate in the tree.

Historically the byte models grew next to their first consumers: the
per-verb collective factors lived in :mod:`raft_tpu.parallel.comms`
(where the obs byte counters apply them), the distributed-build
per-iteration models in :mod:`raft_tpu.parallel.sharded_ann`, and the
search-merge per-query model in :mod:`raft_tpu.ops.pallas.ring_topk`.
They now live here — one module, no jax dependency, import-cheap, read
by :func:`raft_tpu.plan.plan_comm_mode` — and the original homes re-export them
unchanged (every byte value below is pinned by the pre-existing tests
at those import paths: ``tests/test_sharded_ann.py``,
``tests/test_ring_topk.py``, ``tests/test_scan_ring_topk.py``).
"""
from __future__ import annotations

from raft_tpu.core.errors import expects

#: Per-verb wire models: bytes a rank actually moves over the fabric for
#: an input payload of ``p`` bytes on an ``n``-rank axis, assuming XLA's
#: ring schedules. The allgather family RECEIVES every other rank's block
#: ((n-1)·p — NOT the p the old accounting charged, and not the n·p the
#: stacked output shape would suggest); ring allreduce is reduce-scatter
#: + all-gather (2p(n-1)/n); reducescatter keeps only the scatter half.
#: Permutation verbs ship one block per rank regardless of n.
WIRE_FACTORS = {
    "allreduce": lambda p, n: 2.0 * p * (n - 1) / n,
    "reduce": lambda p, n: 2.0 * p * (n - 1) / n,
    "barrier": lambda p, n: 2.0 * p * (n - 1) / n,
    "reducescatter": lambda p, n: p * (n - 1) / n,
    "allgather": lambda p, n: p * (n - 1),
    "bcast": lambda p, n: p * (n - 1),
    "gather": lambda p, n: p * (n - 1),
    "gatherv": lambda p, n: p * (n - 1),
    "scatter": lambda p, n: p * (n - 1),
    "multicast_sendrecv": lambda p, n: p * (n - 1),
    "ppermute": lambda p, n: p,
    "send_recv": lambda p, n: p,
    "device_sendrecv": lambda p, n: p,
}


def wire_bytes(verb: str, payload_bytes: float, n: int) -> float:
    """Public surface of the :data:`WIRE_FACTORS` wire model: bytes one
    rank moves over the fabric for a ``payload_bytes`` input to ``verb``
    on an ``n``-rank axis. This is the same model ``comms.{verb}.bytes``
    counters apply, exposed so byte budgets elsewhere (the
    communication-avoiding build accounting in
    :mod:`raft_tpu.parallel.sharded_ann`, the comm-mode rule in
    :mod:`raft_tpu.plan`, bench columns, docs tables) stay pinned to one
    source of truth."""
    if n <= 1:
        return 0.0
    return float(WIRE_FACTORS.get(verb, lambda p, _: p)(float(payload_bytes), int(n)))


# ---------------------------------------------------------------------------
# search-merge per-query model (ring_topk engines)
# ---------------------------------------------------------------------------

#: Payload per candidate: reduce-scatter hops carry (f32 val, i32 id,
#: i32 pos); all-gather hops carry (val, id) only.
RS_ENTRY_BYTES = 12
AG_ENTRY_BYTES = 8
#: What a reduce-scatter entry costs on the wire: both engines ship the
#: sort key as a fourth lane (key, pos, val, id).
RS_WIRE_ENTRY_BYTES = 16


def ring_wire_bytes(n_shards: int, block_rows: int, slot_width: int) -> float:
    """Bytes one rank sends (and receives) in one ring merge, as the
    engines put them on the wire: ``n-1`` reduce-scatter hops of a
    ``[block_rows, slot_width]`` slot at :data:`RS_WIRE_ENTRY_BYTES` per
    entry, then ``n-1`` all-gather hops at :data:`AG_ENTRY_BYTES`.
    ``slot_width`` is ``k`` for the XLA engine; the fused kernel's DMA
    slots are lane-padded (``ring_topk.lanes(k)``, 128 at k <= 128),
    12.8x the payload at k=10."""
    n = int(n_shards)
    return float(max(0, n - 1) * block_rows * slot_width
                 * (RS_WIRE_ENTRY_BYTES + AG_ENTRY_BYTES))


def wire_bytes_per_query(n_shards: int, k: int, mode: str = "ring") -> float:
    """Estimated per-rank ICI payload bytes received per query for one
    merge: the ``k`` candidate entries that must cross, not the padded
    slots the fused kernel moves (:func:`ring_wire_bytes` counts those).

    ``mode="gather"``: each rank receives ``n-1`` foreign ``[k]`` blocks
    of (f32, i32). ``mode="ring"``: ``n-1`` reduce-scatter hops of one
    ``nq/n``-query block at :data:`RS_ENTRY_BYTES`/candidate plus
    ``n-1`` all-gather hops at :data:`AG_ENTRY_BYTES`, amortized over
    all ``nq`` queries. ``mode="fused_ring"`` moves the same payload
    as ``"ring"`` — only ``k``-wide winners ever enter the ring; the
    fusion's saving is the per-shard ``[nq, k·refine_ratio]`` candidate
    tile never round-tripping through HBM, not the wire."""
    n = int(n_shards)
    if n <= 1:
        return 0.0
    if mode == "gather":
        return float((n - 1) * k * AG_ENTRY_BYTES)
    return float((n - 1) * k * (RS_ENTRY_BYTES + AG_ENTRY_BYTES)) / n


# ---------------------------------------------------------------------------
# distributed-build per-iteration models (sharded_ann builds)
# ---------------------------------------------------------------------------


def ca_exchange_cap(n_rows: int, ca_cap=None) -> int:
    """Exchanged-row budget for the CA accumulator exchange. The default
    quarter-width (floored at 8) keeps the byte model ≥ ~2× below the
    full exchange for any row width the builds use while leaving enough
    slack that Lloyd's churn fits within a couple of iterations (churn
    decays geometrically after the first assignment pass)."""
    if ca_cap is None:
        ca_cap = min(n_rows, max(8, n_rows // 4))
    cap = int(ca_cap)
    expects(1 <= cap <= n_rows, "ca_cap %d outside [1, %d]", cap, n_rows)
    return cap


def lloyd_wire_bytes_per_iter(n_lists: int, d: int, n_shards: int,
                              comm_mode: str = "full", ca_cap=None) -> float:
    """Wire bytes one rank moves per distributed Lloyd iteration under
    the :func:`wire_bytes` model. ``full`` is the fused ``[n_lists,
    d+1]`` f32 allreduce; ``ca`` is the steady-state CA exchange — a
    ``[n_lists]`` changed-count allreduce plus a ``[cap, d+1]``
    selected-rows allreduce (the first iteration's carry-seeding full
    exchange is excluded; it amortises to zero over the training
    loop)."""
    if comm_mode == "full":
        return wire_bytes("allreduce", 4.0 * n_lists * (d + 1), n_shards)
    cap = ca_exchange_cap(n_lists, ca_cap)
    return (wire_bytes("allreduce", 4.0 * n_lists, n_shards)
            + wire_bytes("allreduce", 4.0 * cap * (d + 1), n_shards))


def codebook_wire_bytes_per_iter(pq_dim: int, ksub: int, pq_len: int, n_shards: int,
                                 comm_mode: str = "full", ca_cap=None) -> float:
    """Wire bytes one rank moves per distributed codebook iteration —
    the :func:`lloyd_wire_bytes_per_iter` model over the flattened
    ``[pq_dim·ksub, pq_len+1]`` accumulator rows."""
    return lloyd_wire_bytes_per_iter(pq_dim * ksub, pq_len, n_shards,
                                     comm_mode=comm_mode, ca_cap=ca_cap)
