"""Dispatch rules: every ``"auto"`` engine choice in raft_tpu resolves here.

Each ``plan_*`` function is one plain rule over inputs its call site
already holds (batch size, backend, the kernel-eligibility verdict the
call site computed) and returns the chosen engine's name. A call site
calls its rule once and dispatches on the string. ``docs/planner.md``
lists each rule, what it reads and where it is called.
"""
from __future__ import annotations

from typing import Optional

from raft_tpu.parallel.wire_model import lloyd_wire_bytes_per_iter

#: batch size from which one dense scan launch (or the fused probed-list
#: kernel) beats per-query probe gathers
SCAN_MIN_BATCH = 128
#: feature width above which sparse pairwise distances stop densifying
#: rows (the native sort-merge path needs no [block, n_cols] tiles)
DENSIFY_MAX_COLS = 1 << 18
#: bytes charged to the CA exchange's changed-row select: CA must
#: undercut the full exchange by more than one 8-byte entry per
#: iteration, so equal byte counts (one shard) keep ``full``
CA_SELECT_BYTES = 8.0


def plan_search_mode(nq: int, *, on_tpu: bool, fused_ok: bool,
                     wants_f32_lut: bool = False) -> str:
    """IVF-Flat / IVF-PQ / RaBitQ engine for a batch of ``nq`` queries:
    ``probe`` below :data:`SCAN_MIN_BATCH`, else ``fused`` on a TPU when
    the kernel is eligible and no f32 LUT is asked for, else ``scan``."""
    if nq < SCAN_MIN_BATCH:
        return "probe"
    if on_tpu and fused_ok and not wants_f32_lut:
        return "fused"
    return "scan"


def plan_cagra_mode(*, on_tpu: bool, fused_ok: bool) -> str:
    """CAGRA beam engine: ``fused`` on a TPU when eligible, else ``xla``."""
    return "fused" if on_tpu and fused_ok else "xla"


def plan_merge_mode(n_shards: int) -> str:
    """Cross-shard merge engine: ``ring`` across shards, ``gather`` for
    one. ``fused_ring`` is never chosen here: a caller pins it."""
    return "ring" if n_shards > 1 else "gather"


def plan_comm_mode(n_shards: int, n_rows: Optional[int] = None,
                   d: Optional[int] = None, ca_cap=None) -> str:
    """Distributed-build accumulator exchange over ``[n_rows, d+1]`` f32
    rows: ``ca`` when its wire bytes plus :data:`CA_SELECT_BYTES` are
    below the full exchange's. Without the shape, ``ca`` across shards."""
    if n_rows is None or d is None:
        return "ca" if n_shards > 1 else "full"
    ca = lloyd_wire_bytes_per_iter(n_rows, d, n_shards, comm_mode="ca",
                                   ca_cap=ca_cap)
    full = lloyd_wire_bytes_per_iter(n_rows, d, n_shards, comm_mode="full")
    return "ca" if ca + CA_SELECT_BYTES < full else "full"


def plan_delta_mode(*, eligible: bool, on_tpu: bool) -> str:
    """Mutable delta scan: ``fused`` on a TPU inside the banked kernel's
    lossless window (``eligible``), else ``exact``."""
    return "fused" if eligible and on_tpu else "exact"


def plan_pq_kind(pq_bits: int, per_subspace: bool) -> str:
    """PQ code family at build: ``rabitq`` at 1 bit, ``nibble`` at 8 bits
    with per-subspace codebooks, else ``kmeans`` (whose build validates
    ``pq_bits``)."""
    if pq_bits == 1:
        return "rabitq"
    return "nibble" if pq_bits == 8 and per_subspace else "kmeans"


def plan_sparse_mode(n_cols: int, *, native_ok: bool) -> str:
    """Sparse pairwise engine: ``native`` above :data:`DENSIFY_MAX_COLS`
    columns when the metric has a native path, else ``densify``."""
    return "native" if n_cols > DENSIFY_MAX_COLS and native_ok else "densify"


__all__ = [
    "CA_SELECT_BYTES",
    "DENSIFY_MAX_COLS",
    "SCAN_MIN_BATCH",
    "plan_cagra_mode",
    "plan_comm_mode",
    "plan_delta_mode",
    "plan_merge_mode",
    "plan_pq_kind",
    "plan_search_mode",
    "plan_sparse_mode",
]
