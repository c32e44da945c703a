"""Segmented mutable index: main segment + delta segment + tombstones.

A production corpus is never static, but every raft_tpu index type is an
immutable XLA buffer built once. This module recasts the Faiss
add-with-ids/remove story for that constraint the LSM way — an index
becomes a generation-numbered **segment list**:

* the **main segment** is one ordinary immutable index (brute-force /
  IVF-Flat / IVF-PQ / CAGRA) over the rows that existed at the last
  compaction, plus a positional tombstone bitset
  (:class:`raft_tpu.core.bitset.Bitset`) passed *in-scan* as the index's
  ``prefilter`` — deletes mask candidates inside the kernels, before the
  k-way merge, so a dead row can never shadow a live one;
* the **delta segment** is a small append-only brute-force segment
  holding rows inserted since that compaction (served exactly), with its
  own live-mask; its row count is padded to a power of two so the
  jitted delta scan compiles ``log2`` programs, not one per insert;
* a **global id space** (int64, user-supplied or auto-assigned) maps
  onto (segment, position) so results from both segments merge into one
  best-first list.

Durability: every mutation is appended to the generation's write-ahead
log (:mod:`raft_tpu.mutable.wal`) — durable *then* visible — and
:func:`raft_tpu.mutable.compact.compact` folds delta + tombstones into
a rebuilt main segment published via an atomic manifest swap
(:mod:`raft_tpu.mutable.manifest`). :meth:`MutableIndex.snapshot`
returns an immutable, internally consistent :class:`Snapshot` that the
serving engine dispatches against, so queries in flight never observe a
half-applied mutation.
"""
from __future__ import annotations

import dataclasses
import os
import threading
from typing import Dict, List, Optional, Tuple

import jax.numpy as jnp
import numpy as np

from raft_tpu import obs
from raft_tpu.core import serialize as ser
from raft_tpu.core.bitset import Bitset
from raft_tpu.core.errors import expects
from raft_tpu.mutable import manifest as man
from raft_tpu.utils import lockcheck
from raft_tpu.mutable.wal import WalRecord, WriteAheadLog
from raft_tpu.ops.distance import DistanceType, is_min_close, resolve_metric

ALGOS = ("brute_force", "ivf_flat", "ivf_pq", "cagra")

#: delta-scan routing knobs accepted by ``delta_mode``
DELTA_MODES = ("auto", "exact", "fused")

#: The fused delta scan is lossless only while every merge bank holds a
#: single 128-lane group (see ``ops.pallas.ivf_scan._seg_compress``):
#: with the ``bank8`` merge that caps ONE kernel call at 8 * 128 padded
#: rows. Past that the delta is tiled into multiple 1024-row banks, each
#: scanned by its own (identically-shaped, so compiled-once) kernel call
#: inside the lossless window, and the per-bank top-k lists are k-way
#: merged on the accelerator by one stable sort — so routing through the
#: kernel keeps *bitwise* candidate parity with the exact XLA scan at
#: any banked size, rather than the approximate-top-k semantics the big
#: fused indexes accept.
_DELTA_FUSED_MAX_ROWS = 1024
_DELTA_FUSED_QT = 128
#: fused-route ceiling in banks: past 32 banks (32k padded rows) the
#: per-bank launch overhead beats the XLA scan and compaction is overdue
#: anyway — CompactionPolicy's delta-row trigger should have fired long
#: before.
_DELTA_FUSED_MAX_BANKS = 32

#: metrics whose fused-kernel epilogue matches brute-force exact
#: distances term-for-term (cosine divides by the norm product on the
#: XLA path but multiplies by rsqrt in-kernel — not bit-comparable)
_DELTA_FUSED_METRICS = frozenset(
    {
        DistanceType.L2Expanded,
        DistanceType.L2SqrtExpanded,
        DistanceType.InnerProduct,
    }
)

#: initial delta-buffer capacity (rows); grows by doubling
_DELTA_MIN_CAP = 64

#: serialized sidecar holding the main segment's raw rows + global ids
_ROWS_KIND = "mutable_rows"
_ROWS_VERSION = 1


def _po2(n: int) -> int:
    b = 1
    while b < n:
        b <<= 1
    return b


def _build_main(algo: str, data: np.ndarray, index_params, metric):
    """Build one immutable main-segment index over ``data`` rows whose
    positional ids are 0..n-1 (each builder assigns ``arange(n)``)."""
    from raft_tpu.neighbors import brute_force, cagra, ivf_flat, ivf_pq

    if algo == "brute_force":
        return brute_force.build(data, metric=metric)
    if algo == "ivf_flat":
        params = index_params or ivf_flat.IvfFlatIndexParams(metric=metric)
        return ivf_flat.build(data, params=params)
    if algo == "ivf_pq":
        params = index_params or ivf_pq.IvfPqIndexParams(metric=metric)
        return ivf_pq.build(data, params=params)
    if algo == "cagra":
        params = index_params or cagra.CagraIndexParams(metric=metric)
        return cagra.build(data, params=params)
    raise ValueError(f"unknown mutable algo {algo!r}")


def _search_main(algo: str, index, queries, k: int, params, prefilter, dataset, **kw):
    """Dispatch one main-segment search with the tombstone prefilter
    applied in-scan (every index type consumes a keep-``Bitset``)."""
    from raft_tpu.neighbors import brute_force, cagra, ivf_flat, ivf_pq

    if algo == "brute_force":
        return brute_force.search(index, queries, k, prefilter=prefilter, **kw)
    if algo == "ivf_flat":
        return ivf_flat.search(index, queries, k, params, prefilter=prefilter, **kw)
    if algo == "ivf_pq":
        return ivf_pq.search(
            index, queries, k, params, prefilter=prefilter, dataset=dataset, **kw
        )
    if algo == "cagra":
        return cagra.search(index, queries, k, params, prefilter=prefilter, **kw)
    raise ValueError(f"unknown mutable algo {algo!r}")


def _delta_fused_eligible(metric, cap: int, k: int) -> bool:
    """True when the banked fused scan reproduces the exact scan
    bit-for-bit: a supported metric, the padded delta within the banked
    window (each 1024-row bank stays inside the lossless bank-merge
    width), and k within one extract width."""
    return (
        metric in _DELTA_FUSED_METRICS
        and cap <= _DELTA_FUSED_MAX_ROWS * _DELTA_FUSED_MAX_BANKS
        and k <= 128
    )


def _delta_route(mode: str, metric, cap: int, k: int) -> str:
    """Resolve ``delta_mode`` to the scan that actually runs."""
    expects(mode in DELTA_MODES, "delta_mode must be %s, got %r",
            "|".join(DELTA_MODES), mode)
    if mode == "exact":
        return "exact"
    eligible = _delta_fused_eligible(metric, cap, k)
    if mode == "fused":
        expects(
            eligible,
            "delta_mode='fused' needs an L2/IP metric, a delta of <= %d "
            "(padded) rows and k <= 128",
            _DELTA_FUSED_MAX_ROWS * _DELTA_FUSED_MAX_BANKS,
        )
        return "fused"
    import jax

    from raft_tpu import plan as _plan

    return _plan.plan_delta_mode(eligible=eligible, on_tpu=jax.default_backend() == "tpu")


def _delta_fused_search(metric, delta_bf, delta_live, queries, k: int):
    """Delta scan through the fused Pallas probed-list kernel, treating
    each 1024-row tile of the padded delta buffer as ONE list that every
    query tile probes.

    Within the eligibility window (:func:`_delta_fused_eligible`) the
    kernel's lane-group compression is a pure reshuffle — no candidate
    is ever merged away — and its distance epilogue applies the same
    expanded-metric terms as :func:`raft_tpu.neighbors.brute_force.search`
    ``mode="exact"``, so ids match exactly and distances to float
    rounding (the parity gate in ``tests/test_mutable.py``). Dead and
    padding rows fold into the slot validity the same way the live
    bitset masks the exact scan.

    Past one bank (padded cap > 1024 — always a multiple of 1024, the
    cap grows by doubling) every bank is scanned by its own kernel call,
    each inside the lossless window, and the per-bank top-k lists are
    k-way merged by one stable sort on the kernel-space scores: the
    epilogue is a per-query monotone map, per-bank lists break ties by
    ascending slot, and banks concatenate in ascending-slot order — so
    the merged ids keep the exact scan's lowest-id-wins tie discipline.
    The bank count is published as the ``mutable.delta.banks`` gauge.
    """
    import jax

    from raft_tpu.ops.pallas.ivf_scan import fused_list_topk

    cap = int(delta_bf.size)
    qf = jnp.asarray(queries, jnp.float32)
    nq = qf.shape[0]
    qt = _DELTA_FUSED_QT
    n_qt = max(1, (nq + qt - 1) // qt)
    nq_pad = n_qt * qt
    if nq_pad != nq:
        qf = jnp.concatenate(
            [qf, jnp.broadcast_to(qf[:1], (nq_pad - nq, qf.shape[1]))]
        )
    mask = (
        jnp.asarray(delta_live.to_mask())
        if delta_live is not None
        else jnp.ones((cap,), bool)
    )
    positions = jnp.arange(cap, dtype=jnp.int32)
    tile_probes = jnp.zeros((n_qt, 1), jnp.int32)
    probe_valid = jnp.ones((n_qt, 1), jnp.int32)
    norms = delta_bf.norms
    interpret = jax.default_backend() != "tpu"

    bank_rows = _DELTA_FUSED_MAX_ROWS
    n_banks = max(1, (cap + bank_rows - 1) // bank_rows)

    bank_vals, bank_slots = [], []
    for b in range(n_banks):
        lo, hi = b * bank_rows, min((b + 1) * bank_rows, cap)
        list_indices = jnp.where(mask[lo:hi], positions[lo:hi], -1)[None, :]
        v, s = fused_list_topk(
            delta_bf.dataset[lo:hi][None].astype(jnp.float32),
            norms[lo:hi][None] if norms is not None else None,
            list_indices,
            qf,
            tile_probes,
            probe_valid,
            k=k,
            metric=metric,
            qt=qt,
            merge="bank8",
            interpret=interpret,
        )
        bank_vals.append(v)
        # Kernel slots are rows within the data it was handed — lift the
        # bank's rows back to global delta positions (invalid stays -1).
        bank_slots.append(jnp.where(s >= 0, s + lo, -1))
    if obs.is_enabled():
        obs.set_gauge("mutable.delta.banks", float(n_banks))
    if n_banks == 1:
        vals, slots = bank_vals[0], bank_slots[0]
    else:
        all_v = jnp.concatenate(bank_vals, axis=1)
        all_s = jnp.concatenate(bank_slots, axis=1)
        order = jnp.argsort(all_v, axis=1, stable=True)[:, :k]
        vals = jnp.take_along_axis(all_v, order, axis=1)
        slots = jnp.take_along_axis(all_s, order, axis=1)
    idx = jnp.where(slots >= 0, slots, -1)
    if metric == DistanceType.InnerProduct:
        out = -vals
    else:
        qn = jnp.sum(qf * qf, axis=1)
        out = jnp.maximum(qn[:, None] + vals, 0.0)
        if metric == DistanceType.L2SqrtExpanded:
            out = jnp.sqrt(out)
        out = jnp.where(idx >= 0, out, jnp.inf)
    return out[:nq], idx[:nq]


def _save_rows(path: str, ids: np.ndarray, data: np.ndarray) -> str:
    """Atomic checksummed sidecar with the main segment's source rows
    (the rebuild input future compactions need — PQ codes are lossy)."""
    import io

    body = io.BytesIO()
    ser.serialize_array(body, np.asarray(ids, np.int64))
    ser.serialize_array(body, np.asarray(data, np.float32))
    payload = body.getvalue()
    return ser.atomic_write(
        path, lambda f: ser.save_stream(f, _ROWS_KIND, _ROWS_VERSION, payload)
    )


def _load_rows(path: str) -> Tuple[np.ndarray, np.ndarray]:
    with open(path, "rb") as f:
        _version, body = ser.load_stream(f, _ROWS_KIND)
        ids = np.asarray(ser.deserialize_array(body))
        data = np.asarray(ser.deserialize_array(body))
    return ids, data


@dataclasses.dataclass(frozen=True)
class Snapshot:
    """An immutable, search-consistent view of a :class:`MutableIndex`.

    Everything a query needs is pinned here: the main segment and its
    tombstone bitset, the (padded) delta brute-force segment and its
    live bitset, and the position→global-id maps. Mutations after
    :meth:`MutableIndex.snapshot` returned never alter this object, so
    a serving batch dispatched against it is atomic with respect to
    writers.
    """

    generation: int
    version: int
    algo: str
    metric: DistanceType
    dim: int
    main_index: object  # built index or None when the main segment is empty
    main_ids: np.ndarray  # int64[n_main] position -> global id
    main_live: Optional[Bitset]  # None = no tombstones (fast path)
    n_main_live: int
    refine_dataset: object  # ivf_pq exact re-rank rows (device) or None
    delta_bf: object  # BruteForceIndex over the padded delta, or None
    delta_ids: np.ndarray  # int64[delta_cap] position -> global id (-1 pad)
    delta_live: Optional[Bitset]  # live bits over the padded delta rows
    n_delta_live: int
    search_params: object = None
    search_kwargs: Dict[str, object] = dataclasses.field(default_factory=dict)
    delta_mode: str = "auto"  # auto | exact | fused (see _delta_route)

    @property
    def size(self) -> int:
        """Live (visible) row count."""
        return self.n_main_live + self.n_delta_live

    @property
    def select_min(self) -> bool:
        return is_min_close(self.metric)

    def search(self, queries, k: int, params=None, **kw) -> Tuple[np.ndarray, np.ndarray]:
        """Best-first search over both segments with tombstones masked
        in-scan. Returns ``(distances f32 [m, k], ids int64 [m, k])``;
        unfilled slots get id -1 and the worst-sentinel distance.

        The main segment runs its native search (fused/XLA per its
        ``mode``) with the tombstone bitset as ``prefilter``; the delta
        segment runs an exact brute-force scan over its padded buffer
        with dead+padding rows masked; candidates merge k-way by
        distance on the host. With an empty delta and no tombstones the
        result is bit-for-bit the main index's own output (ids mapped
        to the global space).
        """
        queries = np.asarray(queries, np.float32)
        expects(queries.ndim == 2 and queries.shape[1] == self.dim, "bad query shape")
        expects(k >= 1, "k must be >= 1")
        m = queries.shape[0]
        params = params if params is not None else self.search_params
        kw = {**self.search_kwargs, **kw}
        worst = np.float32(np.inf if self.select_min else -np.inf)
        parts: List[Tuple[np.ndarray, np.ndarray]] = []

        if self.main_index is not None and len(self.main_ids):
            k_main = min(k, len(self.main_ids))
            d, p = _search_main(
                self.algo, self.main_index, queries, k_main, params,
                prefilter=self.main_live, dataset=self.refine_dataset, **kw
            )
            d = np.asarray(d, np.float32)
            p = np.asarray(p)
            ids = np.where(p >= 0, self.main_ids[np.clip(p, 0, None)], np.int64(-1))
            d = np.where(ids >= 0, d, worst)
            parts.append((d, ids))
            if self.delta_bf is None and k_main == k:
                return d, ids  # pure-main fast path: native ordering intact

        if self.delta_bf is not None:
            from raft_tpu.neighbors import brute_force

            k_delta = min(k, int(self.delta_bf.size))
            route = _delta_route(
                self.delta_mode, self.metric, int(self.delta_bf.size), k_delta
            )
            d = p = None
            if route == "fused":
                from raft_tpu.robust.fallback import FALLBACK_ERRORS, record_fallback

                try:
                    d, p = _delta_fused_search(
                        self.metric, self.delta_bf, self.delta_live, queries, k_delta
                    )
                except FALLBACK_ERRORS as e:
                    # kernel failure degrades to the XLA scan, counted
                    record_fallback("mutable_delta", e)
                    route = "exact"
            if d is None:
                d, p = brute_force.search(
                    self.delta_bf, queries, k_delta,
                    prefilter=self.delta_live, mode="exact",
                )
            if obs.is_enabled():
                obs.inc("mutable.delta.scans", mode=route)
            d = np.asarray(d, np.float32)
            p = np.asarray(p)
            ids = np.where(p >= 0, self.delta_ids[np.clip(p, 0, None)], np.int64(-1))
            d = np.where(ids >= 0, d, worst)
            parts.append((d, ids))

        if not parts:
            return (
                np.full((m, k), worst, np.float32),
                np.full((m, k), -1, np.int64),
            )
        all_d = np.concatenate([d for d, _ in parts], axis=1)
        all_i = np.concatenate([i for _, i in parts], axis=1)
        # dead/unfilled slots already carry the worst sentinel, so one
        # stable argsort is the k-way merge (ties keep main-first order)
        key = all_d if self.select_min else -all_d
        order = np.argsort(key, axis=1, kind="stable")[:, :k]
        out_d = np.take_along_axis(all_d, order, axis=1)
        out_i = np.take_along_axis(all_i, order, axis=1)
        if out_d.shape[1] < k:
            pad = k - out_d.shape[1]
            out_d = np.pad(out_d, ((0, 0), (0, pad)), constant_values=worst)
            out_i = np.pad(out_i, ((0, 0), (0, pad)), constant_values=-1)
        return out_d, out_i


@lockcheck.guarded_fields
class MutableIndex:
    """A mutable, crash-consistent index over one immutable index type.

    >>> mut = MutableIndex.open("/data/wiki", "ivf_flat", dim=128)
    >>> ids = mut.insert(rows)               # durable-then-visible
    >>> mut.delete(ids[:10])                 # tombstoned in-scan
    >>> dist, gids = mut.search(queries, 10)
    >>> mut.compact()                        # fold delta+tombstones, new generation

    ``directory=None`` runs fully in memory (no WAL, no manifest) — the
    same visibility semantics without durability, for tests and
    benchmarks.
    """

    def __init__(
        self,
        algo: str,
        dim: int,
        *,
        directory: Optional[str] = None,
        index_params=None,
        search_params=None,
        metric=None,
        name: Optional[str] = None,
        max_wal_bytes: Optional[int] = None,
        delta_mode: str = "auto",
    ):
        expects(algo in ALGOS, "unknown mutable algo %r (want one of %s)",
                algo, ", ".join(ALGOS))
        expects(dim >= 1, "dim must be >= 1")
        expects(delta_mode in DELTA_MODES, "delta_mode must be %s, got %r",
                "|".join(DELTA_MODES), delta_mode)
        expects(max_wal_bytes is None or max_wal_bytes > 0,
                "max_wal_bytes must be positive when set")
        self.algo = algo
        self.dim = int(dim)
        self.directory = directory
        self.index_params = index_params
        self.search_params = search_params
        self.max_wal_bytes = max_wal_bytes
        self.delta_mode = delta_mode
        if metric is None:
            metric = getattr(index_params, "metric", DistanceType.L2Expanded)
        self.metric = resolve_metric(metric)
        self.name = name or (os.path.basename(directory) if directory else "mutable")
        self._lock = lockcheck.tracked(threading.RLock(), "mutable.lock")
        # lock ordering: _compact_mutex (if taken) strictly before _lock.
        # It serializes whole compactions (foreground or background) so
        # two rebuilds can never race a generation number, while writers
        # and searchers keep taking _lock alone. The full ordering
        # contract is machine-checked: tools/graft_lint/lock_order.toml
        # declares it, the lock-order lint derives it statically, and
        # the RAFT_TPU_LOCKCHECK witness asserts it at runtime.
        self._compact_mutex = lockcheck.tracked(
            threading.Lock(), "mutable.compact_mutex"
        )
        #: when a background compaction is between pin and flip, every
        #: applied mutation is also recorded here so the in-memory
        #: (directory=None) catch-up replay has a source of truth; the
        #: directory-backed path reads the WAL instead
        self._capture: Optional[List[WalRecord]] = None
        # main segment state
        self.main_index = None
        self.main_data = np.zeros((0, dim), np.float32)
        self.main_ids = np.zeros((0,), np.int64)
        self._main_live_mask = np.zeros((0,), bool)
        self._n_main_dead = 0
        self._refine_dataset = None
        # delta segment state (append-only buffer, doubling capacity)
        self._delta_data = np.zeros((_DELTA_MIN_CAP, dim), np.float32)
        self._delta_ids = np.full((_DELTA_MIN_CAP,), -1, np.int64)
        self._delta_live = np.zeros((_DELTA_MIN_CAP,), bool)
        self._n_delta = 0
        self._n_delta_dead = 0
        # id space + versions
        self._id_loc: Dict[int, Tuple[str, int]] = {}
        self.next_id = 0
        self.generation = 0
        self.version = 0  # mutation counter (any visible change bumps it)
        self.wal: Optional[WriteAheadLog] = None
        self._snap: Optional[Snapshot] = None
        self._delta_bf_cache: Tuple[int, object] = (-1, None)

    # -- construction ------------------------------------------------------

    @classmethod
    def open(
        cls,
        directory: str,
        algo: str,
        dim: int,
        *,
        index_params=None,
        search_params=None,
        metric=None,
        name: Optional[str] = None,
        max_wal_bytes: Optional[int] = None,
        delta_mode: str = "auto",
        res=None,
    ) -> "MutableIndex":
        """Open (or create) the mutable index at ``directory``.

        Recovery is manifest-then-WAL: the manifest names the live
        generation, its main-segment snapshot loads through the
        checksummed v4 path, and the generation's WAL replays on top —
        any valid prefix of a torn log recovers cleanly, so a crash at
        any point yields either the pre- or post-mutation state.
        ``max_wal_bytes`` arms size-triggered WAL segment rotation;
        ``delta_mode`` routes delta-segment scans (see
        :func:`_delta_route`).
        """
        self = cls(
            algo, dim, directory=directory, index_params=index_params,
            search_params=search_params, metric=metric, name=name,
            max_wal_bytes=max_wal_bytes, delta_mode=delta_mode,
        )
        m = man.read(directory)
        if m is None:
            m = man.Manifest(
                generation=0, algo=algo, dim=self.dim, main=None, rows=None,
                wal=_wal_name(0), next_id=0,
            )
            man.swap(directory, m)
        expects(m.algo == algo, "directory holds a %r index, not %r", m.algo, algo)
        expects(m.dim == self.dim, "directory holds dim=%d, not %d", m.dim, self.dim)
        self.generation = m.generation
        self.next_id = m.next_id
        if m.rows is not None:
            ids, data = _load_rows(os.path.join(directory, m.rows))
            self._install_main(ids, data, index=None, res=res)
            if m.main is not None:
                self.main_index = _load_main(
                    algo, os.path.join(directory, m.main), data, res=res
                )
        self.wal, records = WriteAheadLog.open(
            os.path.join(directory, m.wal), max_bytes=self.max_wal_bytes
        )
        for rec in records:
            self._apply(rec)
        self._note_obs()
        return self

    def _install_main(self, ids: np.ndarray, data: np.ndarray, index, res=None) -> None:
        """Replace the main segment (compaction/open): fresh tombstones,
        fresh id map for the main rows."""
        self.main_ids = np.asarray(ids, np.int64)
        self.main_data = np.asarray(data, np.float32)
        self.main_index = index
        self._main_live_mask = np.ones((len(ids),), bool)
        self._n_main_dead = 0
        self._refine_dataset = None
        if self.algo == "ivf_pq" and len(ids):
            # exact re-rank rows for the integrated refine path, pushed
            # to device once per generation
            self._refine_dataset = jnp.asarray(self.main_data)
        for pos, gid in enumerate(self.main_ids):
            self._id_loc[int(gid)] = ("m", pos)
        if len(ids):
            self.next_id = max(self.next_id, int(self.main_ids.max()) + 1)

    # -- introspection -----------------------------------------------------

    @property
    def size(self) -> int:
        """Visible (live) row count across both segments."""
        with self._lock:
            return (len(self.main_ids) - self._n_main_dead) + (
                self._n_delta - self._n_delta_dead
            )

    @property
    def delta_rows(self) -> int:
        with self._lock:
            return self._n_delta - self._n_delta_dead

    @property
    def tombstone_fraction(self) -> float:
        with self._lock:
            total = len(self.main_ids) + self._n_delta
            dead = self._n_main_dead + self._n_delta_dead
            return dead / total if total else 0.0

    def live_rows(self) -> Tuple[np.ndarray, np.ndarray]:
        """All live ``(ids, vectors)`` in stable segment order (main
        position order, then delta insertion order) — the exact input a
        from-scratch rebuild (or compaction) consumes."""
        with self._lock:
            mm = self._main_live_mask
            dm = self._delta_live[: self._n_delta]
            ids = np.concatenate([self.main_ids[mm], self._delta_ids[: self._n_delta][dm]])
            vecs = np.concatenate(
                [self.main_data[mm], self._delta_data[: self._n_delta][dm]], axis=0
            )
        return ids, vecs

    # -- mutations (durable then visible) ----------------------------------

    def insert(self, vectors, ids=None) -> np.ndarray:
        """Insert rows; returns their global ids (auto-assigned when
        ``ids`` is None). Fails on a live duplicate id — use
        :meth:`upsert` to replace."""
        vectors = np.ascontiguousarray(np.asarray(vectors, np.float32))
        if vectors.ndim == 1:
            vectors = vectors[None, :]
        expects(vectors.ndim == 2 and vectors.shape[1] == self.dim, "bad insert shape")
        with self._lock:
            if ids is None:
                ids = np.arange(self.next_id, self.next_id + len(vectors), dtype=np.int64)
            else:
                ids = np.asarray(ids, np.int64).reshape(-1)
                expects(len(ids) == len(vectors), "ids/vectors length mismatch")
                for gid in ids:
                    expects(int(gid) not in self._id_loc,
                            "id %d already live — use upsert()", int(gid))
            rec = WalRecord(op="insert", ids=ids, vectors=vectors)
            if self.wal is not None:
                self.wal.append(rec)
            self._apply(rec)
            self._note_obs()
        return ids

    def delete(self, ids) -> int:
        """Tombstone rows by global id; unknown ids are ignored. Returns
        the number of rows actually deleted."""
        ids = np.asarray(ids, np.int64).reshape(-1)
        with self._lock:
            rec = WalRecord(op="delete", ids=ids)
            if self.wal is not None:
                self.wal.append(rec)
            n = self._apply(rec)
            self._note_obs()
        return n

    def upsert(self, ids, vectors) -> np.ndarray:
        """Replace-or-insert rows at explicit global ids (Faiss
        ``add_with_ids`` over existing ids): any live row with a given
        id is tombstoned and the new row becomes visible atomically."""
        vectors = np.ascontiguousarray(np.asarray(vectors, np.float32))
        if vectors.ndim == 1:
            vectors = vectors[None, :]
        ids = np.asarray(ids, np.int64).reshape(-1)
        expects(len(ids) == len(vectors), "ids/vectors length mismatch")
        expects(vectors.shape[1] == self.dim, "bad upsert shape")
        with self._lock:
            rec = WalRecord(op="upsert", ids=ids, vectors=vectors)
            if self.wal is not None:
                self.wal.append(rec)
            self._apply(rec)
            self._note_obs()
        return ids

    # -- application (shared by live mutation and WAL replay) --------------

    def _apply(self, rec: WalRecord) -> int:
        if self._capture is not None:
            # a background compaction pinned before this mutation: queue
            # it for the catch-up replay into the new generation
            self._capture.append(rec)
        if rec.op == "insert":
            self._apply_rows(rec.ids, rec.vectors, replace=False)
            if obs.is_enabled():
                obs.inc("mutable.inserts", float(len(rec.ids)), index=self.name)
            return len(rec.ids)
        if rec.op == "upsert":
            self._apply_rows(rec.ids, rec.vectors, replace=True)
            if obs.is_enabled():
                obs.inc("mutable.upserts", float(len(rec.ids)), index=self.name)
            return len(rec.ids)
        if rec.op == "delete":
            n = 0
            for gid in rec.ids:
                n += self._tombstone(int(gid))
            self.version += 1
            if obs.is_enabled():
                obs.inc("mutable.deletes", float(n), index=self.name)
            return n
        raise ValueError(f"unknown WAL op {rec.op!r}")

    def _tombstone(self, gid: int) -> int:
        loc = self._id_loc.pop(gid, None)
        if loc is None:
            return 0
        seg, pos = loc
        if seg == "m":
            self._main_live_mask[pos] = False
            self._n_main_dead += 1
        else:
            self._delta_live[pos] = False
            self._n_delta_dead += 1
        return 1

    def _apply_rows(self, ids: np.ndarray, vectors: np.ndarray, replace: bool) -> None:
        for gid, row in zip(ids, vectors):
            gid = int(gid)
            if replace:
                self._tombstone(gid)
            pos = self._n_delta
            if pos == len(self._delta_data):
                new_cap = max(_DELTA_MIN_CAP, 2 * len(self._delta_data))
                self._delta_data = np.concatenate(
                    [self._delta_data,
                     np.zeros((new_cap - len(self._delta_data), self.dim), np.float32)]
                )
                self._delta_ids = np.concatenate(
                    [self._delta_ids,
                     np.full((new_cap - len(self._delta_ids),), -1, np.int64)]
                )
                self._delta_live = np.concatenate(
                    [self._delta_live, np.zeros((new_cap - len(self._delta_live),), bool)]
                )
            self._delta_data[pos] = row
            self._delta_ids[pos] = gid
            self._delta_live[pos] = True
            self._id_loc[gid] = ("d", pos)
            self._n_delta += 1
            self.next_id = max(self.next_id, gid + 1)
        self.version += 1

    # -- snapshots ---------------------------------------------------------

    def snapshot(self) -> Snapshot:
        """An immutable search-consistent view at this instant (cached
        until the next mutation or compaction)."""
        with self._lock:
            snap = self._snap
            if snap is not None and snap.generation == self.generation and snap.version == self.version:
                return snap
            main_live = None
            if self._n_main_dead and len(self.main_ids):
                main_live = Bitset.from_mask(jnp.asarray(self._main_live_mask))
            delta_bf, delta_live, delta_ids = None, None, self._delta_ids
            if self._n_delta - self._n_delta_dead > 0:
                delta_bf, delta_live, delta_ids = self._delta_segment()
            snap = Snapshot(
                generation=self.generation,
                version=self.version,
                algo=self.algo,
                metric=self.metric,
                dim=self.dim,
                main_index=self.main_index,
                main_ids=self.main_ids,
                main_live=main_live,
                n_main_live=len(self.main_ids) - self._n_main_dead,
                refine_dataset=self._refine_dataset,
                delta_bf=delta_bf,
                delta_ids=delta_ids,
                delta_live=delta_live,
                n_delta_live=self._n_delta - self._n_delta_dead,
                search_params=self.search_params,
                delta_mode=self.delta_mode,
            )
            self._snap = snap
            return snap

    def _delta_segment(self):
        """Brute-force view of the delta rows, padded to a power of two
        so the jitted scan sees at most log2 distinct shapes; padding
        and dead rows are masked by the live bitset."""
        from raft_tpu.neighbors import brute_force

        cap = _po2(max(self._n_delta, 1))
        key = (self.version, cap)
        cached_key, cached = self._delta_bf_cache
        if cached_key == key:
            return cached
        data = self._delta_data[:cap]
        ids = self._delta_ids[:cap]
        mask = np.zeros((cap,), bool)
        mask[: self._n_delta] = self._delta_live[: self._n_delta]
        bf = brute_force.build(data, metric=self.metric)
        out = (bf, Bitset.from_mask(jnp.asarray(mask)), ids.copy())
        self._delta_bf_cache = (key, out)
        return out

    def search(self, queries, k: int, params=None, **kw):
        """Convenience: :meth:`snapshot` then :meth:`Snapshot.search`."""
        return self.snapshot().search(queries, k, params=params, **kw)

    def compact(self, res=None) -> int:
        """Fold delta + tombstones into a rebuilt main segment and
        publish it as the next generation (see
        :func:`raft_tpu.mutable.compact.compact`)."""
        from raft_tpu.mutable.compact import compact

        return compact(self, res=res)

    def compact_background(self, res=None, _mid_rebuild=None) -> int:
        """One off-lock compaction on the calling thread: pin, rebuild
        without the lock, catch-up + flip under a brief lock (see
        :func:`raft_tpu.mutable.maintenance.compact_background`).
        Production callers want a :class:`~raft_tpu.mutable.maintenance.
        Compactor` worker instead."""
        from raft_tpu.mutable.maintenance import compact_background

        return compact_background(self, res=res, _mid_rebuild=_mid_rebuild)

    def close(self) -> None:
        with self._lock:
            if self.wal is not None:
                self.wal.close()
                self.wal = None

    # -- obs ---------------------------------------------------------------

    def _note_obs(self) -> None:
        if not obs.is_enabled():
            return
        obs.set_gauge("mutable.generation", float(self.generation), index=self.name)
        obs.set_gauge("mutable.delta_rows", float(self.delta_rows), index=self.name)
        obs.set_gauge("mutable.size", float(self.size), index=self.name)
        obs.set_gauge(
            "mutable.tombstone_fraction", float(self.tombstone_fraction), index=self.name
        )


def _wal_name(generation: int) -> str:
    return f"wal-{generation:08d}.log"


def _gen_dirname(generation: int) -> str:
    return f"gen-{generation:08d}"


def _load_main(algo: str, path: str, data: np.ndarray, res=None):
    """Load one main-segment snapshot through the per-algo checksummed
    loader (CAGRA snapshots may externalize the dataset — re-attach the
    sidecar rows)."""
    from raft_tpu.neighbors import brute_force, cagra, ivf_flat, ivf_pq

    if algo == "brute_force":
        return brute_force.load_path(path, res=res)
    if algo == "ivf_flat":
        return ivf_flat.load_path(path, res=res)
    if algo == "ivf_pq":
        return ivf_pq.load_path(path, res=res)
    if algo == "cagra":
        return cagra.load_path(path, dataset=jnp.asarray(data), res=res)
    raise ValueError(f"unknown mutable algo {algo!r}")
