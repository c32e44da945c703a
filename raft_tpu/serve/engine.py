"""Online query-serving engine over the raft_tpu index family.

:class:`ServingEngine` turns a stream of small, arrival-timed search
requests into the large fixed-shape micro-batches the fused Pallas
kernels were built for:

* requests enter through a futures API (:meth:`ServingEngine.submit` /
  :meth:`submit_many`) into a bounded :class:`~raft_tpu.serve.batcher.
  MicroBatcher` (typed ``QueueFull`` / ``DeadlineExceeded`` rejection,
  never unbounded latency);
* micro-batches are padded to the closed power-of-two shape vocabulary
  of :mod:`raft_tpu.serve.bucketing` and dispatched through an LRU
  :class:`~raft_tpu.serve.bucketing.ProgramCache`, so the engine only
  ever compiles ``log2(max_batch)+1`` programs per configuration;
* dispatch routes through the existing robustness machinery — fused
  kernels degrade to XLA inside ``mode="auto"`` search (see
  :mod:`raft_tpu.robust.fallback`), sharded indexes route through
  :func:`raft_tpu.robust.degrade.sharded_search_degraded` with a timed
  per-shard health probe, so a failed or *slow* shard yields a
  degraded response carrying ``coverage < 1.0`` instead of a timeout;
* the whole path is instrumented with :mod:`raft_tpu.obs`
  (``serve.queue_depth`` gauge, ``serve.time_in_queue_ms`` /
  ``serve.batch_fill`` histograms, ``serve.rejections`` counter, and a
  span per phase of a step: ``serve.step`` over ``serve.maintenance``,
  ``serve.assemble`` and ``serve.dispatch``, which holds
  ``serve.launch``, ``serve.wait`` and ``serve.fetch``, then
  ``serve.complete``) and chaos-testable at the ``serve.dispatch``
  fault seam (:mod:`raft_tpu.robust.faults`).

The engine is **synchronous by design**: :meth:`step` processes at most
one micro-batch on the caller's thread and :meth:`run_until_idle`
drains the queue, so tests and single-threaded load generators drive
it deterministically; a deployment wraps :meth:`step` in its own
thread/event loop. With obs, faults, and the serve seam all disabled,
results are bit-identical to calling ``search()`` directly with the
same parameters (``tests/test_serve.py`` gate-parity).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np

from raft_tpu import obs
from raft_tpu.core.errors import ShardFailure, expects
from raft_tpu.robust import faults
from raft_tpu.serve.batcher import (
    DeadlineExceeded,
    MicroBatcher,
    QueueFull,
    Request,
    ServeFuture,
)
from raft_tpu.serve.bucketing import (
    ProgramCache,
    ProgramKey,
    bucket_for,
    bucket_sizes,
    pad_rows,
    params_key,
)

#: algo name -> default dispatch mode at registration
_DEFAULT_MODES = {
    "brute_force": "exact",
    "ivf_flat": "auto",
    "ivf_pq": "auto",
    "cagra": "auto",
    "sharded_ivf_flat": "sharded",
    "sharded_ivf_pq_lists": "sharded",
    # pre-built TieredIndex: device scan + host-tier refine gather
    "tiered": "auto",
    # pre-built (or auto-degraded) TieredShardedIndex: per-shard HBM
    # codes behind the ring merge, per-shard host tiers for the re-rank
    "tiered_sharded": "sharded",
}

#: algos the HBM placement planner knows how to model (and whose refine
#: dataset can degrade to the host tier)
_TIERABLE_ALGOS = ("ivf_pq", "ivf_flat", "brute_force")

#: sharded algos whose refine dataset can degrade to per-shard host
#: tiers (the registration converts to algo="tiered_sharded")
_SHARDED_TIERABLE = {
    "sharded_ivf_flat": ("ivf_flat", "ivf_flat"),
    "sharded_ivf_pq_lists": ("ivf_pq", "ivf_pq_lists"),
}


@dataclasses.dataclass(frozen=True)
class ServeResult:
    """One request's response: results plus the serving telemetry and
    the health picture they were computed under."""

    distances: np.ndarray  # [m, k]
    indices: np.ndarray  # [m, k]
    #: fraction of the index that answered (1.0 on non-sharded paths)
    coverage: float = 1.0
    degraded: bool = False
    failed_shards: Tuple[int, ...] = ()
    time_in_queue_ms: float = 0.0
    bucket: int = 0
    batch_rows: int = 0
    #: mutable-index generation the answer was computed against (0 for
    #: immutable registrations) — lets clients reason about freshness
    generation: int = 0
    #: obs request trace ID ("" with the gate off) — resolves to the
    #: request's spans/flow track in the Perfetto export and to its
    #: histogram exemplars (docs/observability.md "Request traces")
    trace_id: str = ""

    def __iter__(self):  # unpack like a plain (distances, indices)
        return iter((self.distances, self.indices))


@dataclasses.dataclass
class _Registration:
    index_id: str
    algo: str
    index: object
    params: object
    mode: str
    dataset: object = None
    mesh: object = None
    axis: str = "data"
    min_coverage: float = 0.0
    #: cross-shard exchange engine for the sharded algos
    #: ("auto" | "ring" | "gather"; see parallel/sharded_ann.py)
    merge_mode: str = "auto"
    search_kwargs: Dict[str, object] = dataclasses.field(default_factory=dict)
    #: background maintenance worker for mutable registrations (None
    #: when auto-compaction is not armed)
    compactor: object = None
    #: generation of the last dispatched batch (-1 before the first) —
    #: crossing a flip bumps the ``serve.generation_flips`` counter
    last_generation: int = -1
    #: active :class:`raft_tpu.plan.RegistrationPlan` (None with the
    #: planner gate off); swapped atomically by the re-plan tick
    plan: object = None
    #: live batch-size histogram since the last plan (bucket -> batches)
    bucket_counts: Dict[int, int] = dataclasses.field(default_factory=dict)
    #: dispatched-rows/s EWMA — the traffic model's arrival-rate input
    ewma_rows_per_s: float = 0.0
    last_dispatch_t: float = -1.0
    #: k of the most recent dispatch — what a plan flip precompiles for
    last_k: int = 10


class ServingEngine:
    """Dynamic micro-batching serving engine over registered indexes.

    >>> eng = ServingEngine(max_batch=64, max_wait_ms=2.0)
    >>> eng.register("wiki", "cagra", index)
    >>> fut = eng.submit("wiki", query_rows, k=10, deadline_ms=50)
    >>> eng.run_until_idle()
    >>> res = fut.result()          # ServeResult
    """

    def __init__(
        self,
        max_batch: int = 64,
        max_wait_ms: float = 2.0,
        queue_capacity: int = 1024,
        cache_capacity: int = 64,
        clock: Optional[Callable[[], float]] = None,
        slow_shard_s: Optional[float] = 0.25,
        maintenance_interval_ms: float = 10.0,
        hbm_budget_bytes: Optional[int] = None,
        host_budget_bytes: Optional[int] = None,
    ):
        self.max_batch = int(max_batch)
        #: device-HBM budget for the placement planner (None = unplanned:
        #: every registration keeps its dataset wherever the caller put it)
        self.hbm_budget_bytes = hbm_budget_bytes
        #: per-shard host-RAM budget for the sharded three-level planner
        #: (None = unconstrained: spilled slabs stay in host RAM, never
        #: planned to disk)
        self.host_budget_bytes = host_budget_bytes
        self._residencies: Dict[str, object] = {}
        #: the planner's last verdict (an hbm_model.Placement), for
        #: introspection/tests after registrations
        self.placement = None
        #: per-registration sharded verdicts (hbm_model.ShardedPlacement),
        #: keyed by index_id — sharded registrations plan per shard and
        #: do not join the single-device fleet plan above
        self.sharded_placements: Dict[str, object] = {}
        self.batcher = MicroBatcher(
            max_batch=max_batch,
            max_wait_ms=max_wait_ms,
            capacity=queue_capacity,
            clock=clock,
        )
        self.cache = ProgramCache(capacity=cache_capacity)
        #: a health probe slower than this marks the shard unhealthy —
        #: serve degraded coverage now rather than a timeout later
        self.slow_shard_s = slow_shard_s
        #: floor between maintenance ticks driven from :meth:`step`
        self.maintenance_interval_ms = float(maintenance_interval_ms)
        self._last_maint = -float("inf")
        self._indexes: Dict[str, _Registration] = {}
        #: per-index SLO trackers (see :meth:`set_slo` / :meth:`health`)
        self._slos: Dict[str, obs.SloTracker] = {}

    # -- registration ------------------------------------------------------

    def register(
        self,
        index_id: str,
        algo: str,
        index,
        *,
        params=None,
        mode: Optional[str] = None,
        dataset=None,
        mesh=None,
        axis: str = "data",
        min_coverage: float = 0.0,
        merge_mode: str = "auto",
        **search_kwargs,
    ) -> None:
        """Register ``index`` under ``index_id``.

        ``algo`` is one of ``brute_force`` / ``ivf_flat`` / ``ivf_pq`` /
        ``cagra`` / ``sharded_ivf_flat`` / ``sharded_ivf_pq_lists``.
        ``params``/``mode``/``search_kwargs`` are pinned at registration
        and become part of every program key; ``dataset`` enables
        IVF-PQ exact re-ranking; ``mesh`` is required for the sharded
        algos, ``min_coverage`` is their floor (below it the request
        fails with :class:`~raft_tpu.core.errors.ShardFailure` rather
        than return near-empty results), and ``merge_mode`` pins their
        cross-shard exchange engine (``"auto"`` | ``"ring"`` |
        ``"gather"``).

        ``algo="tiered"`` registers a pre-built
        :class:`raft_tpu.tiered.TieredIndex` (its store, refine ratio and
        params travel with the object). With the engine's
        ``hbm_budget_bytes`` set, a ``dataset`` that the
        :mod:`~raft_tpu.ops.pallas.hbm_model` planner cannot fit next to
        the already-registered indexes is transparently rewrapped in a
        :class:`~raft_tpu.tiered.HostVectorStore` — registration degrades
        to tiered serving instead of OOMing at first dispatch.

        ``algo="tiered_sharded"`` registers a pre-built
        :class:`raft_tpu.tiered.TieredShardedIndex` (``mesh``/``axis``
        default to the index's own). A *sharded* registration with a
        ``dataset`` and the budget set runs the per-shard three-level
        planner instead: a refine slab that cannot stay device-resident
        per shard converts the registration to ``tiered_sharded`` over
        per-shard :class:`~raft_tpu.tiered.ShardedHostTier` stores —
        ring-merged winners re-rank from each shard's host.
        """
        expects(algo in _DEFAULT_MODES, "unknown serving algo %r (want one of %s)",
                algo, ", ".join(sorted(_DEFAULT_MODES)))
        if algo == "tiered_sharded" and mesh is None:
            mesh = index.mesh
            axis = index.axis
        if algo.startswith("sharded_") or algo == "tiered_sharded":
            expects(mesh is not None, "sharded algo %r needs mesh=", algo)
        if algo in _SHARDED_TIERABLE:
            algo, index, dataset = self._plan_tier_sharded(
                index_id, algo, index, dataset, mesh=mesh, axis=axis,
                merge_mode=merge_mode, params=params, search_kwargs=search_kwargs,
            )
        else:
            dataset = self._plan_tier(index_id, algo, index, dataset)
        reg = _Registration(
            index_id=index_id,
            algo=algo,
            index=index,
            params=params,
            mode=mode if mode is not None else _DEFAULT_MODES[algo],
            dataset=dataset,
            mesh=mesh,
            axis=axis,
            min_coverage=min_coverage,
            merge_mode=merge_mode,
            search_kwargs=dict(search_kwargs),
        )
        reg.plan = self._plan_registration(reg)
        self._indexes[index_id] = reg

    def _plan_tier(self, index_id: str, algo: str, index, dataset):
        """Consult the HBM placement planner for this registration.

        With no budget, or an algo the model does not cover, the dataset
        passes through untouched. Otherwise the index's measured
        residency joins the fleet plan: required (scan) components must
        fit — an infeasible plan is a typed registration error — and a
        refine dataset the plan spills is rewrapped as a
        :class:`~raft_tpu.tiered.HostVectorStore`, so dispatch gathers
        winners from host RAM instead of holding the raw f32 slab in HBM.
        """
        if self.hbm_budget_bytes is None or algo not in _TIERABLE_ALGOS:
            return dataset
        from raft_tpu.neighbors.refine import is_host_dataset
        from raft_tpu.ops.pallas.hbm_model import plan_placement, residency_for_index

        refine_rows = 0
        if dataset is not None and not is_host_dataset(dataset):
            refine_rows = int(np.shape(dataset)[0])
        res = residency_for_index(index_id, algo, index, refine_rows=refine_rows)
        fleet = [r for iid, r in self._residencies.items() if iid != index_id]
        placement = plan_placement(fleet + [res], hbm_budget=self.hbm_budget_bytes)
        expects(
            placement.feasible,
            "registering %r needs %d B of scan-resident HBM against a budget "
            "of %d B — required components cannot tier to the host; shard or "
            "shrink the index",
            index_id, sum(r.required_bytes for r in fleet) + res.required_bytes,
            self.hbm_budget_bytes,
        )
        self._residencies[index_id] = res
        self.placement = placement
        if refine_rows and placement.tier(index_id, "raw_vectors") == "host":
            from raft_tpu.tiered import HostVectorStore

            dataset = HostVectorStore(np.asarray(dataset))
            obs.inc("serve.tiered_degrades", index_id=index_id, algo=algo)
        return dataset

    def _plan_tier_sharded(
        self, index_id: str, algo: str, index, dataset, *,
        mesh, axis, merge_mode, params, search_kwargs,
    ):
        """Per-shard three-level placement for a lists-sharded
        registration. Returns the (possibly converted) ``(algo, index,
        dataset)`` triple.

        With no budget or no refine dataset (or a caller-prepared host
        store) the registration passes through untouched. Otherwise the
        index's measured residency runs through
        :func:`~raft_tpu.ops.pallas.hbm_model.plan_placement_sharded`:
        required components must fit each shard's device cap — an
        infeasible plan is a typed registration error — and a spilled
        refine slab converts the registration to a
        :class:`~raft_tpu.tiered.TieredShardedIndex` whose per-shard
        :class:`~raft_tpu.tiered.ShardedHostTier` follows the lists-
        sharded row ownership, so each candidate re-ranks from the host
        of the shard that scanned it."""
        if self.hbm_budget_bytes is None or dataset is None:
            return algo, index, dataset
        from raft_tpu.neighbors.refine import is_host_dataset

        if is_host_dataset(dataset):
            return algo, index, dataset
        from raft_tpu.ops.pallas.hbm_model import (
            plan_placement_sharded,
            residency_for_index,
        )

        res_algo, scan_algo = _SHARDED_TIERABLE[algo]
        n_shards = mesh.shape[axis]
        res = residency_for_index(
            index_id, res_algo, index, refine_rows=int(np.shape(dataset)[0])
        )
        placement = plan_placement_sharded(
            [res], n_shards,
            hbm_budget_per_shard=self.hbm_budget_bytes,
            host_budget_per_shard=self.host_budget_bytes,
        )
        expects(
            placement.feasible,
            "registering %r needs %d B/shard of scan-resident HBM over %d "
            "shards against a per-shard budget of %d B — required components "
            "cannot tier to the host; add shards or shrink the index",
            index_id, placement.device_bytes_per_shard - placement.staging_device_bytes,
            n_shards, self.hbm_budget_bytes,
        )
        self.sharded_placements[index_id] = placement
        if placement.tier(index_id, "raw_vectors") == "device":
            return algo, index, dataset
        from raft_tpu.tiered import ShardedHostTier, TieredShardedIndex

        tier_kw = {
            key: search_kwargs.pop(key)
            for key in ("refine_ratio", "micro_batch", "metric_arg")
            if key in search_kwargs
        }
        tier = ShardedHostTier.from_lists(
            index, np.asarray(dataset), n_shards,
            fetch_depth_rows=search_kwargs.pop("fetch_depth_rows", None),
        )
        tiered = TieredShardedIndex(
            mesh, scan_algo, index, tier, axis=axis,
            search_params=params, merge_mode=merge_mode, **tier_kw,
        )
        obs.inc("serve.tiered_degrades", index_id=index_id, algo=algo)
        return "tiered_sharded", tiered, None

    def register_mutable(
        self,
        index_id: str,
        mutable,
        *,
        params=None,
        policy=None,
        compactor=None,
        **search_kwargs,
    ) -> None:
        """Register a :class:`raft_tpu.mutable.MutableIndex`.

        Each micro-batch is dispatched against one immutable
        :meth:`~raft_tpu.mutable.segments.MutableIndex.snapshot` taken
        at dispatch time, so concurrent insert/delete/upsert (and
        compaction's generation flips) are atomic with respect to
        serving — a batch sees the whole mutation or none of it. The
        snapshot's generation joins the :class:`ProgramKey`, retiring
        stale programs through the LRU and bounding distinct programs
        to ``generations × (log2(max_batch)+1)`` per configuration.

        ``policy`` (a :class:`raft_tpu.mutable.CompactionPolicy`) arms
        auto-compaction: the engine starts a background
        :class:`~raft_tpu.mutable.Compactor` for the index and drives
        its watchdog/trigger tick from :meth:`step`, so a churning
        index rebuilds itself off-thread while this engine keeps
        serving snapshots. Pass a pre-built ``compactor`` instead to
        control retry policy, seed, or resources; :meth:`shutdown`
        stops engine-owned workers either way.
        """
        old = self._indexes.get(index_id)
        if old is not None and old.compactor is not None:
            old.compactor.stop()
        if compactor is None and policy is not None:
            from raft_tpu.mutable.maintenance import Compactor

            compactor = Compactor(mutable, policy=policy, name=index_id)
        if compactor is not None:
            compactor.start()
        reg = _Registration(
            index_id=index_id,
            algo="mutable",
            index=mutable,
            params=params,
            mode="snapshot",
            search_kwargs=dict(search_kwargs),
            compactor=compactor,
        )
        # no engine pick for snapshot dispatch, but the plan still
        # carries the corpus/traffic anchors the re-plan tick tracks
        reg.plan = self._plan_registration(reg)
        self._indexes[index_id] = reg

    def registered(self) -> List[str]:
        return list(self._indexes)

    # -- submission --------------------------------------------------------

    def submit(
        self,
        index_id: str,
        queries,
        k: int,
        deadline_ms: Optional[float] = None,
        trace_id: Optional[str] = None,
    ) -> ServeFuture:
        """Enqueue one request (``queries`` [m, dim] or a single [dim]
        row) and return its future. Raises :class:`QueueFull` /
        :class:`DeadlineExceeded` at admission — rejected work never
        occupies the queue. ``trace_id`` adopts an existing obs trace
        instead of minting one — how a replica group keeps one identity
        on a request across failover re-submissions
        (``docs/replication.md``)."""
        reg = self._reg(index_id)
        q = np.asarray(queries)
        if q.ndim == 1:
            q = q[None, :]
        expects(q.ndim == 2, "queries must be [m, dim] (or one [dim] row)")
        expects(
            q.shape[0] <= self.max_batch,
            "request has %d rows > max_batch %d — use submit_many to split",
            q.shape[0], self.max_batch,
        )
        now = self.batcher.now()
        req = Request(
            queries=q,
            k=int(k),
            group=(index_id, int(k)),
            t_arrival=now,
            deadline_s=(now + deadline_ms / 1e3) if deadline_ms is not None else None,
        )
        if obs.is_enabled():
            # trace identity is minted at admission: the synthetic
            # serve.queue span starts here, and every span recorded under
            # this request's dispatch carries the ID (obs/request.py)
            req.trace_id = trace_id or obs.new_trace_id()
            req.t_submit_us = obs.registry().now_us()
        try:
            self.batcher.offer(req)
        except QueueFull:
            obs.inc("serve.rejections", reason="queue_full", index_id=index_id)
            raise
        except DeadlineExceeded:
            obs.inc("serve.rejections", reason="deadline_admission", index_id=index_id)
            raise
        if obs.is_enabled():
            obs.inc("serve.requests", index_id=index_id, algo=reg.algo)
            obs.set_gauge("serve.queue_depth", self.batcher.depth_rows())
        return req.future

    def submit_many(
        self,
        index_id: str,
        queries,
        k: int,
        deadline_ms: Optional[float] = None,
        request_rows: int = 1,
    ) -> List[ServeFuture]:
        """Split ``queries`` [n, dim] into requests of ``request_rows``
        rows each and submit them all; returns one future per request."""
        q = np.asarray(queries)
        expects(q.ndim == 2, "queries must be [n, dim]")
        expects(1 <= request_rows <= self.max_batch,
                "request_rows must be in [1, max_batch]")
        return [
            self.submit(index_id, q[s : s + request_rows], k, deadline_ms=deadline_ms)
            for s in range(0, q.shape[0], request_rows)
        ]

    # -- the synchronous loop driver ---------------------------------------

    def step(self, force: bool = False) -> int:
        """Process at most one micro-batch on the calling thread.

        Flushes when the batcher says so (full bucket or aged past
        ``max_wait_ms``) or unconditionally with ``force=True``.
        Returns the number of requests completed (including deadline
        rejections)."""
        with obs.span("serve.step"):
            now = self.batcher.now()
            if now - self._last_maint >= self.maintenance_interval_ms / 1e3:
                self._last_maint = now
                self.maintenance_tick()
            if not self.batcher.ready(now) and not (force and self.batcher.depth_requests()):
                return 0
            with obs.span("serve.assemble"):
                batch, expired = self.batcher.next_batch(now)
                shaped = self._assemble(batch) if batch else None
            for r in expired:
                obs.inc("serve.rejections", reason="deadline_expired",
                        index_id=r.group[0])
                tracker = self._slos.get(r.group[0])
                if tracker is not None:
                    tracker.record(ok=False)  # shed work burns the budget
            done = len(expired)
            if batch:
                self._dispatch(batch, now, *shaped)
                done += len(batch)
            if obs.is_enabled():
                obs.set_gauge("serve.queue_depth", self.batcher.depth_rows())
            return done

    def run_until_idle(self, max_steps: int = 1_000_000) -> int:
        """Drive :meth:`step` until the queue is empty; returns requests
        completed. The safety valve ``max_steps`` bounds pathological
        loops (it is not a rate limit)."""
        total = 0
        for _ in range(max_steps):
            if not self.batcher.depth_requests():
                break
            total += self.step(force=True)
        return total

    def queue_depth(self) -> int:
        return self.batcher.depth_rows()

    def evict_queued(self) -> List[Request]:
        """Evacuate every queued request without completing its future
        (see :meth:`~raft_tpu.serve.batcher.MicroBatcher.drain_requests`).
        The replica layer calls this when this engine's replica is
        declared dead, then re-queues the evicted work on a healthy
        replica — the queue must not keep rows a failed engine will
        never serve."""
        out = self.batcher.drain_requests()
        if obs.is_enabled():
            obs.set_gauge("serve.queue_depth", self.batcher.depth_rows())
        return out

    # -- SLOs and health ---------------------------------------------------

    def set_slo(
        self,
        index_id: str,
        *,
        latency_ms: Optional[float] = None,
        target: float = 0.999,
        window_s: float = 3600.0,
        fast_window_s: float = 60.0,
        slow_window_s: float = 300.0,
        burn_threshold: float = 10.0,
    ) -> obs.SloTracker:
        """Declare a latency/availability objective for a registered
        index. Every completed request records against it (a request is
        *bad* when it errors, is shed past its deadline, or — with
        ``latency_ms`` set — finishes slower than the threshold,
        measured arrival→completion on the engine clock). The tracker
        shares the engine's injectable clock, so virtual-time tests
        drive burn-rate windows deterministically. Returns the tracker;
        :meth:`health` surfaces its :meth:`~raft_tpu.obs.SloTracker.
        evaluate` snapshot."""
        self._reg(index_id)  # must be registered
        tracker = obs.SloTracker(
            obs.SLO(
                index_id=index_id,
                latency_ms=latency_ms,
                target=target,
                window_s=window_s,
                fast_window_s=fast_window_s,
                slow_window_s=slow_window_s,
                burn_threshold=burn_threshold,
            ),
            clock=self.batcher.now,
        )
        self._slos[index_id] = tracker
        return tracker

    def slo_burn(self, index_id: str) -> Optional[float]:
        """The index's fast-window SLO burn rate right now (None when
        no SLO is declared) — the scalar the replica autoscaler feeds
        its scale-up threshold."""
        tracker = self._slos.get(index_id)
        if tracker is None:
            return None
        return tracker.evaluate().burn_fast

    def health(self) -> Dict[str, object]:
        """Structured health snapshot: queue + cache pressure, span-drop
        signal, and per-index registration state with SLO budget/burn
        status (``docs/serving.md``; the substrate the replicated-serving
        and SLA-adaptive roadmap items read)."""
        cache_stats = self.cache.stats()
        out: Dict[str, object] = {
            "queue": {
                "depth_rows": self.batcher.depth_rows(),
                "depth_requests": self.batcher.depth_requests(),
                "capacity": self.batcher.capacity,
            },
            "cache": {
                "hits": cache_stats.hits,
                "misses": cache_stats.misses,
                "evictions": cache_stats.evictions,
                "size": cache_stats.size,
            },
            "obs": {
                "enabled": obs.is_enabled(),
                "spans_dropped": obs.registry().spans_dropped,
            },
            "indexes": {},
        }
        for index_id, reg in self._indexes.items():
            entry: Dict[str, object] = {
                "algo": reg.algo,
                "mode": reg.mode,
                "generation": max(reg.last_generation, 0),
            }
            tracker = self._slos.get(index_id)
            entry["slo"] = tracker.evaluate().as_dict() if tracker else None
            out["indexes"][index_id] = entry
        return out

    # -- maintenance -------------------------------------------------------

    def maintenance_tick(self) -> None:
        """One watchdog + auto-compaction pass over every registration
        that carries a :class:`~raft_tpu.mutable.Compactor`, followed by
        the planner's drift check (:meth:`_replan_tick`). Driven
        from :meth:`step` (rate-limited by ``maintenance_interval_ms``)
        so serving loops get background maintenance for free; callable
        directly by deployments with their own schedulers."""
        with obs.span("serve.maintenance"):
            for reg in list(self._indexes.values()):
                if reg.compactor is not None:
                    reg.compactor.tick()
            self._replan_tick()
            # flight-recorder sampler tick: retains the serving time series
            # and drains any fault-latched dump; no-op unless a recorder is
            # installed AND obs is enabled
            obs.recorder.tick()

    def shutdown(self, wait: bool = True) -> None:
        """Stop every engine-owned background compactor. Queued
        requests stay queued — this only halts maintenance threads."""
        for reg in self._indexes.values():
            if reg.compactor is not None:
                reg.compactor.stop(wait=wait)

    # -- precompile --------------------------------------------------------

    def warmup(self, index_id: str, k: int, run: bool = True) -> List[ProgramKey]:
        """Build (and with ``run=True`` execute on zero queries, forcing
        the XLA compile) every bucket's program for ``(index_id, k)`` —
        the deploy-time precompile API. Returns the keys warmed."""
        reg = self._reg(index_id)
        snap = reg.index.snapshot() if reg.algo == "mutable" else None
        generation = snap.generation if snap is not None else 0
        keys = [
            ProgramKey(index_id, reg.algo, b, int(k),
                       self._program_params(reg, b), generation)
            for b in bucket_sizes(self.max_batch)
        ]
        built = self.cache.warmup(
            keys, lambda key: (lambda: self._build_program(reg, key.bucket, key.k))
        )
        if run:
            dim = self._index_dim(reg)
            for key in keys:
                prog = self.cache.get(
                    key, lambda: self._build_program(reg, key.bucket, key.k)
                )
                zeros = np.zeros((key.bucket, dim), np.float32)
                out = tuple(prog(zeros, snap) if snap is not None else prog(zeros))
                np.asarray(out[0])  # block until the compile+run completes  # graft-lint: ignore[sync-transfer-in-loop] — warmup exists to block on each compile
        return built

    # -- internals ---------------------------------------------------------

    def _reg(self, index_id: str) -> _Registration:
        expects(index_id in self._indexes, "no index registered as %r", index_id)
        return self._indexes[index_id]

    @staticmethod
    def _index_dim(reg: _Registration) -> int:
        idx = reg.index
        if hasattr(idx, "dim"):
            return int(idx.dim)
        return int(np.asarray(idx.dataset).shape[1])

    def _probe_health_timed(self, reg: _Registration) -> Tuple[bool, ...]:
        """Per-shard health through the ``sharded_ann.shard_scan`` fault
        point, with a latency budget: a probe slower than
        ``slow_shard_s`` marks the shard unhealthy so the query degrades
        coverage instead of waiting out a slow shard (the FusionANNS
        tail-tolerance policy)."""
        mesh, axis, algo = reg.mesh, reg.axis, reg.algo.replace("sharded_", "")
        n_shards = mesh.shape[axis]
        health = []
        for s in range(n_shards):
            t0 = time.perf_counter()
            try:
                faults.fire("sharded_ann.shard_scan", shard=s, algo=algo, axis=axis)
                ok = True
            except ShardFailure:
                obs.inc("robust.shard_failures", algo=algo, shard=str(s))
                ok = False
            if ok and self.slow_shard_s is not None:
                if time.perf_counter() - t0 > self.slow_shard_s:
                    obs.inc("serve.slow_shards", index_id=reg.index_id, shard=str(s))
                    ok = False
            health.append(ok)
        return tuple(health)

    # -- query planning ----------------------------------------------------

    def _tier_label(self, reg: _Registration) -> str:
        """Placement verdict recorded on the plan ("" = unplanned)."""
        if reg.algo in ("tiered", "tiered_sharded"):
            return reg.algo
        if reg.dataset is not None:
            from raft_tpu.neighbors.refine import is_host_dataset

            if is_host_dataset(reg.dataset):
                return "tiered"
        if reg.index_id in self._residencies or reg.index_id in self.sharded_placements:
            return "resident"
        return ""

    @staticmethod
    def _corpus_rows(reg: _Registration) -> int:
        try:
            return int(getattr(reg.index, "size", 0) or 0)
        except (TypeError, ValueError):
            return 0

    def _plan_registration(self, reg: _Registration, k: Optional[int] = None,
                           traffic=None, epoch: int = 0):
        """Cost this registration's full decision set (None = gate off).

        ``fused_ok`` is passed optimistically: a planned ``fused``
        dispatches as ``"auto"`` (see :meth:`_planned_mode`), so the
        search's own kernel-feasibility check remains authoritative and
        the fused→scan/xla degrade contract is preserved."""
        from raft_tpu import plan as _plan

        if not _plan.is_enabled():
            return None
        import jax

        n_shards = reg.mesh.shape[reg.axis] if reg.mesh is not None else 0
        with obs.span("plan.build", index_id=reg.index_id, algo=reg.algo,
                      epoch=epoch):
            return _plan.plan_registration(
                reg.index_id,
                reg.algo,
                buckets=bucket_sizes(self.max_batch),
                corpus_rows=self._corpus_rows(reg),
                on_tpu=jax.default_backend() == "tpu",
                fused_ok=True,
                n_shards=n_shards,
                k=int(k if k is not None else reg.last_k),
                tier=self._tier_label(reg),
                mode_pinned=reg.mode != "auto",
                merge_pinned=reg.merge_mode != "auto",
                traffic=traffic,
                epoch=epoch,
            )

    def _planned_mode(self, reg: _Registration, bucket: int,
                      plan=None) -> Optional[str]:
        """The plan's resolved engine for this bucket (None = dispatch
        on ``reg.mode`` unchanged). A planned ``fused`` is dispatched as
        ``"auto"``: the search re-resolves to fused by the same
        calibration when the kernel is actually feasible, and keeps the
        documented auto-degrade path when it is not."""
        plan = plan if plan is not None else reg.plan
        if plan is None or reg.mode != "auto":
            return None
        m = plan.mode_for(bucket, "")
        if not m:
            return None
        return "auto" if m == "fused" else m

    def _program_params(self, reg: _Registration, bucket: int,
                        plan=None) -> Tuple:
        """Params tuple for the ProgramKey: the registration params plus
        the planned engine when one applies, so a plan flip that changes
        a bucket's engine compiles a distinct program (bounded by
        engines × buckets) and one that does not reuses the cache."""
        pk = params_key(reg.params)
        m = self._planned_mode(reg, bucket, plan=plan)
        if m is not None:
            pk = pk + (("planned_mode", m),)
        return pk

    def plan_explain(self, index_id: str) -> Optional[str]:
        """The active plan's full cost breakdown (None = planner off)."""
        reg = self._reg(index_id)
        return reg.plan.explain() if reg.plan is not None else None

    def _warm_plan(self, reg: _Registration, new_plan) -> List[ProgramKey]:
        """Precompile the new plan's warm buckets BEFORE the swap, so a
        flip never pays an XLA compile on the serving path."""
        if reg.mode != "auto" or not new_plan.bucket_modes:
            return []
        keys = [
            ProgramKey(reg.index_id, reg.algo, b, int(reg.last_k),
                       self._program_params(reg, b, plan=new_plan), 0)
            for b in new_plan.warm_buckets
            if new_plan.mode_for(b, "")
        ]
        if not keys:
            return []
        dim = self._index_dim(reg)
        for key in keys:
            prog = self.cache.get(
                key, lambda: self._build_program(reg, key.bucket, key.k,
                                                 plan=new_plan)
            )
            zeros = np.zeros((key.bucket, dim), np.float32)
            out = tuple(prog(zeros))
            np.asarray(out[0])  # block until the compile+run completes  # graft-lint: ignore[sync-transfer-in-loop] — flip warmup exists to block on each compile
        return keys

    def _replan_tick(self) -> None:
        """Re-cost every planned registration whose corpus/traffic has
        drifted past the hysteresis thresholds; swap the plan atomically
        when a decision changed (``serve.plan_flips``), refresh the
        anchors when not (``serve.plan.recosts``)."""
        from raft_tpu import plan as _plan

        if not _plan.is_enabled():
            return
        with obs.span("plan.replan"):
            for reg in list(self._indexes.values()):
                rp = reg.plan
                if rp is None:
                    continue
                traffic = _plan.traffic_from_counts(
                    reg.bucket_counts, reg.ewma_rows_per_s)
                rows = self._corpus_rows(reg)
                if not _plan.needs_replan(rp, rows, traffic):
                    continue
                new = self._plan_registration(
                    reg, k=reg.last_k, traffic=traffic, epoch=rp.epoch + 1)
                if new is None:
                    continue
                if rp.same_decisions(new):
                    # drift acknowledged, decisions unchanged: re-anchor
                    # without burning an epoch (or a compile)
                    reg.plan = dataclasses.replace(new, epoch=rp.epoch)
                    obs.inc("serve.plan.recosts", index_id=reg.index_id)
                    continue
                with obs.span("plan.flip", index_id=reg.index_id,
                              epoch=new.epoch, algo=reg.algo):
                    self._warm_plan(reg, new)
                    # one assignment: a concurrent dispatch reads the old
                    # plan or the new one, never a mix
                    reg.plan = new
                reg.bucket_counts = {}
                obs.inc("serve.plan_flips", index_id=reg.index_id)
                obs.set_gauge("serve.plan.epoch", float(new.epoch),
                              index_id=reg.index_id)
                # flight-recorder trigger: the swap is complete and no
                # engine lock is held here
                obs.recorder.note_plan_flip(reg.index_id, int(new.epoch))

    def _build_program(self, reg: _Registration, bucket: int, k: int,
                       plan=None) -> Callable:
        """One dispatchable closure for ``(reg, bucket, k)``; its jitted
        inner search is XLA-cached by the bucket's fixed shape."""
        from raft_tpu.neighbors import brute_force, cagra, ivf_flat, ivf_pq

        kw = reg.search_kwargs
        # the planner's resolved engine for this bucket ("auto" for a
        # planned fused — the search's own feasibility check decides)
        mode = self._planned_mode(reg, bucket, plan=plan) or reg.mode
        if reg.algo == "mutable":
            # the snapshot is NOT baked into the closure — it arrives per
            # dispatch, so a cached program can never serve a stale view
            return lambda q, snap: snap.search(q, k, params=reg.params, **kw)
        if reg.algo == "tiered":
            # "auto" defers to the TieredIndex's own per-family default
            t_mode = None if reg.mode == "auto" else reg.mode
            return lambda q: reg.index.search(q, k, mode=t_mode, **kw)
        if reg.algo == "brute_force":
            return lambda q: brute_force.search(
                reg.index, q, k, query_batch=bucket, mode=reg.mode,
                dataset=reg.dataset, **kw
            )
        if reg.algo == "ivf_flat":
            return lambda q: ivf_flat.search(
                reg.index, q, k, reg.params, query_batch=bucket, mode=mode,
                dataset=reg.dataset, **kw
            )
        if reg.algo == "ivf_pq":
            return lambda q: ivf_pq.search(
                reg.index, q, k, reg.params, query_batch=bucket, mode=mode,
                dataset=reg.dataset, **kw
            )
        if reg.algo == "cagra":
            return lambda q: cagra.search(
                reg.index, q, k, reg.params, query_batch=bucket, mode=mode, **kw
            )
        if reg.algo == "tiered_sharded":
            # the composition path: timed health probe feeds the scan-side
            # mask, tier-side failures are detected in-line by the gather;
            # the index returns a DegradedResult with combined coverage
            def tiered_sharded_prog(q):
                health = self._probe_health_timed(reg)
                return reg.index.search(
                    q, k, health=health, min_coverage=reg.min_coverage,
                    merge_mode=None if reg.merge_mode == "auto" else reg.merge_mode,
                    **kw,
                )

            return tiered_sharded_prog
        # sharded paths ride the degraded-search machinery: per-dispatch
        # timed health probe, failed/slow shards excluded, coverage out
        from raft_tpu.robust.degrade import sharded_search_degraded

        algo = reg.algo.replace("sharded_", "")

        def sharded_prog(q):
            health = self._probe_health_timed(reg)
            return sharded_search_degraded(
                reg.mesh, reg.index, q, k,
                algo=algo, params=reg.params, axis=reg.axis,
                health=health, min_coverage=reg.min_coverage,
                merge_mode=reg.merge_mode, **kw,
            )

        return sharded_prog

    def _assemble(self, batch: Sequence[Request]) -> Tuple[int, int, np.ndarray]:
        """The batch's row count, its bucket, and its requests' query
        rows concatenated and padded to the bucket: ``(n, bucket,
        padded)``."""
        rows = np.concatenate([r.queries for r in batch], axis=0)
        n = rows.shape[0]
        bucket = bucket_for(n, self.max_batch)
        return n, bucket, pad_rows(rows, bucket)

    def _dispatch(self, batch: Sequence[Request], now: float, n: int, bucket: int,
                  padded: np.ndarray) -> None:
        """Fetch the batch's compiled program, run it on the ``padded``
        rows, and complete every request's future. A dispatch failure
        fails this batch's futures — typed and visible — and the engine
        keeps serving."""
        reg = self._reg(batch[0].group[0])
        k = batch[0].group[1]
        # one snapshot per micro-batch: every request in the batch sees
        # the same immutable view, and writers never race the dispatch
        snap = reg.index.snapshot() if reg.algo == "mutable" else None
        generation = snap.generation if snap is not None else 0
        if snap is not None:
            # a batch that crosses a background flip lands wholly on one
            # side of it (this snapshot); count the crossing
            if reg.last_generation >= 0 and generation != reg.last_generation:
                obs.inc("serve.generation_flips", index_id=reg.index_id)
            reg.last_generation = generation
        # traffic model inputs: the batch-size histogram and arrival-rate
        # EWMA the re-plan tick measures drift against
        reg.bucket_counts[bucket] = reg.bucket_counts.get(bucket, 0) + 1
        reg.last_k = k
        if reg.last_dispatch_t >= 0.0:
            rate = n / max(now - reg.last_dispatch_t, 1e-6)
            reg.ewma_rows_per_s = 0.25 * rate + 0.75 * reg.ewma_rows_per_s
        reg.last_dispatch_t = now
        key = ProgramKey(
            reg.index_id, reg.algo, bucket, k,
            self._program_params(reg, bucket), generation
        )
        tracker = self._slos.get(reg.index_id)
        # the batch's trace identities ride the dispatch thread: every
        # span recorded below (dispatch, degrade, tiered fetch/refine)
        # is tagged with them; NULL_SCOPE keeps the disabled path free of
        # per-dispatch allocation
        scope = (
            obs.trace_scope(tuple(r.trace_id for r in batch))
            if obs.is_enabled() else obs.NULL_SCOPE
        )
        try:
            with scope, obs.span(
                "serve.dispatch", algo=reg.algo, bucket=bucket, rows=n, k=k
            ):
                with obs.span("serve.launch"):
                    program = self.cache.get(
                        key, lambda: self._build_program(reg, bucket, k)
                    )
                    # the chaos seam: one host-level hook before the device work
                    faults.fire(
                        "serve.dispatch",
                        index_id=reg.index_id, algo=reg.algo, bucket=bucket, rows=n,
                    )
                    t0 = time.perf_counter()
                    out = program(padded, snap) if snap is not None else program(padded)
                    # queue the copies back behind the program now, so the
                    # wait below adds no host round trip before them
                    for a in tuple(out):
                        if isinstance(a, jax.Array):
                            a.copy_to_host_async()
                with obs.span("serve.wait"):
                    jax.block_until_ready(tuple(out))
                with obs.span("serve.fetch"):
                    d_np = np.asarray(out.distances if hasattr(out, "distances") else out[0])
                    i_np = np.asarray(out.indices if hasattr(out, "indices") else out[1])
            self.batcher.note_service_time(time.perf_counter() - t0)
            coverage, degraded, failed = 1.0, False, ()
            if hasattr(out, "coverage"):  # DegradedResult from sharded paths
                coverage, degraded, failed = out.coverage, out.degraded, out.failed_shards
        except Exception as e:
            obs.inc("serve.dispatch_errors", index_id=reg.index_id,
                    kind=type(e).__name__)
            for r in batch:
                r.future.set_exception(e)
                if tracker is not None:
                    tracker.record(ok=False)
            return
        with obs.span("serve.complete"):
            if obs.is_enabled():
                obs.inc("serve.batches", index_id=reg.index_id, algo=reg.algo)
                obs.observe("serve.batch_fill", n / bucket)
                obs.observe("serve.batch_rows", float(n))
                # per-index result coverage (1.0 unless a sharded path
                # degraded) — the coverage-drop drift detector's input
                obs.set_gauge("serve.coverage", float(coverage),
                              index_id=reg.index_id)
                if snap is not None:
                    obs.set_gauge("serve.generation", float(generation),
                                  index_id=reg.index_id)
            t_done = self.batcher.now() if tracker is not None else now
            off = 0
            for r in batch:
                m = r.n_rows
                tiq_ms = (now - r.t_arrival) * 1e3
                if obs.is_enabled():
                    obs.observe("serve.time_in_queue_ms", tiq_ms,
                                trace_id=r.trace_id or None)
                    if r.trace_id:
                        # synthetic per-request queue span on its own track
                        # (tid derived from req_id): the first hop of the
                        # request's flow chain in the Perfetto export
                        obs.registry().record_span(
                            "serve.queue", r.t_submit_us, max(tiq_ms, 0.0) * 1e3,
                            0x40000000 + (r.req_id % 0x3FFFFFFF), 0,
                            {"index_id": reg.index_id, "rows": m},
                            trace=(r.trace_id,),
                        )
                r.future.set_result(
                    ServeResult(
                        distances=d_np[off : off + m],
                        indices=i_np[off : off + m],
                        coverage=float(coverage),
                        degraded=bool(degraded),
                        failed_shards=tuple(failed),
                        time_in_queue_ms=tiq_ms,
                        bucket=bucket,
                        batch_rows=n,
                        generation=generation,
                        trace_id=r.trace_id,
                    )
                )
                if tracker is not None:
                    tracker.record(latency_ms=(t_done - r.t_arrival) * 1e3)
                off += m
