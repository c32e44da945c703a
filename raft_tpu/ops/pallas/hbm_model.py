"""HBM residency model and device/host placement planner.

Sibling of :mod:`raft_tpu.ops.pallas.vmem_model`, one level up the memory
hierarchy: where the VMEM model accounts for what one *grid step* keeps
live on-core, this module accounts for what a whole *index* keeps live in
device HBM — codes, coarse centroids, id maps, mutable delta banks, and
(optionally) the raw f32 vectors the refine re-rank reads.

The accounting drives :func:`plan_placement`: given every registered
index and an HBM budget, decide per component whether it lives on the
device or in host RAM. The rule mirrors the FusionANNS split (ROADMAP
item 2): components the *scan* touches every query (``required=True`` —
codes, centroids, ids, norms, graph) must be device-resident or the
registration is infeasible; the raw-vector slab the *refine* touches
only for ``k * refine_ratio`` winners per query (``required=False``) is
device-resident while budget remains and spills to the host tier
otherwise, where :mod:`raft_tpu.tiered` serves it via an overlapped
per-batch gather.

Estimates are exact for the dominant buffers (they are computed from the
same ``shape x itemsize`` arithmetic that allocates them — tests assert
model == ``arr.nbytes`` on built indexes) and deliberately omit
transient compile/workspace allocations, which the headroom fraction
absorbs.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, Iterable, Optional, Sequence, Tuple

#: Per-device HBM on current TPU generations (v4: 32 GiB, v5e: 16 GiB).
#: A *budget*, not a limit — callers pass the slice of HBM the index
#: tier may plan for; the remainder belongs to XLA workspaces and the
#: serving engine's program cache.
HBM_DEFAULT_BUDGET_BYTES = 16 * 1024 * 1024 * 1024

#: Fraction of the stated budget the planner fills. The rest absorbs
#: what the model cannot see: fragmentation, donation copies, and the
#: compiler's scratch HBM.
HBM_HEADROOM = 0.9

#: Staging-slab model defaults. A spilled index gathers its refine rows
#: through the host tier's double-buffered staging
#: (``HostVectorStore._staging``): two host buffers of
#: ``[micro_batch, n_cand, dim]`` plus the one in-flight transfer slab
#: in device HBM. ``k * refine_ratio`` is not known at planning time, so
#: the planner charges this nominal candidate width (the serving
#: defaults: micro_batch 256, k 10 x refine_ratio ~6 rounded up).
STAGING_MICRO_BATCH = 256
STAGING_N_CAND = 64


@dataclasses.dataclass(frozen=True)
class HbmComponent:
    """One HBM-resident buffer of an index.

    ``required=True`` marks buffers the per-query *scan* reads (codes,
    centroids, ids): these cannot leave the device without losing the
    fused kernels. ``required=False`` marks the refine raw-vector slab,
    which :func:`plan_placement` may move to the host tier.

    ``replicated=True`` marks buffers every shard of a lists-sharded
    search keeps whole (coarse centroids, rotation, PQ codebook —
    everything ``sharded_ann`` device_puts with a replicated spec);
    :func:`plan_placement_sharded` charges them at full size per shard
    instead of ``1/n_shards``."""

    name: str
    shape: Tuple[int, ...]
    itemsize: int
    required: bool = True
    replicated: bool = False

    @property
    def nbytes(self) -> int:
        return int(math.prod(self.shape)) * self.itemsize

    def per_shard_bytes(self, n_shards: int) -> int:
        """Bytes this component costs on EACH shard of an
        ``n_shards``-way lists-sharded placement."""
        if self.replicated or n_shards <= 1:
            return self.nbytes
        return -(-self.nbytes // n_shards)  # ceil


def staging_footprint(
    dim: int,
    itemsize: int = 4,
    *,
    micro_batch: int = STAGING_MICRO_BATCH,
    n_cand: int = STAGING_N_CAND,
) -> Tuple[int, int]:
    """``(host_bytes, device_bytes)`` staging cost of ONE index whose
    raw slab lives on the host tier: two host buffers (double buffering
    — slab *i* stays valid for the in-flight refine while *i+1* fills)
    plus the one in-flight ``[micro_batch, n_cand, dim]`` transfer slab
    the refine jit holds in device HBM."""
    slab = int(micro_batch) * int(n_cand) * int(dim) * int(itemsize)
    return 2 * slab, slab


@dataclasses.dataclass(frozen=True)
class IndexResidency:
    """The model's full HBM accounting for one registered index."""

    index_id: str
    algo: str
    components: Tuple[HbmComponent, ...]

    @property
    def total_bytes(self) -> int:
        return sum(c.nbytes for c in self.components)

    @property
    def required_bytes(self) -> int:
        """Bytes that must stay device-resident for the scan to run."""
        return sum(c.nbytes for c in self.components if c.required)

    @property
    def optional_bytes(self) -> int:
        """Bytes eligible for the host tier (refine raw vectors)."""
        return sum(c.nbytes for c in self.components if not c.required)

    def by_name(self, name: str) -> HbmComponent:
        for c in self.components:
            if c.name == name:
                return c
        raise KeyError(name)

    def table(self) -> str:
        rows = [
            "%-14s %-18s %12d B  [%s]"
            % (c.name, "x".join(map(str, c.shape)), c.nbytes,
               "scan" if c.required else "refine")
            for c in self.components
        ]
        rows.append("total: %d B (%.2f GiB)" % (self.total_bytes, self.total_bytes / 2**30))
        return "\n".join(rows)


def _dataset_component(n_rows: int, dim: int, itemsize: int = 4) -> HbmComponent:
    return HbmComponent("raw_vectors", (n_rows, dim), itemsize, required=False)


def ivf_pq_residency(
    index_id: str,
    *,
    n_rows: int,
    dim: int,
    n_lists: int,
    pq_dim: int,
    pq_bits: int,
    ksub: int = 256,
    rot_dim: Optional[int] = None,
    max_list: Optional[int] = None,
    rabitq: bool = False,
    refine_rows: int = 0,
    refine_itemsize: int = 4,
) -> IndexResidency:
    """HBM residency of an IVF-PQ (or IVF-RaBitQ) index.

    ``refine_rows > 0`` adds the optional raw-vector slab the integrated
    refine path gathers from (``refine_rows`` is usually ``n_rows``)."""
    max_list = max_list or math.ceil(n_rows / max(n_lists, 1))
    rot = rot_dim or dim
    bpr = max(1, (pq_dim * pq_bits + 7) // 8)  # bytes per packed row
    comps = [
        HbmComponent("codes", (n_lists, max_list, bpr), 1),
        HbmComponent("centers", (n_lists, dim), 4, replicated=True),
        HbmComponent("ids", (n_lists, max_list), 4),
    ]
    if rabitq:
        # RaBitQ: 1 bit/dim codes already counted via bpr; per-row f32
        # correction factors replace the PQ codebook.
        comps.append(HbmComponent("corrections", (n_lists, max_list, 2), 4))
    else:
        comps.append(HbmComponent("codebook", (pq_dim, ksub, rot // max(pq_dim, 1)), 4,
                                  replicated=True))
        comps.append(HbmComponent("rotation", (rot, dim), 4, replicated=True))
    if refine_rows > 0:
        comps.append(_dataset_component(refine_rows, dim, refine_itemsize))
    return IndexResidency(index_id, "ivf_rabitq" if rabitq else "ivf_pq", tuple(comps))


def ivf_flat_residency(
    index_id: str,
    *,
    n_rows: int,
    dim: int,
    n_lists: int,
    itemsize: int = 4,
    max_list: Optional[int] = None,
    refine_rows: int = 0,
    refine_itemsize: int = 4,
) -> IndexResidency:
    """HBM residency of an IVF-Flat index (list-major padded storage)."""
    max_list = max_list or math.ceil(n_rows / max(n_lists, 1))
    comps = [
        HbmComponent("list_data", (n_lists, max_list, dim), itemsize),
        HbmComponent("centers", (n_lists, dim), 4, replicated=True),
        HbmComponent("ids", (n_lists, max_list), 4),
        HbmComponent("norms", (n_lists, max_list), 4),
    ]
    if refine_rows > 0:
        comps.append(_dataset_component(refine_rows, dim, refine_itemsize))
    return IndexResidency(index_id, "ivf_flat", tuple(comps))


def brute_force_residency(
    index_id: str,
    *,
    n_rows: int,
    dim: int,
    itemsize: int = 4,
    has_norms: bool = True,
    refine_rows: int = 0,
    refine_itemsize: int = 4,
) -> IndexResidency:
    """HBM residency of a brute-force index. With ``refine_rows`` the
    scan copy may be a narrow dtype (bf16) while the refine slab holds
    the f32 originals."""
    comps = [HbmComponent("dataset", (n_rows, dim), itemsize)]
    if has_norms:
        comps.append(HbmComponent("norms", (n_rows,), 4))
    if refine_rows > 0:
        comps.append(_dataset_component(refine_rows, dim, refine_itemsize))
    return IndexResidency(index_id, "brute_force", tuple(comps))


def cagra_residency(
    index_id: str,
    *,
    n_rows: int,
    dim: int,
    graph_degree: int,
    itemsize: int = 4,
) -> IndexResidency:
    """HBM residency of a CAGRA graph index (dataset + fixed-degree
    neighbor graph, both scanned every query)."""
    # sharded CAGRA shards queries, not the graph: both buffers are
    # replicated on every shard
    return IndexResidency(index_id, "cagra", (
        HbmComponent("dataset", (n_rows, dim), itemsize, replicated=True),
        HbmComponent("graph", (n_rows, graph_degree), 4, replicated=True),
    ))


def device_free_bytes(device=None) -> int:
    """HBM the allocator of ``device`` (default: the first device) can
    still hand out — ``bytes_limit - bytes_in_use`` from
    ``memory_stats()``. A backend that reports no stats (the CPU) gets
    :data:`HBM_DEFAULT_BUDGET_BYTES`."""
    import jax

    stats = (device or jax.devices()[0]).memory_stats() or {}
    if "bytes_limit" not in stats:
        return HBM_DEFAULT_BUDGET_BYTES
    return int(stats["bytes_limit"]) - int(stats.get("bytes_in_use", 0))


def delta_bank_residency(
    index_id: str,
    *,
    cap: int,
    dim: int,
    bank_rows: int = 1024,
) -> IndexResidency:
    """HBM residency of a mutable index's delta segment: the po2-padded
    f32 brute-force rows plus per-bank norms (see
    :mod:`raft_tpu.mutable.segments` — past ``bank_rows`` the fused scan
    tiles the delta into ``ceil(cap / bank_rows)`` banks)."""
    banks = max(1, math.ceil(cap / bank_rows))
    return IndexResidency(index_id, "mutable_delta", (
        HbmComponent("delta_rows", (cap, dim), 4),
        HbmComponent("delta_norms", (cap,), 4),
        HbmComponent("delta_ids", (banks, min(cap, bank_rows)), 4),
    ))


def residency_for_index(index_id: str, algo: str, index, *,
                        refine_rows: int = 0) -> IndexResidency:
    """Model a *built* index object by reading its buffer shapes, so the
    estimate matches allocation exactly (tests assert component nbytes ==
    the live arrays' nbytes)."""
    if algo in ("ivf_pq", "ivf_rabitq"):
        # replicated flags follow the device_put specs of the lists-
        # sharded scan: centroids / rotation / codebook go up with P()
        # (every shard keeps them whole), codes / ids / norms with P(axis)
        comps = [
            HbmComponent("codes", tuple(index.codes.shape), index.codes.dtype.itemsize),
            HbmComponent("centers", tuple(index.centers.shape), index.centers.dtype.itemsize,
                         replicated=True),
            HbmComponent("centers_rot", tuple(index.centers_rot.shape),
                         index.centers_rot.dtype.itemsize, replicated=True),
            HbmComponent("rotation", tuple(index.rotation.shape), index.rotation.dtype.itemsize,
                         replicated=True),
            HbmComponent("codebook", tuple(index.pq_centers.shape),
                         index.pq_centers.dtype.itemsize, replicated=True),
            HbmComponent("ids", tuple(index.list_indices.shape), index.list_indices.dtype.itemsize),
            HbmComponent("sqnorms", tuple(index.rot_sqnorms.shape),
                         index.rot_sqnorms.dtype.itemsize),
        ]
        corr = getattr(index, "corrections", None)
        if corr is not None:
            comps.append(HbmComponent("corrections", tuple(corr.shape), corr.dtype.itemsize))
    elif algo == "ivf_flat":
        comps = [
            HbmComponent("list_data", tuple(index.list_data.shape), index.list_data.dtype.itemsize),
            HbmComponent("centers", tuple(index.centers.shape), index.centers.dtype.itemsize,
                         replicated=True),
            HbmComponent("ids", tuple(index.list_indices.shape), index.list_indices.dtype.itemsize),
            HbmComponent("norms", tuple(index.list_norms.shape), index.list_norms.dtype.itemsize),
        ]
    elif algo == "brute_force":
        comps = [HbmComponent("dataset", tuple(index.dataset.shape), index.dataset.dtype.itemsize)]
        if index.norms is not None:
            comps.append(HbmComponent("norms", tuple(index.norms.shape), index.norms.dtype.itemsize))
    elif algo == "cagra":
        comps = [
            HbmComponent("dataset", tuple(index.dataset.shape), index.dataset.dtype.itemsize),
            HbmComponent("graph", tuple(index.graph.shape), index.graph.dtype.itemsize),
        ]
    else:
        raise KeyError(f"no HBM residency model for algo {algo!r}")
    if refine_rows > 0:
        dim = comps[0].shape[-1] if algo in ("brute_force", "cagra") else (
            index.centers.shape[-1])
        comps.append(_dataset_component(refine_rows, dim))
    return IndexResidency(index_id, algo, tuple(comps))


@dataclasses.dataclass(frozen=True)
class Placement:
    """The planner's verdict for a set of indexes under one budget.

    ``tiers`` maps ``index_id -> {component_name -> "device" | "host"}``.
    ``feasible`` is False when even the required (scan) components
    overflow the budget — the caller must shard or shrink, there is no
    host tier for codes."""

    hbm_budget: int
    tiers: Dict[str, Dict[str, str]]
    device_bytes: int
    host_bytes: int
    feasible: bool
    #: double-buffered host staging slabs of spilled indexes (2x each)
    staging_host_bytes: int = 0
    #: in-flight gather transfer slabs of spilled indexes (1x each),
    #: included in ``device_bytes``
    staging_device_bytes: int = 0

    def tier(self, index_id: str, component: str) -> str:
        return self.tiers[index_id][component]

    def spilled(self, index_id: str) -> bool:
        """Does any component of ``index_id`` live off the device?"""
        return any(t != "device" for t in self.tiers[index_id].values())

    def table(self) -> str:
        rows = []
        for iid, comps in sorted(self.tiers.items()):
            for name, tier in comps.items():
                rows.append("%-20s %-14s -> %s" % (iid, name, tier))
        if self.staging_host_bytes or self.staging_device_bytes:
            rows.append(
                "staging: host %.2f MiB (2x double-buffer)  device %.2f MiB (transfer)"
                % (self.staging_host_bytes / 2**20, self.staging_device_bytes / 2**20)
            )
        rows.append(
            "device: %.2f GiB  host: %.2f GiB  budget: %.2f GiB%s"
            % (self.device_bytes / 2**30, self.host_bytes / 2**30,
               self.hbm_budget / 2**30, "" if self.feasible else "  INFEASIBLE")
        )
        return "\n".join(rows)


def plan_placement(
    indexes: Sequence[IndexResidency] | Iterable[IndexResidency],
    hbm_budget: int = HBM_DEFAULT_BUDGET_BYTES,
    *,
    headroom: float = HBM_HEADROOM,
) -> Placement:
    """Decide device- vs host-tier per component.

    Required components always plan to the device (the scan cannot run
    otherwise); if their sum exceeds ``hbm_budget * headroom`` the plan
    is marked infeasible. Optional components (refine raw vectors) are
    then admitted largest-first into the remaining budget — spilling the
    *biggest* slab first buys the most headroom per spilled index, so a
    mixed fleet keeps its small indexes fully resident.

    Every spilled index additionally charges its staging footprint
    (:func:`staging_footprint`): 2x host buffers into
    ``staging_host_bytes`` and the in-flight transfer slab into
    ``device_bytes`` / ``staging_device_bytes``. Admission is
    smallest-first, so spills form a suffix of the admission order and
    staging charges (which accrue only on spill) never retroactively
    evict an already-admitted slab; ``feasible`` stays a required-bytes
    criterion — staging is accounting the operator reads, not a reason
    to refuse a scan that fits.
    """
    indexes = list(indexes)
    cap = int(hbm_budget * headroom)
    tiers: Dict[str, Dict[str, str]] = {}
    device = 0
    for res in indexes:
        tiers[res.index_id] = {c.name: "device" for c in res.components if c.required}
        device += res.required_bytes
    feasible = device <= cap

    optional = sorted(
        ((c, res) for res in indexes for c in res.components if not c.required),
        key=lambda pair: pair[0].nbytes,
    )
    host = 0
    stage_host = stage_dev = 0
    staged = set()
    # smallest-first admission == largest-first spill
    for comp, res in optional:
        if feasible and device + comp.nbytes <= cap:
            tiers[res.index_id][comp.name] = "device"
            device += comp.nbytes
        else:
            tiers[res.index_id][comp.name] = "host"
            host += comp.nbytes
            if res.index_id not in staged:
                staged.add(res.index_id)
                sh, sd = staging_footprint(int(comp.shape[-1]), comp.itemsize)
                stage_host += sh
                stage_dev += sd
    return Placement(
        hbm_budget=int(hbm_budget), tiers=tiers,
        device_bytes=device + stage_dev, host_bytes=host, feasible=feasible,
        staging_host_bytes=stage_host, staging_device_bytes=stage_dev,
    )


@dataclasses.dataclass(frozen=True)
class ShardedPlacement:
    """Per-shard verdict of :func:`plan_placement_sharded`.

    All byte totals are PER SHARD. ``tiers`` maps ``index_id ->
    {component_name -> "device" | "host" | "disk"}``: device HBM, the
    shard host's RAM (an in-memory :class:`~raft_tpu.tiered.store.
    HostVectorStore`), or the shard host's disk (the mmap/SSD-backed
    store variant — read-ahead hints + the fetch-depth budget keep its
    p99 bounded on cold pages)."""

    n_shards: int
    hbm_budget_per_shard: int
    host_budget_per_shard: Optional[int]
    tiers: Dict[str, Dict[str, str]]
    device_bytes_per_shard: int
    host_bytes_per_shard: int
    disk_bytes_per_shard: int
    feasible: bool
    #: double-buffered host staging slabs of spilled indexes (2x each),
    #: charged against the host budget alongside RAM-tier slabs
    staging_host_bytes: int = 0
    #: in-flight gather transfer slabs (1x each), included in
    #: ``device_bytes_per_shard``
    staging_device_bytes: int = 0

    def tier(self, index_id: str, component: str) -> str:
        return self.tiers[index_id][component]

    def spilled(self, index_id: str) -> bool:
        """Does any component of ``index_id`` live off the device?"""
        return any(t != "device" for t in self.tiers[index_id].values())

    def table(self) -> str:
        rows = ["per-shard placement over %d shards:" % self.n_shards]
        for iid, comps in sorted(self.tiers.items()):
            for name, tier in comps.items():
                rows.append("%-20s %-14s -> %s" % (iid, name, tier))
        if self.staging_host_bytes or self.staging_device_bytes:
            rows.append(
                "staging/shard: host %.2f MiB (2x double-buffer)  device %.2f MiB (transfer)"
                % (self.staging_host_bytes / 2**20, self.staging_device_bytes / 2**20)
            )
        rows.append(
            "per shard — device: %.2f GiB  host: %.2f GiB  disk: %.2f GiB  hbm budget: %.2f GiB%s"
            % (self.device_bytes_per_shard / 2**30, self.host_bytes_per_shard / 2**30,
               self.disk_bytes_per_shard / 2**30, self.hbm_budget_per_shard / 2**30,
               "" if self.feasible else "  INFEASIBLE")
        )
        return "\n".join(rows)


def plan_placement_sharded(
    indexes: Sequence[IndexResidency] | Iterable[IndexResidency],
    n_shards: int,
    hbm_budget_per_shard: int = HBM_DEFAULT_BUDGET_BYTES,
    *,
    host_budget_per_shard: Optional[int] = None,
    headroom: float = HBM_HEADROOM,
    staging_micro_batch: int = STAGING_MICRO_BATCH,
    staging_n_cand: int = STAGING_N_CAND,
) -> ShardedPlacement:
    """Per-shard placement over the three-level hierarchy the pod-scale
    tier composition serves from: device HBM, the shard host's RAM, and
    the shard host's disk.

    Replicated components (coarse centroids, rotation, PQ codebook —
    see :attr:`HbmComponent.replicated`) cost their FULL size on every
    shard; everything else costs ``ceil(nbytes / n_shards)``. Required
    components must fit the per-shard device cap or the plan is
    infeasible (codes cannot leave HBM). Optional slabs admit
    smallest-first to the device; a spilled slab lands in host RAM
    while the per-shard host budget — charged with the 2x
    double-buffered staging slabs the spill brings — still holds, and
    on disk past it (the mmap/SSD-backed store; same gather, the OS
    pages rows in under read-ahead hints). ``host_budget_per_shard=None``
    means unconstrained host RAM: nothing plans to disk.
    """
    indexes = list(indexes)
    if n_shards < 1:
        raise ValueError(f"n_shards must be >= 1, got {n_shards}")
    cap = int(hbm_budget_per_shard * headroom)
    tiers: Dict[str, Dict[str, str]] = {}
    device = 0
    for res in indexes:
        tiers[res.index_id] = {c.name: "device" for c in res.components if c.required}
        device += sum(
            c.per_shard_bytes(n_shards) for c in res.components if c.required
        )
    feasible = device <= cap

    optional = sorted(
        ((c, res) for res in indexes for c in res.components if not c.required),
        key=lambda pair: pair[0].per_shard_bytes(n_shards),
    )
    host = disk = stage_host = stage_dev = 0
    staged = set()
    for comp, res in optional:
        b = comp.per_shard_bytes(n_shards)
        if feasible and device + b <= cap:
            tiers[res.index_id][comp.name] = "device"
            device += b
            continue
        # spilling: the index starts staging through the host no matter
        # which off-device tier the slab itself lands in
        sh, sd = staging_footprint(
            int(comp.shape[-1]), comp.itemsize,
            micro_batch=staging_micro_batch, n_cand=staging_n_cand,
        )
        charge_h = sh if res.index_id not in staged else 0
        if host_budget_per_shard is None or (
            host + b + stage_host + charge_h <= int(host_budget_per_shard)
        ):
            tiers[res.index_id][comp.name] = "host"
            host += b
        else:
            tiers[res.index_id][comp.name] = "disk"
            disk += b
        if res.index_id not in staged:
            staged.add(res.index_id)
            stage_host += sh
            stage_dev += sd
    return ShardedPlacement(
        n_shards=int(n_shards),
        hbm_budget_per_shard=int(hbm_budget_per_shard),
        host_budget_per_shard=(
            None if host_budget_per_shard is None else int(host_budget_per_shard)
        ),
        tiers=tiers,
        device_bytes_per_shard=device + stage_dev,
        host_bytes_per_shard=host,
        disk_bytes_per_shard=disk,
        feasible=feasible,
        staging_host_bytes=stage_host,
        staging_device_bytes=stage_dev,
    )
