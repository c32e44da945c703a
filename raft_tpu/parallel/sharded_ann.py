"""ICI-sharded ANN search for the IVF and CAGRA indexes.

SURVEY.md §7 step 7 / §2.5: the reference leaves multi-GPU ANN to
downstream consumers (``docs/source/using_raft_comms.rst:5-7``); this
framework ships it in-tree. Two shardings, mirroring how the data
structures scale:

* **IVF-Flat: inverted lists sharded** across the mesh axis. Coarse
  probing runs against the replicated centers (tiny), each shard streams
  only its slice of the padded lists through the dense masked scan
  (:func:`raft_tpu.neighbors.ivf_flat.flat_scan_core`) — list ids in the
  padded layout are global dataset row ids, so per-shard top-k merge with
  one ``all_gather`` + k-way merge (``knn_merge_parts`` pattern).
* **IVF-PQ: inverted lists sharded** (round 4): replicated coarse centers
  + quantizers (tiny), each shard decode-scans only ITS slice of the code
  lists (:func:`raft_tpu.neighbors.ivf_pq.pq_scan_core`), allgather +
  k-way merge — the compressed analog of the IVF-Flat sharding, and the
  path that takes DEEP-100M-class datasets past one chip's HBM.
  :func:`sharded_ivf_pq_build` is the matching distributed-build sketch
  (psum-Lloyd coarse centers + codebooks over row-sharded data).
* **CAGRA / IVF-PQ (small indexes): queries sharded, index replicated** —
  graph beam search is latency-bound per query and the graph is compact,
  so replicated-index data parallelism is the first-order scaling knob
  (the reference's multi-GPU story for CAGRA is likewise index-replica
  sharding at the serving layer).

Everything runs under ``shard_map`` over a :func:`make_mesh` mesh and
works identically on real ICI or the virtual CPU test mesh.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import functools

from raft_tpu.core.errors import expects
from raft_tpu.neighbors import cagra as cagra_mod, ivf_flat as ivf_flat_mod, ivf_pq as ivf_pq_mod
from raft_tpu.neighbors.ivf_common import coarse_from_dots
from raft_tpu.ops.distance import DistanceType
from raft_tpu.ops.pallas._guard import kernel_guard
from raft_tpu.ops.select_k import merge_parts, worst_value
from jax import shard_map
from raft_tpu.random.rng import as_key
from raft_tpu.robust.fallback import FALLBACK_ERRORS, record_fallback


def _health_array(health, n_shards) -> jnp.ndarray:
    """Replicated per-shard health mask [n_shards] bool; ``None`` means
    all healthy (and callers then build the unmasked program)."""
    h = jnp.asarray(health, bool)
    expects(h.shape == (n_shards,), "health mask shape %s != (%d,)", h.shape, n_shards)
    return h


#: candidate-exchange engines for the lists-sharded searches
_MERGE_MODES = ("auto", "ring", "fused_ring", "gather")


def _resolve_merge_mode(merge_mode: str, n_shards: int) -> str:
    """``auto`` prefers the ring exchange whenever there is more than one
    shard (parity with gather is exact, wire bytes are ~0.4n× lower); a
    single shard has nothing to exchange and keeps the trivial path.
    ``fused_ring`` keeps the same wire schedule but folds the scan's
    candidate tile to the merge width inside the ring engine; only a
    caller that pins it gets it.

    This is the single merge-engine chokepoint: every sharded search
    (and the serving engine's sharded registrations, transitively)
    resolves ``auto`` here, by :func:`raft_tpu.plan.plan_merge_mode`."""
    expects(merge_mode in _MERGE_MODES, "merge_mode %r (want one of %s)",
            merge_mode, _MERGE_MODES)
    if merge_mode == "auto":
        from raft_tpu import plan as _plan

        return _plan.plan_merge_mode(n_shards)
    if merge_mode == "fused_ring" and n_shards == 1:
        return "gather"
    return merge_mode


def _exchange_merge(v, i, k, select_min, axis, merge_mode):
    """Cross-shard candidate exchange + merge (runs inside ``shard_map``).

    ``ring`` streams each shard's surviving top-k around the ICI ring
    (:func:`raft_tpu.ops.pallas.ring_topk.ring_topk`), keeping wire bytes
    and peak memory O(k) per hop; ``fused_ring`` hands the scan's
    candidate tile (any width >= k) to
    :func:`~raft_tpu.ops.pallas.ring_topk.scan_ring_topk`, which runs the
    scan's final top-k fold inside the ring engine so the per-shard
    ``[nq, k]`` winners never round-trip through HBM before the exchange;
    ``gather`` materialises the full ``n_shards × k`` candidate set on
    every shard and is kept as the reference engine and both rings'
    fallback target. Ids are bit-identical across all three by the ring's
    (value, position) total-order contract.
    """
    if merge_mode == "fused_ring":
        from raft_tpu.ops.pallas.ring_topk import scan_ring_topk  # lazy: parallel <-> ops cycle

        return scan_ring_topk(v, i, k, select_min=select_min, axis=axis)
    if merge_mode == "ring":
        from raft_tpu.ops.pallas.ring_topk import ring_topk  # lazy: parallel <-> ops cycle

        return ring_topk(v, i, k, select_min=select_min, axis=axis)
    nq = v.shape[0]
    all_v = jax.lax.all_gather(v, axis)  # graft-lint: ignore[gather-merge] — reference engine + ring/fused_ring fallback target
    all_i = jax.lax.all_gather(i, axis)
    cat_v = jnp.moveaxis(all_v, 0, 1).reshape(nq, -1)
    cat_i = jnp.moveaxis(all_i, 0, 1).reshape(nq, -1)
    # invalid (-1) slots carry +/-inf values and lose the merge
    return merge_parts(cat_v, cat_i, k, select_min=select_min)


def _run_with_ring_fallback(build, args, mode):
    """Execute the resolved-engine program; a failing ring program
    (injected ``comms.ring_topk`` chaos, or a real lowering/runtime error
    on hardware) is re-run on the gather engine. Both rings are purely a
    transport — results are bit-identical — so falling back is always
    safe, including for explicitly requested ``merge_mode="ring"`` /
    ``"fused_ring"`` (unlike ``mode="fused"`` kernels, where the engine
    *is* the request). Fallbacks count under the existing
    ``fallbacks{algo}`` counter with the engine's own algo label.
    """
    if mode in ("ring", "fused_ring"):
        algo = "ring_topk" if mode == "ring" else "scan_ring_topk"
        try:
            with kernel_guard(algo):
                return build(mode)(*args)
        except FALLBACK_ERRORS as exc:
            record_fallback(algo, exc)
    return build("gather")(*args)


@functools.lru_cache(maxsize=64)
def _ivf_flat_fn(mesh, axis, k, n_probes, metric, g, l_local, masked=False,
                 merge_mode="gather"):
    """Cached jitted shard_map program (rebuilding it per call would
    re-trace and recompile every search). With ``masked=True`` the program
    takes an extra replicated ``healthy [n_shards]`` input and unhealthy
    shards' candidates are demoted to worst-value/-1 before the exchange,
    so the merge drops them (degraded-mode search; a demoted shard loses
    every ring fold the same way it loses the gathered merge)."""

    def local(centers, ld, li, ln, q, *rest):
        rank = lax.axis_index(axis)
        qf = q
        if metric == DistanceType.CosineExpanded:
            qf = qf / jnp.maximum(jnp.linalg.norm(qf, axis=1, keepdims=True), 1e-12)
        probed = ivf_flat_mod.probe_mask(centers, qf, n_probes, metric)
        probed_local = lax.dynamic_slice_in_dim(probed, rank * l_local, l_local, axis=1)
        v, i = ivf_flat_mod.flat_scan_core(
            ld, li, ln, qf, probed_local, None,
            k=k, metric=metric, has_filter=False, chunk_lists=g,
        )
        select_min = metric != DistanceType.InnerProduct
        if masked:
            (healthy,) = rest
            ok = healthy[rank]
            v = jnp.where(ok, v, worst_value(v.dtype, select_min))
            i = jnp.where(ok, i, -1)
        return _exchange_merge(v, i, k, select_min, axis, merge_mode)

    extra = (P(),) if masked else ()
    return jax.jit(
        shard_map(
            local,
            mesh=mesh,
            in_specs=(P(), P(axis), P(axis), P(axis), P()) + extra,
            out_specs=(P(), P()),
            check_vma=False,
        )
    )


def sharded_ivf_flat_search(
    mesh: Mesh,
    index: "ivf_flat_mod.IvfFlatIndex",
    queries,
    k: int,
    params: Optional["ivf_flat_mod.IvfFlatSearchParams"] = None,
    axis: str = "data",
    health=None,
    merge_mode: str = "auto",
    **kwargs,
) -> Tuple[jax.Array, jax.Array]:
    """IVF-Flat search with lists sharded over ``mesh`` axis ``axis``.

    Returns replicated ``(distances [nq, k], indices [nq, k])`` drawn from
    the same probed candidate set as single-device scan search. With a
    per-shard boolean ``health`` mask, unhealthy shards are excluded from
    the merge (degraded-mode search; see :mod:`raft_tpu.robust.degrade`).
    ``merge_mode`` picks the cross-shard exchange: ``"ring"`` (in-VMEM
    ring top-k), ``"gather"`` (all-gather + merge reference), or
    ``"auto"`` (ring when sharded, with automatic gather fallback on
    kernel failure).
    """
    if params is None:
        params = ivf_flat_mod.IvfFlatSearchParams(**kwargs)
    queries = jnp.asarray(queries, jnp.float32)
    n_shards = mesh.shape[axis]
    L = index.n_lists
    expects(L % n_shards == 0, "n_lists %d not divisible by %d shards", L, n_shards)
    l_local = L // n_shards
    n_probes = min(params.n_probes, L)
    metric = index.metric
    g = ivf_flat_mod.scan_chunk_lists(l_local, index.max_list)

    masked = health is not None
    mode = _resolve_merge_mode(merge_mode, n_shards)
    ln = index.list_norms
    if ln is None:
        ln = jnp.zeros(index.list_indices.shape, jnp.float32)
    put = lambda x, spec: jax.device_put(x, NamedSharding(mesh, spec))
    args = [
        put(index.centers, P()),
        put(index.list_data, P(axis)),
        put(index.list_indices, P(axis)),
        put(ln, P(axis)),
        put(queries, P()),
    ]
    if masked:
        args.append(put(_health_array(health, n_shards), P()))
    build = lambda m: _ivf_flat_fn(
        mesh, axis, k, n_probes, metric, g, l_local, masked, m
    )
    return _run_with_ring_fallback(build, args, mode)


@functools.lru_cache(maxsize=64)
def _cagra_fn(mesh, axis, k, itopk, width, iters, n_init, size, metric, seed, use_vpq, init_sample):
    key = as_key(seed)

    def local(sqnorms, graph, q, *data_args):
        rank = lax.axis_index(axis)
        kb = jax.random.fold_in(key, rank)
        if init_sample > 0:
            init_ids = cagra_mod.strided_seed_ids(size, init_sample)
        else:
            init_ids = jax.random.randint(kb, (q.shape[0], n_init), 0, size, jnp.int32)
        if use_vpq:
            dataset, vpq_arrays = None, tuple(data_args)
        else:
            (dataset,), vpq_arrays = data_args, None
        return cagra_mod._cagra_search_impl(
            dataset, sqnorms, graph, q, init_ids, None, vpq_arrays,
            k=k, itopk=itopk, width=width, iters=iters,
            metric=metric, has_filter=False, use_vpq=use_vpq,
        )

    n_data = 4 if use_vpq else 1
    return jax.jit(
        shard_map(
            local,
            mesh=mesh,
            in_specs=(P(), P(), P(axis)) + (P(),) * n_data,
            out_specs=(P(axis), P(axis)),
            check_vma=False,
        )
    )


def sharded_cagra_search(
    mesh: Mesh,
    index: "cagra_mod.CagraIndex",
    queries,
    k: int,
    params: Optional["cagra_mod.CagraSearchParams"] = None,
    axis: str = "data",
    **kwargs,
) -> Tuple[jax.Array, jax.Array]:
    """CAGRA beam search with queries sharded over the mesh (replicated
    graph + dataset). Results come back query-sharded and are returned as
    one array."""
    if params is None:
        params = cagra_mod.CagraSearchParams(**kwargs)
    queries = jnp.asarray(queries, jnp.float32)
    nq = queries.shape[0]
    n_shards = mesh.shape[axis]
    expects(nq % n_shards == 0, "n_queries %d not divisible by %d shards", nq, n_shards)

    itopk, width, iters, n_init = cagra_mod.derive_search_config(params, k, index.size)
    use_vpq = index.dataset is None
    if use_vpq:
        expects(index.vpq is not None, "index has neither dataset nor vpq data")
    fn = _cagra_fn(
        mesh, axis, k, itopk, width, iters, n_init, index.size, index.metric,
        params.seed, use_vpq, params.init_sample,
    )
    put = lambda x, spec: jax.device_put(x, NamedSharding(mesh, spec))
    if use_vpq:
        return fn(
            put(index.vpq.sqnorms, P()),
            put(index.graph, P()),
            put(queries, P(axis)),
            put(index.vpq.vq_centers, P()),
            put(index.vpq.vq_labels, P()),
            put(index.vpq.pq_centers, P()),
            put(index.vpq.codes, P()),
        )
    return fn(
        put(index.sqnorms, P()),
        put(index.graph, P()),
        put(queries, P(axis)),
        put(index.dataset, P()),
    )


@functools.lru_cache(maxsize=64)
def _ivf_pq_lists_fn(mesh, axis, k, n_probes, metric, g, bf16, l_local, masked=False,
                     merge_mode="gather", row_norm=0.0):
    """Lists-sharded PQ search program: replicated centers/quantizers,
    per-shard decode scan over the local list slice, cross-shard exchange
    + merge (``merge_mode`` engine). ``masked=True`` adds the replicated
    per-shard health input (see :func:`_ivf_flat_fn`)."""

    def local(centers, centers_rot, rotation, pq_centers, codes, li, sqn, q, *rest):
        rank = lax.axis_index(axis)
        qf = q.astype(jnp.float32)
        q_dot_c = qf @ centers.T
        coarse = coarse_from_dots(q_dot_c, centers, qf, metric, row_norm)
        nq = q.shape[0]
        n_lists = centers.shape[0]
        from raft_tpu.ops.select_k import select_k as _sk

        probed = jnp.zeros((nq, n_lists), bool)
        if n_probes < n_lists:
            _, probes = _sk(coarse, n_probes, select_min=True)
            probed = probed.at[jnp.arange(nq)[:, None], probes].set(True)
        else:
            probed = jnp.ones((nq, n_lists), bool)
        probed_l = lax.dynamic_slice_in_dim(probed, rank * l_local, l_local, axis=1)
        qdc_l = lax.dynamic_slice_in_dim(q_dot_c, rank * l_local, l_local, axis=1)
        q_rot = qf @ rotation.T
        v, i = ivf_pq_mod.pq_scan_core(
            pq_centers, codes, li, sqn, q_rot, qdc_l, probed_l, None,
            k=k, metric=metric, per_cluster=False, has_filter=False,
            chunk_lists=g, bf16=bf16,
        )
        select_min = metric != DistanceType.InnerProduct
        if masked:
            (healthy,) = rest
            ok = healthy[rank]
            v = jnp.where(ok, v, worst_value(v.dtype, select_min))
            i = jnp.where(ok, i, -1)
        return _exchange_merge(v, i, k, select_min, axis, merge_mode)

    extra = (P(),) if masked else ()
    return jax.jit(
        shard_map(
            local,
            mesh=mesh,
            in_specs=(P(), P(), P(), P(), P(axis), P(axis), P(axis), P()) + extra,
            out_specs=(P(), P()),
            check_vma=False,
        )
    )


def sharded_ivf_pq_lists_search(
    mesh: Mesh,
    index: "ivf_pq_mod.IvfPqIndex",
    queries,
    k: int,
    params: Optional["ivf_pq_mod.IvfPqSearchParams"] = None,
    axis: str = "data",
    health=None,
    merge_mode: str = "auto",
    **kwargs,
) -> Tuple[jax.Array, jax.Array]:
    """IVF-PQ search with the CODE LISTS sharded over ``mesh`` axis
    ``axis`` (replicated coarse centers + codebooks). Per-shard HBM holds
    ``1/n_shards`` of the codes — the scaling mode for datasets beyond one
    chip (SURVEY §7 step 7). Returns replicated ``(distances, indices)``
    from the same probed candidate set as single-device scan search.
    ``health`` (per-shard bools) excludes failed shards from the merge;
    ``merge_mode`` picks the exchange engine (see
    :func:`sharded_ivf_flat_search`)."""
    if params is None:
        params = ivf_pq_mod.IvfPqSearchParams(**kwargs)
    expects(
        index.codebook_kind == ivf_pq_mod.PER_SUBSPACE,
        "lists-sharded PQ needs per_subspace codebooks (per_cluster books would shard too)",
    )
    queries = jnp.asarray(queries, jnp.float32)
    n_shards = mesh.shape[axis]
    L = index.n_lists
    expects(L % n_shards == 0, "n_lists %d not divisible by %d shards", L, n_shards)
    l_local = L // n_shards
    n_probes = min(params.n_probes, L)
    g = ivf_pq_mod.scan_chunk_lists(l_local, index.max_list)
    bf16 = ivf_pq_mod.scan_bf16(params.lut_dtype)

    masked = health is not None
    mode = _resolve_merge_mode(merge_mode, n_shards)
    put = lambda x, spec: jax.device_put(x, NamedSharding(mesh, spec))
    args = [
        put(index.centers, P()),
        put(index.centers_rot, P()),
        put(index.rotation, P()),
        put(index.pq_centers, P()),
        put(index.codes_unpacked(), P(axis)),
        put(index.list_indices, P(axis)),
        put(index.rot_sqnorms, P(axis)),
        put(queries, P()),
    ]
    if masked:
        args.append(put(_health_array(health, n_shards), P()))
    build = lambda m: _ivf_pq_lists_fn(
        mesh, axis, k, n_probes, index.metric, g, bf16, l_local, masked, m,
        index.row_norm,
    )
    return _run_with_ring_fallback(build, args, mode)


#: cross-shard accumulator-exchange engines for the distributed builds
_COMM_MODES = ("auto", "full", "ca")


def _resolve_comm_mode(comm_mode: str, n_shards: int, n_rows=None,
                       d=None, ca_cap=None) -> str:
    """``auto`` prefers the communication-avoiding exchange whenever
    there is more than one shard (wire bytes per iteration drop to the
    changed-row fraction); a single shard pays no wire bytes either way
    and keeps the reference ``full`` exchange. With the accumulator
    shape known (``n_rows``/``d``), :func:`raft_tpu.plan.plan_comm_mode`
    compares the wire model's bytes, which also keeps ``full`` where the
    CA row cap cannot undercut the full exchange."""
    expects(comm_mode in _COMM_MODES, "comm_mode %r (want one of %s)",
            comm_mode, _COMM_MODES)
    if comm_mode == "auto":
        from raft_tpu import plan as _plan

        return _plan.plan_comm_mode(n_shards, n_rows=n_rows, d=d, ca_cap=ca_cap)
    return comm_mode


def _ca_cap(n_rows: int, ca_cap) -> int:
    """Exchanged-row budget for the CA accumulator exchange — the
    consolidated :func:`raft_tpu.parallel.wire_model.ca_exchange_cap`
    (kept as the builds' local name)."""
    from raft_tpu.parallel.wire_model import ca_exchange_cap

    return ca_exchange_cap(n_rows, ca_cap)


def _note_build_comms(phase: str, payload_bytes: float, axis: str,
                      verb: str = "allreduce", launches: int = 1) -> None:
    """Trace-time build-comms accounting: one ``comms.build.launches``
    tick per collective launch and the wire-model bytes
    (:func:`raft_tpu.parallel.comms.wire_bytes`) under
    ``comms.build.bytes``, both labelled with the build ``phase``. The
    build programs retrace per call, so per-iteration launches inside the
    Python training loop each fire once."""
    from raft_tpu import obs
    from jax.lax import axis_size
    from raft_tpu.parallel.comms import wire_bytes

    if not obs.is_enabled():
        return
    n = axis_size(axis)
    obs.inc("comms.build.launches", float(launches), phase=phase)
    obs.inc("comms.build.bytes", wire_bytes(verb, payload_bytes, n), phase=phase)


def _ca_exchange(rows_local, changed_local, gsums, cap, axis, phase):
    """Communication-avoiding accumulator exchange (runs inside
    ``shard_map``): allreduce the tiny per-row changed-count vector,
    pick the ``cap`` rows with the most global churn (``lax.top_k`` on a
    replicated input — every shard selects the same rows, ties broken by
    lowest index), allreduce ONLY those rows' fresh local partials, and
    patch them into the carried global accumulator.

    Exactness: a row whose assignments did not change on ANY shard has a
    bit-identical local partial this iteration (same rows, summed in the
    same order), so its carried psum value already equals a fresh
    full-width psum bit-for-bit. Whenever the global changed-row count
    fits under ``cap`` every iteration, the CA trajectory is therefore
    bit-identical to the ``full`` exchange (trivially so at
    ``cap=n_rows``); beyond the cap the least-churned rows lag one
    iteration — the bounded-drift regime covered by the recall-floor
    contract. Zero-change rows drafted to fill the cap re-psum to
    identical bits, so over-selection is harmless."""
    from raft_tpu.parallel.comms import allreduce

    gchanged = allreduce(changed_local, "sum", axis)
    _, sel = lax.top_k(gchanged, cap)
    block = allreduce(jnp.take(rows_local, sel, axis=0), "sum", axis)
    _note_build_comms(
        phase,
        changed_local.size * 4 + block.size * 4,
        axis,
        launches=2,
    )
    return gsums.at[sel].set(block)


def dist_lloyd_step(centers, x_local, n_lists, axis, cache=None, fuse_comms=True,
                    comm_mode="full", carry=None, ca_cap=None):
    """One distributed Lloyd iteration (runs inside ``shard_map``):
    Flash-KMeans blocked E step on the local rows (``cache`` from
    :func:`raft_tpu.cluster.kmeans.flash_norm_cache`, hoisted across
    iterations), then the centroid sums and counts are packed into ONE
    concatenated ``[n_lists, d+1]`` allreduce instead of two. psum is
    elementwise, so the packed reduction is bit-identical to the
    separate pair — the Lloyd trajectory is unchanged
    (``fuse_comms=False`` keeps the two-allreduce reference for the
    trajectory/byte-count tests).

    ``comm_mode="ca"`` is the communication-avoiding exchange: the step
    carries ``(prev_labels, packed_global_sums)`` across iterations and
    each iteration moves only the ``ca_cap`` most-churned lists' partial
    sums (plus a ``[n_lists]`` changed-count vector) over the wire — see
    :func:`_ca_exchange` for the bit-identical-under-cap contract and
    :func:`lloyd_wire_bytes_per_iter` for the byte model. In CA mode the
    step returns ``(centers, labels, carry)``; pass ``carry=None`` on
    the first iteration (which pays one full-width exchange to seed the
    carried accumulator)."""
    from raft_tpu.cluster.kmeans import flash_min_cluster_and_distance
    from raft_tpu.parallel.comms import allreduce

    lab, _ = flash_min_cluster_and_distance(
        x_local, centers, metric=DistanceType.L2Expanded, cache=cache
    )
    sums = jax.ops.segment_sum(x_local, lab, num_segments=n_lists)
    cnts = jax.ops.segment_sum(jnp.ones_like(lab, jnp.float32), lab, num_segments=n_lists)
    if comm_mode == "ca":
        local_rows = jnp.concatenate([sums, cnts[:, None]], axis=1)
        if carry is None:
            packed = allreduce(local_rows, "sum", axis)
            _note_build_comms("kmeans_full", local_rows.size * 4, axis)
        else:
            prev_lab, gsums = carry
            moved = (lab != prev_lab).astype(jnp.float32)
            changed = (
                jax.ops.segment_sum(moved, lab, num_segments=n_lists)
                + jax.ops.segment_sum(moved, prev_lab, num_segments=n_lists)
            )
            cap = _ca_cap(n_lists, ca_cap)
            packed = _ca_exchange(local_rows, changed, gsums, cap, axis, "kmeans_ca")
        gs, gc = packed[:, :-1], packed[:, -1]
        new = gs / jnp.maximum(gc[:, None], 1e-9)
        centers_out = jnp.where(gc[:, None] > 0, new, centers)
        return centers_out, lab, (lab, packed)
    if fuse_comms:
        packed = allreduce(jnp.concatenate([sums, cnts[:, None]], axis=1), "sum", axis)
        _note_build_comms("kmeans_full", packed.size * 4, axis)
        sums, cnts = packed[:, :-1], packed[:, -1]
    else:
        sums = allreduce(sums, "sum", axis)
        cnts = allreduce(cnts, "sum", axis)
        _note_build_comms("kmeans_full", sums.size * 4 + cnts.size * 4, axis,
                          launches=2)
    new = sums / jnp.maximum(cnts[:, None], 1e-9)
    return jnp.where(cnts[:, None] > 0, new, centers), lab


#: Residual rows per step of the codebook update's local pass: bounds its
#: ``[rows, pq_dim, ksub]`` distance and one-hot temporaries (at 1M rows
#: a shard, pq_dim 64 and 256 sub-centers one such f32 array is 65 GB).
_CODEBOOK_ROWS = 8192


def _codebook_partials(books, resid, ksub, prev_code=None):
    """The local pass of a codebook update, ``_CODEBOOK_ROWS`` rows at a
    time: each residual sub-vector's nearest sub-center ``code [nl,
    pq_dim]``, the per-sub-center sums ``[pq_dim, ksub, pq_len]`` and
    counts ``[pq_dim, ksub]``, and, given ``prev_code``, how many rows
    entered or left each sub-center ``[pq_dim, ksub]``."""
    nl, pq_dim, pq_len = resid.shape
    rows = min(_CODEBOOK_ROWS, nl)
    n_steps = -(-nl // rows)
    pad = n_steps * rows - nl
    valid = (jnp.arange(n_steps * rows) < nl).astype(jnp.float32).reshape(n_steps, rows)
    r = jnp.pad(resid, ((0, pad), (0, 0), (0, 0))).reshape(n_steps, rows, pq_dim, pq_len)
    prev = (jnp.zeros((n_steps, rows, pq_dim), jnp.int32) if prev_code is None else
            jnp.pad(prev_code, ((0, pad), (0, 0))).reshape(n_steps, rows, pq_dim))

    def step(acc, xs):
        rc, w, pc = xs
        code = ivf_pq_mod._nearest_sub(rc, books)  # [rows, pq_dim]
        oh = jax.nn.one_hot(code, ksub, dtype=jnp.float32) * w[:, None, None]
        sums = acc[0] + jnp.einsum("npk,npl->pkl", oh, rc, precision=lax.Precision.HIGHEST)
        cnts = acc[1] + jnp.sum(oh, axis=0)
        moved = (code != pc).astype(jnp.float32) * w[:, None]  # [rows, pq_dim]
        prev_oh = jax.nn.one_hot(pc, ksub, dtype=jnp.float32)
        changed = acc[2] + jnp.sum(moved[..., None] * (oh + prev_oh), axis=0)
        return (sums, cnts, changed), code

    zero = jnp.zeros((pq_dim, ksub), jnp.float32)
    init = (jnp.zeros((pq_dim, ksub, pq_len), jnp.float32), zero, zero)
    (sums, cnts, changed), codes = lax.scan(step, init, (r, valid, prev))
    return codes.reshape(n_steps * rows, pq_dim)[:nl], sums, cnts, changed


def dist_codebook_step(books, resid, ksub, axis, fuse_comms=True,
                       comm_mode="full", carry=None, ca_cap=None):
    """One distributed per-subspace codebook update (runs inside
    ``shard_map``): local assignment of residual sub-vectors, then the
    ``[pq_dim, ksub, pq_len]`` sums and ``[pq_dim, ksub]`` counts ride
    one concatenated allreduce (counts as an extra trailing column),
    matching :func:`dist_lloyd_step`'s comm fusion bit-for-bit.

    ``comm_mode="ca"`` flattens the accumulator to ``[pq_dim·ksub,
    pq_len+1]`` rows and applies the same carried changed-rows exchange
    as the Lloyd step (:func:`_ca_exchange`); returns ``(books, carry)``
    with ``carry=(codes, packed_rows)``."""
    from raft_tpu.parallel.comms import allreduce

    prev_code = carry[0] if comm_mode == "ca" and carry is not None else None
    code, sums, cnts, changed = _codebook_partials(books, resid, ksub, prev_code)
    if comm_mode == "ca":
        pq_dim, _, pq_len = sums.shape
        local_rows = jnp.concatenate([sums, cnts[..., None]], axis=-1)
        local_rows = local_rows.reshape(pq_dim * ksub, pq_len + 1)
        if carry is None:
            packed = allreduce(local_rows, "sum", axis)
            _note_build_comms("pq_codebook_full", local_rows.size * 4, axis)
        else:
            grows = carry[1]
            changed = changed.reshape(pq_dim * ksub)
            cap = _ca_cap(pq_dim * ksub, ca_cap)
            packed = _ca_exchange(local_rows, changed, grows, cap, axis, "pq_codebook_ca")
        rows = packed.reshape(pq_dim, ksub, pq_len + 1)
        gs, gc = rows[..., :-1], rows[..., -1]
        new = gs / jnp.maximum(gc[..., None], 1e-9)
        return jnp.where(gc[..., None] > 0, new, books), (code, packed)
    if fuse_comms:
        packed = allreduce(jnp.concatenate([sums, cnts[..., None]], axis=-1), "sum", axis)
        _note_build_comms("pq_codebook_full", packed.size * 4, axis)
        sums, cnts = packed[..., :-1], packed[..., -1]
    else:
        sums = allreduce(sums, "sum", axis)
        cnts = allreduce(cnts, "sum", axis)
        _note_build_comms("pq_codebook_full", sums.size * 4 + cnts.size * 4, axis,
                          launches=2)
    new = sums / jnp.maximum(cnts[..., None], 1e-9)
    return jnp.where(cnts[..., None] > 0, new, books)


# The per-iteration build byte models moved to the consolidated
# raft_tpu.parallel.wire_model (the comm-mode rule in raft_tpu.plan
# reads the same table); re-exported at this original home, where the
# bench dist_build phase and tests import them from.
from raft_tpu.parallel.wire_model import (  # noqa: E402,F401  (re-export)
    codebook_wire_bytes_per_iter,
    lloyd_wire_bytes_per_iter,
)


def _dist_pq_train_fn(mesh, axis, rotation, *, n_lists, pq_dim, ksub, n_iters, mode,
                      fuse_comms, ca_cap, ca_warmup):
    """The training program of :func:`sharded_ivf_pq_build`: coarse
    centers by psum-Lloyd, then per-subspace codebooks, over the rows
    sharded on ``axis``. Takes ``(dataset, init_centers)`` and returns
    replicated ``(centers, books)``; exposed so its memory can be read
    from an ahead-of-time compile (``tests/test_tpu_compile.py``)."""
    n_shards = mesh.shape[axis]

    def train(x_local, centers0):
        from raft_tpu.cluster.kmeans import flash_min_cluster_and_distance, flash_norm_cache
        from raft_tpu.parallel.comms import allgather

        # sample-side norms are iteration-invariant: hoist them out of
        # the Lloyd loop (the Flash-KMeans cache discipline)
        cache = flash_norm_cache(x_local, DistanceType.L2Expanded)
        centers = centers0
        if mode == "ca":
            carry = None
            for it in range(n_iters):
                if it < ca_warmup - 1:
                    # warm-up: full-width while churn is still heavy
                    # (the first CA call re-seeds full-width anyway, so
                    # ca_warmup counts TOTAL full exchanges)
                    centers, _ = dist_lloyd_step(
                        centers, x_local, n_lists, axis, cache=cache,
                        fuse_comms=True,
                    )
                    continue
                centers, lab, carry = dist_lloyd_step(
                    centers, x_local, n_lists, axis, cache=cache,
                    comm_mode="ca", carry=carry, ca_cap=ca_cap,
                )
            # final labeling against the converged centers is comm-free
            lab, _ = flash_min_cluster_and_distance(
                x_local, centers, metric=DistanceType.L2Expanded, cache=cache
            )
        else:
            for _ in range(n_iters):
                centers, _ = dist_lloyd_step(
                    centers, x_local, n_lists, axis, cache=cache, fuse_comms=fuse_comms
                )
            _, lab = dist_lloyd_step(
                centers, x_local, n_lists, axis, cache=cache, fuse_comms=fuse_comms
            )
        # per-subspace codebooks on local residuals, psum'd updates;
        # seeded from a stride-spread sample of EVERY shard's residuals
        # (real-data init — random gaussians collapse to few used
        # centers; a single shard's rows skew or under-fill the pool)
        resid = jnp.dot(x_local - centers[lab], rotation.T, precision=lax.Precision.HIGHEST
                        ).reshape(x_local.shape[0], pq_dim, -1)
        nl_local = resid.shape[0]
        per = -(-ksub // n_shards)
        stride = max(1, nl_local // per)
        idx = jnp.minimum(jnp.arange(per) * stride, nl_local - 1)
        pool = allgather(resid[idx], axis)  # [n_shards, per, pq_dim, pq_len]
        _note_build_comms("seed", pool[0].size * 4, axis, verb="allgather")
        seed = jnp.swapaxes(pool, 0, 1).reshape(n_shards * per, pq_dim, -1)
        n_seed = min(ksub, n_shards * nl_local)
        books = jnp.transpose(seed[:n_seed], (1, 0, 2))
        if n_seed < ksub:
            reps = -(-ksub // n_seed)
            books = jnp.tile(books, (1, reps, 1))[:, :ksub, :]

        if mode == "ca":
            bcarry = None
            for _ in range(max(4, n_iters)):
                books, bcarry = dist_codebook_step(
                    books, resid, ksub, axis,
                    comm_mode="ca", carry=bcarry, ca_cap=ca_cap,
                )
        else:
            for _ in range(max(4, n_iters)):
                books = dist_codebook_step(books, resid, ksub, axis, fuse_comms=fuse_comms)
        return centers, books

    return jax.jit(
        shard_map(
            train,
            mesh=mesh,
            in_specs=(P(axis), P()),
            out_specs=(P(), P()),
            check_vma=False,
        )
    )


def sharded_ivf_pq_build(
    mesh: Mesh,
    dataset,
    params: Optional["ivf_pq_mod.IvfPqIndexParams"] = None,
    axis: str = "data",
    fuse_comms: bool = True,
    comm_mode: str = "auto",
    ca_cap=None,
    ca_warmup: int = 2,
    **kwargs,
) -> "ivf_pq_mod.IvfPqIndex":
    """Distributed IVF-PQ build sketch (SURVEY §7 step 7): dataset rows
    sharded over the mesh, coarse centers and per-subspace codebooks
    trained with psum-Lloyd (local Flash-KMeans assign + summed center
    updates — the allreduce pattern of ``cluster/detail/kmeans_balanced.cuh``
    scaled out, with sums+counts fused into one allreduce per iteration),
    then every shard encodes its rows locally and the packed lists
    are assembled. The returned index is replicated (at DCN scale the
    final allgather would be skipped and the lists kept sharded for
    :func:`sharded_ivf_pq_lists_search`).

    ``comm_mode`` picks the per-iteration accumulator exchange:
    ``"full"`` is the reference fused allreduce, ``"ca"`` carries the
    global accumulator and moves only the most-churned rows each
    iteration (:func:`_ca_exchange`; bit-identical to ``full`` while the
    per-iteration churn fits under ``ca_cap``, recall-bounded beyond
    it), ``"auto"`` is CA whenever sharded. ``ca_warmup`` full-width
    Lloyd exchanges run before the capped exchange takes over —
    assignment churn is front-loaded (it decays geometrically once the
    centers coarse-settle), so paying full bytes for the first couple
    of iterations recovers nearly all of the full-mode recall while the
    steady-state per-iteration wire stays at the CA rate. Codebooks are
    seeded from a strided sample of EVERY shard's residuals (one
    init-only allgather) so the seed pool spans the global residual
    distribution — the rank-0-only seed this replaces left ~0.02 recall
    on the table vs the single-chip build whenever one shard's rows
    couldn't cover ``ksub`` distinct seeds."""
    if params is None:
        params = ivf_pq_mod.IvfPqIndexParams(**kwargs)
    dataset = jnp.asarray(dataset, jnp.float32)
    n, d = dataset.shape
    n_shards = mesh.shape[axis]
    expects(n % n_shards == 0, "rows %d not divisible by %d shards", n, n_shards)
    n_lists = min(params.n_lists, n)
    pq_dim = params.pq_dim or ivf_pq_mod._default_pq_dim(d)
    rot_dim = ((d + pq_dim - 1) // pq_dim) * pq_dim
    ksub = 1 << params.pq_bits
    mode = _resolve_comm_mode(comm_mode, n_shards, n_rows=n_lists, d=d,
                              ca_cap=ca_cap)

    key = as_key(params.seed)
    k_init, k_rot = jax.random.split(key)
    init_centers = dataset[jax.random.permutation(k_init, n)[:n_lists]]
    rotation = ivf_pq_mod._make_rotation(k_rot, rot_dim, d, params.force_random_rotation)

    fn = _dist_pq_train_fn(mesh, axis, rotation, n_lists=n_lists, pq_dim=pq_dim, ksub=ksub,
                           n_iters=params.kmeans_n_iters, mode=mode, fuse_comms=fuse_comms,
                           ca_cap=ca_cap, ca_warmup=ca_warmup)
    put = lambda x, spec: jax.device_put(x, NamedSharding(mesh, spec))
    centers, books = fn(put(dataset, P(axis)), put(init_centers, P()))

    # assignment + encode + pack. The row-local steps (candidate lists,
    # encode) and the list-local one (decoded norms) run as one program a
    # shard: as eager calls over the row-sharded array they were chunk
    # loops that XLA partitioned call by call. Slot assignment and the
    # scatter need every row and stay global.
    from raft_tpu.neighbors import ivf_common

    def per_shard(f, *specs):
        return jax.jit(shard_map(f, mesh=mesh, in_specs=specs, out_specs=P(axis),
                                 check_vma=False))

    rows, rep = P(axis), P()
    dataset = put(dataset, rows)
    cand = per_shard(lambda x, c: ivf_common.topk_labels(x, c, k=8), rows, rep)(
        dataset, centers)
    max_list = ivf_common.choose_max_list(cand[:, 0], n, n_lists, params.list_cap_factor)
    slot = ivf_common.assign_slots(cand, n_lists=n_lists, max_list=max_list)
    final_labels = (slot // max_list).astype(jnp.int32)
    codes_rows = per_shard(
        lambda x, lab, c, b: ivf_pq_mod._encode_all(x, lab, c, rotation, b, pq_dim, False),
        rows, rows, rep, rep)(dataset, put(final_labels, rows), centers, books)
    codes, list_indices, list_sizes = ivf_common.scatter_rows(
        codes_rows, jnp.arange(n, dtype=jnp.int32), slot, n_lists=n_lists, max_list=max_list
    )
    centers_rot = jnp.dot(centers, rotation.T, precision=lax.Precision.HIGHEST)
    sqnorms = functools.partial(ivf_pq_mod._sqnorms_for, per_cluster=False)
    if n_lists % n_shards == 0:
        sqnorms = per_shard(sqnorms, rows, rows, rep)
    rot_sqnorms = sqnorms(codes, centers_rot, books)
    metric = ivf_pq_mod.resolve_metric(params.metric)
    return ivf_pq_mod.IvfPqIndex(
        centers=centers,
        centers_rot=centers_rot,
        rotation=rotation,
        pq_centers=books,
        codes=codes,
        list_indices=list_indices,
        list_sizes=list_sizes,
        rot_sqnorms=rot_sqnorms,
        metric=metric,
        codebook_kind=ivf_pq_mod.PER_SUBSPACE,
        pq_bits=params.pq_bits,
        size=n,
        list_cap_factor=params.list_cap_factor,
        center_rank=None,
        row_norm=(ivf_common.one_norm(dataset)
                  if metric == DistanceType.InnerProduct else 0.0),
    )


@functools.lru_cache(maxsize=64)
def _ivf_pq_fn(mesh, axis, k, n_probes, metric, per_cluster, g, bf16, row_norm=0.0):
    def local(centers, centers_rot, rotation, pq_centers, codes, li, sqn, q):
        return ivf_pq_mod._ivf_pq_scan_impl(
            centers, centers_rot, rotation, pq_centers, codes, li, sqn, q, None,
            k=k, n_probes=n_probes, metric=metric,
            per_cluster=per_cluster, has_filter=False, chunk_lists=g, bf16=bf16,
            row_norm=row_norm,
        )

    return jax.jit(
        shard_map(
            local,
            mesh=mesh,
            in_specs=(P(), P(), P(), P(), P(), P(), P(), P(axis)),
            out_specs=(P(axis), P(axis)),
            check_vma=False,
        )
    )


def sharded_ivf_pq_search(
    mesh: Mesh,
    index: "ivf_pq_mod.IvfPqIndex",
    queries,
    k: int,
    params: Optional["ivf_pq_mod.IvfPqSearchParams"] = None,
    axis: str = "data",
    **kwargs,
) -> Tuple[jax.Array, jax.Array]:
    """IVF-PQ search with queries sharded over the mesh (replicated
    compressed index). The code footprint is ~pq_dim bytes/row, so a
    replica per chip covers far larger datasets than raw vectors would;
    query data-parallelism is the first-order ICI scaling knob."""
    if params is None:
        params = ivf_pq_mod.IvfPqSearchParams(**kwargs)
    queries = jnp.asarray(queries, jnp.float32)
    nq = queries.shape[0]
    n_shards = mesh.shape[axis]
    expects(nq % n_shards == 0, "n_queries %d not divisible by %d shards", nq, n_shards)
    n_probes = min(params.n_probes, index.n_lists)
    g = ivf_pq_mod.scan_chunk_lists(index.n_lists, index.max_list)
    per_cluster = index.codebook_kind == ivf_pq_mod.PER_CLUSTER
    bf16 = ivf_pq_mod.scan_bf16(params.lut_dtype)

    fn = _ivf_pq_fn(mesh, axis, k, n_probes, index.metric, per_cluster, g, bf16,
                    index.row_norm)
    put = lambda x, spec: jax.device_put(x, NamedSharding(mesh, spec))
    return fn(
        put(index.centers, P()),
        put(index.centers_rot, P()),
        put(index.rotation, P()),
        put(index.pq_centers, P()),
        put(index.codes_unpacked(), P()),
        put(index.list_indices, P()),
        put(index.rot_sqnorms, P()),
        put(queries, P(axis)),
    )
