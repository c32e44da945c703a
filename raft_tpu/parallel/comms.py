"""Comms verb set — TPU-native analog of ``raft::comms::comms_t``.

The reference defines a virtual communicator interface (allreduce, bcast,
reduce, allgather(v), gather, reducescatter, barrier, comm_split, p2p
send/recv) implemented over NCCL/UCX/MPI (``core/comms.hpp:125``
``comms_iface``, ``:137-241``; ``comms/std_comms.hpp:70``), injected into the
resources handle and fetched by algorithms via ``resource::get_comms``.

On TPU the transport is the ICI/DCN fabric driven by XLA collectives; the
communicator object dissolves into a `jax.sharding.Mesh` plus `jax.lax`
collective ops that are only meaningful inside `shard_map`. This module
provides:

* mesh construction / installation on :class:`Resources` (the
  ``build_comms_nccl_only`` analog — no uniqueId dance: `jax.distributed`
  handles multi-host bootstrap),
* the typed verb set as thin wrappers over ``jax.lax`` collectives, usable
  inside ``shard_map`` bodies,
* ``comm_split`` as mesh-axis subsetting (the SUB_COMMUNICATOR slot,
  ``core/comms.hpp:274``).

Self-tests mirroring ``comms/comms_test.hpp:117-155`` live in
``tests/test_comms.py``.
"""
from __future__ import annotations

import functools
import inspect
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from raft_tpu import obs
from raft_tpu.core.errors import expects
from raft_tpu.core.resources import Resources, ensure_resources
from jax.lax import axis_size as _axis_size
from raft_tpu.robust import faults

DEFAULT_AXIS = "data"

_REDUCE_OPS = ("sum", "max", "min", "prod")


def _payload_bytes(x) -> float:
    """Per-rank payload size of a verb argument, from static shape/dtype
    metadata only — safe on tracers inside ``shard_map`` bodies."""
    total = 0
    for leaf in jax.tree_util.tree_leaves(x):
        shape = getattr(leaf, "shape", None)
        dtype = getattr(leaf, "dtype", None)
        if shape is None or dtype is None:
            leaf = np.asarray(leaf)
            shape, dtype = leaf.shape, leaf.dtype
        total += int(np.prod(shape, dtype=np.int64)) * np.dtype(dtype).itemsize
    return float(total)


# The per-verb wire model now lives in raft_tpu.parallel.wire_model so
# the comm-mode rule, the build byte accounting, and these obs counters
# all price collectives from one table; re-exported here because this
# module is where the ``comms.{verb}.bytes`` counters apply it and where
# older consumers imported it from.
from raft_tpu.parallel.wire_model import (  # noqa: F401  (re-export)
    WIRE_FACTORS as _WIRE_FACTORS,
    wire_bytes,
)


def _instrumented(verb: str):
    """Wrap a comms verb with obs counters + a trace-time span.

    Verbs execute while XLA is *tracing* a ``shard_map`` body, so there is
    no device work to sync on here — the span records trace-time only
    (flagged ``traced=True`` in its args) while the counters record call
    counts and per-rank bytes MOVED, i.e. the static input payload scaled
    by the verb's :data:`_WIRE_FACTORS` wire model (outside a named-axis
    trace, where the axis size is unknowable, the raw payload is counted).
    Composite verbs (``reduce`` → ``allreduce``, ``scatter`` → ``bcast``)
    also count their inner verb: that matches the collectives actually
    issued."""

    def deco(fn):
        sig = inspect.signature(fn)

        @functools.wraps(fn)
        def wrapper(*a, **kw):
            if not obs.is_enabled():
                return fn(*a, **kw)
            bound = sig.bind(*a, **kw)
            bound.apply_defaults()
            x = bound.arguments.get("x")
            axis = str(bound.arguments.get("axis", DEFAULT_AXIS))
            nbytes = _payload_bytes(x) if x is not None else 4.0
            try:
                n = _axis_size(axis)
            except Exception:  # graft-lint: ignore[silent-except] — outside any axis trace
                n = None
            if n and n > 0:
                nbytes = _WIRE_FACTORS.get(verb, lambda p, _: p)(nbytes, n)
            obs.inc(f"comms.{verb}.calls", axis=axis)
            obs.inc(f"comms.{verb}.bytes", nbytes, axis=axis)
            with obs.span(f"comms.{verb}", bytes=nbytes, axis=axis, traced=True):
                return fn(*a, **kw)

        return wrapper

    return deco


def make_mesh(
    devices: Optional[Sequence[jax.Device]] = None,
    shape: Optional[Sequence[int]] = None,
    axis_names: Sequence[str] = (DEFAULT_AXIS,),
) -> Mesh:
    """Build a device mesh. Default: 1-D mesh over all local devices.

    The analog of communicator construction (``std_comms.hpp:70``); mesh
    axes are communicator "dimensions" and sub-communicators are axis
    subsets.
    """
    if devices is None:
        devices = jax.devices()
    devices = list(devices)
    if shape is None:
        shape = (len(devices),)
    expects(
        int(np.prod(shape)) == len(devices),
        "mesh shape %s does not cover %d devices",
        shape,
        len(devices),
    )
    arr = np.asarray(devices, dtype=object).reshape(shape)
    return Mesh(arr, tuple(axis_names))


def init_comms(
    res: Optional[Resources] = None,
    devices: Optional[Sequence[jax.Device]] = None,
    shape: Optional[Sequence[int]] = None,
    axis_names: Sequence[str] = (DEFAULT_AXIS,),
) -> Mesh:
    """Create a mesh and install it on the resources handle — the analog of
    ``inject_comms_on_handle`` (``raft_dask/common/comms_utils.pyx:259``)."""
    res = ensure_resources(res)
    mesh = make_mesh(devices, shape, axis_names)
    res.mesh = mesh
    return mesh


# ---------------------------------------------------------------------------
# Verb set (valid inside shard_map bodies)
# ---------------------------------------------------------------------------


def comm_rank(axis: str = DEFAULT_AXIS) -> jax.Array:
    """This shard's rank along ``axis`` (``comms_t::get_rank``)."""
    return lax.axis_index(axis)


def comm_size(axis: str = DEFAULT_AXIS) -> int:
    """Number of shards along ``axis`` (``comms_t::get_size``)."""
    return _axis_size(axis)


@_instrumented("allreduce")
def allreduce(x, op: str = "sum", axis: str = DEFAULT_AXIS):
    """``comms_t::allreduce`` (``core/comms.hpp:297``)."""
    expects(op in _REDUCE_OPS, "unknown reduce op %s", op)
    if op == "sum":
        return lax.psum(x, axis)
    if op == "max":
        return lax.pmax(x, axis)
    if op == "min":
        return lax.pmin(x, axis)
    # prod via log-domain would lose signs; use exp(sum(log|x|)) only for
    # positive inputs — instead do an allgather+reduce which is exact.
    # all_gather stacks a leading rank axis; reducing over it restores the
    # input shape, keeping prod consistent with sum/max/min.
    return jnp.prod(lax.all_gather(x, axis), axis=0)


@_instrumented("allgather")
def allgather(x, axis: str = DEFAULT_AXIS, tiled: bool = False):
    """``comms_t::allgather`` — concatenate per-rank blocks along axis 0
    (``core/comms.hpp:330``). With ``tiled=False`` a new leading rank axis is
    stacked; with ``tiled=True`` blocks are concatenated along axis 0."""
    # fault point fires at trace time (verbs run while shard_map traces);
    # an injected failure here aborts program construction, the collective
    # analog of a lost participant
    faults.fire("comms.all_gather", axis=str(axis))
    return lax.all_gather(x, axis, tiled=tiled)


@_instrumented("reducescatter")
def reducescatter(x, op: str = "sum", axis: str = DEFAULT_AXIS):
    """``comms_t::reducescatter`` (``core/comms.hpp:367``): elementwise
    reduce across ranks, then scatter equal chunks of axis 0."""
    expects(op == "sum", "reducescatter supports sum (psum_scatter)")
    return lax.psum_scatter(x, axis, tiled=True)


@_instrumented("bcast")
def bcast(x, root: int = 0, axis: str = DEFAULT_AXIS):
    """``comms_t::bcast`` (``core/comms.hpp:343``): every rank receives
    root's block."""
    gathered = lax.all_gather(x, axis)
    return jax.tree_util.tree_map(lambda g: g[root], gathered)


@_instrumented("reduce")
def reduce(x, root: int = 0, op: str = "sum", axis: str = DEFAULT_AXIS):
    """``comms_t::reduce``: reduction delivered to ``root``; other ranks get
    zeros (XLA collectives are symmetric, so we mask post-allreduce — same
    cost on ICI)."""
    full = allreduce(x, op=op, axis=axis)
    is_root = lax.axis_index(axis) == root
    return jax.tree_util.tree_map(lambda f: jnp.where(is_root, f, jnp.zeros_like(f)), full)


@_instrumented("ppermute")
def ppermute(x, perm: Sequence[tuple], axis: str = DEFAULT_AXIS):
    """Point-to-point ring/permutation send — the device p2p verb set
    (``comms_t::device_send/device_recv``) expressed as XLA's collective
    permute. ``perm`` is a list of (src, dst) pairs; ranks not named as a
    dst receive zeros."""
    return lax.ppermute(x, axis, perm)


@_instrumented("gather")
def gather(x, root: int = 0, axis: str = DEFAULT_AXIS):
    """``comms_t::gather`` (``core/comms.hpp:400``): root receives every
    rank's block stacked on a new leading axis; other ranks get zeros.
    XLA collectives are symmetric, so this is an all_gather + root mask —
    same ICI cost, and the mask keeps the verb's contract."""
    g = lax.all_gather(x, axis)
    is_root = lax.axis_index(axis) == root
    return jax.tree_util.tree_map(lambda a: jnp.where(is_root, a, jnp.zeros_like(a)), g)


@_instrumented("gatherv")
def gatherv(x, valid_n, root: int = 0, axis: str = DEFAULT_AXIS):
    """``comms_t::gatherv`` (``core/comms.hpp:417``): variable-size gather.
    XLA needs static shapes, so each rank contributes a padded block ``x
    [cap, ...]`` plus its true row count ``valid_n``; root receives
    ``(blocks [size, cap, ...], sizes [size])`` and other ranks zeros.
    Callers compact with the sizes (the raft recvcounts/displs analog)."""
    blocks = lax.all_gather(x, axis)
    sizes = lax.all_gather(jnp.asarray(valid_n, jnp.int32), axis)
    is_root = lax.axis_index(axis) == root
    mask = lambda a: jnp.where(is_root, a, jnp.zeros_like(a))  # noqa: E731
    return mask(blocks), mask(sizes)


@_instrumented("scatter")
def scatter(x, root: int = 0, axis: str = DEFAULT_AXIS):
    """Inverse of :func:`gather`: ``x [size, ...]`` on root (every rank
    passes the same-shaped buffer under SPMD); rank r receives block
    ``x_root[r]``. (The reference exposes this through raft-dask's
    scatter; the C++ iface covers it with device_send loops.)"""
    x_root = bcast(x, root=root, axis=axis)
    return jax.tree_util.tree_map(
        lambda a: lax.dynamic_index_in_dim(a, lax.axis_index(axis), 0, keepdims=False),
        x_root,
    )


@_instrumented("send_recv")
def send_recv(x, src: int, dst: int, axis: str = DEFAULT_AXIS):
    """One device p2p transfer (``comms_t::device_send``/``device_recv``
    pair, ``core/comms.hpp:506-540``): rank ``dst`` receives ``src``'s
    ``x``; every other rank (src included) gets zeros."""
    return lax.ppermute(x, axis, [(src, dst)])


@_instrumented("device_sendrecv")
def device_sendrecv(x, partner_of: Sequence[tuple], axis: str = DEFAULT_AXIS):
    """``comms_t::device_sendrecv`` (``core/comms.hpp:559``): simultaneous
    exchange — each (a, b) pair in ``partner_of`` ships a→b AND b→a in one
    collective permute."""
    perm = []
    for a, b in partner_of:
        perm.append((a, b))
        perm.append((b, a))
    return lax.ppermute(x, axis, perm)


@_instrumented("multicast_sendrecv")
def multicast_sendrecv(x, pairs: Sequence[tuple], axis: str = DEFAULT_AXIS):
    """``comms_t::device_multicast_sendrecv`` (``core/comms.hpp:580``):
    one source may feed several destinations — not a permutation, so XLA's
    ppermute cannot express it; an all_gather + per-rank source select
    does (one extra ICI hop vs NCCL's grouped sends)."""
    size = _axis_size(axis)
    src_of = np.full((size,), -1, np.int64)
    for s, d in pairs:
        src_of[d] = s
    g = lax.all_gather(x, axis)  # [size, ...]
    my_src = jnp.asarray(src_of, jnp.int32)[lax.axis_index(axis)]
    picked = jax.tree_util.tree_map(
        lambda a: lax.dynamic_index_in_dim(a, jnp.maximum(my_src, 0), 0, keepdims=False),
        g,
    )
    return jax.tree_util.tree_map(
        lambda a: jnp.where(my_src >= 0, a, jnp.zeros_like(a)), picked
    )


@_instrumented("barrier")
def barrier(axis: str = DEFAULT_AXIS):
    """``comms_t::barrier`` (``core/comms.hpp:389``): XLA programs are
    bulk-synchronous per collective, so a tiny psum is a true rendezvous.
    Returns a token array that must be consumed (data-dependence is what
    orders XLA programs)."""
    return lax.psum(jnp.ones((), jnp.int32), axis)


def comm_split(mesh: Mesh, axis: str) -> dict:
    """Split a multi-axis mesh into per-axis "sub-communicators"
    (``comms_t::comm_split``, ``core/comms.hpp:274``; SUB_COMMUNICATOR slot).

    In the mesh model a sub-communicator along ``axis`` is simply collectives
    over that axis name; this helper returns the axis metadata (name, size)
    callers use to target verbs at the sub-communicator.
    """
    expects(axis in mesh.axis_names, "axis %s not in mesh axes %s", axis, mesh.axis_names)
    return {"axis": axis, "size": mesh.shape[axis]}


def replicated(mesh: Mesh) -> NamedSharding:
    """Sharding for replicated arrays on ``mesh``."""
    return NamedSharding(mesh, P())


def row_sharded(mesh: Mesh, axis: str = DEFAULT_AXIS) -> NamedSharding:
    """Sharding that splits axis 0 across ``axis``."""
    return NamedSharding(mesh, P(axis))
