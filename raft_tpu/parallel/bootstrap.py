"""Multi-host bootstrap — the raft-dask ``Comms`` analog.

Reference: ``python/raft-dask/raft_dask/common/comms.py:39`` (``Comms``),
``:172`` (``init``), ``:430`` (``_func_init_all``): a Dask cluster
broadcasts an NCCL uniqueId from the root worker, every worker calls
``ncclCommInitRank`` and injects a ``std_comms`` into its handle.

On TPU the entire dance collapses into ``jax.distributed.initialize`` —
the coordinator address plays the uniqueId role, the runtime wires ICI/DCN
collectives, and a global mesh over ``jax.devices()`` is the communicator.
This module wraps that with the same lifecycle nouns (init / parts of a
session / destroy) plus the comms self-test entry point
(``comms/comms_test.hpp:117-155``) runnable on every host.

Single-host degenerate path: ``init_distributed`` is a no-op (local
devices only), so all downstream code is identical on 1 host and on a pod.

Pod usage (one process per host)::

    from raft_tpu.parallel import bootstrap
    bootstrap.init_distributed(coordinator_address="host0:1234",
                               num_processes=4, process_id=rank)
    mesh = bootstrap.global_mesh()          # all chips across all hosts
    ok = bootstrap.run_comms_self_test(mesh)
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from raft_tpu.core.logging import info, warn
from raft_tpu.parallel import comms as comms_mod
from raft_tpu.robust import faults
from raft_tpu.robust.retry import RetryPolicy, retry_call

_initialized = False

#: coordinator bootstrap races its peers — transient connection errors are
#: the norm, so retry them (raft-dask's Comms.init polls the same way)
DEFAULT_INIT_RETRY = RetryPolicy(
    max_attempts=4, base_delay_s=0.2, multiplier=2.0, max_delay_s=5.0,
    retryable=(ConnectionError, TimeoutError, OSError, RuntimeError),
)


class _AlreadyInitialized(Exception):
    """Internal marker: the launcher beat us to ``jax.distributed`` —
    success, not a retryable failure."""


def init_distributed(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
    retry_policy: Optional[RetryPolicy] = DEFAULT_INIT_RETRY,
) -> bool:
    """Initialize the multi-host runtime (``Comms.init`` analog,
    ``raft_dask/common/comms.py:172``).

    With no arguments on a single host this is a no-op returning False
    (local devices already visible); on a pod each host passes the shared
    coordinator address and its rank, and all hosts' devices become
    globally addressable. Safe to call more than once. Transient
    coordinator failures are retried per ``retry_policy`` (pass ``None``
    to fail fast).
    """
    global _initialized
    if _initialized:
        return True

    def _attempt() -> bool:
        global _initialized
        faults.fire("bootstrap.init", coordinator=coordinator_address)
        if coordinator_address is None and jax.process_count() == 1:
            # single-host degenerate path: nothing to bootstrap
            return False
        try:
            jax.distributed.initialize(
                coordinator_address=coordinator_address,
                num_processes=num_processes,
                process_id=process_id,
            )
        except RuntimeError as e:  # already initialized by the launcher
            msg = str(e).lower()
            if "already initialized" in msg or "should only be called once" in msg:
                raise _AlreadyInitialized from e
            raise
        _initialized = True
        info(
            "raft_tpu.parallel.bootstrap: process %d/%d, %d global devices",
            jax.process_index(),
            jax.process_count(),
            len(jax.devices()),
        )
        return True

    try:
        if retry_policy is None:
            return _attempt()
        return retry_call(_attempt, policy=retry_policy, op="bootstrap.init")
    except _AlreadyInitialized:
        _initialized = True
        return True


def shutdown() -> None:
    """``Comms.destroy`` analog."""
    global _initialized
    if _initialized:
        jax.distributed.shutdown()
        _initialized = False


def global_mesh(
    axis_names: Sequence[str] = (comms_mod.DEFAULT_AXIS,),
    shape: Optional[Sequence[int]] = None,
):
    """Mesh over ALL devices (every host's chips). With a 2-D ``shape``
    like ``(n_hosts, chips_per_host)`` the first axis rides DCN and the
    second ICI — the sub-communicator split of ``core/comms.hpp:274``."""
    return comms_mod.make_mesh(jax.devices(), shape=shape, axis_names=axis_names)


def local_mesh(axis_names: Sequence[str] = (comms_mod.DEFAULT_AXIS,)):
    """Mesh over this host's chips only."""
    return comms_mod.make_mesh(jax.local_devices(), axis_names=axis_names)


def run_comms_self_test(mesh=None, axis: str = comms_mod.DEFAULT_AXIS) -> bool:
    """Collective self-test (``comms/comms_test.hpp:117-155``
    ``test_collective_allreduce`` analog), runnable per host after
    bootstrap. Exercises allreduce / allgather / bcast / ppermute /
    barrier over the mesh; returns True when every verb round-trips."""
    from jax.sharding import PartitionSpec as P

    from jax import shard_map

    if mesh is None:
        mesh = global_mesh()
    n = mesh.shape[axis]

    def body(xs):
        # xs: [1] per-rank block holding its rank id
        rank = comms_mod.comm_rank(axis)
        total = comms_mod.allreduce(xs.sum(), op="sum", axis=axis)
        gathered = comms_mod.allgather(xs, axis=axis)  # [n, 1]
        rooted = comms_mod.bcast(xs, root=0, axis=axis)
        shifted = comms_mod.ppermute(
            xs, [(i, (i + 1) % n) for i in range(n)], axis=axis
        )
        comms_mod.barrier(axis=axis)
        ok = (total == n * (n - 1) // 2).astype(jnp.float32)
        ok = ok * (gathered.reshape(-1) == jnp.arange(n, dtype=xs.dtype)).all()
        ok = ok * (rooted[0] == 0).astype(jnp.float32)
        ok = ok * (shifted[0] == (rank - 1) % n).astype(jnp.float32)
        return ok[None]

    fn = shard_map(
        body, mesh=mesh, in_specs=(P(axis),), out_specs=P(axis), check_vma=False
    )
    x = jnp.arange(n, dtype=jnp.float32).reshape(n, 1)[:, 0]
    oks = np.asarray(jax.jit(fn)(x))
    ok = bool(oks.min() >= 1.0)
    if not ok:
        warn("comms self-test FAILED on process %d", jax.process_index())
    return ok
