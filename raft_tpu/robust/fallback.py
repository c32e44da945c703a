"""Fused-kernel → XLA fallback policy.

``mode="auto"`` dispatch in :mod:`raft_tpu.neighbors.cagra` /
:mod:`raft_tpu.neighbors.ivf_pq` prefers the fused Pallas kernels on TPU;
when a kernel fails (injected :class:`KernelFailure` chaos, or a real
lowering/runtime error) the query must not — the dispatch catches
:func:`fallback_errors`, records the event here, and re-executes on the
XLA path, which produces identical ids by the PR-2 parity contract.

Explicitly requested ``mode="fused"`` never falls back: the caller asked
for that engine, so the failure propagates.
"""
from __future__ import annotations

import threading
import warnings

from jax.errors import JaxRuntimeError

from raft_tpu import obs
from raft_tpu.core.errors import KernelFailure
from raft_tpu.utils import lockcheck


#: exception types the auto-mode dispatch treats as "kernel failed, XLA can
#: still answer" — typed chaos plus real accelerator-runtime errors
FALLBACK_ERRORS = (KernelFailure, JaxRuntimeError)


def fallback_errors() -> tuple:
    return FALLBACK_ERRORS


_warned: set = set()
_lock = lockcheck.tracked(threading.Lock(), "robust.fallback")


def record_fallback(algo: str, exc: BaseException) -> str:
    """Count a fused→XLA fallback and warn once per (algo, reason).

    Returns the reason label used in the ``fallbacks{algo,reason}``
    counter.
    """
    reason = type(exc).__name__
    obs.inc("fallbacks", algo=algo, reason=reason)
    key = (algo, reason)
    with _lock:
        first = key not in _warned
        if first:
            _warned.add(key)
    if first:
        warnings.warn(
            f"raft_tpu: fused {algo} kernel failed ({reason}: {exc}); "
            "falling back to the XLA path (identical results, lower "
            "throughput). Further fallbacks for this cause are counted in "
            "obs 'fallbacks' but not re-warned.",
            RuntimeWarning,
            stacklevel=3,
        )
    return reason


def reset_warned() -> None:
    """Test hook: forget which (algo, reason) pairs already warned."""
    with _lock:
        _warned.clear()
