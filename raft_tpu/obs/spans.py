"""Nested spans on the profiler's clock, recorded as wall-clock spans
when observability is on.

A :func:`span` names one phase of the host's work. It has two outputs,
each with its own switch:

* **Profiler annotation.** While a ``jax.profiler`` session collects
  (``TraceAnnotation.is_enabled()``), the span enters a
  ``jax.profiler.TraceAnnotation`` of the same name and args. It lands
  on the host plane of the session's ``.xplane.pb``, on the clock the
  device trace shares, so an idle stretch of the device can be laid to
  the host phase that covers it. In this mode :meth:`Span.sync` does not
  block: the device trace already holds the device time, and a traced
  run keeps the schedule of an untraced one.

* **Registry record.** With ``RAFT_TPU_OBS`` on, the span is recorded
  into the :mod:`raft_tpu.obs.metrics` registry on its own
  ``perf_counter`` clock and is exportable as Chrome-trace
  ``trace_events`` (:mod:`raft_tpu.obs.export`). JAX dispatch is
  asynchronous, so outputs registered with :meth:`Span.sync` are
  ``block_until_ready``-ed at span end and the recorded duration covers
  the device work (the ``unsynced-timing`` graft-lint rule flags a
  naive ``perf_counter`` delta around a jitted call).

With neither on, ``span()`` returns one shared null scope whose handle
passes values through: nothing is allocated and nothing is timed.
Call sites that would compute span args test :func:`active` first.

While a profiler session collects, a ``gc.callbacks`` hook also marks
every garbage collection as a ``py.gc`` annotation (arg
``generation``). It is installed the first time a span finds the
profiler on, so a process that is never traced runs no callback.

Recorded spans nest by wall-clock containment per thread (the
Perfetto/Chrome ``ph: "X"`` convention); ``depth`` is tracked explicitly
so reporters need not re-derive it.
"""
from __future__ import annotations

import contextlib
import gc
import threading
import time
from typing import Any, Iterator

from jax.profiler import TraceAnnotation

from raft_tpu.obs import metrics, request

_tls = threading.local()


def _stack() -> list:
    st = getattr(_tls, "stack", None)
    if st is None:
        st = _tls.stack = []
    return st


def active() -> bool:
    """True when a span does anything: obs is on, or a profiler session
    collects."""
    return metrics.is_enabled() or TraceAnnotation.is_enabled()


class Span:
    """Mutable scope handle yielded by :func:`span` with obs on."""

    __slots__ = ("name", "args", "_sync")

    def __init__(self, name: str, args: dict):
        self.name = name
        self.args = args
        self._sync: list = []

    def set(self, **kv) -> None:
        """Attach/overwrite trace args (visible in Perfetto's arg pane)."""
        self.args.update(kv)

    def sync(self, outputs):
        """Register ``outputs`` (any pytree of jax arrays) to be
        ``block_until_ready``-ed at span end; returns ``outputs`` so call
        sites can wrap a return value in place."""
        self._sync.append(outputs)
        return outputs


class _NullSpan:
    """Disabled-path stand-in: same surface, does nothing."""

    __slots__ = ()

    def set(self, **kv) -> None:
        pass

    def sync(self, outputs):
        return outputs

    def __enter__(self):
        return self

    def __exit__(self, *exc) -> bool:
        return False


_NULL = _NullSpan()


class _Annotation:
    """Profiler-only span: a ``TraceAnnotation`` and a handle that never
    blocks (``sync`` passes values through, ``set`` becomes metadata)."""

    __slots__ = ("_ann",)

    def __init__(self, name: str, args: dict):
        self._ann = TraceAnnotation(name, **args)

    def set(self, **kv) -> None:
        self._ann.set_metadata(**kv)

    def sync(self, outputs):
        return outputs

    def __enter__(self):
        self._ann.__enter__()
        return self

    def __exit__(self, *exc) -> bool:
        self._ann.__exit__(*exc)
        return False


def span(name: str, **args):
    """A scope named ``name`` with trace args ``args``: a profiler
    annotation while a session collects, a registry span while obs is
    on, nothing otherwise. Use ``sp.sync(out)`` on the yielded handle to
    include device completion in a recorded span's duration."""
    if metrics.is_enabled():
        return _recorded(name, args)
    if TraceAnnotation.is_enabled():
        _hook_gc()
        return _Annotation(name, args)
    return _NULL


@contextlib.contextmanager
def _recorded(name: str, args: dict) -> Iterator[Any]:
    ann = None
    if TraceAnnotation.is_enabled():
        _hook_gc()
        ann = TraceAnnotation(name, **args)
        ann.__enter__()
    reg = metrics.registry()
    st = _stack()
    depth = len(st)
    s = Span(name, dict(args))
    st.append(s)
    ts = reg.now_us()
    t0 = time.perf_counter()
    try:
        yield s
    finally:
        if s._sync:
            import jax

            try:
                jax.block_until_ready(s._sync)
            except Exception:  # graft-lint: ignore[silent-except] — timing must never mask the real error
                pass
        dur = (time.perf_counter() - t0) * 1e6
        if st and st[-1] is s:
            st.pop()
        reg.record_span(
            name, ts, dur, threading.get_ident(), depth, s.args,
            trace=request.current_trace(),
        )
        if ann is not None:
            ann.__exit__(None, None, None)


# -- garbage collection as a span ---------------------------------------------

_gc_hooked = False
#: the open ``py.gc`` annotation (collections do not nest)
_gc_open: list = []


def _hook_gc() -> None:
    # Unlocked: two threads racing here at most install the hook twice,
    # and each copy opens and closes its own annotation.
    global _gc_hooked
    if not _gc_hooked:
        _gc_hooked = True
        if _on_gc not in gc.callbacks:
            gc.callbacks.append(_on_gc)


def _on_gc(phase: str, info: dict) -> None:
    if phase == "start":
        if TraceAnnotation.is_enabled():
            ann = TraceAnnotation("py.gc", generation=info["generation"])
            ann.__enter__()
            _gc_open.append(ann)
    elif _gc_open:
        _gc_open.pop().__exit__(None, None, None)
