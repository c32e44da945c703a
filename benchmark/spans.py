"""From the program's own spans in a traced window to the device's idle
time under each of them.

``raft_tpu.obs.span`` enters a ``jax.profiler.TraceAnnotation`` while a
profiler session collects, so the window's host plane holds the
program's phases (``serve.step`` and its children, ``ivf_flat.search``,
``refine.refine``, ``py.gc`` for a garbage collection, ...) on the clock
the device planes share. This module reads them and lays every idle
instant of the first chip to the innermost program span that covers it
on the thread that drives the engine:

- ``program_spans`` reads the spans whose names fall under
  ``PREFIXES`` (or are ``py.gc``) from the host line that holds the
  ``bench.window`` annotation. The Python tracer's events (``$``-named)
  and the harness's ``bench.*`` spans are not among them.
- ``attribute`` splits each idle gap ``(label, start, end)`` of a
  ``trace.Summary`` into stretches named by the innermost program span
  over them. A ``py.gc`` stretch counts to its nearest enclosing
  program span. A stretch that no program span covers keeps the gap's
  own label: the harness's ``bench.*`` span, or ``idle``.
- ``idle_ms_per_batch`` sums the stretches under some prefixes, per
  dispatched batch: the per-layer metrics ``serve_idle_ms.batch`` and
  ``search_idle_ms.batch`` read it. It is None when the summary carries
  no program span, as a trace of a program without these spans has
  none.

A summary carries program spans as ``program_spans``, a list of
``(name, start_ns, end_ns)``; ``benchmark/idle.py`` sets it.
"""
from __future__ import annotations

import collections
import re
from typing import List, Optional, Sequence, Tuple

from benchmark.trace import WINDOW

GC = "py.gc"
SERVE = ("serve.", "plan.")
SEARCH = ("ivf_flat.", "ivf_pq.", "refine.")
PREFIXES = SERVE + SEARCH
#: a span name as the program writes them: dotted lower-case words
_NAME = re.compile(r"[a-z0-9_]+(\.[a-z0-9_]+)+")

Span = Tuple[str, int, int]


def is_program_span(name: str) -> bool:
    return name == GC or (name.startswith(PREFIXES) and _NAME.fullmatch(name) is not None)


def program_spans(xplane_path: str) -> List[Span]:
    """The program spans of the host thread that holds the window
    annotation, as ``(name, start_ns, end_ns)`` in order of start; empty
    when there is none."""
    import jax

    data = jax.profiler.ProfileData.from_file(xplane_path)
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            events = [(e.name, int(e.start_ns), int(e.duration_ns)) for e in line.events]
            if any(name == WINDOW for name, _, _ in events):
                return sorted(((name, s, s + d) for name, s, d in events
                               if is_program_span(name)), key=lambda sp: sp[1])
    return []


def innermost(spans: Sequence[Span]) -> List[Span]:
    """The union of ``spans`` (nested by containment, as one thread's
    are) cut into ``(name, start, end)`` stretches, each named by the
    innermost span over it; a ``py.gc`` stretch takes the name of its
    nearest enclosing span, and is left out when none encloses it."""
    out: List[Span] = []
    stack: List[Tuple[int, str]] = []  # (end, name) of the open spans
    cur = 0

    def emit(t: int) -> None:
        nonlocal cur
        if t > cur:
            name = next((n for _, n in reversed(stack) if n != GC), None)
            if name is not None:
                out.append((name, cur, t))
        cur = t

    for name, s, e in sorted(spans, key=lambda sp: (sp[1], -sp[2])):
        while stack and stack[-1][0] <= s:
            emit(stack[-1][0])
            stack.pop()
        emit(s)
        stack.append((e, name))
    while stack:
        emit(stack[-1][0])
        stack.pop()
    return out


def attribute(gaps: Sequence[Tuple[str, int, int]], spans: Sequence[Span]) -> List[Span]:
    """Each gap cut into ``(label, start, end)`` stretches: the innermost
    program span over a stretch, else the gap's own label. The
    stretches of a gap add up to the gap."""
    segs = innermost(spans)
    out: List[Span] = []
    j = 0
    for label, s, e in sorted(gaps, key=lambda g: g[1]):
        while j < len(segs) and segs[j][2] <= s:
            j += 1
        t, k = s, j
        while k < len(segs) and segs[k][1] < e:
            name, a, b = segs[k]
            if a > t:
                out.append((label, t, a))
                t = a
            hi = min(b, e)
            if hi > t:
                out.append((name, t, hi))
                t = hi
            k += 1
        if t < e:
            out.append((label, t, e))
    return out


def idle_by_program_span(summary) -> List[Tuple[str, float]]:
    """Seconds of the first chip's idle time under each label, largest
    first."""
    tot = collections.Counter()
    for label, s, e in attribute(summary.gaps, summary.program_spans):
        tot[label] += e - s
    return [(k, v / 1e9) for k, v in tot.most_common()]


def idle_ms_per_batch(ctx, prefixes: Sequence[str]) -> Optional[float]:
    """Idle milliseconds of the first chip a dispatched batch under the
    program spans named with one of ``prefixes``; None when the trace
    holds no program span or the window no batch."""
    t = ctx.trace
    spans = getattr(t, "program_spans", None) if t is not None else None
    if not spans or not ctx.window.batches:
        return None
    ns = sum(e - s for label, s, e in attribute(t.gaps, spans)
             if label.startswith(tuple(prefixes)))
    return ns / 1e6 / len(ctx.window.batches)
