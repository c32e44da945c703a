"""Load generators that drive ``ServingEngine.submit`` and ``step``.

Copied in idea from the program's own generator, so that a change to the
program cannot move the yardstick:

- ``closed``: ``clients`` callers, each with one request of
  ``request_rows`` queries outstanding, sending the next when the last
  returns. Queries are drawn in order from the pool.
- ``open``: single-query requests at a fixed mean rate. The gaps are a
  fixed set of exponential quantiles (a Poisson process's gaps, the same
  set for every seed), shuffled by the seed. A request is timed from the
  moment it was due, not from when the generator got to it, so a stall
  shows in the latency of every request it delays.

Both run the engine's synchronous loop on the calling thread. The host
work is wrapped in ``jax.profiler.TraceAnnotation`` spans (``bench.submit``,
``bench.step``, ``bench.results``, ``bench.sleep``), which a traced run
uses to say what the host did while the device sat idle.
"""
from __future__ import annotations

import dataclasses
import time
from typing import List

import numpy as np


@dataclasses.dataclass
class Window:
    """What one window sent and got back."""

    t_start: float
    t_end: float = 0.0
    attempted: int = 0
    failed: int = 0
    #: per answered request
    latency_ms: List[float] = dataclasses.field(default_factory=list)
    time_in_queue_ms: List[float] = dataclasses.field(default_factory=list)
    q_rows: List[np.ndarray] = dataclasses.field(default_factory=list)
    ids: List[np.ndarray] = dataclasses.field(default_factory=list)
    dists: List[np.ndarray] = dataclasses.field(default_factory=list)
    #: per dispatched batch: (rows, bucket, pool rows of its queries)
    batches: List[tuple] = dataclasses.field(default_factory=list)
    #: open loop: seconds by which each send was late
    lateness_s: List[float] = dataclasses.field(default_factory=list)
    errors: dict = dataclasses.field(default_factory=dict)

    @property
    def seconds(self) -> float:
        return self.t_end - self.t_start

    @property
    def rows_answered(self) -> int:
        return int(sum(r.shape[0] for r in self.q_rows))


def _span(name):
    import jax

    return jax.profiler.TraceAnnotation(name)


def _collect(win: Window, pending: list, now: float) -> list:
    """Record every finished request; return those still pending. Called
    after each ``step``, which dispatches at most one batch, so the
    requests finished here are that batch."""
    still = []
    batch = []
    for t_due, fut, rows in pending:
        if not fut.done():
            still.append((t_due, fut, rows))
            continue
        exc = fut.exception()
        if exc is not None:
            win.failed += 1
            win.errors[type(exc).__name__] = win.errors.get(type(exc).__name__, 0) + 1
            continue
        res = fut.result()
        win.latency_ms.append((now - t_due) * 1e3)
        win.time_in_queue_ms.append(float(res.time_in_queue_ms))
        win.q_rows.append(rows)
        win.ids.append(np.asarray(res.indices))
        win.dists.append(np.asarray(res.distances))
        batch.append((int(res.batch_rows), int(res.bucket), rows))
    if batch:
        win.batches.append((batch[0][0], batch[0][1], np.concatenate([b[2] for b in batch])))
    return still


def _submit(engine, index_id, pool, rows, k, win):
    from raft_tpu.serve.batcher import DeadlineExceeded, QueueFull

    win.attempted += 1
    try:
        return engine.submit(index_id, pool[rows], k)
    except (QueueFull, DeadlineExceeded) as e:
        win.failed += 1
        win.errors[type(e).__name__] = win.errors.get(type(e).__name__, 0) + 1
        return None


def closed(engine, index_id: str, pool: np.ndarray, k: int, seconds: float, *,
           clients: int, request_rows: int, first_row: int = 0) -> Window:
    """``clients`` closed-loop callers for ``seconds``; then the requests
    still out are answered. The window ends with the last answer."""
    n_pool = pool.shape[0]
    nxt = first_row
    pending = []
    win = Window(t_start=time.perf_counter())
    deadline = win.t_start + seconds
    while True:
        now = time.perf_counter()
        with _span("bench.submit"):
            while now < deadline and len(pending) < clients:
                rows = (nxt + np.arange(request_rows)) % n_pool
                nxt = (nxt + request_rows) % n_pool
                fut = _submit(engine, index_id, pool, rows, k, win)
                if fut is not None:
                    pending.append((time.perf_counter(), fut, rows))
        if not pending:
            break
        with _span("bench.step"):
            engine.step(force=True)
        with _span("bench.results"):
            pending = _collect(win, pending, time.perf_counter())
    win.t_end = time.perf_counter()
    return win


def poisson_offsets(rate: float, seconds: float, seed: int) -> np.ndarray:
    """Send times (s from the start) of ``round(rate * seconds)``
    requests: the gaps are the exponential distribution's quantiles at
    ``(i + 0.5) / n``, in an order drawn from ``seed``."""
    n = max(1, int(round(rate * seconds)))
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n) / rate
    rng = np.random.default_rng(int(seed) % (1 << 63))
    return np.cumsum(rng.permutation(gaps))


def open_loop(engine, index_id: str, pool: np.ndarray, k: int, seconds: float, *,
              rate: float, seed: int, request_rows: int = 1, first_row: int = 0) -> Window:
    """Open-loop requests at ``rate`` per second for ``seconds``; then
    the queue is drained. Latency runs from each request's due time to
    its answer on the host."""
    offsets = poisson_offsets(rate, seconds, seed)
    n_pool = pool.shape[0]
    max_wait = engine.batcher.max_wait_s
    pending = []
    i = 0
    win = Window(t_start=time.perf_counter())
    t0 = win.t_start
    while i < len(offsets) or pending:
        now = time.perf_counter() - t0
        if i < len(offsets) and offsets[i] <= now:
            with _span("bench.submit"):
                while i < len(offsets) and offsets[i] <= now:
                    rows = (first_row + i * request_rows + np.arange(request_rows)) % n_pool
                    win.lateness_s.append(time.perf_counter() - t0 - offsets[i])
                    fut = _submit(engine, index_id, pool, rows, k, win)
                    if fut is not None:
                        pending.append((t0 + offsets[i], fut, rows))
                    i += 1
        if engine.batcher.ready() or (i >= len(offsets) and pending):
            with _span("bench.step"):
                engine.step(force=i >= len(offsets))
            with _span("bench.results"):
                pending = _collect(win, pending, time.perf_counter())
            continue
        # nothing to flush yet: wait for the next send or the flush age
        wake = offsets[i] if i < len(offsets) else now
        if pending:
            wake = min(wake, pending[0][0] - t0 + max_wait)
        dt = wake - (time.perf_counter() - t0)
        if dt > 2e-4:
            with _span("bench.sleep"):
                time.sleep(dt - 1e-4)
    win.t_end = time.perf_counter()
    return win
