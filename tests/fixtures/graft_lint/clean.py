"""Clean fixture: the same shapes of code as the bad fixtures, written
the way graft-lint wants them. Must produce zero violations.

Covers the negative space of every rule: static-arg branches,
trace-time shape checks, numpy on static values, explicit dtypes,
module-scope jit, synced wall-clock timing around jitted calls,
aligned tiles within budget, a *derived* (not hard-coded) chunk
budget, except handlers that actually handle, bounded work queues, and
rebuilds that run off-lock.
"""
import collections
import functools
import queue
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


@functools.partial(jax.jit, static_argnames=("squared",))
def fold(x, squared=False):
    if squared:  # static parameter: a Python branch is fine
        x = x * x
    if x.ndim == 1:  # .ndim is a trace-time constant
        x = x[None, :]
    steps = int(np.prod(x.shape))  # numpy on static shape values: fine
    ramp = jnp.arange(x.shape[1], dtype=jnp.float32)
    return jnp.where(x > 0, x, -x) * ramp, steps


relu = jax.jit(lambda x: jnp.maximum(x, 0.0))  # module scope, not a loop


def timed_relu(x):
    # synced timing: block_until_ready inside the region keeps the delta
    # honest, so unsynced-timing stays quiet
    t0 = time.perf_counter()
    y = jax.block_until_ready(relu(x))
    dt = time.perf_counter() - t0
    # scalar-fetch sync is the other accepted idiom
    t1 = time.perf_counter()
    s = float(jnp.sum(relu(x)))
    dt2 = time.perf_counter() - t1
    # untimed region: a delta with no jitted call inside is fine too
    t2 = time.perf_counter()
    overhead = time.perf_counter() - t2
    return y, s, dt + dt2 + overhead


def make_bounded_queues(capacity):
    # unbounded-queue negative space: every construction carries a bound
    # (a literal, a positional maxsize, or a runtime expression the
    # checker trusts)
    pending = queue.Queue(maxsize=1024)
    lifo = queue.LifoQueue(64)
    prio = queue.PriorityQueue(maxsize=capacity)
    window = collections.deque(maxlen=capacity)
    tail = collections.deque([], 16)
    return pending, lifo, prio, window, tail


def publish_atomically(path, payload):
    # non-atomic-write negative space: the open() targets a temp name
    # and the enclosing function renames it onto the published path —
    # the idiom the checker exists to enforce
    import os

    tmp = path + ".tmp"
    with open(tmp, "wb") as f:
        f.write(payload)
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, path)


def read_manifest(path):
    # reads (and appends, which recover via replay) are not flagged
    with open(path, "r", encoding="utf-8") as f:
        head = f.read()
    with open(path, "a+b") as f:
        f.write(b"")
    return head


def close_quietly(stream, fallback):
    # silent-except negative space: a handler that *does* something
    # (returns a fallback / re-raises on the typed path) is fine
    try:
        stream.close()
    except OSError:
        return fallback
    except Exception:
        raise
    return stream


def compact_off_lock(build, rows, lock):
    # blocking-under-lock negative space: pin under the lock, run the
    # rebuild outside it, re-enter briefly for the pointer flip — the
    # background-compaction shape the rule exists to push toward
    with lock:
        pinned = list(rows)
    index = build(pinned)  # off-lock: writers and searchers proceed
    with lock:
        published = index
    return published


def _copy_kernel(x_ref, o_ref, acc_ref):
    acc_ref[...] = x_ref[...]
    o_ref[...] = acc_ref[...]


# derived from the declarations below, not hard-coded — stale-budget
# only inspects integer-literal assignments
_COPY_CHUNK_BUDGET = int(16 * 1024 * 1024 * 0.75) - 3 * 256 * 128 * 4


def tiled_copy(x):
    return pl.pallas_call(
        _copy_kernel,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        grid=(x.shape[0] // 256,),
        in_specs=[pl.BlockSpec((256, 128), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((256, 128), lambda i: (i, 0)),
        scratch_shapes=[pltpu.VMEM((256, 128), jnp.float32)],
    )(x)


def broadcast_sizes(sizes, axis):
    # gather-merge negative space: a single all_gather with no top-k
    # consumer is a verb implementation detail, not a candidate exchange
    return jax.lax.all_gather(sizes, axis)


def gather_then_pick(blocks, sizes, root, axis):
    # two all_gathers but no merge over the concatenation (the gatherv
    # shape): also fine
    b = jax.lax.all_gather(blocks, axis)
    s = jax.lax.all_gather(sizes, axis)
    return b[root], s[root]


@jax.jit
def _stage(x):
    return jnp.tanh(x)


def overlapped_pipeline(chunks):
    # sync-transfer-in-loop negative space: the double-buffer idiom —
    # iteration i blocks only after i+1's work is in flight, and the
    # blocked-on name (`cur`) is bound from a Name, not a dispatch
    out = []
    nxt = _stage(chunks[0])
    for i in range(len(chunks)):
        cur = nxt
        if i + 1 < len(chunks):
            nxt = _stage(chunks[i + 1])
        out.append(np.asarray(cur))
    return out


def hoisted_sync(chunks):
    # dispatch everything, then one sync outside the loop: also fine
    ys = []
    for c in chunks:
        y = _stage(c)
        ys.append(y)
    return [np.asarray(y) for y in ys]


class MutableIndex:
    # lock-order negative space: the declared order (lock_order.toml) —
    # _compact_mutex strictly before _lock — resolved via the class name
    def __init__(self):
        import threading

        self._lock = threading.RLock()
        self._compact_mutex = threading.Lock()
        self._generation = 0

    def compact_declared_order(self):
        with self._compact_mutex:
            with self._lock:
                self._generation += 1
        return self._generation


def mask_by_root(x, root, axis):
    # collective-divergence negative space: rank-dependent *data* is
    # fine — every rank issues the same psum; the rank only selects
    # values inside it
    r = jax.lax.axis_index(axis)
    contribution = jnp.where(r == root, x, jnp.zeros_like(x))
    return jax.lax.psum(contribution, axis)


def uniform_shape_branch(x, axis, n):
    # a branch on axis *size* (or any value every rank agrees on) takes
    # the same arm on every rank — not divergence
    if n == 1:
        return x
    return jax.lax.psum(x, axis)


def symmetric_rank_branch(x, axis):
    # both arms of a rank-dependent branch issue the same collective
    # sequence: every rank still reaches one psum — no hang
    r = jax.lax.axis_index(axis)
    if r == 0:
        return jax.lax.psum(x * 2.0, axis)
    else:
        return jax.lax.psum(x, axis)


def record_dynamic_metric(obs, kind, value):
    # metric-drift negative space: dynamic names are outside the static
    # namespace the doc table documents
    name = f"fixture.{kind}.count"
    obs.inc(name, value)


def record_bounded_labels(obs, rid, trace_id, latency_ms):
    # unbounded-label negative space: enum literals and small-domain ids
    # (a replica ordinal) are bounded; the observe trace_id keyword is
    # the exemplar channel, not a label
    obs.inc("serve.requests", index_id="main", algo="ivf_flat")
    obs.inc("serve.slow_shards", index_id="main", shard=str(rid))
    obs.observe("serve.time_in_queue_ms", latency_ms, trace_id=trace_id)


def trace_documented_phase(obs, queries):
    # orphan-span negative space: a documented taxonomy name is fine,
    # and dynamic span names are outside the static taxonomy
    with obs.span("host.fetch", rows=len(queries)):
        phase = f"fixture.{len(queries)}.phase"
        with obs.span(phase):
            return queries


# fault-point-drift negative space: every seam here is documented in
# docs/robustness.md and exercised by the chaos tests
FAULT_POINTS = (
    "wal.append",
    "manifest.swap",
)


# --- guarded-field / guard-inference / thread-lifecycle negative space ---
import threading  # noqa: E402  (grouped with the section it serves)


class Compactor:
    """Every ``Compactor._pending`` touch here is covered: own-__init__,
    lock held directly, lock proven held at every call site (entry-held
    analysis), and a write_guarded atomic-reference read. The class name
    deliberately matches the manifest [[guards]] entry so the clean
    fixture exercises the rule's escapes, not its absence."""

    def __init__(self):
        self._state_lock = threading.Lock()
        self._pending = False  # own-__init__: pre-publication escape
        self._thread = None

    def request(self):
        with self._state_lock:
            self._pending = True

    def _note_pending_locked(self):
        # bare write, but the entry-held fixpoint proves the only call
        # site already holds compactor.state
        self._pending = True

    def drive(self):
        with self._state_lock:
            self._note_pending_locked()

    @property
    def running(self):
        # write_guarded field: a lock-free *read* of the atomic
        # reference is the sanctioned snapshot idiom
        return self._thread is not None


def fresh_compactor():
    # fresh-object escape: not yet visible to any other thread
    c = Compactor()
    c._pending = True
    return c


class CleanWorker:
    """thread-lifecycle positive: daemon'd thread, joined on the stop
    path of the owning object."""

    def __init__(self):
        self._stop = threading.Event()
        self._thread = None

    def start(self):
        self._thread = threading.Thread(target=self._run, daemon=True)
        self._thread.start()

    def _run(self):
        while not self._stop.wait(0.01):
            pass

    def stop(self):
        self._stop.set()
        if self._thread is not None:
            self._thread.join()


def plan_routed_search(index, queries, k, mode="auto"):
    # scattered-auto negative: the "auto" branch calls its one rule in
    # raft_tpu.plan — the sanctioned pattern
    from raft_tpu import plan as _plan

    if mode == "auto":
        mode = _plan.plan_search_mode(queries.shape[0], on_tpu=False, fused_ok=False)
    return index.run(queries, k, mode)


def validate_mode(mode):
    # scattered-auto negative: membership validation is input checking,
    # not a dispatch decision
    assert mode in ("auto", "scan", "probe", "fused")
    return mode
