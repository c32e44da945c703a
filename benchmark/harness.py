"""One run of one cell: set-up, a measured window, the comparison with the
plain reference, and the result line.

Everything that belongs to one configuration, traffic mix, index family
or per-layer metric is a file of its own, found by its name:

- ``BENCHMARK.json`` names each cell's configuration and traffic, and
  the configuration's file;
- ``benchmark/traffic/<traffic>.json`` is the mix;
- ``benchmark/families/<family>.py`` builds and registers the index the
  configuration's ``family`` names;
- ``benchmark/metrics/<metric>.py`` reads one per-layer metric; its
  ``read(ctx)`` returns a number, or None when it finds nothing to read.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import os
import sys
import tempfile
import time
from typing import Optional

import numpy as np

from benchmark import check, corpus, loadgen, reference

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


class NoDevice(RuntimeError):
    """No accelerator, or fewer chips than the cell asks for."""


# -- lookup by name ---------------------------------------------------------


def load_benchmark(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def entry(entries: list, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r} in BENCHMARK.json")


def _load_module(path: str, name: str):
    if not os.path.isfile(path):
        raise FileNotFoundError(path)
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_config(bench: dict, name: str, root: str = ROOT) -> dict:
    with open(os.path.join(root, entry(bench["configs"], name, "configuration")["file"])) as f:
        return json.load(f)


def load_traffic(name: str, base: str = HERE) -> dict:
    with open(os.path.join(base, "traffic", name + ".json")) as f:
        return json.load(f)


def load_family(name: str, base: str = HERE):
    return _load_module(os.path.join(base, "families", name + ".py"), "bench_family_" + name)


def load_reader(name: str, base: str = HERE):
    mod = _load_module(os.path.join(base, "metrics", name + ".py"),
                       "bench_metric_" + name.replace(".", "_").replace("-", "_"))
    return mod.read


def cell_metrics(bench: dict, cell: str, kind: str) -> list:
    """The ``end_to_end`` or ``per_layer`` entries that this cell reports."""
    return [m for m in bench[kind] if cell in m.get("workloads", [cell])]


def overlay(base: dict, over: dict) -> dict:
    """``base`` with ``over``'s keys put in, nested groups merged."""
    out = dict(base)
    for k, v in over.items():
        out[k] = overlay(out[k], v) if isinstance(v, dict) and isinstance(out.get(k), dict) else v
    return out


# -- the device --------------------------------------------------------------


def load_peaks(kind: str, base: str = HERE) -> dict:
    with open(os.path.join(base, "peaks.json")) as f:
        table = json.load(f)["devices"]
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json")
    return table[kind]


def devices_for(chips: int, rehearsal: bool):
    import jax

    devs = jax.devices()
    if not rehearsal and devs[0].platform != "tpu":
        raise NoDevice(f"no TPU: JAX found platform {devs[0].platform!r}")
    if len(devs) < chips:
        raise NoDevice(f"the cell asks for {chips} chips; JAX found {len(devs)}")
    return devs[:chips]


def peak_bytes(devs) -> int:
    peaks = [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0)) for d in devs]
    return max(peaks)


class CompileCounter:
    """Counts JAX traces and backend compiles as they happen."""

    EVENTS = {"/jax/core/compile/jaxpr_trace_duration": "traces",
              "/jax/core/compile/backend_compile_duration": "compiles"}

    def __init__(self):
        import jax

        self.counts = {"traces": 0, "compiles": 0}
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        key = self.EVENTS.get(event)
        if key:
            self.counts[key] += 1

    def snapshot(self) -> dict:
        return dict(self.counts)


# -- one run -----------------------------------------------------------------


@dataclasses.dataclass
class Context:
    """What a per-layer reader may read."""

    cfg: dict
    window: loadgen.Window
    facts: dict
    peaks: Optional[dict]
    pool: Optional[np.ndarray] = None  # the query pool, rows as ``window`` names them
    trace: object = None  # trace.Summary of the traced window, or None
    family: object = None


def log(**fields) -> None:
    print(json.dumps(fields), file=sys.stderr, flush=True)


def drive(c, seconds: float, seed: int, first_row: int = 0, rate: Optional[float] = None):
    """The cell's traffic against its engine for ``seconds``."""
    t = c.traffic
    if t["kind"] == "closed":
        return loadgen.closed(c.engine, "cell", c.pool, c.k, seconds, clients=t["clients"],
                              request_rows=t["request_rows"], first_row=first_row)
    if t["kind"] == "open":
        return loadgen.open_loop(c.engine, "cell", c.pool, c.k, seconds,
                                 rate=rate or t["rate_qps"], seed=seed,
                                 request_rows=t["request_rows"], first_row=first_row)
    raise ValueError(f"unknown traffic kind {t['kind']!r}")


@dataclasses.dataclass
class Cell:
    """A cell after set-up: the index built and registered, warm."""

    bench: dict
    cfg: dict
    traffic: dict
    family: object
    devs: list
    peaks: Optional[dict]
    counter: CompileCounter
    k: int
    data: object  # the corpus on the device, or None when the family drops it
    queries: object  # the query pool on the device
    pool: np.ndarray  # the query pool on the host
    engine: object
    facts: dict
    build_s: float
    setup_s: float = 0.0


def set_up(workload: str, seed: int, *, rehearsal: bool = False,
           t_start: Optional[float] = None, root: str = ROOT, base: str = HERE) -> Cell:
    """Corpus, build, register, warm the cell's buckets, settle."""
    t_start = time.perf_counter() if t_start is None else t_start
    bench = load_benchmark(root)
    cell = entry(bench["workloads"], workload, "workload")
    cfg = load_config(bench, cell["config"], root)
    traffic = load_traffic(cell["traffic"], base)
    if rehearsal:
        cfg = overlay(cfg, cfg.get("rehearsal", {}))
        traffic = overlay(traffic, traffic.get("rehearsal", {}))
    family = load_family(cfg["family"], base)
    devs = devices_for(int(cell["chips"]), rehearsal)
    import jax

    from raft_tpu.serve import ServingEngine

    peaks = None if rehearsal else load_peaks(devs[0].device_kind, base)
    counter = CompileCounter()
    k = int(cfg["k"])
    data, queries = corpus.make(cfg, seed)
    jax.block_until_ready((data, queries))
    pool = np.asarray(queries)
    # The build runs twice and the second is timed: balanced k-means
    # compiles a program whose shape depends on the data (its largest
    # mesocluster allocation), so a first build on a new corpus may
    # compile where the last seed's did not. The first build takes that
    # compile into set-up; ``build_s`` is the build with every program
    # compiled, the same work for every seed.
    jax.block_until_ready(jax.tree_util.tree_leaves(family.build(data, cfg)))
    t0 = time.perf_counter()
    index = family.build(data, cfg)
    jax.block_until_ready(jax.tree_util.tree_leaves(index))
    build_s = time.perf_counter() - t0
    engine = ServingEngine(**traffic["engine"])
    family.register(engine, "cell", index, data, cfg)
    facts = family.facts(index, cfg)
    del index
    if not family.KEEPS_CORPUS:
        data = None
    for b in traffic["warm_buckets"]:
        fut = engine.submit("cell", pool[:b], k)
        engine.run_until_idle()
        fut.result()
    c = Cell(bench=bench, cfg=cfg, traffic=traffic, family=family, devs=devs,
             peaks=peaks, counter=counter, k=k, data=data, queries=queries, pool=pool,
             engine=engine, facts=facts, build_s=build_s)
    settle = drive(c, traffic["settle_seconds"], seed + 1, first_row=pool.shape[0] // 2)
    # What set-up left on the heap (JAX's traces and programs, the
    # corpus's host copy) goes to the permanent generation, so that a
    # full collection in the window scans only the window's own objects:
    # unfrozen, one took ~0.1 s and stalled the window (the longest idle
    # gap of a traced v5e run).
    gc.collect()
    gc.freeze()
    c.setup_s = time.perf_counter() - t_start
    log(stage="setup", setup_s=c.setup_s, build_s=build_s, settle_requests=settle.attempted,
        settle_failed=settle.failed, compiles_in_setup=counter.snapshot())
    modes = [ln.strip() for ln in (engine.plan_explain("cell") or "").splitlines()
             if "bucket modes" in ln]
    log(stage="plan", warm_buckets=traffic["warm_buckets"], planner=modes or "off")
    return c


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *, rehearsal: bool = False,
             t_start: Optional[float] = None, root: str = ROOT, base: str = HERE) -> dict:
    """Run ``workload`` once and return the result line's object."""
    import jax

    from benchmark import trace as trace_mod

    c = set_up(workload, seed, rehearsal=rehearsal, t_start=t_start, root=root, base=base)
    before = c.counter.snapshot()
    if trace:
        trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
        trace_mod.start(trace_dir)
        with jax.profiler.TraceAnnotation(trace_mod.WINDOW):
            win = drive(c, seconds, seed)
        trace_mod.stop()
    else:
        win = drive(c, seconds, seed)
    after = c.counter.snapshot()
    mem_peak = peak_bytes(c.devs)
    late = np.asarray(win.lateness_s or [0.0]) * 1e3
    log(stage="window", seconds=win.seconds, attempted=win.attempted, failed=win.failed,
        errors=win.errors, batches=len(win.batches),
        compiles_in_window={key: after[key] - before[key] for key in after},
        generator_late_ms={"p50": float(np.percentile(late, 50)),
                           "p99": float(np.percentile(late, 99)), "max": float(late.max())},
        memory_peak_bytes=mem_peak)

    # the program's state is freed before the reference runs
    data, queries = c.data, c.queries
    c.engine = c.data = None
    gc.collect()
    if data is None:
        data, _ = corpus.make(c.cfg, seed)
    t0 = time.perf_counter()
    ref_d, ref_i = reference.topk(data, queries, c.k, c.cfg["metric"])
    ans = check.Answers(np.concatenate(win.q_rows), np.concatenate(win.ids),
                        np.concatenate(win.dists)) if win.q_rows else \
        check.Answers(np.zeros(0, np.int64), np.zeros((0, c.k), np.int32),
                      np.zeros((0, c.k), np.float32))
    failed = win.attempted - len(win.latency_ms)  # rejected, raised or never answered
    numbers = check.compare(ans, ref_i, ref_d, data, queries, c.cfg["metric"], c.cfg["limits"],
                            failed)
    log(stage="reference", seconds=time.perf_counter() - t0)

    prefix = "cpu_rehearsal." if rehearsal else ""
    device = {"platform": c.devs[0].platform, "kind": c.devs[0].device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": mem_peak}
    metrics, breakdown = {}, None
    if not trace:
        e2e = {
            "qps": win.rows_answered / win.seconds,
            "p99_ms": float(np.percentile(win.latency_ms, 99)) if win.latency_ms else None,
            "recall_at_10": numbers[0].value,
            "build_s": c.build_s,
            "setup_s": c.setup_s,
        }
        for m in cell_metrics(c.bench, workload, "end_to_end"):
            if e2e[m["name"]] is not None:
                metrics[prefix + m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}
    else:
        summary = trace_mod.reduce(trace_dir)
        trace_mod.cleanup(trace_dir)
        ctx = Context(cfg=c.cfg, window=win, facts=c.facts, peaks=c.peaks,
                      pool=c.pool, trace=summary, family=c.family)
        for m in cell_metrics(c.bench, workload, "per_layer"):
            value = load_reader(m["name"], base)(ctx)
            if value is not None:
                metrics[prefix + m["name"]] = {"value": value, "unit": m["unit"]}
        if summary is not None:
            device["busy_s"] = summary.busy_s
            device["window_s"] = summary.window_s
            breakdown = summary.breakdown()
            log(stage="trace", **summary.describe())
    out = {"correct": all(n.ok for n in numbers), "attempted": win.attempted, "failed": failed,
           "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = {n.name: {"value": n.value, "limit": n.limit,
                              "rule": "at_least" if n.at_least else "at_most"} for n in numbers}
    for n in numbers:
        print(f"check {n.name} {n.value!r} {'>=' if n.at_least else '<='} limit {n.limit!r}"
              f" {'ok' if n.ok else 'FAILED'}", file=sys.stderr, flush=True)
    return out
