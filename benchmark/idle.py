"""Trace one window of a cell and lay the device's idle time to the
program's own spans.

    python3 benchmark/idle.py --workload <cell> --seed <n> --seconds <s>
    python3 benchmark/idle.py --workload <cell> --seed <n> --batches 4 --keep <dir>

Set-up and traffic are the cell's, as ``run.py`` runs them; the window
is traced as ``run.py --trace 1`` traces it. No answer is checked. The
last line of standard output is one JSON object:

- ``qps_traced``: query rows answered over the window's seconds;
- ``window_s``, ``busy_s``, ``idle_frac`` (``device_idle_frac.batch``)
  and ``idle_ms_per_batch``, the idle time over the batches;
- ``serve_idle_ms.batch`` and ``search_idle_ms.batch`` as their readers
  in ``metrics/`` compute them, and ``harness_ms``, the rest of the idle
  time a batch (the harness's ``bench.*`` spans, or none);
- ``idle_by_program_span``: idle seconds under each innermost span;
- ``long_gaps``: every idle gap over 50 ms, with the spans under it and
  the milliseconds of garbage collection in it;
- ``device_ops``: the window's ten largest ops by device time.

``--batches n`` sends ``n`` single requests one after another instead of
the cell's traffic, and ``--keep <dir>`` keeps the trace there.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[0] = ROOT

LONG_GAP_NS = 50_000_000


def fixed_batches(c, n: int):
    """``n`` requests of the traffic's ``request_rows``, each sent,
    stepped and collected before the next, under the harness's spans."""
    import numpy as np

    from benchmark import loadgen

    rows_per = c.traffic["request_rows"]
    win = loadgen.Window(t_start=time.perf_counter())
    for i in range(n):
        rows = (i * rows_per + np.arange(rows_per)) % c.pool.shape[0]
        with loadgen._span("bench.submit"):
            fut = loadgen._submit(c.engine, "cell", c.pool, rows, c.k, win)
        with loadgen._span("bench.step"):
            c.engine.step(force=True)
        with loadgen._span("bench.results"):
            loadgen._collect(win, [(time.perf_counter(), fut, rows)], time.perf_counter())
    win.t_end = time.perf_counter()
    return win


def long_gaps(summary, program) -> list:
    """Each idle gap over ``LONG_GAP_NS``: its length, the harness span
    over it, and the milliseconds under each innermost program span and
    under garbage collection."""
    from benchmark import spans

    out = []
    gc_spans = [sp for sp in program if sp[0] == spans.GC]
    for label, s, e in summary.gaps:
        if e - s < LONG_GAP_NS:
            continue
        under = {}
        for name, a, b in spans.attribute([(label, s, e)], program):
            under[name] = under.get(name, 0.0) + (b - a) / 1e6
        gc_ms = sum(max(0, min(b, e) - max(a, s)) for _, a, b in gc_spans) / 1e6
        out.append({"ms": (e - s) / 1e6, "harness": label,
                    "under_ms": dict(sorted(under.items(), key=lambda kv: -kv[1])),
                    "gc_ms": gc_ms})
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--batches", type=int, default=0,
                    help="send this many single requests instead of the cell's traffic")
    ap.add_argument("--keep", default="", help="keep the trace in this directory")
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="run on the CPU at the rehearsal size (no device numbers)")
    args = ap.parse_args(argv)
    if args.cpu_rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
    from benchmark.run import compile_cache

    compile_cache(ROOT)
    import jax

    from benchmark import harness, spans, trace

    c = harness.set_up(args.workload, args.seed, rehearsal=args.cpu_rehearsal, t_start=T_START)
    trace_dir = args.keep or tempfile.mkdtemp(prefix="bench_idle_")
    trace.start(trace_dir)
    with jax.profiler.TraceAnnotation(trace.WINDOW):
        if args.batches:
            win = fixed_batches(c, args.batches)
        else:
            win = harness.drive(c, args.seconds, args.seed)
    trace.stop()
    summary = trace.reduce(trace_dir)
    out = {"workload": args.workload, "seed": args.seed, "window_seconds": win.seconds,
           "rows": win.rows_answered, "qps_traced": win.rows_answered / win.seconds,
           "batches": len(win.batches), "failed": win.failed,
           "device": {"platform": c.devs[0].platform, "kind": c.devs[0].device_kind}}
    if summary is not None:
        summary.program_spans = spans.program_spans(trace.find_xplane(trace_dir))
        ctx = harness.Context(cfg=c.cfg, window=win, facts=c.facts, peaks=c.peaks,
                              pool=c.pool, trace=summary, family=c.family)
        idle_ms = (summary.window_s - summary.busy_s) * 1e3 / max(len(win.batches), 1)
        serve = harness.load_reader("serve_idle_ms.batch")(ctx)
        search = harness.load_reader("search_idle_ms.batch")(ctx)
        out.update({
            "window_s": summary.window_s, "busy_s": summary.busy_s,
            "idle_frac": 1.0 - summary.busy_s / summary.window_s,
            "idle_ms_per_batch": idle_ms,
            "serve_idle_ms.batch": serve, "search_idle_ms.batch": search,
            "harness_ms": idle_ms - (serve or 0.0) - (search or 0.0),
            "program_spans": len(summary.program_spans),
            "idle_by_program_span": spans.idle_by_program_span(summary)[:12],
            "long_gaps": long_gaps(summary, summary.program_spans),
            "device_ops": summary.op_totals()[:10],
        })
    if not args.keep:
        trace.cleanup(trace_dir)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
