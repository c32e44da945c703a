#!/usr/bin/env python
"""Summarize raft_tpu.obs artifacts: a metrics JSONL snapshot
(``obs.write_metrics_jsonl``) and/or a Chrome-trace JSON
(``obs.write_trace``).

Usage::

    python tools/obs_report.py bench_artifacts/metrics.jsonl
    python tools/obs_report.py bench_artifacts/trace.json --top 15
    python tools/obs_report.py bench_artifacts/metrics.jsonl bench_artifacts/trace.json

Prints the top spans by **self-time** (wall-clock minus the wall-clock of
nested child spans, computed per thread with a stack sweep — the number
that says where time actually went, not just which outermost span
contained it), then the counter/gauge tables and histogram summaries.

When several files are given, spans and metrics are each taken from the
first file that provides them (a JSONL snapshot and the trace exported
from the same registry describe the same spans — reading both would
double-count). Pure stdlib; safe to run anywhere.
"""
from __future__ import annotations

import argparse
import json
import sys
from typing import Any, Dict, List, Optional, Tuple


def parse_file(path: str) -> Dict[str, Any]:
    """Parse one artifact into ``{"spans": [...], "counters": {...},
    "gauges": {...}, "histograms": {...}}``. JSONL snapshots carry all
    four; Chrome traces carry spans (ph "X") and counters (ph "C")."""
    out: Dict[str, Any] = {
        "spans": [], "counters": {}, "gauges": {}, "histograms": {},
        "spans_dropped": 0,
    }
    if path.endswith(".jsonl"):
        with open(path, "r", encoding="utf-8") as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                rec = json.loads(line)
                kind = rec.get("kind")
                if kind == "span":
                    out["spans"].append(
                        {
                            "name": rec["name"],
                            "ts": float(rec["ts_us"]),
                            "dur": float(rec["dur_us"]),
                            "tid": rec.get("tid", 0),
                            "trace": rec.get("trace") or [],
                        }
                    )
                elif kind in ("counter", "gauge"):
                    out[kind + "s"][_key(rec)] = rec.get("value", 0.0)
                elif kind == "histogram":
                    out["histograms"][_key(rec)] = {
                        "count": rec.get("count", 0),
                        "sum": rec.get("sum", 0.0),
                        "exemplars": rec.get("exemplars") or [],
                    }
        return out
    with open(path, "r", encoding="utf-8") as f:
        doc = json.load(f)
    out["spans_dropped"] = int(
        (doc.get("otherData") or {}).get("spans_dropped", 0) or 0
    )
    for ev in doc.get("traceEvents", []):
        ph = ev.get("ph")
        if ph == "X":
            args = ev.get("args") or {}
            out["spans"].append(
                {
                    "name": ev["name"],
                    "ts": float(ev["ts"]),
                    "dur": float(ev["dur"]),
                    "tid": ev.get("tid", 0),
                    "trace": args.get("trace") or [],
                }
            )
        elif ph == "C":
            out["counters"][ev["name"]] = ev.get("args", {}).get("value", 0.0)
    return out


#: counter-name prefixes that describe which search kernel/mode a query
#: actually took (includes the rabitq/pq lut labels on ivf_pq.search.*
#: and the mutable delta segment's fused-vs-exact routing)
_DISPATCH_PREFIXES = (
    "ivf_pq.search.",
    "ivf_flat.search.",
    "brute_force.search.",
    "cagra.search.",
    "mutable.delta.",
)

#: prefixes routed to the "robustness & mutability" health table
_HEALTH_PREFIXES = ("robust.", "mutable.", "faults.")

#: distributed-build comm accounting (comms.build.bytes{phase}/
#: comms.build.launches{phase}) — its own table so the CA-vs-full byte
#: savings are visible per build phase, not buried among serving
#: counters
_BUILD_COMMS_PREFIX = "comms.build."

#: serve-side metrics that belong to the health picture, not the
#: generic serving tables (a generation flip is a mutability event the
#: operator correlates with compactions, not with QPS)
_HEALTH_EXTRAS = ("serve.generation_flips",)


def _key(rec: Dict[str, Any]) -> str:
    labels = rec.get("labels") or {}
    if not labels:
        return rec["name"]
    inner = ",".join(f'{k}="{v}"' for k, v in sorted(labels.items()))
    return f"{rec['name']}{{{inner}}}"


def self_times(spans: List[Dict[str, Any]]) -> List[Tuple[str, float, float]]:
    """Per-span (name, dur_us, self_us) via a per-tid stack sweep over
    wall-clock containment: a span's self-time is its duration minus the
    durations of the spans directly nested inside it."""
    out: List[Tuple[str, float, float]] = []
    by_tid: Dict[Any, List[Dict[str, Any]]] = {}
    for s in spans:
        by_tid.setdefault(s["tid"], []).append(s)
    for tid_spans in by_tid.values():
        # parents first: earlier start, then longer duration on ties
        tid_spans.sort(key=lambda s: (s["ts"], -s["dur"]))
        stack: List[List[Any]] = []  # [end_ts, name, dur, self]
        def flush(upto: float) -> None:
            while stack and stack[-1][0] <= upto:
                end, name, dur, self_us = stack.pop()
                if stack:
                    stack[-1][3] -= dur
                out.append((name, dur, max(self_us, 0.0)))
        for s in tid_spans:
            flush(s["ts"])
            stack.append([s["ts"] + s["dur"], s["name"], s["dur"], s["dur"]])
        flush(float("inf"))
    return out


def aggregate(per_span: List[Tuple[str, float, float]]) -> List[Dict[str, Any]]:
    """Aggregate per-span rows into per-name totals sorted by self-time."""
    agg: Dict[str, Dict[str, Any]] = {}
    for name, dur, self_us in per_span:
        row = agg.setdefault(name, {"name": name, "count": 0, "total_us": 0.0, "self_us": 0.0})
        row["count"] += 1
        row["total_us"] += dur
        row["self_us"] += self_us
    return sorted(agg.values(), key=lambda r: -r["self_us"])


def tail_attribution(
    spans: List[Dict[str, Any]],
    histograms: Dict[str, Dict[str, Any]],
    top: int = 3,
) -> List[Dict[str, Any]]:
    """Attribute the slowest exemplar traces to their per-phase self-time.

    Histogram exemplars name concrete request traces; for the ``top``
    worst (largest exemplar value, deduped by trace ID) this resolves
    each trace's spans and runs the same self-time sweep restricted to
    them, answering "where did THIS p99 request spend its time — queue,
    dispatch, fetch, refine?". Returns one row per trace:
    ``{trace, source, value, dominant, phases: [(name, self_us), ...]}``.
    """
    exemplars: List[Tuple[float, str, str]] = []
    for hname, h in histograms.items():
        for e in h.get("exemplars", []):
            tid = e.get("trace_id")
            if tid:
                exemplars.append((float(e.get("value", 0.0)), str(tid), hname))
    exemplars.sort(key=lambda x: -x[0])
    rows: List[Dict[str, Any]] = []
    seen = set()
    for value, trace_id, hname in exemplars:
        if trace_id in seen:
            continue
        seen.add(trace_id)
        tspans = [s for s in spans if trace_id in (s.get("trace") or [])]
        if not tspans:
            continue
        agg = aggregate(self_times(tspans))
        rows.append(
            {
                "trace": trace_id,
                "source": hname,
                "value": value,
                "dominant": agg[0]["name"],
                "phases": [(r["name"], r["self_us"]) for r in agg],
            }
        )
        if len(rows) >= top:
            break
    return rows


#: robust.breaker.state gauge encoding (see raft_tpu.robust.retry)
_BREAKER_STATES = {0.0: "closed", 1.0: "half_open", 2.0: "open"}


def cluster_health_section(
    gauges: Dict[str, float], health: Optional[Dict[str, Any]] = None
) -> Optional[str]:
    """The "cluster health" section: per-target breaker states decoded
    from the ``robust.breaker.state{target}`` gauges in the artifact,
    plus the aggregated ``ReplicaGroup.health()["cluster"]`` snapshot
    when a health dump is supplied (``--health``). Returns None when
    neither source has anything to say."""
    parts: List[str] = []
    if health:
        cluster = health.get("cluster") or {}
        if cluster:
            rows = [[k, f"{v}" if isinstance(v, str) else f"{v:g}"]
                    for k, v in sorted(cluster.items())]
            parts.append(_table(rows, ["cluster", "value"]))
        replicas = health.get("replicas") or []
        if replicas:
            rows = [
                [str(i), str(r.get("breaker", "?")),
                 f"{r.get('staleness_records', 0):g}",
                 f"{r.get('queue_rows', 0):g}"]
                for i, r in enumerate(replicas)
            ]
            parts.append(_table(rows, ["replica", "breaker", "staleness",
                                       "queue"]))
    breaker_rows = []
    for key, v in sorted(gauges.items()):
        if key.startswith("robust.breaker.state"):
            state = _BREAKER_STATES.get(float(v), f"?{v:g}")
            breaker_rows.append([key, state])
    if breaker_rows:
        parts.append(_table(breaker_rows, ["breaker gauge", "state"]))
    if not parts:
        return None
    return "## cluster health\n" + "\n\n".join(parts)


def _table(rows: List[List[str]], header: List[str]) -> str:
    widths = [max(len(str(r[i])) for r in [header] + rows) for i in range(len(header))]
    def fmt(r):
        return "  ".join(str(c).ljust(w) if i == 0 else str(c).rjust(w)
                         for i, (c, w) in enumerate(zip(r, widths)))
    lines = [fmt(header), fmt(["-" * w for w in widths])]
    lines += [fmt(r) for r in rows]
    return "\n".join(lines)


def render_report(*paths: str, top: int = 10,
                  health: Optional[Dict[str, Any]] = None) -> str:
    """Build the text report over one or more obs artifact files.

    ``health`` is a ``ReplicaGroup.health()`` dump (``--health
    file.json``) rendered as the cluster-health section alongside the
    breaker-state gauges."""
    spans: List[Dict[str, Any]] = []
    counters: Dict[str, float] = {}
    gauges: Dict[str, float] = {}
    histograms: Dict[str, Dict[str, Any]] = {}
    spans_dropped = 0
    for path in paths:
        if not path:
            continue
        parsed = parse_file(path)
        if parsed["spans"] and not spans:
            spans = parsed["spans"]
        if parsed["counters"] and not counters:
            counters = parsed["counters"]
        if parsed["gauges"] and not gauges:
            gauges = parsed["gauges"]
        if parsed["histograms"] and not histograms:
            histograms = parsed["histograms"]
        spans_dropped = max(spans_dropped, parsed.get("spans_dropped", 0))
    # the counter rides JSONL dumps; otherData rides traces — take either
    spans_dropped = max(
        spans_dropped, int(counters.get("obs.spans_dropped", 0))
    )

    sections: List[str] = ["# obs report"]
    if spans:
        agg = aggregate(self_times(spans))[:top]
        rows = [
            [r["name"], r["count"],
             f"{r['self_us'] / 1e3:.2f}", f"{r['total_us'] / 1e3:.2f}",
             f"{r['total_us'] / 1e3 / r['count']:.2f}"]
            for r in agg
        ]
        section = (f"## top {len(rows)} spans by self-time\n"
                   + _table(rows, ["span", "count", "self_ms", "total_ms", "mean_ms"]))
        if spans_dropped:
            section += (
                f"\n(! {spans_dropped} span(s) dropped at the registry cap — "
                "totals undercount; raise Registry(max_spans=) or reset "
                "between phases)"
            )
        sections.append(section)
    tail = tail_attribution(spans, histograms)
    if tail:
        rows = [
            [r["trace"], r["source"], f"{r['value']:.2f}", r["dominant"],
             "; ".join(f"{n} {s / 1e3:.2f}ms" for n, s in r["phases"][:5])]
            for r in tail
        ]
        sections.append(
            "## tail attribution (slowest exemplar traces)\n"
            + _table(rows, ["trace", "exemplar_of", "value", "dominant", "self-time breakdown"])
        )
    # search-path routing gets its own table: the per-mode dispatch
    # counters (fused / scan / probe, lut="rabitq" vs nibble/f32, the
    # delta segment's fused-vs-exact route) answer the first question a
    # perf investigation asks — "which kernel actually ran?" — including
    # silent fused→XLA fallbacks that only show up as a mode shift here
    dispatch_rows = [
        [k, f"{v:g}"]
        for k, v in sorted(counters.items())
        if k.startswith(_DISPATCH_PREFIXES)
    ]
    if dispatch_rows:
        sections.append("## search dispatch\n"
                        + _table(dispatch_rows, ["counter", "value"]))
    # cluster health: replica breaker states (decoded from the state
    # gauges) and, when a health dump rides along, the replica group's
    # aggregated cluster snapshot — the "is the fleet ok" glance before
    # any per-metric digging
    cluster = cluster_health_section(gauges, health)
    if cluster:
        sections.append(cluster)
    # robustness + mutability get their own table: fault fires, retries,
    # fallbacks, WAL traffic (records/bytes/rotations), tombstone
    # fraction, generations, compaction backlog/heartbeat and serving
    # generation flips — the health picture an operator scans first,
    # pulled out of the generic tables so it cannot drown in per-algo
    # serving counters
    health_rows = [
        [k, kind, f"{v:g}"]
        for kind, table in (("counter", counters), ("gauge", gauges))
        for k, v in sorted(table.items())
        if (k.startswith(_HEALTH_PREFIXES) or k.startswith(_HEALTH_EXTRAS))
        and not k.startswith(_DISPATCH_PREFIXES)
    ]
    if health_rows:
        sections.append("## robustness & mutability\n"
                        + _table(health_rows, ["metric", "kind", "value"]))
    # distributed-build comms: per-phase collective launches and
    # wire-model bytes (kmeans_full vs kmeans_ca vs pq_codebook_*,
    # plus the init-only seed allgather) — the table that SHOWS the
    # communication-avoiding savings instead of just asserting them
    build_rows = [
        [k, f"{v:g}"]
        for k, v in sorted(counters.items())
        if k.startswith(_BUILD_COMMS_PREFIX)
    ]
    if build_rows:
        sections.append("## build comms\n"
                        + _table(build_rows, ["counter", "value"]))
    plain = {k: v for k, v in counters.items()
             if not k.startswith(_HEALTH_PREFIXES + _HEALTH_EXTRAS
                                 + _DISPATCH_PREFIXES + (_BUILD_COMMS_PREFIX,))}
    if plain:
        rows = [[k, f"{v:g}"] for k, v in sorted(plain.items())]
        sections.append("## counters\n" + _table(rows, ["counter", "value"]))
    plain_g = {k: v for k, v in gauges.items()
               if not k.startswith(_HEALTH_PREFIXES + _HEALTH_EXTRAS)}
    if plain_g:
        rows = [[k, f"{v:g}"] for k, v in sorted(plain_g.items())]
        sections.append("## gauges\n" + _table(rows, ["gauge", "value"]))
    if histograms:
        rows = [
            [k, h["count"], f"{h['sum'] / h['count']:.3f}" if h["count"] else "-"]
            for k, h in sorted(histograms.items())
        ]
        sections.append("## histograms\n" + _table(rows, ["histogram", "count", "mean"]))
    if len(sections) == 1:
        sections.append("(no spans or metrics found)")
    return "\n\n".join(sections)


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("paths", nargs="+",
                    help="metrics .jsonl and/or Chrome-trace .json files")
    ap.add_argument("--top", type=int, default=10, help="span rows to show")
    ap.add_argument("--health", metavar="FILE", default=None,
                    help="JSON dump of ReplicaGroup.health() rendered as "
                         "the cluster-health section")
    ns = ap.parse_args(argv)
    health = None
    if ns.health:
        with open(ns.health, "r", encoding="utf-8") as f:
            health = json.load(f)
    try:
        print(render_report(*ns.paths, top=ns.top, health=health))
    except (OSError, json.JSONDecodeError, KeyError, ValueError) as e:
        print(f"obs_report: {type(e).__name__}: {e}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
