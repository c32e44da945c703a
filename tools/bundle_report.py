#!/usr/bin/env python
"""Render a flight-recorder diagnostic bundle (``*.raftbundle``) as a
post-mortem report.

Usage::

    python tools/bundle_report.py bundle-0001-slo.raftbundle
    python tools/bundle_report.py bundle_dir/            # newest bundle
    python tools/bundle_report.py bundle.raftbundle --json

The bundle is the black box :class:`raft_tpu.obs.recorder.FlightRecorder`
writes on a trigger (SLO alert, fault seam, breaker trip, compactor
worker death, or an explicit ``dump()``). This tool answers
the first three incident questions in order: *what tripped* (the
trigger section), *what was the cluster doing* (health + event
timeline), and *where did the slow requests spend their time* (the
exemplar traces, re-attributed with the same self-time sweep
``tools/obs_report.py`` uses).

Loading CRC-verifies the envelope — a torn file is an error, never a
half-read report.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Any, Dict, List, Optional

if __package__ in (None, ""):  # running as `python tools/bundle_report.py`
    sys.path.insert(
        0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    )

from tools.obs_report import _table, aggregate, self_times


def _fmt_ctx(ctx: Dict[str, Any]) -> str:
    return ", ".join(f"{k}={v}" for k, v in sorted(ctx.items())) or "-"


def _fmt_num(v: Any) -> str:
    try:
        return f"{float(v):g}"
    except (TypeError, ValueError):
        return str(v)


def _trigger_section(bundle: Dict[str, Any]) -> str:
    trig = bundle.get("trigger") or {}
    lines = [
        f"cause:    {trig.get('cause', '?')}",
        f"context:  {_fmt_ctx(trig.get('ctx') or {})}",
        f"at:       t={trig.get('t', 0.0):.3f} (monotonic), "
        f"wall={bundle.get('wall_time', 0.0):.3f}",
        f"window:   last {bundle.get('window_s', 0.0):g}s retained",
    ]
    return "## trigger\n" + "\n".join(lines)


def _health_section(bundle: Dict[str, Any]) -> Optional[str]:
    health = bundle.get("health") or {}
    parts: List[str] = []
    for g in health.get("groups") or []:
        cluster = g.get("cluster") or {}
        if cluster:
            rows = [[k, _fmt_num(v)] for k, v in sorted(cluster.items())]
            parts.append(_table(rows, ["cluster", "value"]))
        replicas = g.get("replicas") or []
        if replicas:
            rows = [
                [str(i), r.get("breaker", "?"),
                 _fmt_num(r.get("staleness_records", 0)),
                 _fmt_num(r.get("queue_rows", r.get("queue_depth", 0)))]
                for i, r in enumerate(replicas)
            ]
            parts.append(
                _table(rows, ["replica", "breaker", "staleness", "queue"])
            )
    for i, e in enumerate(health.get("engines") or []):
        if "error" in e:
            parts.append(f"engine[{i}]: {e['error']}")
            continue
        idx = e.get("indexes") or {}
        rows = []
        for iid, st in sorted(idx.items()):
            slo = st.get("slo") or {}
            slo_cell = (
                f"{'ALERT' if slo.get('alerting') else 'ok'} "
                f"burn={slo.get('burn_fast', 0.0):.2f}" if slo else "-"
            )
            rows.append([iid, str(st.get("algo", "?")),
                         str(st.get("mode", "?")),
                         _fmt_num(st.get("generation", 0)), slo_cell])
        if rows:
            parts.append(_table(rows, [f"engine[{i}] index", "algo",
                                       "mode", "gen", "slo"]))
    if not parts:
        return None
    return "## cluster health\n" + "\n\n".join(parts)


#: event kinds the control plane records (docs/replication.md "The
#: control plane"); pulled out of the timeline into their own section
#: because "who was leader when" is the first question of any
#: replication incident
_CONTROL_KINDS = ("election", "fenced", "scale")


def _control_plane_section(bundle: Dict[str, Any]) -> Optional[str]:
    events = [
        e for e in (bundle.get("events") or [])
        if e.get("kind") in _CONTROL_KINDS
    ]
    if not events:
        return None
    t0 = (bundle.get("trigger") or {}).get("t", 0.0)
    rows = []
    for e in events:
        kind = e.get("kind")
        if kind == "election":
            what = (
                f"epoch {e.get('epoch', '?')} -> leader "
                f"{e.get('leader', '?')} ({e.get('reason', '?')})"
            )
        elif kind == "fenced":
            what = (
                f"{e.get('follower', '?')} rejected epoch "
                f"{e.get('epoch', '?')} (fence at {e.get('fence_epoch', '?')})"
            )
        else:
            what = (
                f"{e.get('group', '?')} scaled {e.get('direction', '?')} "
                f"to {e.get('n_replicas', '?')} replicas"
            )
        rows.append([f"{e.get('t', 0.0) - t0:+.3f}s", str(kind), what])
    return "## control plane (elections / fencing / scaling)\n" + _table(
        rows, ["t-trigger", "kind", "what"]
    )


def _events_section(bundle: Dict[str, Any], limit: int) -> Optional[str]:
    events = bundle.get("events") or []
    if not events:
        return None
    t0 = (bundle.get("trigger") or {}).get("t", 0.0)
    rows = []
    for e in events[-limit:]:
        detail = {k: v for k, v in e.items() if k not in ("t", "kind")}
        rows.append([
            f"{e.get('t', 0.0) - t0:+.3f}s",
            str(e.get("kind", "?")),
            _fmt_ctx(detail),
        ])
    head = f"## event timeline (last {len(rows)} of {len(events)})"
    return head + "\n" + _table(rows, ["t-trigger", "kind", "detail"])


def _series_section(bundle: Dict[str, Any]) -> Optional[str]:
    bank = bundle.get("series") or {}
    series = bank.get("series") or []
    if not series:
        return None
    rows = []
    for s in series:
        labels = s.get("labels") or {}
        key = s["name"] + (
            "{" + ",".join(f'{k}="{v}"' for k, v in sorted(labels.items())) + "}"
            if labels else ""
        )
        pts = s.get("points") or []
        if s.get("kind") == "histogram":
            last = f"count={pts[-1][3]:g}" if pts else "-"
        else:
            last = _fmt_num(pts[-1][1]) if pts else "-"
        span = f"{pts[-1][0] - pts[0][0]:.1f}s" if len(pts) > 1 else "-"
        rows.append([key, s.get("kind", "?"), str(len(pts)), span, last])
    stats = bank.get("stats") or {}
    section = "## retained series\n" + _table(
        rows, ["series", "kind", "points", "span", "last"]
    )
    if stats.get("dropped"):
        section += f"\n(! {stats['dropped']} sample(s) dropped at max_series)"
    return section


def _traces_section(bundle: Dict[str, Any]) -> Optional[str]:
    traces = bundle.get("slow_traces") or []
    if not traces:
        return None
    rows = []
    for t in traces:
        spans = [
            {
                "name": s["name"],
                "ts": float(s.get("ts_us", 0.0)),
                "dur": float(s.get("dur_us", 0.0)),
                "tid": s.get("tid", 0),
                "trace": s.get("trace") or [],
            }
            for s in (t.get("spans") or [])
        ]
        if spans:
            agg = aggregate(self_times(spans))
            dominant = agg[0]["name"]
            chain = " -> ".join(
                s["name"] for s in sorted(spans, key=lambda x: x["ts"])
            )
            breakdown = "; ".join(
                f"{r['name']} {r['self_us'] / 1e3:.2f}ms" for r in agg[:5]
            )
        else:
            dominant, chain, breakdown = "-", "-", "-"
        rows.append([
            str(t.get("trace_id", "?")), f"{float(t.get('value', 0.0)):.2f}",
            dominant, chain, breakdown,
        ])
    return "## slowest traces (exemplars)\n" + _table(
        rows, ["trace", "value", "dominant", "span chain", "self-time"]
    )


def _plans_section(bundle: Dict[str, Any]) -> Optional[str]:
    plans = bundle.get("plans") or {}
    texts = [
        f"--- {iid} ---\n{text}" for iid, text in sorted(plans.items()) if text
    ]
    if not texts:
        return None
    return "## plan explain\n" + "\n\n".join(texts)


def _lockcheck_section(bundle: Dict[str, Any]) -> Optional[str]:
    lc = bundle.get("lockcheck") or {}
    if not lc:
        return None
    cov = lc.get("coverage") or {}
    lines = [
        f"witness:     {'on' if lc.get('enabled') else 'off'}",
        f"edges seen:  {len(lc.get('edges') or [])}",
        f"coverage:    {len(cov.get('exercised') or [])}/"
        f"{len(cov.get('declared') or [])} declared edges exercised",
    ]
    for v in lc.get("violations") or []:
        lines.append(f"VIOLATION:   {v}")
    for v in lc.get("field_violations") or []:
        lines.append(f"FIELD RACE:  {v}")
    return "## lockcheck\n" + "\n".join(lines)


def _fingerprint_section(bundle: Dict[str, Any]) -> Optional[str]:
    fp = bundle.get("fingerprint") or {}
    if not fp:
        return None
    rows = [[k, str(v)] for k, v in sorted(fp.items()) if k != "env"]
    rows += [[f"env.{k}", str(v)] for k, v in sorted((fp.get("env") or {}).items())]
    return "## fingerprint\n" + _table(rows, ["key", "value"])


def render_bundle(bundle: Dict[str, Any], path: str = "",
                  events: int = 40) -> str:
    """The full text report for one loaded bundle dict."""
    title = f"# flight-recorder bundle report"
    if path:
        title += f"\n{path}"
    sections = [title, _trigger_section(bundle)]
    for s in (
        _health_section(bundle),
        _control_plane_section(bundle),
        _events_section(bundle, events),
        _series_section(bundle),
        _traces_section(bundle),
        _plans_section(bundle),
        _lockcheck_section(bundle),
        _fingerprint_section(bundle),
    ):
        if s:
            sections.append(s)
    return "\n\n".join(sections)


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("path", help="a .raftbundle file, or a directory "
                                 "(renders the newest bundle in it)")
    ap.add_argument("--events", type=int, default=40,
                    help="event-timeline rows to show")
    ap.add_argument("--json", action="store_true",
                    help="dump the decoded bundle body as JSON instead")
    ns = ap.parse_args(argv)

    from raft_tpu.obs import recorder

    path = ns.path
    if os.path.isdir(path):
        bundles = recorder.list_bundles(path)
        if not bundles:
            print(f"bundle_report: no {recorder.BUNDLE_SUFFIX} files in "
                  f"{path}", file=sys.stderr)
            return 1
        path = bundles[-1]
    try:
        bundle = recorder.load_bundle(path)
    except Exception as e:
        print(f"bundle_report: {type(e).__name__}: {e}", file=sys.stderr)
        return 1
    if ns.json:
        print(json.dumps(bundle, indent=2, sort_keys=True, default=str))
    else:
        print(render_bundle(bundle, path=path, events=ns.events))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
