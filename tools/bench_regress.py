"""Bench-history regression gate: compare the newest BENCH_r*.json
against the prior run and the best run ever recorded.

The driver snapshots every bench invocation as ``BENCH_r<NN>.json`` with
``{n, cmd, rc, tail, parsed}`` where ``tail`` is the (truncated) last
chunk of stdout and ``parsed`` is the headline metric when the run
printed one. The tail usually ends mid-JSON, so rows are recovered by
raw-decoding every ``{"config": ...}`` object that survived the
truncation — partial objects at the cut point are simply skipped.

Comparisons per config row (and for the headline metric):

* qps     — flag when it drops more than ``--qps-drop`` vs the prior
            run, or vs the best-ever value (higher is better),
* p99_ms  — flag when it rises more than ``--p99-rise`` vs prior
            (lower is better; sub-``--ms-floor`` values are noise),
* recall  — flag when it drops more than ``--recall-drop`` absolute.

Exit codes (the CI contract): 0 clean, 1 regression found, 2 not enough
comparable data. ``--smoke`` runs the full pipeline but always exits 0
(unless the tool itself crashes) — that's the ``__graft_entry__``
dryrun wiring, which only wants "the parser still understands the
repo's own BENCH files".
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import re
import sys
from typing import Dict, List, Optional, Tuple

_RUN_RE = re.compile(r"BENCH_r(\d+)\.json$")

#: per-row metrics we understand: name -> (direction, kind, tolerance)
#: direction +1 = higher is better, -1 = lower is better; ``tolerance``
#: names the argparse knob holding the allowed delta (fractional for
#: "ratio" metrics, absolute for "absolute" ones)
_METRICS = {
    "qps": (+1, "ratio", "qps_drop"),
    "p99_ms": (-1, "ratio", "p99_rise"),
    "recall": (+1, "absolute", "recall_drop"),
    # tiered / tiered_sharded phase columns (bench.py): host-tier fetch
    # traffic and ICI wire traffic regress by growing, overlap by shrinking
    "fetch_bytes_per_query": (-1, "ratio", "bytes_rise"),
    "wire_bytes_per_query": (-1, "ratio", "bytes_rise"),
    "overlap_efficiency": (+1, "absolute", "overlap_drop"),
    # dist_build phase columns (bench.py): per-iteration build-comms
    # traffic regresses by growing, the full:ca reduction by shrinking
    "wire_bytes_per_iter": (-1, "ratio", "bytes_rise"),
    "build_bytes_ratio": (+1, "ratio", "bytes_rise"),
    # obs_overhead phase column (bench.py): fractional QPS cost of the
    # always-on recorder + time-series pipeline on the serve row —
    # regresses by growing (absolute: the fraction itself is the delta)
    "recorder_overhead_frac": (-1, "absolute", "overhead_rise"),
    # control_plane phase column (bench.py): the leader-kill failover
    # drill's kill->election window — regresses by growing; rides the
    # p99 tolerance since both are tail-latency-class wall-clock
    "unavailability_ms": (-1, "ratio", "p99_rise"),
}


def discover(bench_dir: str) -> List[Tuple[int, str]]:
    """All ``BENCH_r*.json`` under ``bench_dir``, sorted by run number."""
    out = []
    for path in glob.glob(os.path.join(bench_dir, "BENCH_r*.json")):
        m = _RUN_RE.search(os.path.basename(path))
        if m:
            out.append((int(m.group(1)), path))
    return sorted(out)


def extract_rows(tail: str) -> List[dict]:
    """Recover complete ``{"config": ...}`` row objects from a
    (possibly mid-JSON-truncated) stdout tail."""
    rows = []
    dec = json.JSONDecoder()
    for m in re.finditer(r'\{"config"', tail):
        try:
            obj, _ = dec.raw_decode(tail, m.start())
        except ValueError:
            continue  # cut off by the tail truncation — not a real row
        if isinstance(obj, dict) and isinstance(obj.get("config"), str):
            rows.append(obj)
    return rows


def load_run(path: str) -> Optional[dict]:
    """One run's comparable surface: ``{n, rc, rows, headline}`` or
    ``None`` when the file is unreadable."""
    try:
        with open(path) as f:
            rec = json.load(f)
    except (OSError, ValueError):
        return None
    rows: Dict[str, dict] = {}
    for row in extract_rows(rec.get("tail") or ""):
        key = row["config"]
        i = 2
        while key in rows:  # same config string twice (different section)
            key = f"{row['config']}#{i}"
            i += 1
        rows[key] = row
    return {
        "n": int(rec.get("n", -1)),
        "path": path,
        "rc": int(rec.get("rc", 1)),
        "rows": rows,
        "headline": rec.get("parsed") or None,
    }


def _metric_values(row: dict) -> Dict[str, float]:
    out = {}
    for name in _METRICS:
        v = row.get(name)
        if isinstance(v, (int, float)) and not isinstance(v, bool):
            out[name] = float(v)
    return out


def _check(name: str, new: float, ref: float, ref_label: str,
           args) -> Optional[str]:
    """One metric comparison; returns a human-readable regression line
    or ``None`` when within tolerance."""
    direction, kind, tol_name = _METRICS[name]
    tol = getattr(args, tol_name)
    if kind == "absolute":
        drop = ref - new if direction > 0 else new - ref
        if drop > tol:
            return (f"{name} {new:.4f} vs {ref_label} {ref:.4f} "
                    f"(drop {drop:.4f} > {tol:.4f})")
        return None
    if direction > 0:  # qps: flag a fractional drop
        if ref <= 0:
            return None
        drop = 1.0 - new / ref
        if drop > tol:
            return (f"{name} {new:.1f} vs {ref_label} {ref:.1f} "
                    f"(-{drop:.0%} > {tol:.0%})")
        return None
    # lower-is-better ratio (p99, byte counters): flag a fractional rise;
    # wall-clock metrics additionally ignore sub-floor values (timer
    # noise — byte counters are deterministic, so they get no floor)
    if name.endswith("_ms") and ref < args.ms_floor and new < args.ms_floor:
        return None
    if ref <= 0:
        return None
    rise = new / ref - 1.0
    if rise > tol:
        unit = "ms" if name.endswith("_ms") else ""
        return (f"{name} {new:.3f}{unit} vs {ref_label} {ref:.3f}{unit} "
                f"(+{rise:.0%} > {tol:.0%})")
    return None


def compare(runs: List[dict], args) -> Tuple[List[str], int]:
    """Compare the newest clean run against prior + best-ever.

    Returns ``(regression_lines, n_comparisons)``.
    """
    clean = [r for r in runs if r["rc"] == 0 and (r["rows"] or r["headline"])]
    if len(clean) < 2:
        return [], 0
    newest, history = clean[-1], clean[:-1]
    regressions: List[str] = []
    n_cmp = 0

    # -- per-config rows -----------------------------------------------------
    for key, row in sorted(newest["rows"].items()):
        vals = _metric_values(row)
        for name, new_v in sorted(vals.items()):
            refs = []
            # "prior" = most recent older run that measured this config
            for h in reversed(history):
                h_row = h["rows"].get(key)
                if h_row is not None and name in _metric_values(h_row):
                    refs.append((f"prior(r{h['n']:02d})",
                                 _metric_values(h_row)[name]))
                    break
            direction, _, _tol = _METRICS[name]
            hist_vals = [
                (h["n"], _metric_values(h["rows"][key])[name])
                for h in history
                if key in h["rows"] and name in _metric_values(h["rows"][key])
            ]
            if hist_vals:
                best_n, best_v = (max if direction > 0 else min)(
                    hist_vals, key=lambda t: direction * t[1]
                )
                refs.append((f"best(r{best_n:02d})", best_v))
            for ref_label, ref_v in refs:
                n_cmp += 1
                msg = _check(name, new_v, ref_v, ref_label, args)
                if msg:
                    regressions.append(f"[{key}] {msg}")

    # -- headline metric -----------------------------------------------------
    head = newest["headline"]
    if head and isinstance(head.get("value"), (int, float)):
        metric = head.get("metric", "headline")
        hist = [
            (h["n"], float(h["headline"]["value"]))
            for h in history
            if h["headline"] and h["headline"].get("metric") == metric
            and isinstance(h["headline"].get("value"), (int, float))
        ]
        if hist:
            new_v = float(head["value"])
            prior_n, prior_v = hist[-1]
            best_n, best_v = max(hist, key=lambda t: t[1])
            for ref_label, ref_v in (
                (f"prior(r{prior_n:02d})", prior_v),
                (f"best(r{best_n:02d})", best_v),
            ):
                n_cmp += 1
                msg = _check("qps", new_v, ref_v, ref_label, args)
                if msg:
                    regressions.append(f"[headline {metric}] {msg}")
    return regressions, n_cmp


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="bench_regress",
        description="flag bench regressions across BENCH_r*.json history",
    )
    ap.add_argument("--dir", default=os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), help="directory holding BENCH_r*.json")
    ap.add_argument("--qps-drop", type=float, default=0.25,
                    help="flag qps drops beyond this fraction (default 0.25)")
    ap.add_argument("--p99-rise", type=float, default=0.50,
                    help="flag p99 rises beyond this fraction (default 0.50)")
    ap.add_argument("--recall-drop", type=float, default=0.02,
                    help="flag absolute recall drops beyond this (default 0.02)")
    ap.add_argument("--bytes-rise", type=float, default=0.50,
                    help="flag fetch/wire bytes-per-query rises beyond this "
                         "fraction (default 0.50)")
    ap.add_argument("--overlap-drop", type=float, default=0.25,
                    help="flag absolute overlap_efficiency drops beyond this "
                         "(default 0.25)")
    ap.add_argument("--overhead-rise", type=float, default=0.02,
                    help="flag absolute recorder_overhead_frac rises beyond "
                         "this (default 0.02 — the <2%% overhead contract)")
    ap.add_argument("--ms-floor", type=float, default=0.05,
                    help="ignore p99 deltas when both sides sit under this")
    ap.add_argument("--smoke", action="store_true",
                    help="parse + compare but always exit 0 (CI dryrun wiring)")
    args = ap.parse_args(argv)

    found = discover(args.dir)
    runs = [r for r in (load_run(p) for _, p in found) if r is not None]
    usable = [r for r in runs if r["rc"] == 0 and (r["rows"] or r["headline"])]
    print(f"bench_regress: {len(found)} BENCH file(s), "
          f"{len(usable)} with comparable data")
    for r in runs:
        tag = "skip (rc!=0)" if r["rc"] != 0 else (
            "skip (no rows)" if not (r["rows"] or r["headline"]) else "ok")
        print(f"  r{r['n']:02d}: rc={r['rc']} rows={len(r['rows'])} "
              f"headline={'yes' if r['headline'] else 'no'} [{tag}]")

    regressions, n_cmp = compare(runs, args)
    if n_cmp == 0:
        if len(usable) < 2:
            print("bench_regress: not enough clean runs (need 2+)")
        else:
            print("bench_regress: no shared config/headline between the "
                  "newest run and history — nothing to gate on")
        return 0 if args.smoke else 2
    newest = usable[-1]
    print(f"bench_regress: r{newest['n']:02d} vs history — "
          f"{n_cmp} comparison(s), {len(regressions)} regression(s)")
    for line in regressions:
        print(f"  REGRESSION {line}")
    if regressions and not args.smoke:
        return 1
    if not regressions:
        print("bench_regress: clean")
    return 0


if __name__ == "__main__":
    sys.exit(main())
