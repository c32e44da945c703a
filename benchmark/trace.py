"""From a profiler trace of the window to device busy and idle time,
per-op device time, and idle gaps labelled by what the host was doing.

The harness traces its window with ``start``/``stop`` and wraps the
window in a ``bench.window`` annotation and its own host calls in
``bench.*`` annotations (see ``loadgen.py``). ``reduce`` reads the ``.xplane.pb`` the
profiler wrote:

- device planes are the planes named ``/device:TPU:<n>``; their ops are
  the events of the line named ``XLA Ops``, and the programs that ran
  them the events of the line named ``XLA Modules``;
- the window is the ``bench.window`` annotation's interval on the host
  plane, on the profiler's clock, which the device events share;
- busy time is the union of a chip's op intervals inside the window,
  averaged over the chips; idle is the rest;
- an idle gap is labelled by the harness span on the host that covers
  most of it (``idle`` when none does).
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import os
import shutil
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

WINDOW = "bench.window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def start(log_dir: str) -> None:
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0  # the harness's annotations, not every Python call
    opts.host_tracer_level = 2
    jax.profiler.start_trace(log_dir, profiler_options=opts)


def stop() -> None:
    import jax

    jax.profiler.stop_trace()


def cleanup(log_dir: str) -> None:
    shutil.rmtree(log_dir, ignore_errors=True)


def find_xplane(log_dir: str) -> Optional[str]:
    files = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True))
    return files[-1] if files else None


def union_ns(intervals: np.ndarray) -> int:
    """Total length of the union of ``[start, end)`` intervals [n, 2]."""
    if len(intervals) == 0:
        return 0
    iv = intervals[np.argsort(intervals[:, 0], kind="stable")]
    total, cur_s, cur_e = 0, int(iv[0, 0]), int(iv[0, 1])
    for s, e in iv[1:]:
        if s > cur_e:
            total += cur_e - cur_s
            cur_s, cur_e = int(s), int(e)
        elif e > cur_e:
            cur_e = int(e)
    return total + cur_e - cur_s


def gaps_ns(intervals: np.ndarray, lo: int, hi: int) -> List[Tuple[int, int]]:
    """The ``[start, end)`` stretches of ``[lo, hi)`` that no interval covers."""
    out, t = [], lo
    for s, e in intervals[np.argsort(intervals[:, 0], kind="stable")]:
        if s > t:
            out.append((t, min(int(s), hi)))
        t = max(t, int(e))
        if t >= hi:
            break
    if t < hi:
        out.append((t, hi))
    return [(s, e) for s, e in out if e > s]


@dataclasses.dataclass
class Op:
    name: str
    module: str
    start_ns: int
    dur_ns: int


@dataclasses.dataclass
class Summary:
    """The traced window, reduced."""

    window_s: float
    busy_s: float
    n_chips: int
    ops: List[Op]
    #: (label, start_ns, end_ns) of every idle gap of the first chip
    gaps: List[Tuple[str, int, int]]

    def kernel_seconds(self, names: Sequence[str]) -> Optional[float]:
        """Device seconds of the ops named ``<name>`` or ``<name>.<n>``
        for one of ``names`` (a Pallas kernel's op takes the name of the
        jitted function that calls it), averaged over the chips; None
        when there is none."""
        hit = [o.dur_ns for o in self.ops if o.name.split(".")[0] in names]
        return sum(hit) / 1e9 / self.n_chips if hit else None

    def program_seconds(self, word: str) -> Optional[float]:
        """Device seconds of the ops of programs whose name holds
        ``word``, averaged over the chips; None when there is none."""
        hit = [o.dur_ns for o in self.ops if word in o.module]
        return sum(hit) / 1e9 / self.n_chips if hit else None

    def op_totals(self) -> List[Tuple[str, float]]:
        tot: Dict[str, int] = collections.Counter()
        for o in self.ops:
            tot[f"{o.module}/{o.name}" if o.module else o.name] += o.dur_ns
        return [(k, v / 1e9 / self.n_chips) for k, v in tot.most_common()]

    def idle_by_label(self) -> List[Tuple[str, float]]:
        tot: Dict[str, int] = collections.Counter()
        for label, s, e in self.gaps:
            tot[label] += e - s
        return [(k, v / 1e9) for k, v in tot.most_common()]

    def breakdown(self) -> dict:
        longest = sorted(self.gaps, key=lambda g: g[2] - g[1], reverse=True)[:10]
        return {"device_ops": [[n, s] for n, s in self.op_totals()[:10]],
                "idle_gaps": [[label, (e - s) / 1e9] for label, s, e in longest]}

    def describe(self) -> dict:
        return {"window_s": self.window_s, "busy_s": self.busy_s, "ops": len(self.ops),
                "idle_s_by_host_span": self.idle_by_label()[:10],
                "device_ops_s": self.op_totals()[:15]}


def _events(line):
    for e in line.events:
        yield e.name, int(e.start_ns), int(e.duration_ns)


def op_name(hlo: str) -> str:
    """An ``XLA Ops`` event is named by its HLO text,
    ``%fused_list_topk.1 = (...) custom-call(...)``: keep ``fused_list_topk.1``."""
    return hlo.split(" = ", 1)[0].lstrip("%")


def program_name(module: str) -> str:
    """``jit_ivf_flat_fused_search(1205...)`` -> ``jit_ivf_flat_fused_search``."""
    return module.split("(", 1)[0]


def reduce(log_dir: str) -> Optional[Summary]:
    """Reduce the trace under ``log_dir``; None when it holds no device
    plane (a CPU run) or no window annotation."""
    import jax

    path = find_xplane(log_dir)
    if path is None:
        return None
    data = jax.profiler.ProfileData.from_file(path)
    window = None
    spans = []
    devices = []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            devices.append(plane)
            continue
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for name, s, d in _events(line):
                if name == WINDOW:
                    window = (s, s + d)
                elif name.startswith("bench."):
                    spans.append((name, s, s + d))
    if not devices or window is None:
        return None
    lo, hi = window
    ops: List[Op] = []
    busy, first_gaps = 0, []
    for i, plane in enumerate(devices):
        lines = {line.name: line for line in plane.lines}
        mods = sorted((s, s + d, program_name(name))
                      for name, s, d in _events(lines[MODULES_LINE])) \
            if MODULES_LINE in lines else []
        mod_starts = np.array([m[0] for m in mods], np.int64)
        iv = []
        for name, s, d in _events(lines.get(OPS_LINE, ())) if OPS_LINE in lines else ():
            s0, e0 = max(s, lo), min(s + d, hi)
            if e0 <= s0:
                continue
            iv.append((s0, e0))
            j = int(np.searchsorted(mod_starts, s, side="right")) - 1
            module = mods[j][2] if j >= 0 and mods[j][1] >= s else ""
            ops.append(Op(op_name(name), module, s0, e0 - s0))
        iv = np.array(iv, np.int64).reshape(-1, 2)
        busy += union_ns(iv)
        if i == 0:
            first_gaps = gaps_ns(iv, lo, hi)
    labelled = _label_gaps(first_gaps, spans)
    n = len(devices)
    return Summary(window_s=(hi - lo) / 1e9, busy_s=busy / 1e9 / n, n_chips=n, ops=ops,
                   gaps=labelled)


def _label_gaps(gaps, spans) -> List[Tuple[str, int, int]]:
    """Each gap with the host span that overlaps it the most, or
    ``idle``. The harness's spans run one after another on one thread,
    so sorted by start they are sorted by end too."""
    spans = sorted(spans, key=lambda sp: sp[1])
    starts = np.array([sp[1] for sp in spans], np.int64)
    ends = np.maximum.accumulate(np.array([sp[2] for sp in spans], np.int64)) \
        if spans else np.zeros(0, np.int64)
    out = []
    for s, e in gaps:
        best, best_ov = "idle", 0
        j = int(np.searchsorted(ends, s, side="right"))
        while j < len(spans) and starts[j] < e:
            name, a, b = spans[j]
            ov = min(e, b) - max(s, a)
            if ov > best_ov:
                best, best_ov = name, ov
            j += 1
        out.append((best, s, e))
    return out
