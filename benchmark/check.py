"""The comparison that decides ``correct``.

Every answer the window produced is compared with the plain reference
(``reference.py``). Four numbers, each with its limit from the
configuration's ``limits`` block:

- ``recall``: recall@k of all answers against the reference's top k of
  their query (at least the limit; the configuration states it);
- ``dist_err``: the widest gap between a distance the program returned
  and the exact distance of the id it returned with it, over the median
  magnitude of the reference's k-th distance (at most the limit). It
  catches an id or a distance altered, and a row handed to the wrong
  request;
- ``bad_answers``: answers with an id outside the corpus, an id twice,
  or distances out of order (exactly 0);
- ``failed``: requests that were rejected, expired, raised or never
  answered (exactly 0).
"""
from __future__ import annotations

import dataclasses
from typing import List

import numpy as np

from benchmark import reference


@dataclasses.dataclass
class Answers:
    """Every answered query row of a window, in completion order."""

    q_rows: np.ndarray  # [n] pool row of each answered query
    ids: np.ndarray  # [n, k]
    dists: np.ndarray  # [n, k]


@dataclasses.dataclass
class Number:
    name: str
    value: float
    limit: float
    at_least: bool = False  # True: the value must not fall under the limit

    @property
    def ok(self) -> bool:
        if not np.isfinite(self.value):
            return False
        return self.value >= self.limit if self.at_least else self.value <= self.limit


def bad_answer_rows(ids: np.ndarray, dists: np.ndarray, n_rows: int, nearest_first_min: bool):
    """Bool [n]: rows with an id out of range, a repeated id, or
    distances out of order."""
    out = np.any((ids < 0) | (ids >= n_rows), axis=1)
    s = np.sort(ids, axis=1)
    out |= np.any(s[:, 1:] == s[:, :-1], axis=1)
    step = np.diff(dists, axis=1)
    out |= np.any(step < 0 if nearest_first_min else step > 0, axis=1)
    out |= ~np.all(np.isfinite(dists), axis=1)
    return out


def recall(ids: np.ndarray, ref_ids: np.ndarray) -> float:
    hits = (ids[:, :, None] == ref_ids[:, None, :]).any(axis=2).sum()
    return float(hits) / ref_ids.size


def compare(ans: Answers, ref_ids: np.ndarray, ref_d: np.ndarray, data, queries,
            metric: str, limits: dict, failed: int) -> List[Number]:
    """The four numbers of a window, each beside its limit."""
    k = ref_ids.shape[1]
    n_rows = int(data.shape[0])
    smin = reference.select_min(metric)
    if ans.ids.shape[0] == 0:
        return [Number("answers", 0.0, 1.0, at_least=True), Number("failed", float(failed), 0.0)]
    bad = bad_answer_rows(ans.ids, ans.dists, n_rows, smin)
    rec = 0.0
    for s in range(0, ans.ids.shape[0], 65536):
        rec += recall(ans.ids[s:s + 65536], ref_ids[ans.q_rows[s:s + 65536]]) * ans.ids[s:s + 65536].size
    rec /= ans.ids.size
    # exact distance of each distinct (query, id) pair answered
    valid = (ans.ids >= 0) & (ans.ids < n_rows)
    qr = np.broadcast_to(ans.q_rows[:, None], ans.ids.shape)[valid].astype(np.int64)
    keys = qr * n_rows + ans.ids[valid].astype(np.int64)
    uniq, inv = np.unique(keys, return_inverse=True)
    exact = reference.pair_distances(
        data, queries, (uniq // n_rows).astype(np.int32), (uniq % n_rows).astype(np.int32), metric)
    scale = float(np.median(np.abs(ref_d[:, k - 1])))
    gap = np.abs(ans.dists[valid].astype(np.float64) - exact[inv.reshape(-1)]) / scale
    dist_err = float(gap.max()) if gap.size else 0.0
    if not np.all(np.isfinite(ans.dists[valid])):
        dist_err = float("inf")
    return [
        Number("recall", rec, float(limits["recall"]), at_least=True),
        Number("dist_err", dist_err, float(limits["dist_err"])),
        Number("bad_answers", float(bad.sum()), 0.0),
        Number("failed", float(failed), 0.0),
    ]
