"""Timestamped sweep artifacts under ``artifacts/tpu/`` (a TRACKED
directory, unlike ``bench_artifacts/`` which bench.py overwrites).

Use :class:`Recorder` and call ``add(row)`` after EVERY measured config:
the JSON file is rewritten incrementally, so a sweep killed halfway
still leaves every completed row on disk."""
import json
import os
import time


class Recorder:
    def __init__(self, name: str, context=None, out_dir=None):
        if out_dir is None:
            root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
            out_dir = os.path.join(root, "artifacts", "tpu")
        os.makedirs(out_dir, exist_ok=True)
        stamp = time.strftime("%Y%m%dT%H%M%SZ", time.gmtime())
        self.path = os.path.join(out_dir, f"{name}_{stamp}.json")
        self.doc = {"name": name, "utc": stamp, "context": context or {}, "rows": []}
        self._flush()
        print(f"# artifact: {self.path}", flush=True)

    def add(self, row) -> None:
        self.doc["rows"].append(row)
        self._flush()

    def set_context(self, **kw) -> None:
        self.doc["context"].update(kw)
        self._flush()

    def _flush(self) -> None:
        tmp = self.path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(self.doc, f, indent=2)
        os.replace(tmp, self.path)


def record(name: str, rows, context=None) -> str:
    """One-shot write (kept for completed-sweep callers)."""
    r = Recorder(name, context)
    for row in rows:
        r.add(row)
    return r.path
