"""Each fault a cell can have, planted under the timed path, makes
``correct`` come out false.

The run is a whole run of the harness at the rehearsal size on the CPU
(the look for a chip is the one step skipped), with every program the
engine dispatches wrapped so that it:

- ``stale``: returns the answers of its previous call, as a step that
  leaves its state unchanged would;
- ``half``: searches only the first half of the batch and hands the
  second half the first half's answers;
- ``altered``: changes one id of one answer in each batch, and keeps
  the distance it came with.

One chip: no exchange between chips to leave out.
"""
import numpy as np
import pytest

from benchmark import harness

CELLS = ("sift1m-flat.batch", "glove200-pq.batch")


def _broken(prog, fault, state):
    def run(q, *args):
        out = prog(q, *args)
        d = np.array(out[0])
        i = np.array(out[1])
        if fault == "stale":
            prev = state.get(d.shape)
            state[d.shape] = (d, i)
            return prev if prev is not None else (d, i)
        if fault == "half":
            h = -(-d.shape[0] // 2)
            d[h:] = d[:d.shape[0] - h]
            i[h:] = i[:i.shape[0] - h]
            return d, i
        i[0, 0] = (i[0, -1] + state["n_rows"] // 2) % state["n_rows"]
        return d, i

    return run


@pytest.mark.parametrize("fault", ["stale", "half", "altered"])
@pytest.mark.parametrize("cell", CELLS)
def test_fault_fails_the_check(cell, fault, monkeypatch):
    from raft_tpu.serve.engine import ServingEngine

    orig = ServingEngine._build_program
    state = {}

    def patched(self, reg, bucket, k, plan=None):
        state["n_rows"] = int(reg.index.size)
        return _broken(orig(self, reg, bucket, k, plan=plan), fault, state)

    monkeypatch.setattr(ServingEngine, "_build_program", patched)
    out = harness.run_cell(cell, seed=5_000_000_000 + len(fault), seconds=2.0, trace=False,
                           rehearsal=True)
    assert out["correct"] is False, out["checks"]


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(cell):
    out = harness.run_cell(cell, seed=6_000_000_001, seconds=2.0, trace=False, rehearsal=True)
    assert out["correct"] is True, out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert all(name.startswith("cpu_rehearsal.") for name in out["metrics"])
