"""The control, the plain reference in the next precision down (int8 for
the bf16 IVF-Flat lists, bf16 for IVF-PQ's float32 refine), put in the
program's place at the rehearsal size, comes out not correct; the
reference itself comes out correct."""
import numpy as np
import pytest

from benchmark import check, control, corpus, harness, reference

SEEDS = (21, 4_000_000_021, 9_000_000_000)


def _cfg(name):
    cfg = harness.load_config(harness.load_benchmark(), name)
    return harness.overlay(cfg, cfg["rehearsal"])


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("name", ["sift1m-flat", "glove200-pq"])
def test_control_fails_the_check(name, seed):
    out = control.control_numbers(_cfg(name), seed)
    assert out["correct"] is False, out
    # it is the distance check that catches the narrower precision
    assert out["numbers"]["dist_err"]["value"] > out["numbers"]["dist_err"]["limit"]
    assert out["reference_vs_float64"] == 1.0


@pytest.mark.parametrize("name", ["sift1m-flat", "glove200-pq"])
def test_reference_passes_its_own_check(name):
    cfg = _cfg(name)
    data, queries = corpus.make(cfg, SEEDS[0])
    d, i = reference.topk(data, queries, cfg["k"], cfg["metric"])
    numbers = check.compare(check.Answers(np.arange(i.shape[0]), i, d), i, d, data, queries,
                            cfg["metric"], cfg["limits"], 0)
    assert all(n.ok for n in numbers), numbers
    assert numbers[0].value == 1.0
