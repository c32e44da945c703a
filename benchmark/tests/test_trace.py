"""The trace reduction: interval arithmetic by hand, and a small trace
recorded on a v5e (``data/v5e_flat_4batches.xplane.pb``: four IVF-Flat batches
served through ``ServingEngine`` inside a ``bench.window`` annotation,
with the harness's host spans)."""
import os

import numpy as np
import pytest

from benchmark import trace

DATA = os.path.join(os.path.dirname(__file__), "data")


def test_union_and_gaps():
    iv = np.array([[10, 20], [15, 30], [40, 50], [45, 46]], np.int64)
    assert trace.union_ns(iv) == 20 + 10
    assert trace.gaps_ns(iv, 0, 60) == [(0, 10), (30, 40), (50, 60)]
    assert trace.gaps_ns(iv, 12, 45) == [(30, 40)]
    assert trace.union_ns(np.zeros((0, 2), np.int64)) == 0


def test_gap_labels_take_the_widest_overlap():
    spans = [("bench.submit", 0, 5), ("bench.step", 5, 30), ("bench.sleep", 30, 100)]
    got = trace._label_gaps([(2, 9), (25, 60), (200, 210)], spans)
    assert got == [("bench.step", 2, 9), ("bench.sleep", 25, 60), ("idle", 200, 210)]


def test_recorded_v5e_trace():
    """Four 1,024-query IVF-Flat batches on a v5e, then a 10 ms sleep."""
    s = trace.reduce(DATA)
    assert s is not None and s.n_chips == 1
    assert s.window_s == pytest.approx(0.036674797)
    # four runs of the jitted search, 3.63 ms each; its ops cover it
    assert s.program_seconds("jit_ivf_flat_fused_search") == pytest.approx(s.busy_s)
    assert s.busy_s == pytest.approx(0.014511638)
    # the Pallas kernel's op, once a batch
    kernel = [o for o in s.ops if o.name.split(".")[0] == "fused_list_topk"]
    assert len(kernel) == 4
    assert s.kernel_seconds(("fused_list_topk",)) == pytest.approx(0.012142468)
    assert s.kernel_seconds(("fused_pq_topk",)) is None
    assert s.program_seconds("refine") is None
    # the device idled mostly while the host stepped the engine or slept
    idle = dict(s.idle_by_label())
    assert idle["bench.sleep"] == pytest.approx(0.012938126)
    assert sum(idle.values()) == pytest.approx(s.window_s - s.busy_s)
    b = s.breakdown()
    assert b["device_ops"][0][0] == "jit_ivf_flat_fused_search/fused_list_topk.1"
    assert len(b["device_ops"]) == 10 and len(b["idle_gaps"]) == 10


def test_cpu_trace_has_no_device(tmp_path):
    import jax
    import jax.numpy as jnp

    trace.start(str(tmp_path))
    with jax.profiler.TraceAnnotation(trace.WINDOW):
        jnp.ones(8).block_until_ready()
    trace.stop()
    assert trace.reduce(str(tmp_path)) is None
