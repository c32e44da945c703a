"""Idle time laid to the program's own spans: interval arithmetic on
hand-made spans, the readers of ``serve_idle_ms.batch`` and
``search_idle_ms.batch``, the old recorded trace (no program span) and a
v5e trace recorded with them
(``spans_data/v5e_flat_4batches_spans.xplane.pb``: four 1,024-query
IVF-Flat batches through ``ServingEngine`` inside a ``bench.window``
annotation, the Python tracer off, as ``trace.start`` sets it)."""
import os
import types

import pytest

from benchmark import harness, spans, trace

HERE = os.path.dirname(__file__)
OLD = os.path.join(HERE, "data")
RECORDED = os.path.join(HERE, "spans_data")


def _ctx(summary, batches):
    return types.SimpleNamespace(trace=summary, window=types.SimpleNamespace(batches=[()] * batches))


def _readers():
    return (harness.load_reader("serve_idle_ms.batch"),
            harness.load_reader("search_idle_ms.batch"))


def _totals(stretches):
    tot = {}
    for label, s, e in stretches:
        tot[label] = tot.get(label, 0) + e - s
    return tot


# one serving step, as the engine nests its spans, and a collection
# that no program span encloses
STEP = [
    ("serve.step", 0, 100), ("serve.dispatch", 10, 90), ("serve.launch", 10, 30),
    ("ivf_flat.search", 12, 28), ("serve.wait", 30, 70), ("py.gc", 40, 50),
    ("serve.fetch", 70, 90), ("py.gc", 150, 160),
]


def test_program_span_names():
    for name in ("serve.step", "plan.replan", "ivf_pq.search.put", "refine.refine", "py.gc"):
        assert spans.is_program_span(name)
    for name in ("bench.step", "$ivf_flat.py:615 search", "ivf_flat", "serve.step#rows=3#",
                 "jit_ivf_flat_fused_search", "cagra.search"):
        assert not spans.is_program_span(name)


def test_innermost_span_takes_each_stretch():
    got = spans.innermost(STEP)
    # stretches are ordered, disjoint and cover the step
    assert [g[1] for g in got][1:] == [g[2] for g in got][:-1]
    assert _totals(got) == {"serve.step": 20, "serve.launch": 4, "ivf_flat.search": 16,
                            "serve.wait": 40, "serve.fetch": 20}


def test_gaps_split_by_innermost_span_and_gc_folds():
    gaps = [("bench.step", 5, 15), ("bench.step", 25, 45), ("bench.step", 85, 110),
            ("bench.sleep", 140, 170)]
    got = spans.attribute(gaps, STEP)
    assert [(lab, s, e) for lab, s, e in got if s < 15] == [
        ("serve.step", 5, 10), ("serve.launch", 10, 12), ("ivf_flat.search", 12, 15)]
    # a collection inside serve.wait counts to serve.wait; one outside
    # every program span stays the harness's, as does the gap's tail
    # past the step
    assert _totals(got) == {"serve.step": 5 + 10, "serve.launch": 2 + 2,
                            "ivf_flat.search": 3 + 3, "serve.wait": 15, "serve.fetch": 5,
                            "bench.step": 10, "bench.sleep": 30}
    assert sum(_totals(got).values()) == sum(e - s for _, s, e in gaps)


def test_readers_need_program_spans():
    summary = trace.Summary(window_s=2e-7, busy_s=0.0, n_chips=1, ops=[],
                            gaps=[("bench.step", 25, 45), ("bench.step", 85, 110)])
    serve, search = _readers()
    assert serve(_ctx(summary, 2)) is None and search(_ctx(summary, 2)) is None
    summary.program_spans = STEP
    # ns over batches, in ms: serve.* 2 + 15 + 5 + 10 ns, ivf_flat.* 3 ns
    assert serve(_ctx(summary, 2)) == pytest.approx(32 / 2 / 1e6)
    assert search(_ctx(summary, 2)) == pytest.approx(3 / 2 / 1e6)
    assert serve(_ctx(summary, 0)) is None and serve(_ctx(None, 2)) is None


def test_old_recorded_trace_has_no_program_span():
    """The older trace was taken before the program annotated its spans:
    both readers read nothing there."""
    s = trace.reduce(OLD)
    s.program_spans = spans.program_spans(trace.find_xplane(OLD))
    assert s.program_spans == []
    serve, search = _readers()
    assert serve(_ctx(s, 4)) is None and search(_ctx(s, 4)) is None


def test_cpu_trace_keeps_the_window_thread(tmp_path):
    import jax
    import jax.numpy as jnp

    from raft_tpu import obs

    trace.start(str(tmp_path))
    with jax.profiler.TraceAnnotation(trace.WINDOW):
        with obs.span("serve.step"):
            with obs.span("serve.wait"):
                jnp.ones(8).block_until_ready()
        with jax.profiler.TraceAnnotation("bench.results"):
            pass
    trace.stop()
    got = [sp for sp in spans.program_spans(trace.find_xplane(str(tmp_path)))
           if sp[0] != spans.GC]  # the collector may run in the window too
    assert [name for name, _, _ in got] == ["serve.step", "serve.wait"]
    (_, s0, e0), (_, s1, e1) = got
    assert s0 <= s1 <= e1 <= e0


def test_recorded_v5e_span_trace():
    """Four 1,024-query IVF-Flat batches on a v5e, one request at a time;
    the three parts of the idle time add up to it."""
    s = trace.reduce(RECORDED)
    assert s is not None and s.n_chips == 1
    s.program_spans = spans.program_spans(trace.find_xplane(RECORDED))
    names = [name for name, _, _ in s.program_spans]
    for phase in ("serve.step", "serve.assemble", "serve.dispatch", "serve.launch",
                  "ivf_flat.search", "ivf_flat.search.put", "serve.wait", "serve.fetch",
                  "serve.complete"):
        assert names.count(phase) == 4, phase
    # the kernel keeps the op name the roofline reader matches
    assert s.kernel_seconds(("fused_list_topk",)) == pytest.approx(0.012141903)
    serve, search = _readers()
    ctx = _ctx(s, 4)
    serve_ms, search_ms = serve(ctx), search(ctx)
    harness_ms = sum(e - st for label, st, e in spans.attribute(s.gaps, s.program_spans)
                     if not label.startswith(spans.PREFIXES)) / 1e6 / 4
    idle_ms = (s.window_s - s.busy_s) * 1e3 / 4
    assert serve_ms + search_ms + harness_ms == pytest.approx(idle_ms, rel=1e-9)
    assert serve_ms == pytest.approx(2.93042275)
    assert search_ms == pytest.approx(0.01746525)
    # the host waiting on the chip and copying the answers back hold
    # most of it
    top = [label for label, _ in spans.idle_by_program_span(s)[:2]]
    assert top == ["serve.wait", "serve.fetch"]
