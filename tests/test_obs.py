"""raft_tpu.obs — metrics registry, sync-aware spans, Chrome-trace
export, and the query-path instrumentation wired into ivf_pq / cagra /
brute_force / kmeans / comms (ISSUE 3 acceptance tests, CPU).
"""
import io
import json
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from raft_tpu import obs

pytestmark = []


@pytest.fixture
def obs_on():
    """Enabled obs with a clean default registry; restores disabled-off
    state afterwards so other tests see the zero-cost path."""
    reg = obs.registry()
    reg.reset()
    obs.enable()
    yield reg
    obs.disable()
    reg.reset()


# -- registry ---------------------------------------------------------------


def test_disabled_records_nothing():
    obs.disable()
    reg = obs.registry()
    reg.reset()
    obs.inc("x.calls", mode="a")
    obs.set_gauge("x.g", 3.0)
    obs.observe("x.h", 1.0)
    with obs.span("x.span", a=1) as sp:
        sp.set(b=2)
        assert sp.sync(42) == 42  # null span passes values through
    snap = reg.as_dict()
    assert snap["counters"] == {} and snap["gauges"] == {} and snap["histograms"] == {}
    assert snap["n_spans"] == 0
    # zero-allocation: no metric objects were even constructed
    assert reg._metrics == {}


def test_counter_gauge_histogram_with_labels(obs_on):
    obs.inc("q.calls", mode="fused")
    obs.inc("q.calls", mode="fused")
    obs.inc("q.calls", mode="scan")
    obs.set_gauge("q.width", 8.0)
    for v in (0.05, 0.3, 2.0, 9999.0):
        obs.observe("q.ms", v)
    snap = obs_on.as_dict()
    assert snap["counters"]['q.calls{mode="fused"}'] == 2.0
    assert snap["counters"]['q.calls{mode="scan"}'] == 1.0
    assert snap["gauges"]["q.width"] == 8.0
    h = snap["histograms"]["q.ms"]
    assert h["count"] == 4 and sum(h["counts"]) == 4
    assert h["sum"] == pytest.approx(0.05 + 0.3 + 2.0 + 9999.0)
    # last bucket (+Inf overflow) caught the 9999
    assert h["counts"][-1] == 1


def test_histogram_bucket_edges(obs_on):
    hist = obs_on.histogram("edge.ms", buckets=(1.0, 10.0))
    for v in (0.5, 1.0, 5.0, 10.0, 11.0):
        hist.observe(v)
    # upper bounds are inclusive (bisect_left): 1.0 -> first bucket
    assert hist.counts == [2, 2, 1]


def test_prometheus_text(obs_on):
    obs.inc("ivf_pq.search.calls", mode="scan")
    obs.observe("q.ms", 0.2)
    text = obs_on.prometheus_text()
    assert "# TYPE ivf_pq_search_calls counter" in text
    assert 'ivf_pq_search_calls{mode="scan"} 1' in text
    assert "# TYPE q_ms histogram" in text
    assert 'q_ms_bucket{le="+Inf"} 1' in text
    assert "q_ms_count 1" in text


def test_jsonl_dump_round_trip(obs_on):
    obs.inc("a.calls", mode="x")
    obs.set_gauge("a.g", 2.5)
    obs.observe("a.h", 1.0)
    with obs.span("a.span", tag="t"):
        pass
    buf = io.StringIO()
    obs_on.dump_jsonl(buf)
    recs = [json.loads(line) for line in buf.getvalue().splitlines()]
    kinds = {r["kind"] for r in recs}
    assert kinds == {"counter", "gauge", "histogram", "span"}
    span = next(r for r in recs if r["kind"] == "span")
    assert span["name"] == "a.span" and span["args"] == {"tag": "t"}
    assert span["dur_us"] >= 0


def test_registry_reset_and_span_cap(obs_on):
    reg = obs.Registry(max_spans=2)
    for i in range(4):
        reg.record_span("s", 0.0, 1.0, 0, 0)
    assert len(reg.spans()) == 2 and reg.spans_dropped == 2
    reg.reset()
    assert reg.spans() == [] and reg.spans_dropped == 0


# -- spans ------------------------------------------------------------------


def test_span_nesting_depth_and_sync(obs_on):
    x = jnp.arange(8.0)
    with obs.span("outer", k=10) as sp:
        sp.set(extra="v")
        with obs.span("inner"):
            y = sp.sync(x * 2)
    spans = {s["name"]: s for s in obs_on.spans()}
    assert spans["outer"]["depth"] == 0 and spans["inner"]["depth"] == 1
    assert spans["outer"]["args"] == {"k": 10, "extra": "v"}
    assert spans["outer"]["tid"] == threading.get_ident()
    # inner is wall-clock-contained in outer
    oi, ii = spans["outer"], spans["inner"]
    assert oi["ts_us"] <= ii["ts_us"]
    assert ii["ts_us"] + ii["dur_us"] <= oi["ts_us"] + oi["dur_us"] + 50.0
    np.testing.assert_allclose(np.asarray(y), np.arange(8.0) * 2)


def test_span_records_even_when_body_raises(obs_on):
    with pytest.raises(ValueError):
        with obs.span("boom"):
            raise ValueError("x")
    assert len(obs_on.spans("boom")) == 1


# -- chrome-trace export ----------------------------------------------------


def test_chrome_trace_round_trip(tmp_path, obs_on):
    with obs.span("phase.a", nq=4):
        with obs.span("phase.b"):
            pass
    obs.inc("c.calls", mode="m")
    path = obs.write_trace(str(tmp_path / "trace.json"))
    doc = obs.load_trace(path)  # load_trace re-validates
    xs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    cs = [e for e in doc["traceEvents"] if e["ph"] == "C"]
    assert {e["name"] for e in xs} == {"phase.a", "phase.b"}
    assert [e["name"] for e in cs] == ['c.calls{mode="m"}']
    a = next(e for e in xs if e["name"] == "phase.a")
    assert a["args"]["nq"] == 4 and a["args"]["depth"] == 0
    assert isinstance(a["pid"], int) and isinstance(a["tid"], int)
    assert doc["otherData"]["producer"] == "raft_tpu.obs"


def test_validate_trace_rejects_malformed():
    with pytest.raises(ValueError):
        obs.validate_trace([])  # not an object
    with pytest.raises(ValueError):
        obs.validate_trace({"no": "traceEvents"})
    with pytest.raises(ValueError):
        obs.validate_trace({"traceEvents": [{"ph": "X", "name": "s", "ts": 0}]})
    with pytest.raises(ValueError):
        obs.validate_trace(
            {"traceEvents": [{"ph": "X", "name": "s", "ts": 0, "dur": -1, "pid": 1, "tid": 1}]}
        )
    with pytest.raises(ValueError):
        obs.validate_trace({"traceEvents": [{"ph": "C", "name": "c"}]})  # no args
    # well-formed passes
    obs.validate_trace(
        {"traceEvents": [{"ph": "X", "name": "s", "ts": 0, "dur": 1, "pid": 1, "tid": 1}]}
    )


def test_write_metrics_jsonl(tmp_path, obs_on):
    obs.inc("m.calls")
    with obs.span("m.span"):
        pass
    path = obs.write_metrics_jsonl(str(tmp_path / "metrics.jsonl"))
    recs = [json.loads(line) for line in open(path)]
    assert {r["kind"] for r in recs} == {"counter", "span"}


# -- instrumented query paths (CPU) ----------------------------------------


@pytest.fixture(scope="module")
def small_data():
    rng = np.random.default_rng(7)
    X = rng.standard_normal((400, 32)).astype(np.float32)
    q = rng.standard_normal((9, 32)).astype(np.float32)
    return X, q


def test_ivf_pq_search_instrumented(small_data, obs_on):
    from raft_tpu.neighbors import ivf_pq

    X, q = small_data
    idx = ivf_pq.build(
        X, ivf_pq.IvfPqIndexParams(n_lists=8, pq_dim=8, kmeans_n_iters=2)
    )
    obs_on.reset()  # focus on the search path
    sp = ivf_pq.IvfPqSearchParams(n_probes=4, refine_ratio=2)
    v, i = ivf_pq.search(idx, q, 5, sp, mode="scan", dataset=X)
    snap = obs_on.as_dict()
    assert snap["counters"]['ivf_pq.search.calls{lut="default",mode="scan"}'] == 1.0
    assert snap["counters"]["ivf_pq.search.queries"] == 9.0
    assert snap["histograms"]["ivf_pq.search.n_probes"]["sum"] == 4.0
    assert snap["histograms"]["ivf_pq.search.refine_candidates_per_query"]["count"] == 1
    names = {s["name"] for s in obs_on.spans()}
    assert {
        "ivf_pq.search",
        "ivf_pq.search.coarse_probe",
        "ivf_pq.search.pq_scan",
        "ivf_pq.search.refine",
    } <= names
    # result parity with the disabled fast path
    obs.disable()
    v2, i2 = ivf_pq.search(idx, q, 5, sp, mode="scan", dataset=X)
    np.testing.assert_array_equal(np.asarray(i), np.asarray(i2))
    obs.enable()

    obs_on.reset()
    ivf_pq.search(idx, q, 5, ivf_pq.IvfPqSearchParams(n_probes=4, refine_ratio=1), mode="probe")
    names = {s["name"] for s in obs_on.spans()}
    assert "ivf_pq.search.probe_scan" in names


def test_cagra_search_instrumented(small_data, obs_on):
    from raft_tpu.neighbors import cagra

    X, q = small_data
    idx = cagra.build(
        X, cagra.CagraIndexParams(graph_degree=16, intermediate_graph_degree=24)
    )
    obs_on.reset()
    v, i = cagra.search(idx, q, 5)
    snap = obs_on.as_dict()
    assert snap["counters"]['cagra.search.calls{mode="xla"}'] == 1.0
    assert snap["counters"]["cagra.search.queries"] == 9.0
    assert snap["histograms"]["cagra.search.iterations"]["count"] == 1
    occ = snap["histograms"]['cagra.search.beam_occupancy{mode="xla"}']
    assert occ["count"] == 1 and 0.0 <= occ["sum"] <= 1.0
    assert snap["gauges"]["cagra.search.itopk"] > 0
    names = {s["name"] for s in obs_on.spans()}
    assert {"cagra.search", "cagra.search.xla_batch"} <= names
    obs.disable()
    v2, i2 = cagra.search(idx, q, 5)
    np.testing.assert_array_equal(np.asarray(i), np.asarray(i2))
    obs.enable()


def test_brute_force_search_instrumented(small_data, obs_on):
    from raft_tpu.neighbors import brute_force

    X, q = small_data
    idx = brute_force.build(X)
    v, i = brute_force.search(idx, q, 5)
    brute_force.search(idx, q, 5, mode="approx")
    snap = obs_on.as_dict()
    assert snap["counters"]['brute_force.search.calls{mode="exact"}'] == 1.0
    assert snap["counters"]['brute_force.search.calls{mode="approx"}'] == 1.0
    assert snap["counters"]["brute_force.search.queries"] == 18.0
    names = {s["name"] for s in obs_on.spans()}
    assert {
        "brute_force.search",
        "brute_force.search.exact_batch",
        "brute_force.search.approx",
    } <= names
    obs.disable()
    v2, i2 = brute_force.search(idx, q, 5)
    np.testing.assert_array_equal(np.asarray(i), np.asarray(i2))
    obs.enable()


def test_kmeans_fit_instrumented(small_data, obs_on):
    from raft_tpu.cluster import kmeans

    X, _ = small_data
    out = kmeans.fit(X, n_clusters=4, max_iter=5, n_init=2)
    assert out.centroids.shape == (4, 32)
    snap = obs_on.as_dict()
    assert snap["counters"]['kmeans.fit.calls{init="kmeans++"}'] == 1.0
    assert snap["counters"]["kmeans.fit.samples"] == 400.0
    assert snap["histograms"]["kmeans.fit.n_iter"]["count"] == 2  # one per trial
    names = [s["name"] for s in obs_on.spans()]
    assert names.count("kmeans.fit.init") == 2
    assert names.count("kmeans.fit.lloyd") == 2


def test_comms_verbs_instrumented(eight_devices, obs_on):
    from jax.experimental.shard_map import shard_map
    from jax.sharding import PartitionSpec as P

    from raft_tpu.parallel import comms

    mesh = comms.make_mesh(eight_devices)

    def body(x):
        y = comms.allreduce(x)
        comms.allgather(x)
        comms.barrier()
        return y

    f = shard_map(body, mesh=mesh, in_specs=P("data"), out_specs=P("data"))
    out = jax.jit(f)(jnp.arange(16, dtype=jnp.float32))
    jax.block_until_ready(out)
    snap = obs_on.as_dict()
    # 16 f32 over 8 shards -> payload p = 8 bytes per rank, counted once
    # at trace time (not per device) and scaled to bytes MOVED by the
    # verb's wire model: ring allreduce = 2p(n-1)/n = 14, allgather
    # receives the 7 other ranks' blocks = 7p = 56
    assert snap["counters"]['comms.allreduce.calls{axis="data"}'] == 1.0
    assert snap["counters"]['comms.allreduce.bytes{axis="data"}'] == 14.0
    assert snap["counters"]['comms.allgather.bytes{axis="data"}'] == 56.0
    assert snap["counters"]['comms.barrier.calls{axis="data"}'] == 1.0
    names = {s["name"] for s in obs_on.spans()}
    assert {"comms.allreduce", "comms.allgather", "comms.barrier"} <= names
    # spans are trace-time scopes and flagged as such
    assert all(
        s["args"].get("traced") is True
        for s in obs_on.spans()
        if s["name"].startswith("comms.")
    )
    # elementwise psum of per-rank pairs [2r, 2r+1] over r=0..7
    np.testing.assert_allclose(np.asarray(out), np.tile([56.0, 64.0], 8))


def test_payload_bytes_static_shapes():
    from raft_tpu.parallel.comms import _payload_bytes

    assert _payload_bytes(jnp.zeros((4, 3), jnp.float32)) == 48.0
    assert _payload_bytes({"a": jnp.zeros((2,), jnp.int8), "b": np.zeros(5)}) == 42.0


# -- obs_report CLI ---------------------------------------------------------


def _make_artifacts(tmp_path):
    with obs.span("root", k=1):
        with obs.span("leaf"):
            pass
    obs.inc("r.calls", mode="m")
    obs.observe("r.ms", 2.0)
    obs.set_gauge("r.g", 1.0)
    metrics = obs.write_metrics_jsonl(str(tmp_path / "metrics.jsonl"))
    trace = obs.write_trace(str(tmp_path / "trace.json"))
    return metrics, trace


def test_obs_report_renders_both_formats(tmp_path, obs_on):
    from tools import obs_report

    metrics, trace = _make_artifacts(tmp_path)
    for report in (
        obs_report.render_report(metrics),
        obs_report.render_report(trace),
        obs_report.render_report(metrics, trace),
    ):
        assert "root" in report and "leaf" in report
        assert 'r.calls{mode="m"}' in report
    # jsonl carries gauges/histograms too
    full = obs_report.render_report(metrics)
    assert "r.g" in full and "r.ms" in full


def test_obs_report_self_time(obs_on):
    from tools import obs_report

    spans = [
        {"name": "parent", "ts": 0.0, "dur": 100.0, "tid": 1},
        {"name": "child", "ts": 10.0, "dur": 40.0, "tid": 1},
        {"name": "other-thread", "ts": 0.0, "dur": 30.0, "tid": 2},
    ]
    rows = {r["name"]: r for r in obs_report.aggregate(obs_report.self_times(spans))}
    assert rows["parent"]["total_us"] == 100.0
    assert rows["parent"]["self_us"] == 60.0  # child's 40 subtracted
    assert rows["child"]["self_us"] == 40.0
    assert rows["other-thread"]["self_us"] == 30.0


def test_obs_report_cli(tmp_path, obs_on, capsys):
    from tools import obs_report

    metrics, trace = _make_artifacts(tmp_path)
    assert obs_report.main([metrics, trace, "--top", "5"]) == 0
    out = capsys.readouterr().out
    assert "# obs report" in out and "root" in out
    assert obs_report.main([str(tmp_path / "missing.jsonl")]) == 1


# -- request traces: IDs, exemplars, flow events (ISSUE 12) ------------------


def test_disabled_request_api_is_inert():
    obs.disable()
    reg = obs.registry()
    reg.reset()
    assert obs.new_trace_id() == ""
    assert obs.current_trace() == ()
    with obs.NULL_SCOPE:
        assert obs.current_trace() == ()
    # nothing was allocated on the disabled path
    assert reg._metrics == {} and reg.spans() == []


def test_trace_ids_and_scope_nesting(obs_on):
    t1, t2 = obs.new_trace_id(), obs.new_trace_id()
    assert t1.startswith("t") and t2.startswith("t") and t1 != t2
    assert obs.current_trace() == ()
    with obs.trace_scope((t1, t2)):
        assert obs.current_trace() == (t1, t2)
        with obs.trace_scope((t2,)):  # inner binding wins
            assert obs.current_trace() == (t2,)
        assert obs.current_trace() == (t1, t2)
    assert obs.current_trace() == ()
    # empty/falsy ids are filtered out (the disabled-request shape)
    with obs.trace_scope(("", t1)):
        assert obs.current_trace() == (t1,)


def test_spans_tagged_with_active_trace(obs_on):
    tid = obs.new_trace_id()
    with obs.span("untagged.phase"):
        pass
    with obs.trace_scope((tid,)):
        with obs.span("tagged.phase", nq=1):
            with obs.span("tagged.child"):
                pass
    spans = {s["name"]: s for s in obs_on.spans()}
    assert "trace" not in spans["untagged.phase"]
    assert spans["tagged.phase"]["trace"] == [tid]
    assert spans["tagged.child"]["trace"] == [tid]
    got = list(obs.iter_trace_spans(obs_on, tid))
    assert [s["name"] for s in got] == ["tagged.phase", "tagged.child"]


def test_histogram_exemplars_keep_worst_per_bucket(obs_on):
    hist = obs_on.histogram("ex.ms", buckets=(1.0, 10.0))
    hist.observe(0.5, trace_id="fast")
    hist.observe(0.7, trace_id="faster-but-worse")  # same bucket, larger value
    hist.observe(0.6, trace_id="not-retained")
    hist.observe(50.0, trace_id="tail")
    hist.observe(2.0)  # no trace: counted, no exemplar
    rows = hist.exemplar_rows()
    assert rows[0] == {"bucket": 2, "value": 50.0, "trace_id": "tail"}
    assert {"bucket": 0, "value": 0.7, "trace_id": "faster-but-worse"} in rows
    assert all(r["trace_id"] != "not-retained" for r in rows)
    # the facade threads trace_id through to the histogram
    obs.observe("ex2.ms", 3.0, trace_id="t1")
    snap = obs_on.as_dict()
    assert snap["histograms"]["ex2.ms"]["exemplars"] == [
        {"bucket": obs_on.histogram("ex2.ms").counts.index(1), "value": 3.0,
         "trace_id": "t1"}
    ]
    # histograms without exemplars do not grow the key
    obs.observe("ex3.ms", 1.0)
    assert "exemplars" not in snap["histograms"].get("ex3.ms", {})


def test_exemplars_jsonl_round_trip(obs_on):
    obs.observe("rt.ms", 42.0, trace_id="tX")
    buf = io.StringIO()
    obs_on.dump_jsonl(buf)
    recs = [json.loads(line) for line in buf.getvalue().splitlines()]
    h = next(r for r in recs if r["kind"] == "histogram")
    assert h["exemplars"][0]["trace_id"] == "tX"
    assert h["exemplars"][0]["value"] == 42.0


def test_flow_events_round_trip(tmp_path, obs_on):
    tid = obs.new_trace_id()
    lone = obs.new_trace_id()
    with obs.trace_scope((tid,)):
        with obs.span("flow.a"):
            with obs.span("flow.b"):
                pass
    with obs.trace_scope((lone,)):
        with obs.span("flow.single"):  # 1 span: no flow chain emitted
            pass
    path = obs.write_trace(str(tmp_path / "trace.json"))
    doc = obs.load_trace(path)  # validate_trace accepts s/t/f events
    flows = [e for e in doc["traceEvents"] if e["ph"] in ("s", "t", "f")]
    assert {e["ph"] for e in flows} == {"s", "f"}
    assert len({e["id"] for e in flows}) == 1  # one chain, stable id
    fin = next(e for e in flows if e["ph"] == "f")
    assert fin["bp"] == "e"
    xs = {e["name"]: e for e in doc["traceEvents"] if e["ph"] == "X"}
    assert xs["flow.a"]["args"]["trace"] == [tid]
    assert xs["flow.single"]["args"]["trace"] == [lone]


def test_validate_trace_rejects_malformed_flow_events():
    base = {"name": "request", "pid": 1, "tid": 1, "ts": 0.0}
    obs.validate_trace({"traceEvents": [{"ph": "s", "id": 7, **base}]})
    with pytest.raises(ValueError):
        obs.validate_trace({"traceEvents": [{"ph": "s", **base}]})  # no id
    with pytest.raises(ValueError):
        obs.validate_trace(
            {"traceEvents": [{"ph": "t", "id": True, **base}]}  # bool id
        )


def test_span_ring_overflow_counts_dropped_metric(obs_on):
    reg = obs.Registry(max_spans=2)
    for _ in range(5):
        reg.record_span("s", 0.0, 1.0, 0, 0)
    assert reg.spans_dropped == 3
    assert reg.as_dict()["counters"]["obs.spans_dropped"] == 3.0


def test_obs_report_notes_dropped_spans(tmp_path, obs_on):
    from tools import obs_report

    reg = obs.Registry(max_spans=1)
    for _ in range(3):
        reg.record_span("tiny", 0.0, 1.0, 0, 0)
    path = tmp_path / "metrics.jsonl"
    with open(path, "w") as f:
        reg.dump_jsonl(f)
    report = obs_report.render_report(str(path))
    assert "2 span(s) dropped" in report
    assert "undercount" in report


def test_obs_report_tail_attribution(tmp_path, obs_on):
    from tools import obs_report

    slow, fast = obs.new_trace_id(), obs.new_trace_id()
    for tid, fetch_s in ((slow, 0.02), (fast, 0.001)):
        with obs.trace_scope((tid,)):
            with obs.span("req.root"):
                with obs.span("req.fetch"):
                    import time as _t
                    _t.sleep(fetch_s)
        obs.observe("req.latency_ms", 30.0 if tid == slow else 2.0,
                    trace_id=tid)
    path = obs.write_metrics_jsonl(str(tmp_path / "metrics.jsonl"))
    report = obs_report.render_report(path)
    assert "tail attribution" in report
    assert slow in report
    # the injected-latency phase dominates the slow trace's self-time
    row_line = next(ln for ln in report.splitlines() if slow in ln)
    assert "req.fetch" in row_line
