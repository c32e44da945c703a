"""obs spans under a jax.profiler session: annotations on the host plane
of the written ``.xplane.pb`` (the device trace's clock), no device sync
unless recording is on, the shared null scope when neither is on, and
``py.gc`` marks for garbage collections."""
import gc
import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from raft_tpu import obs
from raft_tpu.obs import spans


def _profile(log_dir, fn):
    """Run ``fn`` under a profiler session with the Python tracer off (as
    the benchmark traces) and return the host events of the written
    trace: ``{name: [(start_ns, end_ns, line, args)]}``, the Python
    tracer's ``$``-named events left out."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(log_dir), profiler_options=opts)
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(str(log_dir), "**", "*.xplane.pb"), recursive=True)
    events = {}
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("$"):
                    continue
                s = int(e.start_ns)
                events.setdefault(e.name, []).append(
                    (s, s + int(e.duration_ns), line.name, dict(e.stats)))
    return events


def _within(inner, outer):
    (s_i, e_i, line_i, _), (s_o, e_o, line_o, _) = inner, outer
    return line_i == line_o and s_o <= s_i and e_i <= e_o


@pytest.fixture
def obs_off():
    was = obs.is_enabled()
    obs.disable()
    obs.registry().reset()
    yield obs.registry()
    obs.enable(was)
    obs.registry().reset()


@pytest.fixture
def block_calls(monkeypatch):
    """Every ``jax.block_until_ready`` call, by argument."""
    calls = []
    real = jax.block_until_ready

    def counting(x):
        calls.append(x)
        return real(x)

    monkeypatch.setattr(jax, "block_until_ready", counting)
    return calls


def test_spans_land_on_the_host_plane_nested(tmp_path, obs_off):
    def body():
        assert obs.active()
        with obs.span("serve.step"):
            with obs.span("serve.dispatch", rows=3, algo="ivf_flat"):
                jnp.ones(8).block_until_ready()

    ev = _profile(tmp_path, body)
    (step,), (disp,) = ev["serve.step"], ev["serve.dispatch"]
    assert _within(disp, step)
    assert disp[3] == {"rows": 3, "algo": "ivf_flat"}
    # recording was off: the registry saw nothing
    assert obs_off.spans() == [] and obs_off._metrics == {}


def test_obs_off_spans_never_block(tmp_path, obs_off, block_calls):
    def synced_span():
        with obs.span("serve.wait") as sp:
            out = sp.sync(jnp.arange(4.0) * 2)
        return out

    np.testing.assert_allclose(np.asarray(synced_span()), [0.0, 2.0, 4.0, 6.0])
    ev = _profile(tmp_path, synced_span)
    assert "serve.wait" in ev
    assert block_calls == []


def test_obs_on_records_and_syncs_as_before(tmp_path, block_calls):
    reg = obs.registry()
    reg.reset()
    obs.enable()
    try:
        x = jnp.arange(3.0)
        with obs.span("serve.launch", k=10) as sp:
            sp.set(extra="v")
            sp.sync(x)
        assert len(block_calls) == 1
        (rec,) = reg.spans("serve.launch")
        assert rec["args"] == {"k": 10, "extra": "v"} and rec["depth"] == 0

        def under_profiler():
            with obs.span("serve.fetch") as sp2:
                sp2.sync(x + 1)

        ev = _profile(tmp_path, under_profiler)
    finally:
        obs.disable()
    # both outputs: the profiler annotation and the registry record
    assert "serve.fetch" in ev and len(reg.spans("serve.fetch")) == 1
    assert len(block_calls) == 2
    reg.reset()


def test_null_span_is_shared(obs_off):
    assert not obs.active()
    a, b = obs.span("serve.step"), obs.span("serve.fetch", rows=2)
    # no per-call scope object: every span is the one null handle
    assert a is b is spans._NULL
    with a as sp:
        sp.set(x=1)
        assert sp.sync(42) == 42 and sp is spans._NULL
    assert obs_off.spans() == [] and obs_off._metrics == {}


def test_one_span_api():
    assert not hasattr(obs, "traced")
    with pytest.raises(ImportError):
        from raft_tpu.core import tracing  # noqa: F401


def test_engine_step_phases_nest(tmp_path, obs_off):
    from raft_tpu.neighbors import ivf_flat
    from raft_tpu.serve import ServingEngine

    rng = np.random.default_rng(3)
    X = rng.standard_normal((512, 16)).astype(np.float32)
    Q = rng.standard_normal((8, 16)).astype(np.float32)
    idx = ivf_flat.build(X, ivf_flat.IvfFlatIndexParams(n_lists=16, seed=3))
    eng = ServingEngine(max_batch=8, max_wait_ms=0.0)
    eng.register("f", "ivf_flat", idx, params=ivf_flat.IvfFlatSearchParams(n_probes=8),
                 mode="probe")
    eng.warmup("f", k=5)
    futs = []

    def one_step():
        futs.append(eng.submit("f", Q, k=5))
        assert eng.step(force=True) == 1

    ev = _profile(tmp_path, one_step)
    assert futs[0].result().indices.shape == (8, 5)
    (step,), (disp,) = ev["serve.step"], ev["serve.dispatch"]
    (launch,), (wait,), (fetch,) = ev["serve.launch"], ev["serve.wait"], ev["serve.fetch"]
    assert _within(disp, step)
    for phase in (launch, wait, fetch):
        assert _within(phase, disp)
    assert launch[1] <= wait[0] and wait[1] <= fetch[0]
    (search,) = ev["ivf_flat.search"]
    assert _within(search, launch)
    (put,) = ev["ivf_flat.search.put"]
    assert _within(put, search)
    for name in ("serve.maintenance", "serve.assemble", "serve.complete"):
        assert all(_within(e, step) for e in ev[name]), name


def test_gc_under_profiler_is_a_span(tmp_path, obs_off):
    def collect():
        with obs.span("serve.step"):
            pass  # the first span under a session installs the hook
        gc.collect()

    ev = _profile(tmp_path, collect)
    assert {"generation": 2} in [e[3] for e in ev["py.gc"]]
    assert spans._on_gc in gc.callbacks
    # no session: a collection adds no annotation and leaves none open
    gc.collect()
    assert spans._gc_open == []


def test_ivf_pq_search_under_profiler(tmp_path, obs_off):
    from raft_tpu.neighbors import ivf_pq

    rng = np.random.default_rng(5)
    X = rng.standard_normal((400, 32)).astype(np.float32)
    Q = rng.standard_normal((9, 32)).astype(np.float32)
    idx = ivf_pq.build(X, ivf_pq.IvfPqIndexParams(n_lists=8, pq_dim=8, kmeans_n_iters=2))
    sp = ivf_pq.IvfPqSearchParams(n_probes=4, refine_ratio=2)
    want = ivf_pq.search(idx, Q, 5, sp, mode="scan", dataset=X)
    got = []
    ev = _profile(tmp_path, lambda: got.append(
        ivf_pq.search(idx, Q, 5, sp, mode="scan", dataset=X)))
    np.testing.assert_array_equal(np.asarray(got[0][1]), np.asarray(want[1]))
    outer = max(ev["ivf_pq.search"], key=lambda e: e[1] - e[0])
    (refine,) = ev["ivf_pq.search.refine"]
    assert _within(refine, outer)
    assert all(_within(e, outer) for e in ev["ivf_pq.search.put"])
    assert _within(ev["refine.refine"][0], refine)
