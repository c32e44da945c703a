"""raft_tpu.obs.timeseries + raft_tpu.obs.recorder — flight recorder
(ISSUE 18 acceptance, CPU).

Bounded ring-buffer time series with windowed queries, the
SeriesBank's prefix-allowlist auto-discovery and max_series backstop,
EWMA-baseline drift detection (warmup, baseline floor, and the
baseline-folds-forward property that stops sustained alarms), and the
FlightRecorder black box: the lock-free event ring, trigger semantics
(SLO fire dumps inline, error faults latch for the next tick, latency
faults never dump), the auto-dump debounce, the SLO chaos drill that
must yield exactly one CRC-valid bundle whose slowest exemplar trace
resolves its complete span chain, the ``recorder.dump`` torn-write
drill (no bundle or a CRC-valid one, never a torn file), and gates-off
parity (an installed recorder with ``RAFT_TPU_OBS`` off changes
nothing, bit for bit).
"""
import os

import numpy as np
import pytest

from raft_tpu import obs
from raft_tpu.neighbors import brute_force
from raft_tpu.obs import recorder, timeseries
from raft_tpu.robust import faults
from raft_tpu.serve import ServingEngine


@pytest.fixture(autouse=True)
def _pristine_gates():
    """Every test starts and ends with injection off, the fault registry
    empty, obs off, and no process-wide recorder installed."""
    faults.disable()
    faults.clear()
    obs.disable()
    obs.registry().reset()
    recorder.uninstall()
    yield
    faults.disable()
    faults.clear()
    obs.disable()
    obs.registry().reset()
    recorder.uninstall()


@pytest.fixture
def obs_on():
    reg = obs.registry()
    reg.reset()
    obs.enable()
    yield reg
    obs.disable()
    reg.reset()


class VClock:
    """Deterministic injectable clock."""

    def __init__(self, t=0.0):
        self.t = float(t)

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


def _data(rng, n, d, nc=16, scale=0.25):
    c = rng.standard_normal((nc, d)).astype(np.float32)
    return (c[rng.integers(0, nc, n)] + scale * rng.standard_normal((n, d))).astype(
        np.float32
    )


@pytest.fixture(scope="module")
def corpus():
    rng = np.random.default_rng(11)
    return _data(rng, 256, 16), _data(rng, 64, 16)


# -- TimeSeries --------------------------------------------------------------


class TestTimeSeries:
    def test_ring_evicts_oldest(self):
        s = timeseries.TimeSeries("g", capacity=4)
        for i in range(6):
            s.append(float(i), float(i * 10))
        assert len(s) == 4
        assert s.points()[0] == (2.0, 20.0)
        assert s.latest() == (5.0, 50.0)

    def test_windowed_delta_rate_mean(self):
        s = timeseries.TimeSeries("c", kind="counter")
        for t, v in [(0.0, 0.0), (1.0, 10.0), (2.0, 30.0), (3.0, 60.0)]:
            s.append(t, v)
        # full window: 60 - 0 over 3s
        assert s.delta(10.0, now=3.0) == 60.0
        assert s.rate(10.0, now=3.0) == pytest.approx(20.0)
        # window clipped to the last two samples: 60 - 30 over 1s
        assert s.delta(1.5, now=3.0) == 30.0
        assert s.rate(1.5, now=3.0) == pytest.approx(30.0)
        assert s.mean(1.5, now=3.0) == pytest.approx(45.0)

    def test_single_sample_windows_are_zero(self):
        s = timeseries.TimeSeries("g")
        s.append(1.0, 5.0)
        assert s.delta(10.0, now=1.0) == 0.0
        assert s.rate(10.0, now=1.0) == 0.0
        assert s.percentile(99.0, 10.0, now=1.0) == 5.0

    def test_percentile_interpolates(self):
        s = timeseries.TimeSeries("g")
        for i, v in enumerate([0.0, 10.0]):
            s.append(float(i), v)
        assert s.percentile(50.0, 10.0, now=1.0) == pytest.approx(5.0)
        assert s.percentile(0.0, 10.0, now=1.0) == 0.0
        assert s.percentile(100.0, 10.0, now=1.0) == 10.0

    def test_as_dict_round_trips_points(self):
        s = timeseries.TimeSeries("g", labels={"index_id": "a"})
        s.append(1.0, 2.0)
        d = s.as_dict()
        assert d["name"] == "g" and d["labels"] == {"index_id": "a"}
        assert d["points"] == [[1.0, 2.0]]


class TestHistogramSeries:
    def _series(self):
        h = timeseries.HistogramSeries("h", buckets=(1.0, 10.0, 100.0))
        # per-bucket counts include the +Inf slot (4 entries for 3
        # finite bounds)
        h.append(0.0, (0, 0, 0, 0), 0.0, 0)
        h.append(1.0, (2, 4, 2, 0), 100.0, 8)
        return h

    def test_windowed_stats_difference_snapshots(self):
        h = self._series()
        assert h.delta(10.0, now=1.0) == 8.0
        assert h.rate(10.0, now=1.0) == pytest.approx(8.0)
        assert h.mean(10.0, now=1.0) == pytest.approx(12.5)

    def test_needs_two_snapshots_inside_window(self):
        h = self._series()
        # window so small only the t=1.0 snapshot is inside
        assert h.delta(0.5, now=1.0) == 0.0
        assert h.percentile(99.0, 0.5, now=1.0) == 0.0

    def test_percentile_bucket_interpolation(self):
        h = self._series()
        # 2 in (0,1], 4 in (1,10], 2 in (10,100] -> the p50 target of 4
        # observations lands halfway through the second bucket
        p50 = h.percentile(50.0, 10.0, now=1.0)
        assert 1.0 < p50 <= 10.0
        assert p50 == pytest.approx(1.0 + (10.0 - 1.0) * (2.0 / 4.0))

    def test_inf_bucket_resolves_to_last_finite_bound(self):
        h = timeseries.HistogramSeries("h", buckets=(1.0, 10.0))
        h.append(0.0, (0, 0, 0), 0.0, 0)
        h.append(1.0, (0, 0, 5), 5000.0, 5)  # all in +Inf
        assert h.percentile(99.0, 10.0, now=1.0) == 10.0


# -- SeriesBank --------------------------------------------------------------


class TestSeriesBank:
    def test_auto_discovers_tracked_prefixes_only(self, obs_on):
        obs.inc("serve.requests", index_id="a")
        obs.set_gauge("serve.queue_depth", 3.0)
        obs.inc("brute_force.search.calls")  # not tracked
        bank = timeseries.SeriesBank(clock=VClock(1.0))
        bank.sample(obs_on)
        names = {s.name for s in bank.series()}
        assert "serve.requests" in names
        assert "serve.queue_depth" in names
        assert "brute_force.search.calls" not in names
        assert bank.stats()["samples"] == 1

    def test_histograms_become_histogram_series(self, obs_on):
        obs.observe("serve.time_in_queue_ms", 5.0)
        bank = timeseries.SeriesBank(clock=VClock(1.0))
        bank.sample(obs_on)
        (s,) = bank.find("serve.time_in_queue_ms")
        assert isinstance(s, timeseries.HistogramSeries)
        assert s.latest()[3] == 1  # cumulative count

    def test_max_series_overflow_is_counted_not_grown(self, obs_on):
        for i in range(4):
            obs.inc("serve.requests", index_id=f"idx{i}")
        bank = timeseries.SeriesBank(max_series=2, clock=VClock(1.0))
        bank.sample(obs_on)
        assert len(bank) == 2
        assert bank.stats()["dropped"] == 2

    def test_get_by_labels(self, obs_on):
        obs.inc("serve.requests", index_id="a")
        bank = timeseries.SeriesBank(clock=VClock(1.0))
        bank.sample(obs_on)
        assert bank.get("serve.requests", index_id="a") is not None
        assert bank.get("serve.requests", index_id="zzz") is None

    def test_disabled_sample_is_a_noop(self):
        bank = timeseries.SeriesBank(clock=VClock(1.0))
        bank.sample()
        assert len(bank) == 0
        assert bank.stats()["samples"] == 0


# -- EwmaDetector ------------------------------------------------------------


def _static_extract(pairs):
    """An extract() that replays a mutable list of (key, value) pairs."""

    def extract(bank, now, window_s):
        return list(pairs)

    return extract


class TestEwmaDetector:
    def test_unknown_mode_raises(self):
        with pytest.raises(ValueError):
            timeseries.EwmaDetector("x", _static_extract([]), mode="bogus")

    def test_warmup_then_spike_fires(self):
        pairs = [("a", 1.0)]
        det = timeseries.EwmaDetector(
            "latency_drift", _static_extract(pairs), mode="ratio_above",
            threshold=3.0, warmup=3,
        )
        bank = timeseries.SeriesBank()
        # seeding + warmup: steady values never alarm
        for t in range(4):
            assert det.check(bank, float(t)) == []
        pairs[0] = ("a", 10.0)  # 10x the ~1.0 baseline
        anomalies = det.check(bank, 5.0)
        assert len(anomalies) == 1
        a = anomalies[0]
        assert a.signal == "latency_drift" and a.index_id == "a"
        assert a.value == 10.0 and a.baseline < 3.0
        assert a.as_dict()["t"] == 5.0

    def test_baseline_folds_so_sustained_shift_stops_alarming(self):
        pairs = [("a", 1.0)]
        det = timeseries.EwmaDetector(
            "latency_drift", _static_extract(pairs), mode="ratio_above",
            threshold=2.0, alpha=0.5, warmup=2,
        )
        bank = timeseries.SeriesBank()
        for t in range(3):
            det.check(bank, float(t))
        pairs[0] = ("a", 10.0)  # sustained regime change
        fired = [bool(det.check(bank, 3.0 + t)) for t in range(8)]
        assert fired[0] is True          # the shift itself alarms
        assert fired[-1] is False        # the baseline caught up
        assert True not in fired[fired.index(False):]  # and stays quiet

    def test_ratio_below_needs_baseline_above_floor(self):
        pairs = [("a", 0.5)]
        det = timeseries.EwmaDetector(
            "qps_cliff", _static_extract(pairs), mode="ratio_below",
            threshold=0.3, warmup=2, min_baseline=1.0,
        )
        bank = timeseries.SeriesBank()
        for t in range(4):
            det.check(bank, float(t))
        pairs[0] = ("a", 0.0)  # a cliff from ~0.5 qps — under the floor
        assert det.check(bank, 5.0) == []

    def test_abs_above_ignores_baseline(self):
        pairs = [("a", 0.1)]
        det = timeseries.EwmaDetector(
            "burn_rate_slope", _static_extract(pairs), mode="abs_above",
            threshold=0.5, warmup=2,
        )
        bank = timeseries.SeriesBank()
        det.check(bank, 0.0)
        det.check(bank, 1.0)
        pairs[0] = ("a", 0.9)
        assert len(det.check(bank, 2.0)) == 1

    def test_first_observation_seeds_without_alarming(self):
        det = timeseries.EwmaDetector(
            "x", _static_extract([("a", 1e9)]), mode="abs_above",
            threshold=0.5, warmup=1,
        )
        assert det.check(timeseries.SeriesBank(), 0.0) == []


# -- FlightRecorder: events, triggers, dumping -------------------------------


class TestRecorderEvents:
    def test_event_ring_is_bounded(self, obs_on, tmp_path):
        r = recorder.FlightRecorder(str(tmp_path), max_events=8, clock=VClock())
        for i in range(20):
            r.note_fault("wal.append", "latency")
        assert len(r.events()) == 8

    def test_events_window_filters_by_age(self, tmp_path, obs_on):
        clk = VClock(0.0)
        r = recorder.FlightRecorder(str(tmp_path), clock=clk)
        r.note_breaker("replica0", "half_open")
        clk.advance(100.0)
        r.note_breaker("replica1", "half_open")
        assert len(r.events()) == 2
        assert [e["target"] for e in r.events(window_s=10.0)] == ["replica1"]

    def test_gated_off_notes_record_nothing(self, tmp_path):
        r = recorder.FlightRecorder(str(tmp_path))
        r.note_fault("wal.append", "error")
        r.note_breaker("replica0", "open")
        assert r.events() == []
        assert r._pending[0] is None
        assert r.dump() is None
        assert recorder.list_bundles(str(tmp_path)) == []

    def test_error_fault_latches_and_tick_drains(self, obs_on, tmp_path):
        clk = VClock(10.0)
        r = recorder.FlightRecorder(str(tmp_path), clock=clk)
        r.note_fault("wal.append", "error")
        assert r._pending[0] is not None  # latched, not dumped inline
        assert recorder.list_bundles(str(tmp_path)) == []
        clk.advance(1.0)
        r.tick(obs_on)
        (path,) = recorder.list_bundles(str(tmp_path))
        bundle = recorder.load_bundle(path)
        assert bundle["trigger"]["cause"] == "fault"
        assert bundle["trigger"]["ctx"]["point"] == "wal.append"
        assert bundle["trigger"]["ctx"]["latched_t"] == 10.0
        assert r._pending[0] is None

    def test_latency_faults_never_latch(self, obs_on, tmp_path):
        r = recorder.FlightRecorder(str(tmp_path), clock=VClock())
        r.note_fault("serve.dispatch", "latency")
        assert r._pending[0] is None
        assert [e["fault_kind"] for e in r.events()] == ["latency"]

    def test_breaker_open_dumps_inline(self, obs_on, tmp_path):
        r = recorder.FlightRecorder(str(tmp_path), clock=VClock(5.0))
        assert r.note_breaker("replica2", "half_open") is None
        path = r.note_breaker("replica2", "open")
        assert path is not None and os.path.exists(path)
        bundle = recorder.load_bundle(path)
        assert bundle["trigger"]["cause"] == "breaker"
        assert bundle["trigger"]["ctx"]["target"] == "replica2"

    def test_auto_dumps_debounce_manual_does_not(self, obs_on, tmp_path):
        clk = VClock(0.0)
        r = recorder.FlightRecorder(
            str(tmp_path), min_dump_interval_s=5.0, clock=clk
        )
        assert r.note_breaker("a", "open") is not None
        clk.advance(1.0)
        assert r.note_breaker("b", "open") is None   # debounced
        assert r.dump() is not None                   # manual rides through
        clk.advance(5.0)
        assert r.note_breaker("c", "open") is not None
        assert len(r.dumps()) == 3

    def test_untriggered_causes_do_not_dump(self, obs_on, tmp_path):
        r = recorder.FlightRecorder(
            str(tmp_path), triggers=("slo",), clock=VClock()
        )
        assert r.note_breaker("a", "open") is None
        assert recorder.list_bundles(str(tmp_path)) == []

    def test_bundle_body_shape(self, obs_on, tmp_path):
        obs.inc("serve.requests", index_id="a")
        obs.observe("serve.time_in_queue_ms", 4.0, trace_id="t-1")
        obs_on.record_span("serve.queue", 0.0, 4000.0, 1, 0, trace=("t-1",))
        clk = VClock(1.0)
        r = recorder.FlightRecorder(str(tmp_path), clock=clk)
        r.tick(obs_on)
        path = r.dump(ctx={"who": "test"})
        bundle = recorder.load_bundle(path)
        assert bundle["format"] == "raft_tpu.obs_bundle"
        assert bundle["trigger"] == {
            "cause": "manual", "ctx": {"who": "test"}, "t": 1.0,
        }
        names = {s["name"] for s in bundle["series"]["series"]}
        assert "serve.requests" in names
        traces = bundle["slow_traces"]
        assert traces and traces[0]["trace_id"] == "t-1"
        assert {s["name"] for s in traces[0]["spans"]} == {"serve.queue"}
        assert bundle["lockcheck"]["coverage"] is not None
        assert bundle["fingerprint"]["python"]
        assert r.dumps() == [path]

    def test_tick_sampling_rate_limited(self, obs_on, tmp_path):
        # the maintenance tick fires every ~10ms but the sampler must
        # not scan the registry (shared instrument lock!) faster than
        # sample_interval_s; the fault-latch drain still runs every tick
        obs.inc("serve.requests", index_id="a")
        clk = VClock(0.0)
        r = recorder.FlightRecorder(
            str(tmp_path), sample_interval_s=1.0, clock=clk
        )
        r.tick(obs_on)                       # first tick always samples
        n0 = r._bank.stats()["samples"]
        assert n0 > 0
        clk.advance(0.2)
        r.note_fault("wal.append", "error")  # latched mid-interval
        r.tick(obs_on)
        assert r._bank.stats()["samples"] > n0  # dump's at-trigger sample
        (path,) = recorder.list_bundles(str(tmp_path))
        assert recorder.load_bundle(path)["trigger"]["cause"] == "fault"
        clk.advance(0.2)
        n1 = r._bank.stats()["samples"]
        r.tick(obs_on)                       # still inside the interval
        assert r._bank.stats()["samples"] == n1
        clk.advance(1.0)
        r.tick(obs_on)                       # interval elapsed: samples
        assert r._bank.stats()["samples"] > n1

    def test_tick_retains_only_tracked_series(self, obs_on, tmp_path):
        obs.inc("serve.requests", index_id="a")
        obs.inc("brute_force.search.calls")
        r = recorder.FlightRecorder(str(tmp_path), clock=VClock(1.0))
        r.tick(obs_on)
        bundle = recorder.load_bundle(r.dump())
        names = {s["name"] for s in bundle["series"]["series"]}
        assert "serve.requests" in names
        assert "brute_force.search.calls" not in names


# -- the recorder.dump chaos seam (torn-write drill) -------------------------


class TestTornDump:
    def test_killed_dump_leaves_no_file_and_is_counted(self, obs_on, tmp_path):
        obs.inc("serve.requests", index_id="a")
        r = recorder.FlightRecorder(str(tmp_path), clock=VClock(1.0))
        with faults.injected("recorder.dump", error=RuntimeError("torn")):
            assert r.dump() is None
        # atomic_write discarded the temp file: the directory holds no
        # bundle and no debris
        assert recorder.list_bundles(str(tmp_path)) == []
        assert os.listdir(str(tmp_path)) == []
        assert obs_on.as_dict()["counters"][
            'recorder.dump_failures{kind="RuntimeError"}'
        ] == 1
        # the recorder's own seam never latches a fault-trigger dump
        assert r._pending[0] is None
        # and the recorder still works afterwards
        path = r.dump()
        assert path is not None
        assert recorder.load_bundle(path)["trigger"]["cause"] == "manual"


# -- the SLO chaos drill (the ISSUE 18 acceptance scenario) ------------------


class TestSloChaosDrill:
    def test_slo_alert_auto_dumps_one_complete_bundle(
        self, corpus, tmp_path
    ):
        X, Q = corpus
        obs.registry().reset()
        obs.enable()
        r = recorder.install(
            str(tmp_path),
            triggers=("slo",),
            min_dump_interval_s=300.0,  # the drill must yield exactly one
            slow_traces=3,
        )
        eng = ServingEngine(
            max_batch=8, max_wait_ms=0.0, maintenance_interval_ms=1.0
        )
        r.attach_engine(eng)
        eng.register("wiki", "brute_force", brute_force.build(X))
        with faults.injected("serve.dispatch", latency_s=0.02):
            # warm-up traffic: metrics, exemplars, and sampler ticks
            # accumulate before the SLO is armed, so the bundle's series
            # provably cover the run-up to the alert
            for i in range(3):
                eng.submit("wiki", Q[i : i + 1], k=5)
                eng.run_until_idle()
            # arm the SLO: every 20ms+ request breaches the 1ms target,
            # so burn = 1/(1-0.9) = 10x >> threshold in both windows
            eng.set_slo(
                "wiki", latency_ms=1.0, target=0.9, burn_threshold=2.0
            )
            for i in range(3):
                eng.submit("wiki", Q[i : i + 1], k=5)
                eng.run_until_idle()

        # exactly one bundle: the fire transition happens once (the
        # alert latches) and latency faults never latch a dump
        (path,) = recorder.list_bundles(str(tmp_path))
        bundle = recorder.load_bundle(path)  # CRC-verified load

        trig = bundle["trigger"]
        assert trig["cause"] == "slo"
        assert trig["ctx"]["index_id"] == "wiki"

        # the event stream saw the latency-fault firings AND the alert
        kinds = {e["kind"] for e in bundle["events"]}
        assert {"fault", "slo"} <= kinds
        slo_events = [e for e in bundle["events"] if e["kind"] == "slo"]
        assert slo_events[-1]["transition"] == "fire"
        assert slo_events[-1]["burn_fast"] >= 2.0

        # retained time series cover the window leading up to the alert
        series = {
            (s["name"], tuple(sorted((s["labels"] or {}).items()))): s
            for s in bundle["series"]["series"]
        }
        tiq = [s for (name, _), s in series.items()
               if name == "serve.time_in_queue_ms"]
        assert tiq and tiq[0]["points"]
        assert tiq[0]["points"][0][0] <= trig["t"]

        # the slowest exemplar trace resolves its complete span chain
        assert bundle["slow_traces"]
        slowest = bundle["slow_traces"][0]
        names = {s["name"] for s in slowest["spans"]}
        assert {"serve.queue", "serve.dispatch"} <= names
        by_ts = sorted(slowest["spans"], key=lambda s: s["ts_us"])
        assert by_ts[0]["name"] == "serve.queue"

        # health + plans rode along from the attached engine
        (h,) = bundle["health"]["engines"]
        assert h["indexes"]["wiki"]["slo"]["alerting"] is True
        assert h["indexes"]["wiki"]["slo"]["alerts_fired"] == 1
        assert "wiki" in bundle["plans"]

        # the dump itself was counted under its trigger cause
        assert obs.registry().as_dict()["counters"][
            'recorder.dumps{cause="slo"}'
        ] == 1
        obs.disable()
        obs.registry().reset()


# -- gates-off parity --------------------------------------------------------


class TestGatesOffParity:
    def test_installed_recorder_with_obs_off_changes_nothing(
        self, corpus, tmp_path
    ):
        X, Q = corpus
        idx = brute_force.build(X)

        def serve(install_recorder):
            if install_recorder:
                r = recorder.install(str(tmp_path))
            eng = ServingEngine(max_batch=8, max_wait_ms=0.0,
                                maintenance_interval_ms=0.0)
            eng.register("wiki", "brute_force", idx)
            futs = [eng.submit("wiki", Q[i : i + 8], k=10) for i in range(3)]
            eng.run_until_idle()
            out = [f.result() for f in futs]
            if install_recorder:
                return out, r
            return out, None

        base, _ = serve(install_recorder=False)
        res, r = serve(install_recorder=True)

        for a, b in zip(base, res):
            assert np.array_equal(a.indices, b.indices)
            assert np.array_equal(a.distances, b.distances)

        # the recorder did nothing: no events, no samples, no bundles
        assert r.events() == []
        assert r._bank.stats()["samples"] == 0
        assert r.dump() is None
        assert recorder.list_bundles(str(tmp_path)) == []

    def test_module_level_hooks_noop_without_active_recorder(self, obs_on):
        recorder.uninstall()
        recorder.note_fault("wal.append", "error")
        recorder.note_breaker("a", "open")
        recorder.tick()
        assert recorder.dump() is None
        assert recorder.installed() is None
