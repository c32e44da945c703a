"""data_export CSV + recall/QPS plot — raft-ann-bench L8 parity
(``raft_ann_bench/data_export/__main__.py``, ``plot/__main__.py`` analogs).
"""
import csv
import json
import os

import pytest

from raft_tpu.bench.data_export import export_csv
from raft_tpu.bench.plot import _frontier, plot_report


def _report():
    return {
        "context": {"device": "cpu-test"},
        "benchmarks": [
            {
                "name": f"ivf_flat/npr={p}",
                "algo": "ivf_flat",
                "dataset": "unit",
                "k": 10,
                "n_queries": 64,
                "Recall": r,
                "items_per_second": q,
                "Latency": 0.001,
                "end_to_end": 0.01,
                "build_time": 1.0,
                "build_params": {"n_lists": 16},
                "search_params": {"n_probes": p},
            }
            for p, r, q in [(4, 0.8, 1000.0), (8, 0.9, 700.0), (16, 0.97, 400.0), (8, 0.85, 300.0)]
        ],
    }


def test_export_csv_round_trip(tmp_path):
    out = export_csv(_report(), str(tmp_path / "res.csv"))
    with open(out) as f:
        rows = list(csv.DictReader(f))
    assert len(rows) == 4
    assert rows[0]["algo"] == "ivf_flat"
    assert float(rows[2]["recall"]) == 0.97
    assert json.loads(rows[0]["search_params"]) == {"n_probes": 4}


def test_export_csv_from_json_file(tmp_path):
    p = tmp_path / "rep.json"
    p.write_text(json.dumps(_report()))
    out = export_csv(str(p), str(tmp_path / "res.csv"))
    assert os.path.exists(out)


def test_pareto_frontier_shape():
    pts = [(0.8, 1000.0), (0.9, 700.0), (0.97, 400.0), (0.85, 300.0)]
    fr = _frontier(pts)
    # (0.85, 300) is dominated by (0.9, 700); the rest survive
    assert fr == [(0.8, 1000.0), (0.9, 700.0), (0.97, 400.0)]


def test_plot_writes_png(tmp_path):
    out = plot_report(_report(), str(tmp_path / "plot.png"), title="unit")
    assert os.path.exists(out) and os.path.getsize(out) > 1000


def test_artifact_recorder_incremental(tmp_path):
    """tools/_artifact.Recorder: every add() leaves a complete, parseable
    JSON on disk (atomic replace), so a run killed between rows cannot
    corrupt or lose earlier measurements — the property the round-4
    verdict asked the TPU evidence chain to have."""
    import importlib.util

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "_artifact_under_test", os.path.join(root, "tools", "_artifact.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)

    rec = mod.Recorder("unit", {"device": "test"}, out_dir=str(tmp_path))
    assert os.path.exists(rec.path)
    for i in range(3):
        rec.add({"row": i})
        with open(rec.path) as f:
            doc = json.load(f)
        assert [r["row"] for r in doc["rows"]] == list(range(i + 1))
    rec.set_context(extra=1)
    with open(rec.path) as f:
        doc = json.load(f)
    assert doc["context"]["extra"] == 1 and doc["context"]["device"] == "test"
    assert not os.path.exists(rec.path + ".tmp")


def _load_bench():
    import importlib.util

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "_bench_under_test", os.path.join(root, "bench.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_compute_efficiency_fractions_bounded():
    """Device-resident delta-timed probes (``_hw_context``) mean a kernel
    can at best match the measured peak: for consistent inputs every
    efficiency fraction must land in (0, 1]."""
    bench = _load_bench()
    hw = {"bf16_matmul_tflops": 100.0, "hbm_copy_gbps": 800.0}
    ops = {
        "ivf_flat": {"stream_gbps_est": 640.0},
        "cagra_fused": {"stream_gbps_est": 200.0},
    }
    eff = bench.compute_efficiency(ops, hw, exact_tflops=42.0)
    assert eff["exact_achieved_tflops"] == 42.0
    for key in (
        "mfu_vs_measured_peak",
        "fused_frac_of_measured_copy_bw",
        "cagra_fused_frac_of_measured_copy_bw",
    ):
        assert eff[key] is not None
        assert 0.0 < eff[key] <= 1.0, f"{key}={eff[key]} — probe is lying"
    assert eff["fused_stream_gbps_est"] == 640.0
    assert eff["cagra_fused_stream_gbps_est"] == 200.0


def test_compute_efficiency_guards_zero_peak():
    bench = _load_bench()
    hw = {"bf16_matmul_tflops": 0.0, "hbm_copy_gbps": 0.0}
    ops = {"ivf_flat": {"stream_gbps_est": 640.0}}
    eff = bench.compute_efficiency(ops, hw, exact_tflops=42.0)
    assert eff["mfu_vs_measured_peak"] is None
    assert eff["fused_frac_of_measured_copy_bw"] is None


def test_compute_efficiency_absent_ops_keys():
    bench = _load_bench()
    hw = {"bf16_matmul_tflops": 100.0, "hbm_copy_gbps": 800.0}
    eff = bench.compute_efficiency({}, hw, exact_tflops=10.0)
    assert "fused_stream_gbps_est" not in eff
    assert "cagra_fused_frac_of_measured_copy_bw" not in eff
    assert eff["mfu_vs_measured_peak"] == 0.1


@pytest.mark.parametrize("failed", [{}, {"ivf_pq": "RuntimeError: boom"}])
def test_bench_runs_only_on_tpu_and_fails_loud(monkeypatch, failed):
    """No CPU fallback: off the TPU (and without an explicit smoke) the
    bench refuses before any phase runs; with the smoke, a failed phase
    exits 1 after the result line, a clean run exits normally."""
    bench = _load_bench()
    ran = []
    monkeypatch.setattr(bench, "_bench_main", lambda: ran.append(1) or failed)
    monkeypatch.delenv("RAFT_TPU_BENCH_SMOKE", raising=False)
    with pytest.raises(SystemExit) as e:
        bench.main()
    assert e.value.code == 2 and not ran
    monkeypatch.setenv("RAFT_TPU_BENCH_SMOKE", "1")
    if failed:
        with pytest.raises(SystemExit) as e:
            bench.main()
        assert e.value.code == 1
    else:
        bench.main()
    assert ran == [1]
