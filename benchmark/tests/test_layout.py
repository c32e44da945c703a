"""A later change adds a configuration, traffic mixes (closed and open
loop), an index family and a per-layer metric as new files plus
``BENCHMARK.json`` entries, and edits no file that is already there.

The test writes such a set into a temporary tree (the benchmark's own
files stay as they are), runs the harness's lookup and a whole run of
the new cell on the CPU at a tiny size, and finds the new metric in the
result.
"""
import json
import os

import pytest

from benchmark import harness

FAMILY = '''
KEEPS_CORPUS = True
SCAN_KERNELS = ()


def build(data, cfg):
    from raft_tpu.neighbors import brute_force
    from raft_tpu.ops.distance import DistanceType

    return brute_force.build(data, metric=DistanceType.L2Expanded)


def register(engine, index_id, index, data, cfg):
    engine.register(index_id, "brute_force", index)


def facts(index, cfg):
    return {}
'''

METRIC = '''
def read(ctx):
    return float(len(ctx.window.batches))
'''

CONFIG = {
    "name": "tiny-exact", "family": "exact_demo", "rows": 3000, "dim": 16,
    "metric": "sqeuclidean", "n_queries": 256, "k": 10,
    "data": {"kind": "blobs", "n_centers": 10, "normalize": False},
    "control": "bfloat16", "limits": {"recall": 0.99, "dist_err": 1e-4},
}
TRAFFIC = {
    "kind": "closed", "clients": 1, "request_rows": 64,
    "engine": {"max_batch": 64, "max_wait_ms": 0.0, "queue_capacity": 64},
    "warm_buckets": [64], "settle_seconds": 0.2,
}
OPEN_TRAFFIC = {
    "kind": "open", "request_rows": 1, "rate_qps": 200.0,
    "engine": {"max_batch": 16, "max_wait_ms": 2.0, "queue_capacity": 1024},
    "warm_buckets": [1, 2, 4, 8, 16], "settle_seconds": 0.2,
}


@pytest.fixture
def tree(tmp_path):
    root = tmp_path
    base = root / "benchmark"
    for sub in ("configs", "traffic", "families", "metrics"):
        (base / sub).mkdir(parents=True)
    bench = harness.load_benchmark()
    bench["configs"].append({"name": "tiny-exact", "source": "https://example.org/tiny",
                             "file": "benchmark/configs/tiny-exact.json", "reduced": [],
                             "why": "a configuration a later change adds"})
    bench["workloads"].append({"name": "tiny-exact.demo", "config": "tiny-exact",
                               "traffic": "demo_closed", "chips": 1, "why": "added later"})
    bench["workloads"].append({"name": "tiny-exact.open", "config": "tiny-exact",
                               "traffic": "demo_open", "chips": 1, "why": "added later"})
    bench["end_to_end"].append({"name": "p99_ms", "unit": "ms", "better": "lower", "bound": 0.25,
                                "source": "host_clock", "workloads": ["tiny-exact.open"]})
    bench["per_layer"].append({"name": "demo_batches", "unit": "batches", "better": "higher",
                               "source": "program_counter", "layer": "serving: demo",
                               "moves": "qps", "workloads": ["tiny-exact.demo"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    (base / "configs" / "tiny-exact.json").write_text(json.dumps(CONFIG))
    (base / "traffic" / "demo_closed.json").write_text(json.dumps(TRAFFIC))
    (base / "traffic" / "demo_open.json").write_text(json.dumps(OPEN_TRAFFIC))
    (base / "families" / "exact_demo.py").write_text(FAMILY)
    (base / "metrics" / "demo_batches.py").write_text(METRIC)
    return str(root), str(base)


def test_lookup_finds_new_files_by_name(tree):
    root, base = tree
    bench = harness.load_benchmark(root)
    cell = harness.entry(bench["workloads"], "tiny-exact.demo", "workload")
    assert harness.load_config(bench, cell["config"], root)["family"] == "exact_demo"
    assert harness.load_traffic(cell["traffic"], base)["clients"] == 1
    assert harness.load_family("exact_demo", base).KEEPS_CORPUS is True
    assert [m["name"] for m in harness.cell_metrics(bench, "tiny-exact.demo", "per_layer")] == [
        "demo_batches"]
    assert "qps" in [m["name"] for m in harness.cell_metrics(bench, "tiny-exact.demo", "end_to_end")]
    assert "p99_ms" not in [m["name"]
                            for m in harness.cell_metrics(bench, "tiny-exact.demo", "end_to_end")]


def test_new_cell_runs_with_its_metric(tree):
    root, base = tree
    traced = harness.run_cell("tiny-exact.demo", seed=7, seconds=1.0, trace=True,
                              rehearsal=True, root=root, base=base)
    assert traced["correct"] is True, traced["checks"]
    assert traced["metrics"]["cpu_rehearsal.demo_batches"]["value"] > 0
    plain = harness.run_cell("tiny-exact.demo", seed=7, seconds=1.0, trace=False,
                             rehearsal=True, root=root, base=base)
    assert set(plain["metrics"]) == {"cpu_rehearsal." + n for n in
                                     ("qps", "recall_at_10", "build_s", "setup_s")}


def test_new_open_loop_cell_reports_its_tail(tree):
    root, base = tree
    out = harness.run_cell("tiny-exact.open", seed=8, seconds=1.0, trace=False,
                           rehearsal=True, root=root, base=base)
    assert out["correct"] is True, out["checks"]
    assert out["attempted"] == 200 and out["failed"] == 0
    assert out["metrics"]["cpu_rehearsal.p99_ms"]["value"] > 0


def test_the_benchmark_files_are_untouched(tree):
    # the new files went to the temporary tree only
    for sub, name in (("configs", "tiny-exact.json"), ("traffic", "demo_closed.json"),
                      ("traffic", "demo_open.json"),
                      ("families", "exact_demo.py"), ("metrics", "demo_batches.py")):
        assert not os.path.exists(os.path.join(harness.HERE, sub, name))
