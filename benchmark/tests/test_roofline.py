"""The roofline readers' work counts, on a case worked by hand.

Four lists in two dimensions, centers (0,0), (10,0), (0,10), (10,10),
holding 3, 5, 7 and 11 rows. With two probes, query (1,2) probes lists
0 and 2 (squared distances 5 and 65 against 85 and 145), and query
(9,2) lists 1 and 3 (5 and 65 against 85 and 145).
"""
import os
import types

import numpy as np
import pytest

from benchmark import harness, work

CENTERS = np.array([[0, 0], [10, 0], [0, 10], [10, 10]], np.float32)
SIZES = np.array([3, 5, 7, 11], np.int64)
QUERIES = np.array([[1, 2], [9, 2]], np.float32)
PEAKS = {"ops_per_s": {"bfloat16": 197e12, "uint8": 393e12}, "hbm_bytes_per_s": 819e9}


def _metric(name):
    return harness._load_module(os.path.join(harness.HERE, "metrics", name + ".py"), "t_" + name)


def _ctx(search):
    return types.SimpleNamespace(cfg={"k": 10, "metric": "sqeuclidean", "search": search})


def test_probe_sets():
    probes = work.probe_sets(CENTERS, QUERIES, 2, "sqeuclidean")
    assert [sorted(p) for p in probes.tolist()] == [[0, 2], [1, 3]]
    # inner product: the largest products win
    ip = work.probe_sets(CENTERS, QUERIES, 1, "inner_product")
    assert ip.tolist() == [[3], [3]]


def test_ivf_flat_batch_work():
    facts = {"list_sizes": SIZES, "row_bytes": 2 * 2 + 4, "dim": 2}
    probes = np.array([[0, 2], [1, 3]])
    n_bytes, n_ops = _metric("ivf_scan_roofline").batch_work(probes, facts, _ctx({}))
    # every list once: 26 rows x 8 B; 2 queries x 2 x 4 B; 2 x 10 x (4 + 4) B out
    assert n_bytes == 26 * 8 + 16 + 160
    # query 0 scores 3 + 7 rows, query 1 scores 5 + 11; 2 x dim a row
    assert n_ops == 2 * 2 * 26
    one = _metric("ivf_scan_roofline").batch_work(probes[:1], facts, _ctx({}))
    assert one == (10 * 8 + 8 + 80, 2 * 2 * 10)


def test_pq_batch_work():
    facts = {"list_sizes": SIZES, "row_bytes": 64, "dim": 2, "pq_dim": 64}
    probes = np.array([[0, 2], [1, 3]])
    n_bytes, n_ops = _metric("pq_scan_roofline").batch_work(probes, facts, _ctx({"refine_ratio": 8}))
    # codes of 26 rows x 64 B; queries 16 B; 2 x 80 candidates x 8 B
    assert n_bytes == 26 * 64 + 16 + 2 * 80 * 8
    assert n_ops == 64 * 26


def test_least_seconds_names_its_bound():
    t, bound = work.least_seconds(819e9, 1.0, PEAKS, "bfloat16")
    assert bound == "hbm" and t == pytest.approx(1.0)
    t, bound = work.least_seconds(1.0, 393e12, PEAKS, "uint8")
    assert bound == "compute" and t == pytest.approx(1.0)
    with pytest.raises(KeyError):
        work.least_seconds(1.0, 1.0, PEAKS, "float32")
