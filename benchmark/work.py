"""What the roofline readers share: each query's probed lists, found by
the benchmark itself over the index's centers, and the least time a
piece of work can take at the chip's published peaks."""
from __future__ import annotations

import numpy as np


def probe_sets(centers: np.ndarray, queries: np.ndarray, n_probes: int, metric: str,
               block: int = 2048) -> np.ndarray:
    """[nq, n_probes] ids of each query's nearest centers (squared L2, or
    the largest inner product), in float64."""
    c = centers.astype(np.float64)
    cn = np.sum(c * c, axis=1)
    out = np.empty((queries.shape[0], n_probes), np.int64)
    for s in range(0, queries.shape[0], block):
        dot = queries[s:s + block].astype(np.float64) @ c.T
        score = -dot if metric == "inner_product" else cn[None, :] - 2.0 * dot
        out[s:s + block] = np.argpartition(score, n_probes - 1, axis=1)[:, :n_probes]
    return out


def least_seconds(n_bytes: float, n_ops: float, peaks: dict, stored_dtype: str):
    """``(seconds, bound)``: the larger of bytes over the HBM bandwidth and
    operations over the peak for the stored dtype, and which of the two
    it is (``"hbm"`` or ``"compute"``)."""
    ops_peak = peaks["ops_per_s"][stored_dtype]
    t_mem = n_bytes / peaks["hbm_bytes_per_s"]
    t_ops = n_ops / ops_peak
    return (t_mem, "hbm") if t_mem >= t_ops else (t_ops, "compute")


def roofline_share(ctx, batch_work) -> float:
    """Percent of the scan kernel's traced device time that the least
    time of the window's batches makes up. ``batch_work(probes, facts,
    ctx)`` gives one batch's ``(bytes, ops)`` from its queries' probe
    sets. Raises when the trace holds no scan kernel: a path that
    silently skipped the kernel must not pass."""
    kernel_s = ctx.trace.kernel_seconds(ctx.family.SCAN_KERNELS)
    if not kernel_s:
        raise RuntimeError(f"no scan kernel {ctx.family.SCAN_KERNELS} in the traced window")
    f = ctx.facts
    probes = probe_sets(f["centers"], ctx.pool, f["n_probes"], ctx.cfg["metric"])
    least = 0.0
    for _, _, rows in ctx.window.batches:
        n_bytes, n_ops = batch_work(probes[rows], f, ctx)
        least += least_seconds(n_bytes, n_ops, ctx.peaks, f["stored_dtype"])[0]
    return 100.0 * least / kernel_s
