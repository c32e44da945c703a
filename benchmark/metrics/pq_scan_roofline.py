"""Share of the IVF-PQ code scan's roofline: the least time the
window's batches need, over the PQ scan kernel's device time in the
trace.

The work of one batch, whatever implements the scan: each distinct list
that some query of the batch probes has its codes read once, the
queries are read as float32 and the k x refine_ratio candidates
(distance and id) written out; each query decodes and adds pq_dim
lookups for every row of its own probed lists. The bound is HBM
bandwidth unless those operations at the codes' dtype's peak take
longer (see ``work.least_seconds``).
"""
import numpy as np

from benchmark import work


def batch_work(probes: np.ndarray, facts: dict, ctx):
    """``(bytes, operations)`` of one batch whose queries probe ``probes``
    [nq, n_probes]."""
    sizes = facts["list_sizes"]
    nq, dim = probes.shape[0], facts["dim"]
    k_out = ctx.cfg["k"] * ctx.cfg["search"].get("refine_ratio", 1)
    n_bytes = sizes[np.unique(probes)].sum() * facts["row_bytes"] + nq * dim * 4 + nq * k_out * 8
    n_ops = float(facts["pq_dim"]) * sizes[probes].sum()
    return float(n_bytes), float(n_ops)


def read(ctx):
    if ctx.trace is None:
        return None
    return work.roofline_share(ctx, batch_work)
