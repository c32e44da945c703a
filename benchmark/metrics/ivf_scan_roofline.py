"""Share of the IVF-Flat list scan's roofline: the least time the
window's batches need, over the scan kernel's device time in the trace.

The work of one batch, whatever implements the scan: each distinct list
that some query of the batch probes is read once (its rows at the stored
width, plus a float32 norm a row for L2), the queries are read as
float32 and the top k (distance and id) written out; each query scores
every row of its own probed lists, 2 x dim operations a row. The bound
is HBM bandwidth unless the operations at the stored dtype's peak take
longer (see ``work.least_seconds``).
"""
import numpy as np

from benchmark import work


def batch_work(probes: np.ndarray, facts: dict, ctx):
    """``(bytes, operations)`` of one batch whose queries probe ``probes``
    [nq, n_probes]."""
    sizes = facts["list_sizes"]
    nq, dim, k = probes.shape[0], facts["dim"], ctx.cfg["k"]
    n_bytes = sizes[np.unique(probes)].sum() * facts["row_bytes"] + nq * dim * 4 + nq * k * 8
    n_ops = 2.0 * dim * sizes[probes].sum()
    return float(n_bytes), float(n_ops)


def read(ctx):
    if ctx.trace is None:
        return None
    return work.roofline_share(ctx, batch_work)
