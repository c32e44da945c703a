"""Milliseconds a dispatched batch in which the device sat idle while the
host was inside the index search: idle stretches of the first chip
under the innermost program span named ``ivf_flat.*``, ``ivf_pq.*`` or
``refine.*`` (the search's prelude, the query copy, the hand-off from
the scan to the refine), a ``py.gc`` counted to the span that encloses
it. None when the trace holds no program span."""
from benchmark import spans


def read(ctx):
    return spans.idle_ms_per_batch(ctx, spans.SEARCH)
