"""Milliseconds a dispatched batch in which the device sat idle while the
host was inside the serving engine: idle stretches of the first chip
under the innermost program span named ``serve.*`` or ``plan.*``
(``serve.step``, ``serve.assemble``, ``serve.wait``, ``serve.fetch``,
``plan.replan``, ...), a ``py.gc`` counted to the span that encloses
it. None when the trace holds no program span."""
from benchmark import spans


def read(ctx):
    return spans.idle_ms_per_batch(ctx, spans.SERVE)
