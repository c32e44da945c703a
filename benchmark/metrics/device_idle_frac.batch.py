"""Share of the traced window in which no op ran on the device:
1 - (union of device op intervals / window), on the batch cells."""


def read(ctx):
    t = ctx.trace
    if t is None or not t.window_s:
        return None
    return 1.0 - t.busy_s / t.window_s
