"""Share of the device's busy time spent in the exact refine: the device
time of the ops of the refine's jitted program (found by its name in the
trace), over the union of all device op intervals in the window."""


def read(ctx):
    t = ctx.trace
    if t is None or not t.busy_s:
        return None
    s = t.program_seconds("refine")
    if s is None:
        return None
    return 100.0 * s / t.busy_s
