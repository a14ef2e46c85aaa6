"""idle_share: the share of the traced window in which no operation ran on
the device (1 - union of device-op intervals over the window)."""
from bench import trace


def read(ctx):
    if ctx.trace is None:
        return None
    t0, t1 = ctx.trace_window
    ops = ctx.trace.ops[min(ctx.trace.ops)]
    return 100.0 * (1.0 - trace.busy_seconds(ops, t0, t1) / (t1 - t0))
