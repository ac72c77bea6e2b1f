"""Share of the traced window in which no operation ran on the device,
in %: 1 - (union of the op intervals) / (window)."""
from bench import trace


def read(ctx):
    if ctx.trace is None:
        return None
    w = trace.window_s(ctx.trace)
    if w <= 0 or not ctx.trace.ops:
        return None
    return 100.0 * (1.0 - trace.device_busy_s(ctx.trace) / w)
