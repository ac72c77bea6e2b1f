"""90th percentile of queue wait (due to first admission) over the
requests due in the window; one never admitted waits until the drain
ended."""
import numpy as np


def read(ctx):
    if ctx.backlog or not ctx.records:
        return None
    ms = [
        ((r["admit"] if r["admit"] is not None else ctx.drain_end) - r["arrival"]) * 1e3
        for r in ctx.records
    ]
    return float(np.percentile(ms, 90))
