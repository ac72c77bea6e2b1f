"""90th percentile of time to first token over every request due in the
window, from the moment it was due. A request that never got its first
token before the drain ended counts with the time it waited until then,
a lower bound on what it would have waited."""
import numpy as np


def read(ctx):
    if ctx.backlog or not ctx.records:
        return None
    ms = [
        ((r["first"] if r["first"] is not None else ctx.drain_end) - r["arrival"]) * 1e3
        for r in ctx.records
    ]
    return float(np.percentile(ms, 90))
