"""90th percentile of time per output token after the first, over every
request with at least two tokens: (last token - first token) / (tokens
- 1). A request unfinished at the close (backlog) or at the end of the
drain counts up to then."""
import numpy as np


def read(ctx):
    ms = [
        (r["end"] - r["first"]) / (r["tokens"] - 1) * 1e3
        for r in ctx.records
        if r["first"] is not None and r["tokens"] >= 2
    ]
    return float(np.percentile(ms, 90)) if ms else None
