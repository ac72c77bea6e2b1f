"""Mean wall time of the scheduler's steps inside the window
(``ServeStats.step_ms``: admission, prefill and one batched decode,
ending in ``block_until_ready``)."""
import numpy as np


def read(ctx):
    return float(np.mean(ctx.step_ms)) if ctx.step_ms else None
