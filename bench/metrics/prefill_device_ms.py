"""Device time per prefill call in the traced part of the window: the
mean length of the prefill programs on the device's module line. The
engine jits its cold and its prefix-hit prefill as lambdas, so their
programs are the modules whose names hold ``lambda``."""
import numpy as np

from bench import trace

PREFILL = r"lambda"


def read(ctx):
    if ctx.trace is None:
        return None
    mods = trace.modules_matching(ctx.trace, PREFILL)
    if not mods:
        return None
    return float(np.mean([m.dur for m in mods])) / 1e6
