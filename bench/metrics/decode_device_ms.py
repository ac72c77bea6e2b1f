"""Device time per decode step in the traced part of the window: the
mean length of the decode program on the device's module line. The
decode program is the one that runs the paged kernel (the engine jits
it from a ``functools.partial``, so its module name says ``_unknown``)."""
import numpy as np

from bench import trace


def read(ctx):
    if ctx.trace is None:
        return None
    mods = trace.kernel_modules(ctx.trace)
    if not mods:
        return None
    return float(np.mean([m.dur for m in mods])) / 1e6
