"""Device time per install of a prefill's K/V into the paged pool, in ms:
the mean length of the install program (``jit_install_prefill_blocks``)
on the device's module line in the traced part of the window. One runs
for every admission that brings fresh blocks."""
import numpy as np

from bench import trace

INSTALL = r"^jit_install_prefill_blocks\b"


def read(ctx):
    if ctx.trace is None:
        return None
    mods = trace.modules_matching(ctx.trace, INSTALL)
    if not mods:
        return None
    return float(np.mean([m.dur for m in mods])) / 1e6
