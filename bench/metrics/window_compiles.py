"""Executables JAX built (compiled or read from the compile cache)
inside the measured window, counted through ``jax.monitoring``. The
warm-up should have built every one the window needs, so this reads 0;
the names of any that were built go into the run's notes."""


def read(ctx):
    if ctx.window_compiles:
        ctx.notes["window_compiles"] = ctx.window_compiles
    return len(ctx.window_compiles)
