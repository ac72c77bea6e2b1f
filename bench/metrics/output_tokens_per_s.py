"""Tokens emitted inside the window, finished requests or not, over the
window's seconds as run."""


def read(ctx):
    if ctx.elapsed <= 0 or not ctx.tokens_in_window:
        return None
    return ctx.tokens_in_window / ctx.elapsed
