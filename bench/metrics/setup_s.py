"""Set-up: process start to the first timed request (weights, engine,
warm-up of every program the cell's traffic reaches)."""


def read(ctx):
    return ctx.setup_s
