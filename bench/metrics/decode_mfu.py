"""The whole decode step's share of the chip's bf16 peak, in %: the
model FLOPs of every decode step in the window (live rows only, each at
its own context length, the block's ``decode_flops``) over the steps'
wall time (dispatch to ``block_until_ready``) times the peak."""


def read(ctx):
    calls = [c for c in ctx.decode_calls if c[1] <= ctx.elapsed]
    if not calls:
        return None
    work = sum(ctx.block.decode_flops(ctx.sizes, c) for _, _, c in calls)
    wall = sum(b - a for a, b, _ in calls)
    return 100.0 * work / (wall * ctx.peaks["bf16_flops_per_s"])
