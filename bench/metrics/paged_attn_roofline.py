"""The paged decode attention kernel's share of its roofline, in %: the
least time the chip could take for the traced decode steps' attention
(the larger of its FLOPs over the bf16 peak and its bytes over the HBM
peak, the block's ``paged_attn_cost``: live K/V, q and o) over the
kernel's device time in the trace. The bound that binds goes into the notes.

The kernel is the one Pallas call on the path: on the device's op line
a TPU custom call (``_paged_attention_impl``), one per layer a step."""
from bench import trace


def read(ctx):
    if ctx.trace is None:
        return None
    calls = ctx.traced_decode_calls()
    kern = trace.ops_matching(ctx.trace, trace.is_kernel)
    if not calls or not kern:
        return None
    f = b = 0
    for _, _, c in calls:
        df, db = ctx.block.paged_attn_cost(ctx.sizes, c)
        f, b = f + df, b + db
    t_flops = f / ctx.peaks["bf16_flops_per_s"]
    t_bytes = b / ctx.peaks["hbm_bytes_per_s"]
    kernel_s = sum(e.dur for e in kern) / 1e9
    ctx.notes["paged_attn_roofline"] = {
        "bound": "hbm" if t_bytes >= t_flops else "flops",
        "kernel_s": kernel_s,
        "kernel_calls": len(kern),
        "decode_steps": len(calls),
        "kernel": trace.op_name(kern[0]),
    }
    return 100.0 * max(t_flops, t_bytes) / kernel_s
