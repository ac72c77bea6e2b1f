"""Operations and bytes the algorithm needs, counted from shapes.

These are the model's own counts, not what a kernel happens to execute:
a dead decode row, a pad position or a block DMA'd but masked off costs
nothing here. ``sizes`` is ``reference.sizes_from_config``'s dict.
"""
from __future__ import annotations

BF16 = 2  # bytes


def matmul_params(sizes: dict) -> int:
    """Weights one token multiplies through: every block's projections
    and MLP, and the LM head over the published vocabulary."""
    d, h, kv, hd, f = (sizes[k] for k in ("d_model", "n_heads", "n_kv_heads", "head_dim", "d_ff"))
    per_layer = d * h * hd + 2 * d * kv * hd + h * hd * d + 3 * d * f
    return sizes["layers"] * per_layer + d * sizes["vocab"]


def attn_flops(sizes: dict, ctx: int) -> int:
    """One query over ``ctx`` cached positions, every layer: q·k and p·v."""
    return sizes["layers"] * 4 * sizes["n_heads"] * sizes["head_dim"] * ctx


def decode_flops(sizes: dict, ctx_lengths) -> int:
    """Model FLOPs of one decode step over the live rows, row i
    attending ``ctx_lengths[i]`` positions (its cache plus the new one)."""
    ctx_lengths = [int(c) for c in ctx_lengths]
    mm = 2 * matmul_params(sizes) * len(ctx_lengths)
    return mm + sum(attn_flops(sizes, c) for c in ctx_lengths)


def paged_attn_cost(sizes: dict, ctx_lengths) -> tuple[int, int]:
    """(FLOPs, bytes) of one decode step's attention kernel calls, all
    layers: per live row, q·k and p·v over its context, reading its live
    K and V once and q and o once."""
    h, kv, hd, L = sizes["n_heads"], sizes["n_kv_heads"], sizes["head_dim"], sizes["layers"]
    flops = 0
    nbytes = 0
    for c in ctx_lengths:
        c = int(c)
        flops += L * 4 * h * hd * c
        nbytes += L * (2 * c * kv * hd + 2 * h * hd) * BF16
    return flops, nbytes

