"""The comparison that decides ``correct``, and the pieces every block's
reference shares.

A block's reference (``blocks/<name>.py``), teacher-forced over one
request's prompt and served tokens, gives the logits at every served
position. A served token is judged by its gap: how far its reference
logit lies below the reference's best at that position. Greedy decoding
in exact arithmetic has gap 0; rounding in bfloat16 opens small gaps at
near-ties; a wrong cache, mask, block or token opens wide ones.

The control is the same reference with every matmul operand (weights per
tensor, activations per row) rounded through float8_e4m3 with a max-abs
scale (``mm(..., quant=True)``), the next precision below the
configurations' bfloat16. Its gap is read for the token it puts first.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
F8_MAX = 448.0  # largest finite float8_e4m3fn


def q8(x, axis):
    """Round through float8_e4m3 with a max-abs scale over ``axis``."""
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / F8_MAX
    scale = jnp.where(scale > 0, scale, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def mm(spec, a, w, quant):
    """einsum of an activation (scaled per row) and a weight."""
    if quant:
        a = q8(a, -1)
        w = q8(w, None)
    return jnp.einsum(spec, a, w, precision=HI)


def gaps(ref_logits: np.ndarray, tokens: np.ndarray) -> np.ndarray:
    """How far each token's reference logit lies below the best."""
    tokens = np.asarray(tokens)
    picked = ref_logits[np.arange(len(tokens)), tokens]
    return ref_logits.max(axis=-1) - picked


def judge(logits, params, sample, *, sizes, max_seq, window, control=False) -> dict:
    """Widest gap over every served token of ``sample`` (a list of
    (prompt, served tokens)), by a block's ``logits(params, prompt,
    served, *, sizes, max_seq, window, quant)``. With ``control``, also
    the widest gap of the tokens the fp8 reference puts first at the
    same positions."""
    widest, n_tok, n_diff = 0.0, 0, 0
    ctrl = 0.0
    for prompt, served in sample:
        ref = logits(params, prompt, served, sizes=sizes, max_seq=max_seq, window=window)
        g = gaps(ref, served)
        widest = max(widest, float(g.max()))
        n_tok += len(served)
        n_diff += int((ref.argmax(-1) != np.asarray(served)).sum())
        if control:
            low = logits(
                params, prompt, served, sizes=sizes, max_seq=max_seq,
                window=window, quant="fp8",
            )
            ctrl = max(ctrl, float(gaps(ref, low.argmax(-1)).max()))
    out = {"max_logit_gap": widest, "tokens": n_tok, "argmax_differs": n_diff}
    if control:
        out["control_max_logit_gap"] = ctrl
    return out
