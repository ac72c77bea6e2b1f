"""The Llama-shaped dense block: its sizes, the program fields that must
match them, its weight rule, its plain reference and its counts.

The reference is the published decoder written out in float32 with
``Precision.HIGHEST`` matmuls and nothing else: token embedding,
``num_hidden_layers`` pre-norm blocks of RMSNorm, causal grouped-query
attention with rotary positions (the half-split rotation of the Llama
family, over the whole head) and a SwiGLU MLP, a final RMSNorm and the
LM head (the transposed embedding where it is tied). It imports nothing
of the program; it reads the weights the benchmark made, upcast to
float32. ``compare.judge`` compares what the program served with its
``logits``; ``quant="fp8"`` is the control. Another block of this shape
passes its own ``norm`` and ``rope`` (``blocks/stablelm.py``).

The counts are the model's own, not what a kernel happens to execute: a
dead decode row, a pad position or a block DMA'd but masked off costs
nothing here.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.compare import HI, mm, q8

BF16 = 2  # bytes

# --------------------------------------------------------------------------
# sizes and the program's fields


def sizes(conf: dict) -> dict:
    """The reference's sizes, read from a configuration file's published
    keys."""
    d = conf["hidden_size"]
    h = conf["num_attention_heads"]
    return {
        "layers": conf["num_hidden_layers"],
        "d_model": d,
        "n_heads": h,
        "n_kv_heads": conf["num_key_value_heads"],
        "head_dim": conf.get("head_dim", d // h),
        "d_ff": conf["intermediate_size"],
        "vocab": conf["vocab_size"],
        "eps": conf.get("rms_norm_eps", conf.get("layer_norm_eps")),
        "theta": float(conf["rope_theta"]),
        "tied": bool(conf["tie_word_embeddings"]),
    }


def program_fields(sz: dict, conf: dict) -> dict:
    """The program config's fields that must equal the file's sizes."""
    return {
        "num_layers": sz["layers"],
        "d_model": sz["d_model"],
        "n_heads": sz["n_heads"],
        "n_kv_heads": sz["n_kv_heads"],
        "head_dim": sz["head_dim"],
        "d_ff": sz["d_ff"],
        "vocab_size": sz["vocab"],
        "tie_embeddings": sz["tied"],
        "norm_eps": sz["eps"],
        "rope_theta": sz["theta"],
        "dtype": conf["torch_dtype"],
        "family": "dense",
    }


# --------------------------------------------------------------------------
# weight rule: N(0, fan_in^-0.5) for every leaf but the norm scales (ones)

ONES = ("final_norm", "attn_norm", "mlp_norm", "norm")


def fan_in(name: str, sz: dict) -> int:
    leaf = name.rsplit("/", 1)[-1]
    if leaf == "wo":
        return sz["n_heads"] * sz["head_dim"]
    if leaf == "w2":
        return sz["d_ff"]
    return sz["d_model"]


# --------------------------------------------------------------------------
# the plain reference


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def rope(x, pos, sz):
    """The half-split rotation over the whole of the last axis."""
    theta = sz["theta"]
    half = x.shape[-1] // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = pos.astype(jnp.float32)[:, None] * freqs  # [S, half]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _attention(q, k, v, *, n_rep, quant, chunk):
    """Causal attention, queries in chunks so the scores stay small.
    q [S, H, hd]; k, v [S, KV, hd] → [S, H, hd]."""
    S, H, hd = q.shape
    k = jnp.repeat(k, n_rep, axis=1)
    v = jnp.repeat(v, n_rep, axis=1)
    if quant:
        k, v = q8(k, -1), q8(v, 0)
    scale = 1.0 / np.sqrt(hd)
    qc = q.reshape(S // chunk, chunk, H, hd)

    def one(args):
        i, qi = args
        if quant:
            qi = q8(qi, -1)
        s = jnp.einsum("qhd,khd->hqk", qi, k, precision=HI) * scale
        qpos = i * chunk + jnp.arange(chunk)
        mask = jnp.arange(S)[None, :] <= qpos[:, None]
        s = jnp.where(mask[None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        if quant:
            p = q8(p, -1)
        return jnp.einsum("hqk,khd->qhd", p, v, precision=HI)

    out = jax.lax.map(one, (jnp.arange(S // chunk), qc))
    return out.reshape(S, H, hd)


@functools.partial(jax.jit, static_argnames=("sz", "window", "quant", "norm", "rope"))
def _forward(params, tokens, start, *, sz, window, quant, norm, rope):
    sz = dict(sz)
    f32 = jnp.float32
    S = tokens.shape[0]
    H, KV = sz["n_heads"], sz["n_kv_heads"]
    eps = sz["eps"]
    pos = jnp.arange(S)
    x = params["embed"][tokens].astype(f32)
    chunk = min(S, 512)

    def layer(x, lp):
        lp = jax.tree.map(lambda w: w.astype(f32), lp)
        a = lp["attn"]
        h = norm(x, lp["attn_norm"], eps)
        q = rope(mm("sd,dhk->shk", h, a["wq"], quant), pos, sz)
        k = rope(mm("sd,dhk->shk", h, a["wk"], quant), pos, sz)
        v = mm("sd,dhk->shk", h, a["wv"], quant)
        o = _attention(q, k, v, n_rep=H // KV, quant=quant, chunk=chunk)
        x = x + mm("shk,hkd->sd", o.reshape(S, H, -1), a["wo"], quant)
        h = norm(x, lp["mlp_norm"], eps)
        m = lp["mlp"]
        g = jax.nn.silu(mm("sd,df->sf", h, m["w1"], quant))
        u = mm("sd,df->sf", h, m["w3"], quant)
        return x + mm("sf,fd->sd", g * u, m["w2"], quant), None

    x, _ = jax.lax.scan(layer, x, params["layers"])
    x = jax.lax.dynamic_slice_in_dim(x, start, window, axis=0)
    x = norm(x, params["final_norm"].astype(f32), eps)
    if sz["tied"]:
        head = params["embed"].astype(f32).T
    else:
        head = params["lm_head"].astype(f32)
    return mm("sd,dv->sv", x, head, quant)


def logits(
    params, prompt, served, *, sizes, max_seq, window, quant=None, norm=rms_norm, rope=rope
):
    """Reference logits at each served position: row i predicts
    ``served[i]``."""
    prompt = np.asarray(prompt, np.int32)
    served = np.asarray(served, np.int32)
    n, p = len(served), len(prompt)
    if n > window:
        raise ValueError(f"{n} served tokens exceed the reference window {window}")
    # pad to a power of two (at least ``window``, at most ``max_seq``):
    # causal attention leaves the real positions as they are, and a
    # cell's requests share a few programs
    S = min(max_seq, max(window, 512, 1 << (p + n - 1).bit_length()))
    seq = np.zeros(S, np.int32)
    seq[:p] = prompt
    seq[p : p + n - 1] = served[: n - 1]
    start = min(p - 1, S - window)
    off = p - 1 - start
    out = _forward(
        params, jnp.asarray(seq), jnp.int32(start),
        sz=tuple(sorted(sizes.items())), window=window, quant=quant == "fp8",
        norm=norm, rope=rope,
    )
    return np.asarray(out[off : off + n], np.float32)


# --------------------------------------------------------------------------
# counts


def matmul_params(sz: dict) -> int:
    """Weights one token multiplies through: every block's projections
    and MLP, and the LM head over the published vocabulary."""
    d, h, kv, hd, f = (sz[k] for k in ("d_model", "n_heads", "n_kv_heads", "head_dim", "d_ff"))
    per_layer = d * h * hd + 2 * d * kv * hd + h * hd * d + 3 * d * f
    return sz["layers"] * per_layer + d * sz["vocab"]


def attn_flops(sz: dict, ctx: int) -> int:
    """One query over ``ctx`` cached positions, every layer: q·k and p·v."""
    return sz["layers"] * 4 * sz["n_heads"] * sz["head_dim"] * ctx


def decode_flops(sz: dict, ctx_lengths) -> int:
    """Model FLOPs of one decode step over the live rows, row i
    attending ``ctx_lengths[i]`` positions (its cache plus the new one)."""
    ctx_lengths = [int(c) for c in ctx_lengths]
    mmf = 2 * matmul_params(sz) * len(ctx_lengths)
    return mmf + sum(attn_flops(sz, c) for c in ctx_lengths)


def paged_attn_cost(sz: dict, ctx_lengths) -> tuple[int, int]:
    """(FLOPs, bytes) of one decode step's attention kernel calls, all
    layers: per live row, q·k and p·v over its context, reading its live
    K and V once and q and o once."""
    h, kv, hd, L = sz["n_heads"], sz["n_kv_heads"], sz["head_dim"], sz["layers"]
    flops = 0
    nbytes = 0
    for c in ctx_lengths:
        c = int(c)
        flops += L * 4 * h * hd * c
        nbytes += L * (2 * c * kv * hd + 2 * h * hd) * BF16
    return flops, nbytes
