"""Plain reference of the served models, and the comparison that decides
``correct``.

The reference is the published decoder written out in float32 with
``Precision.HIGHEST`` matmuls and nothing else: token embedding,
``num_hidden_layers`` pre-norm blocks of RMSNorm, causal grouped-query
attention with rotary positions (the half-split rotation of the Llama
family) and a SwiGLU MLP, a final RMSNorm and the LM head (the
transposed embedding where it is tied). It imports nothing of the
program; it reads the weights the benchmark made, upcast to float32.

Teacher-forced over one request's prompt and served tokens, it gives
the logits at every served position. A served token is judged by its
gap: how far its reference logit lies below the reference's best at
that position. Greedy decoding in exact arithmetic has gap 0; rounding
in bfloat16 opens small gaps at near-ties; a wrong cache, mask, block
or token opens wide ones.

``quant="fp8"`` is the control: the same reference with every matmul
operand (weights per tensor, activations per row) rounded through
float8_e4m3 with a max-abs scale, the next precision below the
configuration's bfloat16. Its gap is read for the token it puts first.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
F8_MAX = 448.0  # largest finite float8_e4m3fn


def sizes_from_config(conf: dict) -> dict:
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


def _q8(x, axis):
    """Round through float8_e4m3 with a max-abs scale over ``axis``."""
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / F8_MAX
    scale = jnp.where(scale > 0, scale, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _mm(spec, a, w, quant):
    """einsum of an activation (scaled per row) and a weight."""
    if quant:
        a = _q8(a, -1)
        w = _q8(w, None)
    return jnp.einsum(spec, a, w, precision=HI)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _rope(x, pos, theta):
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
        k, v = _q8(k, -1), _q8(v, 0)
    scale = 1.0 / np.sqrt(hd)
    qc = q.reshape(S // chunk, chunk, H, hd)

    def one(args):
        i, qi = args
        if quant:
            qi = _q8(qi, -1)
        s = jnp.einsum("qhd,khd->hqk", qi, k, precision=HI) * scale
        qpos = i * chunk + jnp.arange(chunk)
        mask = jnp.arange(S)[None, :] <= qpos[:, None]
        s = jnp.where(mask[None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        if quant:
            p = _q8(p, -1)
        return jnp.einsum("hqk,khd->qhd", p, v, precision=HI)

    out = jax.lax.map(one, (jnp.arange(S // chunk), qc))
    return out.reshape(S, H, hd)


@functools.partial(jax.jit, static_argnames=("sz", "window", "quant"))
def _forward(params, tokens, start, *, sz, window, quant):
    sizes = dict(sz)
    f32 = jnp.float32
    S = tokens.shape[0]
    H, KV = sizes["n_heads"], sizes["n_kv_heads"]
    eps, theta = sizes["eps"], sizes["theta"]
    pos = jnp.arange(S)
    x = params["embed"][tokens].astype(f32)
    chunk = min(S, 512)

    def layer(x, lp):
        lp = jax.tree.map(lambda w: w.astype(f32), lp)
        a = lp["attn"]
        h = _rms(x, lp["attn_norm"], eps)
        q = _rope(_mm("sd,dhk->shk", h, a["wq"], quant), pos, theta)
        k = _rope(_mm("sd,dhk->shk", h, a["wk"], quant), pos, theta)
        v = _mm("sd,dhk->shk", h, a["wv"], quant)
        o = _attention(q, k, v, n_rep=H // KV, quant=quant, chunk=chunk)
        x = x + _mm("shk,hkd->sd", o.reshape(S, H, -1), a["wo"], quant)
        h = _rms(x, lp["mlp_norm"], eps)
        m = lp["mlp"]
        g = jax.nn.silu(_mm("sd,df->sf", h, m["w1"], quant))
        u = _mm("sd,df->sf", h, m["w3"], quant)
        return x + _mm("sf,fd->sd", g * u, m["w2"], quant), None

    x, _ = jax.lax.scan(layer, x, params["layers"])
    x = jax.lax.dynamic_slice_in_dim(x, start, window, axis=0)
    x = _rms(x, params["final_norm"].astype(f32), eps)
    if sizes["tied"]:
        head = params["embed"].astype(f32).T
    else:
        head = params["lm_head"].astype(f32)
    return _mm("sd,dv->sv", x, head, quant)


def logits(params, prompt, served, *, sizes, max_seq, window, quant=None):
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
    )
    return np.asarray(out[off : off + n], np.float32)


def gaps(ref_logits: np.ndarray, tokens: np.ndarray) -> np.ndarray:
    """How far each token's reference logit lies below the best."""
    tokens = np.asarray(tokens)
    picked = ref_logits[np.arange(len(tokens)), tokens]
    return ref_logits.max(axis=-1) - picked


def judge(params, sample, *, sizes, max_seq, window, control=False) -> dict:
    """Widest gap over every served token of ``sample`` (a list of
    (prompt, served tokens)). With ``control``, also the widest gap of
    the tokens the fp8 reference puts first at the same positions."""
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
