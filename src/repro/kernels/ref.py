"""Pure-jnp oracles for every Pallas kernel (the allclose targets)."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def matmul_ref(x, w):
    return jnp.dot(x, w, preferred_element_type=jnp.float32).astype(x.dtype)


def attention_ref(q, k, v, causal=True):
    """Naive full-softmax attention. q [B,Sq,H,hd], k/v [B,Skv,KV,hd]."""
    B, Sq, H, hd = q.shape
    Skv, KV = k.shape[1], k.shape[2]
    g = H // KV
    k = jnp.repeat(k, g, axis=2)
    v = jnp.repeat(v, g, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) / math.sqrt(hd)
    if causal:
        q_pos = jnp.arange(Sq) + (Skv - Sq)
        mask = q_pos[:, None] >= jnp.arange(Skv)[None, :]
        s = jnp.where(mask[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v)


def decode_attention_ref(q, k_cache, v_cache, cache_len):
    """q [B,H,hd]; caches [B,Smax,KV,hd]; cache_len [B]."""
    B, H, hd = q.shape
    Smax, KV = k_cache.shape[1], k_cache.shape[2]
    g = H // KV
    kc = jnp.repeat(k_cache, g, axis=2)
    vc = jnp.repeat(v_cache, g, axis=2)
    s = jnp.einsum("bhd,bshd->bhs", q, kc).astype(jnp.float32) / math.sqrt(hd)
    valid = jnp.arange(Smax)[None, :] < cache_len[:, None]
    s = jnp.where(valid[:, None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhs,bshd->bhd", p.astype(vc.dtype), vc)


def paged_attention_ref(
    q, k_pool, v_pool, block_tables, lengths, k_scale=None, v_scale=None, layer=0
):
    """Dense-gather oracle for the block-paged decode/verify kernel.

    q [B,T,H,hd]; pools are stored layer-stacked leaves [L,NB,KV,R,W]
    (token i of a block in row i // P, lanes (i % P)·hd on, P = W/hd),
    of which layer ``layer`` is read; tables [B,MB]; lengths [B] (query
    t attends positions < lengths + t + 1). int8 pools pass per-token
    scales [L,NB,KV,R,P]."""
    B, T, H, hd = q.shape
    KV, R, W = k_pool.shape[2:]
    P = W // hd
    MB = block_tables.shape[1]
    S = MB * R * P
    g = H // KV

    def dense(leaf, d):  # [B, MB·BS, KV, d] f32
        x = jnp.take(jax.lax.dynamic_index_in_dim(leaf, layer, 0, False), block_tables, axis=0)
        x = x.reshape(B, MB, KV, R * P, d).transpose(0, 1, 3, 2, 4)
        return x.reshape(B, S, KV, d).astype(jnp.float32)

    kd, vd = dense(k_pool, hd), dense(v_pool, hd)
    if k_scale is not None:
        kd = kd * (dense(k_scale, 1) / 127.0)
        vd = vd * (dense(v_scale, 1) / 127.0)
    kd = jnp.repeat(kd, g, axis=2)
    vd = jnp.repeat(vd, g, axis=2)
    s = jnp.einsum("bthd,bshd->bths", q.astype(jnp.float32), kd) / math.sqrt(hd)
    pos = jnp.arange(S)[None, None, :]
    valid = pos < (lengths[:, None] + jnp.arange(T)[None, :] + 1)[:, :, None]
    s = jnp.where(valid[:, :, None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bths,bshd->bthd", p, vd).astype(q.dtype)


def ssd_ref(xh, a, b, c, dt):
    """Sequential (unchunked) SSD recurrence — the ground truth.

    xh [B,S,H,hd]; a [B,S,H] (decay = exp(dt·A)); b,c [B,S,N]; dt [B,S,H].
    h_t = a_t·h_{t-1} + dt_t·(b_t ⊗ x_t);  y_t = c_t·h_t
    """
    B, S, H, hd = xh.shape
    N = b.shape[-1]

    def step(state, args):
        x_t, a_t, b_t, c_t, dt_t = args
        state = state * a_t[..., None, None] + jnp.einsum(
            "bhd,bn,bh->bhdn", x_t.astype(jnp.float32), b_t.astype(jnp.float32), dt_t
        )
        y = jnp.einsum("bn,bhdn->bhd", c_t.astype(jnp.float32), state)
        return state, y

    s0 = jnp.zeros((B, H, hd, N), jnp.float32)
    xs = (
        xh.swapaxes(0, 1),
        a.swapaxes(0, 1),
        b.swapaxes(0, 1),
        c.swapaxes(0, 1),
        dt.swapaxes(0, 1),
    )
    _, ys = jax.lax.scan(step, s0, xs)
    return ys.swapaxes(0, 1).astype(xh.dtype)  # [B,S,H,hd]
