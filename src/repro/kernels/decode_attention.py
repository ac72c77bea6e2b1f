"""Flash-decode: single-token attention over a long KV cache.

This is the paper's latency-critical regime transplanted to TPU: one query
token, a huge memory-bound KV stream, near-idle MXU. The kernel pipelines
cache blocks (DMA "memory thread") against the tiny logits/PV contractions
("compute thread") with running max/sum in VMEM scratch — the SMT-pair
co-scheduling that recovers the idle resource.

Grid (B, KV, S/bk): sequential cache-block axis innermost; the g query
heads of each kv group ride in the sublane dim.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG = -1e30


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *, scale, bk):
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    valid_len = len_ref[0]  # scalar int32 for this batch row
    # skip cache blocks entirely past the valid length ("memory thread"
    # stops streaming once the data is dead — Relic's early task retire)
    @pl.when(ik * bk < valid_len)
    def _step():
        q = q_ref[0, 0]  # [g, hd]
        k = k_ref[0, 0]  # [bk, hd]
        v = v_ref[0, 0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # [g, bk]
        pos = ik * bk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = pos < valid_len
        s = jnp.where(mask, s, NEG)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + p.sum(axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[...] = m_new

    @pl.when(ik == pl.num_programs(2) - 1)
    def _flush():
        o_ref[0, 0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(
            o_ref.dtype
        )


def decode_attention(
    q: jax.Array,
    k_cache: jax.Array,
    v_cache: jax.Array,
    cache_len: jax.Array,
    *,
    bk: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """q [B,H,hd]; caches [B,Smax,KV,hd]; cache_len [B] → out [B,H,hd]."""
    B, H, hd = q.shape
    Smax, KV = k_cache.shape[1], k_cache.shape[2]
    g = H // KV
    bk = min(bk, Smax)
    assert Smax % bk == 0
    scale = 1.0 / math.sqrt(hd)

    qr = q.reshape(B, KV, g, hd)
    kr = k_cache.transpose(0, 2, 1, 3)  # [B, KV, Smax, hd]
    vr = v_cache.transpose(0, 2, 1, 3)

    grid = (B, KV, Smax // bk)
    out = pl.pallas_call(
        functools.partial(_decode_kernel, scale=scale, bk=bk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1,), lambda b, kv, ik: (b,), memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, g, hd), lambda b, kv, ik: (b, kv, 0, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, kv, ik: (b, kv, ik, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, kv, ik: (b, kv, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, g, hd), lambda b, kv, ik: (b, kv, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, KV, g, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(cache_len, qr, kr, vr)
    return out.reshape(B, H, hd)
