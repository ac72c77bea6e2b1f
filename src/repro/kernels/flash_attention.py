"""Causal flash attention (prefill/train) as a Pallas TPU kernel.

Grid (B·KV, Sq/bq, Skv/bk): the KV-block stream (DMA "memory thread")
pipelines against the MXU logits/PV contractions ("compute thread");
running max/sum/acc live in VMEM scratch across the sequential kv axis.
Causal block-skipping: kv blocks strictly above the diagonal are skipped
with ``pl.when`` — this is the FLOP saving the pure-jnp `masked` path
cannot express (EXPERIMENTS.md §Perf hillclimb #prefill).

GQA is handled by the index map (query heads of one kv group share the
kv block) without materializing repeated K/V.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *, scale, bq, bk, causal):
    iq, ik = pl.program_id(1), pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # causal: skip kv blocks strictly above the diagonal (the ½-FLOP win)
    run = (ik * bk < (iq + 1) * bq) if causal else True

    @pl.when(run)
    def _step():
        q = q_ref[0, 0]  # [g*bq, hd] — g query heads × bq rows flattened
        k = k_ref[0]  # [bk, hd]
        v = v_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # [g*bq, bk]
        if causal:
            g_bq = q.shape[0]
            q_pos = iq * bq + (jax.lax.broadcasted_iota(jnp.int32, (g_bq, bk), 0) % bq)
            k_pos = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (g_bq, bk), 1)
            mask = q_pos >= k_pos
            s = jnp.where(mask, s, NEG)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        if causal:
            p = jnp.where(mask, p, 0.0)
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


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    bq: int = 256,
    bk: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """q [B,Sq,H,hd]; k,v [B,Skv,KV,hd] → [B,Sq,H,hd]."""
    B, Sq, H, hd = q.shape
    Skv, KV = k.shape[1], k.shape[2]
    g = H // KV
    bq, bk = min(bq, Sq), min(bk, Skv)
    assert Sq % bq == 0 and Skv % bk == 0
    scale = 1.0 / math.sqrt(hd)

    # layout: fold (B, KV) into the leading grid axis; queries of one kv
    # group are flattened into the row dim so one kv block serves g heads.
    qr = (
        q.reshape(B, Sq // bq, bq, KV, g, hd)
        .transpose(0, 3, 1, 4, 2, 5)
        .reshape(B * KV, (Sq // bq), g * bq, hd)
    )
    kr = k.transpose(0, 2, 1, 3).reshape(B * KV, Skv, hd)
    vr = v.transpose(0, 2, 1, 3).reshape(B * KV, Skv, hd)

    grid = (B * KV, Sq // bq, Skv // bk)
    out = pl.pallas_call(
        functools.partial(_flash_kernel, scale=scale, bq=bq, bk=bk, causal=causal),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, g * bq, hd), lambda bh, iq, ik: (bh, iq, 0, 0)),
            pl.BlockSpec((1, bk, hd), lambda bh, iq, ik: (bh, ik, 0)),
            pl.BlockSpec((1, bk, hd), lambda bh, iq, ik: (bh, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, g * bq, hd), lambda bh, iq, ik: (bh, iq, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B * KV, Sq // bq, g * bq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((g * bq, 1), jnp.float32),
            pltpu.VMEM((g * bq, 1), jnp.float32),
            pltpu.VMEM((g * bq, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(qr, kr, vr)
    out = (
        out.reshape(B, KV, Sq // bq, g, bq, hd)
        .transpose(0, 2, 4, 1, 3, 5)
        .reshape(B, Sq, H, hd)
    )
    return out
