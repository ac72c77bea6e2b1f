"""GQA attention: chunked-causal prefill/train and single-token decode.

The prefill path is a pure-jnp flash-equivalent (running-max softmax over
KV chunks) so activation memory stays O(S·chunk) rather than O(S²) — this
is the reference semantics for ``kernels/flash_attention.py`` and the path
the multi-pod dry-run lowers.

Two causal blocking modes (the §Perf hillclimb axis):
  masked      — every q attends over all KV chunks with a mask (2× causal
                FLOPs, smallest HLO)
  triangular  — python-unrolled q-blocks, each contracting only its causal
                KV prefix (≈½ the FLOPs, bigger HLO)
"""
from __future__ import annotations

import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import ops as kernel_ops
from repro.models.layers import apply_rope, constrain, normal, rope_tables
from repro.parallel.collectives import distributed_softmax

NEG = -1e30


def init_attention(key, cfg, dtype):
    d, h, kv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    s = d**-0.5
    if cfg.attn_flat_tp:
        # head-agnostic layout: projections shard the FLATTENED q/kv dim
        # over 'model' even when n_heads ∤ mesh (phi3 40H, smollm 9H) —
        # weights and their grads stay sharded; the head structure is
        # recovered by a reshape + resharding constraint at attention
        # entry (EXPERIMENTS.md §Perf hillclimb C it.4).
        params = {
            "wq": normal(ks[0], (d, h * hd), s, dtype),
            "wk": normal(ks[1], (d, kv * hd), s, dtype),
            "wv": normal(ks[2], (d, kv * hd), s, dtype),
            "wo": normal(ks[3], (h * hd, d), (h * hd) ** -0.5, dtype),
        }
        axes = {
            "wq": ("embed", "qdim"),
            "wk": ("embed", "qdim"),
            "wv": ("embed", "qdim"),
            "wo": ("qdim", "embed"),
        }
        return params, axes
    params = {
        "wq": normal(ks[0], (d, h, hd), s, dtype),
        "wk": normal(ks[1], (d, kv, hd), s, dtype),
        "wv": normal(ks[2], (d, kv, hd), s, dtype),
        "wo": normal(ks[3], (h, hd, d), (h * hd) ** -0.5, dtype),
    }
    axes = {
        "wq": ("embed", "heads", "head_dim"),
        "wk": ("embed", "kv_heads", "head_dim"),
        "wv": ("embed", "kv_heads", "head_dim"),
        "wo": ("heads", "head_dim", "embed"),
    }
    return params, axes


def _repeat_kv(k, n_rep):
    if n_rep == 1:
        return k
    b, s, kv, hd = k.shape
    return jnp.broadcast_to(
        k[:, :, :, None, :], (b, s, kv, n_rep, hd)
    ).reshape(b, s, kv * n_rep, hd)


# ---------------------------------------------------------------------------
# prefill / train


def _attn_chunk_step(q, kc, vc, k_pos, q_pos, m, l, acc, scale):
    """One flash step: q [B,Sq,H,hd] against one KV chunk [B,Ck,H,hd]."""
    s = jnp.einsum("bqhd,bkhd->bhqk", q, kc).astype(jnp.float32) * scale
    mask = q_pos[None, None, :, None] >= k_pos[None, None, None, :]
    s = jnp.where(mask, s, NEG)
    m_new = jnp.maximum(m, s.max(axis=-1))
    p = jnp.where(mask, jnp.exp(s - m_new[..., None]), 0.0)
    corr = jnp.exp(m - m_new)
    l_new = l * corr + p.sum(axis=-1)
    pv = jnp.einsum("bhqk,bkhd->bqhd", p.astype(vc.dtype), vc)
    acc_new = acc * corr.transpose(0, 2, 1)[..., None] + pv.astype(jnp.float32)
    return m_new, l_new, acc_new


def gqa_attention(
    q,
    k,
    v,
    *,
    q_offset=0,
    chunk: int = 512,
    blocking: str = "masked",
    rules=None,
):
    """Causal GQA attention. q [B,Sq,H,hd]; k,v [B,Skv,KV,hd] (RoPE'd).

    q_offset: absolute position of q[0] (Sq may be a suffix of Skv).
    """
    B, Sq, H, hd = q.shape
    Skv, KV = k.shape[1], k.shape[2]
    n_rep = H // KV
    scale = 1.0 / math.sqrt(hd)
    chunk = min(chunk, Skv)
    while Skv % chunk:
        chunk -= 1
    nc = Skv // chunk
    q_pos = q_offset + jnp.arange(Sq)

    m0 = jnp.full((B, H, Sq), NEG, jnp.float32)
    l0 = jnp.zeros((B, H, Sq), jnp.float32)
    a0 = jnp.zeros((B, Sq, H, hd), jnp.float32)

    if blocking == "triangular" and q_offset == 0 and Sq == Skv:
        out = _triangular_attention(q, k, v, n_rep, scale, chunk, rules)
        return constrain(rules, out, ("batch", "seq_sp", "heads", None))

    def body(carry, idx):
        m, l, acc = carry
        kc = jax.lax.dynamic_slice_in_dim(k, idx * chunk, chunk, axis=1)
        vc = jax.lax.dynamic_slice_in_dim(v, idx * chunk, chunk, axis=1)
        kc, vc = _repeat_kv(kc, n_rep), _repeat_kv(vc, n_rep)
        k_pos = idx * chunk + jnp.arange(chunk)
        m, l, acc = _attn_chunk_step(q, kc, vc, k_pos, q_pos, m, l, acc, scale)
        return (m, l, acc), None

    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0), jnp.arange(nc))
    out = acc / jnp.maximum(l, 1e-30).transpose(0, 2, 1)[..., None]
    out = out.astype(q.dtype)
    return constrain(rules, out, ("batch", "seq_sp", "heads", None))


def _triangular_attention(q, k, v, n_rep, scale, chunk, rules):
    """Unrolled q-blocks, each over only its causal KV prefix (½ FLOPs)."""
    B, Sq, H, hd = q.shape
    nq = Sq // chunk
    outs = []
    for i in range(nq):
        qi = jax.lax.slice_in_dim(q, i * chunk, (i + 1) * chunk, axis=1)
        kv_len = (i + 1) * chunk
        ki = _repeat_kv(jax.lax.slice_in_dim(k, 0, kv_len, axis=1), n_rep)
        vi = _repeat_kv(jax.lax.slice_in_dim(v, 0, kv_len, axis=1), n_rep)
        q_pos = i * chunk + jnp.arange(chunk)
        k_pos = jnp.arange(kv_len)
        m0 = jnp.full((B, H, chunk), NEG, jnp.float32)
        l0 = jnp.zeros((B, H, chunk), jnp.float32)
        a0 = jnp.zeros((B, chunk, H, hd), jnp.float32)
        m, l, acc = _attn_chunk_step(qi, ki, vi, k_pos, q_pos, m0, l0, a0, scale)
        outs.append(
            (acc / jnp.maximum(l, 1e-30).transpose(0, 2, 1)[..., None]).astype(q.dtype)
        )
    return jnp.concatenate(outs, axis=1)


# ---------------------------------------------------------------------------
# decode


def scatter_token(stack, new, cache_len, layer_idx):
    """Append tokens per batch row into a layer-stacked cache, each row
    at its *own* length. ``stack`` [L,B,Smax,...]; ``new`` [B,T,...]
    (T=1 for decode, T=K+1 for speculative verify — the slice write
    appends all T rows starting at the row's length); ``cache_len``
    [B]. Under continuous batching the batch rows are slots of
    different requests decoding at divergent positions, so the write
    position is per-row — not the shared ``cache_len[0]`` a fixed batch
    would allow."""
    zero = jnp.int32(0)

    def one(stack_b, new_b, pos):
        start = (layer_idx, pos) + (zero,) * (stack_b.ndim - 2)
        return jax.lax.dynamic_update_slice(stack_b, new_b[None], start)

    return jax.vmap(one, in_axes=(1, 0, 0), out_axes=1)(stack, new, cache_len)


def scatter_token_flat(cache, new, cache_len):
    """Per-row token append for a per-layer (non-stacked) cache:
    ``cache`` [B,Smax,...]; ``new`` [B,T,...]; ``cache_len`` [B]."""
    return jax.vmap(
        lambda cb, nb, pos: jax.lax.dynamic_update_slice_in_dim(cb, nb, pos, axis=0)
    )(cache, new, cache_len)


def gather_block_rows(pool_leaf, block_tables):
    """Dense per-row cache view of a block-paged pool leaf.

    ``pool_leaf`` [L, NB, BS, ...] (NB physical blocks of BS tokens);
    ``block_tables`` [B, MB] int block ids per decode row. Returns
    [L, B, MB·BS, ...] — each row's logical KV sequence gathered through
    its block table. Fixed shape regardless of how many blocks a row
    actually owns (unowned table entries point at the null block and are
    masked off by ``cache_len`` in ``decode_attention``).

    This dense materialization is the *reference/differential* path of
    the paged layout (and the prefix-reuse ``gather_prefix``); the
    serving hot path walks the tables inside the block-paged kernel
    instead (DESIGN.md §4, §3.1)."""
    mb = block_tables.shape[1]
    t = jnp.take(pool_leaf, block_tables, axis=1)  # [L, B, MB, BS, ...]
    return t.reshape(t.shape[:2] + (mb * pool_leaf.shape[2],) + t.shape[4:])


def scatter_block_token(pool_leaf, token_rows, block_ids, offsets):
    """Append one token per decode row into its *tail block* in place.

    ``pool_leaf`` [L, NB, BS, ...]; ``token_rows`` [L, B, ...] (the new
    token's KV rows); ``block_ids``/``offsets`` [B] — per-row physical
    block and in-block position of the write. Rows map to distinct live
    blocks (shared prefix blocks are immutable and never a write
    target), so the scatter is conflict-free; dead rows target the null
    block."""
    return pool_leaf.at[:, block_ids, offsets].set(token_rows)


def scatter_block_tokens(pool_leaf, token_rows, block_ids, offsets):
    """Append T tokens per decode row into their tail blocks in place.

    ``pool_leaf`` [L, NB, BS, ...]; ``token_rows`` [L, B, T, ...] (the
    speculative-verify KV rows); ``block_ids``/``offsets`` [B, T] —
    per-token physical block and in-block position (the T positions may
    span a block boundary; the scheduler pre-claims every tail block
    the verify can reach via ``ensure_tail_n``). Live rows write
    exclusively-owned blocks; dead rows' table entries all point at the
    null block, so their (possibly colliding) writes land in scratch."""
    return pool_leaf.at[:, block_ids, offsets].set(token_rows)


def _dense_as_pool(leaf, bs):
    """A dense per-row cache leaf [B, Smax, ...] viewed as a block pool
    [B·MB, BS, ...] — reshape only, no data movement — so the slot
    layout routes through the same block-paged kernel as the paged one
    (with the identity block table)."""
    B, Smax = leaf.shape[0], leaf.shape[1]
    return leaf.reshape((B * (Smax // bs), bs) + leaf.shape[2:])


def _dense_block_size(smax, bs=256):
    """Largest divisor of ``smax`` that is ≤ ``bs`` — the identity-table
    pool view must tile the dense cache exactly."""
    bs = min(bs, smax)
    while smax % bs:
        bs -= 1
    return bs


def _kernel_cached_attention(q, k_cache, v_cache, cache_len, k_scale, v_scale, backend):
    """Dense-cache decode/verify through the block-paged kernel: the
    [B, Smax] cache is exactly a block pool with an identity table, so
    one kernel serves both KV layouts. ``cache_len`` is the committed
    length — query t attends positions < cache_len + t + 1."""
    B, T, H, hd = q.shape
    Smax = k_cache.shape[1]
    bs = _dense_block_size(Smax)
    if bs < min(8, Smax):
        # a (near-)prime max_seq has no usable tiling: the kernel grid
        # would degrade to up to Smax single-token blocks. A kernel
        # backend never swaps in other numerics for such a shape
        raise ValueError(
            f"attention backend {backend!r}: max_seq={Smax} has no block "
            f"divisor >= 8 (largest is {bs}) for the paged kernel's "
            f"identity-table view of the [B={B}, {Smax}, KV, hd] cache; "
            "choose a max_seq with a divisor >= 8, or backend='reference'"
        )
    tables = jnp.arange(B * (Smax // bs), dtype=jnp.int32).reshape(B, Smax // bs)
    return kernel_ops.paged_attention(
        q,
        _dense_as_pool(k_cache, bs),
        _dense_as_pool(v_cache, bs),
        tables,
        cache_len,
        None if k_scale is None else _dense_as_pool(k_scale, bs),
        None if v_scale is None else _dense_as_pool(v_scale, bs),
        mode=backend,
    )


def paged_attention(
    q, k_pool, v_pool, block_tables, cache_len,
    *, k_scale=None, v_scale=None, rules=None, backend=None,
):
    """Backend-dispatched paged decode/verify attention for one layer.

    q [B,T,H,hd]; pools [NB,BS,KV,hd] (+ per-vector int8 scales);
    ``block_tables`` [B,MB]; ``cache_len`` [B] committed lengths (the
    new token rows are already scattered into the tail blocks; query t
    attends positions < cache_len + t + 1). The kernel backends walk
    the tables directly; the reference backend is the dense
    ``gather_block_rows`` materialization — kept as the differential
    oracle, no longer the serving hot path (DESIGN.md §4)."""
    backend = kernel_ops.resolve_attention_backend(backend)
    if backend != "reference" and rules is None:
        return kernel_ops.paged_attention(
            q, k_pool, v_pool, block_tables, cache_len, k_scale, v_scale,
            mode=backend,
        )
    kd = gather_block_rows(k_pool[None], block_tables)[0]  # [B, MB·BS, KV, hd]
    vd = gather_block_rows(v_pool[None], block_tables)[0]
    if k_scale is not None:
        kd = dequantize_kv(kd, gather_block_rows(k_scale[None], block_tables)[0], q.dtype)
        vd = dequantize_kv(vd, gather_block_rows(v_scale[None], block_tables)[0], q.dtype)
    return _cached_attention(q, kd, vd, cache_len, rules=rules, backend="reference")


def paged_flash_partials(
    q, k_pool, v_pool, block_tables, cache_len, owned,
    *, k_scale=None, v_scale=None, backend=None,
):
    """Per-rank flash running-form partials for kv-sequence-split serving.

    Same inputs as ``paged_attention`` on a LOCAL pool shard, with the
    tables already localized to this rank (unowned entries point at the
    rank's scratch block) and ``owned`` [B, MB] marking which table
    entries this rank's shard actually holds. Returns the unnormalized
    flash triple over owned positions only —

        m   [B, T, H]      running max of the masked logits
        l   [B, T, H]      Σ exp(logit − m)
        acc [B, T, H, hd]  Σ exp(logit − m) · v   (float32)

    — for ``collectives.distributed_softmax`` to combine across the seq
    mesh axis. A rank holding zero valid positions for a row reports the
    NEG sentinel / zero / zeros, which the combine's empty-shard guard
    turns into scale 0 (DESIGN.md §5). Kernel backends run the paged
    kernel's partials mode; the reference path mirrors the
    decode/verify masked softmax with the ownership mask folded in.
    """
    if kernel_ops.resolve_attention_backend(backend) != "reference":
        return kernel_ops.paged_attention_partials(
            q, k_pool, v_pool, block_tables, cache_len, owned,
            k_scale, v_scale, mode=backend,
        )
    kd = gather_block_rows(k_pool[None], block_tables)[0]  # [B, MB·BS, KV, hd]
    vd = gather_block_rows(v_pool[None], block_tables)[0]
    if k_scale is not None:
        kd = dequantize_kv(kd, gather_block_rows(k_scale[None], block_tables)[0], q.dtype)
        vd = dequantize_kv(vd, gather_block_rows(v_scale[None], block_tables)[0], q.dtype)
    B, T, H, hd = q.shape
    S, KV = kd.shape[1], kd.shape[2]
    bs = S // block_tables.shape[1]
    g = H // KV
    scale = 1.0 / math.sqrt(hd)
    qg = q.reshape(B, T, KV, g, hd)
    s = jnp.einsum("btkgd,bskd->bkgts", qg, kd).astype(jnp.float32) * scale
    pos_ok = (
        jnp.arange(S)[None, None, :]
        < (cache_len[:, None] + jnp.arange(T)[None, :] + 1)[:, :, None]
    )  # [B, T, S] — query t attends positions < cache_len + t + 1
    own_ok = jnp.repeat(owned, bs, axis=1)  # [B, MB] → per-position [B, S]
    valid = pos_ok & own_ok[:, None, :]
    s = jnp.where(valid[:, None, None, :, :], s, NEG)
    m = s.max(axis=-1)  # [B, KV, g, T]
    p = jnp.where(valid[:, None, None, :, :], jnp.exp(s - m[..., None]), 0.0)
    l = p.sum(axis=-1)
    acc = jnp.einsum("bkgts,bskd->btkgd", p, vd.astype(jnp.float32))
    m = m.transpose(0, 3, 1, 2).reshape(B, T, H)
    l = l.transpose(0, 3, 1, 2).reshape(B, T, H)
    return m, l, acc.reshape(B, T, H, hd)


def block_write_positions(block_tables, cache_len, t, block_size):
    """Per-row (physical block id, in-block offset), each [B, t], for
    the ``t`` write positions starting at each row's committed length —
    THE table walk every paged decode/verify write goes through (the t
    positions may span block boundaries; dead rows' unowned table
    entries resolve to the null block, so their writes land in
    scratch)."""
    pos = cache_len[:, None] + jnp.arange(t)[None, :]
    bid = jnp.take_along_axis(block_tables, pos // block_size, axis=1)
    return bid, pos % block_size


def verify_attention(q, k_cache, v_cache, cache_len, *, rules=None, backend=None):
    """Multi-token (speculative verify) attention over the decode cache.

    q [B,T,H,hd] are T proposed tokens at absolute positions
    ``cache_len + arange(T)`` (their KV rows already scattered into the
    caches); caches [B,Smax,KV,hd]; cache_len [B] committed lengths.
    Query t attends to cache positions < cache_len + t + 1 — the same
    single-pass masked softmax as ``decode_attention`` with one extra
    *static* query axis, so each query row's reduction runs over the
    identical masked [Smax] series the sequential decode would see. T
    is shape, acceptance is data: one trace serves every acceptance
    pattern at a given speculation depth (DESIGN.md §3.2). Non-reference
    backends route through the block-paged kernel's K+1-query variant
    (identity block table); sharded callers (``rules`` set) stay on the
    reference path — the kernel is not SPMD-partitioned.
    """
    backend = kernel_ops.resolve_attention_backend(backend)
    if backend != "reference" and rules is None:
        return _kernel_cached_attention(
            q, k_cache, v_cache, cache_len, None, None, backend
        )
    B, T, H, hd = q.shape
    Smax, KV = k_cache.shape[1], k_cache.shape[2]
    g = H // KV
    scale = 1.0 / math.sqrt(hd)
    qg = q.reshape(B, T, KV, g, hd)
    s = jnp.einsum("btkgd,bskd->bkgts", qg, k_cache).astype(jnp.float32) * scale
    valid = (
        jnp.arange(Smax)[None, None, :]
        < (cache_len[:, None] + jnp.arange(T)[None, :] + 1)[:, :, None]
    )  # [B, T, Smax]
    s = jnp.where(valid[:, None, None, :, :], s, NEG)
    m = s.max(axis=-1, keepdims=True)
    p = jnp.where(valid[:, None, None, :, :], jnp.exp(s - m), 0.0)
    l = p.sum(axis=-1, keepdims=True)
    out = jnp.einsum(
        "bkgts,bskd->btkgd",
        (p / jnp.maximum(l, 1e-30)).astype(v_cache.dtype),
        v_cache,
    )
    return out.reshape(B, T, H, hd)


def _cached_attention(
    q, k_cache, v_cache, cache_len, *, k_scale=None, v_scale=None,
    rules=None, backend=None,
):
    """Dispatch decode-cache attention on the backend and the (static)
    query count: the reference backend keeps the exact jnp decode/verify
    numerics (dequantizing int8 caches first, as before the registry);
    kernel backends view the dense cache as an identity-table block pool
    and run the paged Pallas kernel — T=1 is plain decode, T>1 the
    speculative verify, int8 scales dequantize in-kernel. Sharded
    callers (``rules`` set) always take the reference path: the kernel
    is not SPMD-partitioned, and silently replicating a seq-sharded
    cache would be worse than the jnp flash-decode semantics the
    reference implements (partial max/sum + all-reduce)."""
    backend = kernel_ops.resolve_attention_backend(backend)
    if backend != "reference" and rules is None:
        return _kernel_cached_attention(
            q, k_cache, v_cache, cache_len, k_scale, v_scale, backend
        )
    if k_scale is not None:
        k_cache = dequantize_kv(k_cache, k_scale, q.dtype)
        v_cache = dequantize_kv(v_cache, v_scale, q.dtype)
    if q.shape[1] == 1:
        return decode_attention(
            q, k_cache, v_cache, cache_len + 1, rules=rules, backend="reference"
        )
    return verify_attention(
        q, k_cache, v_cache, cache_len, rules=rules, backend="reference"
    )


def decode_attention(q, k_cache, v_cache, cache_len, *, rules=None, backend=None):
    """One-token attention over a (possibly seq-sharded) KV cache.

    q [B,1,H,hd]; caches [B,Smax,KV,hd]; cache_len [B] valid lengths
    (positions < cache_len participate). Softmax over the sharded Smax dim
    partitions into partial max/sum + all-reduce (flash-decode semantics).
    Non-reference backends route through the block-paged kernel (identity
    block table; the kernel's committed length is ``cache_len - 1`` since
    its single query attends one position past it); sharded callers
    (``rules`` set) stay on the reference path — the kernel is not
    SPMD-partitioned, and these flash-decode semantics are what the
    seq-sharded dry-run lowers.
    """
    backend = kernel_ops.resolve_attention_backend(backend)
    if backend != "reference" and rules is None:
        return _kernel_cached_attention(
            q, k_cache, v_cache, cache_len - 1, None, None, backend
        )
    B, _, H, hd = q.shape
    Smax, KV = k_cache.shape[1], k_cache.shape[2]
    g = H // KV
    scale = 1.0 / math.sqrt(hd)
    qg = q.reshape(B, KV, g, hd)
    s = jnp.einsum("bkgd,bskd->bkgs", qg, k_cache).astype(jnp.float32) * scale
    valid = jnp.arange(Smax)[None, :] < cache_len[:, None]  # [B, Smax]
    s = jnp.where(valid[:, None, None, :], s, NEG)
    m = s.max(axis=-1, keepdims=True)
    p = jnp.where(valid[:, None, None, :], jnp.exp(s - m), 0.0)
    l = p.sum(axis=-1, keepdims=True)
    out = jnp.einsum("bkgs,bskd->bkgd", (p / jnp.maximum(l, 1e-30)).astype(v_cache.dtype), v_cache)
    return out.reshape(B, 1, H, hd)


# ---------------------------------------------------------------------------
# int8 KV-cache quantization (beyond-paper; EXPERIMENTS.md §Perf #zamba2)


def quantize_kv(x):
    """x [..., hd] → (int8 values, per-vector scale). Exactly invertible
    up to 1/254 relative error; halves decode cache bandwidth vs bf16."""
    scale = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1, keepdims=True)
    scale = jnp.maximum(scale, 1e-6)
    q = jnp.round(x.astype(jnp.float32) / scale * 127.0).astype(jnp.int8)
    return q, scale[..., 0].astype(jnp.bfloat16)


def dequantize_kv(q, scale, dtype):
    return (
        q.astype(jnp.float32) * (scale.astype(jnp.float32) / 127.0)[..., None]
    ).astype(dtype)


# ---------------------------------------------------------------------------
# full block


def attention_block(
    params,
    x,
    *,
    cfg,
    rules=None,
    positions=None,
    cache=None,
    cache_len=None,
    prefix_kv=None,
    backend=None,
    tp_axis=None,
    seq_axis=None,
):
    """Pre-norm'd GQA attention. Returns (out, new_cache_kv).

    Train/prefill: cache is None → causal self-attention, cache returned
    when ``cfg`` asks (prefill writes the cache it produced).
    Suffix prefill: ``prefix_kv`` = (k, v) [B,h,KV,hd] of an already-
    computed (prefix-cache hit) prompt prefix; x is the suffix only and
    attends over prefix + suffix with ``q_offset=h``. Per-query flash
    accumulation is independent of which query rows run, so suffix rows
    come out bitwise-identical to a cold full-prompt prefill.
    Decode: x is [B,1,D]; cache = (k,v) [B,Smax,KV,hd]; cache_len [B].
    Chunked prefill rides the decode-cache path with S=W queries: the
    verify-style per-query mask (``pos < cache_len + t + 1``) is exactly
    the causal mask at a running data offset, so each chunk attends over
    earlier-chunk KV + itself — same math as the ``prefix_kv`` branch,
    with the prefix read from the cache instead of concatenated.
    Paged decode/verify: cache is a dict {k, v[, k_scale, v_scale],
    tables, li} of layer-stacked pool leaves [L,NB,BS,KV,hd] plus the
    per-row block tables — the new token rows scatter into each row's
    tail block and attention walks the tables (DESIGN.md §4).
    ``backend`` picks the decode/verify attention backend (None → the
    ops registry default).
    """
    B, S, D = x.shape
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    if params["wq"].ndim == 2:  # flat-TP layout (attn_flat_tp)
        q = jnp.einsum("bsd,de->bse", x, params["wq"]).reshape(B, S, H, hd)
        k = jnp.einsum("bsd,de->bse", x, params["wk"]).reshape(B, S, KV, hd)
        v = jnp.einsum("bsd,de->bse", x, params["wv"]).reshape(B, S, KV, hd)
    else:
        q = jnp.einsum("bsd,dhk->bshk", x, params["wq"])
        k = jnp.einsum("bsd,dhk->bshk", x, params["wk"])
        v = jnp.einsum("bsd,dhk->bshk", x, params["wv"])
    q = constrain(rules, q, ("batch", "seq_sp", "heads", None))
    k = constrain(rules, k, ("batch", None, "kv_heads", None))
    v = constrain(rules, v, ("batch", None, "kv_heads", None))

    if positions is None:
        positions = jnp.arange(S)[None, :].astype(jnp.int32)
    cos, sin = rope_tables(positions, cfg.head_dim, cfg.rope_theta)
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)

    if cache is None and prefix_kv is not None:
        pk, pv = prefix_kv
        k_full = jnp.concatenate([pk.astype(k.dtype), k], axis=1)
        v_full = jnp.concatenate([pv.astype(v.dtype), v], axis=1)
        out = gqa_attention(
            q,
            k_full,
            v_full,
            q_offset=pk.shape[1],
            chunk=cfg.attn_chunk,
            blocking=cfg.causal_blocking,
            rules=rules,
        )
        new_kv = (k_full, v_full)  # full prefix+suffix KV, cache-fillable
    elif cache is None:
        out = gqa_attention(
            q, k, v, chunk=cfg.attn_chunk, blocking=cfg.causal_blocking, rules=rules
        )
        new_kv = (k, v)
    elif isinstance(cache, dict):
        # block-paged pool (layer-stacked leaves + per-row tables): the
        # new token rows scatter into each row's tail block, then the
        # backend attends through the tables — no dense gather on the
        # kernel backends (DESIGN.md §4). Dead rows' tables point at the
        # null block, so their writes land in scratch.
        tables, li = cache["tables"], cache["li"]
        bs = cache["k"].shape[2]
        T = k.shape[1]
        owned = None
        if seq_axis is not None:
            # kv-sequence split (shard_map body): the pool leaves here are
            # this rank's block-dim shard. Localize the replicated tables
            # — owned entries become local slot ids, unowned entries the
            # rank's scratch slot — so writes land on the owner (scratch
            # elsewhere) and attention knows which positions are real.
            from repro.serve.kv_cache import local_table_view

            tables, owned = local_table_view(
                tables, cache["k"].shape[1], jax.lax.axis_index(seq_axis)
            )
        bid, off = block_write_positions(tables, cache_len, T, bs)
        quant = "k_scale" in cache
        if quant:
            k_q, k_s = quantize_kv(k)
            v_q, v_s = quantize_kv(v)
            writes = (("k", k_q), ("k_scale", k_s), ("v", v_q), ("v_scale", v_s))
        else:
            writes = (("k", k), ("v", v))
        stacks = {
            name: cache[name].at[li, bid, off].set(val.astype(cache[name].dtype))
            for name, val in writes
        }
        leaf = lambda name: jax.lax.dynamic_index_in_dim(
            stacks[name], li, 0, keepdims=False
        )
        if owned is None:
            out = paged_attention(
                q,
                leaf("k"),
                leaf("v"),
                tables,
                cache_len,
                k_scale=leaf("k_scale") if quant else None,
                v_scale=leaf("v_scale") if quant else None,
                rules=rules,
                backend=backend,
            )
        else:
            # each rank attends over its owned positions only; the exact
            # combine (with the empty-shard guard) reassembles the global
            # softmax across the seq mesh axis — rounding-level, not
            # bitwise (DESIGN.md §5)
            m_p, l_p, acc_p = paged_flash_partials(
                q,
                leaf("k"),
                leaf("v"),
                tables,
                cache_len,
                owned,
                k_scale=leaf("k_scale") if quant else None,
                v_scale=leaf("v_scale") if quant else None,
                backend=backend,
            )
            out = distributed_softmax(m_p, l_p, acc_p, seq_axis).astype(q.dtype)
        new_kv = tuple(stacks[name] for name, _ in writes)
    elif len(cache) == 5:
        # int8-quantized stacked cache: (k_all int8, k_scale, v_all int8,
        # v_scale, layer_idx). Reads move half the bytes of bf16.
        k_all, ks_all, v_all, vs_all, li = cache
        k_q, k_s = quantize_kv(k)
        v_q, v_s = quantize_kv(v)
        k_all = scatter_token(k_all, k_q, cache_len, li)
        ks_all = scatter_token(ks_all, k_s, cache_len, li)
        v_all = scatter_token(v_all, v_q, cache_len, li)
        vs_all = scatter_token(vs_all, v_s, cache_len, li)
        take = lambda s: jax.lax.dynamic_index_in_dim(s, li, 0, keepdims=False)
        out = _cached_attention(
            q,
            take(k_all),
            take(v_all),
            cache_len,
            k_scale=take(ks_all),
            v_scale=take(vs_all),
            rules=rules,
            backend=backend,
        )
        new_kv = (k_all, ks_all, v_all, vs_all)
    elif len(cache) == 3:
        # stacked-cache decode: (k_all [L,B,S,KV,hd], v_all, layer_idx).
        # The new token is written in place into the full stack (update =
        # one token, not one layer slice) — the scan carries the stack, so
        # donation aliases it and per-step traffic is O(token), not
        # O(layer cache). See EXPERIMENTS.md §Perf #decode-cache.
        k_all, v_all, li = cache
        k_all = constrain(rules, k_all, (None, "batch", "kv_seq", "kv_heads", None))
        v_all = constrain(rules, v_all, (None, "batch", "kv_seq", "kv_heads", None))
        k_all = scatter_token(k_all, k, cache_len, li)
        v_all = scatter_token(v_all, v, cache_len, li)
        k_cache = jax.lax.dynamic_index_in_dim(k_all, li, 0, keepdims=False)
        v_cache = jax.lax.dynamic_index_in_dim(v_all, li, 0, keepdims=False)
        out = _cached_attention(
            q, k_cache, v_cache, cache_len, rules=rules, backend=backend
        )
        new_kv = (k_all, v_all)
    else:
        k_cache, v_cache = cache
        k_cache = constrain(rules, k_cache, ("batch", "kv_seq", "kv_heads", None))
        v_cache = constrain(rules, v_cache, ("batch", "kv_seq", "kv_heads", None))
        # insert the new token(s) at each row's own cache_len
        k_cache = scatter_token_flat(k_cache, k, cache_len)
        v_cache = scatter_token_flat(v_cache, v, cache_len)
        out = _cached_attention(
            q, k_cache, v_cache, cache_len, rules=rules, backend=backend
        )
        new_kv = (k_cache, v_cache)

    if tp_axis is not None:
        # Head-partitioned serving (shard_map body with a local-view cfg):
        # each rank computed a contiguous head block [r·H_loc, (r+1)·H_loc).
        # Softmax is per-head so the shards are already final — gather them
        # back into global head order and run the FULL (replicated) output
        # projection, which keeps the wo contraction order — and thus the
        # residual stream — bitwise identical to the unsharded step
        # (DESIGN.md §5). kv-sequence splits would instead combine partials
        # via collectives.distributed_softmax before this point.
        out = jax.lax.all_gather(out, tp_axis, axis=2, tiled=True)
    # head count derived from the attention output, not cfg: under tp_axis
    # the gather restores the global head axis while cfg carries local heads
    if params["wo"].ndim == 2:  # flat-TP layout
        o2 = out.astype(x.dtype).reshape(B, out.shape[1], out.shape[2] * hd)
        o2 = constrain(rules, o2, ("batch", "seq_sp", "qdim"))
        out = jnp.einsum("bse,ed->bsd", o2, params["wo"])
    else:
        out = jnp.einsum("bshk,hkd->bsd", out.astype(x.dtype), params["wo"])
    return constrain(rules, out, ("batch", "seq_sp", None)), new_kv
