"""Block-paged flash-decode/verify: attention straight off the block pool.

The serving layer's paged decode used to materialize a dense
``[B, MB·BS]`` copy of every row's KV through ``gather_block_rows``
before attending — a per-step bandwidth tax proportional to the pool's
*capacity*, not its contents. This kernel walks each row's block table
instead: the index_map reads the table (scalar-prefetched into SMEM)
and DMAs KV blocks directly from the paged pool, so the "memory
thread" streams exactly the blocks the row owns while the "compute
thread" runs the running-max softmax in VMEM — the same SMT-pair
co-scheduling as ``decode_attention``, now addressed through pages.

Grid ``(B, MB)``: the sequential block-table axis is innermost. Each
grid step DMAs one whole pool block ``[BS, KV, hd]`` — all kv groups at
once, so the block's trailing dims are the pool's own and the TPU
compiler accepts the tiling — and loops over the kv groups in the body;
the T·g query rows of each kv group ride in the sublane dim. T is
static — T=1 is plain decode, T=K+1 the speculative verify (query t
attends positions < len + t + 1, so the masked reduction per query is
bitwise the one the sequential decode would run: blocks wholly past a
query's window contribute exp-weights of exactly zero and a
correction factor of exactly one). Unowned table entries point at the
pool's null block, whose data is masked off by ``lengths`` — every
table entry is therefore always a safe DMA source. int8-KV pools
dequantize in-kernel (per-vector scales ride in their own prefetched
blocks), halving the streamed bytes vs a dense bf16 gather.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG = -1e30


def _paged_kernel(
    tbl_ref,  # [B, MB] int32 (scalar prefetch)
    len_ref,  # [B] int32 (scalar prefetch)
    q_ref,  # [1, KV, T·g, hd]
    k_ref,  # [1, BS, KV, hd] — one pool block, every kv group
    v_ref,
    o_ref,  # [1, KV, T·g, hd]
    m_ref,  # [KV, T·g, 1] f32 scratch
    l_ref,
    acc_ref,  # [KV, T·g, hd] f32 scratch
    *,
    scale,
    bs,
    t,
    g,
    kv,
    ks_ref=None,  # [1, BS, KV] per-vector scales (int8 pools)
    vs_ref=None,
    own_ref=None,  # [B, MB] int32 ownership (scalar prefetch, seq split)
    om_ref=None,  # [1, KV, T·g, 1] partials outputs (seq split)
    ol_ref=None,
):
    b = pl.program_id(0)
    mb = pl.program_id(1)

    @pl.when(mb == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    base = len_ref[b]  # committed length; query t sees pos < base + t + 1
    # skip blocks wholly past the last query's window ("memory thread"
    # stops streaming dead data — Relic's early task retire). Under the
    # kv-sequence split, blocks this rank's shard does not own are
    # skipped the same way — ownership is block-granular, so the mask
    # needs no per-position term
    live = mb * bs < base + t
    if own_ref is not None:
        live = jnp.logical_and(live, own_ref[b, mb] != 0)

    @pl.when(live)
    def _step():
        shape = (t * g, bs)
        pos = mb * bs + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
        tq = jax.lax.broadcasted_iota(jnp.int32, shape, 0) // g
        mask = pos < base + tq + 1
        # the block holds every kv group (its trailing (KV, hd) dims are
        # the pool's own, which is what the TPU tiling rule accepts);
        # the groups are a short static loop over the one DMA'd block
        for h in range(kv):
            q = q_ref[0, h]  # [T·g, hd]
            k = k_ref[0, :, h, :]  # [BS, hd]
            v = v_ref[0, :, h, :]
            if ks_ref is not None:
                # dequantize in-kernel to the activation dtype, as the
                # reference does: int8 · scale/127
                k = (k.astype(jnp.float32) * (
                    ks_ref[0, :, h : h + 1].astype(jnp.float32) / 127.0
                )).astype(q.dtype)
                v = (v.astype(jnp.float32) * (
                    vs_ref[0, :, h : h + 1].astype(jnp.float32) / 127.0
                )).astype(q.dtype)
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale  # [T·g, BS]
            s = jnp.where(mask, s, NEG)
            m_prev = m_ref[h]
            m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
            p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
            corr = jnp.exp(m_prev - m_new)
            l_ref[h] = l_ref[h] * corr + p.sum(axis=1, keepdims=True)
            acc_ref[h] = acc_ref[h] * corr + jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            m_ref[h] = m_new

    @pl.when(mb == pl.num_programs(1) - 1)
    def _flush():
        if om_ref is None:
            o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(
                o_ref.dtype
            )
        else:
            # partials mode: emit the unnormalized flash triple — the
            # cross-rank distributed_softmax combine normalizes. An
            # all-skipped shard flushes (NEG, 0, 0), which the combine's
            # empty-shard guard scales to exactly zero
            o_ref[0] = acc_ref[...]
            om_ref[0] = m_ref[...]
            ol_ref[0] = l_ref[...]


def paged_decode_attention(
    q: jax.Array,
    k_pool: jax.Array,
    v_pool: jax.Array,
    block_tables: jax.Array,
    lengths: jax.Array,
    *,
    k_scale: jax.Array | None = None,
    v_scale: jax.Array | None = None,
    owned: jax.Array | None = None,
    partials: bool = False,
    interpret: bool = False,
):
    """q [B,T,H,hd]; pools [NB,BS,KV,hd]; tables [B,MB] int32 block ids;
    lengths [B] committed lengths (query t valid positions are
    < lengths + t + 1) → out [B,T,H,hd]. int8 pools pass per-vector
    ``k_scale``/``v_scale`` [NB,BS,KV] and dequantize in-kernel.

    kv-sequence split (DESIGN.md §5): ``owned`` [B, MB] marks the table
    entries whose blocks live in this rank's pool shard (unowned entries
    must already point at a safe local scratch slot — they are skipped,
    never streamed into the softmax). ``partials=True`` returns the
    unnormalized flash triple ``(m [B,T,H], l [B,T,H], acc [B,T,H,hd]
    float32)`` instead of the normalized output, for the cross-rank
    ``distributed_softmax`` combine."""
    B, T, H, hd = q.shape
    NB, BS, KV = k_pool.shape[0], k_pool.shape[1], k_pool.shape[2]
    MB = block_tables.shape[1]
    g = H // KV
    scale = 1.0 / math.sqrt(hd)
    quant = k_scale is not None

    # the g query heads of each kv group — and the T verify queries —
    # ride together in the sublane dim: [B, KV, T·g, hd]
    qr = q.reshape(B, T, KV, g, hd).transpose(0, 2, 1, 3, 4).reshape(B, KV, T * g, hd)

    grid = (B, MB)
    # every block's trailing two dims equal the array's own, which is
    # what Mosaic's tiling rule asks of a block narrower than (8, 128).
    # Index maps take *pref so one lambda serves both prefetch layouts
    # (tbl, lens) and (tbl, lens, owned)
    kv_spec = pl.BlockSpec(
        (1, BS, KV, hd), lambda b, mb, *pref: (pref[0][b, mb], 0, 0, 0)
    )
    q_spec = pl.BlockSpec((1, KV, T * g, hd), lambda b, mb, *pref: (b, 0, 0, 0))
    in_specs = [q_spec, kv_spec, kv_spec]
    operands = [qr, k_pool, v_pool]
    if quant:
        sc_spec = pl.BlockSpec(
            (1, BS, KV), lambda b, mb, *pref: (pref[0][b, mb], 0, 0)
        )
        in_specs += [sc_spec, sc_spec]
        operands += [k_scale, v_scale]

    prefetch = [block_tables.astype(jnp.int32), lengths.astype(jnp.int32)]
    if owned is not None:
        prefetch.append(owned.astype(jnp.int32))

    if partials:
        ml_spec = pl.BlockSpec((1, KV, T * g, 1), lambda b, mb, *pref: (b, 0, 0, 0))
        out_specs = (q_spec, ml_spec, ml_spec)
        out_shape = (
            jax.ShapeDtypeStruct((B, KV, T * g, hd), jnp.float32),  # acc
            jax.ShapeDtypeStruct((B, KV, T * g, 1), jnp.float32),  # m
            jax.ShapeDtypeStruct((B, KV, T * g, 1), jnp.float32),  # l
        )
    else:
        out_specs = q_spec
        out_shape = jax.ShapeDtypeStruct((B, KV, T * g, hd), q.dtype)

    n_pref = len(prefetch)
    n_in = len(operands)
    n_out = 3 if partials else 1

    def kernel(*refs):
        tbl, lens = refs[0], refs[1]
        own = refs[2] if owned is not None else None
        i = n_pref
        qf, kf, vf = refs[i : i + 3]
        i += 3
        ksf, vsf = (refs[i], refs[i + 1]) if quant else (None, None)
        i = n_pref + n_in
        of = refs[i]
        omf, olf = (refs[i + 1], refs[i + 2]) if partials else (None, None)
        mf, lf, accf = refs[i + n_out : i + n_out + 3]
        return _paged_kernel(
            tbl, lens, qf, kf, vf, of, mf, lf, accf,
            scale=scale, bs=BS, t=T, g=g, kv=KV, ks_ref=ksf, vs_ref=vsf,
            own_ref=own, om_ref=omf, ol_ref=olf,
        )

    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=n_pref,
            grid=grid,
            in_specs=in_specs,
            out_specs=out_specs,
            scratch_shapes=[
                pltpu.VMEM((KV, T * g, 1), jnp.float32),
                pltpu.VMEM((KV, T * g, 1), jnp.float32),
                pltpu.VMEM((KV, T * g, hd), jnp.float32),
            ],
        ),
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        interpret=interpret,
    )(*prefetch, *operands)

    def heads_out(x, d):
        return x.reshape(B, KV, T, g, d).transpose(0, 2, 1, 3, 4).reshape(B, T, H, d)

    if not partials:
        return heads_out(out, hd)
    acc, m, l = out
    return (
        heads_out(m, 1).reshape(B, T, H),
        heads_out(l, 1).reshape(B, T, H),
        heads_out(acc, hd),
    )
