"""Compute/communication co-scheduling: ring-overlapped TP collectives.

The paper's Relic pairs a memory-bound stream with a compute-bound stream
on one SMT core. At cluster scale the analogous idle-resource pair is
ICI (collective) vs MXU (compute): a blocking all-gather before a TP
matmul leaves the MXU idle exactly like a cache miss leaves CPU ports
idle. These ring schedules interleave one ``ppermute`` hop with one
partial matmul per step, so in the compiled HLO the collective-permute
overlaps the dot — the beyond-paper optimization recorded in
EXPERIMENTS.md §Perf.

All functions are *local views* meant to run inside ``shard_map``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import PartitionSpec as P


def _ring_perm(p):
    return [(j, (j + 1) % p) for j in range(p)]


def ring_allgather_matmul(x_loc, w_loc, axis_name: str):
    """y_global = all_gather(x, seq) @ w_loc, one ring hop per chunk.

    x_loc [T_l, D] (sequence-sharded), w_loc [D, F_l] → y [P·T_l, F_l].
    Each step multiplies the chunk currently held while the next chunk is
    in flight (the DMA/MXU pair at ICI scale).
    """
    p = lax.axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    t_l = x_loc.shape[0]
    acc = jnp.zeros((p * t_l, w_loc.shape[1]), x_loc.dtype)

    # unrolled python loop: lets XLA schedule permute i+1 against dot i.
    # after i ring hops (j → j+1) device idx holds chunk (idx - i) % p.
    x_cur = x_loc
    for i in range(p):
        if i != p - 1:
            x_nxt = lax.ppermute(x_cur, axis_name, _ring_perm(p))  # comm stream
        part = jnp.dot(x_cur, w_loc)  # compute stream
        src = (idx - i) % p
        acc = lax.dynamic_update_slice(acc, part.astype(acc.dtype), (src * t_l, 0))
        if i != p - 1:
            x_cur = x_nxt
    return acc


def matmul_reducescatter(h_loc, w_loc, axis_name: str):
    """y_loc = reduce_scatter(h_global_chunks @ w_loc) over `axis_name`.

    h_loc [T, F_l] (full sequence, hidden-sharded), w_loc [F_l, D] →
    y [T/P, D]: each step computes the partial for one peer's sequence
    chunk and passes the accumulating partial around the ring.
    """
    p = lax.axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    t = h_loc.shape[0]
    t_l = t // p
    d = w_loc.shape[1]

    acc = jnp.zeros((t_l, d), jnp.float32)
    for i in range(p):
        # at step i every device contributes its partial for the chunk
        # that will land on its owner after the remaining p-1-i hops
        src = (idx + p - 1 - i) % p
        chunk = lax.dynamic_slice(h_loc, (src * t_l, 0), (t_l, h_loc.shape[1]))
        part = jnp.dot(chunk, w_loc, preferred_element_type=jnp.float32)
        acc = acc + part
        if i != p - 1:
            acc = lax.ppermute(acc, axis_name, _ring_perm(p))
    return acc.astype(h_loc.dtype)


def distributed_softmax(m_loc, l_loc, acc_loc, axis_name: str):
    """Combine per-rank flash-decode partials over ``axis_name``.

    Each rank holds a partial softmax over its local slice of the KV
    sequence for the SAME query/head set, in the usual flash-attention
    running form:

        m_loc   [...]      local running max of the logits
        l_loc   [...]      local sum of exp(logit - m_loc)
        acc_loc [..., d]   local sum of exp(logit - m_loc) · v

    The exact global softmax follows from rescaling each rank's partial
    to the global max m = max_r m_r:

        l   = Σ_r l_r · exp(m_r − m)
        acc = Σ_r acc_r · exp(m_r − m)
        out = acc / l

    because exp(logit − m) = exp(logit − m_r) · exp(m_r − m) for every
    logit that rank r saw. Returns the combined ``out [..., d]``.

    This is the kv-sequence-split combine (``ShardingRules`` 'kv_seq',
    DESIGN.md §5): it runs on the serving hot path whenever the paged
    pool is partitioned over a ``"seq"`` mesh axis. The head-partitioned
    path never calls it — softmax is per-head, so a head shard completes
    its softmax locally.

    Empty shards: a rank whose slice holds zero valid keys carries
    ``m_loc = -inf`` (or the ``-1e30`` mask sentinel the masked-softmax
    paths use) with ``l_loc = 0``. ``exp(m_loc - m)`` would be
    ``exp(-inf - -inf) = NaN`` when every rank is empty, and even a
    single empty rank must not poison the psum — so ``scale`` is forced
    to exactly 0 on empty shards, and the all-ranks-empty case returns
    exact zeros (0-acc over the tiny-clamped denominator), never NaN.
    """
    empty = m_loc <= jnp.asarray(-1e30, m_loc.dtype)  # -inf or mask sentinel
    m = lax.pmax(m_loc, axis_name)
    scale = jnp.where(empty, 0.0, jnp.exp(m_loc - m))
    l = lax.psum(l_loc * scale, axis_name)
    acc = lax.psum(acc_loc * scale[..., None], axis_name)
    return acc / jnp.maximum(l, jnp.finfo(acc.dtype).tiny)[..., None]


def sp_swiglu(x, w1, w3, w2, rules):
    """Sequence-parallel SwiGLU with ring-overlapped TP collectives.

    x [B, S, D] with S sharded over 'model'; w1/w3 [D, F], w2 [F, D] with
    F sharded over 'model'. Equivalent to swiglu() but the all-gather of
    x and the reduce-scatter of the output are software-pipelined against
    the matmuls.
    """
    mesh = rules.mesh
    batch_axes = rules.table["batch"]

    def body(x_loc, w1_loc, w3_loc, w2_loc):
        b, s_l, d = x_loc.shape
        x2 = x_loc.reshape(b * s_l, d)
        h1 = ring_allgather_matmul(x2, w1_loc, "model")  # [B·S, F_l]
        h3 = ring_allgather_matmul(x2, w3_loc, "model")
        h = jax.nn.silu(h1) * h3
        y = matmul_reducescatter(h, w2_loc, "model")  # [B·S/P, D]
        s_out = s_l  # P·s_l / P
        return y.reshape(b, s_out, d)

    fn = shard_map(
        body,
        mesh=mesh,
        in_specs=(
            P(batch_axes, "model", None),
            P(None, "model"),
            P(None, "model"),
            P("model", None),
        ),
        out_specs=P(batch_axes, "model", None),
        check_vma=False,
    )
    return fn(x, w1, w3, w2)
