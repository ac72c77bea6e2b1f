"""Top-k routed expert MLP with expert parallelism over the 'model' axis.

Two execution paths (selected per workload shape, DESIGN.md §6):

dispatch — train/prefill: tokens are sequence-sharded over the full mesh
    (SP), routed locally, exchanged with ``lax.all_to_all`` over 'model'
    (each model shard owns E/16 experts), expert FFN, reverse all-to-all,
    weighted combine. Capacity-based with dropping (static shapes).

dense — decode (token count < mesh size): tokens stay batch-sharded and
    replicated over 'model'; each model shard computes only its local
    experts' masked contribution and a psum over 'model' combines. This is
    the fine-grained/low-occupancy regime — the paper's latency-critical
    case — and the adviser's overlap model prices both paths.

Without a mesh (CPU smoke tests) both paths collapse to a local reference.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.models.layers import constrain, normal


def init_moe(key, cfg, dtype):
    d, f, e = cfg.d_model, cfg.d_ff, cfg.n_experts
    ks = jax.random.split(key, 4)
    s_in, s_out = d**-0.5, f**-0.5
    params = {
        "router": normal(ks[0], (d, e), s_in, jnp.float32),
        "we1": normal(ks[1], (e, d, f), s_in, dtype),
        "we3": normal(ks[2], (e, d, f), s_in, dtype),
        "we2": normal(ks[3], (e, f, d), s_out, dtype),
    }
    # expert weights shard E over 'model' and (fsdp) F over 'data' — the
    # decode path consumes exactly this layout with NO weight gather
    # (EXPERIMENTS.md §Perf hillclimb #dbrx-decode)
    axes = {
        "router": ("embed", None),
        "we1": ("experts", None, "expert_mlp"),
        "we3": ("experts", None, "expert_mlp"),
        "we2": ("experts", "expert_mlp", None),
    }
    return params, axes


def _route(x, router_w, top_k):
    """Returns (gates [T,k] fp32, idx [T,k] int32, aux_loss scalar)."""
    logits = jnp.einsum("td,de->te", x.astype(jnp.float32), router_w)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, idx = jax.lax.top_k(probs, top_k)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    # Switch-style load-balance aux loss: E * Σ_e f_e · p_e
    e = router_w.shape[1]
    me = probs.mean(0)
    ce = jnp.zeros((e,)).at[idx.reshape(-1)].add(1.0) / (idx.size)
    aux = e * jnp.sum(me * ce)
    return gates, idx, aux


def _expert_ffn(xe, we1, we3, we2):
    """xe [..., C, D] × per-expert weights [E, D, F] → [..., C, D]."""
    h = jax.nn.silu(jnp.einsum("...ecd,edf->...ecf", xe, we1))
    h = h * jnp.einsum("...ecd,edf->...ecf", xe, we3)
    return jnp.einsum("...ecf,efd->...ecd", h, we2)


def _dispatch_local(x, gates, idx, n_experts, capacity):
    """Capacity-based dispatch (local view). Returns (buf [E,C,D], lin_idx,
    gate_flat) where lin_idx[t*k+j] addresses buf.reshape(E*C, D) or E*C
    (dropped)."""
    T, D = x.shape
    k = idx.shape[1]
    flat_e = idx.reshape(-1)  # [T*k]
    onehot = (flat_e[:, None] == jnp.arange(n_experts)[None, :]).astype(jnp.int32)
    pos = jnp.cumsum(onehot, axis=0) - 1
    pos_in_e = jnp.sum(onehot * pos, axis=1)  # [T*k]
    keep = pos_in_e < capacity
    lin = jnp.where(keep, flat_e * capacity + pos_in_e, n_experts * capacity)
    tok = jnp.arange(T * k) // k
    buf = jnp.zeros((n_experts * capacity, D), x.dtype)
    buf = buf.at[lin].add(x[tok], mode="drop")
    return buf.reshape(n_experts, capacity, D), lin, gates.reshape(-1)


def _combine_local(y_buf, lin, gate_flat, T, k):
    """Inverse of dispatch: gather expert outputs back per token."""
    D = y_buf.shape[-1]
    flat = y_buf.reshape(-1, D)
    res = jnp.take(flat, jnp.minimum(lin, flat.shape[0] - 1), axis=0)
    res = jnp.where((lin < flat.shape[0])[:, None], res, 0.0)
    out = (gate_flat[:, None].astype(res.dtype) * res).reshape(T, k, D).sum(1)
    return out


def moe_capacity(tokens_local: int, cfg) -> int:
    c = math.ceil(tokens_local * cfg.top_k / cfg.n_experts * cfg.capacity_factor)
    return max(4, int(math.ceil(c / 4) * 4))


# ---------------------------------------------------------------------------
# paths


def _moe_reference(x2d, params, cfg):
    """Single-device reference (also the test oracle)."""
    T, D = x2d.shape
    gates, idx, aux = _route(x2d, params["router"], cfg.top_k)
    C = moe_capacity(T, cfg)
    buf, lin, gf = _dispatch_local(x2d, gates, idx, cfg.n_experts, C)
    y = _expert_ffn(buf, params["we1"], params["we3"], params["we2"])
    return _combine_local(y, lin, gf, T, cfg.top_k), aux


def _moe_dispatch_sharded(x2d, params, cfg, rules):
    """Expert-parallel all-to-all path under shard_map."""
    mesh = rules.mesh
    ep = mesh.shape["model"]
    assert cfg.n_experts % ep == 0, (cfg.n_experts, ep)
    token_axes = rules.table["tokens_ep"]
    T = x2d.shape[0]
    n_shards = math.prod(mesh.shape[a] for a in token_axes)
    T_l = T // n_shards
    C = moe_capacity(T_l, cfg)

    def body(x_loc, router_w, we1, we3, we2):
        # x_loc [T_l, D]; expert weights are the local E/ep slice
        gates, idx, aux = _route(x_loc, router_w, cfg.top_k)
        buf, lin, gf = _dispatch_local(x_loc, gates, idx, cfg.n_experts, C)
        el = cfg.n_experts // ep
        buf = buf.reshape(ep, el, C, -1)
        recv = jax.lax.all_to_all(buf, "model", split_axis=0, concat_axis=0)
        y = _expert_ffn(recv, we1, we3, we2)  # [ep, el, C, D]
        back = jax.lax.all_to_all(y, "model", split_axis=0, concat_axis=0)
        out = _combine_local(
            back.reshape(cfg.n_experts, C, -1), lin, gf, x_loc.shape[0], cfg.top_k
        )
        aux = jax.lax.pmean(aux, token_axes)
        return out, aux

    in_specs = (
        P(token_axes, None),
        P(None, None),
        P("model", None, None),
        P("model", None, None),
        P("model", None, None),
    )
    out_specs = (P(token_axes, None), P())
    fn = shard_map(
        body, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False
    )
    return fn(x2d, params["router"], params["we1"], params["we3"], params["we2"])


# baseline switch (set by launch.dryrun --legacy): the pre-optimization
# dense path demanded F-replicated expert weights, so XLA all-gathered
# the FSDP shards every step (EXPERIMENTS.md §Perf #dbrx-decode)
LEGACY_DENSE = False


def _moe_dense_legacy(x2d, params, cfg, rules):
    """Pre-optimization decode path (kept for baseline measurement)."""
    mesh = rules.mesh
    ep = mesh.shape["model"]
    batch_axes = rules.table["batch"]

    def body(x_loc, router_w, we1, we3, we2):
        el = cfg.n_experts // ep
        my = jax.lax.axis_index("model") * el + jnp.arange(el)
        gates, idx, aux = _route(x_loc, router_w, cfg.top_k)
        g_local = ((idx[:, :, None] == my[None, None, :]) * gates[:, :, None]).sum(1)
        h = jax.nn.silu(jnp.einsum("td,edf->etf", x_loc, we1))
        h = h * jnp.einsum("td,edf->etf", x_loc, we3)
        y = jnp.einsum("etf,efd->etd", h, we2)
        out = jnp.einsum("etd,te->td", y, g_local.astype(y.dtype))
        out = jax.lax.psum(out, "model")
        aux = jax.lax.pmean(aux, batch_axes)
        return out, aux

    in_specs = (
        P(batch_axes, None),
        P(None, None),
        P("model", None, None),  # demands F replicated → per-step gather
        P("model", None, None),
        P("model", None, None),
    )
    out_specs = (P(batch_axes, None), P())
    fn = shard_map(
        body, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False
    )
    return fn(x2d, params["router"], params["we1"], params["we3"], params["we2"])


def _moe_dense_sharded(x2d, params, cfg, rules):
    """Decode path: gather the (tiny) token batch, compute each shard's
    (expert-slice × hidden-slice) partial FFN in place, psum the (tiny)
    output. Weights stay sharded [E/model, D, F/data] — NO weight
    all-gather, unlike the FSDP train layout's default lowering: at
    decode, tokens ≪ weights, so we move tokens to weights (the
    Relic principle — co-locate work with the resident data)."""
    mesh = rules.mesh
    ep = mesh.shape["model"]
    batch_axes = rules.table["batch"]
    n_b = math.prod(mesh.shape[a] for a in batch_axes)
    fsdp_axes = tuple(a for a in ("data",) if rules.cfg.param_sharding == "fsdp")

    def body(x_loc, router_w, we1, we3, we2):
        # x_loc [T_l, D] → all tokens [T, D] (a few hundred KB at decode)
        x_all = jax.lax.all_gather(x_loc, batch_axes, axis=0, tiled=True)
        T = x_all.shape[0]
        el = cfg.n_experts // ep
        my = jax.lax.axis_index("model") * el + jnp.arange(el)
        gates, idx, aux = _route(x_all, router_w, cfg.top_k)
        g_local = (
            (idx[:, :, None] == my[None, None, :]) * gates[:, :, None]
        ).sum(1)
        # we1 [el, D, F_l]: hidden stays F-sharded; we2 [el, F_l, D]
        h = jax.nn.silu(jnp.einsum("td,edf->etf", x_all, we1))
        h = h * jnp.einsum("td,edf->etf", x_all, we3)
        y = jnp.einsum("etf,efd->etd", h, we2)  # partial over F shards
        out = jnp.einsum("etd,te->td", y, g_local.astype(y.dtype))
        out = jax.lax.psum(out, ("model",) + tuple(fsdp_axes))
        # back to the token shard this device owns
        i = jnp.int32(0)
        for a in (batch_axes if isinstance(batch_axes, tuple) else (batch_axes,)):
            i = i * mesh.shape[a] + jax.lax.axis_index(a)
        out = jax.lax.dynamic_slice_in_dim(out, i * (T // n_b), T // n_b, axis=0)
        aux = jax.lax.pmean(aux, batch_axes)
        return out, aux

    f_spec = "data" if rules.cfg.param_sharding == "fsdp" else None
    in_specs = (
        P(batch_axes, None),
        P(None, None),
        P("model", None, f_spec),
        P("model", None, f_spec),
        P("model", f_spec, None),
    )
    out_specs = (P(batch_axes, None), P())
    fn = shard_map(
        body, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False
    )
    return fn(x2d, params["router"], params["we1"], params["we3"], params["we2"])


def moe_block(x, params, cfg, rules=None, path="dispatch"):
    """x [B,S,D] → (y [B,S,D], aux_loss)."""
    B, S, D = x.shape
    x2d = x.reshape(B * S, D)
    if rules is None or rules.mesh is None:
        y, aux = _moe_reference(x2d, params, cfg)
    elif path == "dense":
        impl = _moe_dense_legacy if LEGACY_DENSE else _moe_dense_sharded
        y, aux = impl(x2d, params, cfg, rules)
    else:
        y, aux = _moe_dispatch_sharded(x2d, params, cfg, rules)
    return y.reshape(B, S, D).astype(x.dtype), aux
