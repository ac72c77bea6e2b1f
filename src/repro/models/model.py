"""Unified model: config → init / loss / prefill / decode for all families.

Layer stacks are scanned (``lax.scan`` over stacked [L, ...] parameters) so
the HLO stays compact at 48+ layers; remat policy wraps the scan body.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.kernels import ops as kernel_ops
from repro.models import attention as attn
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models.layers import (
    chunked_softmax_xent,
    constrain,
    embed_tokens,
    init_mlp,
    normal,
    rms_norm,
    swiglu,
)
from repro.parallel import sharding as sharding_mod
from repro.parallel.sharding import ShardingRules

ATTN_FAMILIES = ("dense", "moe", "audio", "vlm")

# Families whose suffix prefill (prefill_with_prefix) is bitwise-identical
# to a cold prefill, so KV prefix reuse cannot change tokens. MoE is out
# (dispatch capacity depends on tokens-per-call, so suffix routing can
# drop different tokens), VLM is out (patch embeddings occupy cache rows
# that are not token-addressable). int8-KV rides along, but only
# *approximately*: the suffix path attends over DEQUANTIZED prefix K/V
# (≤1/254 relative error vs the fp rows the cold run saw), so deeper-
# layer suffix K/V and the first-token logits carry a quantization-level
# perturbation — greedy tokens agree unless an argmax near-tie flips
# (the differential test pins the tracked config; subsequent decode
# steps read the same quantized pool either way).
PREFIX_FAMILIES = ("dense", "audio")

# Families the speculative verify_step supports: the KV cache must be
# rewindable (truncating `len` un-commits rejected draft entries). SSM
# and hybrid are out — recurrent state cannot be truncated — and MoE is
# out because dispatch capacity depends on tokens-per-call, so a K+1
# token verify could route (and drop) differently than the sequential
# decode it must reproduce token-for-token.
SPEC_FAMILIES = ("dense", "audio", "vlm")

# Families whose prefill may be right-padded to a bucketed shape without
# changing tokens: position-addressable KV caches ignore pad rows (pad
# keys sit at positions strictly after every real query, so the causal
# mask removes them; pad KV rows past ``len`` are masked off and
# overwritten by later writes). SSM/hybrid are out — recurrent state
# integrates every position, pads included — and MoE is out because
# dispatch capacity depends on tokens-per-call, so padding changes which
# tokens get dropped.
PAD_PREFILL_FAMILIES = ("dense", "audio", "vlm")

# Families the chunked (incremental) prefill supports: one
# ``prefill_chunk`` call per ``chunk_size``-token slice of the prompt,
# riding the verify_step machinery (per-query causal masking at a data
# offset). Same exclusions as PREFIX_FAMILIES — chunk c>1 queries attend
# over cached earlier-chunk KV exactly like a suffix prefill over a
# prefix hit — plus VLM (patch embeddings are not token-chunkable).
CHUNKED_PREFILL_FAMILIES = ("dense", "audio")


def prefill_bucket(n: int, cap: Optional[int] = None) -> int:
    """Smallest power of two ≥ ``n`` (≥ 1), clamped to ``cap``.

    The prefill trace family: padding prompts (and prefill chunks) up to
    pow2 buckets means mixed-length open-loop workloads compile one
    prefill executable per bucket, not one per distinct length."""
    w = 1 << max(0, int(n) - 1).bit_length()
    return min(w, cap) if cap is not None else w

# baseline switch (launch.dryrun --legacy): pre-optimization decode scan
# slices the cache per layer via xs/ys, which writes a full layer-cache
# slice back per step (EXPERIMENTS.md §Perf #decode-cache)
LEGACY_CACHE_SCAN = False


def _dtype(cfg):
    return jnp.dtype(cfg.dtype)


class Model:
    """Functional model wrapper: all methods are pure and jit-friendly."""

    def __init__(self, cfg: ModelConfig, mesh=None, tp_axis=None, seq_axis=None):
        self.cfg = cfg
        self.mesh = mesh
        self.rules = ShardingRules(mesh, cfg) if mesh is not None else None
        # serving tensor parallelism: set on the LOCAL-view model built by
        # ``sharded_paged_step`` (cfg carries per-rank head counts, rules is
        # None so the kernel backend engages per-shard); attention gathers
        # head shards over this shard_map axis before the output projection
        self.tp_axis = tp_axis
        # serving kv-sequence split: also set by ``sharded_paged_step``.
        # Each rank holds a contiguous block-dim shard of the paged pool;
        # attention localizes the replicated block tables, computes flash
        # partials over owned positions only, and combines them with
        # collectives.distributed_softmax over this shard_map axis
        self.seq_axis = seq_axis

    # ------------------------------------------------------------------
    # parameters
    def init(self, key):
        cfg, dt = self.cfg, _dtype(self.cfg)
        keys = jax.random.split(key, cfg.num_layers + 8)
        V, D = cfg.padded_vocab, cfg.d_model
        params: dict[str, Any] = {
            "embed": normal(keys[0], (V, D), D**-0.5, dt),
            "final_norm": jnp.ones((D,), dt),
        }
        axes: dict[str, Any] = {
            "embed": ("vocab", "embed"),
            "final_norm": ("embed",),
        }
        if not cfg.tie_embeddings:
            params["lm_head"] = normal(keys[1], (D, V), D**-0.5, dt)
            axes["lm_head"] = ("embed", "vocab")
        if cfg.family == "vlm":
            params["patch_proj"] = normal(keys[2], (D, D), D**-0.5, dt)
            axes["patch_proj"] = (None, "embed")

        lp, la = [], None
        for i in range(cfg.num_layers):
            p, a = self._init_layer(keys[3 + i], i)
            lp.append(p)
            la = a
        params["layers"] = jax.tree.map(lambda *xs: jnp.stack(xs), *lp)
        axes["layers"] = jax.tree.map(
            lambda ax: ("layers",) + ax, la, is_leaf=lambda x: isinstance(x, tuple)
        )
        if cfg.family == "hybrid":
            p, a = self._init_shared_block(keys[2])
            params["shared"], axes["shared"] = p, a
        return params, axes

    def _init_layer(self, key, i):
        cfg, dt = self.cfg, _dtype(self.cfg)
        k1, k2, k3 = jax.random.split(key, 3)
        if cfg.family in ("ssm", "hybrid"):
            p, a = ssm_mod.init_ssm(k1, cfg, dt)
            return (
                {"ssm": p, "norm": jnp.ones((cfg.d_model,), dt)},
                {"ssm": a, "norm": ("embed",)},
            )
        ap, aa = attn.init_attention(k1, cfg, dt)
        p = {
            "attn": ap,
            "attn_norm": jnp.ones((cfg.d_model,), dt),
            "mlp_norm": jnp.ones((cfg.d_model,), dt),
        }
        a = {"attn": aa, "attn_norm": ("embed",), "mlp_norm": ("embed",)}
        if cfg.family == "moe":
            p["moe"], a["moe"] = moe_mod.init_moe(k2, cfg, dt)
        else:
            p["mlp"], a["mlp"] = init_mlp(k2, cfg.d_model, cfg.d_ff, dt)
        return p, a

    def _init_shared_block(self, key):
        cfg, dt = self.cfg, _dtype(self.cfg)
        k1, k2 = jax.random.split(key)
        ap, aa = attn.init_attention(k1, cfg, dt)
        mp, ma = init_mlp(k2, cfg.d_model, cfg.d_ff, dt)
        p = {
            "attn": ap,
            "mlp": mp,
            "attn_norm": jnp.ones((cfg.d_model,), dt),
            "mlp_norm": jnp.ones((cfg.d_model,), dt),
        }
        a = {"attn": aa, "mlp": ma, "attn_norm": ("embed",), "mlp_norm": ("embed",)}
        return p, a

    def abstract_params(self):
        """(ShapeDtypeStruct tree, logical-axes tree) without allocation."""
        box = {}

        def f(k):
            p, a = self.init(k)
            box["axes"] = a
            return p

        shapes = jax.eval_shape(f, jax.random.key(0))
        return shapes, box["axes"]

    # ------------------------------------------------------------------
    # shared layer bodies
    def _dense_layer(
        self, x, lp, path, positions=None, cache=None, cache_len=None,
        prefix_kv=None, backend=None,
    ):
        cfg, rules = self.cfg, self.rules
        h, new_kv = attn.attention_block(
            lp["attn"],
            rms_norm(x, lp["attn_norm"], cfg.norm_eps),
            cfg=cfg,
            rules=rules,
            positions=positions,
            cache=cache,
            cache_len=cache_len,
            prefix_kv=prefix_kv,
            backend=backend,
            tp_axis=self.tp_axis,
            seq_axis=self.seq_axis,
        )
        x = x + h
        hin = rms_norm(x, lp["mlp_norm"], cfg.norm_eps)
        if cfg.family == "moe":
            h, aux = moe_mod.moe_block(hin, lp["moe"], cfg, rules, path=path)
        else:
            h, aux = swiglu(hin, lp["mlp"]["w1"], lp["mlp"]["w3"], lp["mlp"]["w2"], rules), 0.0
        return x + h, aux, new_kv

    def _ssm_layer(self, x, lp, state=None, want_state=False):
        cfg, rules = self.cfg, self.rules
        h, new_state = ssm_mod.ssm_block(
            lp["ssm"],
            rms_norm(x, lp["norm"], cfg.norm_eps),
            cfg,
            rules,
            state=state,
            want_state=want_state,
        )
        return x + h, new_state

    # ------------------------------------------------------------------
    # forward (train / prefill)
    def forward(self, params, tokens, patch_embeds=None, want_cache=False):
        """tokens [B,S'] → final hidden [B,S,D] (+ per-layer KV if asked)."""
        cfg, rules = self.cfg, self.rules
        x = embed_tokens(params["embed"], tokens, rules)
        if cfg.family == "vlm":
            pe = jnp.einsum("bpd,de->bpe", patch_embeds.astype(x.dtype), params["patch_proj"])
            x = jnp.concatenate([pe, x], axis=1)
        x = constrain(rules, x, ("batch", "seq", None))

        if cfg.family in ATTN_FAMILIES:
            x, aux, caches = self._forward_attn_stack(params, x, want_cache)
        elif cfg.family == "ssm":
            x, caches = self._forward_ssm_stack(params, x, want_cache)
            aux = 0.0
        elif cfg.family == "hybrid":
            x, caches = self._forward_hybrid_stack(params, x, want_cache)
            aux = 0.0
        else:
            raise ValueError(cfg.family)
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        return x, aux, caches

    def _maybe_remat(self, fn):
        if self.cfg.remat == "none":
            return fn
        if self.cfg.remat == "full":
            policy = jax.checkpoint_policies.nothing_saveable
        else:
            policy = jax.checkpoint_policies.dots_with_no_batch_dims_saveable
        return jax.checkpoint(fn, policy=policy)

    def _forward_attn_stack(self, params, x, want_cache):
        path = "dispatch"

        def body(carry, lp):
            x, aux = carry
            x, a, kv = self._dense_layer(x, lp, path)
            ys = kv if want_cache else None
            return (x, aux + a), ys

        (x, aux), caches = jax.lax.scan(
            self._maybe_remat(body), (x, 0.0), params["layers"]
        )
        return x, aux, caches

    def _forward_ssm_stack(self, params, x, want_state=False):
        def body(x, lp):
            x, st = self._ssm_layer(x, lp, want_state=want_state)
            return x, st

        x, sts = jax.lax.scan(self._maybe_remat(body), x, params["layers"])
        return x, sts

    def _hybrid_grouped_params(self, params):
        cfg = self.cfg
        G = cfg.num_layers // cfg.attn_every
        return jax.tree.map(
            lambda p: p.reshape((G, cfg.attn_every) + p.shape[1:]), params["layers"]
        )

    def _forward_hybrid_stack(self, params, x, want_cache):
        cfg = self.cfg
        shared = params["shared"]

        def group(carry, glp):
            x = carry

            def inner(x, lp):
                x, st = self._ssm_layer(x, lp, want_state=want_cache)
                return x, st

            x, sts = jax.lax.scan(inner, x, glp)
            h, kv = attn.attention_block(
                shared["attn"],
                rms_norm(x, shared["attn_norm"], cfg.norm_eps),
                cfg=cfg,
                rules=self.rules,
            )
            x = x + h
            x = x + swiglu(
                rms_norm(x, shared["mlp_norm"], cfg.norm_eps),
                shared["mlp"]["w1"],
                shared["mlp"]["w3"],
                shared["mlp"]["w2"],
                self.rules,
            )
            return x, ((kv, sts) if want_cache else None)

        x, caches = jax.lax.scan(
            self._maybe_remat(group), x, self._hybrid_grouped_params(params)
        )
        return x, caches

    # ------------------------------------------------------------------
    # losses / steps
    def loss(self, params, batch):
        """batch: tokens [B,S], labels [B,S], mask [B,S] (+patch_embeds)."""
        cfg = self.cfg
        x, aux, _ = self.forward(
            params, batch["tokens"], patch_embeds=batch.get("patch_embeds")
        )
        if cfg.family == "vlm":
            # hidden includes prepended patches; they predict nothing
            n = cfg.n_frontend_tokens
            x = x[:, n:, :]
        head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
        ce, cnt = chunked_softmax_xent(
            x, head, batch["labels"], batch["mask"], rules=self.rules
        )
        loss = ce + 0.01 * aux
        return loss, {"ce": ce, "aux": aux, "tokens": cnt}

    # ------------------------------------------------------------------
    # decode
    def jit_step(self, name: str, backend=None, donate=True):
        """``jax.jit`` of one decode-family step (``decode_step``,
        ``verify_step``, ``decode_step_paged``, ``verify_step_paged``)
        with the attention backend resolved and bound STATICALLY.
        A paged step (``*_paged``) takes the pool as argument 1 and
        hands back the pool with the new tokens written in: with
        ``donate`` the program writes into the input buffer in place,
        so the layer scan's carry is the pool itself and no second pool
        is ever live.
        The single place backend binding happens (engine, scheduler,
        and draft streams all build their step fns here), so the
        registry is consulted before tracing — a later registry change
        can never silently retarget an existing trace (DESIGN.md §4)."""
        backend = kernel_ops.resolve_attention_backend(backend)
        step = getattr(self, name)

        def run(*args, **kw):
            return step(*args, backend=backend, **kw)

        # the program's name in a profile: ``jit_serve_<name>``, not the
        # ``jit__unknown`` a functools.partial would give
        run.__name__ = f"serve_{name}"
        paged = donate and name.endswith("_paged")
        return jax.jit(run, donate_argnums=(1,) if paged else ())

    def paged_pool_specs(self, axis="model", seq_axis=None):
        """PartitionSpecs for the block-pool leaves under a serving mesh
        (DESIGN.md §5): KV (and scale) leaves shard on the kv-head axis
        over ``axis`` (head TP) and/or on the block dim over ``seq_axis``
        (kv-sequence split); the layer/offset axes are physical storage
        walked identically by every rank. Block tables and lengths are
        data, not pool leaves — they stay replicated (seq ranks localize
        them in-body). Delegates to ``parallel.sharding.paged_pool_specs``,
        where the seq rule lives."""
        return sharding_mod.paged_pool_specs(
            axis, seq_axis, quantized=bool(self.cfg.kv_quant)
        )

    def sharded_paged_step(
        self, name: str, mesh, backend=None, axis="model", seq_axis="seq", donate=True
    ):
        """``jit_step`` counterpart for mesh-sharded paged serving:
        ``jit(shard_map(...))`` of ``decode_step_paged`` /
        ``verify_step_paged`` over a 1D or 2D serving mesh. Everything
        but the pool (params, block tables, lengths, tokens, logits)
        stays replicated.

        Head split (mesh axis ``axis``, PR 7 — bitwise): each rank
        slices its contiguous head block out of the replicated q/k/v
        projections (rank r owns q heads [r·H/P, (r+1)·H/P) and the
        matching kv groups — GQA groups never straddle ranks) and runs
        the UNSHARDED step body through a local-view model whose cfg
        carries the per-rank head counts. Head shards are gathered back
        before the (replicated) output projection inside
        ``attention_block`` — no cross-rank float reduction, so the
        logits are bitwise single-device.

        Sequence split (mesh axis ``seq_axis`` — rounding-level): the
        pool's block dim is partitioned so each rank owns a contiguous
        range of physical blocks (``serve/kv_cache.py`` lays slots out
        with one scratch block per shard). Attention localizes the
        replicated block tables in-body (unowned entries → the rank's
        scratch slot), computes flash running-form partials (m, l, acc)
        over owned positions only, and combines them with
        ``collectives.distributed_softmax`` over ``seq_axis`` — a
        cross-rank float reduction, hence tokens match single-device to
        rounding, not bitwise (the tolerance differential lane,
        DESIGN.md §5). Both splits compose on a 2D ``(axis, seq_axis)``
        mesh: per-rank partials cover (local heads × owned positions);
        the seq combine completes each head's softmax, then the head
        gather reassembles the full head set. Tables and lengths remain
        data, so the single-trace / no-retrace invariants of
        ``jit_step`` carry over unchanged, and so does ``donate``."""
        backend = kernel_ops.resolve_attention_backend(backend, mesh=mesh)
        cfg = self.cfg
        tp = mesh.shape.get(axis, 1) if axis else 1
        sp = mesh.shape.get(seq_axis, 1) if seq_axis else 1
        if tp == 1 and sp == 1:
            return self.jit_step(name, backend=backend, donate=donate)
        if tp > 1 and (cfg.n_kv_heads % tp or cfg.n_heads % tp):
            raise ValueError(
                f"n_kv_heads={cfg.n_kv_heads}/n_heads={cfg.n_heads} do not "
                f"divide mesh axis {axis!r} (size {tp}); ShardingRules "
                "dropped the head mapping — serve replicated instead"
            )
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        h_loc, kv_loc, hd = cfg.n_heads // tp, cfg.n_kv_heads // tp, cfg.head_dim
        local = Model(
            dataclasses.replace(cfg, n_heads=h_loc, n_kv_heads=kv_loc),
            tp_axis=axis if tp > 1 else None,
            seq_axis=seq_axis if sp > 1 else None,
        )
        step = getattr(local, name)

        def slice_heads(attn_p):
            r = jax.lax.axis_index(axis)

            def sl(w, n_loc):
                if w.ndim == 3:  # flat-TP [L, d, h·hd]: heads are
                    # flattened h-major, so a head block is a contiguous
                    # column range
                    return jax.lax.dynamic_slice_in_dim(
                        w, r * n_loc * hd, n_loc * hd, axis=2
                    )
                return jax.lax.dynamic_slice_in_dim(w, r * n_loc, n_loc, axis=2)

            return dict(
                attn_p,
                wq=sl(attn_p["wq"], h_loc),
                wk=sl(attn_p["wk"], kv_loc),
                wv=sl(attn_p["wv"], kv_loc),
            )  # wo stays full: the output projection runs on gathered heads

        def body(params, pool, block_tables, cache_len, tokens):
            layers = params["layers"]
            if tp > 1:
                layers = dict(layers, attn=slice_heads(layers["attn"]))
            return step(
                dict(params, layers=layers),
                pool,
                block_tables,
                cache_len,
                tokens,
                backend=backend,
            )

        pool_specs = self.paged_pool_specs(
            axis if tp > 1 else None, seq_axis if sp > 1 else None
        )
        fn = shard_map(
            body,
            mesh=mesh,
            in_specs=(P(), pool_specs, P(), P(), P()),
            out_specs=(P(), pool_specs),
            check_vma=False,
        )
        return jax.jit(fn, donate_argnums=(1,) if donate else ())

    def init_cache(self, batch, max_seq, dtype=None):
        cfg = self.cfg
        dt = dtype or _dtype(cfg)
        KV, hd = cfg.n_kv_heads, cfg.head_dim

        def kv_cache(n):
            if cfg.kv_quant:
                return {
                    "k": jnp.zeros((n, batch, max_seq, KV, hd), jnp.int8),
                    "v": jnp.zeros((n, batch, max_seq, KV, hd), jnp.int8),
                    "k_scale": jnp.zeros((n, batch, max_seq, KV), jnp.bfloat16),
                    "v_scale": jnp.zeros((n, batch, max_seq, KV), jnp.bfloat16),
                }
            return {
                "k": jnp.zeros((n, batch, max_seq, KV, hd), dt),
                "v": jnp.zeros((n, batch, max_seq, KV, hd), dt),
            }

        cache: dict[str, Any] = {"len": jnp.zeros((batch,), jnp.int32)}
        if cfg.family in ATTN_FAMILIES:
            cache.update(kv_cache(cfg.num_layers))
        elif cfg.family == "ssm":
            st = ssm_mod.init_ssm_state(cfg, batch, dt)
            cache["ssm_state"] = jax.tree.map(
                lambda s: jnp.broadcast_to(s[None], (cfg.num_layers,) + s.shape), st
            )
        elif cfg.family == "hybrid":
            G = cfg.num_layers // cfg.attn_every
            st = ssm_mod.init_ssm_state(cfg, batch, dt)
            cache["ssm_state"] = jax.tree.map(
                lambda s: jnp.broadcast_to(s[None], (cfg.num_layers,) + s.shape), st
            )
            cache.update(kv_cache(G))
        return cache

    def cache_axes(self, cache):
        """Logical axes for every cache leaf (for dry-run shardings)."""

        def leaf_axes(path, leaf):
            names = [getattr(p, "key", getattr(p, "name", "")) for p in path]
            if "k_scale" in names or "v_scale" in names:
                return (None, "batch", "kv_seq", "kv_heads")
            if "k" in names or "v" in names:
                return (None, "batch", "kv_seq", "kv_heads", None)
            if "ssm" in names:
                return (None, "batch", "ssm_heads", None, None)
            if "conv_x" in names:
                return (None, "batch", None, "ssm_inner")
            if "conv_B" in names or "conv_C" in names:
                return (None, "batch", None, None)
            if "len" in names:
                return (None,)
            return (None,) * leaf.ndim

        return jax.tree_util.tree_map_with_path(leaf_axes, cache)

    def cache_batch_axes(self, cache):
        """Per-leaf batch-axis index of the decode cache, derived from the
        ``cache_axes`` logical names (leaves without an explicit 'batch'
        axis — ``len`` — are batch-leading). The serving slot pool and the
        decode Region both slice per-request views through this, so slot
        logic is family-agnostic."""
        return jax.tree.map(
            lambda t: t.index("batch") if "batch" in t else 0,
            self.cache_axes(cache),
            is_leaf=lambda x: isinstance(x, tuple),
        )

    def read_cache_slot(self, pool, slot):
        """Batch slot ``slot`` of a pooled decode cache as a batch=1 cache."""
        return jax.tree.map(
            lambda leaf, ax: jax.lax.dynamic_slice_in_dim(leaf, slot, 1, axis=ax),
            pool,
            self.cache_batch_axes(pool),
        )

    def write_cache_slot(self, pool, slot_cache, slot):
        """Write a batch=1 cache (e.g. one request's prefill output) into
        batch slot ``slot`` of a pooled decode cache; non-batch dims must
        match the pool's (same ``max_seq``)."""
        return jax.tree.map(
            lambda leaf, sl, ax: jax.lax.dynamic_update_slice_in_dim(
                leaf, sl.astype(leaf.dtype), slot, axis=ax
            ),
            pool,
            slot_cache,
            self.cache_batch_axes(pool),
        )

    # ------------------------------------------------------------------
    # block-paged decode cache (serve/kv_cache.PagedKVCache)
    def init_paged_cache(self, num_blocks, block_size, dtype=None):
        """Physical block pool: ``num_blocks`` blocks of ``block_size``
        tokens, each leaf stored layer-stacked as the paged kernel reads
        it: K/V ``[L, NB, KV, BS/P, P·hd]`` and int8 scales ``[L, NB, KV,
        BS/P, P]``, ``P = attention.tokens_per_row(BS, hd)`` (DESIGN.md
        §4). Attention families only — SSM state has no sequence axis
        to page. Per-row lengths live with the block tables
        (PagedKVCache), not in the pool."""
        if self.cfg.family not in ATTN_FAMILIES:
            raise ValueError(
                f"paged KV cache needs an attention family, got {self.cfg.family!r}"
            )
        cfg = self.cfg
        p = attn.tokens_per_row(block_size, cfg.head_dim)
        blocks = (cfg.num_layers, num_blocks, cfg.n_kv_heads, block_size // p)
        kv = blocks + (p * cfg.head_dim,)
        if cfg.kv_quant:
            return {
                "k": jnp.zeros(kv, jnp.int8),
                "v": jnp.zeros(kv, jnp.int8),
                "k_scale": jnp.zeros(blocks + (p,), jnp.bfloat16),
                "v_scale": jnp.zeros(blocks + (p,), jnp.bfloat16),
            }
        dt = dtype or _dtype(cfg)
        return {"k": jnp.zeros(kv, dt), "v": jnp.zeros(kv, dt)}

    def paged_view(self, pool, block_tables):
        """Dense [L, B, MB·BS, ...] per-row caches gathered from the
        stored block pool through ``block_tables`` [B, MB] — the
        fixed-shape read side of the reference paged decode."""
        p = pool["k"].shape[-1] // self.cfg.head_dim  # tokens a stored row
        view = {}
        for name, leaf in pool.items():
            rows = attn.gather_block_rows(leaf, block_tables, p)
            view[name] = rows[..., 0] if attn.is_scale(name) else rows
        return view

    def decode_step_paged(
        self, params, pool, block_tables, cache_len, tokens, *, backend=None
    ):
        """One decode token over a block-paged KV cache.

        Kernel backends run the layer scan directly over the pool: each
        layer scatters the new token's KV rows into the row's tail block
        and attends *through the block tables* inside the Pallas kernel
        — no dense materialization (DESIGN.md §4). The reference backend
        keeps the original differential route: gather each row's K/V
        through its table into the fixed-shape dense view
        (``gather_block_rows``), run the ordinary ``decode_step``
        (identical numerics), scatter the appended token back. Shared
        prefix blocks are never a write target (the scheduler only
        shares immutable full-prompt blocks), so the scatter touches
        exclusively-owned blocks only. ``block_tables`` and
        ``cache_len`` are data, not shape: one jit trace serves any
        block layout and live set.

        Under the kv-sequence split (``self.seq_axis`` set on the
        per-rank model inside ``sharded_paged_step``) the reference
        backend also runs the layer scan: the dense differential route
        gathers through *global* tables, which cannot address a rank's
        local pool shard — the dict-cache path localizes them and
        combines per-rank flash partials instead (DESIGN.md §5)."""
        backend = kernel_ops.resolve_attention_backend(backend)
        if backend != "reference" or self.seq_axis is not None:
            logits, new_pool = self._step_paged_kernel(
                params, pool, block_tables, cache_len, tokens, backend
            )
            return logits[:, 0], new_pool
        dense = self.paged_view(pool, block_tables)
        logits, new_dense = self.decode_step(
            params, dict(dense, len=cache_len), tokens, backend="reference"
        )
        return logits, self._write_back(pool, new_dense, block_tables, cache_len, 1)

    def _write_back(self, pool, new_dense, block_tables, cache_len, t):
        """Scatter the ``t`` token rows a reference step appended to its
        dense view (at each row's ``cache_len``) back into the stored
        pool through the tables — the reference route's write."""
        bs = new_dense["k"].shape[2] // block_tables.shape[1]
        bid, off = attn.block_write_positions(block_tables, cache_len, t, bs)
        pos = cache_len[:, None] + jnp.arange(t)[None, :]  # [B, T]
        new_pool = {}
        for name, leaf in pool.items():
            nd = new_dense[name]  # [L, B, MB·BS, KV(, hd)]
            if attn.is_scale(name):
                nd = nd[..., None]
            idx = pos.reshape((1,) + pos.shape + (1,) * (nd.ndim - 3))
            token_rows = jnp.take_along_axis(nd, idx, axis=2)  # [L, B, T, KV, d]
            new_pool[name] = attn.scatter_block_tokens(leaf, token_rows, bid, off)
        return new_pool

    def _step_paged_kernel(self, params, pool, block_tables, cache_len, tokens, backend):
        """Shared decode/verify layer scan over the block pool itself:
        the per-layer cache is the dict form ``attention_block`` pages
        through (tail-block scatter + table-walking kernel attention).
        tokens [B,T] (T=1 decode, K+1 verify) → (logits [B,T,V], new
        pool). Tables and lengths stay data — one trace per (T, backend)."""
        cfg, rules = self.cfg, self.rules
        B, T = tokens.shape
        x = embed_tokens(params["embed"], tokens, rules)
        x = constrain(rules, x, ("batch", "seq", None))
        positions = cache_len[:, None] + jnp.arange(T)[None, :]
        names = ("k", "k_scale", "v", "v_scale") if cfg.kv_quant else ("k", "v")

        def body(carry, xs):
            x, leaves = carry
            lp, li = xs
            cache = dict(zip(names, leaves), tables=block_tables, li=li)
            xo, _, new_leaves = self._dense_layer(
                x, lp, "dense", positions=positions, cache=cache,
                cache_len=cache_len, backend=backend,
            )
            return (xo, new_leaves), None

        (x, leaves), _ = jax.lax.scan(
            body,
            (x, tuple(pool[n] for n in names)),
            (params["layers"], jnp.arange(cfg.num_layers)),
        )
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
        logits = jnp.einsum("btd,dv->btv", x, head)
        logits = constrain(rules, logits, ("batch", None, "vocab"))
        return logits, dict(zip(names, leaves))

    # ------------------------------------------------------------------
    # speculative verify (serve/speculative.py)
    def verify_step(self, params, cache, tokens, *, backend=None):
        """tokens [B,T] (pending token + T-1 draft tokens) → (logits
        [B,T,V], new cache with len += T). One speculative verify.

        The draft stream's proposals run as ONE forward over the decode
        cache: position t's logits predict the token after
        ``tokens[:, t]``, so greedy acceptance compares each draft
        against the previous position's argmax. T is static (one jit
        trace per speculation depth K = T-1) while acceptance counts
        stay data — the caller rewinds rejected tail entries afterwards
        with ``truncate_row`` (stale KV rows past the committed length
        are masked off by ``len`` and overwritten by later writes, so
        only the lengths rewind). ``SPEC_FAMILIES`` only: rewinding
        needs a length-addressed cache, and MoE token-count-dependent
        routing would break greedy equivalence."""
        cfg, rules = self.cfg, self.rules
        if cfg.family not in SPEC_FAMILIES:
            raise ValueError(
                f"verify_step is only greedy-equivalent for {SPEC_FAMILIES}, "
                f"got {cfg.family!r} (SSM state cannot rewind; MoE capacity "
                "routing depends on tokens-per-call)"
            )
        B, T = tokens.shape
        x = embed_tokens(params["embed"], tokens, rules)
        x = constrain(rules, x, ("batch", "seq", None))
        positions = cache["len"][:, None] + jnp.arange(T)[None, :]

        if cfg.kv_quant:

            def body_q(carry, xs):
                x, ks, kss, vs, vss = carry
                lp, li = xs
                xo, _, (ks, kss, vs, vss) = self._dense_layer(
                    x, lp, "dense", positions=positions,
                    cache=(ks, kss, vs, vss, li), cache_len=cache["len"],
                    backend=backend,
                )
                return (xo, ks, kss, vs, vss), None

            (x, ks, kss, vs, vss), _ = jax.lax.scan(
                body_q,
                (x, cache["k"], cache["k_scale"], cache["v"], cache["v_scale"]),
                (params["layers"], jnp.arange(cfg.num_layers)),
            )
            new_cache = {"k": ks, "k_scale": kss, "v": vs, "v_scale": vss,
                         "len": cache["len"] + T}
        else:

            def body(carry, xs):
                x, ks, vs = carry
                lp, li = xs
                xo, _, (ks, vs) = self._dense_layer(
                    x, lp, "dense", positions=positions,
                    cache=(ks, vs, li), cache_len=cache["len"],
                    backend=backend,
                )
                return (xo, ks, vs), None

            (x, ks, vs), _ = jax.lax.scan(
                body,
                (x, cache["k"], cache["v"]),
                (params["layers"], jnp.arange(cfg.num_layers)),
            )
            new_cache = {"k": ks, "v": vs, "len": cache["len"] + T}

        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
        logits = jnp.einsum("btd,dv->btv", x, head)
        logits = constrain(rules, logits, ("batch", None, "vocab"))
        return logits, new_cache

    def verify_step_paged(
        self, params, pool, block_tables, cache_len, tokens, *, backend=None
    ):
        """Speculative verify over the block-paged cache.

        Kernel backends run the same table-walking layer scan as
        ``decode_step_paged`` with T = K+1 queries (the kernel's verify
        variant; the T positions may cross a block boundary — the
        scheduler pre-claims every reachable tail block via
        ``ensure_tail_n`` before calling). The reference backend
        gathers each row's dense view through its block table, runs the
        ordinary ``verify_step`` (identical numerics), then scatters the
        T new per-token KV rows back through the tables
        (``scatter_block_tokens``). Dead rows' tables point at the null
        block, so their writes land in scratch. Tables, lengths, and
        acceptance are data: one trace per depth. Like
        ``decode_step_paged``, the kv-sequence split forces the layer
        scan for the reference backend too (global tables cannot
        address a rank's local pool shard)."""
        backend = kernel_ops.resolve_attention_backend(backend)
        if backend != "reference" or self.seq_axis is not None:
            if self.cfg.family not in SPEC_FAMILIES:
                raise ValueError(
                    f"verify_step is only greedy-equivalent for {SPEC_FAMILIES}, "
                    f"got {self.cfg.family!r}"
                )
            return self._step_paged_kernel(
                params, pool, block_tables, cache_len, tokens, backend
            )
        dense = self.paged_view(pool, block_tables)
        logits, new_dense = self.verify_step(
            params, dict(dense, len=cache_len), tokens, backend="reference"
        )
        T = tokens.shape[1]
        return logits, self._write_back(pool, new_dense, block_tables, cache_len, T)

    def decode_step(self, params, cache, tokens, *, backend=None):
        """tokens [B,1] → (logits [B,V], new cache). One new token.
        ``backend`` picks the cached-attention backend (DESIGN.md §4);
        None resolves the ops-registry default at trace time."""
        cfg, rules = self.cfg, self.rules
        B = tokens.shape[0]
        x = embed_tokens(params["embed"], tokens, rules)
        x = constrain(rules, x, ("batch", "seq", None))
        positions = cache["len"][:, None]
        aux = 0.0

        if cfg.family in ATTN_FAMILIES and LEGACY_CACHE_SCAN:

            def body_legacy(x, xs):
                lp, kc, vc = xs
                xo, _, (kc, vc) = self._dense_layer(
                    x, lp, "dense", positions=positions,
                    cache=(kc, vc), cache_len=cache["len"],
                    backend=backend,
                )
                return xo, (kc, vc)

            x, (ks, vs) = jax.lax.scan(
                body_legacy, x, (params["layers"], cache["k"], cache["v"])
            )
            new_cache = {"k": ks, "v": vs, "len": cache["len"] + 1}
        elif cfg.family in ATTN_FAMILIES and cfg.kv_quant:

            def body_q(carry, xs):
                x, ks, kss, vs, vss = carry
                lp, li = xs
                xo, _, (ks, kss, vs, vss) = self._dense_layer(
                    x, lp, "dense", positions=positions,
                    cache=(ks, kss, vs, vss, li), cache_len=cache["len"],
                    backend=backend,
                )
                return (xo, ks, kss, vs, vss), None

            (x, ks, kss, vs, vss), _ = jax.lax.scan(
                body_q,
                (x, cache["k"], cache["k_scale"], cache["v"], cache["v_scale"]),
                (params["layers"], jnp.arange(cfg.num_layers)),
            )
            new_cache = {"k": ks, "k_scale": kss, "v": vs, "v_scale": vss,
                         "len": cache["len"] + 1}
        elif cfg.family in ATTN_FAMILIES:
            # the cache STACK rides in the carry: per-step writes are one
            # token, and donation aliases the whole stack in place
            def body(carry, xs):
                x, ks, vs = carry
                lp, li = xs
                xo, _, (ks, vs) = self._dense_layer(
                    x, lp, "dense", positions=positions,
                    cache=(ks, vs, li), cache_len=cache["len"],
                    backend=backend,
                )
                return (xo, ks, vs), None

            (x, ks, vs), _ = jax.lax.scan(
                body,
                (x, cache["k"], cache["v"]),
                (params["layers"], jnp.arange(cfg.num_layers)),
            )
            new_cache = {"k": ks, "v": vs, "len": cache["len"] + 1}
        elif cfg.family == "ssm":

            def body(x, xs):
                lp, st = xs
                x, new_st = self._ssm_layer(x, lp, state=st)
                return x, new_st

            x, sts = jax.lax.scan(body, x, (params["layers"], cache["ssm_state"]))
            new_cache = {"ssm_state": sts, "len": cache["len"] + 1}
        elif cfg.family == "hybrid":
            x, new_cache = self._hybrid_decode(params, x, cache, positions, backend)
        else:
            raise ValueError(cfg.family)

        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
        logits = jnp.einsum("bsd,dv->bsv", x, head)[:, 0]
        logits = constrain(rules, logits, ("batch", "vocab"))
        return logits, new_cache

    def _hybrid_decode(self, params, x, cache, positions, backend=None):
        cfg = self.cfg
        shared = params["shared"]
        glp = self._hybrid_grouped_params(params)
        G = cfg.num_layers // cfg.attn_every
        sts = jax.tree.map(
            lambda s: s.reshape((G, cfg.attn_every) + s.shape[1:]),
            cache["ssm_state"],
        )

        if LEGACY_CACHE_SCAN:
            return self._hybrid_decode_legacy(params, x, cache, positions, shared, glp, sts, G)

        quant = cfg.kv_quant

        def group(carry, xs):
            x, kv = carry
            lp, st, gi = xs

            def inner(x, xs2):
                lp2, st2 = xs2
                x, new_st2 = self._ssm_layer(x, lp2, state=st2)
                return x, new_st2

            x, new_st = jax.lax.scan(inner, x, (lp, st))
            h, kv = attn.attention_block(
                shared["attn"],
                rms_norm(x, shared["attn_norm"], cfg.norm_eps),
                cfg=cfg,
                rules=self.rules,
                positions=positions,
                cache=kv + (gi,),  # in-place token write into the stack
                cache_len=cache["len"],
                backend=backend,
            )
            x = x + h
            x = x + swiglu(
                rms_norm(x, shared["mlp_norm"], cfg.norm_eps),
                shared["mlp"]["w1"],
                shared["mlp"]["w3"],
                shared["mlp"]["w2"],
                self.rules,
            )
            return (x, kv), new_st

        kv0 = (
            (cache["k"], cache["k_scale"], cache["v"], cache["v_scale"])
            if quant
            else (cache["k"], cache["v"])
        )
        (x, kv), new_sts = jax.lax.scan(group, (x, kv0), (glp, sts, jnp.arange(G)))
        new_sts = jax.tree.map(
            lambda s: s.reshape((cfg.num_layers,) + s.shape[2:]), new_sts
        )
        out_cache = {"ssm_state": new_sts, "len": cache["len"] + 1}
        if quant:
            out_cache.update(k=kv[0], k_scale=kv[1], v=kv[2], v_scale=kv[3])
        else:
            out_cache.update(k=kv[0], v=kv[1])
        return x, out_cache

    def _hybrid_decode_legacy(self, params, x, cache, positions, shared, glp, sts, G):
        """Pre-optimization hybrid decode (baseline measurement only)."""
        cfg = self.cfg

        def group(x, xs):
            lp, st, kc, vc = xs

            def inner(x, xs2):
                lp2, st2 = xs2
                x, new_st2 = self._ssm_layer(x, lp2, state=st2)
                return x, new_st2

            x, new_st = jax.lax.scan(inner, x, (lp, st))
            h, (kc, vc) = attn.attention_block(
                shared["attn"],
                rms_norm(x, shared["attn_norm"], cfg.norm_eps),
                cfg=cfg, rules=self.rules, positions=positions,
                cache=(kc, vc), cache_len=cache["len"],
            )
            x = x + h
            x = x + swiglu(
                rms_norm(x, shared["mlp_norm"], cfg.norm_eps),
                shared["mlp"]["w1"], shared["mlp"]["w3"], shared["mlp"]["w2"],
                self.rules,
            )
            return x, (new_st, kc, vc)

        x, (new_sts, ks, vs) = jax.lax.scan(group, x, (glp, sts, cache["k"], cache["v"]))
        new_sts = jax.tree.map(
            lambda s: s.reshape((cfg.num_layers,) + s.shape[2:]), new_sts
        )
        return x, {"ssm_state": new_sts, "k": ks, "v": vs, "len": cache["len"] + 1}

    # ------------------------------------------------------------------
    def prefill(self, params, tokens, max_seq, patch_embeds=None, prompt_len=None):
        """Run the prompt, return (next-token logits [B,V], filled cache).

        ``prompt_len`` [B] (optional) marks per-row effective prompt
        lengths when ``tokens`` is right-padded to a pow2 bucket
        (``prefill_bucket``): logits come from each row's last *real*
        token and ``cache["len"]`` becomes a per-row vector, so pad
        rows never commit. Pad keys sit at positions strictly after
        every real query, so causal masking makes the padded run
        bitwise-identical to the unpadded one for
        ``PAD_PREFILL_FAMILIES``."""
        cfg = self.cfg
        if prompt_len is not None and cfg.family not in PAD_PREFILL_FAMILIES:
            raise ValueError(
                f"padded prefill is only token-identical for "
                f"{PAD_PREFILL_FAMILIES}, got {cfg.family!r} (recurrent state "
                "integrates pad positions; MoE capacity depends on tokens-per-call)"
            )
        x, _, caches = self.forward(
            params, tokens, patch_embeds=patch_embeds, want_cache=True
        )
        head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
        B, S = x.shape[0], x.shape[1]
        if prompt_len is None:
            x_last = x[:, -1]
        else:
            n_lead = S - tokens.shape[1]  # VLM patch rows lead the tokens
            idx = n_lead + jnp.asarray(prompt_len, jnp.int32) - 1
            x_last = x[jnp.arange(B), idx]
        logits = jnp.einsum("bd,dv->bv", x_last, head)
        cache = self.init_cache(B, max_seq)

        def fill_kv(cache, k, v):
            if cfg.kv_quant:
                kq, ks = attn.quantize_kv(k)
                vq, vs = attn.quantize_kv(v)
                for name, val, ax in (
                    ("k", kq, 2), ("k_scale", ks, 2), ("v", vq, 2), ("v_scale", vs, 2),
                ):
                    cache[name] = jax.lax.dynamic_update_slice_in_dim(
                        cache[name], val, 0, axis=ax
                    )
            else:
                cache["k"] = jax.lax.dynamic_update_slice_in_dim(cache["k"], k, 0, axis=2)
                cache["v"] = jax.lax.dynamic_update_slice_in_dim(cache["v"], v, 0, axis=2)
            return cache

        if cfg.family in ATTN_FAMILIES:
            k, v = caches  # [L,B,S,KV,hd]
            cache = fill_kv(cache, k, v)
        elif cfg.family == "ssm":
            cache["ssm_state"] = caches
        elif cfg.family == "hybrid":
            (k, v), sts = caches  # kv [G,B,S,KV,hd]; sts [G,per,...]
            cache = fill_kv(cache, k, v)
            cache["ssm_state"] = jax.tree.map(
                lambda s: s.reshape((cfg.num_layers,) + s.shape[2:]), sts
            )
        if prompt_len is None:
            cache["len"] = jnp.full_like(cache["len"], S)
        else:
            n_lead = S - tokens.shape[1]
            cache["len"] = (n_lead + jnp.asarray(prompt_len, jnp.int32)).astype(
                cache["len"].dtype
            )
        return logits, cache

    def prefill_with_prefix(
        self, params, tokens, prefix_k, prefix_v, max_seq, suffix_len=None
    ):
        """Suffix prefill over an already-cached prompt prefix.

        ``tokens`` [B, Ssuf] are the prompt tokens *after* the cached
        prefix; ``prefix_k``/``prefix_v`` [L, B, h, KV, hd] are the
        prefix's post-RoPE KV rows (as gathered from the paged pool).
        Returns (next-token logits [B, V], dense cache holding the full
        prefix+suffix KV, len = h + Ssuf). Because per-query flash
        accumulation never depends on which other query rows run, the
        suffix comes out bitwise-identical to a cold full-prompt
        ``prefill`` for dense/audio families — at the cost of the suffix
        only, which is where the shared-prefix TTFT win comes from.
        (MoE is excluded from prefix *reuse* upstream: dispatch capacity
        depends on tokens-per-call, so suffix routing can drop different
        tokens than the cold run.)

        int8-KV: ``prefix_k``/``prefix_v`` arrive dequantized (the
        paged pool's ``gather_prefix`` undoes the per-vector scales) and
        the returned cache is requantized whole. Unlike the fp
        families this is *approximate*, not bitwise: suffix queries
        attend over dequantized prefix K/V (≤1/254 relative error vs
        the fp rows the cold prefill used), so layer≥2 suffix K/V and
        the first-token logits carry a quantization-level perturbation
        — greedy tokens agree unless an argmax near-tie flips. Prefix
        rows themselves round-trip exactly (quantize∘dequantize is
        idempotent — the max-|x| element pins each scale) and the
        scheduler never rewrites the shared blocks anyway
        (``write_prefill(skip_blocks=)``), so every *subsequent* decode
        step reads the identical quantized pool either way."""
        cfg, rules = self.cfg, self.rules
        if cfg.family not in PREFIX_FAMILIES:
            raise ValueError(
                f"prefix prefill is only token-identical for {PREFIX_FAMILIES}, "
                f"got {cfg.family!r} (MoE capacity routing / VLM patch rows diverge)"
            )
        h = prefix_k.shape[2]
        B, Ssuf = tokens.shape
        x = embed_tokens(params["embed"], tokens, rules)
        x = constrain(rules, x, ("batch", "seq", None))
        positions = (h + jnp.arange(Ssuf))[None, :].astype(jnp.int32)

        def body(carry, xs):
            x, aux = carry
            lp, pk, pv = xs
            x, a, _kv = self._dense_layer(
                x, lp, "dispatch", positions=positions, prefix_kv=(pk, pv)
            )
            return (x, aux + a), _kv

        (x, _), (k, v) = jax.lax.scan(
            self._maybe_remat(body), (x, 0.0), (params["layers"], prefix_k, prefix_v)
        )
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
        if suffix_len is None:
            x_last = x[:, -1]
        else:
            # tokens right-padded to a bucket: last *real* suffix row
            idx = jnp.asarray(suffix_len, jnp.int32) - 1
            x_last = x[jnp.arange(B), idx]
        logits = jnp.einsum("bd,dv->bv", x_last, head)
        cache = self.init_cache(B, max_seq)
        if cfg.kv_quant:
            kq, ks = attn.quantize_kv(k)
            vq, vs = attn.quantize_kv(v)
            for name, val in (("k", kq), ("k_scale", ks), ("v", vq), ("v_scale", vs)):
                cache[name] = jax.lax.dynamic_update_slice_in_dim(
                    cache[name], val, 0, axis=2
                )
        else:
            cache["k"] = jax.lax.dynamic_update_slice_in_dim(cache["k"], k, 0, axis=2)
            cache["v"] = jax.lax.dynamic_update_slice_in_dim(cache["v"], v, 0, axis=2)
        if suffix_len is None:
            cache["len"] = jnp.full_like(cache["len"], h + Ssuf)
        else:
            cache["len"] = (h + jnp.asarray(suffix_len, jnp.int32)).astype(
                cache["len"].dtype
            )
        return logits, cache

    # ------------------------------------------------------------------
    # chunked prefill (serve/scheduler.py token-budget step loop)
    def prefill_chunk(self, params, cache, tokens, n_valid, *, backend=None):
        """One chunk of an incremental prefill: run ``tokens`` [B, W]
        (right-padded to the pow2 bucket W) against a partially filled
        dense cache and commit ``n_valid`` [B] new rows.

        This IS the speculative ``verify_step`` — W queries attend over
        cached earlier-chunk KV plus themselves via the same per-query
        causal mask at a data offset (``pos < len + t + 1``) — except
        the length advance is ``n_valid`` (data) instead of W (shape),
        so pad rows never commit: their K/V land past the new ``len``,
        masked off and overwritten by the next chunk. One jit trace per
        bucket W; chunk position is data (``cache['len']``), so walking
        a prompt never retraces. Returns (logits [B, W, V], new cache);
        ``logits[b, n_valid[b]-1]`` predicts the token after the last
        real chunk token. ``CHUNKED_PREFILL_FAMILIES`` only."""
        if self.cfg.family not in CHUNKED_PREFILL_FAMILIES:
            raise ValueError(
                f"chunked prefill is only token-identical for "
                f"{CHUNKED_PREFILL_FAMILIES}, got {self.cfg.family!r}"
            )
        logits, new_cache = self.verify_step(params, cache, tokens, backend=backend)
        new_cache["len"] = (
            cache["len"] + jnp.asarray(n_valid, jnp.int32)
        ).astype(cache["len"].dtype)
        return logits, new_cache

    def seed_cache_with_prefix(self, prefix_k, prefix_v, max_seq):
        """Dense batch-1 cache pre-loaded with a prefix-cache hit, ready
        for ``prefill_chunk`` to continue at ``len = h``.

        ``prefix_k``/``prefix_v`` [L, 1, h, KV, hd] arrive dequantized
        (``gather_prefix``); int8 configs requantize on write — the
        round-trip is exact (the max-|x| element pins each scale), so
        the seeded rows match the pool bitwise. Host-side glue, not
        jitted: runs once per admission, shapes vary with h."""
        cfg = self.cfg
        if cfg.family not in PREFIX_FAMILIES:
            raise ValueError(
                f"prefix seeding is only token-identical for {PREFIX_FAMILIES}, "
                f"got {cfg.family!r}"
            )
        h = prefix_k.shape[2]
        cache = self.init_cache(prefix_k.shape[1], max_seq)
        if cfg.kv_quant:
            kq, ks = attn.quantize_kv(jnp.asarray(prefix_k))
            vq, vs = attn.quantize_kv(jnp.asarray(prefix_v))
            seeds = (("k", kq), ("k_scale", ks), ("v", vq), ("v_scale", vs))
        else:
            seeds = (("k", prefix_k), ("v", prefix_v))
        # assemble on the host: h varies per admission, and a per-h
        # XLA update-slice would compile inside the serving window
        for name, val in seeds:
            buf = np.zeros(cache[name].shape, cache[name].dtype)
            buf[:, :, :h] = np.asarray(val)
            cache[name] = jnp.asarray(buf)
        cache["len"] = jnp.full_like(cache["len"], h)
        return cache
