"""Continuous-batching serving engine (facade over the serving core).

The paper's subject is latency-critical request processing; at LM scale
that is the decode loop. PR 1 made the decode step *advisable* (one
``Region`` whose work items are concurrent requests); this layer makes
it *servable*: requests are admitted, decoded, and retired individually
(DESIGN.md §3), with the decode step still routable through an accepted
``RegionPlan``.

  request.py    Request lifecycle (queued → prefill → decode → finished)
                + per-request TTFT/TPOT/e2e + prefix-hit accounting
                (``ServeStats``)
  block.py      ``BlockAllocator`` (refcounted block pool, LRU eviction)
                + ``PrefixCache`` (trie of immutable prompt blocks)
  kv_cache.py   ``SlotKVCache`` — fixed pool of ``max_batch`` cache
                slots; allocate on admit, free on finish/EOS.
                ``PagedKVCache`` — block-granular cache memory with
                shared-prefix reuse (``kv_layout="paged"``)
  scheduler.py  ``Scheduler`` — per step: admit into free rows (charged
                in slots or blocks; prefix hits prefill the suffix
                only), one batched decode over the full pool (masked
                plan execution when a plan is set; block-table
                gather/scatter when paged — live-count, table, and
                length changes never retrace)
  speculative.py ``DraftSource`` streams (n-gram prompt-lookup / small
                draft model), ``SpecConfig``, and ``advise_depth`` —
                probe-measure a workload, let the
                ``SpeculationAdvisorTool`` pick K (DESIGN.md §3.2)
  engine.py     this facade: ``serve()`` is the open-loop entry,
                ``generate()`` the fixed-batch compatibility wrapper,
                ``decode_region()``/``set_decode_plan()`` the PR 1
                advisory contract, unchanged. ``kv_layout="paged"``
                (constructor default or per-call) selects the paged
                path; the slotted path stays as the differential
                baseline. ``spec=SpecConfig(...)`` (constructor default
                or per-call) turns on speculative decoding — greedy
                token streams are unchanged by construction.
                ``attention_backend=`` (constructor default or
                per-call) picks the decode/verify attention backend
                (DESIGN.md §4): the block-paged Pallas kernel walks
                block tables directly — no dense gather per step — and
                each backend gets its own statically-bound jitted step
                family, so switching never retraces another backend's
                executables.
"""
from __future__ import annotations

import dataclasses
import logging
import time
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import ops as kernel_ops
from repro.serve.request import Request, ServeStats  # noqa: F401 (re-export)
from repro.serve.scheduler import Scheduler, paged_step
from repro.serve.telemetry import TID_BACKEND, get_telemetry

log = logging.getLogger("repro.serve")


class ServingEngine:
    def __init__(
        self,
        model,
        params,
        *,
        max_seq: int,
        temperature: float = 0.0,
        decode_plan=None,
        max_batch: Optional[int] = None,
        kv_layout: str = "slot",
        block_size: int = 8,
        num_blocks: Optional[int] = None,
        prefix_cache: bool = True,
        spec=None,
        attention_backend: Optional[str] = None,
        chunk_size: Optional[int] = None,
        mesh=None,
        telemetry=None,
    ):
        self.model = model
        self.params = params
        self.max_seq = max_seq
        self.temperature = temperature
        self.max_batch = max_batch  # default slot-pool size for serve()
        self.kv_layout = kv_layout  # default layout for serve()/scheduler()
        self.block_size = block_size
        self.num_blocks = num_blocks
        self.prefix_cache = prefix_cache
        self.spec = spec  # default SpecConfig for serve()/scheduler()
        self.chunk_size = chunk_size  # default chunked-prefill token budget
        # flight recorder + metrics (DESIGN.md §8): every scheduler this
        # engine makes shares the tracer (and the stats registry the
        # windowed metrics live in). Default is the module-global
        # telemetry, which is disabled — the hard off-switch.
        self.telemetry = telemetry if telemetry is not None else get_telemetry()
        self.stats = ServeStats()
        # serving tensor parallelism (DESIGN.md §5): a mesh with a 'model'
        # axis head-partitions the paged pool and runs the decode/verify
        # steps under shard_map. Head counts that do not divide the axis
        # fall back LOUDLY to replicated serving via the ShardingRules
        # drop-rule — tokens are identical either way, only the layout
        # changes, so a warning (never silence, never a crash) is right.
        # Warned ONCE per (cfg, mesh): repeated serve(mesh=) calls on the
        # same engine re-check but neither re-warn nor re-append the
        # fallback record (the regression test counts warnings).
        self._mesh_warned: set = set()
        self.mesh_fallbacks: list[str] = []
        self.mesh = self._check_mesh(mesh)
        if self.mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            # params are replicated once, up front: every rank slices its
            # own head block inside the step, so no per-step weight moves
            self.params = jax.device_put(
                params, NamedSharding(self.mesh, P())
            )
        # the decode/verify attention backend is resolved ONCE, here,
        # before anything is jitted (DESIGN.md §4): each backend gets its
        # own statically-bound jitted step family in ``self._steps``, so
        # switching backends (per serve()/scheduler() call) reuses that
        # backend's compiled executables and can never retarget — or
        # retrace — another backend's traces. Mesh-aware: on a non-TPU
        # mesh "kernel" resolves to "interpret" (the kernel composes with
        # shard_map per-shard instead of falling back to reference).
        self.attention_backend = kernel_ops.resolve_attention_backend(
            attention_backend, mesh=self.mesh
        )
        # engine-owned jitted steps, shared by every scheduler this engine
        # makes: repeated generate()/serve() calls reuse the executables
        self._prefill = jax.jit(lambda p, t, **kw: model.prefill(p, t, max_seq, **kw))
        self._prefill_prefix = None  # lazy: attention families only
        self._steps: dict[str, dict] = {}  # backend → jitted decode/verify family
        self._plan_steps: dict = {}  # (plan key, pool size) → jitted plan step
        self._decode_plan = None
        self._pool_recorded = False  # pool layout telemetry, once an engine
        if decode_plan is not None:
            self.set_decode_plan(decode_plan)

    def _check_mesh(self, mesh):
        """The engine's serving mesh, or None after the loud GQA
        fallback: head counts that don't divide the 'model' axis mean
        ``ShardingRules`` drops the head mapping, and the engine serves
        replicated — warned, never silent, never wrong tokens. A pure
        "seq"-axis mesh always passes: the kv-sequence split partitions
        blocks, not heads, and the slot layout imposes no divisibility
        constraint. The fallback record is kept (deduped) on the engine's
        ``mesh_fallbacks`` and the warning fires once per (cfg, mesh) —
        re-serving through the same fallen-back engine stays quiet."""
        if mesh is None or mesh.shape.get("model", 1) == 1:
            return mesh
        from repro.parallel.sharding import ShardingRules

        cfg = self.model.cfg
        rules = ShardingRules(mesh, cfg)
        tp = mesh.shape["model"]
        if rules.table["kv_heads"] is None or cfg.n_heads % tp:
            record = (
                f"kv_heads:{cfg.n_kv_heads}/heads:{cfg.n_heads} ∤ mesh "
                f"model({tp}); serving replicated"
            )
            if record not in self.mesh_fallbacks:
                self.mesh_fallbacks.append(record)
                if self.telemetry.enabled:
                    self.telemetry.count(
                        "serve.mesh_fallbacks", registry=self.stats.registry
                    )
                    self.telemetry.tracer.instant(
                        "mesh-fallback", "backend", tid=TID_BACKEND,
                        args={"record": record},
                    )
            rules.fallbacks.append(record)
            key = (id(cfg), tuple(sorted(mesh.shape.items())))
            if key not in self._mesh_warned:
                self._mesh_warned.add(key)
                log.warning(
                    "serving mesh dropped: n_kv_heads=%d/n_heads=%d do not "
                    "divide mesh axis 'model' (size %d) — serving replicated "
                    "(ShardingRules fallbacks: %s)",
                    cfg.n_kv_heads, cfg.n_heads, tp, rules.fallbacks,
                )
            return None
        return mesh

    def _step_fns(self, backend: str) -> dict:
        """The jitted decode/verify family for ``backend``, built lazily
        (only attention families page, only SPEC_FAMILIES verify) and
        cached per backend."""
        fns = self._steps.setdefault(backend, {})
        if "decode" not in fns:
            fns["decode"] = self.model.jit_step("decode_step", backend)
        return fns

    def _paged_fns(self, backend: str):
        fns = self._step_fns(backend)
        if "decode_paged" not in fns:
            fns["decode_paged"] = paged_step(
                self.model, "decode_step_paged", backend, self.mesh
            )
        if self._prefill_prefix is None:
            model, max_seq = self.model, self.max_seq
            self._prefill_prefix = jax.jit(
                lambda p, t, pk, pv, **kw: model.prefill_with_prefix(
                    p, t, pk, pv, max_seq, **kw
                )
            )
        return fns["decode_paged"], self._prefill_prefix

    # ------------------------------------------------------------------
    # the decode step as an advisable region (requests = work items)

    def _decode_cache_spec(self, cache):
        """(treedef, per-leaf batch-axis index) of the decode cache."""
        leaves, treedef = jax.tree.flatten(cache)
        axes = tuple(jax.tree.leaves(self.model.cache_batch_axes(cache)))
        assert len(axes) == len(leaves)
        return treedef, axes

    def _per_request_decode(self, treedef, axes):
        """Per-item fn over (token, batchless cache leaves): one request's
        decode step — what relic_pfor co-schedules across requests.
        Decodes through the engine's resolved attention backend, like
        every other step family."""
        model, params, backend = self.model, self.params, self.attention_backend

        def fn(item):
            tok, leaves = item
            cache = jax.tree.unflatten(
                treedef, [jnp.expand_dims(l, ax) for l, ax in zip(leaves, axes)]
            )
            logits, new_cache = model.decode_step(
                params, cache, tok.reshape(1, 1), backend=backend
            )
            new_leaves = [
                jnp.moveaxis(l, ax, 0)[0]
                for l, ax in zip(jax.tree.leaves(new_cache), axes)
            ]
            return logits[0], new_leaves

        return fn

    def _decode_items(self, cache, tok, axes):
        leaves = jax.tree.leaves(cache)
        return (tok, [jnp.moveaxis(l, ax, 0) for l, ax in zip(leaves, axes)])

    def decode_region(
        self,
        prompts: jax.Array,
        *,
        name: str = "serve-decode",
        seed: int = 0,
        task_flops: Optional[float] = None,
        task_bytes: Optional[float] = None,
        task_chain: int = 0,
        force: bool = False,
    ):
        """Expose one decode step as an Aira ``Region``.

        Items are the batch of concurrent requests. The attached dynamic
        trace records each request touching only its own cache slice
        (disjoint by construction), so the dependence stages clear and
        the overlap gate decides. ``seed`` seeds the advisory trace's
        first sampled token, so traces aren't silently correlated with
        serving seeds. Default napkin cost: weight-streaming decode —
        2·n_params FLOPs and n_params·4 bytes per request-token (batched
        decode is bandwidth-bound, which is exactly why the gate usually
        says no and latency-critical deployments ``force``).
        """
        from repro.core.adviser import Region
        from repro.core.deps import MemoryTrace

        logits, cache = self._prefill(self.params, prompts)
        tok = self._sample(logits, jax.random.key(seed))
        treedef, axes = self._decode_cache_spec(cache)
        items = self._decode_items(cache, tok, axes)
        n_params = sum(l.size for l in jax.tree.leaves(self.params))
        batch = int(tok.shape[0])
        trace = MemoryTrace(
            reads=[[i] for i in range(batch)], writes=[[i] for i in range(batch)]
        )
        return Region(
            name=name,
            fn=self._per_request_decode(treedef, axes),
            items=items,
            task_flops=2.0 * n_params if task_flops is None else task_flops,
            task_bytes=4.0 * n_params if task_bytes is None else task_bytes,
            task_chain=task_chain,
            vector=False,
            trace=trace,
            force=force,
        )

    def set_decode_plan(self, plan) -> None:
        """Route the decode step through an accepted ``RegionPlan`` (as
        produced by advising ``decode_region`` — stack combine). Applies
        to schedulers created from here on (masked execution over the
        active-slot view)."""
        if plan is not None and plan.key.combine != "stack":
            raise ValueError("decode plan must preserve per-request order (combine='stack')")
        self._decode_plan = plan

    # ------------------------------------------------------------------
    # serving entries
    def _spec_fns(self, layout: str, backend: str):
        fns = self._step_fns(backend)
        if "verify" not in fns:
            fns["verify"] = self.model.jit_step("verify_step", backend)
        if layout == "paged" and "verify_paged" not in fns:
            fns["verify_paged"] = paged_step(
                self.model, "verify_step_paged", backend, self.mesh
            )
        return fns["verify"], fns.get("verify_paged")

    def prime(
        self,
        max_batch: Optional[int] = None,
        *,
        ks=(0, 2, 4, 8),
        backends=None,
        kv_layout: Optional[str] = None,
        reps: int = 2,
    ) -> dict:
        """Pre-jit (and measure) the K × backend decode/verify grid an
        online controller can switch across (DESIGN.md §9).

        Each candidate depth is one ``[max_batch, k+1]`` verify trace in
        the shared jitted verify fn (jit caches per input shape) and
        each backend one entry in the engine's step-fn cache, so after
        this every mid-serve switch the controller makes is a trace-
        cache hit — the drift benchmark pins that with trace counters.
        ``max_batch`` must match the pool size later serves use (pool
        shapes are static). Depths are clamped to ``(0,)`` for model
        families without a rewindable cache. Runs against a throwaway
        pool (all rows dead — writes route to the null block / junk
        slots), timing ``reps`` calls per cell, and returns
        ``{"cells": {backend: {k: ms}}, "ks", "backends", ...}`` —
        feed it to ``OnlineAdviser.seed_costs`` so the first decision
        prices measured numbers."""
        from repro.models.model import SPEC_FAMILIES
        from repro.serve.kv_cache import PagedKVCache

        mb = int(max_batch or self.max_batch or 4)
        layout = kv_layout or self.kv_layout
        if self.model.cfg.family not in SPEC_FAMILIES:
            ks = (0,)
        ks = tuple(sorted({int(k) for k in ks}))
        names = backends if backends else (self.attention_backend,)
        names = tuple(
            dict.fromkeys(
                kernel_ops.resolve_attention_backend(b, mesh=self.mesh)
                for b in names
            )
        )
        tok = jnp.zeros((mb, 1), jnp.int32)
        cells: dict[str, dict[int, float]] = {}

        def _time(fn) -> float:
            jax.block_until_ready(fn())  # compile (not timed)
            t0 = time.perf_counter()
            for _ in range(max(1, reps)):
                jax.block_until_ready(fn())
            return (time.perf_counter() - t0) / max(1, reps) * 1e3

        for backend in names:
            per_k: dict[int, float] = {}
            if layout == "paged":
                kv = PagedKVCache(
                    self.model, mb, self.max_seq,
                    block_size=self.block_size, num_blocks=self.num_blocks,
                    prefix_cache=False, mesh=self.mesh,
                )
                _, tables, lens = kv.kernel_inputs()
                decode_paged, _ = self._paged_fns(backend)
                verify_paged = None
                if any(k > 0 for k in ks):
                    _, verify_paged = self._spec_fns("paged", backend)

                def paged(step, blk):
                    # the steps take the pool donated: each call runs on
                    # the pool the one before handed back
                    logits, kv.pool = step(self.params, kv.pool, tables, lens, blk)
                    return logits

                for k in ks:
                    if k == 0:
                        per_k[0] = _time(lambda: paged(decode_paged, tok))
                    else:
                        blk = jnp.zeros((mb, k + 1), jnp.int32)
                        per_k[k] = _time(lambda blk=blk: paged(verify_paged, blk))
            else:
                cache = self.model.init_cache(mb, self.max_seq)
                decode = self._step_fns(backend)["decode"]
                verify = None
                if any(k > 0 for k in ks):
                    verify, _ = self._spec_fns("slot", backend)
                for k in ks:
                    if k == 0:
                        per_k[0] = _time(lambda: decode(self.params, cache, tok)[0])
                    else:
                        blk = jnp.zeros((mb, k + 1), jnp.int32)
                        per_k[k] = _time(
                            lambda blk=blk: verify(self.params, cache, blk)[0]
                        )
            cells[backend] = per_k
        return {
            "cells": cells,
            "ks": ks,
            "backends": names,
            "max_batch": mb,
            "layout": layout,
        }

    def scheduler(
        self,
        max_batch: int,
        *,
        seed: int = 0,
        kv_layout: Optional[str] = None,
        spec=None,
        attention_backend: Optional[str] = None,
        chunk_size: Optional[int] = None,
        telemetry=None,
        controller=None,
    ) -> Scheduler:
        """A fresh continuous-batching scheduler over ``max_batch`` rows
        (slots, or paged block tables), sharing this engine's stats,
        jitted steps, and decode plan. ``spec`` overrides the engine's
        default ``SpecConfig`` (``SpecConfig(k=0)`` disables);
        ``attention_backend`` overrides the engine default — each
        backend's jitted step family is cached separately, so switching
        is retrace-free after first use. ``chunk_size`` overrides the
        engine's chunked-prefill budget (``0`` disables for this call).
        ``telemetry`` overrides the engine's flight recorder for this
        scheduler (the instrumented-vs-off overhead benchmark serves the
        same warmed engine both ways). ``controller`` attaches an online
        adviser (DESIGN.md §9) that re-decides K/backend/admission from
        the windowed telemetry mid-run — the scheduler switches through
        this engine's pre-warmed step families (``prime()`` makes every
        switch a trace-cache hit); when the controller carries positive
        candidate depths and no ``spec`` is set, a default n-gram
        ``SpecConfig(k=max(ks))`` is installed so the margin and drafter
        cover the deepest arm."""
        layout = kv_layout or self.kv_layout
        if self.mesh is not None and layout != "paged":
            raise ValueError(
                "a serving mesh shards the paged block pool; the slotted "
                "layout has no head-partitioned storage — use "
                "kv_layout='paged' (or build the engine without mesh=)"
            )
        spec = spec if spec is not None else self.spec
        chunk = chunk_size if chunk_size is not None else self.chunk_size
        chunk = None if not chunk else int(chunk)
        backend = kernel_ops.resolve_attention_backend(
            attention_backend or self.attention_backend, mesh=self.mesh
        )
        if controller is not None:
            ctl_ks = tuple(getattr(controller, "ks", (0,)))
            kmax = max(ctl_ks) if ctl_ks else 0
            if kmax > 0 and (spec is None or spec.k < kmax):
                from repro.serve.speculative import SpecConfig

                spec = (
                    SpecConfig(k=kmax, drafter="ngram")
                    if spec is None
                    else dataclasses.replace(spec, k=kmax)
                )
        if self._decode_plan is not None and backend != self.attention_backend:
            # the plan's per-request fn captured the engine backend when
            # the region was advised; honoring a different per-call
            # backend here would silently run (and mislabel) the old one
            raise ValueError(
                f"attention_backend={backend!r} cannot override a decode "
                f"plan advised under {self.attention_backend!r} — re-advise "
                "decode_region() on an engine built with that backend"
            )
        paged_kw = {}
        if layout == "paged":
            decode_paged, prefill_prefix = self._paged_fns(backend)
            paged_kw = dict(
                block_size=self.block_size,
                num_blocks=self.num_blocks,
                prefix_cache=self.prefix_cache,
                paged_decode_fn=decode_paged,
                prefix_prefill_fn=prefill_prefix,
                mesh=self.mesh,
            )
        if spec is not None and spec.k > 0:
            verify, verify_paged = self._spec_fns(layout, backend)
            paged_kw.update(verify_fn=verify, paged_verify_fn=verify_paged)
        if chunk is not None:
            fns = self._step_fns(backend)
            if "prefill_chunk" not in fns:
                fns["prefill_chunk"] = self.model.jit_step("prefill_chunk", backend)
            paged_kw.update(chunk_prefill_fn=fns["prefill_chunk"])
        if controller is not None:
            # live backend re-decision resolves into THIS engine's shared
            # step-fn caches — after prime() every switch is a cache hit
            _spec, _chunk, _layout = spec, chunk, layout

            def _resolver(b):
                rb = kernel_ops.resolve_attention_backend(b, mesh=self.mesh)
                out = {"backend": rb, "decode": self._step_fns(rb)["decode"]}
                if _layout == "paged":
                    out["decode_paged"], _ = self._paged_fns(rb)
                if _spec is not None and _spec.k > 0:
                    out["verify"], out["verify_paged"] = self._spec_fns(_layout, rb)
                if _chunk is not None:
                    f = self._step_fns(rb)
                    if "prefill_chunk" not in f:
                        f["prefill_chunk"] = self.model.jit_step("prefill_chunk", rb)
                    out["prefill_chunk"] = f["prefill_chunk"]
                return out

            paged_kw.update(controller=controller, step_fn_resolver=_resolver)
        sched = Scheduler(
            self.model,
            self.params,
            max_batch=max_batch,
            max_seq=self.max_seq,
            temperature=self.temperature,
            decode_plan=self._decode_plan,
            stats=self.stats,
            seed=seed,
            kv_layout=layout,
            spec=spec,
            attention_backend=backend,
            chunk_size=chunk,
            prefill_fn=self._prefill,
            decode_fn=self._step_fns(backend)["decode"],
            plan_step_cache=self._plan_steps,
            telemetry=telemetry if telemetry is not None else self.telemetry,
            **paged_kw,
        )
        if layout == "paged" and not self._pool_recorded and sched.tel.enabled:
            self._record_pool(sched)
        return sched

    def _record_pool(self, sched) -> None:
        """Once per engine, with telemetry on: how the paged pool is
        stored — ``native_layout`` 1 when each stored head block is a
        whole number of (8, 128) tiles, the shape the TPU keeps in the
        layout the kernel reads in place (``attention.tokens_per_row``)
        — and its bytes, as gauges and one instant on the backend
        lane."""
        pool = sched.kv.pool
        rows, lanes = pool["k"].shape[3:]
        native = int(rows % 8 == 0 and lanes % 128 == 0)
        nbytes = sum(leaf.size * leaf.dtype.itemsize for leaf in pool.values())
        reg = self.stats.registry
        reg.gauge("pool.native_layout").set(native)
        reg.gauge("pool.bytes").set(nbytes)
        sched.tel.tracer.instant(
            "pool-layout", "backend", tid=TID_BACKEND,
            args={"native_layout": native, "pool_bytes": nbytes},
        )
        self._pool_recorded = True

    def serve(
        self,
        requests,
        *,
        max_batch: Optional[int] = None,
        seed: int = 0,
        kv_layout: Optional[str] = None,
        spec=None,
        attention_backend: Optional[str] = None,
        chunk_size: Optional[int] = None,
        mesh=None,
        telemetry=None,
        controller=None,
    ) -> dict:
        """Continuous-batching entry: drive ``requests`` (each with its
        own arrival time, prompt length, and token budget) to completion
        through a slotted or block-paged pool, optionally speculating
        ``spec.k`` draft tokens per verify (greedy streams unchanged —
        ``spec`` usually comes from ``speculative.advise_depth``),
        optionally overriding the attention backend for this run,
        optionally chunking prefill (``chunk_size`` tokens per step;
        ``0`` forces monolithic), and optionally closed-loop controlled
        (``controller=OnlineAdviser(...)`` re-decides K/backend/
        admission from live telemetry — see ``scheduler()``; run
        ``prime()`` first so every switch is retrace-free). ``mesh``
        must match the engine's serving mesh (the sharded step family
        and the replicated params are built against it at construction);
        passing it on a mesh-less engine adopts it, provided no step has
        been jitted yet. Returns rid → generated tokens."""
        if mesh is not None and mesh is not self.mesh:
            if self.mesh is not None:
                raise ValueError(
                    "serve(mesh=) differs from the engine's mesh — the "
                    "sharded step family is built against the constructor "
                    "mesh; create one engine per mesh"
                )
            # check BEFORE the jitted-steps guard: a mesh the GQA fallback
            # drops adopts nothing, so re-serving the same mesh through an
            # engine that has already jitted replicated steps is fine (and
            # warns only once — _check_mesh dedupes)
            checked = self._check_mesh(mesh)
            if checked is not None:
                if self._steps or self._prefill_prefix is not None:
                    raise ValueError(
                        "serve(mesh=) after steps were jitted without a mesh "
                        "— pass mesh= to the ServingEngine constructor instead"
                    )
                from jax.sharding import NamedSharding, PartitionSpec as P

                self.mesh = checked
                self.params = jax.device_put(
                    self.params, NamedSharding(self.mesh, P())
                )
                self.attention_backend = kernel_ops.resolve_attention_backend(
                    self.attention_backend, mesh=self.mesh
                )
        requests = list(requests)
        mb = max_batch or self.max_batch or max(1, min(8, len(requests)))
        return self.scheduler(
            mb, seed=seed, kv_layout=kv_layout, spec=spec,
            attention_backend=attention_backend, chunk_size=chunk_size,
            telemetry=telemetry, controller=controller,
        ).run(requests)

    def _sample(self, logits, key):
        if self.temperature <= 0.0:
            return jnp.argmax(logits, axis=-1)
        return jax.random.categorical(key, logits / self.temperature, axis=-1)

    def generate(self, prompts: jax.Array, n_steps: int, *, seed: int = 0, patch_embeds=None):
        """prompts [B, S0] → generated tokens [B, n_steps].

        Fixed-batch compatibility wrapper: B requests all arriving at
        t=0 into a B-slot pool — one full continuous batch. Stats start
        clean every call."""
        B = int(prompts.shape[0])
        if n_steps <= 0:
            self.stats.reset()
            return jnp.zeros((B, 0), jnp.int32)
        reqs = [
            Request(
                prompt=prompts[i],
                max_new_tokens=n_steps,
                patch_embeds=None if patch_embeds is None else patch_embeds[i],
            )
            for i in range(B)
        ]
        out = self.scheduler(B, seed=seed).run(reqs)
        return jnp.stack([jnp.asarray(out[r.rid]) for r in reqs], axis=0)
