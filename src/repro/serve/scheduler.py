"""Plan-aware continuous-batching scheduler over a slotted or paged pool.

Each ``step()`` (the serving analogue of one Relic task-queue tick):

  1. admits arrived queued requests in strict priority order — prefill
     written into the pool, first token sampled from the prefill logits
     (that instant is the request's TTFT). Slotted admission charges one
     slot per request; paged admission charges *blocks* (worst case
     reserved, physical blocks claimed lazily) and, on a prefix-cache
     hit, prefills only the un-cached prompt suffix — shared blocks are
     aliased, which is where the shared-prompt TTFT drop comes from.
     When the pool is dry and the queue head outranks a live row, the
     lowest-priority row is *preempted*: its committed full blocks
     re-register in the prefix trie (paged), so resumption is a
     suffix-only recompute, not a cold prefill;
  2. with ``chunk_size`` set, spends at most ``chunk_size`` prompt
     tokens of *chunked prefill* work — one ``prefill_chunk`` call per
     in-flight prompt slice, highest-priority first — so a long prompt
     never monopolizes a step: the paper's fine-grained co-scheduling
     argument applied to the decode loop, where the batched decode is
     the latency-critical stream and prefill is the heavy thread that
     must be sliced to interleave (chunk position is data, one trace
     per pow2 chunk bucket);
  3. runs ONE batched decode over the full fixed-shape row pool —
     through the engine's accepted ``RegionPlan`` via masked execution
     when one is set (slotted layout), or through the block tables
     (paged layout) — so neither jit nor the plan retraces as the
     number of live requests changes (liveness, block tables, and
     per-row lengths are data, not shape);
  4. samples the next token per live row, retires requests that hit
     their token budget or EOS, and frees their slots/blocks.

``step_ms`` times the whole step — admission + chunk work + decode —
so a monolithic prefill stall lands in the step tail it actually
causes (the ``serving.p99_step_ms`` the chunked mode exists to kill).

With speculation on (``spec=SpecConfig(k>0)``), step 2 becomes ONE
fused draft→verify round over the same fixed-shape pool: the draft
stream proposes K tokens per row, a single ``verify_step`` forward
prices all of them, greedy-equivalence acceptance commits the matched
prefix plus the corrected token, and the KV pools (target and any
draft-model pool) rewind the rejected tail via ``truncate_row``.
Acceptance counts are data, not shape — one verify trace per depth K
serves every acceptance pattern (DESIGN.md §3.2).

Dead rows still flow through the decode (static shapes); their outputs
are ignored (plain path), zeroed (masked plan path), or routed to the
null block (paged writes). Greedy decoding is batch-size independent
per row, so a half-full continuous batch reproduces the fixed-batch
baseline token-for-token — and the paged gather/scatter and the
speculative draft→verify→rollback round both reproduce the plain
greedy stream bitwise — the properties the serving tests pin.

Driving is open-loop: ``run()`` injects requests at their
``arrival_time`` regardless of completions, which is the honest way to
load a latency-critical server (closed-loop drivers hide queueing).
"""
from __future__ import annotations

import time
from dataclasses import dataclass
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops as kernel_ops
from repro.models.model import (
    CHUNKED_PREFILL_FAMILIES,
    PAD_PREFILL_FAMILIES,
    prefill_bucket,
)
from repro.serve.kv_cache import PagedKVCache, SlotKVCache
from repro.serve.request import (
    DECODE,
    FINISHED,
    PREEMPTED,
    PREFILL,
    Request,
    ServeStats,
)
from repro.serve.telemetry import TID_ADVISER, Telemetry, get_telemetry


def paged_step(model, name: str, backend, mesh=None):
    """The jitted paged decode-family step ``name`` for ``backend``:
    ``Model.sharded_paged_step`` under a serving mesh, else
    ``Model.jit_step``, the pool donated. The one exception is the
    ``interpret`` backend, whose steps keep their input pool: the
    benchmark's check plants a fault that hands a decode step's input
    pool back as its output (``bench/tests/test_check.py``,
    ``state_unchanged``), which must serve wrong tokens, not fail on a
    deleted buffer. The exception goes once that fault hands back a
    copy of the pool."""
    backend = kernel_ops.resolve_attention_backend(backend, mesh=mesh)
    donate = backend != "interpret"
    if mesh is not None:
        return model.sharded_paged_step(name, mesh, backend, donate=donate)
    return model.jit_step(name, backend, donate=donate)


@dataclass
class _ChunkState:
    """An in-flight chunked prefill: the request's private batch-1 dense
    cache (seeded from a prefix hit when there was one) plus the cursor
    into its effective prompt. Installed into the pool when ``pos``
    reaches the end."""

    req: Request
    cache: Any  # batch-1 dense cache, len tracks committed chunk rows
    prompt: np.ndarray  # effective prompt (prompt + committed tokens on resume)
    pos: int  # next un-prefilled prompt position (starts at the prefix hit)
    skip_blocks: int  # leading prefix-hit blocks write_prefill must not touch


class Scheduler:
    def __init__(
        self,
        model,
        params,
        *,
        max_batch: int,
        max_seq: int,
        temperature: float = 0.0,
        decode_plan=None,
        stats: Optional[ServeStats] = None,
        seed: int = 0,
        kv_layout: str = "slot",
        block_size: int = 8,
        num_blocks: Optional[int] = None,
        prefix_cache: bool = True,
        spec=None,
        attention_backend: Optional[str] = None,
        chunk_size: Optional[int] = None,
        prefill_fn=None,
        decode_fn=None,
        paged_decode_fn=None,
        prefix_prefill_fn=None,
        verify_fn=None,
        paged_verify_fn=None,
        chunk_prefill_fn=None,
        plan_step_cache: Optional[dict] = None,
        mesh=None,
        telemetry=None,
        controller=None,
        step_fn_resolver=None,
    ):
        self.model = model
        self.params = params
        self.max_seq = max_seq
        self.temperature = float(temperature)
        self.seed = seed
        # flight recorder (DESIGN.md §8): engine-provided, explicit, or
        # the module-global default (disabled). `_ton` is the hard
        # off-switch — every instrumentation site below guards on it,
        # so a disabled tracer leaves the hot path as it was.
        self.tel = telemetry if telemetry is not None else get_telemetry()
        # an online controller senses through the windowed metric rings,
        # so a controller-driven scheduler records into a private live
        # Telemetry when the caller left the recorder off — the module-
        # global off-switch contract (disabled ⇒ untouched hot path) is
        # unchanged for controller-less runs (DESIGN.md §9)
        self.controller = controller
        if controller is not None and not self.tel.enabled:
            self.tel = Telemetry(enabled=True, capacity=8192)
        self._ton = bool(self.tel.enabled)
        self.stats = stats if stats is not None else ServeStats()
        if self._ton:
            reg = self.stats.registry
            self._g_queue = reg.gauge("sched.queue_depth")
            self._g_active = reg.gauge("sched.active")
            self._g_occ = reg.gauge("pool.occupancy")
            self._g_free_blocks = reg.gauge("pool.free_blocks")
            self._c_admitted = reg.counter("sched.admitted")
            self._c_retraces = reg.counter("engine.retraces")
            self._s_chunk_util = reg.series("sched.chunk_util")
        self._step_seq = 0
        if kv_layout not in ("slot", "paged"):
            raise ValueError(f"kv_layout must be 'slot' or 'paged', got {kv_layout!r}")
        self.kv_layout = kv_layout
        self._mesh = mesh
        if kv_layout == "paged":
            if decode_plan is not None:
                raise ValueError(
                    "decode plans route through the slotted layout; "
                    "use kv_layout='slot' to serve through a RegionPlan"
                )
            self.kv = PagedKVCache(
                model,
                max_batch,
                max_seq,
                block_size=block_size,
                num_blocks=num_blocks,
                prefix_cache=prefix_cache,
                mesh=mesh,
                metrics=self.stats.registry if self._ton else None,
            )
        else:
            self.kv = SlotKVCache(model, max_batch, max_seq)
        # resolve the decode/verify attention backend ONCE, before any
        # jit: the jitted step family binds it statically, so backend
        # choice can never leak between traces (DESIGN.md §4). Engine-
        # made schedulers receive already-bound fns instead.
        self.attention_backend = kernel_ops.resolve_attention_backend(attention_backend)
        # pow2 prompt-shape bucketing (pad + mask): one prefill trace per
        # bucket instead of one per distinct prompt length
        self._bucket = model.cfg.family in PAD_PREFILL_FAMILIES
        if chunk_size is not None:
            chunk_size = int(chunk_size)
            if chunk_size < 1 or chunk_size & (chunk_size - 1):
                raise ValueError(
                    f"chunk_size must be a power of two >= 1, got {chunk_size} "
                    "(chunks pad to pow2 buckets; a non-pow2 cap would add a "
                    "one-off trace per partial chunk)"
                )
            if model.cfg.family not in CHUNKED_PREFILL_FAMILIES:
                raise ValueError(
                    f"chunked prefill needs a {CHUNKED_PREFILL_FAMILIES} family "
                    f"(length-addressed KV cache), got {model.cfg.family!r}"
                )
        self.chunk_size = chunk_size
        self._queue: list[Request] = []  # sorted by (-priority, arrival_time, rid)
        self._active: dict[int, Request] = {}  # row → request
        self._chunking: dict[int, _ChunkState] = {}  # row → in-flight chunked prefill
        self._n_admitted = 0  # per-run sampling-key ordinal (not the global rid)
        self._ordinals: dict[int, int] = {}  # rid → ordinal, admission → first sample
        self._tok = jnp.zeros((max_batch,), jnp.int32)  # last token per row
        self._keys = jax.vmap(jax.random.PRNGKey)(jnp.arange(max_batch, dtype=jnp.uint32))
        # jitted steps are engine-owned when schedulers are engine-made, so
        # repeated generate()/serve() calls reuse compiled executables
        self._prefill = prefill_fn or jax.jit(
            lambda p, t, **kw: model.prefill(p, t, max_seq, **kw)
        )
        be = self.attention_backend
        self._decode = decode_fn or model.jit_step("decode_step", be)
        self._decode_paged = paged_decode_fn or (
            paged_step(model, "decode_step_paged", be) if kv_layout == "paged" else None
        )
        self._prefill_prefix = prefix_prefill_fn or (
            jax.jit(
                lambda p, t, pk, pv, **kw: model.prefill_with_prefix(
                    p, t, pk, pv, max_seq, **kw
                )
            )
            if kv_layout == "paged"
            else None
        )
        self._prefill_chunk = chunk_prefill_fn or (
            model.jit_step("prefill_chunk", be) if chunk_size is not None else None
        )
        # speculative decode: a draft stream + the fused verify step
        self.spec = spec if (spec is not None and spec.k > 0) else None
        self._drafter = None
        self._verify = self._verify_paged = None
        if self.spec is not None:
            from repro.models.model import SPEC_FAMILIES

            if model.cfg.family not in SPEC_FAMILIES:
                raise ValueError(
                    f"speculative decode needs a {SPEC_FAMILIES} family "
                    f"(rewindable KV cache), got {model.cfg.family!r}"
                )
            if self.temperature > 0.0:
                raise ValueError(
                    "speculative decode is greedy-equivalence only; "
                    "serve with temperature=0"
                )
            if decode_plan is not None:
                raise ValueError(
                    "speculation and decode plans both rewrite the decode "
                    "step — set one or the other"
                )
            self._drafter = self.spec.make_drafter(attention_backend=be)
            self._drafter.bind(max_batch, max_seq)
            self._verify = verify_fn or model.jit_step("verify_step", be)
            self._verify_paged = paged_verify_fn or (
                paged_step(model, "verify_step_paged", be) if kv_layout == "paged" else None
            )
        # live speculation depth: SpecConfig.k is the *maximum* (it sizes
        # the admission margin, the drafter overhang, and the deepest
        # pre-warmed verify trace); a controller re-decides the serving
        # depth within [0, spec.k] mid-run, retrace-free
        self._live_k = self.spec.k if self.spec is not None else 0
        self._fn_resolver = step_fn_resolver
        self._local_fns: dict[str, dict] = {}  # standalone resolver cache
        self._ctl_steps = 0
        self._admit_budget: Optional[int] = None
        if controller is not None:
            self._wire_controller(controller)
        self._plan_steps = plan_step_cache if plan_step_cache is not None else {}
        self._decode_plan = None
        self._t0: Optional[float] = None
        if decode_plan is not None:
            self.set_decode_plan(decode_plan)
        if self._ton:
            # retrace watch: jitted-step compile-cache sizes, sampled at
            # step boundaries — growth mid-run means a shape escaped its
            # trace family (the no-retrace contract the chunked tests pin).
            # Baseline now: engine-shared fns arrive pre-warmed, and those
            # compiles are not this run's retraces.
            self._rebuild_trace_watch()

    def _rebuild_trace_watch(self) -> None:
        """(Re)collect the jitted step fns under retrace watch and
        re-baseline their compile-cache sizes — called at construction
        and after a live backend swap installs a different fn family."""
        self._traced_fns = [
            f
            for f in (
                self._prefill,
                self._decode,
                self._decode_paged,
                self._prefill_prefix,
                self._prefill_chunk,
                self._verify,
                self._verify_paged,
            )
            if f is not None and hasattr(f, "_cache_size")
        ]
        self._cache_size_seen = sum(f._cache_size() for f in self._traced_fns)

    # ------------------------------------------------------------------
    # online adaptive adviser (DESIGN.md §9): observe → decide → apply
    def _wire_controller(self, controller) -> None:
        """Validate the controller's candidate arms against this
        scheduler's capacity and apply its initial arm.  The deepest
        candidate must fit inside ``spec.k`` (the admission margin and
        drafter overhang were sized for it), and a multi-backend
        controller needs a step-fn resolver (engine-made schedulers get
        the engine's pre-warmed families; standalone ones fall back to
        a scheduler-local cache)."""
        ks = tuple(getattr(controller, "ks", (0,)))
        kmax = max(ks) if ks else 0
        if kmax > 0:
            if self.spec is None:
                raise ValueError(
                    f"controller ks={ks} include positive depths but the "
                    "scheduler has no speculation configured — build it with "
                    "spec=SpecConfig(k=max(ks)) so the margin/drafter cover "
                    "the deepest arm"
                )
            if kmax > self.spec.k:
                raise ValueError(
                    f"controller kmax={kmax} exceeds spec.k={self.spec.k} — "
                    "the admission margin and drafter overhang are sized by "
                    "spec.k, so the deepest candidate must fit inside it"
                )
        backends = getattr(controller, "backends", None)
        if backends is None:
            controller.backends = (self.attention_backend,)
        else:
            # resolve candidate names once (e.g. "kernel" → "interpret"
            # on CPU) so controller arms and scheduler state agree
            controller.backends = tuple(
                dict.fromkeys(
                    kernel_ops.resolve_attention_backend(b, mesh=self._mesh)
                    for b in backends
                )
            )
        init_k = getattr(controller, "initial_k", None)
        if init_k is not None and int(init_k) != self._live_k:
            self._set_live_k(int(init_k))

    def _resolve_fns(self, backend: str) -> dict:
        """Step-fn family for ``backend``: the engine's shared cache
        when this scheduler is engine-made, else a local jit cache (the
        retrace-free switching contract only holds for pre-warmed
        engine families — see ``ServingEngine.prime``)."""
        if self._fn_resolver is not None:
            return self._fn_resolver(backend)
        backend = kernel_ops.resolve_attention_backend(backend, mesh=self._mesh)
        fns = self._local_fns.get(backend)
        if fns is None:
            model = self.model
            fns = {"backend": backend, "decode": model.jit_step("decode_step", backend)}
            if self.kv_layout == "paged":
                fns["decode_paged"] = paged_step(model, "decode_step_paged", backend)
            if self.spec is not None:
                fns["verify"] = model.jit_step("verify_step", backend)
                if self.kv_layout == "paged":
                    fns["verify_paged"] = paged_step(model, "verify_step_paged", backend)
            if self.chunk_size is not None:
                fns["prefill_chunk"] = model.jit_step("prefill_chunk", backend)
            self._local_fns[backend] = fns
        return fns

    def _set_backend(self, backend: str) -> None:
        """Swap the decode/verify attention backend live: a dictionary
        lookup into the pre-built step family — pool state (KV leaves,
        block tables, lengths) is backend-independent, so nothing else
        moves. The trace watch re-baselines so the swap itself is never
        miscounted as a retrace (and an un-warmed family's first-call
        compiles still are)."""
        if backend == self.attention_backend:
            return
        fns = self._resolve_fns(backend)
        self.attention_backend = fns.get("backend", backend)
        self._decode = fns["decode"]
        self._decode_paged = fns.get("decode_paged", self._decode_paged)
        self._verify = fns.get("verify", self._verify)
        self._verify_paged = fns.get("verify_paged", self._verify_paged)
        self._prefill_chunk = fns.get("prefill_chunk", self._prefill_chunk)
        if self._ton:
            self._rebuild_trace_watch()

    def _set_live_k(self, k: int) -> None:
        """Re-decide the speculation depth live. Every depth in
        [1, spec.k] hits a distinct [max_batch, k+1] verify trace in the
        SAME jitted fn (jit caches per input shape), so after priming
        the transition is free. The stateful-drafter catch-up: rows that
        decoded plain while K was 0 advanced the target cache without
        the draft cache seeing their tokens, so a 0→K transition
        re-syncs every active row via ``on_admit`` (re-prefilling the
        committed history, pow2-bucketed — a bounded, off-hot-path
        cost). K→K′ moves between positive depths need no sync: rollback
        leaves the draft cache exactly on the committed stream."""
        k = int(k)
        if k == self._live_k:
            return
        if k < 0 or (k > 0 and (self.spec is None or k > self.spec.k)):
            cap = self.spec.k if self.spec is not None else 0
            raise ValueError(f"live k={k} outside [0, {cap}]")
        was, self._live_k = self._live_k, k
        if self._drafter is not None and k > 0:
            if hasattr(self._drafter, "set_k"):
                self._drafter.set_k(k)
            if was == 0:
                for row, req in self._active.items():
                    self._drafter.on_admit(row, req)

    def _controller_tick(self) -> None:
        """One observe→decide→apply round, every ``decision_interval``
        working steps: read the windowed sensor vector, let the
        controller price the arms, apply the verdict, and record the
        decision on the telemetry adviser lane + the controller gauges
        (current K/backend, switches, dwell) — the paper's audit trail,
        live."""
        c = self.controller
        self._ctl_steps += 1
        if self._ctl_steps % max(1, int(getattr(c, "decision_interval", 8))):
            return
        summary = self.stats.registry.window_summary(int(getattr(c, "window", 16)))
        d = c.decide(
            summary,
            k_live=self._live_k,
            backend_live=self.attention_backend,
            step=self._ctl_steps,
        )
        self._apply_decision(d)
        reg = self.stats.registry
        reg.counter("controller.decisions").inc()
        if d.switched:
            reg.counter("controller.switches").inc()
        reg.gauge("controller.k").set(float(self._live_k))
        backends = getattr(c, "backends", None) or ()
        reg.gauge("controller.backend_index").set(
            float(backends.index(self.attention_backend))
            if self.attention_backend in backends
            else -1.0
        )
        reg.gauge("controller.dwell_remaining").set(
            float(getattr(c, "dwell_remaining", 0))
        )
        self.stats.controller_info = {
            "decisions": len(getattr(c, "decisions", ())) or self._ctl_steps,
            "switches": int(getattr(c, "n_switches", 0)),
            "k": self._live_k,
            "backend": self.attention_backend,
            "admit_budget": self._admit_budget,
            "dwell_remaining": int(getattr(c, "dwell_remaining", 0)),
        }
        self.tel.tracer.instant(
            "online-decision", "adviser", tid=TID_ADVISER, args=d.to_json()
        )

    def _apply_decision(self, d) -> None:
        """Apply one ``Decision``: backend first (the verify trace the
        new K lands on must belong to the new family), then depth, then
        the admission budget."""
        if d.backend is not None:
            self._set_backend(d.backend)
        if d.k is not None:
            self._set_live_k(d.k)
        self._admit_budget = (
            max(1, int(d.admit_budget)) if d.admit_budget is not None else None
        )

    # ------------------------------------------------------------------
    # plan routing (PR 1 contract, now over the active-slot view)
    def set_decode_plan(self, plan) -> None:
        """Route the pool decode through an accepted ``RegionPlan`` (as
        produced by advising ``decode_region`` — stack combine only,
        since request order is externally visible)."""
        if plan is not None and self.kv_layout == "paged":
            raise ValueError("decode plans are not supported on the paged layout")
        if plan is not None and self.spec is not None:
            raise ValueError(
                "speculation and decode plans both rewrite the decode step "
                "— set one or the other"
            )
        if plan is not None and plan.key.combine != "stack":
            raise ValueError(
                "decode plan must preserve per-request order (combine='stack')"
            )
        self._decode_plan = plan

    def _plan_decode(self, cache, tok, mask):
        cache_key = (self._decode_plan.key, self.kv.max_batch)
        if cache_key not in self._plan_steps:
            # pool spec is invariant across steps: fold the batch-axis
            # shuffling into one jitted step; the plan's masked executor
            # keeps a single trace across live-count changes, and the
            # step itself is cached per (plan, pool size) — engine-wide
            # when the scheduler is engine-made
            leaves, treedef = jax.tree.flatten(cache)
            axes = tuple(jax.tree.leaves(self.model.cache_batch_axes(cache)))
            assert len(axes) == len(leaves)
            plan = self._decode_plan

            def step(cache, tok, mask):
                leaves = jax.tree.leaves(cache)
                items = (tok, [jnp.moveaxis(l, ax, 0) for l, ax in zip(leaves, axes)])
                logits, new_leaves = plan.execute_masked(items, mask)
                new_cache = jax.tree.unflatten(
                    treedef,
                    [jnp.moveaxis(l, 0, ax) for l, ax in zip(new_leaves, axes)],
                )
                return logits, new_cache

            self._plan_steps[cache_key] = jax.jit(step)
        return self._plan_steps[cache_key](cache, tok, mask)

    # ------------------------------------------------------------------
    # clock: seconds since run start (arrival_time's frame)
    def _clock(self) -> float:
        if self._t0 is None:
            self._t0 = time.perf_counter()
        return time.perf_counter() - self._t0

    # ------------------------------------------------------------------
    # lifecycle transitions
    @property
    def _spec_margin(self) -> int:
        """Row capacity a speculative verify can transiently overhang:
        the last verify before a request retires may write K rejected
        entries past its final committed length."""
        return self.spec.k if self.spec is not None else 0

    @staticmethod
    def _queue_key(req: Request):
        """Strict priority (higher first), then arrival, then rid."""
        return (-req.priority, req.arrival_time, req.rid)

    def submit(self, req: Request) -> None:
        need = int(jnp.asarray(req.prompt).shape[0]) + req.max_new_tokens
        if req.patch_embeds is not None:
            need += int(jnp.asarray(req.patch_embeds).shape[0])
        need += self._spec_margin
        if self.chunk_size is not None and req.patch_embeds is not None:
            self.stats.rejected_submissions += 1
            raise ValueError(
                f"request {req.rid}: chunked prefill cannot split patch "
                "embeddings (not token-addressable) — serve VLM requests "
                "with chunk_size=None"
            )
        if need > self.max_seq:
            # past max_seq the cache write clamps and silently corrupts
            # the newest KV entry — fail loudly at submission instead
            self.stats.rejected_submissions += 1
            margin = (
                f" (incl. speculative margin K={self._spec_margin})"
                if self._spec_margin
                else ""
            )
            raise ValueError(
                f"request {req.rid}: prompt + max_new_tokens = {need}{margin} "
                f"exceeds the row capacity max_seq={self.max_seq}"
            )
        if self.kv_layout == "paged":
            # a request whose block budget can never fit would sit at the
            # queue head forever (admission is head-of-line) — reject it
            # loudly, in the block currency admission actually charges
            nb = self.kv.blocks_for(need)
            if nb > self.kv.num_blocks:
                self.stats.rejected_submissions += 1
                raise ValueError(
                    f"request {req.rid}: needs {nb} KV blocks "
                    f"({need} tokens at block_size={self.kv.block_size}) but the "
                    f"pool holds {self.kv.num_blocks} blocks total "
                    f"({self.kv.n_free_blocks} free) — it can never be admitted"
                )
        req.state = "queued"
        self._queue.append(req)
        self._queue.sort(key=self._queue_key)
        if self._ton:
            self.tel.tracer.async_begin(
                "request",
                req.rid,
                "request",
                args={
                    "prompt_len": int(np.asarray(req.prompt).shape[0]),
                    "max_new_tokens": req.max_new_tokens,
                    "priority": req.priority,
                },
            )

    def _sample_row(self, logits_row, key):
        if self.temperature <= 0.0:
            return jnp.argmax(logits_row, axis=-1)
        return jax.random.categorical(key, logits_row / self.temperature, axis=-1)

    def _start_decode(self, req: Request, row: int, logits_row, now: float) -> None:
        """Shared admission tail: sample the first token from the prefill
        logits (TTFT is this instant) and arm the decode row."""
        key = jax.random.fold_in(
            jax.random.PRNGKey(self.seed), self._ordinals.pop(req.rid)
        )
        key, sub = jax.random.split(key)
        tok0 = int(self._sample_row(logits_row, sub))
        req.t_first = self._clock()  # first token exists from here
        if self._ton:
            self.tel.tracer.async_instant("first_token", req.rid, "request")
        req.tokens.append(tok0)
        req.state = DECODE
        self._tok = self._tok.at[row].set(tok0)
        self._keys = self._keys.at[row].set(key)
        self._active[row] = req
        if len(req.tokens) >= req.max_new_tokens or tok0 == req.eos_id:
            self._retire(req, self._clock())

    def _note_admitted(self, req: Request, now: float) -> None:
        """Shared admission bookkeeping: queue wait ends at the FIRST
        admission (re-admissions after preemption don't reset it); the
        sampling-key ordinal is assigned once — a resumed request
        continues its saved key chain instead."""
        if req.t_first_admit is None:
            req.t_first_admit = now
        if self._ton:
            self._c_admitted.inc()
            self.tel.tracer.async_instant(
                "resume" if req.tokens else "admit", req.rid, "request"
            )
        if not req.tokens:
            # key by the per-run admission ordinal, not the process-global
            # rid: the same seed reproduces the same tokens across runs
            self._ordinals[req.rid] = self._n_admitted
            self._n_admitted += 1
        req.state, req.t_admit = PREFILL, now

    @staticmethod
    def _effective_prompt(req: Request) -> np.ndarray:
        """The tokens a (re-)prefill must commit: the prompt plus, on
        resume, every generated token except the pending last one (the
        invariant ``committed len = S + n - 1`` — the newest token is
        fed to decode, never pre-written)."""
        prompt = np.asarray(req.prompt)
        if not req.tokens:
            return prompt
        return np.concatenate(
            [prompt, np.asarray(req.tokens[:-1], prompt.dtype)]
        ) if len(req.tokens) > 1 else prompt

    def _resume_decode(self, req: Request, row: int, now: float) -> None:
        """Re-arm a preempted request mid-stream: the pending token and
        the saved per-row sampling key restore, so the continued decode
        is token-identical to the uninterrupted run."""
        del now
        req.state = DECODE
        self._tok = self._tok.at[row].set(int(req.tokens[-1]))
        if req.sample_key is not None:
            self._keys = self._keys.at[row].set(
                jnp.asarray(np.asarray(req.sample_key), jnp.uint32)
            )
        self._active[row] = req

    def _admit(self, reqs: list, now: float) -> None:
        """Admit a wave of arrived requests into slots: same-bucket
        prompts prefill as ONE batched call (pow2 padding makes mixed
        lengths share both the call and the trace), each row then
        written into its own slot via ``read_cache_slot``. Resumed
        requests re-prefill their effective prompt (prompt + committed
        tokens) and continue their stream."""
        for req in reqs:
            self._note_admitted(req, now)
        groups: dict = {}
        for req in reqs:
            eff = self._effective_prompt(req)
            pe = None if req.patch_embeds is None else tuple(jnp.asarray(req.patch_embeds).shape)
            n_lead = 0 if pe is None else pe[0]
            W = len(eff)
            if self._bucket:
                W = prefill_bucket(W)
                if n_lead + W > self.max_seq:  # cache write would clamp
                    W = len(eff)
            groups.setdefault((W, pe), []).append((req, eff))
        for (W, pe), group in groups.items():
            kw = {}
            if pe is not None:
                kw["patch_embeds"] = jnp.stack(
                    [jnp.asarray(r.patch_embeds) for r, _ in group]
                )
            if self._bucket:
                mat = np.zeros((len(group), W), np.int32)
                for i, (_, eff) in enumerate(group):
                    mat[i, : len(eff)] = eff
                prompts = jnp.asarray(mat)
                kw["prompt_len"] = jnp.asarray(
                    [len(eff) for _, eff in group], jnp.int32
                )
            else:
                prompts = jnp.stack([jnp.asarray(eff) for _, eff in group])
            with self.tel.span("serve.prefill"):
                logits, cache = self._prefill(self.params, prompts, **kw)
            for i, (req, eff) in enumerate(group):
                with self.tel.span(
                    "serve.admit_request", rid=req.rid, prompt_tokens=len(eff), hit_tokens=0
                ):
                    slot = self.kv.alloc(req.rid)
                    req.slot = slot
                    self.kv.write(slot, self.model.read_cache_slot(cache, i))
                    if req.tokens:
                        self.stats.recomputed_tokens += len(eff)
                        self._resume_decode(req, slot, now)
                    else:
                        self._start_decode(req, slot, logits[i], now)
                    if self._drafter is not None and self._live_k > 0 and not req.finished:
                        self._drafter.on_admit(slot, req)

    def _start_chunk_slot(self, req: Request, now: float) -> None:
        """Slotted chunked admission: claim the slot now, prefill later
        in ``chunk_size`` slices. The slot's pool row holds junk until
        the install (its decode outputs are ignored — the row is not in
        ``_active`` — and ``kv.write`` overwrites everything)."""
        eff = self._effective_prompt(req)
        with self.tel.span(
            "serve.admit_request", rid=req.rid, prompt_tokens=len(eff), hit_tokens=0
        ):
            self._note_admitted(req, now)
            slot = self.kv.alloc(req.rid)
            req.slot = slot
            if req.tokens:
                self.stats.recomputed_tokens += len(eff)
            self._chunking[slot] = _ChunkState(
                req=req,
                cache=self.model.init_cache(1, self.max_seq),
                prompt=eff,
                pos=0,
                skip_blocks=0,
            )

    def _try_admit_paged(self, req: Request, now: float) -> bool:
        """Paged admission, one request at a time: prefix-match the
        effective prompt, charge the block budget, prefill only the
        un-cached suffix on a hit (a resumed request's committed blocks
        re-registered at preemption, so its resume is usually one
        partial tail block of recompute). Returns False when the
        row/block budget does not fit yet (the request stays queued).
        With chunking on, admission only *reserves*: the prompt runs
        through ``_prefill_phase`` in ``chunk_size`` slices and the trie
        registration waits until the blocks actually hold KV."""
        resume = bool(req.tokens)
        eff = self._effective_prompt(req)
        n_cache = len(eff)
        # the block budget carries the speculative margin: the rejected
        # tail of a verify transiently occupies blocks past the final
        # committed length, and lazy tail claims must never fail. A
        # resume charges only the remaining budget (+1: the pending
        # token still needs its row), so S_eff + budget is the same
        # worst case as the fresh admission's.
        budget = req.max_new_tokens + self._spec_margin
        if resume:
            budget -= len(req.tokens) - 1
        with self.tel.span("serve.prefix_match"):
            tokens = tuple(int(t) for t in eff)
            if req.patch_embeds is not None:
                # patch embeddings occupy cache rows ahead of the tokens and
                # are not token-addressable — no prefix matching for them
                n_cache += int(jnp.asarray(req.patch_embeds).shape[0])
                tokens = ()
            got = self.kv.try_admit(
                req.rid,
                tokens,
                budget,
                n_tokens=n_cache,
                register=self.chunk_size is None,
            )
        if got is None:
            return False
        row, hit_ids = got
        hit = len(hit_ids) * self.kv.block_size
        with self.tel.span(
            "serve.admit_request", rid=req.rid, prompt_tokens=n_cache, hit_tokens=hit
        ):
            self._admit_paged_row(req, row, eff, hit_ids, now)
        return True

    def _admit_paged_row(self, req: Request, row: int, eff, hit_ids, now: float) -> None:
        """The admitted request's work: with chunking, its prefill state;
        else the prefill of the un-cached suffix (gathering the hit
        prefix first), its install into the pool, and its first token."""
        resume = bool(req.tokens)
        self._note_admitted(req, now)
        req.slot = row
        hit = len(hit_ids) * self.kv.block_size
        if resume:
            self.stats.recomputed_tokens += int(self.kv.cache_len[row]) - hit
        else:
            # prefix_hit stays the FIRST admission's hit: it feeds the
            # prompt-token prefix_hit_rate, where resume recompute
            # accounting would double-count the same prompt tokens
            req.prefix_hit = hit
        if hit:
            with self.tel.span("serve.gather_prefix"):
                pk, pv = self.kv.gather_prefix(hit_ids)
        if self.chunk_size is not None:
            if hit:
                cache = self.model.seed_cache_with_prefix(pk, pv, self.max_seq)
            else:
                cache = self.model.init_cache(1, self.max_seq)
            self._chunking[row] = _ChunkState(
                req=req, cache=cache, prompt=eff, pos=hit,
                skip_blocks=len(hit_ids),
            )
            return
        prompt_dev = jnp.asarray(eff)
        if hit:
            suffix = prompt_dev[hit:]
            Ssuf = int(suffix.shape[0])
            kw = {}
            if self._bucket:
                W = prefill_bucket(Ssuf)
                if hit + W > self.max_seq:
                    W = Ssuf
                padded = np.zeros((1, W), np.int32)
                padded[0, :Ssuf] = np.asarray(suffix)
                suffix = jnp.asarray(padded)[0]
                kw["suffix_len"] = jnp.asarray([Ssuf], jnp.int32)
            with self.tel.span("serve.prefill"):
                logits, cache = self._prefill_prefix(
                    self.params, suffix[None, :], pk, pv, **kw
                )
        else:
            kw = {}
            if req.patch_embeds is not None:
                kw["patch_embeds"] = jnp.asarray(req.patch_embeds)[None]
            S = int(prompt_dev.shape[0])
            n_lead = 0 if req.patch_embeds is None else int(
                jnp.asarray(req.patch_embeds).shape[0]
            )
            if self._bucket:
                W = prefill_bucket(S)
                if n_lead + W > self.max_seq:
                    W = S
                padded = np.zeros((1, W), np.int32)
                padded[0, :S] = np.asarray(prompt_dev)
                prompt_dev = jnp.asarray(padded)[0]
                kw["prompt_len"] = jnp.asarray([S], jnp.int32)
            with self.tel.span("serve.prefill"):
                logits, cache = self._prefill(self.params, prompt_dev[None, :], **kw)
        self._write_prefill(row, cache, len(hit_ids))
        if resume:
            self._resume_decode(req, row, now)
        else:
            self._start_decode(req, row, logits[0], now)
        if self._drafter is not None and self._live_k > 0 and not req.finished:
            self._drafter.on_admit(row, req)

    # ------------------------------------------------------------------
    # priority preemption
    def _maybe_preempt(self, head: Request) -> bool:
        """Evict the lowest-priority live row to make room for ``head``
        — only when ``head`` STRICTLY outranks it (equal priorities
        never preempt each other: that would livelock two requests
        trading the same row). Ties break toward the most recently
        admitted victim (least sunk work lost). Returns True when a row
        was freed (the caller retries admission)."""
        victims = [
            (req.priority, -(req.t_admit or 0.0), -req.rid, row)
            for row, req in self._active.items()
            if req.priority < head.priority
        ]
        if not victims:
            return False
        victims.sort()
        self._preempt(victims[0][3])
        return True

    def _preempt(self, row: int) -> None:
        """Evict a live decode row, keeping its stream resumable: the
        sampling key and generated tokens persist on the request; the
        paged layout re-registers its committed full blocks in the
        prefix trie (they park instead of vanishing), so the resume
        prefix-matches the whole committed history and recomputes only
        the partial tail block."""
        req = self._active.pop(row)
        req.sample_key = np.asarray(self._keys[row])
        committed = None
        if self.kv_layout == "paged" and req.patch_embeds is None:
            committed = tuple(int(t) for t in self._effective_prompt(req))
        self.kv.preempt_row(row, committed)
        req.state = PREEMPTED
        req.slot = None
        req.preemptions += 1
        self.stats.n_preemptions += 1
        if self._ton:
            self.tel.tracer.async_instant(
                "preempt", req.rid, "request",
                args={"committed_tokens": len(req.tokens)},
            )
        self._queue.append(req)
        self._queue.sort(key=self._queue_key)

    def _retire(self, req: Request, now: float) -> None:
        req.state, req.t_finish = FINISHED, now
        self.stats.record(req)
        if self._ton:
            self.tel.tracer.async_end(
                "request",
                req.rid,
                "request",
                args={
                    "tokens": len(req.tokens),
                    "preemptions": req.preemptions,
                    "prefix_hit": req.prefix_hit,
                },
            )
        if self.kv_layout == "paged":
            self.kv.free_row(req.slot)
        else:
            self.kv.free(req.slot)
        del self._active[req.slot]

    # ------------------------------------------------------------------
    def _decode_pool(self, mask):
        """One batched decode over the full row pool; returns logits and
        installs the new cache."""
        if self.kv_layout == "paged":
            with self.tel.span("serve.decode_inputs"):
                for row in self._active:
                    self.kv.ensure_tail(row)
                pool, tables, lens = self.kv.kernel_inputs()
            with self.tel.span("serve.decode", **self._decode_args()) as sp:
                logits, new_pool = self._decode_paged(
                    self.params, pool, tables, lens, self._tok[:, None]
                )
                logits.block_until_ready()
                self._note_donated(sp, pool)
            self.kv.pool = new_pool
            return logits
        with self.tel.span("serve.decode", **self._decode_args()):
            if self._decode_plan is not None:
                logits, new_cache = self._plan_decode(
                    self.kv.cache, self._tok, jnp.asarray(mask)
                )
            else:
                logits, new_cache = self._decode(
                    self.params, self.kv.cache, self._tok[:, None]
                )
            logits.block_until_ready()
        self.kv.cache = new_cache
        return logits

    def _note_donated(self, span, pool) -> None:
        """The span's ``donated`` arg: 1 when the step consumed its input
        pool (every leaf deleted by donation), read only when the tracer
        is on."""
        if self._ton:
            span.note(donated=int(all(leaf.is_deleted() for leaf in jax.tree.leaves(pool))))

    def _decode_args(self) -> dict:
        """The decode span's args, read from host state only when the
        tracer is on: the rows decoded and, on the paged layout (whose
        lengths live on the host), the context they attend over — each
        live row's committed length plus the token being decoded."""
        if not self._ton:
            return {}
        rows = list(self._active)
        args = {"live_rows": len(rows)}
        if self.kv_layout == "paged":
            args["context_tokens"] = int(self.kv.cache_len[rows].sum()) + len(rows)
        return args

    def _spec_step(self) -> None:
        """One fused draft→verify speculation round over the full pool.

        The draft stream proposes K tokens per row; ONE ``verify_step``
        forward (fixed [max_batch, K+1] shape — acceptance is data)
        returns per-position target logits; greedy-equivalence
        acceptance commits each row's matched draft prefix plus the
        corrected argmax token, so the emitted stream is token-for-token
        the plain greedy stream. The KV pools then rewind the rejected
        tail: slot lengths truncate in one vectorized update, paged rows
        release their un-needed claimed tail blocks, and a stateful
        drafter rolls back by the same per-row vector (DESIGN.md §3.2).
        """
        K = self._live_k
        t_start = time.perf_counter()
        with self.tel.span("serve.draft", k=K):
            drafts = self._drafter.propose(self._active, np.asarray(self._tok))
        t_draft = time.perf_counter()
        self.stats.draft_ms.append((t_draft - t_start) * 1e3)
        tokens_in = jnp.concatenate(
            [self._tok[:, None], jnp.asarray(drafts, jnp.int32)], axis=1
        )
        if self.kv_layout == "paged":
            with self.tel.span("serve.decode_inputs"):
                for row in self._active:
                    self.kv.ensure_tail_n(row, K + 1)
                pool, tables, lens = self.kv.kernel_inputs()
            with self.tel.span("serve.verify", k=K) as sp:
                logits, new_pool = self._verify_paged(
                    self.params, pool, tables, lens, tokens_in
                )
                logits.block_until_ready()
                self._note_donated(sp, pool)
            self.kv.pool = new_pool
        else:
            with self.tel.span("serve.verify", k=K):
                logits, new_cache = self._verify(self.params, self.kv.cache, tokens_in)
                logits.block_until_ready()
            self.kv.cache = new_cache
        greedy = np.asarray(jnp.argmax(logits, axis=-1))  # [max_batch, K+1]
        self.stats.verify_ms.append((time.perf_counter() - t_draft) * 1e3)
        self.stats.spec_k = K
        self.stats.spec_steps += 1
        with self.tel.span("serve.sample"):
            self._accept(drafts, greedy, K)

    def _accept(self, drafts, greedy, K: int) -> None:
        """Greedy-equivalence acceptance of one verify round: commit
        each row's matched draft prefix plus the corrected token, retire
        rows that reach their budget or EOS, and rewind the rejected
        tails of the pools and the drafter."""
        rej = np.full((self.kv.max_batch,), K + 1, np.int32)
        for row, req in list(self._active.items()):
            d, g = drafts[row], greedy[row]
            a = 0
            while a < K and d[a] == g[a]:
                a += 1
            self.stats.spec_proposed += K
            self.stats.spec_accepted += a
            stream = [int(t) for t in d[:a]] + [int(g[a])]
            done = False
            for t in stream:
                req.tokens.append(t)
                if len(req.tokens) >= req.max_new_tokens or t == req.eos_id:
                    done = True
                    break
            if done:
                # budget/EOS mid-stream: the row retires, its junk tail
                # (and, paged, all its blocks) goes with it
                self._retire(req, self._clock())
            else:
                self._tok = self._tok.at[row].set(stream[-1])
                # valid new entries: the pending token + a accepted
                # drafts (the corrected token is pending, not cached)
                rej[row] = K - a
                if self.kv_layout == "paged":
                    self.kv.advance_n(row, K + 1)
                    self.kv.truncate_row(row, K - a)
        if self.kv_layout != "paged":
            # dead/retired rows truncate the full verify width, so their
            # lengths return to the pre-verify value and never drift
            self.kv.truncate_rows(rej)
        self._drafter.rollback(rej)

    def _admit_phase(self, now: float) -> bool:
        """Admit arrived requests, highest priority first, preempting a
        strictly-lower-priority live row when the pool is dry. The loop
        terminates: each admission consumes capacity and each preemption
        strictly raises the active set's priority multiset, both finite.
        A controller-set ``_admit_budget`` caps admissions per step
        (back-pressure under preemption churn); ``None`` is unlimited."""
        admitted = False
        n_admitted = 0
        budget = self._admit_budget
        while True:
            if budget is not None and n_admitted >= budget:
                return admitted
            arrived = [r for r in self._queue if r.arrival_time <= now]
            if not arrived:
                return admitted
            if self.kv_layout == "paged":
                head = arrived[0]
                if self._try_admit_paged(head, now):
                    self._queue.remove(head)
                    admitted = True
                    n_admitted += 1
                    continue
            else:
                wave = arrived[: self.kv.n_free]
                if budget is not None:
                    wave = wave[: budget - n_admitted]
                if wave:
                    for r in wave:
                        self._queue.remove(r)
                    if self.chunk_size is not None:
                        for r in wave:
                            self._start_chunk_slot(r, now)
                    else:
                        self._admit(wave, now)
                    admitted = True
                    n_admitted += len(wave)
                    continue
                head = arrived[0]
            if not self._maybe_preempt(head):
                return admitted

    def _prefill_phase(self, now: float) -> bool:
        """Spend at most ``chunk_size`` prompt tokens of chunked prefill
        work, highest-priority request first. Each slice is one
        ``prefill_chunk`` call padded to its pow2 bucket (≤ chunk_size)
        — chunk position rides in the cache's ``len``, so walking a
        prompt reuses one trace per bucket. A prompt that completes
        installs into the pool and its first token samples this step."""
        if not self._chunking:
            return False
        budget = self.chunk_size
        while budget > 0 and self._chunking:
            row, st = min(
                self._chunking.items(), key=lambda it: self._queue_key(it[1].req)
            )
            n = min(budget, len(st.prompt) - st.pos)
            W = prefill_bucket(n, self.chunk_size)
            while st.pos + W > self.max_seq:  # pad row would overrun the cache
                W //= 2
            n = min(n, W)
            toks = np.zeros((1, W), np.int32)
            toks[0, :n] = st.prompt[st.pos : st.pos + n]
            with self.tel.span("serve.prefill_chunk"):
                logits, st.cache = self._prefill_chunk(
                    self.params, st.cache, jnp.asarray(toks),
                    jnp.asarray([n], jnp.int32),
                )
            st.pos += n
            budget -= n
            if self._ton:
                self.tel.tracer.async_instant(
                    "prefill-chunk", st.req.rid, "request",
                    args={"n": n, "pos": st.pos, "of": len(st.prompt)},
                )
            if st.pos == len(st.prompt):
                del self._chunking[row]
                self._install_chunked(st, row, logits[0, n - 1], now)
        if self._ton:
            # fraction of the per-step token budget actually spent —
            # sustained < 1 with a non-empty queue means admission, not
            # chunk work, is the bottleneck
            self._s_chunk_util.append((self.chunk_size - budget) / self.chunk_size)
        return True

    def _write_prefill(self, row: int, cache, skip_blocks: int) -> None:
        """``kv.write_prefill`` under its span, whose ``blocks`` arg is the
        number of fresh blocks the install writes."""
        fresh = self.kv.blocks_for(int(self.kv.cache_len[row])) - skip_blocks
        with self.tel.span("serve.write_prefill", blocks=fresh):
            self.kv.write_prefill(row, cache, skip_blocks=skip_blocks)

    def _install_chunked(self, st: _ChunkState, row: int, logits_row, now) -> None:
        """A fully-chunked prompt lands in the pool: paged rows write
        their fresh blocks (prefix-hit blocks skipped — already shared
        and immutable) and register the prompt in the trie now that the
        blocks hold real KV; slotted rows install the whole dense cache."""
        req = st.req
        if self.kv_layout == "paged":
            self._write_prefill(row, st.cache, st.skip_blocks)
            self.kv.register_prompt(row, tuple(int(t) for t in st.prompt))
        else:
            self.kv.write(row, st.cache)
        if req.tokens:
            self._resume_decode(req, row, now)
        else:
            self._start_decode(req, row, logits_row, now)
        if self._drafter is not None and self._live_k > 0 and not req.finished:
            self._drafter.on_admit(row, req)

    def prime(self) -> None:
        """Pre-compile the chunked-prefill trace family: one trace per
        pow2 bucket W ≤ chunk_size. The family is closed — every slice
        ``_prefill_phase`` can emit (full chunks, resume tails, the
        overrun-halved fallback) pads to one of these widths — so a
        primed scheduler never retraces chunk prefill mid-run. No-op
        when chunking is off. The jitted fn is shared through the
        engine's step-fn cache, so priming one scheduler warms every
        later scheduler on the same engine and backend."""
        if self._prefill_chunk is None:
            return
        cache = self.model.init_cache(1, self.max_seq)
        W = 1
        while W <= self.chunk_size:
            logits, _ = self._prefill_chunk(
                self.params, cache, jnp.zeros((1, W), jnp.int32),
                jnp.asarray([W], jnp.int32),
            )
            jax.block_until_ready(logits)
            W *= 2

    def _finish_step(self, span) -> None:
        """Telemetry step boundary (only reached with the tracer on, for
        a step that did work): note the live and queued counts on the
        step's ring event, refresh the pool/queue gauges, sample the jit
        caches for retraces, and tick the registry so every counter
        lands in its window ring. All host-side bookkeeping — no device
        syncs beyond the ones the step already performed."""
        span.note(active=len(self._active), queued=len(self._queue))
        self._g_queue.set(len(self._queue))
        self._g_active.set(len(self._active))
        self._g_occ.set(self.kv.occupancy)
        if self.kv_layout == "paged":
            self._g_free_blocks.set(self.kv.n_free_blocks)
        size = sum(f._cache_size() for f in self._traced_fns)
        if size > self._cache_size_seen:
            self._c_retraces.inc(size - self._cache_size_seen)
            self.tel.tracer.instant(
                "retrace", "sched", args={"new": size - self._cache_size_seen}
            )
        self._cache_size_seen = size
        self.stats.registry.tick()

    def step(self, now: Optional[float] = None) -> bool:
        """Admit arrived requests, spend the chunked-prefill token
        budget, then run one batched decode over the live set. Returns
        False when there was nothing to do. ``step_ms`` covers the whole
        step, so prefill stalls show up in the tail they cause. With a
        controller attached, every working step also advances the
        observe→decide→apply loop (after the telemetry tick, so the
        decision prices a window that includes this step)."""
        did = self._step_inner(now)
        if did and self.controller is not None:
            self._controller_tick()
        return did

    def _step_inner(self, now: Optional[float] = None) -> bool:
        if now is None:
            now = self._clock()
        if not self._ton:
            return self._step_body(now)
        self._step_seq += 1
        with self.tel.span("serve.step", seq=self._step_seq) as span:
            did = self._step_body(now)
            if did:
                self._finish_step(span)
        return did

    def _step_body(self, now: float) -> bool:
        t0 = time.perf_counter()
        with self.tel.span("serve.admit"):
            admitted = self._admit_phase(now)
        chunked = self._prefill_phase(now)
        if not self._active:
            if admitted or chunked:
                self.stats.step_ms.append((time.perf_counter() - t0) * 1e3)
                return True
            return False
        if self.spec is not None and self._live_k > 0:
            self._spec_step()
            self.stats.step_ms.append((time.perf_counter() - t0) * 1e3)
            return True

        mask = self.kv.live_mask()
        logits = self._decode_pool(mask)
        self.stats.step_ms.append((time.perf_counter() - t0) * 1e3)
        with self.tel.span("serve.sample"):
            if self.kv_layout == "paged":
                for row in self._active:
                    self.kv.advance(row)
            keys, subs = jax.vmap(jax.random.split, out_axes=1)(self._keys)
            nxt = jax.vmap(self._sample_row)(logits, subs)
            live = jnp.asarray(mask)
            self._tok = jnp.where(live, nxt, self._tok)
            self._keys = jnp.where(live[:, None], keys, self._keys)
            nxt_host = np.asarray(nxt)
            for row, req in list(self._active.items()):
                tok = int(nxt_host[row])
                req.tokens.append(tok)
                if len(req.tokens) >= req.max_new_tokens or tok == req.eos_id:
                    self._retire(req, self._clock())
        return True

    def run(self, requests=None, *, reset_stats: bool = True) -> dict:
        """Open-loop drive to completion: submit ``requests``, admit each
        at its ``arrival_time``, decode until everything finishes.
        Returns rid → generated tokens (np.int32)."""
        if reset_stats:
            self.stats.reset()
        self._t0 = time.perf_counter()
        requests = list(requests or [])
        for r in requests:
            self.submit(r)
        while self._queue or self._active or self._chunking:
            if not self._active and not self._chunking and self._queue:
                # earliest arrival, not queue head: the queue is priority-
                # ordered, so the head may arrive later than a lower-
                # priority request
                wait = min(r.arrival_time for r in self._queue) - self._clock()
                if wait > 0:
                    time.sleep(wait)
            self.step()
        return {r.rid: r.output() for r in requests}
