"""Speculative decoding: draft streams, verify/rollback, depth advice.

The paper's central move is a lightweight helper stream running beside a
latency-critical main thread, committed only after an SMT-aware
simulation predicts a gain. Speculative decoding is that architecture at
the serving layer (DESIGN.md §3.2): a cheap *draft* stream runs ahead of
the target model (`DraftSource`), one fixed-shape `Model.verify_step`
forward accepts or rejects its proposals under greedy equivalence, the
KV pools rewind the rejected tail (`truncate_row`), and an advisory cost
model — `core.tools.SpeculationAdvisorTool`, the serving analogue of
`OverlapSimTool`'s simulate-before-commit gate — decides per workload
whether and how deep to speculate (K ∈ {0, 2, 4, 8}).

Two drafters ship:

* ``NGramDraftSource`` — prompt-lookup decoding: propose the
  continuation of the most recent earlier occurrence of the current
  tail n-gram in the request's own history (prompt + generated). Free
  (no second model, no device state), and strong on templated or
  self-repetitive generations.
* ``ModelDraftSource`` — a small ``ModelConfig``-driven draft model
  sharing the target's tokenizer space, with its own slotted cache pool
  aligned row-for-row with the scheduler's.

Both are pool-shaped: ``propose`` returns ``[max_batch, K]`` over the
full fixed row pool (dead rows carry junk that the verify routes to
scratch), so the scheduler's draft→verify round is one fused step whose
only per-request quantity — the acceptance count — is data, not shape.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Protocol, runtime_checkable

import jax
import jax.numpy as jnp
import numpy as np


@runtime_checkable
class DraftSource(Protocol):
    """One draft stream. All hooks are pool-shaped (see module doc)."""

    def bind(self, max_batch: int, max_seq: int) -> None:
        """Size internal state to the scheduler's row pool (called once
        per scheduler, before any propose)."""
        ...

    def on_admit(self, row: int, req) -> None:
        """A request entered decode on ``row`` (catch up on its prompt)."""
        ...

    def propose(self, active: dict, tok: np.ndarray) -> np.ndarray:
        """K draft tokens per row following ``tok`` [max_batch] (the
        pending last-committed token). Returns [max_batch, K] int32;
        rows not in ``active`` may carry anything."""
        ...

    def rollback(self, n_rejected: np.ndarray) -> None:
        """Per-row rejected-entry counts from the verify (the same
        vector the KV pools truncate by); stateful drafters rewind."""
        ...


@dataclass
class SpecConfig:
    """One speculation policy: depth K plus which draft stream runs.

    ``k=0`` disables speculation (the scheduler takes the plain decode
    path); ``drafter`` is ``"ngram"``, ``"model"`` (requires
    ``draft_model``/``draft_params``), or a ``DraftSource`` instance.
    """

    k: int = 4
    drafter: Any = "ngram"
    ngram: tuple = (3, 2, 1)  # tail n-gram sizes tried, longest first
    draft_model: Any = None  # repro.models.Model (drafter="model")
    draft_params: Any = None

    def make_drafter(self, attention_backend=None):
        """Build the draft stream; a model drafter decodes through
        ``attention_backend`` (the scheduler passes its resolved
        backend, so draft and target ride the same kernel path)."""
        if self.k <= 0:
            return None
        if self.drafter == "ngram":
            return NGramDraftSource(self.k, self.ngram)
        if self.drafter == "model":
            if self.draft_model is None:
                raise ValueError("drafter='model' needs draft_model/draft_params")
            return ModelDraftSource(
                self.draft_model, self.draft_params, self.k,
                attention_backend=attention_backend,
            )
        return self.drafter


class NGramDraftSource:
    """Prompt-lookup drafter: no second model.

    For each live row, find the most recent earlier occurrence of the
    history's tail n-gram (longest ``ngram`` size first) and propose
    the K tokens that followed it, cycle-extended when the match sits
    near the end (greedy loops — the common case for self-repetitive
    generations — then verify at ~100% acceptance). With no match the
    proposal degenerates to repeating the last token; wrong guesses
    only cost their share of the fixed-shape verify."""

    def __init__(self, k: int, ngram=(3, 2, 1)):
        self.k = int(k)
        self.ngrams = tuple(int(n) for n in ngram)
        self._max_batch = 0

    def bind(self, max_batch: int, max_seq: int) -> None:
        self._max_batch = int(max_batch)

    def on_admit(self, row: int, req) -> None:
        pass  # the request history IS the state

    def set_k(self, k: int) -> None:
        """Re-depth the proposal window live (online adviser K
        re-decision): the lookup is stateless, so this only resizes the
        proposal matrix for subsequent rounds."""
        self.k = int(k)

    def propose(self, active: dict, tok: np.ndarray) -> np.ndarray:
        out = np.zeros((self._max_batch, self.k), np.int32)
        for row, req in active.items():
            hist = np.concatenate(
                [np.asarray(req.prompt, np.int32), np.asarray(req.tokens, np.int32)]
            )
            out[row] = self._lookup(hist)
        return out

    def rollback(self, n_rejected: np.ndarray) -> None:
        pass

    def _lookup(self, hist: np.ndarray) -> np.ndarray:
        cont = None
        for n in self.ngrams:
            if len(hist) <= n:
                continue
            tail = hist[-n:]
            for j in range(len(hist) - n - 1, -1, -1):
                if np.array_equal(hist[j : j + n], tail):
                    cont = hist[j + n : j + n + self.k]
                    break
            if cont is not None and len(cont):
                break
            cont = None
        if cont is None or not len(cont):
            cont = hist[-1:]
        out = np.empty((self.k,), np.int32)
        for i in range(self.k):
            out[i] = cont[i % len(cont)]  # cycle-extend short matches
        return out


class ModelDraftSource:
    """K-token greedy drafter backed by a small draft model sharing the
    target's tokenizer space.

    Owns a slotted decode cache aligned row-for-row with the
    scheduler's pool: the prompt is prefilled on admission, each
    propose round runs K sequential greedy decode steps plus ONE
    catch-up step (processing the K-th draft, so full acceptance
    leaves no hole in the draft cache), and ``rollback`` truncates by
    the same per-row vector as the target pool — after which the draft
    cache holds exactly the committed stream, mirroring the target.
    The draft rows carry ``k+1`` tokens of speculative overhang, hence
    the padded ``max_seq``."""

    def __init__(self, model, params, k: int, attention_backend=None):
        from repro.models.model import SPEC_FAMILIES

        if model.cfg.family not in SPEC_FAMILIES:
            raise ValueError(
                f"draft model must be a {SPEC_FAMILIES} family (rewindable "
                f"cache), got {model.cfg.family!r}"
            )
        self.model = model
        self.params = params
        self.k = int(k)
        self._k_max = int(k)  # construction depth sizes the cache overhang
        # the draft stream decodes through the same attention backend
        # as the target (the scheduler passes its resolved backend via
        # make_drafter), bound statically like every jitted step
        self._decode = model.jit_step("decode_step", attention_backend)
        self._prefill = None  # needs max_seq: built in bind()
        self.cache = None

    def set_k(self, k: int) -> None:
        """Re-depth the draft loop live (online adviser K re-decision).
        The cache overhang was sized for the construction depth, so the
        live depth may only move within it."""
        if not 0 < int(k) <= self._k_max:
            raise ValueError(
                f"live k={k} outside (0, {self._k_max}] — the draft cache "
                f"overhang was bound for k={self._k_max}"
            )
        self.k = int(k)

    def bind(self, max_batch: int, max_seq: int) -> None:
        self._max_seq = int(max_seq) + self._k_max + 1  # speculative overhang
        model = self.model
        seq = self._max_seq
        self._prefill = jax.jit(
            lambda p, t, n: model.prefill(p, t, seq, prompt_len=n)
        )
        self.cache = model.init_cache(int(max_batch), seq)

    def on_admit(self, row: int, req) -> None:
        # catch up on the request's committed history: the prompt, plus
        # — when resuming after a preemption — every generated token but
        # the pending last one (it is fed to propose, never pre-cached).
        # Pow2-bucketed (pad + per-row length): SPEC_FAMILIES are all
        # pad-safe, and one trace per bucket beats one per prompt length.
        from repro.models.model import prefill_bucket

        hist = np.asarray(req.prompt, np.int32)
        if len(req.tokens) > 1:
            hist = np.concatenate([hist, np.asarray(req.tokens[:-1], np.int32)])
        S = len(hist)
        W = prefill_bucket(S, self._max_seq)
        padded = np.zeros((1, W), np.int32)
        padded[0, :S] = hist
        _, cache1 = self._prefill(
            self.params, jnp.asarray(padded), jnp.asarray([S], jnp.int32)
        )
        self.cache = self.model.write_cache_slot(self.cache, cache1, row)

    def propose(self, active: dict, tok: np.ndarray) -> np.ndarray:
        from repro.serve.telemetry import get_telemetry

        cur = jnp.asarray(np.asarray(tok, np.int32))
        cache = self.cache
        out = []
        with get_telemetry().span("serve.draft_model"):
            for _ in range(self.k):
                logits, cache = self._decode(self.params, cache, cur[:, None])
                cur = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                out.append(cur)
            # catch-up: process the K-th draft so a fully-accepted round
            # leaves the draft cache one-for-one with the target's
            _, cache = self._decode(self.params, cache, cur[:, None])
        self.cache = cache
        return np.stack([np.asarray(t) for t in out], axis=1).astype(np.int32)

    def rollback(self, n_rejected: np.ndarray) -> None:
        vec = jnp.asarray(np.asarray(n_rejected, np.int32))
        self.cache["len"] = jnp.maximum(self.cache["len"] - vec, 0)


# ---------------------------------------------------------------------------
# depth advice (the serving analogue of advise-then-execute)


def advise_depth(
    engine,
    workload_fn,
    *,
    drafter: Any = "ngram",
    ks=(0, 2, 4, 8),
    max_batch: int = 4,
    threshold: float = 0.02,
    draft_model=None,
    draft_params=None,
    seed: int = 0,
):
    """Probe-measure this workload, then let ``SpeculationAdvisorTool``
    pick the speculation depth.

    Runs ``workload_fn()`` (a fresh request list per call — requests
    are stateful) twice through ``engine``: once plain (the K=0 decode
    cost) and once at ``max(ks)`` (draft cost, verify cost, acceptance
    rate). The tool prices expected per-output-token latency at every
    candidate K from those measurements — interpolating verify cost
    between the probed depths — and gates on ``threshold`` predicted
    gain, exactly the shape of ``OverlapSimTool``'s simulate stage.
    Returns ``(SpecConfig, SpecMeasurement, log_line)``;
    ``engine.serve(spec=...)`` honors the decision.
    """
    from repro.core.tools import SpecMeasurement, SpeculationAdvisorTool

    kmax = max(ks)
    if kmax <= 0:
        raise ValueError("ks needs at least one positive candidate depth")
    spec_kw = dict(drafter=drafter, draft_model=draft_model, draft_params=draft_params)
    engine.serve(workload_fn(), max_batch=max_batch, seed=seed, spec=SpecConfig(k=0))
    decode_ms = engine.stats.percentile(50)
    engine.serve(
        workload_fn(), max_batch=max_batch, seed=seed,
        spec=SpecConfig(k=kmax, **spec_kw),
    )
    s = engine.stats
    n_drafted = max(1, kmax * s.spec_steps)
    meas = SpecMeasurement(
        draft_ms_per_token=float(np.sum(s.draft_ms)) / n_drafted,
        verify_ms={0: decode_ms, kmax: s.percentile(50, "verify_ms")},
        acceptance_rate=s.acceptance_rate,
    )
    tool = SpeculationAdvisorTool(ks=tuple(ks))
    k, _gain, log = tool.choose(meas, threshold=threshold)
    return SpecConfig(k=k, **spec_kw), meas, log
