"""§Serving — open-loop Poisson-arrival load on the continuous-batching
serving core: p50/p99 TTFT and per-token decode latency.

Open-loop means arrivals follow their own (Poisson) schedule regardless
of completions — the honest way to load a latency-critical server,
since closed-loop drivers self-throttle and hide queueing delay. Each
request gets a random prompt length and token budget, so the run
exercises divergent per-slot cache lengths and slot reuse.

The speculative mode (``run_speculative`` / ``--speculative``) serves an
identical workload at K=0 and K ∈ {2,4,8} with the n-gram prompt-lookup
drafter on a mid-size config: it verifies token-identity against the
K=0 greedy baseline, asserts a nonzero acceptance rate (the CI smoke
contract), reports per-output-token latency at every depth, and runs
the ``SpeculationAdvisorTool`` on the measured profile so the advised
depth lands in the summary next to the measured best.

The shared-prefix mode (``run_shared_prefix`` / ``--shared-prefix``)
drives the paged engine with prompts sharing one long header (a system
prompt), once with prefix reuse on and once off, on an identical
workload: it reports the hit rate and p50/p99 TTFT both ways, verifies
the two runs decode token-identically, and asserts a nonzero hit rate
(the CI smoke contract). A mid-size config is used so prefill compute —
the cost reuse removes — dominates per-call dispatch overhead.

The backend mode (``run_backend_sweep`` / ``--backend``) serves an
identical open-loop workload on BOTH KV layouts through each attention
backend (DESIGN.md §4): it asserts token-identity against the
``reference`` backend per layout (the CI interpret-mode kernel smoke
contract — the real kernel code runs on CPU), reports p50 TPOT and
per-decode-step latency per (layout × backend), microbenches the
attention call itself (block-paged kernel vs the dense-gather
reference) at a serving-representative shape, and feeds the measured
per-step costs to the ``KernelAdvisorTool`` so the advised backend per
(family, layout, K) cell lands in the summary — measured, not assumed.

The SLO mode (``run_slo`` / ``--chunked [--overload]``) serves the
mixed interactive/batch workload (short high-priority prompts with a
long low-priority prompt every fourth arrival) once with monolithic
prefill and once chunked (``chunk_size`` prompt tokens per decode
step), on identical arrivals and a deliberately under-provisioned
paged pool, and reports SLO-attainment *goodput* — the fraction of
requests finishing within a TTFT/TPOT budget — for both. Monolithic
prefill stalls every co-resident decode for the full long-prompt
forward (and re-stalls on preemption-resume recompute, where its
prompt shapes also pay retraces the chunked trace family never
does — that tail is the measured phenomenon, not an artifact);
chunking bounds per-step work at ``chunk_size`` tokens, which is the
p99-step contract asserted here. The CI smoke contract: nonzero
preemptions under overload, nonzero goodput, and a strictly smaller
chunked p99 step.

The sharded mode (``run_sharded`` / ``--mesh N`` or ``--mesh NxM``)
serves one identical open-loop workload at every mesh shape through the
mesh-sharded paged path (DESIGN.md §5): head-only ``("model",)`` sizes
shard the paged pool's KV leaves head-wise, kv-sequence shapes
(``("seq",)`` and the 2D ``("model","seq")`` composition) partition the
pool's block dimension and recombine each softmax from per-rank flash
partials. Bitwise token identity against the single-device paged engine
is asserted for the head-only sizes, for plain, speculative (K=2), and
chunked-prefill serving — head partitioning moves parallel work, never
a reduction order — while the seq lanes assert argmax token identity
(the exact-combine tolerance contract); per-decode-step
latency is recorded per mesh size. When a CPU process has fewer
devices than the largest mesh (the normal single-device CI run), the
sweep re-execs itself in a CPU-pinned subprocess with a forced
multi-device host platform, so ``benchmarks.run`` still lands
``serving.sharded`` in the summary. On an accelerator it runs
in-process or refuses, naming the device count it needs.

The drift mode (``run_drift`` / ``--drift``) is the online-adviser
proof (DESIGN.md §9): a phased workload whose draftability drifts
(repetitive → churn → shared-prefix), served once per static K arm and
once under the closed-loop ``OnlineAdviser`` (primed K × backend grid,
live re-decision from telemetry windows). Every static arm loses in
some phase; the controller must beat the worst static arm's p50 TPOT
and land within ``oracle_tolerance`` of the per-phase-best oracle,
with bitwise token identity across every arm, at least one live
switch, and ZERO retraces after ``prime()`` — pinned both by the
engine's jit-cache sizes and the ``engine.retraces`` counter. The
decision audit trail is written to ``--drift``'s JSON path (the CI
artifact).

The observability mode (``run_observability`` / ``--trace [PATH]``)
pins the flight-recorder contract (DESIGN.md §8): one warmed engine
serves an identical paged + speculative + chunked workload with
telemetry OFF and then ON (per-call ``telemetry=`` override, so both
runs share every jitted executable), asserts bitwise token identity
(recording is observation, never behaviour), asserts the instrumented
p50 decode step stays within a pinned factor of the uninstrumented
one, sanity-checks the ``window_summary`` adviser signal vector, runs
the ``SpeculationAdvisorTool`` on the measured profile so the decision
(with its priced inputs) lands in the trace as an adviser-audit event,
and — with ``--trace`` — exports Chrome/Perfetto trace-event JSON
(load in ui.perfetto.dev) validated by
``repro.serve.telemetry.validate_chrome_trace``.

Feeds the ``serving`` section of ``BENCH_aira.json`` (benchmarks/run.py)
so serving latency is tracked across PRs. Request generation lives in
``repro.serve.load`` (shared with examples/serve_decode.py).

Usage: PYTHONPATH=src python -m benchmarks.serving_load [--shared-prefix]
"""
from __future__ import annotations

import dataclasses

import jax
import numpy as np


def run(
    *,
    arch: str = "smollm-135m",
    n_requests: int = 12,
    rate_rps: float = 20.0,
    max_batch: int = 4,
    tokens: int = 8,
    seed: int = 0,
    kv_layout: str = "slot",
    backend: str = "auto",
    print_fn=print,
) -> dict:
    from repro.configs import get_config
    from repro.models import Model
    from repro.serve import ServingEngine
    from repro.serve.load import make_requests

    cfg = get_config(arch).reduced()
    model = Model(cfg)
    params, _ = model.init(jax.random.key(seed))
    engine = ServingEngine(
        model, params, max_seq=64, kv_layout=kv_layout, attention_backend=backend
    )
    rng = np.random.default_rng(seed)
    reqs = make_requests(
        n_requests, rate_rps, vocab=cfg.vocab_size, max_new_tokens=tokens, rng=rng
    )
    outputs = engine.serve(reqs, max_batch=max_batch, seed=seed)
    assert all(r.finished for r in reqs)
    assert all(len(outputs[r.rid]) == len(r.tokens) for r in reqs)

    summary = dict(
        engine.stats.serving_summary(),
        arch=arch,
        rate_rps=rate_rps,
        max_batch=max_batch,
        kv_layout=kv_layout,
        # scalar key is `backend`: `attention_backend` is the sweep
        # section run.py records beside this summary
        backend=engine.attention_backend,
    )
    print_fn("# serving — open-loop Poisson arrivals (continuous batching)")
    print_fn(
        f"arch={arch} requests={n_requests} rate={rate_rps}/s pool={max_batch} "
        f"layout={kv_layout} backend={engine.attention_backend}"
    )
    print_fn(
        f"ttft p50={summary['p50_ttft_ms']:.2f}ms p99={summary['p99_ttft_ms']:.2f}ms | "
        f"tpot p50={summary['p50_tpot_ms']:.2f}ms p99={summary['p99_tpot_ms']:.2f}ms | "
        f"step p50={summary['p50_step_ms']:.2f}ms p99={summary['p99_step_ms']:.2f}ms"
    )
    return summary


def _attention_microbench(backends, reps: int = 20, seed: int = 0) -> dict:
    """Per-call attention-step wall-clock at a serving-representative
    paged shape: the block-table-walking kernel vs the dense-gather
    reference, isolated from the rest of the decode step. Returns
    backend → µs/call."""
    import time

    import jax.numpy as jnp

    from repro.kernels import ops
    from repro.models.attention import to_stored, tokens_per_row

    rng = np.random.default_rng(seed)
    B, T, KV, g, hd, NB, BS, MB = 4, 1, 2, 2, 32, 64, 16, 8
    q = jnp.asarray(rng.normal(size=(B, T, KV * g, hd)), jnp.float32)
    # one layer of a pool, stored as the kernel reads it
    pool = lambda: to_stored(
        jnp.asarray(rng.normal(size=(1, NB, BS, KV, hd)), jnp.float32),
        tokens_per_row(BS, hd),
    )
    kp, vp = pool(), pool()
    tbl = jnp.asarray(rng.integers(0, NB, size=(B, MB)), jnp.int32)
    lens = jnp.asarray(rng.integers(BS, MB * BS - 1, size=(B,)), jnp.int32)
    out = {}
    for backend in backends:
        f = lambda: ops.paged_attention(q, kp, vp, tbl, lens, mode=backend)
        jax.block_until_ready(f())  # compile
        t0 = time.perf_counter()
        for _ in range(reps):
            jax.block_until_ready(f())
        out[backend] = (time.perf_counter() - t0) / reps * 1e6
    return out


def run_backend_sweep(
    *,
    arch: str = "smollm-135m",
    n_requests: int = 6,
    rate_rps: float = 50.0,
    max_batch: int = 3,
    tokens: int = 8,
    backends=("reference", "interpret"),
    seed: int = 0,
    print_fn=print,
) -> dict:
    """Identical open-loop workload on both KV layouts through each
    attention backend: token-identity vs ``reference`` asserted per
    layout (the CI kernel-smoke contract — interpret mode runs the real
    block-paged kernel code on CPU), p50 TPOT / per-step latency
    recorded per (layout × backend), and the measured per-step costs
    fed to the ``KernelAdvisorTool`` for the advised backend per cell."""
    from repro.configs import get_config
    from repro.core.tools import KernelAdvisorTool, KernelMeasurement
    from repro.models import Model
    from repro.serve import ServingEngine
    from repro.serve.load import make_requests

    # reference leads (it is the identity baseline); dedupe so
    # --backend reference degrades to a plain reference run, not a
    # vacuous self-comparison
    backends = tuple(dict.fromkeys(("reference",) + tuple(backends)))
    cfg = get_config(arch).reduced()
    model = Model(cfg)
    params, _ = model.init(jax.random.key(seed))
    engine = ServingEngine(model, params, max_seq=64, block_size=8)

    def workload():
        return make_requests(
            n_requests, rate_rps, vocab=cfg.vocab_size, max_new_tokens=tokens,
            rng=np.random.default_rng(seed),
        )

    results: dict = {}
    for layout in ("slot", "paged"):
        results[layout] = {}
        outputs = {}
        for backend in backends:
            reqs = workload()  # warm the jit cache outside the window
            engine.serve(reqs, max_batch=max_batch, seed=seed,
                         kv_layout=layout, attention_backend=backend)
            reqs = workload()
            out = engine.serve(reqs, max_batch=max_batch, seed=seed,
                               kv_layout=layout, attention_backend=backend)
            outputs[backend] = [np.asarray(out[r.rid]) for r in reqs]
            s = engine.stats.serving_summary()
            results[layout][backend] = {
                "p50_tpot_ms": s["p50_tpot_ms"],
                "p50_step_ms": s["p50_step_ms"],
                "p99_step_ms": s["p99_step_ms"],
            }
        for backend in backends[1:]:
            for a, b in zip(outputs["reference"], outputs[backend]):
                np.testing.assert_array_equal(
                    a, b,
                    err_msg=f"{layout}/{backend} diverged from the reference backend",
                )

    attn_us = _attention_microbench(backends, seed=seed)

    # the advisory gate prices the measured per-step cost per cell —
    # the engine honors the decision via serve(attention_backend=...)
    tool = KernelAdvisorTool()
    advised, advisor_log = {}, []
    for layout in ("slot", "paged"):
        m = KernelMeasurement.make(
            cfg.family, layout, 0,
            {b: results[layout][b]["p50_step_ms"] for b in backends},
        )
        choice, gain, log = tool.choose(m)
        advised[layout] = choice
        advisor_log.append(log)

    summary = {
        "arch": arch,
        "backends": list(backends),
        "slot": results["slot"],
        "paged": results["paged"],
        "attn_us": attn_us,
        "advised": advised,
    }
    print_fn("# serving — attention-backend sweep (token-identity asserted)")
    print_fn(f"arch={arch} requests={n_requests} tokens={tokens} pool={max_batch}")
    for layout in ("slot", "paged"):
        for backend in backends:
            r = results[layout][backend]
            print_fn(
                f"{layout:5s}/{backend:9s} tpot p50={r['p50_tpot_ms']:.2f}ms "
                f"step p50={r['p50_step_ms']:.2f}ms"
            )
    print_fn(
        "attention µbench: "
        + " ".join(f"{b}={us:.0f}µs" for b, us in attn_us.items())
    )
    for line in advisor_log:
        print_fn(f"advisor: {line}")
    return summary


def run_shared_prefix(
    *,
    arch: str = "smollm-135m",
    n_requests: int = 8,
    rate_rps: float = 50.0,
    max_batch: int = 4,
    prefix_len: int = 160,
    suffix_len: int = 32,
    tokens: int = 4,
    block_size: int = 16,
    seed: int = 0,
    print_fn=print,
) -> dict:
    from repro.configs import get_config
    from repro.models import Model
    from repro.serve import ServingEngine
    from repro.serve.load import make_shared_prefix_requests

    # mid-size so prefill compute (what reuse removes) beats dispatch noise
    cfg = dataclasses.replace(
        get_config(arch).reduced(),
        num_layers=4, d_model=128, d_ff=384, n_heads=4, n_kv_heads=2, head_dim=32,
    )
    model = Model(cfg)
    params, _ = model.init(jax.random.key(seed))
    max_seq = prefix_len + suffix_len + tokens + block_size
    max_seq += (-max_seq) % block_size

    header = np.random.default_rng(seed).integers(
        0, cfg.vocab_size, size=(prefix_len,)
    ).astype(np.int32)

    def workload(rng):
        return make_shared_prefix_requests(
            n_requests, rate_rps, vocab=cfg.vocab_size, prefix_len=prefix_len,
            suffix_len=suffix_len, max_new_tokens=tokens, rng=rng, prefix=header,
        )

    results, outputs = {}, {}
    for reuse in (True, False):
        engine = ServingEngine(
            model, params, max_seq=max_seq, kv_layout="paged",
            block_size=block_size, prefix_cache=reuse,
        )
        sched = engine.scheduler(max_batch, seed=seed)
        # warm the jit caches AND (reuse on) the prefix trie: the warmup
        # workload shares the measured header but has different random
        # suffixes, so every measured request hits exactly the header
        # (same already-compiled suffix-prefill shape) — steady state,
        # no cold prefill and no compile inside the measured window
        sched.run(workload(np.random.default_rng(seed + 1)))
        reqs = workload(np.random.default_rng(seed))
        out = sched.run(reqs)
        sched.kv.check_invariants()
        key = "reuse_on" if reuse else "reuse_off"
        results[key] = engine.stats.serving_summary()
        outputs[key] = [np.asarray(out[r.rid]) for r in reqs]

    for a, b in zip(outputs["reuse_on"], outputs["reuse_off"]):
        np.testing.assert_array_equal(a, b)  # reuse must not change tokens
    hit_rate = results["reuse_on"]["prefix_hit_rate"]
    assert hit_rate > 0, "shared-prefix workload produced no prefix hits"
    assert results["reuse_off"]["prefix_hit_rate"] == 0

    summary = {
        "arch": arch,
        "prefix_len": prefix_len,
        "suffix_len": suffix_len,
        "block_size": block_size,
        "prefix_hit_rate": hit_rate,
        "reuse_on": results["reuse_on"],
        "reuse_off": results["reuse_off"],
        "ttft_p50_speedup": (
            results["reuse_off"]["p50_ttft_ms"] / results["reuse_on"]["p50_ttft_ms"]
            if results["reuse_on"]["p50_ttft_ms"]
            else 0.0
        ),
    }
    print_fn("# serving — shared-prefix reuse (paged KV cache)")
    print_fn(
        f"arch={arch} requests={n_requests} prompt={prefix_len}+{suffix_len} "
        f"block={block_size} hit_rate={hit_rate:.2f}"
    )
    for key in ("reuse_on", "reuse_off"):
        s = results[key]
        print_fn(
            f"{key:9s} ttft p50={s['p50_ttft_ms']:.2f}ms p99={s['p99_ttft_ms']:.2f}ms | "
            f"tpot p50={s['p50_tpot_ms']:.2f}ms"
        )
    print_fn(f"p50 TTFT speedup from reuse: {summary['ttft_p50_speedup']:.2f}x")
    return summary


def run_speculative(
    *,
    arch: str = "smollm-135m",
    n_requests: int = 6,
    rate_rps: float = 50.0,
    max_batch: int = 3,
    prompt_len: int = 12,
    tokens: int = 24,
    ks=(2, 4, 8),
    seed: int = 0,
    print_fn=print,
) -> dict:
    from repro.configs import get_config
    from repro.core.tools import SpecMeasurement, SpeculationAdvisorTool
    from repro.models import Model
    from repro.serve import ServingEngine, SpecConfig
    from repro.serve.load import make_requests

    # mid-size so a saved decode step (what acceptance removes) is real
    # compute, not dispatch noise — same sizing as the shared-prefix mode
    cfg = dataclasses.replace(
        get_config(arch).reduced(),
        num_layers=4, d_model=128, d_ff=384, n_heads=4, n_kv_heads=2, head_dim=32,
    )
    model = Model(cfg)
    params, _ = model.init(jax.random.key(seed))
    engine = ServingEngine(model, params, max_seq=prompt_len + tokens + max(ks) + 8)

    def workload(rng_seed=seed):
        # long budgets on short prompts: tiny greedy models settle into
        # repetitive continuations, exactly what prompt-lookup drafts
        return make_requests(
            n_requests, rate_rps, vocab=cfg.vocab_size, max_new_tokens=tokens,
            prompt_lens=(prompt_len,), rng=np.random.default_rng(rng_seed),
        )

    results, outputs = {}, {}
    for k in (0,) + tuple(ks):
        reqs = workload()
        spec = SpecConfig(k=k, drafter="ngram")
        engine.serve(reqs, max_batch=max_batch, seed=seed, spec=spec)  # warm jit
        reqs = workload()
        out = engine.serve(reqs, max_batch=max_batch, seed=seed, spec=spec)
        results[k] = engine.stats.serving_summary()
        outputs[k] = [np.asarray(out[r.rid]) for r in reqs]

    for k in ks:
        for a, b in zip(outputs[0], outputs[k]):
            np.testing.assert_array_equal(a, b)  # speculation must not change tokens
        assert results[k]["speculative"]["acceptance_rate"] > 0, (
            f"K={k}: no draft token survived the verify"
        )

    # feed the measured profile to the advisory gate (probe = deepest K)
    kmax = max(ks)
    s = results[kmax]["speculative"]
    meas = SpecMeasurement(
        draft_ms_per_token=s["p50_draft_ms"] / kmax,
        verify_ms={0: results[0]["p50_step_ms"], kmax: s["p50_verify_ms"]},
        acceptance_rate=s["acceptance_rate"],
    )
    advised_k, gain, log = SpeculationAdvisorTool(ks=(0,) + tuple(ks)).choose(meas)

    best_k = min(ks, key=lambda k: results[k]["p50_tpot_ms"])
    summary = {
        "arch": arch,
        "drafter": "ngram",
        "baseline": results[0],
        **{f"k{k}": results[k] for k in ks},
        "advised_k": advised_k,
        "advised_gain": gain,
        "best_k": best_k,
        "tpot_p50_speedup": (
            results[0]["p50_tpot_ms"] / results[best_k]["p50_tpot_ms"]
            if results[best_k]["p50_tpot_ms"]
            else 0.0
        ),
    }
    print_fn("# serving — speculative decode (n-gram drafter, K=0 baseline)")
    print_fn(f"arch={arch} requests={n_requests} prompt={prompt_len} tokens={tokens}")
    for k in (0,) + tuple(ks):
        s = results[k]
        extra = (
            f" accept={s['speculative']['acceptance_rate']:.2f}"
            if k else " (plain greedy)"
        )
        print_fn(
            f"K={k}: tpot p50={s['p50_tpot_ms']:.2f}ms "
            f"step p50={s['p50_step_ms']:.2f}ms{extra}"
        )
    print_fn(f"advisor: {log}")
    print_fn(
        f"best K={best_k}: {summary['tpot_p50_speedup']:.2f}x per-token speedup vs K=0"
    )
    # token-identity and nonzero acceptance above are deterministic
    # contracts; the latency comparison is wall-clock and can wobble on
    # a noisy shared runner, so it is reported exactly but asserted
    # with slack — a genuine regression (speculation slower than plain
    # greedy) still trips it
    assert summary["tpot_p50_speedup"] > 0.9, (
        f"speculation made per-output-token latency materially worse "
        f"({summary['tpot_p50_speedup']:.2f}x vs the K=0 baseline)"
    )
    return summary


def _jit_cache_size(engine) -> int:
    """Total compile-cache entries across the engine's shared jitted
    step fns — the drift benchmark's no-retrace witness: any mid-serve
    K/backend switch that escaped the primed trace families grows it."""
    fns = [engine._prefill, engine._prefill_prefix]
    for family in engine._steps.values():
        fns.extend(family.values())
    return sum(
        f._cache_size() for f in fns if f is not None and hasattr(f, "_cache_size")
    )


def run_drift(
    *,
    arch: str = "smollm-135m",
    max_batch: int = 3,
    rate_rps: float = 60.0,
    ks=(0, 2, 4),
    phase_n=(8, 10, 8),
    rep_tokens: int = 24,
    churn_tokens: int = 4,
    churn_prompt_lens=(24, 32, 40),
    prefix_len: int = 16,
    decision_interval: int = 4,
    window: int = 12,
    oracle_tolerance: float = 1.6,
    decisions_path=None,
    seed: int = 0,
    print_fn=print,
) -> dict:
    import json

    from repro.configs import get_config
    from repro.models import Model
    from repro.serve import OnlineAdviser, ServingEngine, SpecConfig, Telemetry
    from repro.serve.load import make_drift_requests

    # mid-size (run_speculative sizing): a saved decode step must be
    # real compute, or no arm separates from any other
    cfg = dataclasses.replace(
        get_config(arch).reduced(),
        num_layers=4, d_model=128, d_ff=384, n_heads=4, n_kv_heads=2, head_dim=32,
    )
    model = Model(cfg)
    params, _ = model.init(jax.random.key(seed))
    engine = ServingEngine(model, params, max_seq=64, kv_layout="paged", block_size=8)
    ks = tuple(sorted({int(k) for k in ks}))
    kmax = max(ks)
    # one shared header across every draw, so warmup and measured runs
    # hit the same prefix-cache entries
    prefix = (
        np.random.default_rng(seed + 7)
        .integers(0, cfg.vocab_size, size=(prefix_len,))
        .astype(np.int32)
    )

    def workload(rng_seed=seed):
        # churn: long random prompts (an expensive n-gram scan per draft
        # round, nothing draftable in them) with tiny budgets — every
        # speculated token is pure overhead there
        return make_drift_requests(
            phase_n, rate_rps, vocab=cfg.vocab_size, rep_tokens=rep_tokens,
            churn_tokens=churn_tokens, churn_prompt_lens=churn_prompt_lens,
            prefix_len=prefix_len,
            rng=np.random.default_rng(rng_seed), prefix=prefix,
        )

    def spec_for(k):
        return SpecConfig(k=k, drafter="ngram") if k else SpecConfig(k=0)

    # prime the K × backend grid (decode + every verify width), then warm
    # each arm's full trace family on the real workload shapes (prefill
    # buckets, prefix path); everything after this must be a cache hit
    primed = engine.prime(max_batch, ks=ks)
    for k in ks:
        reqs, _ = workload()
        engine.serve(reqs, max_batch=max_batch, seed=seed, spec=spec_for(k))
    cache_warm = _jit_cache_size(engine)

    def _tpots(rs):
        return [r.tpot_ms for r in rs if r.tpot_ms is not None]

    def _p50(vals):
        return float(np.percentile(vals, 50)) if vals else 0.0

    # measured static arms: identical workload per arm, per-phase TPOT.
    # Every measured run — static and controlled — serves through the
    # same enabled flight recorder: the controller NEEDS the windowed
    # metrics, so the static arms pay the identical instrumented cost
    # (policies are compared, not telemetry overhead)
    tel = Telemetry(enabled=True, capacity=8192)
    arm_tpots, phase_tpots, outputs = {}, {}, {}
    spans = None
    for k in ks:
        reqs, spans = workload()
        out = engine.serve(
            reqs, max_batch=max_batch, seed=seed, spec=spec_for(k), telemetry=tel
        )
        outputs[k] = [np.asarray(out[r.rid]) for r in reqs]
        arm_tpots[k] = _tpots(reqs)
        phase_tpots[k] = {name: _tpots(reqs[s:e]) for name, s, e in spans}

    # the controller run: deepest arm's margin + drafter, live depth
    # re-decided every decision_interval steps from the telemetry window
    ctl = OnlineAdviser(
        ks=ks, decision_interval=decision_interval, window=window,
        dwell=1, threshold=0.03, probe_every=2,
    )
    ctl.seed_costs(primed)
    reqs, spans = workload()
    out = engine.serve(
        reqs, max_batch=max_batch, seed=seed,
        spec=SpecConfig(k=kmax, drafter="ngram"), controller=ctl, telemetry=tel,
    )
    ctl_outputs = [np.asarray(out[r.rid]) for r in reqs]
    ctl_tpots = _tpots(reqs)
    ctl_phase = {name: _tpots(reqs[s:e]) for name, s, e in spans}
    cache_end = _jit_cache_size(engine)
    retraces = engine.stats.registry.counter("engine.retraces").value

    # deterministic contracts first: greedy streams are invariant under
    # speculation depth AND under live re-decision of it
    for k in ks[1:]:
        for a, b in zip(outputs[ks[0]], outputs[k]):
            np.testing.assert_array_equal(a, b)
    for a, b in zip(outputs[ks[0]], ctl_outputs):
        np.testing.assert_array_equal(a, b)
    assert len(ctl.decisions) > 0, "controller never reached a decision interval"
    assert ctl.n_switches >= 1, (
        f"controller never switched arms across a drifting workload "
        f"(decisions={len(ctl.decisions)})"
    )
    assert cache_end == cache_warm, (
        f"live switching retraced: jit cache grew {cache_warm} → {cache_end} "
        f"after prime+warmup"
    )
    assert retraces == 0, f"engine.retraces counter saw {retraces} mid-run compiles"

    # the latency contract: controller beats the worst static arm and
    # tracks the per-phase-best oracle (tpot pooled from each phase's
    # winning arm) within tolerance
    arm_p50 = {k: _p50(arm_tpots[k]) for k in ks}
    worst_k = max(ks, key=lambda k: arm_p50[k])
    best_static_k = min(ks, key=lambda k: arm_p50[k])
    phase_best, oracle_pool = {}, []
    for name, _, _ in spans:
        bk = min(ks, key=lambda k: _p50(phase_tpots[k][name]))
        phase_best[name] = bk
        oracle_pool.extend(phase_tpots[bk][name])
    oracle_p50 = _p50(oracle_pool)
    ctl_p50 = _p50(ctl_tpots)

    summary = {
        "arch": arch,
        "ks": list(ks),
        "phases": [
            {
                "name": name,
                "n": e - s,
                "best_k": phase_best[name],
                **{f"k{k}_p50_tpot_ms": _p50(phase_tpots[k][name]) for k in ks},
                "controller_p50_tpot_ms": _p50(ctl_phase[name]),
            }
            for name, s, e in spans
        ],
        **{f"k{k}_p50_tpot_ms": arm_p50[k] for k in ks},
        "worst_static_k": worst_k,
        "best_static_k": best_static_k,
        "oracle_p50_tpot_ms": oracle_p50,
        "controller_p50_tpot_ms": ctl_p50,
        "controller_vs_worst": arm_p50[worst_k] / ctl_p50 if ctl_p50 else 0.0,
        "controller_vs_oracle": ctl_p50 / oracle_p50 if oracle_p50 else 0.0,
        "decisions": len(ctl.decisions),
        "switches": ctl.n_switches,
        "retraces_after_prime": int(cache_end - cache_warm),
        "controller": ctl.summary(),
    }

    print_fn("# serving — drift workload (online adviser vs static K arms)")
    print_fn(
        f"arch={arch} phases={[n for n, _, _ in spans]} "
        f"requests={sum(int(n) for n in phase_n)} ks={list(ks)}"
    )
    for ph in summary["phases"]:
        cells = " ".join(f"K={k}:{ph[f'k{k}_p50_tpot_ms']:.2f}ms" for k in ks)
        print_fn(
            f"{ph['name']:>13}: {cells} ctl:{ph['controller_p50_tpot_ms']:.2f}ms "
            f"(best K={ph['best_k']})"
        )
    print_fn(
        f"overall p50 tpot: "
        + " ".join(f"K={k}:{arm_p50[k]:.2f}ms" for k in ks)
        + f" oracle:{oracle_p50:.2f}ms controller:{ctl_p50:.2f}ms"
    )
    print_fn(
        f"controller: {len(ctl.decisions)} decisions, {ctl.n_switches} switches, "
        f"{int(cache_end - cache_warm)} retraces after prime"
    )
    for d in ctl.audit_trail():
        print_fn(
            f"  step {d['step']:>3}: k={d['k']} backend={d['backend']}"
            + (" [probe]" if d["probe"] else "")
            + (f" gain={d['predicted_gain']:+.1%}" if d["switched"] else "")
            + f" — {d['reason']}"
        )
    if decisions_path:
        with open(decisions_path, "w") as f:
            json.dump(
                {"decisions": ctl.audit_trail(), "controller": ctl.summary(),
                 "summary": {k: v for k, v in summary.items() if k != "phases"}},
                f, indent=2, default=str,
            )
        print_fn(f"decision audit trail → {decisions_path}")

    assert ctl_p50 < arm_p50[worst_k], (
        f"controller p50 TPOT {ctl_p50:.2f}ms did not beat the worst static "
        f"arm K={worst_k} ({arm_p50[worst_k]:.2f}ms)"
    )
    assert ctl_p50 <= oracle_p50 * oracle_tolerance, (
        f"controller p50 TPOT {ctl_p50:.2f}ms outside {oracle_tolerance}x of "
        f"the per-phase-best oracle ({oracle_p50:.2f}ms)"
    )
    return summary


def _run_sharded_subprocess(kwargs: dict, need: int, print_fn) -> dict:
    """Re-exec ``run_sharded`` with a forced ``need``-device CPU host
    platform. XLA_FLAGS must be set before jax initializes, and this
    process has already initialized it with its real (single) device —
    so the sweep itself runs in a child and ships its summary back as a
    sentinel-prefixed JSON line. CPU only: the child is pinned to the
    CPU platform, and the caller never takes this path on an
    accelerator (the parent would hold the chip the child asks for)."""
    import json
    import os
    import subprocess
    import sys

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={need}"
    ).strip()
    code = (
        "import json\n"
        "from benchmarks import serving_load\n"
        f"s = serving_load.run_sharded(**{kwargs!r})\n"
        "print('SHARDED_JSON::' + json.dumps(s))\n"
    )
    r = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True, env=env,
        timeout=1200,
    )
    if r.returncode != 0:
        raise RuntimeError(
            f"sharded sweep subprocess failed:\n{r.stderr}\n{r.stdout}"
        )
    summary = None
    for line in r.stdout.splitlines():
        if line.startswith("SHARDED_JSON::"):
            summary = json.loads(line[len("SHARDED_JSON::"):])
        else:
            print_fn(line)
    assert summary is not None, r.stdout
    return summary


def sharded_devices_needed(mesh_sizes=(1, 2, 4), seq_shapes=((2,), (2, 2))) -> int:
    """Devices the largest mesh of a ``run_sharded`` sweep spans."""
    return max(
        max(mesh_sizes),
        max((int(np.prod(s)) for s in seq_shapes), default=1),
    )


def run_sharded(
    *,
    arch: str = "smollm-135m",
    n_requests: int = 8,
    rate_rps: float = 50.0,
    max_batch: int = 4,
    tokens: int = 8,
    mesh_sizes=(1, 2, 4),
    seq_shapes=((2,), (2, 2)),
    backend: str = "interpret",
    seed: int = 0,
    print_fn=print,
) -> dict:
    """One workload, every mesh shape: the mesh-sharded paged serving
    path (DESIGN.md §5) vs the single-device paged engine. Two lanes:

    * ``mesh_sizes`` — head-only ``("model",)`` meshes. Per size the
      same open-loop arrivals are served plain, speculative (K=2,
      n-gram drafter), and with chunked prefill; all three streams are
      asserted BITWISE identical to the mesh-less run (head
      partitioning + all-gather preserves every reduction order).
    * ``seq_shapes`` — kv-sequence-split shapes: ``(sp,)`` serves over
      a pure ``("seq",)`` mesh, ``(tp, sp)`` over the 2D
      ``("model", "seq")`` composition. These recombine each softmax
      from per-rank flash partials (``distributed_softmax``), so the
      lane's contract is the tolerance one: argmax token identity
      (greedy streams match exactly) rather than bitwise logits. The
      per-shape step latency lands under the summary's ``"seq"`` key
      (→ ``serving.sharded.seq`` in BENCH).

    Per-decode-step latency is recorded from each plain serve. The
    default ``interpret`` backend runs the real block-paged kernel code
    per-shard on CPU (the CI smoke contract). Latency across forced CPU
    host-platform "devices" shares the same cores, so the numbers track
    dispatch/collective overhead, not speedup — the contract asserted
    here is identity, the latency is reported."""
    seq_shapes = tuple(tuple(s) for s in seq_shapes)
    need = sharded_devices_needed(mesh_sizes, seq_shapes)
    if need > 1 and len(jax.devices()) < need:
        if jax.default_backend() != "cpu":
            raise RuntimeError(
                f"the sharded sweep needs {need} devices; this "
                f"{jax.default_backend()} host has {len(jax.devices())}. "
                "Run it on a host with that many chips (it runs in-process "
                "there), or on a CPU host, where it forces host devices"
            )
        return _run_sharded_subprocess(
            dict(arch=arch, n_requests=n_requests, rate_rps=rate_rps,
                 max_batch=max_batch, tokens=tokens,
                 mesh_sizes=tuple(mesh_sizes), seq_shapes=seq_shapes,
                 backend=backend, seed=seed),
            need, print_fn,
        )

    from repro.configs import get_config
    from repro.models import Model
    from repro.serve import ServingEngine, SpecConfig
    from repro.serve.load import make_requests

    # mid-size with 8 query / 4 kv heads so every mesh size in the sweep
    # divides both (g=2 exercises GQA grouping under the head split)
    cfg = dataclasses.replace(
        get_config(arch).reduced(),
        num_layers=4, d_model=128, d_ff=384, n_heads=8, n_kv_heads=4, head_dim=16,
    )
    model = Model(cfg)
    params, _ = model.init(jax.random.key(seed))

    def workload():
        return make_requests(
            n_requests, rate_rps, vocab=cfg.vocab_size, max_new_tokens=tokens,
            rng=np.random.default_rng(seed),
        )

    modes = (
        ("plain", {}),
        ("speculative", {"spec": SpecConfig(k=2, drafter="ngram")}),
        ("chunked", {"chunk_size": 4}),
    )
    results, outputs, base_raw = {}, {}, {}
    for tp in mesh_sizes:
        if tp > 1:
            mesh = jax.make_mesh(
                (tp,), ("model",), axis_types=(jax.sharding.AxisType.Auto,)
            )
        else:
            mesh = None
        engine = ServingEngine(
            model, params, max_seq=64, kv_layout="paged", mesh=mesh,
            attention_backend=backend,
        )
        if tp > 1:
            assert engine.mesh is mesh, "sharded sweep fell back to replicated"
        outputs[tp] = {}
        for mode, kw in modes:
            engine.serve(workload(), max_batch=max_batch, seed=seed, **kw)  # warm
            reqs = workload()
            out = engine.serve(reqs, max_batch=max_batch, seed=seed, **kw)
            outputs[tp][mode] = [np.asarray(out[r.rid]) for r in reqs]
            if tp == mesh_sizes[0]:
                base_raw[mode] = out
            if mode == "plain":
                s = engine.stats.serving_summary()
                results[f"tp{tp}"] = {
                    "p50_step_ms": s["p50_step_ms"],
                    "p99_step_ms": s["p99_step_ms"],
                    "p50_tpot_ms": s["p50_tpot_ms"],
                }

    base_tp = mesh_sizes[0]
    for tp in mesh_sizes[1:]:
        for mode, _ in modes:
            for a, b in zip(outputs[base_tp][mode], outputs[tp][mode]):
                np.testing.assert_array_equal(
                    a, b,
                    err_msg=f"mesh={tp} {mode} diverged from the "
                            f"single-device paged path",
                )

    # kv-sequence-split lane: pure ("seq",) and 2D ("model","seq")
    # shapes, tolerance contract — argmax token identity via the shared
    # serve-level differential (repro.serve.differential)
    from repro.serve.differential import assert_streams_equal

    seq_results = {}
    for shape in seq_shapes:
        names = ("seq",) if len(shape) == 1 else ("model", "seq")
        key = "x".join(f"{n}{s}" for n, s in zip(names, shape))
        mesh = jax.make_mesh(
            shape, names, axis_types=(jax.sharding.AxisType.Auto,) * len(shape)
        )
        engine = ServingEngine(
            model, params, max_seq=64, kv_layout="paged", mesh=mesh,
            attention_backend=backend,
        )
        assert engine.mesh is mesh, "seq-split sweep fell back to replicated"
        for mode, kw in modes:
            engine.serve(workload(), max_batch=max_batch, seed=seed, **kw)  # warm
            out = engine.serve(workload(), max_batch=max_batch, seed=seed, **kw)
            assert_streams_equal(
                base_raw[mode], out, label=f"mesh={key} {mode}"
            )
            if mode == "plain":
                s = engine.stats.serving_summary()
                seq_results[key] = {
                    "p50_step_ms": s["p50_step_ms"],
                    "p99_step_ms": s["p99_step_ms"],
                    "p50_tpot_ms": s["p50_tpot_ms"],
                }

    summary = {
        "arch": arch,
        "mesh_sizes": list(mesh_sizes),
        "backend": backend,
        "identity": "bitwise (plain, speculative K=2, chunked)",
        **results,
    }
    if seq_results:
        summary["seq"] = {
            "shapes": ["x".join(map(str, s)) for s in seq_shapes],
            "identity": "argmax token identity (tolerance lane, "
                        "exact flash-partials combine)",
            **seq_results,
        }
    print_fn("# serving — mesh-sharded paged decode (token-identity asserted)")
    print_fn(
        f"arch={arch} requests={n_requests} tokens={tokens} pool={max_batch} "
        f"heads={cfg.n_heads}/{cfg.n_kv_heads} backend={backend} "
        f"mesh_sizes={list(mesh_sizes)} seq_shapes={list(seq_shapes)}"
    )
    for tp in mesh_sizes:
        r = results[f"tp{tp}"]
        print_fn(
            f"mesh={tp}: step p50={r['p50_step_ms']:.2f}ms "
            f"p99={r['p99_step_ms']:.2f}ms tpot p50={r['p50_tpot_ms']:.2f}ms"
        )
    for key, r in seq_results.items():
        print_fn(
            f"mesh={key}: step p50={r['p50_step_ms']:.2f}ms "
            f"p99={r['p99_step_ms']:.2f}ms tpot p50={r['p50_tpot_ms']:.2f}ms"
        )
    print_fn("token identity: plain + speculative(K=2) + chunked — "
             "bitwise (model), argmax tokens (seq lanes)")
    return summary


def run_observability(
    *,
    arch: str = "smollm-135m",
    n_requests: int = 8,
    rate_rps: float = 50.0,
    max_batch: int = 3,
    prompt_len: int = 12,
    tokens: int = 12,
    chunk_size: int = 8,
    spec_k: int = 2,
    reps: int = 3,
    overhead_factor: float = 1.5,
    trace_path: str | None = None,
    seed: int = 0,
    print_fn=print,
) -> dict:
    """Flight-recorder contract: tracing observes, never perturbs.

    One warmed paged engine serves the same speculative + chunked
    open-loop workload with telemetry off and on — the per-call
    ``telemetry=`` override means both runs share every jitted
    executable, so the measured delta is pure recording overhead. The
    off/on serves are interleaved ``reps`` times and compared as
    PAIRED per-rep p50 ratios (machine drift moves both sides of a
    pair together, so the best pair isolates the recording cost from
    shared-runner noise); the pinned ``overhead_factor`` is the BENCH
    guard against gross regressions like an accidental per-event host
    sync. Token identity off == on is
    asserted bitwise. The ON run's ``window_summary`` (the online-
    adviser signal vector) is sanity-checked, and the measured
    speculation profile is fed to ``SpeculationAdvisorTool`` while the
    recorder is armed so the decision — with its priced inputs — lands
    in the exported trace as an adviser-audit event. ``trace_path``
    exports Chrome/Perfetto JSON, validated structurally before the
    path is reported."""
    from repro.configs import get_config
    from repro.core.tools import SpecMeasurement, SpeculationAdvisorTool
    from repro.models import Model
    from repro.serve import ServingEngine, SpecConfig
    from repro.serve.load import make_requests
    from repro.serve.telemetry import Telemetry, validate_chrome_trace

    cfg = get_config(arch).reduced()
    model = Model(cfg)
    params, _ = model.init(jax.random.key(seed))
    engine = ServingEngine(
        model, params, max_seq=64, kv_layout="paged", block_size=8
    )
    spec = SpecConfig(k=spec_k, drafter="ngram")
    serve_kw = dict(max_batch=max_batch, seed=seed, spec=spec, chunk_size=chunk_size)

    def workload():
        return make_requests(
            n_requests, rate_rps, vocab=cfg.vocab_size, max_new_tokens=tokens,
            prompt_lens=(prompt_len,), rng=np.random.default_rng(seed),
        )

    tel = Telemetry(enabled=True, capacity=1 << 16)
    off = Telemetry(enabled=False)
    engine.serve(workload(), **serve_kw)  # warm every jitted executable

    p50 = {"off": [], "on": []}
    outputs: dict = {}
    for _ in range(reps):
        for mode, t in (("off", off), ("on", tel)):
            reqs = workload()
            out = engine.serve(reqs, telemetry=t, **serve_kw)
            p50[mode].append(engine.stats.percentile(50))
            outputs[mode] = [np.asarray(out[r.rid]) for r in reqs]
    for a, b in zip(outputs["off"], outputs["on"]):
        np.testing.assert_array_equal(
            a, b, err_msg="telemetry changed the decoded tokens"
        )

    # the ON run left its windows on the shared stats registry
    window = engine.stats.registry.window_summary(8)
    assert window["admitted"] > 0, "no admissions landed in the window"
    assert window["step_cost_ms"] > 0, "no step cost landed in the window"
    assert 0.0 <= window["acceptance_rate"] <= 1.0
    assert window["pool_occupancy"] >= 0.0

    # adviser audit: price the measured profile with the recorder armed
    s = engine.stats.serving_summary()["speculative"]
    meas = SpecMeasurement(
        draft_ms_per_token=s["p50_draft_ms"] / max(1, spec_k),
        verify_ms={0: s["p50_verify_ms"], spec_k: s["p50_verify_ms"]},
        acceptance_rate=s["acceptance_rate"],
    )
    import repro.serve.telemetry as telemetry_mod

    was = telemetry_mod.GLOBAL
    telemetry_mod.GLOBAL = tel  # tools read the module global
    try:
        advised_k, _gain, advisor_line = SpeculationAdvisorTool(
            ks=(0, spec_k)
        ).choose(meas)
    finally:
        telemetry_mod.GLOBAL = was

    names = {e[1] for e in tel.tracer.events}
    assert "serve.step" in names, "no scheduler step span recorded"
    assert "speculation-decision" in names, "advisor decision not in trace"
    counts = validate_chrome_trace(tel.tracer.to_chrome_trace())
    if trace_path:
        tel.tracer.export(trace_path)

    p50_off, p50_on = min(p50["off"]), min(p50["on"])
    ratio = min(
        (on / off) for off, on in zip(p50["off"], p50["on"]) if off
    )
    summary = {
        "arch": arch,
        "n_requests": n_requests,
        "spec_k": spec_k,
        "chunk_size": chunk_size,
        "p50_step_off_ms": p50_off,
        "p50_step_on_ms": p50_on,
        "overhead_ratio": ratio,
        "max_overhead_factor": overhead_factor,
        "trace_events": len(tel.tracer.events),
        "trace_counts": counts,
        "advised_k": advised_k,
        "window": window,
    }
    if trace_path:
        summary["trace_path"] = trace_path
    print_fn("# serving — flight recorder (token-identity + overhead guard)")
    print_fn(
        f"arch={arch} requests={n_requests} K={spec_k} chunk={chunk_size} "
        f"pool={max_batch} reps={reps}"
    )
    print_fn(
        f"step p50 off={p50_off:.3f}ms on={p50_on:.3f}ms "
        f"overhead={ratio:.2f}x (pinned <{overhead_factor}x)"
    )
    print_fn(
        f"trace: {counts['events']} events ({counts['spans']} spans, "
        f"{counts['async_spans']} request spans, {counts['instants']} instants)"
        + (f" → {trace_path}" if trace_path else "")
    )
    print_fn(
        "window(8): "
        f"accept={window['acceptance_rate']:.2f} queue={window['queue_depth']:.1f} "
        f"occ={window['pool_occupancy']:.2f} step={window['step_cost_ms']:.3f}ms"
    )
    print_fn(f"advisor: {advisor_line}")
    assert ratio < overhead_factor, (
        f"telemetry overhead {ratio:.2f}x exceeds the pinned "
        f"{overhead_factor}x budget"
    )
    return summary


def _goodput(reqs, ttft_slo_ms: float, tpot_slo_ms) -> float:
    """Fraction of requests that finished AND met the latency SLO:
    TTFT (queueing included — the user-visible number) within
    ``ttft_slo_ms``, and, when ``tpot_slo_ms`` is set and the request
    decoded more than one token, per-token latency within it."""
    ok = 0
    for r in reqs:
        good = r.finished and r.ttft_ms is not None and r.ttft_ms <= ttft_slo_ms
        if good and tpot_slo_ms is not None and r.tpot_ms is not None:
            good = r.tpot_ms <= tpot_slo_ms
        ok += bool(good)
    return ok / len(reqs) if reqs else 0.0


def run_slo(
    *,
    arch: str = "smollm-135m",
    n_requests: int = 20,
    rate_rps: float = 60.0,
    max_batch: int = 4,
    tokens: int = 8,
    chunk_size: int = 16,
    long_len: int = 192,
    short_lens=(8, 16),
    block_size: int = 16,
    num_blocks=None,
    ttft_slo_steps: float = 12.0,
    tpot_slo_steps: float = 4.0,
    overload: bool = True,
    seed: int = 0,
    print_fn=print,
) -> dict:
    """Chunked vs monolithic prefill under priority load: SLO goodput.

    Identical Poisson workload (every 4th arrival a ``long_len``-token
    low-priority prompt, the rest short high-priority interactive
    requests) served twice through one paged engine — monolithic
    (``chunk_size=0``) then chunked — so both modes share jit caches
    and warm on a same-seeded run. ``overload=True`` under-provisions
    the block pool so high-priority arrivals preempt the long request
    mid-flight (the resume recompute is monolithic's second stall).
    SLO budgets are expressed in decode *steps* (multiples of the
    warmed monolithic p50 step) so the goodput contract is
    machine-speed independent. Token identity chunked == monolithic is
    asserted in both modes — chunking and preemption move work, never
    tokens."""
    from repro.configs import get_config
    from repro.models import Model
    from repro.serve import ServingEngine
    from repro.serve.load import make_slo_requests

    # mid-size so the long-prompt prefill stall is compute, not dispatch
    cfg = dataclasses.replace(
        get_config(arch).reduced(),
        num_layers=4, d_model=128, d_ff=384, n_heads=4, n_kv_heads=2, head_dim=32,
    )
    model = Model(cfg)
    params, _ = model.init(jax.random.key(seed))
    # headroom for the pow2-bucketed resume prefill of a preempted long
    # request (effective prompt up to long_len + tokens - 1 → next pow2)
    max_seq = 2 * long_len + 2 * block_size
    max_seq += (-max_seq) % block_size
    if num_blocks is None:
        if overload:
            # one long admission (ceil((long_len + tokens)/bs) blocks)
            # plus ~1.5 shorts: the next high-priority arrival finds the
            # pool dry and must evict the long — preemption by design
            num_blocks = (long_len + tokens + block_size - 1) // block_size + 3
        else:
            num_blocks = max_batch * (max_seq // block_size)

    engine = ServingEngine(
        model, params, max_seq=max_seq, kv_layout="paged",
        block_size=block_size, num_blocks=num_blocks,
    )

    def workload(rng_seed):
        return make_slo_requests(
            n_requests, rate_rps, vocab=cfg.vocab_size, max_new_tokens=tokens,
            short_lens=short_lens, long_len=long_len,
            rng=np.random.default_rng(rng_seed),
        )

    # pre-compile the chunk trace family (closed: pow2 buckets ≤
    # chunk_size) so a resume tail hitting a fresh bucket mid-window
    # can't charge its compile to the chunked p99 — the monolithic
    # stall being measured is prefill COMPUTE, and the comparison
    # should be too
    engine.scheduler(max_batch, seed=seed, chunk_size=chunk_size).prime()

    results, outputs, requests = {}, {}, {}
    slo_ms = None
    for mode, chunk in (("monolithic", 0), ("chunked", chunk_size)):
        engine.serve(workload(seed), max_batch=max_batch, seed=seed,
                     chunk_size=chunk)  # warm jit caches on the same arrivals
        if slo_ms is None:
            # budget in steps × the warmed monolithic median step: the
            # same absolute targets then price both modes
            base = engine.stats.percentile(50)
            slo_ms = (ttft_slo_steps * base, tpot_slo_steps * base)
        reqs = workload(seed)
        out = engine.serve(reqs, max_batch=max_batch, seed=seed, chunk_size=chunk)
        assert all(r.finished for r in reqs), f"{mode}: starved requests"
        results[mode] = dict(
            engine.stats.serving_summary(),
            goodput=_goodput(reqs, slo_ms[0], slo_ms[1]),
        )
        outputs[mode] = [np.asarray(out[r.rid]) for r in reqs]
        requests[mode] = reqs

    for a, b in zip(outputs["monolithic"], outputs["chunked"]):
        np.testing.assert_array_equal(
            a, b, err_msg="chunked prefill changed the decoded tokens"
        )
    if overload:
        assert results["chunked"]["preemptions"] > 0, (
            "overload pool produced no preemptions — pressure knobs too loose"
        )
        assert results["chunked"]["goodput"] > 0, "no request met the SLO"
        assert (
            results["chunked"]["p99_step_ms"] < results["monolithic"]["p99_step_ms"]
        ), "chunking did not cut the p99 decode step"

    ratio = (
        results["monolithic"]["p99_step_ms"] / results["chunked"]["p99_step_ms"]
        if results["chunked"]["p99_step_ms"]
        else 0.0
    )
    summary = {
        "arch": arch,
        "chunk_size": chunk_size,
        "overload": overload,
        "rate_rps": rate_rps,
        "long_len": long_len,
        "block_size": block_size,
        "num_blocks": num_blocks,
        "ttft_slo_ms": slo_ms[0],
        "tpot_slo_ms": slo_ms[1],
        "monolithic": results["monolithic"],
        "chunked": results["chunked"],
        "p99_step_ratio": ratio,
    }
    print_fn("# serving — chunked prefill + priority/preemption SLO goodput")
    print_fn(
        f"arch={arch} requests={n_requests} rate={rate_rps}/s pool={max_batch} "
        f"blocks={num_blocks}x{block_size} chunk={chunk_size} "
        f"overload={overload} slo: ttft<={slo_ms[0]:.1f}ms tpot<={slo_ms[1]:.1f}ms"
    )
    for mode in ("monolithic", "chunked"):
        s = results[mode]
        print_fn(
            f"{mode:10s} goodput={s['goodput']:.2f} "
            f"ttft p99={s['p99_ttft_ms']:.1f}ms "
            f"step p50={s['p50_step_ms']:.2f}ms p99={s['p99_step_ms']:.2f}ms | "
            f"preempt={s['preemptions']} recompute={s['recomputed_tokens']}tok "
            f"qwait p99={s['p99_queue_wait_ms'] or 0:.1f}ms"
        )
    print_fn(f"p99 step: monolithic/chunked = {ratio:.1f}x")
    return summary


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--shared-prefix", action="store_true",
                    help="shared-prefix reuse mode (paged engine, on vs off)")
    ap.add_argument("--speculative", action="store_true",
                    help="speculative-decode mode (n-gram drafter, K sweep vs K=0)")
    ap.add_argument("--backend", metavar="NAME", default=None,
                    choices=("reference", "kernel", "interpret"),
                    help="attention-backend mode: serve both KV layouts through "
                         "NAME and the reference backend, asserting token "
                         "identity (CI kernel smoke: --backend interpret)")
    ap.add_argument("--drift", metavar="PATH", nargs="?", const="drift_decisions.json",
                    default=None,
                    help="online-adviser mode: serve the drifting-"
                         "draftability workload per static K and under the "
                         "closed-loop controller (token identity, zero "
                         "retraces after prime, controller beats the worst "
                         "static arm — CI drift smoke), writing the decision "
                         "audit trail to PATH (default drift_decisions.json)")
    ap.add_argument("--chunked", action="store_true",
                    help="SLO-goodput mode: chunked vs monolithic prefill on "
                         "the mixed-priority workload")
    ap.add_argument("--overload", action="store_true",
                    help="with --chunked: under-provision the paged pool so "
                         "preemption fires (CI overload smoke)")
    ap.add_argument("--trace", metavar="PATH", nargs="?", const="serving_trace.json",
                    default=None,
                    help="observability mode: serve one workload with the "
                         "flight recorder off and on (token identity + "
                         "overhead guard asserted) and export Chrome/"
                         "Perfetto trace-event JSON to PATH (default "
                         "serving_trace.json; load in ui.perfetto.dev or "
                         "chrome://tracing)")
    ap.add_argument("--mesh", metavar="N[xM]", default=None,
                    help="sharded mode. N: serve one workload at every "
                         "power-of-two mesh size up to N through the "
                         "head-partitioned paged path, asserting bitwise "
                         "token identity vs single-device (CI multi-device "
                         "smoke: --mesh 4). NxM: a 2D ('model','seq') sweep "
                         "— head-only N (bitwise), seq-only M and NxM "
                         "(argmax token identity, the kv-sequence-split "
                         "tolerance lane; CI smoke: --mesh 2x2)")
    args = ap.parse_args()
    from repro import compile_cache

    compile_cache.enable()
    if args.shared_prefix:
        run_shared_prefix()
    elif args.speculative:
        run_speculative()
    elif args.backend:
        run_backend_sweep(backends=("reference", args.backend))
    elif args.drift:
        run_drift(decisions_path=args.drift)
    elif args.trace:
        run_observability(trace_path=args.trace)
    elif args.chunked:
        run_slo(overload=args.overload)
    elif args.mesh:
        if "x" in args.mesh:
            tp, sp = (int(v) for v in args.mesh.split("x"))
            run_sharded(mesh_sizes=(1, tp), seq_shapes=((sp,), (tp, sp)))
        else:
            n = int(args.mesh)
            run_sharded(
                mesh_sizes=tuple(
                    2 ** i for i in range(n.bit_length()) if 2 ** i <= n
                ),
                seq_shapes=(),
            )
    else:
        run()
