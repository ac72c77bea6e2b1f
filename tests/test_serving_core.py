"""Continuous-batching serving core: slot alloc/free invariants, masked
plan execution (no retrace across live counts, mask correctness),
fixed-batch vs continuous-batch token equivalence, per-request latency
accounting, and the stack-combine contract of ``set_decode_plan``."""
import random

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import Aira, Workload, clear_plan_cache
from repro.core.plan import plan_for
from repro.core.relic import relic_pfor
from repro.models import Model
from repro.serve import Request, ServingEngine, SlotKVCache


@pytest.fixture(scope="module")
def served():
    cfg = get_config("smollm-135m").reduced()
    m = Model(cfg)
    params, _ = m.init(jax.random.key(0))
    prompts = jax.random.randint(jax.random.key(1), (4, 8), 0, cfg.vocab_size)
    return cfg, m, params, prompts


# ---------------------------------------------------------------------------
# slot pool


def test_slot_alloc_free_invariants_random_order(served):
    """Random admit/finish sequences preserve the pool partition: every
    slot is exactly one of {free, live}, no double alloc/free, freed
    slots are reusable, lowest-free-first allocation is deterministic."""
    _, m, _, _ = served
    kv = SlotKVCache(m, max_batch=4, max_seq=16)
    rng = random.Random(0)
    live: dict[int, int] = {}  # slot → rid
    rid = 0
    for _ in range(300):
        if kv.n_free and (not live or rng.random() < 0.5):
            slot = kv.alloc(rid)
            assert slot not in live
            assert slot == min(set(range(4)) - set(live))  # lowest free
            assert kv.owner(slot) == rid
            live[slot] = rid
            rid += 1
        else:
            slot = rng.choice(sorted(live))
            kv.free(slot)
            del live[slot]
        kv.check_invariants()
        assert kv.n_live == len(live)
        np.testing.assert_array_equal(
            kv.live_mask(), [s in live for s in range(4)]
        )
    if not live:
        live[kv.alloc(rid)] = rid
    slot = rng.choice(sorted(live))
    kv.free(slot)
    with pytest.raises(RuntimeError, match="double free"):
        kv.free(slot)


def test_slot_pool_exhaustion_and_write_guard(served):
    _, m, _, _ = served
    kv = SlotKVCache(m, max_batch=2, max_seq=16)
    kv.alloc(0), kv.alloc(1)
    with pytest.raises(RuntimeError, match="free cache slot"):
        kv.alloc(2)
    kv.free(0)
    with pytest.raises(RuntimeError, match="free slot"):
        kv.write(0, kv.read(1))  # slot 0 no longer live


def test_slot_write_read_roundtrip(served):
    """A request's prefill cache written into a slot reads back intact."""
    _, m, params, prompts = served
    _, cache1 = m.prefill(params, prompts[1:2], 16)
    kv = SlotKVCache(m, max_batch=3, max_seq=16)
    slot = kv.alloc(7)
    kv.write(slot, cache1)
    back = kv.read(slot)
    for got, want in zip(jax.tree.leaves(back), jax.tree.leaves(cache1)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ---------------------------------------------------------------------------
# continuous batching == fixed batch (greedy, token-for-token)


def test_half_full_continuous_batch_matches_fixed_batch(served):
    _, m, params, prompts = served
    eng = ServingEngine(m, params, max_seq=64)
    base = eng.generate(prompts[:2], n_steps=4)
    eng2 = ServingEngine(m, params, max_seq=64)
    reqs = [
        Request(prompt=prompts[i], max_new_tokens=4, arrival_time=0.02 * i)
        for i in range(2)
    ]
    out = eng2.serve(reqs, max_batch=4)  # pool stays half full
    for i, r in enumerate(reqs):
        np.testing.assert_array_equal(out[r.rid], np.asarray(base[i]))
        assert r.finished and r.ttft_ms is not None and r.e2e_ms is not None
    assert len(eng2.stats.ttft_ms) == 2


def test_staggered_lengths_and_slot_reuse(served):
    """3 requests with different prompt lengths and budgets through a
    2-slot pool: the third is admitted into a freed slot while others
    are mid-decode (divergent per-slot cache lengths), and every output
    matches its single-request baseline."""
    _, m, params, prompts = served
    lens, budgets = (5, 8, 6), (3, 5, 4)
    reqs = [
        Request(
            prompt=prompts[i, : lens[i]],
            max_new_tokens=budgets[i],
            arrival_time=0.01 * i,
        )
        for i in range(3)
    ]
    eng = ServingEngine(m, params, max_seq=64)
    out = eng.serve(reqs, max_batch=2)
    for i, r in enumerate(reqs):
        base = ServingEngine(m, params, max_seq=64).generate(
            prompts[i : i + 1, : lens[i]], n_steps=budgets[i]
        )
        np.testing.assert_array_equal(out[r.rid], np.asarray(base[0]))


def test_eos_finishes_early_and_frees_slot(served):
    """EOS fires on its first occurrence: pick a prompt whose greedy
    stream first emits some token at index 2 (a tiny random model often
    repeats one token, so the choice is made from the streams, not
    assumed), make that token EOS, and the request stops after it."""
    cfg, m, params, _ = served
    cands = jax.random.randint(jax.random.key(2), (16, 8), 0, cfg.vocab_size)
    base = np.asarray(ServingEngine(m, params, max_seq=64).generate(cands, n_steps=4))
    rows = [i for i in range(len(base)) if base[i, 2] not in base[i, :2]]
    assert rows, f"no candidate prompt emits a new token at index 2: {base}"
    i = rows[0]
    eng = ServingEngine(m, params, max_seq=64)
    req = Request(prompt=cands[i], max_new_tokens=16, eos_id=int(base[i, 2]))
    out = eng.serve([req], max_batch=2)
    np.testing.assert_array_equal(out[req.rid], base[i, :3])
    assert req.finished


# ---------------------------------------------------------------------------
# masked plan execution


def test_masked_relic_stack_and_sum():
    fn = lambda x: x * 2.0 + 1.0
    items = jnp.arange(10, dtype=jnp.float32)
    mask = jnp.array([1, 1, 0, 1, 0, 0, 1, 1, 1, 0], bool)
    out = relic_pfor(fn, items, granularity=2, valid=mask)
    np.testing.assert_array_equal(
        np.asarray(out), np.where(np.asarray(mask), np.asarray(fn(items)), 0.0)
    )
    s = relic_pfor(lambda x: x, items, granularity=4, combine="sum", valid=mask)
    np.testing.assert_allclose(float(s), float(items[mask].sum()))


def test_execute_masked_single_trace_across_live_counts():
    """The mask is data, not shape: changing the number of live items
    must not retrace the plan's compiled region."""
    clear_plan_cache()
    traces = []

    def fn(x):  # python side effect fires at trace time only
        traces.append(1)
        return x + 1.0

    items = jnp.arange(12, dtype=jnp.float32)
    plan = plan_for("masked-trace-count", fn, items, granularity=2)
    for n_live in (3, 7, 12, 1):
        mask = jnp.arange(12) < n_live
        got = plan.execute_masked(items, mask)
        np.testing.assert_array_equal(
            np.asarray(got), np.where(np.asarray(mask), np.asarray(items + 1.0), 0.0)
        )
    assert len(traces) == 1, "masked plan execution retraced on live-count change"


def test_masked_plan_decode_matches_plain_partial_batch(served):
    """Plan-decode == plain-decode with a partially full pool: the
    accepted RegionPlan, executed masked over the active-slot view,
    reproduces the unplanned scheduler token-for-token."""
    _, m, params, prompts = served
    eng = ServingEngine(m, params, max_seq=64)
    region = eng.decode_region(prompts, force=True, seed=3)
    d = Aira().advise(Workload("serve-mask", lambda: None, [region])).decisions[0]
    assert d.accepted and d.plan is not None

    def staggered():
        return [
            Request(prompt=prompts[i], max_new_tokens=3 + i, arrival_time=0.01 * i)
            for i in range(2)
        ]

    plain_reqs = staggered()
    plain = ServingEngine(m, params, max_seq=64).serve(plain_reqs, max_batch=4)
    eng2 = ServingEngine(m, params, max_seq=64, decode_plan=d.plan)
    plan_reqs = staggered()
    planned = eng2.serve(plan_reqs, max_batch=4)
    for rp, rq in zip(plain_reqs, plan_reqs):
        np.testing.assert_array_equal(plain[rp.rid], planned[rq.rid])


def test_scheduler_rejects_sum_combine_plan(served):
    _, m, params, _ = served
    eng = ServingEngine(m, params, max_seq=64)
    bad = plan_for("bad-sum", lambda x: x, jnp.arange(4.0), granularity=1, combine="sum")
    with pytest.raises(ValueError, match="stack"):
        eng.set_decode_plan(bad)
    with pytest.raises(ValueError, match="stack"):
        eng.scheduler(2).set_decode_plan(bad)


def test_submit_rejects_over_capacity_request(served):
    """Prompt + budget beyond max_seq would clamp cache writes and
    silently corrupt tokens — submission must fail loudly instead."""
    _, m, params, prompts = served
    eng = ServingEngine(m, params, max_seq=16)
    with pytest.raises(ValueError, match="max_seq"):
        eng.serve(
            [Request(prompt=jnp.ones((12,), jnp.int32), max_new_tokens=8)],
            max_batch=1,
        )


def test_make_requests_handles_budget_of_one():
    from repro.serve.load import make_requests

    reqs = make_requests(
        3, 100.0, vocab=50, max_new_tokens=1, rng=np.random.default_rng(0)
    )
    assert all(r.max_new_tokens == 1 for r in reqs)


# ---------------------------------------------------------------------------
# stats lifecycle


def test_serving_summary_explicit_when_no_request_finished():
    """A run where zero requests finish yields an explicit empty
    summary (empty=True, None latencies) — not 0 ms percentiles over
    empty series — while step timings, measured per decode, survive."""
    from repro.serve import ServeStats

    s = ServeStats()
    out = s.serving_summary()
    assert out["empty"] and out["n_requests"] == 0
    assert out["p50_ttft_ms"] is None and out["p99_e2e_ms"] is None
    assert out["p50_step_ms"] is None  # no steps either
    s.step_ms.extend([1.0, 2.0])  # steps ran, but nothing retired yet
    out = s.serving_summary()
    assert out["empty"] and out["p50_ttft_ms"] is None
    assert out["p50_step_ms"] == 1.5 and out["n_steps"] == 2


def test_stats_reset_per_run(served):
    _, m, params, prompts = served
    eng = ServingEngine(m, params, max_seq=64)
    eng.generate(prompts[:2], n_steps=3)
    first = list(eng.stats.step_ms)
    assert len(first) == 2  # n_steps - 1 decode steps
    eng.generate(prompts[:2], n_steps=3)
    assert len(eng.stats.step_ms) == 2  # clean per run, no accumulation
    assert len(eng.stats.ttft_ms) == 2 and len(eng.stats.e2e_ms) == 2
    assert eng.stats.percentile(50) > 0
    s = eng.stats.serving_summary()
    assert s["n_requests"] == 2 and s["p99_ttft_ms"] >= s["p50_ttft_ms"] >= 0
    eng.stats.reset()
    assert eng.stats.summary().startswith("steps=0")
