"""Priority scheduling + block-pressure preemption: preempt→resume is
token-identical to an uninterrupted run (both layouts, ± speculation),
priority order is respected, nobody starves under random mixed-priority
load, the paged trie re-registration makes resumption suffix-only, and
the new ServeStats counters (preemptions, recomputed tokens, queue-wait
split, rejected submissions) account for all of it."""
import jax
import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.configs import get_config
from repro.models import Model
from repro.serve import Request, ServingEngine, SpecConfig


_STATE: dict = {}


def _model_state():
    """Lazy module singleton, shared by the module fixture and the
    ``@given`` property tests (built once, not once per example)."""
    if not _STATE:
        cfg = get_config("smollm-135m").reduced()
        m = Model(cfg)
        params, _ = m.init(jax.random.key(0))
        _STATE["v"] = (cfg, m, params)
    return _STATE["v"]


@pytest.fixture(scope="module")
def served():
    return _model_state()


def _pressure_workload():
    """Two long low-priority requests admitted first, then a
    high-priority arrival that needs their row: preemption by design."""
    low = [
        Request(prompt=np.arange(20, dtype=np.int32) + i, max_new_tokens=10,
                arrival_time=0.0, priority=0)
        for i in range(2)
    ]
    high = [Request(prompt=np.arange(9, dtype=np.int32), max_new_tokens=4,
                    arrival_time=0.02, priority=5)]
    return low + high


def _engines(m, params, layout, tight):
    """(pressured, roomy) engines: same model, the first sized so the
    high-priority arrival must evict, the second so nothing ever waits."""
    kw = dict(block_size=8, num_blocks=10) if tight else dict(block_size=8)
    pressured = ServingEngine(
        m, params, max_seq=128, kv_layout=layout, max_batch=2, **kw
    )
    roomy = ServingEngine(
        m, params, max_seq=128, kv_layout=layout, max_batch=4, block_size=8
    )
    return pressured, roomy


# ---------------------------------------------------------------------------
# preempt → resume token identity


@pytest.mark.parametrize("layout", ["slot", "paged"])
def test_preempt_resume_token_identity(served, layout):
    """An evicted-and-resumed request decodes bitwise what it would
    have decoded uninterrupted — preemption moves work, never tokens."""
    _, m, params = served
    pressured, roomy = _engines(m, params, layout, tight=layout == "paged")
    p_reqs = _pressure_workload()
    p_out = pressured.serve(p_reqs)
    assert pressured.stats.n_preemptions > 0, "pressure scenario did not evict"
    assert pressured.stats.recomputed_tokens > 0
    assert all(r.finished for r in p_reqs)
    assert any(r.preemptions > 0 for r in p_reqs)

    r_reqs = _pressure_workload()
    r_out = roomy.serve(r_reqs)
    assert roomy.stats.n_preemptions == 0
    for a, b in zip(p_reqs, r_reqs):
        np.testing.assert_array_equal(p_out[a.rid], r_out[b.rid])


@pytest.mark.parametrize("layout", ["slot", "paged"])
def test_preempt_resume_with_speculation(served, layout):
    """Preemption composes with speculative decoding: the saved sample
    key and draft catch-up make the resumed stream bitwise the plain
    uninterrupted greedy one."""
    _, m, params = served
    spec = SpecConfig(k=4, drafter="ngram")
    pressured, roomy = _engines(m, params, layout, tight=layout == "paged")
    p_reqs = _pressure_workload()
    p_out = pressured.serve(p_reqs, spec=spec)
    assert pressured.stats.n_preemptions > 0
    r_reqs = _pressure_workload()
    r_out = roomy.serve(r_reqs, spec=SpecConfig(k=0))
    for a, b in zip(p_reqs, r_reqs):
        np.testing.assert_array_equal(p_out[a.rid], r_out[b.rid])


def test_preempt_resume_chunked(served):
    """Preemption under chunked prefill: the resume recompute walks the
    chunk path and still lands on the identical stream."""
    _, m, params = served
    pressured, roomy = _engines(m, params, "paged", tight=True)
    p_reqs = _pressure_workload()
    p_out = pressured.serve(p_reqs, chunk_size=8)
    assert pressured.stats.n_preemptions > 0
    r_reqs = _pressure_workload()
    r_out = roomy.serve(r_reqs, chunk_size=0)
    for a, b in zip(p_reqs, r_reqs):
        np.testing.assert_array_equal(p_out[a.rid], r_out[b.rid])


# ---------------------------------------------------------------------------
# priority order and starvation


def test_priority_order_first_service(served):
    """With one row and simultaneous arrivals, first admission follows
    (-priority, arrival, rid) strictly."""
    _, m, params = served
    eng = ServingEngine(m, params, max_seq=64, kv_layout="slot", max_batch=1)
    reqs = [
        Request(prompt=np.arange(4, dtype=np.int32) + i, max_new_tokens=2,
                arrival_time=0.0, priority=p)
        for i, p in enumerate([0, 3, 1, 3])
    ]
    eng.serve(reqs)
    order = sorted(reqs, key=lambda r: r.t_first_admit)
    assert [r.rid for r in order] == [
        r.rid for r in sorted(reqs, key=lambda r: (-r.priority, r.arrival_time, r.rid))
    ]


@settings(max_examples=5, deadline=None)
@given(seed=st.integers(0, 10_000))
def test_no_starvation_random_mixed_priorities(seed):
    """Property: random arrivals, lengths, budgets, and priorities on a
    tight paged pool — every admitted request finishes with its full
    budget, and the pool's invariants hold afterwards. Strict priority
    cannot starve: arrivals are finite and every preemption strictly
    raises the running set's priority."""
    _, m, params = _model_state()
    rng = np.random.default_rng(seed)
    eng = ServingEngine(
        m, params, max_seq=96, kv_layout="paged", max_batch=2,
        block_size=8, num_blocks=12,
    )
    n = int(rng.integers(3, 7))
    reqs = [
        Request(
            prompt=rng.integers(0, 100, size=(int(rng.integers(2, 24)),)).astype(np.int32),
            max_new_tokens=int(rng.integers(1, 8)),
            arrival_time=float(rng.uniform(0, 0.05)),
            priority=int(rng.integers(0, 3)),
        )
        for _ in range(n)
    ]
    sched = eng.scheduler(2)
    out = sched.run(reqs)
    assert all(r.finished for r in reqs)
    for r in reqs:
        assert len(out[r.rid]) == r.max_new_tokens  # no eos: full budget
    sched.kv.check_invariants()


# ---------------------------------------------------------------------------
# paged trie re-registration: resume is suffix-only recompute


def test_paged_preempt_reregisters_committed_blocks(served):
    """Eviction parks the victim's committed full blocks in the trie, so
    its resume prefix-matches its own history: the recompute is the
    uncommitted suffix, not the whole prompt."""
    _, m, params = served
    eng, _ = _engines(m, params, "paged", tight=True)
    reqs = _pressure_workload()
    eng.serve(reqs)
    assert eng.stats.n_preemptions > 0
    victim = next(r for r in reqs if r.preemptions > 0)
    # committed history at eviction ≥ the prompt's full blocks; the
    # resume recompute must be smaller than recomputing from scratch
    assert 0 < eng.stats.recomputed_tokens < (
        eng.stats.n_preemptions * (len(victim.prompt) + victim.max_new_tokens)
    )


# ---------------------------------------------------------------------------
# stats accounting


def test_preemption_stats_and_queue_wait_split(served):
    _, m, params = served
    eng, _ = _engines(m, params, "paged", tight=True)
    reqs = _pressure_workload()
    eng.serve(reqs)
    s = eng.stats.serving_summary()
    assert s["preemptions"] == eng.stats.n_preemptions > 0
    assert s["recomputed_tokens"] == eng.stats.recomputed_tokens > 0
    assert s["rejected_submissions"] == 0
    for key in ("p50_queue_wait_ms", "p99_queue_wait_ms",
                "p50_service_ttft_ms", "p99_service_ttft_ms"):
        assert s[key] is not None
    for r in reqs:  # queue wait + service = TTFT, each leg nonnegative
        assert 0 <= r.queue_wait_ms <= r.ttft_ms + 1e-9
        assert abs(r.queue_wait_ms + r.service_ttft_ms - r.ttft_ms) < 1e-6


def test_rejected_submission_counted(served):
    _, m, params = served
    eng = ServingEngine(m, params, max_seq=16, kv_layout="slot", max_batch=1)
    sched = eng.scheduler(1)
    with pytest.raises(ValueError, match="max_seq"):
        sched.submit(Request(prompt=np.arange(20, dtype=np.int32), max_new_tokens=4))
    assert eng.stats.rejected_submissions == 1
    sched.submit(Request(prompt=np.arange(4, dtype=np.int32), max_new_tokens=4))
    assert eng.stats.rejected_submissions == 1
