"""Online adaptive adviser (serve/controller.py, DESIGN.md §9): the
shared pricing functions against the offline advisor tools they were
refactored from, OnlineAdviser hysteresis unit behaviour (switch on a
priced win, threshold and dwell gates, K=0 probing with revert),
admission throttling, retrace-free live switching (randomized mid-serve
K/backend decisions → zero new jit compiles after ``prime()``), token
identity under any decision sequence (a pinned controller == the static
configuration, bitwise), the ModelDraftSource 0→K catch-up, controller
observability surfaces (Prometheus text, registry snapshot,
``serving_summary()["controller"]``), and ``window_summary`` cold-start
finiteness."""
import jax
import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.configs import get_config
from repro.core.tools import (
    KernelAdvisorTool,
    SpecMeasurement,
    SpeculationAdvisorTool,
    price_backends,
    price_speculation,
)
from repro.models import Model
from repro.serve import (
    Decision,
    OnlineAdviser,
    PinnedController,
    Request,
    ServingEngine,
    SpecConfig,
)
from repro.serve.telemetry import MetricsRegistry

_STATE: dict = {}


def _model_state():
    """Lazy module singleton, shared by the module fixture and the
    ``@given`` property tests (built once, not once per example).
    The engine is primed over the K × backend grid once — every test
    that switches mid-serve rides the same warmed trace families."""
    if not _STATE:
        cfg = get_config("smollm-135m").reduced()
        m = Model(cfg)
        params, _ = m.init(jax.random.key(0))
        eng = ServingEngine(m, params, max_seq=64, kv_layout="paged", block_size=8)
        primed = eng.prime(2, ks=(0, 2, 4), backends=("reference", "interpret"))
        _STATE["v"] = (cfg, m, params, eng, primed)
    return _STATE["v"]


@pytest.fixture(scope="module")
def served():
    return _model_state()


def _workload(vocab, specs=((8, 6), (12, 8), (8, 5), (16, 4)), arrival=0.0, seed=7):
    rng = np.random.default_rng(seed)
    return [
        Request(
            prompt=rng.integers(0, vocab, size=n).astype(np.int32),
            max_new_tokens=t, arrival_time=arrival * i,
        )
        for i, (n, t) in enumerate(specs)
    ]


def _jit_cache_size(eng) -> int:
    fns = [eng._prefill, eng._prefill_prefix]
    for family in eng._steps.values():
        fns.extend(family.values())
    return sum(
        f._cache_size() for f in fns if f is not None and hasattr(f, "_cache_size")
    )


# ---------------------------------------------------------------------------
# shared pricing == the offline advisor tools (the refactor changed nothing)


def test_price_speculation_matches_tool():
    tool = SpeculationAdvisorTool(ks=(0, 2, 4, 8))
    for p in (0.0, 0.3, 0.6, 0.9, 1.0):
        for draft in (0.01, 0.1, 1.0):
            for v8 in (2.2, 4.0, 9.0):
                m = SpecMeasurement(
                    draft_ms_per_token=draft,
                    verify_ms={0: 2.0, 8: v8},
                    acceptance_rate=p,
                )
                k_tool, gain_tool, _ = tool.choose(m)
                k, cost, gain, costs = price_speculation(m, (0, 2, 4, 8))
                assert k == k_tool, (p, draft, v8)
                assert gain == pytest.approx(gain_tool)
                assert costs[0] == pytest.approx(m.verify_cost(0))
                if k:
                    assert cost == pytest.approx(costs[k])


def test_price_speculation_threshold_gates_to_zero():
    m = SpecMeasurement(0.05, {0: 2.0, 8: 2.2}, 0.05)  # marginal win at best
    k, cost, gain, _ = price_speculation(m, (0, 2, 4, 8), threshold=0.5)
    assert k == 0 and gain == 0.0 and cost == pytest.approx(m.verify_cost(0))


def test_price_backends_matches_tool():
    tool = KernelAdvisorTool()
    for cells in (
        {"reference": 2.0, "kernel": 1.0},
        {"reference": 1.0, "kernel": 2.0},
        {"reference": 1.0, "kernel": 0.99},  # under the 2% gate
    ):
        from repro.core.tools import KernelMeasurement

        b_tool, gain_tool, _ = tool.choose(
            KernelMeasurement.make("llama", "paged", 2, dict(cells))
        )
        b, ms, gain = price_backends(dict(cells))
        assert b == b_tool and gain == pytest.approx(gain_tool)
        assert ms == pytest.approx(cells[b])
    # online baseline: priced against the live arm, not "reference"
    b, _, gain = price_backends(
        {"reference": 1.0, "kernel": 1.5}, baseline="kernel"
    )
    assert b == "reference" and gain == pytest.approx(0.5)


# ---------------------------------------------------------------------------
# OnlineAdviser unit behaviour over synthetic sensor windows


def _summary(**kw):
    base = dict(
        window=8, ticks=8, acceptance_rate=0.0, proposed=0.0, accepted=0.0,
        spec_steps=0.0, p50_draft_ms=0.0, p50_verify_ms=0.0, queue_depth=0.0,
        active=2.0, pool_occupancy=0.5, pool_free_blocks=10.0,
        step_cost_ms=0.0, p99_step_ms=0.0, admitted=0.0, preemptions=0.0,
        rejected=0.0, prefix_hit_rate=0.0, chunk_utilization=0.0,
        alloc_rate=0.0, evict_rate=0.0, park_rate=0.0, retraces=0.0,
    )
    base.update(kw)
    return base


def _seeded(**kw):
    args = dict(ks=(0, 2, 4), decision_interval=1, window=8, dwell=2,
                threshold=0.05, probe_every=2)
    args.update(kw)
    ctl = OnlineAdviser(**args)
    # K=0 decode 2ms; verify widths barely above it — a high p̂ pays off
    ctl.seed_costs({"reference": {0: 2.0, 2: 2.3, 4: 2.6}},
                   draft_ms_per_token=0.05)
    return ctl


def test_switch_up_on_observed_acceptance():
    ctl = _seeded()
    d = ctl.decide(
        _summary(acceptance_rate=0.9, proposed=8.0, accepted=7.2,
                 p50_draft_ms=0.1, p50_verify_ms=2.3),
        k_live=2, backend_live="reference", step=1,
    )
    assert d.k == 4 and d.switched and d.predicted_gain > 0.05
    assert ctl.n_switches == 1 and ctl.dwell_remaining == 2


def test_dwell_blocks_immediate_reswitch():
    ctl = _seeded()
    ctl.decide(_summary(acceptance_rate=0.9, proposed=8.0, p50_verify_ms=2.3),
               k_live=2, backend_live="reference", step=1)
    assert ctl.dwell_remaining == 2
    # the very next window prices a flip back — dwell holds the arm
    d = ctl.decide(_summary(acceptance_rate=0.0, proposed=8.0, p50_verify_ms=9.0),
                   k_live=4, backend_live="reference", step=2)
    assert d.k == 4 and not d.switched
    d = ctl.decide(_summary(acceptance_rate=0.0, proposed=8.0, p50_verify_ms=9.0),
                   k_live=4, backend_live="reference", step=3)
    assert d.k == 4 and not d.switched
    # dwell spent: the down-switch lands
    d = ctl.decide(_summary(acceptance_rate=0.0, proposed=8.0, p50_verify_ms=9.0),
                   k_live=4, backend_live="reference", step=4)
    assert d.k == 0 and d.switched


def test_threshold_blocks_marginal_switch():
    ctl = _seeded(threshold=10.0, initial_k=2)  # nothing clears a 1000% gate
    d = ctl.decide(
        _summary(acceptance_rate=0.9, proposed=8.0, p50_verify_ms=2.3),
        k_live=2, backend_live="reference", step=1,
    )
    assert d.k == 2 and not d.switched and ctl.n_switches == 0


def test_probe_fires_at_k0_and_reverts_without_win():
    ctl = OnlineAdviser(ks=(0, 2, 4), decision_interval=1, window=8, dwell=0,
                        threshold=0.05, probe_every=2)
    ctl.seed_costs({"reference": {0: 2.0, 2: 4.0, 4: 8.0}})  # spec never pays
    # no observation yet → immediate probe at the smallest positive K
    d = ctl.decide(_summary(step_cost_ms=2.0), k_live=0,
                   backend_live="reference", step=1)
    assert d.probe and d.k == 2 and not d.switched
    # the probe window shows poor acceptance → revert to the committed 0
    d = ctl.decide(_summary(acceptance_rate=0.1, proposed=4.0, accepted=0.4,
                            p50_draft_ms=0.2, p50_verify_ms=4.0),
                   k_live=2, backend_live="reference", step=2)
    assert d.k == 0 and not d.probe and not d.switched
    assert "probe over" in d.reason
    # staleness accumulates again → next probe after probe_every decisions
    d3 = ctl.decide(_summary(step_cost_ms=2.0), k_live=0,
                    backend_live="reference", step=3)
    d4 = ctl.decide(_summary(step_cost_ms=2.0), k_live=0,
                    backend_live="reference", step=4)
    assert not d3.probe and d4.probe


def test_probe_commits_on_priced_win_and_counts_switch():
    ctl = _seeded(dwell=0)
    d = ctl.decide(_summary(step_cost_ms=2.0), k_live=0,
                   backend_live="reference", step=1)
    assert d.probe and d.k == 2
    # probe observed near-perfect acceptance: pricing lifts K and the
    # commit counts as ONE switch against the committed arm (0)
    d = ctl.decide(_summary(acceptance_rate=0.95, proposed=4.0, accepted=3.8,
                            p50_draft_ms=0.1, p50_verify_ms=2.3),
                   k_live=2, backend_live="reference", step=2)
    assert d.k == 4 and d.switched and ctl.n_switches == 1


def test_admission_throttle_under_pressure():
    ctl = _seeded()
    d = ctl.decide(
        _summary(preemptions=2.0, pool_occupancy=0.95, step_cost_ms=2.0),
        k_live=0, backend_live="reference", step=1,
    )
    assert d.admit_budget == 1
    d = ctl.decide(
        _summary(preemptions=0.0, pool_occupancy=0.95, step_cost_ms=2.0),
        k_live=0, backend_live="reference", step=2,
    )
    assert d.admit_budget is None


def test_audit_trail_json_ready():
    import json

    ctl = _seeded()
    ctl.decide(_summary(acceptance_rate=0.9, proposed=8.0, p50_verify_ms=2.3),
               k_live=2, backend_live="reference", step=1)
    trail = ctl.audit_trail()
    assert len(trail) == 1 and trail[0]["k"] == 4
    json.dumps(trail)
    json.dumps(ctl.summary())


def test_bad_construction_rejected():
    with pytest.raises(ValueError, match="initial_k"):
        OnlineAdviser(ks=(0, 2), initial_k=3)
    with pytest.raises(ValueError, match=">= 0"):
        OnlineAdviser(ks=(-1, 2))


# ---------------------------------------------------------------------------
# retrace-free switching + token identity through a real engine


def test_pinned_controller_matches_static_bitwise(served):
    cfg, _, _, eng, _ = _model_state()
    static = eng.serve(_workload(cfg.vocab_size), max_batch=2, seed=0,
                       spec=SpecConfig(k=2, drafter="ngram"))
    pinned = eng.serve(_workload(cfg.vocab_size), max_batch=2, seed=0,
                       spec=SpecConfig(k=2, drafter="ngram"),
                       controller=PinnedController(2, decision_interval=2))
    for rid_a, rid_b in zip(sorted(static), sorted(pinned)):
        np.testing.assert_array_equal(np.asarray(static[rid_a]),
                                      np.asarray(pinned[rid_b]))
    # the pinned run surfaced controller state; decisions were taken
    s = eng.stats.serving_summary()
    assert s["controller"]["k"] == 2 and s["controller"]["decisions"] > 0


class ScriptedController:
    """Duck-typed controller replaying a fixed (k, backend) script —
    the randomized-switching harness (arbitrary mid-serve decisions,
    none of them pricing-driven)."""

    def __init__(self, script, ks=(0, 2, 4), backends=None, interval=2):
        self.script = list(script)
        self.ks = tuple(ks)
        self.backends = backends
        self.decision_interval = int(interval)
        self.window = 8
        self.initial_k = 0
        self.decisions: list = []
        self.n_switches = 0
        self.dwell_remaining = 0
        self._i = 0

    def decide(self, summary, *, k_live, backend_live, step):
        k, backend = self.script[self._i % len(self.script)]
        self._i += 1
        d = Decision(step=step, k=k, backend=backend or backend_live,
                     switched=(k != k_live), reason="scripted")
        self.n_switches += int(d.switched)
        self.decisions.append(d)
        return d


@settings(max_examples=3, deadline=None)
@given(seed=st.integers(0, 10_000))
def test_random_switching_no_retrace_and_token_identity(seed):
    cfg, _, _, eng, _ = _model_state()
    rng = np.random.default_rng(seed)
    script = [
        (int(rng.choice([0, 2, 4])), str(rng.choice(["reference", "interpret"])))
        for _ in range(8)
    ]
    reqs = _workload(cfg.vocab_size, seed=seed)
    base = eng.serve(list(reqs), max_batch=2, seed=0,
                     spec=SpecConfig(k=4, drafter="ngram"))
    size0 = _jit_cache_size(eng)
    ctl = ScriptedController(script, backends=("reference", "interpret"))
    out = eng.serve(_workload(cfg.vocab_size, seed=seed), max_batch=2, seed=0,
                    spec=SpecConfig(k=4, drafter="ngram"), controller=ctl)
    # greedy streams are invariant under ANY live decision sequence
    for rid_a, rid_b in zip(sorted(base), sorted(out)):
        np.testing.assert_array_equal(np.asarray(base[rid_a]),
                                      np.asarray(out[rid_b]))
    # every switch was a cache hit in the primed K × backend grid
    assert _jit_cache_size(eng) == size0
    assert eng.stats.registry.counter("engine.retraces").value == 0.0
    assert len(ctl.decisions) > 0


def test_model_drafter_zero_to_k_catchup(served):
    """0→K transitions with a stateful drafter re-sync the draft cache
    from the committed history (rows that decoded plain while K was 0
    advanced the target cache only) — tokens stay bitwise identical."""
    cfg, m, params, _, _ = _model_state()
    eng = ServingEngine(m, params, max_seq=64, kv_layout="slot")
    spec = SpecConfig(k=2, drafter="model", draft_model=m, draft_params=params)
    base = eng.serve(_workload(cfg.vocab_size), max_batch=2, seed=0,
                     spec=SpecConfig(k=0))
    # flip 0 → 2 → 0 → 2 every other decision, mid-generation
    ctl = ScriptedController([(0, None), (2, None)] * 4, ks=(0, 2), interval=2)
    out = eng.serve(_workload(cfg.vocab_size), max_batch=2, seed=0,
                    spec=spec, controller=ctl)
    for rid_a, rid_b in zip(sorted(base), sorted(out)):
        np.testing.assert_array_equal(np.asarray(base[rid_a]),
                                      np.asarray(out[rid_b]))


def test_online_adviser_end_to_end_with_seeded_costs(served):
    cfg, _, _, eng, primed = _model_state()
    ctl = OnlineAdviser(ks=(0, 2, 4), decision_interval=2, window=6,
                        dwell=1, threshold=0.05, probe_every=2)
    ctl.seed_costs(primed)
    # long budgets on short prompts: self-repetitive → draftable
    reqs = _workload(cfg.vocab_size, specs=((6, 16), (8, 16), (6, 12)))
    base = eng.serve(_workload(cfg.vocab_size, specs=((6, 16), (8, 16), (6, 12))),
                     max_batch=2, seed=0, spec=SpecConfig(k=0))
    out = eng.serve(reqs, max_batch=2, seed=0,
                    spec=SpecConfig(k=4, drafter="ngram"), controller=ctl)
    for rid_a, rid_b in zip(sorted(base), sorted(out)):
        np.testing.assert_array_equal(np.asarray(base[rid_a]),
                                      np.asarray(out[rid_b]))
    assert len(ctl.decisions) > 0
    trail = ctl.audit_trail()
    assert all(d["inputs"]["window"] >= 0 for d in trail)


def test_admit_budget_applied(served):
    cfg, _, _, eng, _ = _model_state()

    class Throttler(PinnedController):
        def decide(self, summary, *, k_live, backend_live, step):
            d = super().decide(summary, k_live=k_live,
                               backend_live=backend_live, step=step)
            d.admit_budget = 1
            return d

    ctl = Throttler(0, decision_interval=1)
    base = eng.serve(_workload(cfg.vocab_size), max_batch=2, seed=0,
                     spec=SpecConfig(k=0))
    out = eng.serve(_workload(cfg.vocab_size), max_batch=2, seed=0,
                    spec=SpecConfig(k=0), controller=ctl)
    for rid_a, rid_b in zip(sorted(base), sorted(out)):
        np.testing.assert_array_equal(np.asarray(base[rid_a]),
                                      np.asarray(out[rid_b]))
    assert eng.stats.serving_summary()["controller"]["admit_budget"] == 1


# ---------------------------------------------------------------------------
# observability surfaces


def test_controller_metrics_in_prometheus_and_snapshot(served):
    cfg, _, _, eng, _ = _model_state()
    eng.serve(_workload(cfg.vocab_size), max_batch=2, seed=0,
              spec=SpecConfig(k=2, drafter="ngram"),
              controller=PinnedController(2, decision_interval=2))
    reg = eng.stats.registry
    assert reg.counter("controller.decisions").value > 0
    snap = reg.snapshot()
    assert "controller.k" in snap["gauges"]
    assert "controller.dwell_remaining" in snap["gauges"]
    assert "controller.backend_index" in snap["gauges"]
    text = reg.prometheus_text()
    assert "# TYPE controller_decisions counter" in text
    assert "controller_k" in text
    # a controller-less serve carries no controller key in the summary
    eng.serve(_workload(cfg.vocab_size), max_batch=2, seed=0,
              spec=SpecConfig(k=0))
    assert "controller" not in eng.stats.serving_summary()


# ---------------------------------------------------------------------------
# window_summary cold start: every sensor is finite from tick zero


def test_window_summary_cold_start_finite():
    import math

    reg = MetricsRegistry()
    for n in (1, 4, 64):
        s = reg.window_summary(n)
        for key, v in s.items():
            assert v is not None, key
            if isinstance(v, float):
                assert math.isfinite(v), (key, v)
        assert s["acceptance_rate"] == 0.0
        assert s["p50_draft_ms"] == 0.0 and s["p50_verify_ms"] == 0.0
        assert s["spec_steps"] == 0.0
        assert s["window"] == 0
    # one tick with zero denominators stays finite too
    reg.counter("serve.spec_proposed")
    reg.counter("serve.spec_accepted")
    reg.tick()
    s = reg.window_summary(4)
    assert s["window"] == 1 and s["acceptance_rate"] == 0.0
    # partial window: fewer ticks than n is well-defined
    reg.series("serve.step_ms").append(2.0)
    reg.tick()
    s = reg.window_summary(64)
    assert s["window"] == 2 and s["step_cost_ms"] == pytest.approx(2.0)
