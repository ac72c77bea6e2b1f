"""Serving flight recorder (serve/telemetry.py, DESIGN.md §8): the
linear-interpolation quantile against numpy.percentile, registry
windows/exposition, the tracer's ring bound and Chrome-trace schema,
the hard off-switch (telemetry on == off token streams, no events when
disabled), trace well-formedness over random open-loop traffic
(exactly one terminal event per admitted request, step/phase spans
nest), the ServeStats→registry refactor's golden ``serving_summary``
schema, XLA-annotation no-op smoke, and the adviser audit trail
(advisor decisions + ToolPipeline stage spans land in the trace with
their priced inputs)."""
import json
import os

import jax
import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.configs import get_config
from repro.models import Model
from repro.serve import Request, ServingEngine, SpecConfig
from repro.serve.telemetry import (
    TID_ADVISER,
    TID_STEP,
    MetricsRegistry,
    Telemetry,
    Tracer,
    quantile,
    validate_chrome_trace,
)

_STATE: dict = {}


def _model_state():
    """Lazy module singleton, shared by the module fixture and the
    ``@given`` property tests (built once, not once per example)."""
    if not _STATE:
        cfg = get_config("smollm-135m").reduced()
        m = Model(cfg)
        params, _ = m.init(jax.random.key(0))
        eng = ServingEngine(m, params, max_seq=64, kv_layout="paged", block_size=8)
        _STATE["v"] = (cfg, m, params, eng)
    return _STATE["v"]


@pytest.fixture(scope="module")
def served():
    return _model_state()


def _workload(vocab, specs=((8, 4), (12, 6), (8, 5), (16, 3)), arrival=0.0):
    rng = np.random.default_rng(7)
    return [
        Request(
            prompt=rng.integers(0, vocab, size=n).astype(np.int32),
            max_new_tokens=t, arrival_time=arrival * i,
        )
        for i, (n, t) in enumerate(specs)
    ]


# ---------------------------------------------------------------------------
# quantile: linear interpolation == numpy.percentile default


def test_quantile_matches_numpy_percentile():
    rng = np.random.default_rng(0)
    for n in (1, 2, 3, 7, 50, 101):
        vals = rng.normal(size=n).tolist()
        for p in (0.0, 1.0, 37.5, 50.0, 90.0, 99.0, 100.0):
            assert quantile(vals, p) == pytest.approx(
                float(np.percentile(vals, p)), abs=1e-12
            ), (n, p)


def test_quantile_interpolates_not_nearest_rank():
    # p99 over 10 samples must land BETWEEN the top two order
    # statistics, not collapse to the max
    vals = list(range(10))
    assert 8.0 < quantile(vals, 99.0) < 9.0
    assert quantile([], 50.0) == 0.0


def test_serve_stats_percentile_uses_quantile(served):
    from repro.serve import ServeStats

    stats = ServeStats()
    stats.step_ms.extend([1.0, 2.0, 3.0, 10.0])
    assert stats.percentile(50) == pytest.approx(float(np.percentile([1, 2, 3, 10], 50)))
    assert stats.percentile(99) == pytest.approx(float(np.percentile([1, 2, 3, 10], 99)))
    assert stats.percentile(50, "ttft_ms") == 0.0  # empty series


# ---------------------------------------------------------------------------
# metrics registry: counters/gauges/series, windows, exposition, reset


def test_registry_windows_and_reset_in_place():
    reg = MetricsRegistry(window=8)
    c = reg.counter("x.count")
    g = reg.gauge("x.gauge")
    s = reg.series("x.series")
    for i in range(12):
        c.inc(2.0)
        g.set(float(i))
        s.append(float(i))
        reg.tick()
    assert reg.ticks == 12
    assert reg.window_delta("x.count", 4) == 8.0
    assert reg.window_delta("x.count", 100) == pytest.approx(c.value)  # ring-capped
    assert reg.window_mean("x.gauge", 4) == pytest.approx((8 + 9 + 10 + 11) / 4)
    assert reg.series_quantile("x.series", 50.0, 4) == pytest.approx(9.5)
    assert reg.window_delta("missing", 4) == 0.0
    # reset is in place: cached handles survive
    reg.reset()
    assert reg.ticks == 0 and c.value == 0.0 and g.value is None and not s
    c.inc()
    assert reg.counter("x.count").value == 1.0
    assert reg.counter("x.count") is c


def test_window_summary_schema():
    reg = MetricsRegistry()
    summary = reg.window_summary(8)
    for key in (
        "window", "ticks", "acceptance_rate", "proposed", "accepted",
        "spec_steps", "p50_draft_ms", "p50_verify_ms",
        "queue_depth", "active", "pool_occupancy", "pool_free_blocks",
        "step_cost_ms", "p99_step_ms", "admitted", "preemptions",
        "rejected", "prefix_hit_rate", "chunk_utilization",
        "alloc_rate", "evict_rate", "park_rate", "retraces",
    ):
        assert key in summary, key
    assert summary["window"] == 0  # no ticks yet


def test_prometheus_and_snapshot_smoke():
    reg = MetricsRegistry()
    reg.counter("pool.alloc").inc(3)
    reg.gauge("sched.queue_depth").set(2.0)
    reg.series("serve.step_ms").extend([1.0, 2.0])
    snap = reg.snapshot()
    assert snap["counters"]["pool.alloc"] == 3.0
    assert snap["gauges"]["sched.queue_depth"] == 2.0
    assert snap["series"]["serve.step_ms"]["count"] == 2
    text = reg.prometheus_text()
    assert "# TYPE pool_alloc counter" in text
    assert "pool_alloc 3" in text
    assert 'serve_step_ms{quantile="0.5"}' in text
    assert "serve_step_ms_count 2" in text
    json.dumps(snap)  # JSON-ready


def test_serve_stats_counters_are_registry_backed():
    from repro.serve import ServeStats

    stats = ServeStats()
    stats.prompt_tokens += 5
    stats.n_preemptions += 1
    assert stats.registry.counter("serve.prompt_tokens").value == 5.0
    assert stats.registry.counter("serve.preemptions").value == 1.0
    assert isinstance(stats.prompt_tokens, int)
    stats.reset()
    assert stats.prompt_tokens == 0 and stats.n_preemptions == 0
    assert stats.step_ms is stats.registry.series("serve.step_ms")


# ---------------------------------------------------------------------------
# tracer: ring bound, schema, validator


def test_tracer_ring_bound_never_exceeded():
    tr = Tracer(capacity=16)
    for i in range(200):
        tr.complete(f"e{i}", "t", float(i), 1.0)
    assert len(tr) == 16
    # oldest dropped first: the survivors are the newest 16
    assert tr.events[0][1] == "e184" and tr.events[-1][1] == "e199"
    counts = validate_chrome_trace(tr.to_chrome_trace())
    assert counts["spans"] == 16


def test_validator_rejects_malformed():
    with pytest.raises(ValueError, match="traceEvents"):
        validate_chrome_trace({"nope": 1})
    bad_ph = [{"name": "x", "ph": "Z", "ts": 0, "pid": 1, "tid": 0}]
    with pytest.raises(ValueError, match="bad ph"):
        validate_chrome_trace(bad_ph)
    no_dur = [{"name": "x", "ph": "X", "ts": 0, "pid": 1, "tid": 0}]
    with pytest.raises(ValueError, match="dur"):
        validate_chrome_trace(no_dur)
    orphan_end = [
        {"name": "x", "ph": "e", "ts": 0, "pid": 1, "tid": 0, "id": 3, "cat": "r"}
    ]
    with pytest.raises(ValueError, match="async end"):
        validate_chrome_trace(orphan_end)


def test_export_round_trips_through_json(tmp_path):
    tr = Tracer()
    tr.async_begin("request", 1, "request")
    tr.instant("mark", "sched")
    tr.async_end("request", 1, "request")
    path = tmp_path / "trace.json"
    tr.export(str(path))
    loaded = json.loads(path.read_text())
    counts = validate_chrome_trace(loaded)
    assert counts["async_spans"] == 1 and counts["instants"] == 1
    assert loaded["displayTimeUnit"] == "ms"


# ---------------------------------------------------------------------------
# the hard off-switch: telemetry on == off, observation only


def test_off_switch_token_identity_and_no_events(served):
    cfg, _, _, eng = _model_state()
    spec = SpecConfig(k=2, drafter="ngram")
    off = Telemetry(enabled=False)
    on = Telemetry(enabled=True)

    out_off = eng.serve(_workload(cfg.vocab_size), max_batch=2, seed=0,
                        spec=spec, telemetry=off)
    assert len(off.tracer) == 0
    assert eng.stats.registry.ticks == 0  # disabled: no tick per step

    out_on = eng.serve(_workload(cfg.vocab_size), max_batch=2, seed=0,
                       spec=spec, telemetry=on)
    assert len(on.tracer) > 0
    assert eng.stats.registry.ticks > 0

    for a, b in zip(out_off.values(), out_on.values()):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    window = eng.stats.registry.window_summary(8)
    assert window["admitted"] > 0
    assert window["step_cost_ms"] > 0
    assert 0.0 <= window["acceptance_rate"] <= 1.0
    assert window["pool_occupancy"] >= 0.0


def test_xla_annotations_noop_smoke(served):
    cfg, _, _, eng = _model_state()
    base = eng.serve(_workload(cfg.vocab_size), max_batch=2, seed=0)
    annotated = Telemetry(enabled=True, xla_annotations=True)
    out = eng.serve(_workload(cfg.vocab_size), max_batch=2, seed=0,
                    telemetry=annotated)
    for a, b in zip(base.values(), out.values()):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # disabled or un-annotated telemetry shares one no-op context
    off = Telemetry(enabled=False)
    assert off.annotate("x") is off.annotate("y")
    with Telemetry(enabled=True, xla_annotations=True).annotate("phase"):
        pass  # TraceAnnotation enters/exits cleanly outside any profile


# ---------------------------------------------------------------------------
# golden serving_summary: the registry refactor changed no schema


def test_golden_serving_summary_schema(served):
    cfg, _, _, eng = _model_state()
    golden = json.load(
        open(os.path.join(os.path.dirname(__file__), "golden_serving_summary.json"))
    )
    eng.serve(_workload(cfg.vocab_size), max_batch=2, seed=0,
              spec=SpecConfig(k=2, drafter="ngram"))
    s = eng.stats.serving_summary()
    assert sorted(s.keys()) == golden["keys"]
    assert sorted(s["speculative"].keys()) == golden["speculative_keys"]
    for key, want in golden["deterministic"].items():
        assert s[key] == want, key
    for key, want in golden["speculative_deterministic"].items():
        assert s["speculative"][key] == want, key
    # which drafts a random model accepts is a rounding accident of the
    # XLA version; the acceptance contract is its definition and range
    sp = s["speculative"]
    assert isinstance(sp["accepted"], int) and 0 <= sp["accepted"] <= sp["proposed"]
    assert sp["acceptance_rate"] == sp["accepted"] / sp["proposed"]
    # latency fields are machine-dependent: type-checked only
    for key in golden["keys"]:
        if key.startswith(("p50_", "p99_")):
            assert s[key] is None or isinstance(s[key], float), key


# ---------------------------------------------------------------------------
# trace well-formedness over random open-loop traffic


def _span_nesting_ok(spans):
    """X-events on one lane either nest or are disjoint: sweeping by
    (ts, -dur), every span starts at-or-after its enclosing span's
    start and must end by the enclosing end."""
    stack = []
    for ts, dur in sorted(spans, key=lambda s: (s[0], -s[1])):
        end = ts + dur
        while stack and ts >= stack[-1] - 1e-6:
            stack.pop()
        if stack and end > stack[-1] + 1e-6:
            return False
        stack.append(end)
    return True


@settings(max_examples=4, deadline=None)
@given(
    seed=st.integers(0, 10_000),
    n_requests=st.integers(3, 6),
    gap_ms=st.sampled_from([0.0, 5.0]),
)
def test_trace_wellformed_random_traffic(seed, n_requests, gap_ms):
    cfg, _, _, eng = _model_state()
    rng = np.random.default_rng(seed)
    reqs = [
        Request(
            prompt=rng.integers(0, cfg.vocab_size, size=int(rng.choice([8, 12]))).astype(np.int32),
            max_new_tokens=int(rng.integers(2, 6)),
            arrival_time=gap_ms * 1e-3 * i,
            priority=int(rng.integers(0, 2)),
        )
        for i in range(n_requests)
    ]
    tel = Telemetry(enabled=True)
    eng.serve(list(reqs), max_batch=2, seed=seed,
              spec=SpecConfig(k=2, drafter="ngram"), telemetry=tel)

    events = tel.tracer.events
    rids = {r.rid for r in reqs}
    begins = [e for e in events if e[0] == "b" and e[1] == "request"]
    ends = [e for e in events if e[0] == "e" and e[1] == "request"]
    assert {e[6] for e in begins} == rids  # every submission opened a span
    # exactly one terminal event per admitted request
    assert sorted(e[6] for e in ends) == sorted(rids)

    # step/phase spans nest on the scheduler lane
    spans = [(e[3], e[4]) for e in events if e[0] == "X" and e[5] == TID_STEP]
    assert spans, "no step spans recorded"
    assert _span_nesting_ok(spans)

    counts = validate_chrome_trace(tel.tracer.to_chrome_trace())
    assert counts["async_spans"] == len(rids)

    # tiny-capacity rerun: the ring bound holds under the same load
    # (async validation is skipped — eviction may drop a span's begin)
    tiny = Telemetry(enabled=True, capacity=24)
    eng.serve(
        [Request(prompt=np.asarray(r.prompt), max_new_tokens=r.max_new_tokens,
                 arrival_time=r.arrival_time, priority=r.priority) for r in reqs],
        max_batch=2, seed=seed, spec=SpecConfig(k=2, drafter="ngram"),
        telemetry=tiny,
    )
    assert len(tiny.tracer) <= 24


def test_preemption_events_in_trace(served):
    """Block pressure → preempt + resume instants and a terminal event
    for every request, preempted ones included."""
    _, m, params, _ = _model_state()
    eng = ServingEngine(m, params, max_seq=128, kv_layout="paged",
                        max_batch=2, block_size=8, num_blocks=10)
    low = [
        Request(prompt=np.arange(20, dtype=np.int32) + i, max_new_tokens=10,
                arrival_time=0.0, priority=0)
        for i in range(2)
    ]
    high = [Request(prompt=np.arange(9, dtype=np.int32), max_new_tokens=4,
                    arrival_time=0.02, priority=5)]
    tel = Telemetry(enabled=True)
    eng.serve(low + high, telemetry=tel)
    assert eng.stats.n_preemptions > 0, "pressure scenario did not evict"
    names = [e[1] for e in tel.tracer.events]
    assert "preempt" in names and "resume" in names
    ends = [e for e in tel.tracer.events if e[0] == "e"]
    assert sorted(e[6] for e in ends) == sorted(r.rid for r in low + high)
    validate_chrome_trace(tel.tracer.to_chrome_trace())


# ---------------------------------------------------------------------------
# adviser audit trail


def test_advisor_decisions_annotated(monkeypatch):
    import repro.serve.telemetry as telemetry_mod
    from repro.core.tools import (
        KernelAdvisorTool,
        KernelMeasurement,
        SpecMeasurement,
        SpeculationAdvisorTool,
    )

    tel = Telemetry(enabled=True)
    monkeypatch.setattr(telemetry_mod, "GLOBAL", tel)

    k, gain, _ = SpeculationAdvisorTool().choose(
        SpecMeasurement(0.05, {0: 2.0, 8: 3.0}, 0.7)
    )
    backend, _, _ = KernelAdvisorTool().choose(
        KernelMeasurement.make("llama", "paged", 2, {"reference": 2.0, "kernel": 1.0})
    )
    by_name = {e[1]: e for e in tel.tracer.events}
    spec_ev = by_name["speculation-decision"]
    assert spec_ev[5] == TID_ADVISER
    assert spec_ev[7]["k"] == k
    # priced inputs ride along with the decision
    assert spec_ev[7]["acceptance_rate"] == pytest.approx(0.7)
    assert spec_ev[7]["draft_ms_per_token"] == pytest.approx(0.05)
    kern_ev = by_name["kernel-backend-decision"]
    assert kern_ev[7]["backend"] == backend == "kernel"
    assert kern_ev[7]["step_ms"]["reference"] == pytest.approx(2.0)
    assert telemetry_mod.global_registry().counter("adviser.decisions").value >= 2


def test_pipeline_stage_spans(monkeypatch):
    import jax.numpy as jnp

    import repro.serve.telemetry as telemetry_mod
    from repro.core import Aira, Region, Workload
    from repro.core.overlap_model import CPU_HW

    tel = Telemetry(enabled=True)
    monkeypatch.setattr(telemetry_mod, "GLOBAL", tel)

    region = Region(
        "audit", lambda x: 2.0 * x + 1.0, jnp.arange(4096, dtype=jnp.float32),
        task_flops=100.0, task_bytes=512.0, task_chain=16,
    )
    Aira(hw=CPU_HW).advise(Workload("w", lambda: None, [region]))
    stage_events = [
        e for e in tel.tracer.events
        if e[0] == "X" and e[5] == TID_ADVISER and e[1].startswith("tool:")
    ]
    stages = [e[1] for e in stage_events]
    assert "tool:profile" in stages and "tool:simulate" in stages
    for e in stage_events:
        assert e[7]["region"] == "audit"
        assert e[7]["verdict"] in ("pass", "reject")
    # disabled recorder: the same pipeline leaves no events
    silent = Telemetry(enabled=False)
    monkeypatch.setattr(telemetry_mod, "GLOBAL", silent)
    region2 = Region(
        "silent", lambda x: 2.0 * x + 1.0, jnp.arange(4096, dtype=jnp.float32),
        task_flops=100.0, task_bytes=512.0, task_chain=16,
    )
    Aira(hw=CPU_HW).advise(Workload("w2", lambda: None, [region2]))
    assert len(silent.tracer) == 0


def test_backend_resolution_annotated(monkeypatch):
    import repro.kernels.ops as ops
    import repro.serve.telemetry as telemetry_mod

    tel = Telemetry(enabled=True)
    monkeypatch.setattr(telemetry_mod, "GLOBAL", tel)
    monkeypatch.setattr(ops, "_DEFAULT_MODE", None)  # force a fresh resolution
    ops.default_kernel_mode()
    names = [e[1] for e in tel.tracer.events]
    assert "kernel-mode-resolved" in names
    assert telemetry_mod.global_registry().counter("backend.resolutions").value >= 1
