"""The program's own spans in a trace: the readers of ``admit_ms``,
``step_host_ms``, ``sample_ms`` and ``decode_rows_mean`` on a hand-made
trace, and the spans a tiny scheduler leaves in one recorded here."""
import jax
import numpy as np
import pytest

from bench import spans, trace
from bench.metrics import admit_ms, decode_rows_mean, sample_ms, step_host_ms
from bench.tests.test_trace import MS, hand_trace
from bench.trace import Ev


def program_trace():
    """The hand trace with the program's spans in it, as a scheduler
    with telemetry on names them: an admission-only step 0.1-0.7 ms;
    step 1 (decode 0.9-3.4 over 3 rows, sample 3.4-3.5); step 2 admits
    one request (prefill, then its install) and decodes 4 rows; an
    admission after the window is not read."""
    tr = hand_trace()
    tr.host = [e for e in tr.host if not e.name.startswith("serve.")] + [
        Ev("serve.step", 0.1 * MS, 0.7 * MS, {"seq": 1}),
        Ev("serve.admit", 0.15 * MS, 0.6 * MS),
        Ev("serve.admit_request", 0.2 * MS, 0.5 * MS, {"rid": 5, "prompt_tokens": 12}),
        Ev("serve.step", 0.85 * MS, 3.55 * MS, {"seq": 2}),
        Ev("serve.admit", 0.86 * MS, 0.88 * MS),
        Ev("serve.decode_inputs", 0.88 * MS, 0.9 * MS),
        Ev("serve.decode", 0.9 * MS, 3.4 * MS, {"live_rows": 3, "context_tokens": 90}),
        Ev("serve.sample", 3.4 * MS, 3.5 * MS),
        Ev("serve.step", 5.52 * MS, 9.4 * MS, {"seq": 3}),
        Ev("serve.admit", 5.53 * MS, 8.2 * MS),
        Ev("serve.admit_request", 5.55 * MS, 8.1 * MS, {"rid": 7, "hit_tokens": 16}),
        Ev("serve.prefill", 5.6 * MS, 5.9 * MS),
        Ev("serve.write_prefill", 5.9 * MS, 8.05 * MS),
        Ev("serve.decode_inputs", 8.2 * MS, 8.3 * MS),
        Ev("serve.decode", 8.3 * MS, 9.0 * MS, {"live_rows": 4, "context_tokens": 130}),
        Ev("serve.sample", 9.0 * MS, 9.3 * MS),
        Ev("serve.step", 10.2 * MS, 11.0 * MS, {"seq": 4}),
        Ev("serve.admit_request", 10.3 * MS, 10.9 * MS, {"rid": 9}),
    ]
    return tr


class _Ctx:
    def __init__(self, tr):
        self.trace = tr


READERS = {
    # mean of 0.3 and 2.55 ms; the admission after the window is left out
    admit_ms: 1.425,
    # steps with a decode: 2.7 - 2.5 and 3.88 - 0.7 ms
    step_host_ms: 1.69,
    sample_ms: 0.2,
    decode_rows_mean: 3.5,
}


@pytest.mark.parametrize("reader", list(READERS), ids=lambda m: m.__name__.split(".")[-1])
def test_program_span_readers(reader):
    assert reader.read(_Ctx(program_trace())) == pytest.approx(READERS[reader])
    # a program that names no step (serve.decode and serve.prefill only)
    assert reader.read(_Ctx(hand_trace())) is None
    assert reader.read(_Ctx(None)) is None


def test_program_spans_by_name():
    by_name = spans.program_spans(program_trace())
    assert [e.stats["seq"] for e in by_name["serve.step"]] == [1, 2, 3]
    assert [e.stats["rid"] for e in by_name["serve.admit_request"]] == [5, 7]
    step = by_name["serve.step"][2]
    assert [e.name for e in spans.inside(step, by_name["serve.write_prefill"])] == [
        "serve.write_prefill"
    ]
    assert spans.program_spans(hand_trace()) == {}


def test_program_spans_in_a_recorded_trace(tmp_path):
    """A tiny scheduler with telemetry on, traced on the CPU: its spans
    land on the window's thread nested as the scheduler runs them, with
    their args as stats, and the ring holds the same names."""
    from bench import run
    from bench.tests import tiny
    from repro.serve.request import Request

    cell = tiny.cell()
    engine, *_ = run.build(cell, tiny.conf(), 5, trace=True, backend="interpret")
    rng = np.random.default_rng(5)

    def reqs():
        return [
            Request(prompt=rng.integers(0, 256, size=n).astype(np.int32), max_new_tokens=4)
            for n in (9, 14, 20)
        ]

    sched = engine.scheduler(int(cell["max_batch"]), seed=5)
    sched.run(reqs())  # compile outside the trace
    engine.telemetry.tracer.clear()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation(trace.WINDOW_SPAN):
        sched.run(reqs())
    jax.profiler.stop_trace()
    by_name = spans.program_spans(trace.load(trace.find_xplane(str(tmp_path))))

    steps = by_name["serve.step"]
    assert [e.stats["seq"] for e in steps] == sorted(e.stats["seq"] for e in steps)

    def parent(e, name):
        held = [p for p in by_name[name] if p.start <= e.start and e.end <= p.end]
        assert len(held) == 1, (e.name, name)
        return held[0]

    admits = by_name["serve.admit_request"]
    assert sorted(e.stats["rid"] for e in admits) == sorted({e.stats["rid"] for e in admits})
    assert len(admits) == 3
    for e in admits:
        assert e.stats["prompt_tokens"] in (9, 14, 20) and e.stats["hit_tokens"] == 0
        parent(parent(e, "serve.admit"), "serve.step")
    for name in ("serve.prefill", "serve.write_prefill"):
        assert len(by_name[name]) == 3
        for e in by_name[name]:
            parent(e, "serve.admit_request")
    decodes = by_name["serve.decode"]
    for e in decodes:
        step = parent(e, "serve.step")
        assert 1 <= e.stats["live_rows"] <= 3
        assert e.stats["context_tokens"] >= 10 * e.stats["live_rows"]
        for name in ("serve.decode_inputs", "serve.sample"):
            assert len(spans.inside(step, by_name[name])) == 1
    assert max(e.stats["live_rows"] for e in decodes) == 3
    ring = {e[1] for e in engine.telemetry.tracer.events if e[0] == "X"}
    assert set(by_name) <= ring
