"""The reduction from a trace to busy time, idle gaps and their labels,
on a hand-made trace and on one recorded here."""
import jax
import jax.numpy as jnp
import pytest

from bench import trace
from bench.trace import Ev, Trace

MS = 1e6  # ns


KERNEL = '%_paged_attention_impl.3 = bf16[8,1,64] custom-call(%p.1), custom_call_target="tpu_custom_call"'


def hand_trace():
    """A 10 ms window, named as a TPU trace names things: a decode program
    1-4 ms whose layer loop (a ``while``) holds a kernel 2-3 ms and a
    fusion 2.5-3.5 ms, a prefill program 6-8 ms; host spans around them."""
    ops = [
        Ev("%fusion.1 = f32[8] fusion(%a.1), kind=kLoop", 1 * MS, 2 * MS),
        Ev("%while.5 = (s32[]) while(%t.2), body=%region_1", 2 * MS, 3.5 * MS),
        Ev(KERNEL, 2 * MS, 3 * MS),
        Ev("%fusion.2 = f32[8] fusion(%b.1), kind=kLoop", 3 * MS, 3.5 * MS),
        Ev("%fusion.9 = bf16[1,64] fusion(%c.1), kind=kOutput", 6 * MS, 8 * MS),
        Ev("%fusion.1 = f32[8] fusion(%a.1), kind=kLoop", 11 * MS, 12 * MS),
    ]
    modules = [
        Ev("jit__unknown(7)", 1 * MS, 4 * MS),
        Ev("jit__lambda(3)", 6 * MS, 8 * MS),
        Ev("jit__unknown(7)", 11 * MS, 12 * MS),
    ]
    host = [
        Ev("bench.window", 0, 10 * MS),
        Ev("bench.step", 0.8 * MS, 3.6 * MS),
        Ev("serve.decode", 0.9 * MS, 1.2 * MS),
        Ev("bench.wait", 4.5 * MS, 5.5 * MS),
        Ev("bench.step", 5.5 * MS, 9.5 * MS),
        Ev("serve.prefill", 5.6 * MS, 5.9 * MS),
    ]
    return Trace((0.0, 10 * MS), {0: ops}, {0: modules}, host, {})


def test_busy_and_window():
    tr = hand_trace()
    # ops cover 1-3.5 and 6-8 inside the window; 11-12 lies outside
    assert trace.device_busy_s(tr) == pytest.approx(4.5e-3)
    assert trace.window_s(tr) == pytest.approx(10e-3)


def test_union_merges_overlaps():
    assert trace.union([(5, 6), (1, 3), (2, 4)]) == [[1, 4], [5, 6]]


def test_breakdown_and_labels():
    tr = hand_trace()
    bd = trace.breakdown(tr)
    ops = dict(bd["device_ops"])
    # the while holds the kernel and a fusion: only the leaves count
    assert ops == pytest.approx({
        "jit__lambda/fusion": 2e-3,
        "jit__unknown/fusion": 1.5e-3,
        "jit__unknown/_paged_attention_impl": 1e-3,
    })
    gaps = bd["idle_gaps"]
    # idle: 3.5-6 (middle in bench.wait), 8-10 (in the second step),
    # 0-1 (before any host span)
    assert [round(g, 6) for _, g in gaps] == [0.0025, 0.002, 0.001]
    assert [lab for lab, _ in gaps] == ["bench.wait", "bench.step", "host:none"]
    assert sum(trace.idle_by_label(tr).values()) == pytest.approx(5.5e-3)


def test_programs_and_kernel_match():
    tr = hand_trace()
    assert [m.name for m in trace.kernel_modules(tr)] == ["jit__unknown(7)"]
    assert len(trace.modules_matching(tr, "lambda")) == 1
    kern = trace.ops_matching(tr, trace.is_kernel)
    assert [trace.op_name(e) for e in kern] == ["_paged_attention_impl"]
    assert trace.inventory(tr)["kernels"] == ["_paged_attention_impl"]


def test_host_labels_pick_the_innermost_span():
    tr = hand_trace()
    assert trace.host_labels(tr, [1.0 * MS, 4.9 * MS, 5.7 * MS, 2.0 * MS, 9.9 * MS]) == [
        "serve.decode", "bench.wait", "serve.prefill", "bench.step", "host:none",
    ]


def test_load_a_recorded_trace(tmp_path):
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation(trace.WINDOW_SPAN):
        with jax.profiler.TraceAnnotation("serve.decode"):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    tr = trace.load(trace.find_xplane(str(tmp_path)))
    assert tr.window[1] > tr.window[0]
    assert any(e.name == "serve.decode" for e in tr.host)
    assert "/host:CPU" in tr.layout
    # a CPU holds no device plane: nothing ran on a device
    assert trace.device_busy_s(tr) == 0.0


def test_install_device_ms():
    from bench.metrics import install_device_ms

    class Ctx:
        trace = hand_trace()

    # no install program in the window: nothing to read
    assert install_device_ms.read(Ctx) is None
    Ctx.trace.modules[0] += [
        Ev("jit_install_prefill_blocks(4)", 8.2 * MS, 8.6 * MS),
        Ev("jit_install_prefill_blocks(4)", 9.0 * MS, 9.2 * MS),
        Ev("jit_install_prefill_blocks(4)", 10.5 * MS, 11.5 * MS),  # after the window
    ]
    assert install_device_ms.read(Ctx) == pytest.approx(0.3)
    Ctx.trace = None
    assert install_device_ms.read(Ctx) is None
