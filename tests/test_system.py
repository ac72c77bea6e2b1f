"""End-to-end behaviour tests for the paper's system: the full Aira flow
(profile → annotate → deps → simulate → restructure) on a real workload,
plus end-to-end train + serve round trips."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.bench_suite import BENCHMARKS
from repro.core import Aira, profile_step
from repro.core.overlap_model import CPU_HW


def test_aira_end_to_end_geospatial():
    """Full pipeline on GeoSpatial: accepted, restructured, semantics
    preserved, report readable."""
    from benchmarks.fig34_aira import make_workload

    b = BENCHMARKS["GeoSpatial"]
    data = b.build()
    wl = make_workload(b, data)
    report = Aira(hw=CPU_HW).advise(wl)
    d = report.decisions[0]
    assert d.accepted
    assert d.schedule.strategy == "smt2"
    # the restructured callable computes the same result (the benchmark
    # declares combine="sum", honored by the plan layer)
    got = np.asarray(d.parallel_fn(), np.float32)
    want = np.asarray(jax.vmap(b.item_fn(data))(b.items(data)).sum(0), np.float32)
    np.testing.assert_allclose(got, want, atol=1e-3, rtol=1e-5)
    # per-item (stack) semantics remain available through the same layer
    stacked = np.asarray(b.parallel_value(data, granularity=d.schedule.granularity))
    np.testing.assert_allclose(
        stacked,
        np.asarray(jax.vmap(b.item_fn(data))(b.items(data))),
        atol=1e-4,
    )
    text = report.render()
    assert "Parallelize this program with Aira" in text
    assert "static:" in d.summary() and "simulate:" in d.summary()


def test_profile_step_roofline_terms():
    ps = profile_step(
        lambda x, w: jnp.tanh(x @ w),
        jax.ShapeDtypeStruct((512, 512), jnp.float32),
        jax.ShapeDtypeStruct((512, 512), jnp.float32),
        name="mm",
    )
    assert ps.flops > 2 * 512**3 * 0.99
    assert ps.terms.dominant in ("compute", "memory")
    rep = ps.report()
    assert "roofline" in rep and "hotspots" in rep


def test_train_then_serve_roundtrip():
    """Train a reduced model a few steps, then serve greedily — the whole
    example-application path in miniature."""
    from repro.configs import get_config
    from repro.data import SyntheticLMData
    from repro.models import Model
    from repro.serve import ServingEngine
    from repro.train import AdamW, make_train_step

    cfg = get_config("smollm-135m").reduced()
    m = Model(cfg)
    params, _ = m.init(jax.random.key(0))
    opt = AdamW(lr=1e-3, warmup_steps=2, total_steps=20)
    state = opt.init(params)
    data = SyntheticLMData(cfg, batch=4, seq=32)
    step = jax.jit(make_train_step(m, opt))
    losses = []
    for i in range(8):
        params, state, metrics = step(params, state, data.batch_at(i))
        losses.append(float(metrics["loss"]))
    assert losses[-1] < losses[0]  # learning happens

    eng = ServingEngine(m, params, max_seq=64)
    out = eng.generate(jnp.ones((2, 8), jnp.int32), n_steps=4)
    assert out.shape == (2, 4)
    assert eng.stats.percentile(50) > 0


def test_sharded_sweep_never_reexecs_on_an_accelerator(monkeypatch):
    """On an accelerator host with too few devices the sharded sweep
    refuses, naming the count it needs: the CPU re-exec would start a
    child that needs the chip the parent already holds."""
    import pytest

    from benchmarks import serving_load

    def no_child(*a, **k):
        raise AssertionError("re-exec attempted on an accelerator host")

    monkeypatch.setattr(serving_load.jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(serving_load, "_run_sharded_subprocess", no_child)
    need = serving_load.sharded_devices_needed()
    with pytest.raises(RuntimeError, match=f"needs {need} devices"):
        serving_load.run_sharded()


def test_compile_cache_dir_env_wins_else_fixed_checkout_path(monkeypatch):
    from pathlib import Path

    from repro import compile_cache

    was = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv(compile_cache.ENV, "/elsewhere/cache")
        jax.config.update("jax_compilation_cache_dir", None)
        assert compile_cache.enable() == "/elsewhere/cache"
        assert jax.config.jax_compilation_cache_dir is None  # left to JAX
        monkeypatch.delenv(compile_cache.ENV)
        path = compile_cache.enable()
        assert path == jax.config.jax_compilation_cache_dir
        assert path == str(compile_cache.DEFAULT_DIR)
        assert compile_cache.DEFAULT_DIR.parent == Path(__file__).resolve().parents[1]
        assert compile_cache.enable() == path  # fixed: no pid, time or temp
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
