"""Hypothesis property tests on system invariants."""
import jax
import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings, strategies as st

from repro.configs.base import pad_to_multiple
from repro.core.overlap_model import CPU_HW, Microtask, OverlapModel
from repro.core.relic import relic_pfor

MODEL = OverlapModel(CPU_HW)


@settings(max_examples=30, deadline=None)
@given(
    n=st.integers(2, 500),
    g=st.integers(1, 64),
    width=st.integers(1, 16),
)
def test_relic_pfor_equals_vmap(n, g, width):
    fn = lambda x: jnp.tanh(x).sum() * 2.0
    xs = jnp.arange(n * width, dtype=jnp.float32).reshape(n, width) / 97.0
    got = relic_pfor(fn, xs, granularity=g)
    want = jax.vmap(fn)(xs)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


@settings(max_examples=50, deadline=None)
@given(
    flops=st.floats(1.0, 1e6),
    nbytes=st.floats(1.0, 1e6),
    chain=st.integers(0, 512),
    n=st.integers(1, 10_000),
)
def test_overlap_model_bounds(flops, nbytes, chain, n):
    t = Microtask(flops=flops, bytes=nbytes, chain=chain, vector=True)
    p = MODEL.predict(t, n)
    c, c_s, m_lat, m_bw = MODEL._components(t)
    # serial is exactly n per-task times
    assert p.serial == (c_s + m_lat + m_bw) * n
    # no schedule beats its shared-resource floors
    assert p.smt2 >= n * m_bw * (1 + CPU_HW.bw_contention) - 1e-12
    assert p.smt2 >= n * c * (1 + CPU_HW.contention) - 1e-12
    # smt2 speedup is bounded by 2× (two streams)
    assert p.serial / p.smt2 <= 2.0 + 1e-9


@settings(max_examples=50, deadline=None)
@given(x=st.integers(1, 10**7), m=st.sampled_from([8, 64, 128, 256, 2048]))
def test_pad_to_multiple(x, m):
    p = pad_to_multiple(x, m)
    assert p >= x and p % m == 0 and p - x < m


@settings(max_examples=20, deadline=None)
@given(
    dim=st.sampled_from([48, 64, 96, 100, 128, 576, 1024]),
    axes=st.sampled_from([("batch",), ("mlp",), ("heads",), ("vocab",)]),
)
def test_sharding_spec_divisibility(dim, axes):
    """spec() never assigns a mesh axis that does not divide the dim."""
    import subprocess, sys, os

    # cheap structural check without a big mesh: rules built on a fake
    # mesh via dataclass stub
    from repro.parallel.sharding import ShardingRules
    from repro.configs import get_config

    class FakeMesh:
        shape = {"data": 16, "model": 16}

    rules = ShardingRules.__new__(ShardingRules)
    rules.mesh = FakeMesh()
    rules.cfg = get_config("smollm-135m")
    rules.fallbacks = []
    rules.table = {
        "batch": ("data",), "mlp": "model", "heads": "model", "vocab": "model",
    }
    spec = rules.spec(axes, (dim,))
    assigned = spec[0]
    if assigned is not None:
        names = (assigned,) if isinstance(assigned, str) else assigned
        size = 1
        for nm in names:
            size *= FakeMesh.shape[nm]
        assert dim % size == 0


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 2**31 - 1))
def test_adamw_descends_quadratic(seed):
    from repro.train.optimizer import AdamW

    key = jax.random.key(seed)
    target = jax.random.normal(key, (8,))
    params = {"w": jnp.zeros((8,))}
    opt = AdamW(lr=0.05, warmup_steps=1, total_steps=100, weight_decay=0.0)
    state = opt.init(params)
    loss = lambda p: jnp.sum((p["w"] - target) ** 2)
    l0 = float(loss(params))
    for _ in range(60):
        g = jax.grad(loss)(params)
        params, state, _ = opt.update(g, state, params)
    assert float(loss(params)) < 0.2 * l0


@settings(max_examples=20, deadline=None)
@given(step=st.integers(0, 10_000))
def test_data_pipeline_deterministic(step):
    from repro.configs import get_config
    from repro.data import SyntheticLMData

    cfg = get_config("smollm-135m").reduced()
    d = SyntheticLMData(cfg, batch=2, seq=16, seed=3)
    a = d.batch_at(step)
    b = d.batch_at(step)
    np.testing.assert_array_equal(np.asarray(a["tokens"]), np.asarray(b["tokens"]))
    assert int(a["tokens"].max()) < cfg.vocab_size
