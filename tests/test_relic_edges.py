"""relic_pfor edge cases: granularity > n_items, padding paths, the
round-robin deal/undeal order property, and combine semantics."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.relic import relic_pfor

from hypothesis import given, settings, strategies as st


def test_granularity_larger_than_n_items():
    """g > n clamps to one chunk of all items (plus stream padding)."""
    fn = lambda x: x * 2.0 + 1.0
    xs = jnp.arange(5, dtype=jnp.float32)
    got = relic_pfor(fn, xs, granularity=16)
    np.testing.assert_allclose(np.asarray(got), np.asarray(jax.vmap(fn)(xs)))


def test_granularity_zero_clamps_to_one():
    fn = lambda x: x - 3.0
    xs = jnp.arange(7, dtype=jnp.float32)
    got = relic_pfor(fn, xs, granularity=0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(jax.vmap(fn)(xs)))


@pytest.mark.parametrize("n,g,streams", [
    (10, 3, 2),   # n % g != 0
    (12, 3, 4),   # n_chunks % n_streams == 0, exact
    (13, 3, 4),   # both padding conditions
    (8, 3, 3),    # odd stream count
    (2, 1, 4),    # fewer items than streams
    (1, 1, 2),    # single item
])
def test_padding_path_preserves_items(n, g, streams):
    """n_chunks not divisible by n_streams → padded; padding must never
    leak into the stacked result."""
    fn = lambda x: jnp.stack([x, x * x])
    xs = jnp.arange(n, dtype=jnp.float32) + 1.0
    got = relic_pfor(fn, xs, granularity=g, n_streams=streams)
    want = jax.vmap(fn)(xs)
    assert got.shape == want.shape
    np.testing.assert_allclose(np.asarray(got), np.asarray(want))


@settings(max_examples=40, deadline=None)
@given(
    n=st.integers(1, 200),
    g=st.integers(1, 64),
    streams=st.sampled_from([1, 2, 3, 4]),
)
def test_round_robin_deal_undeal_is_identity(n, g, streams):
    """Property: dealing chunks round-robin to streams and undealing
    restores the original item order exactly (fn = identity on the item
    index)."""
    idx = jnp.arange(n, dtype=jnp.int32)
    got = relic_pfor(lambda i: i, idx, granularity=g, n_streams=streams)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(idx))


@settings(max_examples=25, deadline=None)
@given(n=st.integers(1, 150), g=st.integers(1, 40))
def test_combine_sum_equals_vmap_sum(n, g):
    """Property: combine="sum" is the tree-sum of per-item results, with
    padding items masked out."""
    fn = lambda x: {"a": x * 2.0, "b": jnp.stack([x, -x])}
    xs = jnp.arange(n, dtype=jnp.float32) + 1.0
    got = relic_pfor(fn, xs, granularity=g, combine="sum")
    want = jax.tree.map(lambda y: y.sum(0), jax.vmap(fn)(xs))
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-4
        ),
        got,
        want,
    )


def test_combine_sum_masks_padding():
    """5 items at granularity 16: the pad repeats item 4 eleven times —
    an unmasked sum would be wildly wrong."""
    fn = lambda x: x
    xs = jnp.full((5,), 100.0)
    got = relic_pfor(fn, xs, granularity=16, combine="sum")
    np.testing.assert_allclose(float(got), 500.0)


def test_combine_sum_under_jit():
    fn = lambda x: x * x
    xs = jnp.arange(33, dtype=jnp.float32)
    f = jax.jit(lambda a: relic_pfor(fn, a, granularity=4, combine="sum"))
    np.testing.assert_allclose(float(f(xs)), float((xs * xs).sum()), rtol=1e-6)


def test_invalid_combine_rejected():
    with pytest.raises(ValueError, match="combine"):
        relic_pfor(lambda x: x, jnp.arange(4.0), granularity=2, combine="mean")


def test_benchmarks_declare_sum_and_plan_honors_it():
    """Benchmark.parallel_value(combine="sum") (the plan-layer path)
    equals the combined serial value."""
    from repro.bench_suite import BENCHMARKS

    b = BENCHMARKS["VWAP"]
    data = b.build()
    assert b.combine == "sum"
    got = b.parallel_value(data, granularity=8, combine=b.combine)
    want = b.serial_value(data, combine=b.combine)
    jax.tree.map(
        lambda a, w: np.testing.assert_allclose(
            np.asarray(a), np.asarray(w), rtol=1e-4, atol=1e-4
        ),
        got,
        want,
    )
