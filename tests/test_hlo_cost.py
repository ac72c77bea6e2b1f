"""Trip-count-aware HLO cost model vs unrolled ground truth."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.hlo_cost import analyze


def _text(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


def test_scan_flops_match_unrolled():
    def make(unroll):
        def f(x, ws):
            def body(c, w):
                return jnp.tanh(c @ w), None

            y, _ = jax.lax.scan(body, x, ws, unroll=unroll)
            return y

        return f

    x = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    ws = jax.ShapeDtypeStruct((12, 128, 128), jnp.float32)
    c_s = analyze(_text(make(False), x, ws))
    c_u = analyze(_text(make(True), x, ws))
    true = 12 * 2 * 128**3
    assert c_s.flops == pytest.approx(true, rel=1e-6)
    assert c_u.flops == pytest.approx(true, rel=1e-6)


def test_nested_scan_trip_counts():
    def f(x, ws):
        def outer(c, w):
            def inner(c2, _):
                return jnp.tanh(c2 @ w), None

            c2, _ = jax.lax.scan(inner, c, None, length=5)
            return c2, None

        y, _ = jax.lax.scan(outer, x, ws)
        return y

    x = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    ws = jax.ShapeDtypeStruct((7, 64, 64), jnp.float32)
    c = analyze(_text(f, x, ws))
    assert c.flops == pytest.approx(7 * 5 * 2 * 64**3, rel=1e-6)


def test_dot_general_contracted_dims():
    def f(a, b):
        return jnp.einsum("bij,bjk->bik", a, b)

    a = jax.ShapeDtypeStruct((4, 32, 64), jnp.float32)
    b = jax.ShapeDtypeStruct((4, 64, 16), jnp.float32)
    c = analyze(_text(f, a, b))
    assert c.flops == pytest.approx(2 * 4 * 32 * 64 * 16, rel=1e-6)


def test_scan_param_slicing_not_overcounted():
    """The scan body reads 1/L of the stacked weights per iteration; the
    walker must NOT charge the full stack every iteration."""
    L, D = 16, 64

    def f(x, ws):
        def body(c, w):
            return c @ w, None

        y, _ = jax.lax.scan(body, x, ws)
        return y

    x = jax.ShapeDtypeStruct((8, D), jnp.float32)
    ws = jax.ShapeDtypeStruct((L, D, D), jnp.float32)
    c = analyze(_text(f, x, ws))
    full_stack = L * D * D * 4
    # total weight traffic ≈ one pass over the stack (± small overheads),
    # NOT L × stack
    assert c.bytes < 4 * full_stack


def test_collectives_multiplied_by_trips():
    import os
    import subprocess
    import sys

    code = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.core.hlo_cost import analyze
mesh = jax.make_mesh((2,4), ("data","model"), axis_types=(jax.sharding.AxisType.Auto,)*2)
def f(x, ws):
    def body(c, w):
        y = jnp.tanh(c @ w)
        return jax.lax.with_sharding_constraint(y, NamedSharding(mesh, P("data", None))), None
    y, _ = jax.lax.scan(body, x, ws)
    return y
x = jax.ShapeDtypeStruct((64, 64), jnp.float32)
ws = jax.ShapeDtypeStruct((10, 64, 64, ), jnp.float32)
with mesh:
    comp = jax.jit(f, in_shardings=(NamedSharding(mesh, P("data", None)),
                                    NamedSharding(mesh, P(None, None, "model")))).lower(x, ws).compile()
c = analyze(comp.as_text())
counts = dict(c.collective_counts)
assert sum(counts.values()) >= 10, counts   # per-layer collective × trip count
print("OK", counts)
"""
    env = dict(os.environ, PYTHONPATH="src")
    r = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True, env=env,
        cwd=os.path.join(os.path.dirname(__file__), ".."),
    )
    assert r.returncode == 0, r.stderr
    assert "OK" in r.stdout
