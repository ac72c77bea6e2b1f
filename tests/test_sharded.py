"""Distributed-correctness tests. Each test runs in a subprocess with
xla_force_host_platform_device_count=8 so the main pytest process keeps
the single real device (per the dry-run isolation rule)."""
import os
import subprocess
import sys

import pytest

ROOT = os.path.join(os.path.dirname(__file__), "..")


def _run(code: str):
    env = dict(os.environ, PYTHONPATH="src")
    r = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True, env=env, cwd=ROOT,
        timeout=600,
    )
    assert r.returncode == 0, f"stderr:\n{r.stderr}\nstdout:\n{r.stdout}"
    assert "PASS" in r.stdout, r.stdout


HEADER = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P
mesh = jax.make_mesh((2, 4), ("data", "model"), axis_types=(jax.sharding.AxisType.Auto,)*2)
"""


def test_moe_dispatch_matches_reference():
    _run(HEADER + """
from repro.configs import get_config
from repro.models.moe import _moe_reference, init_moe, moe_block
from repro.parallel.sharding import ShardingRules
cfg = get_config("granite-moe-1b-a400m").reduced()
params, _ = init_moe(jax.random.key(0), cfg, jnp.float32)
x = jax.random.normal(jax.random.key(1), (2, 16, cfg.d_model))
rules = ShardingRules(mesh, cfg)
with mesh:
    y_sh, aux_sh = jax.jit(lambda x, p: moe_block(x, p, cfg, rules, path="dispatch"))(x, params)
y_ref, aux_ref = moe_block(x, params, cfg, None)
np.testing.assert_allclose(np.asarray(y_sh), np.asarray(y_ref), atol=2e-4, rtol=2e-4)
print("PASS")
""")


def test_moe_dense_path_matches_reference():
    _run(HEADER + """
from repro.configs import get_config
from repro.models.moe import init_moe, moe_block
from repro.parallel.sharding import ShardingRules
cfg = get_config("granite-moe-1b-a400m").reduced()
params, _ = init_moe(jax.random.key(0), cfg, jnp.float32)
x = jax.random.normal(jax.random.key(1), (8, 1, cfg.d_model))
rules = ShardingRules(mesh, cfg)
with mesh:
    y_sh, _ = jax.jit(lambda x, p: moe_block(x, p, cfg, rules, path="dense"))(x, params)
# dense path computes ALL experts' masked contributions — compare against
# an explicit dense-mixture oracle
import jax.numpy as jnp2
from repro.models.moe import _route
x2 = x.reshape(-1, cfg.d_model)
gates, idx, _ = _route(x2, params["router"], cfg.top_k)
h = jax.nn.silu(jnp.einsum("td,edf->etf", x2, params["we1"]))
h = h * jnp.einsum("td,edf->etf", x2, params["we3"])
ye = jnp.einsum("etf,efd->etd", h, params["we2"])
gmat = jnp.zeros((x2.shape[0], cfg.n_experts)).at[jnp.arange(x2.shape[0])[:,None], idx].add(gates)
want = jnp.einsum("etd,te->td", ye, gmat).reshape(x.shape)
np.testing.assert_allclose(np.asarray(y_sh), np.asarray(want), atol=2e-4, rtol=2e-4)
print("PASS")
""")


def test_ring_collectives_match_dense():
    _run(HEADER + """
from repro.parallel.collectives import ring_allgather_matmul, matmul_reducescatter
T, D, F = 32, 16, 24
x = jax.random.normal(jax.random.key(0), (T, D))
w1 = jax.random.normal(jax.random.key(1), (D, F))
w2 = jax.random.normal(jax.random.key(2), (F, D))
agm = shard_map(lambda xl, wl: ring_allgather_matmul(xl, wl, "model"),
                mesh=mesh, in_specs=(P("model", None), P(None, "model")),
                out_specs=P(None, "model"), check_vma=False)
np.testing.assert_allclose(np.asarray(agm(x, w1)), np.asarray(x @ w1), atol=1e-5)
h = jax.random.normal(jax.random.key(3), (T, F))
rsm = shard_map(lambda hl, wl: matmul_reducescatter(hl, wl, "model"),
                mesh=mesh, in_specs=(P(None, "model"), P("model", None)),
                out_specs=P("model", None), check_vma=False)
np.testing.assert_allclose(np.asarray(rsm(h, w2)), np.asarray(h @ w2), atol=1e-5)
print("PASS")
""")


def test_distributed_softmax_combine():
    """Per-rank flash partials over a kv-sequence split combine to the
    exact global softmax-weighted sum (DESIGN.md §5 derivation)."""
    _run(HEADER + """
from repro.parallel.collectives import distributed_softmax
B, H, S, d = 2, 4, 32, 8
logits = jax.random.normal(jax.random.key(0), (B, H, S)) * 4.0
v = jax.random.normal(jax.random.key(1), (B, H, S, d))
want = jnp.einsum("bhs,bhsd->bhd", jax.nn.softmax(logits, axis=-1), v)
def local(lg, vl):
    m = lg.max(-1)
    p = jnp.exp(lg - m[..., None])
    acc = jnp.einsum("bhs,bhsd->bhd", p, vl)
    return distributed_softmax(m, p.sum(-1), acc, "model")
fn = shard_map(local, mesh=mesh,
               in_specs=(P(None, None, "model"), P(None, None, "model", None)),
               out_specs=P(), check_vma=False)
np.testing.assert_allclose(np.asarray(fn(logits, v)), np.asarray(want),
                           atol=1e-5, rtol=1e-5)
print("PASS")
""")


def test_distributed_softmax_empty_shard():
    """Empty-shard guard (DESIGN.md §5): a rank whose kv-sequence shard
    holds zero valid positions reports m = -inf / l = 0 and must
    contribute scale 0 — not NaN — to the combine; when every rank is
    empty the combine returns exact zeros."""
    _run(HEADER + """
from repro.parallel.collectives import distributed_softmax
B, H, S, d = 2, 3, 32, 8
sh = S // 4  # per-rank shard on the 4-way "model" axis
logits = jax.random.normal(jax.random.key(0), (B, H, S)) * 4.0
v = jax.random.normal(jax.random.key(1), (B, H, S, d))
# ranks 1..3 fully masked: only the first shard's positions are valid
valid = jnp.arange(S) < sh
want = jnp.einsum("bhs,bhsd->bhd",
                  jax.nn.softmax(jnp.where(valid, logits, -jnp.inf), axis=-1), v)
def local(lg, vl, keep):
    lg = jnp.where(keep, lg, -jnp.inf)  # a fully-masked shard: m = -inf
    m = lg.max(-1)
    p = jnp.where(keep, jnp.exp(lg - m[..., None]), 0.0)
    acc = jnp.einsum("bhs,bhsd->bhd", p, vl)
    return distributed_softmax(m, p.sum(-1), acc, "model")
fn = shard_map(local, mesh=mesh,
               in_specs=(P(None, None, "model"), P(None, None, "model", None),
                         P("model")),
               out_specs=P(), check_vma=False)
out = np.asarray(jax.jit(fn)(logits, v, valid))
assert not np.isnan(out).any(), "empty shards must not poison the combine"
np.testing.assert_allclose(out, np.asarray(want), atol=1e-5, rtol=1e-5)
# every rank empty -> the 0/0 row returns exact zeros, not NaN
out0 = np.asarray(jax.jit(fn)(logits, v, jnp.zeros(S, bool)))
np.testing.assert_array_equal(out0, np.zeros_like(out0))
print("PASS")
""")


def test_pipeline_two_stage():
    _run(HEADER.replace('(2, 4), ("data", "model")', '(2, 2, 2), ("pod", "data", "model")').replace("*2", "*3") + """
from repro.parallel.pipeline import pipelined_apply
L, D, B = 4, 8, 16
Ws = jax.random.normal(jax.random.key(0), (L, D, D)) * 0.3
def layer_fn(sp, x):
    def bd(x, w): return jnp.tanh(x @ w), None
    y, _ = jax.lax.scan(bd, x, sp)
    return y
x = jax.random.normal(jax.random.key(1), (B, D))
want = layer_fn(Ws[2:], layer_fn(Ws[:2], x))
got = pipelined_apply(layer_fn, Ws.reshape(2, 2, D, D), x, mesh=mesh, n_micro=4)
np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)
print("PASS")
""")


def test_compressed_psum_pod_axis():
    _run(HEADER.replace('(2, 4), ("data", "model")', '(2, 2, 2), ("pod", "data", "model")').replace("*2", "*3") + """
from repro.parallel.compression import compressed_psum
g = jax.random.normal(jax.random.key(2), (64,))
fn = shard_map(lambda gl, el: compressed_psum(gl, "pod", el),
               mesh=mesh, in_specs=(P(), P()), out_specs=(P(), P()), check_vma=False)
mean_g, err = fn(g, jnp.zeros_like(g))
# replicated input → mean == dequantized g, residual == quantization error
np.testing.assert_allclose(np.asarray(mean_g + err), np.asarray(g), atol=1e-6)
assert float(jnp.abs(err).max()) < float(jnp.abs(g).max()) / 64
print("PASS")
""")


def test_sharded_train_step_matches_single_device():
    _run(HEADER + """
from repro.configs import get_config
from repro.models import Model
from repro.data import SyntheticLMData
from repro.train import AdamW, make_train_step
from repro.train.optimizer import OptState
cfg = get_config("smollm-135m").reduced()
data = SyntheticLMData(cfg, batch=4, seq=32)
batch = data.batch_at(0)
opt = AdamW(lr=1e-3, warmup_steps=2, total_steps=10)

# single-device
m1 = Model(cfg)
params, axes = m1.init(jax.random.key(0))
p1, o1, met1 = jax.jit(make_train_step(m1, opt))(params, opt.init(params), batch)

# sharded
m2 = Model(cfg, mesh=mesh)
rules = m2.rules
pshard = rules.tree_shardings(params, axes)
with mesh:
    step = jax.jit(make_train_step(m2, opt))
    p2, o2, met2 = step(params, opt.init(params), batch)
assert abs(float(met1["loss"]) - float(met2["loss"])) < 5e-3, (met1["loss"], met2["loss"])
d = max(float(jnp.abs(a - b).max()) for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)))
assert d < 5e-2, d
print("PASS")
""")
