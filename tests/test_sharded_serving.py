"""Sharded-serving differential lanes (DESIGN.md §5). Head-only
("model") meshes must produce BITWISE the token streams of the
single-device paged path — head partitioning only moves parallel work,
never changes a reduction order. kv-sequence-split meshes ("seq" and 2D
("model","seq")) recombine softmaxes from per-rank flash partials, so
their lane is tolerance-based: argmax token identity plus a
max-abs-logit bound (``repro.serve.differential``). Each test runs in a
subprocess with a forced 4-device CPU host platform so the main pytest
process keeps its single real device."""
import os
import subprocess
import sys

import pytest

ROOT = os.path.join(os.path.dirname(__file__), "..")


def _run(code: str):
    env = dict(os.environ, PYTHONPATH="src")
    r = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True, env=env,
        cwd=ROOT, timeout=600,
    )
    assert r.returncode == 0, f"stderr:\n{r.stderr}\nstdout:\n{r.stdout}"
    assert "PASS" in r.stdout, r.stdout


def _header(tp: int) -> str:
    return f"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import dataclasses
import jax, jax.numpy as jnp, numpy as np
from repro.configs import get_config
from repro.models.model import Model
from repro.serve.engine import ServingEngine
from repro.serve.request import Request
from repro.serve.speculative import SpecConfig
mesh = jax.make_mesh(({tp},), ("model",), axis_types=(jax.sharding.AxisType.Auto,))

# 4 kv heads so both 2- and 4-way meshes divide; g=2 exercises GQA grouping
CFG = dataclasses.replace(
    get_config("smollm-135m").reduced(),
    num_layers=2, d_model=64, n_heads=8, n_kv_heads=4, head_dim=8,
)

def build(cfg):
    m = Model(cfg)
    params, _ = m.init(jax.random.key(0))
    return m, params

def reqs(n=4, plen=6, new=8, **kw):
    return [
        Request(prompt=(np.arange(plen, dtype=np.int32) * (i + 1)) % cfg_vocab,
                max_new_tokens=new, **kw)
        for i in range(n)
    ]
cfg_vocab = CFG.vocab_size

def identical(a, b):
    # rids are globally auto-assigned, so match streams by admission order
    assert len(a) == len(b)
    for (_, va), (_, vb) in zip(sorted(a.items()), sorted(b.items())):
        np.testing.assert_array_equal(np.asarray(va), np.asarray(vb))
"""


@pytest.mark.parametrize("tp", [2, 4])
def test_sharded_token_identity_modes(tp):
    """plain / speculative K∈{2,4} / chunked-prefill serving over a
    tp-way head-partitioned pool == the single-device paged path,
    bitwise, through the interpret (real kernel code) backend."""
    _run(_header(tp) + """
m, params = build(CFG)
base = ServingEngine(m, params, max_seq=64, kv_layout="paged",
                     attention_backend="interpret")
sharded = ServingEngine(m, params, max_seq=64, kv_layout="paged",
                        attention_backend="kernel", mesh=mesh)
assert sharded.mesh is mesh
assert sharded.attention_backend == "interpret"  # mesh-aware resolution

# the pool really is head-partitioned over the mesh
sched = sharded.scheduler(4)
spec = sched.kv.pool["k"].sharding.spec
assert "model" in tuple(spec), spec

identical(base.serve(reqs(), max_batch=4), sharded.serve(reqs(), max_batch=4))
for K in (2, 4):
    identical(base.serve(reqs(), max_batch=4, spec=SpecConfig(k=K)),
              sharded.serve(reqs(), max_batch=4, spec=SpecConfig(k=K)))
identical(base.serve(reqs(plen=12), max_batch=4, chunk_size=4),
          sharded.serve(reqs(plen=12), max_batch=4, chunk_size=4))
print("PASS")
""")


@pytest.mark.parametrize("tp", [2, 4])
def test_sharded_token_identity_int8_kv(tp):
    """int8-KV pool (values + per-vector scales both head-partitioned)
    decodes and verifies bitwise-identically to single-device int8."""
    _run(_header(tp) + """
cfg8 = dataclasses.replace(CFG, kv_quant=True)
m, params = build(cfg8)
base = ServingEngine(m, params, max_seq=64, kv_layout="paged",
                     attention_backend="interpret")
sharded = ServingEngine(m, params, max_seq=64, kv_layout="paged",
                        attention_backend="interpret", mesh=mesh)
identical(base.serve(reqs(), max_batch=4), sharded.serve(reqs(), max_batch=4))
identical(base.serve(reqs(), max_batch=4, spec=SpecConfig(k=2)),
          sharded.serve(reqs(), max_batch=4, spec=SpecConfig(k=2)))
print("PASS")
""")


def _mesh_header(shape, names) -> str:
    """Header with an arbitrary serving mesh (e.g. ``(2, 2)`` over
    ``("model", "seq")``) instead of the head-only one."""
    return _header(2).replace(
    	'jax.make_mesh((2,), ("model",), axis_types=(jax.sharding.AxisType.Auto,))',
    	f'jax.make_mesh({shape!r}, {names!r}, '
    	f'axis_types=(jax.sharding.AxisType.Auto,) * {len(shape)})',
    )


@pytest.mark.parametrize(
    "shape,names",
    [((2,), ("model",)), ((2,), ("seq",)), ((2, 2), ("model", "seq"))],
    ids=["model2", "seq2", "model2xseq2"],
)
def test_mesh_shapes_token_identity(shape, names):
    """The serve-level differential over every mesh family the engine
    supports: head-only (bitwise lane), kv-sequence split, and the 2D
    composition — plain, speculative K=2, and chunked prefill all match
    the single-device paged streams (tolerance lane's argmax token
    identity; greedy tokens ARE the argmax)."""
    _run(_mesh_header(shape, names) + """
from repro.serve.differential import assert_streams_equal
m, params = build(CFG)
base = ServingEngine(m, params, max_seq=64, kv_layout="paged",
                     attention_backend="interpret")
sharded = ServingEngine(m, params, max_seq=64, kv_layout="paged",
                        attention_backend="interpret", mesh=mesh)
assert sharded.mesh is mesh
sched = sharded.scheduler(4)
spec = tuple(sched.kv.pool["k"].sharding.spec)
for ax in mesh.axis_names:
    if mesh.shape[ax] > 1:
        assert ax in spec, (ax, spec)  # pool really partitioned on ax
assert_streams_equal(base.serve(reqs(), max_batch=4),
                     sharded.serve(reqs(), max_batch=4), label="plain")
assert_streams_equal(
    base.serve(reqs(), max_batch=4, spec=SpecConfig(k=2)),
    sharded.serve(reqs(), max_batch=4, spec=SpecConfig(k=2)), label="spec")
assert_streams_equal(
    base.serve(reqs(plen=12), max_batch=4, chunk_size=4),
    sharded.serve(reqs(plen=12), max_batch=4, chunk_size=4), label="chunked")
print("PASS")
""")


def test_seq_split_reference_backend():
    """The reference backend must route through the partials path under
    the kv-sequence split (the dense differential route gathers through
    global tables, which cannot address a local pool shard) — pinned by
    serving through a pure-"seq" mesh with backend="reference"."""
    _run(_mesh_header((2,), ("seq",)) + """
from repro.serve.differential import assert_streams_equal
m, params = build(CFG)
base = ServingEngine(m, params, max_seq=64, kv_layout="paged",
                     attention_backend="reference")
sharded = ServingEngine(m, params, max_seq=64, kv_layout="paged",
                        attention_backend="reference", mesh=mesh)
assert_streams_equal(base.serve(reqs(), max_batch=4),
                     sharded.serve(reqs(), max_batch=4), label="reference")
print("PASS")
""")


def test_seq_split_logit_tolerance_empty_shards():
    """Tolerance-lane logit bound with the empty-shard guard on the hot
    path: rows short enough that one rank's kv-sequence shard holds ZERO
    blocks still decode NaN-free, argmax-identical, and within the
    rounding bound of the single-device step."""
    _run(_mesh_header((2,), ("seq",)) + """
from repro.serve.differential import assert_logits_close
from repro.serve.kv_cache import PagedKVCache
m, params = build(CFG)
prompts = [(np.arange(3, dtype=np.int32) * (i + 1)) % cfg_vocab for i in range(4)]
def one_step(use_mesh):
    kv = PagedKVCache(m, max_batch=4, max_seq=32, block_size=8,
                      mesh=mesh if use_mesh else None, prefix_cache=False)
    for i, p in enumerate(prompts):
        assert kv.try_admit(i, p, budget=8) is not None
        _, dense = jax.jit(lambda pr: m.prefill(params, pr, 32))(jnp.asarray(p)[None])
        kv.write_prefill(i, dense)
    step = (m.sharded_paged_step("decode_step_paged", mesh, backend="interpret")
            if use_mesh else m.jit_step("decode_step_paged", backend="interpret"))
    pool, tables, lens = kv.kernel_inputs()
    tok = jnp.asarray([[int(p[-1])] for p in prompts], jnp.int32)
    logits, _ = step(params, pool, tables, lens, tok)
    return np.asarray(logits)
base, got = one_step(False), one_step(True)
# 3-token rows own one block each; the 2-way slot layout places every
# early block on rank 0, so rank 1 is fully empty -> guard on hot path
assert_logits_close(base, got, atol=1e-4, label="seq2 one-step")
print("PASS")
""")


def test_mesh_fallback_warns_once():
    """GQA fallback dedupe: serving repeatedly through a mesh the head
    partitioning cannot divide warns exactly once per (cfg, mesh), keeps
    one deduped ``mesh_fallbacks`` record, and still serves (replicated,
    never wrong tokens)."""
    _run(_header(2) + """
import logging
cfg3 = dataclasses.replace(CFG, n_heads=6, n_kv_heads=3)  # 3 kv-heads ∤ tp=2
m, params = build(cfg3)
eng = ServingEngine(m, params, max_seq=64, kv_layout="paged",
                    attention_backend="interpret")
base = ServingEngine(m, params, max_seq=64, kv_layout="paged",
                     attention_backend="interpret")

class Count(logging.Handler):
    n = 0
    def emit(self, record):
        Count.n += 1

h = Count()
logging.getLogger("repro.serve").addHandler(h)
outs = [eng.serve(reqs(), max_batch=4, mesh=mesh) for _ in range(3)]
logging.getLogger("repro.serve").removeHandler(h)
assert Count.n == 1, f"fallback warned {Count.n} times, want once"
assert len(eng.mesh_fallbacks) == 1, eng.mesh_fallbacks
assert eng.mesh is None  # never adopted the undividable mesh
want = base.serve(reqs(), max_batch=4)
for got in outs:
    for (_, va), (_, vb) in zip(sorted(want.items()), sorted(got.items())):
        np.testing.assert_array_equal(np.asarray(va), np.asarray(vb))
print("PASS")
""")


@pytest.mark.parametrize("tp", [2, 4])
def test_sharded_preempt_resume_identity(tp):
    """Block-pressure preemption + suffix-resume on the sharded pool:
    evicted-and-resumed requests still match a roomy unsharded serve."""
    _run(_header(tp) + """
m, params = build(CFG)
def workload():
    low = [Request(prompt=np.arange(20, dtype=np.int32) + i, max_new_tokens=10,
                   arrival_time=0.0, priority=0) for i in range(2)]
    high = [Request(prompt=np.arange(9, dtype=np.int32), max_new_tokens=4,
                    arrival_time=0.02, priority=5)]
    return low + high

pressured = ServingEngine(m, params, max_seq=128, kv_layout="paged",
                          max_batch=2, block_size=8, num_blocks=10, mesh=mesh)
roomy = ServingEngine(m, params, max_seq=128, kv_layout="paged",
                      max_batch=4, block_size=8)
p_reqs, r_reqs = workload(), workload()
p_out = pressured.serve(p_reqs)
assert pressured.stats.n_preemptions > 0, "pressure scenario did not evict"
r_out = roomy.serve(r_reqs)
assert roomy.stats.n_preemptions == 0
for a, b in zip(p_reqs, r_reqs):
    np.testing.assert_array_equal(np.asarray(p_out[a.rid]),
                                  np.asarray(r_out[b.rid]))
print("PASS")
""")
