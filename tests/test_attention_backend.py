"""Attention-backend layer: registry resolution, block-paged kernel vs
the dense-gather reference (GQA group sizes × slot/paged layouts ×
int8-KV × verify depths, cache lengths on block boundaries), paged
gather/scatter property tests with null-block routing, no-retrace
contracts, and the measured KernelAdvisorTool gate."""
import dataclasses
import random

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.configs import get_config
from repro.kernels import ops, ref
from repro.models import Model
from repro.models.attention import (
    gather_block_rows,
    scatter_block_token,
    scatter_block_tokens,
)
from repro.serve import Request, ServingEngine, SpecConfig

KEY = jax.random.key(0)


def ks(i):
    return jax.random.fold_in(KEY, i)


@pytest.fixture(scope="module")
def served():
    cfg = get_config("smollm-135m").reduced()
    m = Model(cfg)
    params, _ = m.init(jax.random.key(0))
    prompts = jax.random.randint(jax.random.key(1), (6, 16), 0, cfg.vocab_size)
    return cfg, m, params, prompts


# ---------------------------------------------------------------------------
# registry: resolve once, fail loudly, per-call wins


@pytest.fixture
def clean_registry(monkeypatch):
    """Snapshot/restore the resolved-backend cache around a test."""
    monkeypatch.delenv("REPRO_ATTENTION_BACKEND", raising=False)
    saved = ops._ATTN_BACKEND
    ops.set_attention_backend(None)
    yield monkeypatch
    ops._ATTN_BACKEND = saved


def test_bad_backend_override_fails_with_choices(clean_registry):
    clean_registry.setenv("REPRO_ATTENTION_BACKEND", "warp")
    with pytest.raises(ValueError, match=r"reference.*kernel.*interpret"):
        ops.resolve_attention_backend()
    with pytest.raises(ValueError, match=r"reference.*kernel.*interpret"):
        ops.resolve_attention_backend("warp")
    with pytest.raises(ValueError, match=r"reference.*kernel.*interpret"):
        ops.set_attention_backend("warp")


def test_backend_resolution_order(clean_registry):
    # env resolves once; "auto" maps to the platform default (CPU → reference)
    clean_registry.setenv("REPRO_ATTENTION_BACKEND", "interpret")
    assert ops.resolve_attention_backend() == "interpret"
    # config override beats env; None restores env/platform resolution
    ops.set_attention_backend("reference")
    assert ops.resolve_attention_backend() == "reference"
    # per-call always wins; an explicit "auto" defers to the default
    # chain (config → env → platform), never bypassing the env override
    assert ops.resolve_attention_backend("interpret") == "interpret"
    assert ops.resolve_attention_backend("auto") == "reference"  # config override
    ops.set_attention_backend("auto")  # restores env resolution
    assert ops.resolve_attention_backend("auto") == "interpret"  # env wins


def test_bad_kernel_mode_override_fails_loudly(monkeypatch):
    saved = ops._DEFAULT_MODE
    ops._DEFAULT_MODE = None
    monkeypatch.setenv("REPRO_KERNEL_MODE", "mosaic")
    try:
        with pytest.raises(ValueError, match=r"ref.*kernel.*interpret"):
            ops.default_kernel_mode()
    finally:
        ops._DEFAULT_MODE = saved


def test_kernel_mode_resolves_once(monkeypatch):
    saved = ops._DEFAULT_MODE
    ops._DEFAULT_MODE = None
    monkeypatch.setenv("REPRO_KERNEL_MODE", "interpret")
    try:
        assert ops.default_kernel_mode() == "interpret"
        # cached: later env changes don't re-resolve mid-process
        monkeypatch.setenv("REPRO_KERNEL_MODE", "ref")
        assert ops.default_kernel_mode() == "interpret"
    finally:
        ops._DEFAULT_MODE = saved


# ---------------------------------------------------------------------------
# kernel vs dense-gather oracle (the per-layer differential)


@pytest.mark.parametrize("t", [1, 2, 4, 8])
@pytest.mark.parametrize("h,kv", [(4, 4), (4, 2), (8, 2), (4, 1)])
def test_paged_kernel_matches_oracle(t, h, kv):
    B, hd, NB, BS, MB = 3, 16, 11, 4, 5
    rng = np.random.default_rng(t * 31 + h * 7 + kv)
    q = jnp.asarray(rng.normal(size=(B, t, h, hd)), jnp.float32)
    kp = jnp.asarray(rng.normal(size=(NB, BS, kv, hd)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(NB, BS, kv, hd)), jnp.float32)
    tbl = jnp.asarray(rng.integers(0, NB, size=(B, MB)), jnp.int32)
    # lengths exercise 0, a block-interior value, and an exact block
    # boundary (the mask edge lands precisely between DMA'd blocks)
    lens = jnp.asarray([0, BS * 2, BS * 3 - t][:B], jnp.int32)
    got = ops.paged_attention(q, kp, vp, tbl, lens, mode="interpret")
    want = ops.paged_attention(q, kp, vp, tbl, lens, mode="ref")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("t", [1, 4])
def test_paged_kernel_int8_dequant_in_kernel(t):
    B, h, kv, hd, NB, BS, MB = 2, 4, 2, 16, 9, 8, 3
    rng = np.random.default_rng(t)
    q = jnp.asarray(rng.normal(size=(B, t, h, hd)), jnp.float32)
    kp = jnp.asarray(rng.integers(-127, 128, size=(NB, BS, kv, hd)), jnp.int8)
    vp = jnp.asarray(rng.integers(-127, 128, size=(NB, BS, kv, hd)), jnp.int8)
    kscale = jnp.asarray(rng.uniform(0.05, 1.0, size=(NB, BS, kv)), jnp.bfloat16)
    vscale = jnp.asarray(rng.uniform(0.05, 1.0, size=(NB, BS, kv)), jnp.bfloat16)
    tbl = jnp.asarray(rng.integers(0, NB, size=(B, MB)), jnp.int32)
    lens = jnp.asarray([BS, 2 * BS - t], jnp.int32)  # one on a boundary
    got = ops.paged_attention(q, kp, vp, tbl, lens, kscale, vscale, mode="interpret")
    want = ops.paged_attention(q, kp, vp, tbl, lens, kscale, vscale, mode="ref")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=5e-4, rtol=5e-4)


def test_paged_oracle_matches_decode_attention_ref():
    """The paged oracle with the identity table and T=1 is exactly the
    dense decode oracle — pins the lengths convention (query t sees
    positions < len + t + 1) against the established reference."""
    B, h, kv, hd, Smax, BS = 2, 4, 2, 16, 32, 8
    rng = np.random.default_rng(5)
    q = jnp.asarray(rng.normal(size=(B, 1, h, hd)), jnp.float32)
    kc = jnp.asarray(rng.normal(size=(B, Smax, kv, hd)), jnp.float32)
    vc = jnp.asarray(rng.normal(size=(B, Smax, kv, hd)), jnp.float32)
    clen = jnp.asarray([7, Smax], jnp.int32)
    mb = Smax // BS
    tbl = jnp.arange(B * mb, dtype=jnp.int32).reshape(B, mb)
    pool = lambda a: a.reshape((B * mb, BS) + a.shape[2:])
    got = ref.paged_attention_ref(q, pool(kc), pool(vc), tbl, clen - 1)
    want = ref.decode_attention_ref(q[:, 0], kc, vc, clen)
    np.testing.assert_allclose(np.asarray(got[:, 0]), np.asarray(want), atol=1e-5, rtol=1e-5)


def test_ops_paged_attention_mode_contract(clean_registry):
    """The wrapper accepts the registry's own name ("reference"), fails
    loudly on bad modes, and resolves "auto" OUTSIDE the jit boundary —
    a registry change between calls is honored, not replayed from the
    first trace."""
    rng = np.random.default_rng(9)
    B, t, h, kv, hd, NB, BS, MB = 2, 1, 4, 2, 8, 5, 4, 3  # unique shapes
    q = jnp.asarray(rng.normal(size=(B, t, h, hd)), jnp.float32)
    kp = jnp.asarray(rng.normal(size=(NB, BS, kv, hd)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(NB, BS, kv, hd)), jnp.float32)
    tbl = jnp.asarray(rng.integers(0, NB, size=(B, MB)), jnp.int32)
    lens = jnp.asarray([3, BS * 2], jnp.int32)
    a = ops.paged_attention(q, kp, vp, tbl, lens, mode="reference")
    b = ops.paged_attention(q, kp, vp, tbl, lens, mode="ref")
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    with pytest.raises(ValueError, match=r"reference.*kernel.*interpret"):
        ops.paged_attention(q, kp, vp, tbl, lens, mode="mosaic")
    # auto re-resolves per call: flipping the registry switches branches
    # (distinct static modes → distinct jit entries, same shapes)
    ops.set_attention_backend("reference")
    ref_out = ops.paged_attention(q, kp, vp, tbl, lens, mode="auto")
    size0 = ops._paged_attention_impl._cache_size()  # auto hit the ref trace
    ops.set_attention_backend("interpret")
    int_out = ops.paged_attention(q, kp, vp, tbl, lens, mode="auto")
    # same shapes, new static mode → a NEW trace: auto re-resolved
    assert ops._paged_attention_impl._cache_size() == size0 + 1
    np.testing.assert_allclose(
        np.asarray(ref_out), np.asarray(int_out), atol=2e-5, rtol=2e-5
    )


# ---------------------------------------------------------------------------
# gather/scatter property tests (the reference path stays honest)


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 10_000), bs=st.integers(1, 5))
def test_gather_scatter_block_rows_match_python_reference(seed, bs):
    """Random block tables with null-block entries and boundary-spanning
    verify writes: gather+mask equals a per-row python reference,
    dead-row writes land in the null block ONLY, and live writes touch
    exactly the addressed (block, offset) slots."""
    rng = random.Random(seed)
    L, B, MB = 2, rng.randint(1, 4), rng.randint(2, 5)
    NB, hd = rng.randint(2, 8), 3
    null = NB  # the spare block, as PagedKVCache lays it out
    pool = np.arange(L * (NB + 1) * bs * hd, dtype=np.float32).reshape(
        L, NB + 1, bs, hd
    )
    tables = np.full((B, MB), null, np.int32)
    owned = [rng.randint(0, MB) for _ in range(B)]  # rows own a prefix; rest null
    for b in range(B):
        for j in range(owned[b]):
            tables[b, j] = rng.randrange(NB)

    got = np.asarray(gather_block_rows(jnp.asarray(pool), jnp.asarray(tables)))
    for b in range(B):
        want = np.concatenate([pool[:, tables[b, j]] for j in range(MB)], axis=1)
        np.testing.assert_array_equal(got[:, b], want)

    # single-token scatter: dead rows (no owned tail) target the null block
    tok = np.arange(L * B * hd, dtype=np.float32).reshape(L, B, hd) + 1000.0
    bid = np.array(
        [tables[b, max(owned[b] - 1, 0)] for b in range(B)], np.int32
    )
    off = np.array([rng.randrange(bs) for _ in range(B)], np.int32)
    new = np.asarray(
        scatter_block_token(jnp.asarray(pool), jnp.asarray(tok), jnp.asarray(bid), jnp.asarray(off))
    )
    expect = pool.copy()
    for b in range(B):  # later rows win colliding writes, like jax .set
        expect[:, bid[b], off[b]] = tok[:, b]
    np.testing.assert_array_equal(new, expect)
    touched = {(int(bid[b]), int(off[b])) for b in range(B)}
    unchanged = [
        (blk, o)
        for blk in range(NB + 1)
        for o in range(bs)
        if (blk, o) not in touched
    ]
    for blk, o in unchanged:
        np.testing.assert_array_equal(new[:, blk, o], pool[:, blk, o])
    for b in range(B):
        if owned[b] == 0:  # dead row: its write may only land in the null block
            assert int(bid[b]) == null

    # multi-token (verify) scatter spanning a block boundary
    T = bs + 1  # guarantees at least one boundary crossing
    start = rng.randrange(bs)
    pos = start + np.arange(T)
    rows = np.arange(L * B * T * hd, dtype=np.float32).reshape(L, B, T, hd) - 500.0
    bid2 = np.zeros((B, T), np.int32)
    off2 = np.zeros((B, T), np.int32)
    for b in range(B):
        for t in range(T):
            j = int(pos[t]) // bs
            bid2[b, t] = tables[b, j] if j < MB else null
            off2[b, t] = int(pos[t]) % bs
    new2 = np.asarray(
        scatter_block_tokens(
            jnp.asarray(pool), jnp.asarray(rows), jnp.asarray(bid2), jnp.asarray(off2)
        )
    )
    expect2 = pool.copy()
    for b in range(B):
        for t in range(T):
            expect2[:, bid2[b, t], off2[b, t]] = rows[:, b, t]
    np.testing.assert_array_equal(new2, expect2)


# ---------------------------------------------------------------------------
# serve-level differentials: kernel backend ≡ reference backend


def _trace(prompts, lens, budgets, eos=None, eos_req=None):
    return [
        Request(
            prompt=np.asarray(prompts[i, : lens[i]]),
            max_new_tokens=int(budgets[i]),
            arrival_time=0.01 * i,
            eos_id=eos if i == eos_req else None,
        )
        for i in range(len(lens))
    ]


def _serve_both_backends(m, params, prompts, *, kv_layout, int8=False, spec=None, seed=2):
    cfg = m.cfg
    if int8:
        m = Model(dataclasses.replace(cfg, kv_quant=True))
        params, _ = m.init(jax.random.key(0))
    rng = np.random.default_rng(seed)
    n = 4
    lens = rng.integers(3, 16, size=n)
    # prompt lengths landing exactly on block boundaries included
    lens[0] = 8
    budgets = rng.integers(2, 7, size=n)
    eng = ServingEngine(m, params, max_seq=64, kv_layout=kv_layout, block_size=4)
    outs = {}
    for backend in ("reference", "interpret"):
        reqs = _trace(prompts, lens, budgets)
        sched = eng.scheduler(3, spec=spec, attention_backend=backend)
        out = sched.run(reqs)
        if kv_layout == "paged":
            sched.kv.check_invariants()
        outs[backend] = [np.asarray(out[r.rid]) for r in reqs]
        assert all(r.finished for r in reqs)
    for a, b in zip(outs["reference"], outs["interpret"]):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("kv_layout", ["slot", "paged"])
def test_serve_kernel_backend_token_identical(served, kv_layout):
    """Randomized open-loop trace through the interpret-mode kernel
    backend decodes token-for-token identical to the reference backend
    — no dense gather on the kernel path (both layouts)."""
    _, m, params, prompts = served
    _serve_both_backends(m, params, prompts, kv_layout=kv_layout)


@pytest.mark.parametrize("kv_layout", ["slot", "paged"])
def test_serve_kernel_backend_token_identical_int8(served, kv_layout):
    """int8-KV: per-vector scales ride their own blocks and dequantize
    in-kernel; the token stream still matches the reference backend."""
    _, m, params, prompts = served
    _serve_both_backends(m, params, prompts, kv_layout=kv_layout, int8=True)


@pytest.mark.parametrize("kv_layout", ["slot", "paged"])
@pytest.mark.parametrize("k", [2, 4, 8])
def test_verify_kernel_backend_token_identical(served, kv_layout, k):
    """Speculative serving through the kernel backend (the K+1-query
    verify variant) stays token-identical to the reference backend at
    every depth — acceptance is data, the kernel trace is per depth."""
    _, m, params, prompts = served
    _serve_both_backends(
        m, params, prompts, kv_layout=kv_layout,
        spec=SpecConfig(k=k, drafter="ngram"),
    )


def test_verify_kernel_backend_model_drafter(served):
    """The draft-model stream (its own slot pool) rides the kernel
    backend too: target verify and drafter decode both dispatch through
    the registry, and the stream stays token-identical."""
    cfg, m, params, prompts = served
    dm = Model(dataclasses.replace(cfg, num_layers=1))
    dparams, _ = dm.init(jax.random.key(7))
    _serve_both_backends(
        m, params, prompts, kv_layout="paged",
        spec=SpecConfig(k=4, drafter="model", draft_model=dm, draft_params=dparams),
    )


# ---------------------------------------------------------------------------
# trace discipline


def test_paged_kernel_step_no_retrace_on_table_or_length_changes(served):
    """One jit trace serves any block layout and live set: changing
    only ``cache_len``/``block_tables`` values (same shapes) must not
    retrace the kernel-backend paged step."""
    _, m, params, _ = served
    traces = []

    def counted(params, pool, tables, lens, tok):
        traces.append(1)
        return m.decode_step_paged(params, pool, tables, lens, tok, backend="interpret")

    step = jax.jit(counted)
    B, bs, nb, mb = 2, 4, 12, 4
    pool = m.init_paged_cache(nb + 1, bs)
    tok = jnp.zeros((B, 1), jnp.int32)
    tables = jnp.asarray([[0, 1, nb, nb], [2, 3, nb, nb]], jnp.int32)
    lens = jnp.asarray([3, 5], jnp.int32)
    _, pool = step(params, pool, tables, lens, tok)
    _, pool = step(params, pool, tables + 1, lens + 1, tok)
    _, pool = step(params, pool, jnp.flip(tables, 0), jnp.asarray([0, 8], jnp.int32), tok)
    assert len(traces) == 1, "tables/lengths must be data, not shape"


def test_sharded_callers_stay_on_reference_path():
    """With sharding rules set the kernel dispatch is bypassed — the
    kernel is not SPMD-partitioned, so the seq-sharded flash-decode
    reference semantics must keep serving those callers. Pinned by
    bitwise equality with the explicit reference path (the kernel path
    would differ in accumulation order)."""
    from repro.models.attention import decode_attention

    rng = np.random.default_rng(3)
    B, h, kv, hd, Smax = 2, 4, 2, 16, 32
    q = jnp.asarray(rng.normal(size=(B, 1, h, hd)), jnp.float32)
    kc = jnp.asarray(rng.normal(size=(B, Smax, kv, hd)), jnp.float32)
    vc = jnp.asarray(rng.normal(size=(B, Smax, kv, hd)), jnp.float32)
    clen = jnp.asarray([5, 17], jnp.int32)
    want = decode_attention(q, kc, vc, clen, backend="reference")
    got = decode_attention(q, kc, vc, clen, rules=object(), backend="interpret")
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_kernel_backend_degenerate_max_seq_falls_back_to_reference(served):
    """A (near-)prime max_seq has no usable identity-table tiling. The
    kernel backend does not swap in the reference numerics for that
    shape: it refuses, naming the shape, and only an explicit reference
    backend serves it."""
    from repro.models.attention import _dense_block_size

    assert _dense_block_size(64) == 64
    assert _dense_block_size(512) == 256
    assert _dense_block_size(257) == 1  # prime → degenerate → refused
    _, m, params, prompts = served
    eng = ServingEngine(m, params, max_seq=257, attention_backend="interpret")
    with pytest.raises(ValueError, match="max_seq=257 has no block divisor"):
        eng.generate(prompts[:2, :5], n_steps=3)
    ref = ServingEngine(m, params, max_seq=257, attention_backend="reference")
    assert np.asarray(ref.generate(prompts[:2, :5], n_steps=3)).shape == (2, 3)


def test_backend_override_rejected_under_decode_plan(served):
    """A decode plan's per-request fn binds the engine backend at
    region-advise time; a different per-call override must fail loudly
    instead of silently running (and mislabeling) the old backend."""
    from repro.core import Aira, Workload

    _, m, params, prompts = served
    eng = ServingEngine(m, params, max_seq=64)
    region = eng.decode_region(prompts[:2, :8], force=True)
    d = Aira().advise(Workload("w", lambda: None, [region])).decisions[0]
    assert d.accepted
    eng.set_decode_plan(d.plan)
    with pytest.raises(ValueError, match="re-advise"):
        eng.scheduler(2, attention_backend="interpret")
    eng.scheduler(2)  # engine's own backend still fine


def test_engine_step_family_cached_per_backend(served):
    """Switching backends on one engine reuses each backend's jitted
    family — no cross-backend clobbering, no rebuild on re-request."""
    _, m, params, _ = served
    eng = ServingEngine(m, params, max_seq=32)
    ref_fns = eng._step_fns("reference")
    int_fns = eng._step_fns("interpret")
    assert ref_fns is not int_fns
    assert eng._step_fns("reference")["decode"] is ref_fns["decode"]
    eng._paged_fns("interpret")
    assert "decode_paged" in eng._steps["interpret"]
    assert "decode_paged" not in eng._steps["reference"]


# ---------------------------------------------------------------------------
# the measured backend gate


def test_kernel_advisor_prices_measured_cost():
    from repro.core.tools import KernelAdvisorTool, KernelMeasurement

    tool = KernelAdvisorTool()
    # kernel clearly faster → chosen, gain quoted vs reference
    m = KernelMeasurement.make("dense", "paged", 0, {"reference": 2.0, "kernel": 1.0})
    backend, gain, log = tool.choose(m)
    assert backend == "kernel" and gain == pytest.approx(1.0)
    assert "paged" in log and "kernel" in log
    # inside the threshold → don't switch (measured, not assumed)
    m = KernelMeasurement.make("dense", "slot", 0, {"reference": 1.0, "kernel": 0.99})
    assert tool.choose(m)[0] == "reference"
    # interpret slower than reference (CPU CI) → reference
    m = KernelMeasurement.make("dense", "slot", 4, {"reference": 1.0, "interpret": 3.0})
    backend, gain, _ = tool.choose(m)
    assert backend == "reference" and gain == 0.0
    with pytest.raises(ValueError, match="reference"):
        KernelMeasurement.make("dense", "slot", 0, {"kernel": 1.0})


def test_kernel_advisor_is_silent_for_compute_regions():
    """As a pipeline stage the tool SKIPs (no stage-log line) unless a
    region carries a kernel measurement — golden decisions untouched;
    a measured region gets a 'kernel:' line with the chosen backend."""
    from repro.core import Aira, Workload
    from repro.core.adviser import Region
    from repro.core.overlap_model import CPU_HW
    from repro.core.tools import KernelMeasurement

    def region(name):
        return Region(
            name, lambda x: x * 2.0, jnp.arange(1024, dtype=jnp.float32),
            task_flops=100.0, task_bytes=512.0, task_chain=16,
        )

    r1 = region("plain")
    d = Aira(hw=CPU_HW).advise(Workload("w", lambda: None, [r1])).decisions[0]
    assert d.accepted
    assert not any("kernel" in line for line in d.stage_log)

    r2 = region("measured")
    r2.kernel_measurement = KernelMeasurement.make(
        "dense", "paged", 0, {"reference": 2.0, "kernel": 0.8}
    )
    d2 = Aira(hw=CPU_HW).advise(Workload("w", lambda: None, [r2])).decisions[0]
    assert any(
        line.startswith("kernel:") and "→ kernel" in line for line in d2.stage_log
    )
