"""Chunked prefill: token identity with monolithic prefill (both KV
layouts, with and without speculation), the closed pow2 trace family
(no retrace within a bucket, for chunk steps and bucketed monolithic
prefill alike), and the config gates around the chunked path."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import Model
from repro.models.model import CHUNKED_PREFILL_FAMILIES, prefill_bucket
from repro.serve import Request, ServingEngine, SpecConfig


@pytest.fixture(scope="module")
def served():
    cfg = get_config("smollm-135m").reduced()
    m = Model(cfg)
    params, _ = m.init(jax.random.key(0))
    return cfg, m, params


def _workload(cfg, lens=(13, 5, 29, 8, 17), priorities=(0, 1, 0, 1, 1), tokens=6):
    reqs = []
    for i, (s0, pr) in enumerate(zip(lens, priorities)):
        prompt = np.random.default_rng(100 + i).integers(
            0, cfg.vocab_size, size=(s0,)
        ).astype(np.int32)
        reqs.append(
            Request(
                prompt=prompt, max_new_tokens=tokens,
                arrival_time=0.005 * i, priority=pr,
            )
        )
    return reqs


# ---------------------------------------------------------------------------
# pow2 bucketing


def test_prefill_bucket():
    assert [prefill_bucket(n) for n in (1, 2, 3, 4, 5, 8, 9, 16, 17)] == [
        1, 2, 4, 4, 8, 8, 16, 16, 32,
    ]
    assert prefill_bucket(9, cap=8) == 8  # chunk slices never exceed the budget
    assert prefill_bucket(3, cap=8) == 4


# ---------------------------------------------------------------------------
# token identity: chunked == monolithic


@pytest.mark.parametrize("layout", ["slot", "paged"])
def test_chunked_equals_monolithic(served, layout):
    """Chunking moves prefill work across steps, never tokens: an
    identical staggered mixed-priority workload decodes bitwise the
    same whether prompts prefill monolithically or in 8-token chunks."""
    cfg, m, params = served
    eng = ServingEngine(m, params, max_seq=128, kv_layout=layout, max_batch=3)
    mono_reqs = _workload(cfg)
    mono = eng.serve(mono_reqs, chunk_size=0)
    chunk_reqs = _workload(cfg)
    chunked = eng.serve(chunk_reqs, chunk_size=8)
    assert all(r.finished for r in mono_reqs + chunk_reqs)
    for a, b in zip(mono_reqs, chunk_reqs):
        np.testing.assert_array_equal(mono[a.rid], chunked[b.rid])


@pytest.mark.parametrize("layout", ["slot", "paged"])
def test_chunked_equals_monolithic_with_speculation(served, layout):
    """Chunked prefill composes with speculative decoding: draft
    streams catch up at install time, and the greedy stream is still
    bitwise the plain monolithic one."""
    cfg, m, params = served
    eng = ServingEngine(m, params, max_seq=128, kv_layout=layout, max_batch=3)
    mono_reqs = _workload(cfg)
    mono = eng.serve(mono_reqs, chunk_size=0, spec=SpecConfig(k=0))
    spec_reqs = _workload(cfg)
    spec = eng.serve(
        spec_reqs, chunk_size=8, spec=SpecConfig(k=4, drafter="ngram")
    )
    for a, b in zip(mono_reqs, spec_reqs):
        np.testing.assert_array_equal(mono[a.rid], spec[b.rid])


def test_chunked_prefix_reuse_token_identity(served):
    """Shared-prefix prompts through the chunked path: the second
    request seeds its chunk cache from the trie hit and still decodes
    identically to the monolithic engine (and actually hits)."""
    cfg, m, params = served
    header = np.random.default_rng(7).integers(0, cfg.vocab_size, size=(24,))
    def reqs():
        out = []
        for i in range(3):
            tail = np.random.default_rng(50 + i).integers(
                0, cfg.vocab_size, size=(6,)
            )
            out.append(Request(
                prompt=np.concatenate([header, tail]).astype(np.int32),
                max_new_tokens=4, arrival_time=0.05 * i,
            ))
        return out

    eng = ServingEngine(m, params, max_seq=96, kv_layout="paged",
                        block_size=8, max_batch=2)
    mono_reqs = reqs()
    mono = eng.serve(mono_reqs, chunk_size=0)
    chunk_reqs = reqs()
    chunked = eng.serve(chunk_reqs, chunk_size=8)
    for a, b in zip(mono_reqs, chunk_reqs):
        np.testing.assert_array_equal(mono[a.rid], chunked[b.rid])
    assert any(r.prefix_hit > 0 for r in chunk_reqs[1:])


# ---------------------------------------------------------------------------
# trace family: one trace per pow2 bucket, no retrace across positions


def test_prefill_chunk_no_retrace_across_positions(served):
    """Every chunk of a given bucket width reuses ONE trace no matter
    where in the prompt it lands — the chunk position rides in the
    cache's ``len`` (data), not in any shape."""
    cfg, m, params = served
    traces = []

    @jax.jit
    def chunk_fn(p, cache, toks, n):
        traces.append(1)
        return m.prefill_chunk(p, cache, toks, n)

    cache = m.init_cache(1, 64)
    prompt = np.random.default_rng(0).integers(0, cfg.vocab_size, size=(40,))
    pos = 0
    while pos < len(prompt):
        n = min(8, len(prompt) - pos)
        toks = np.zeros((1, 8), np.int32)
        toks[0, :n] = prompt[pos : pos + n]
        _, cache = chunk_fn(
            params, cache, jnp.asarray(toks), jnp.asarray([n], jnp.int32)
        )
        pos += n
    assert len(traces) == 1  # five chunks at five offsets, one trace
    assert int(cache["len"][0]) == len(prompt)


def test_monolithic_bucketed_prefill_no_retrace(served):
    """All prompt lengths inside one pow2 bucket share a single padded
    prefill trace; crossing a bucket boundary costs exactly one more."""
    cfg, m, params = served
    traces = []

    @jax.jit
    def prefill_fn(p, toks, n):
        traces.append(1)
        return m.prefill(p, toks, 64, prompt_len=n)

    for s0 in (9, 11, 14, 16):  # all bucket to W=16
        W = prefill_bucket(s0)
        assert W == 16
        toks = np.zeros((1, W), np.int32)
        toks[0, :s0] = np.arange(s0) % cfg.vocab_size
        _, cache = prefill_fn(
            params, jnp.asarray(toks), jnp.asarray([s0], jnp.int32)
        )
        assert int(cache["len"][0]) == s0  # pad rows never commit
    assert len(traces) == 1
    _ = prefill_fn(
        params, jnp.zeros((1, 32), jnp.int32), jnp.asarray([20], jnp.int32)
    )
    assert len(traces) == 2  # next bucket, one new trace


def test_bucketed_prefill_matches_exact(served):
    """Padded+masked prefill is the exact-shape prefill: the same greedy
    next token, the same committed length, and next-token logits and
    committed KV rows equal within float32 rounding. The padded call
    reduces over a longer masked axis, so XLA may order the sums
    differently; 1e-5 absolute on O(1) float32 logits is a few ulps of
    that reordering and far below any gap that flips the argmax."""
    cfg, m, params = served
    s0 = 11
    prompt = np.random.default_rng(3).integers(0, cfg.vocab_size, size=(1, s0))
    exact_logits, exact_cache = m.prefill(params, jnp.asarray(prompt), 64)
    W = prefill_bucket(s0)
    padded = np.zeros((1, W), np.int64)
    padded[:, :s0] = prompt
    pad_logits, pad_cache = m.prefill(
        params, jnp.asarray(padded), 64, prompt_len=jnp.asarray([s0])
    )
    exact_logits, pad_logits = np.asarray(exact_logits), np.asarray(pad_logits)
    assert exact_logits.dtype == np.float32
    np.testing.assert_array_equal(exact_logits.argmax(-1), pad_logits.argmax(-1))
    np.testing.assert_allclose(pad_logits, exact_logits, rtol=0, atol=1e-5)
    assert int(pad_cache["len"][0]) == int(exact_cache["len"][0]) == s0
    np.testing.assert_allclose(  # committed KV rows equal too
        np.asarray(pad_cache["k"][:, :, :s0]), np.asarray(exact_cache["k"][:, :, :s0]),
        rtol=0, atol=1e-5,
    )


# ---------------------------------------------------------------------------
# config gates


def test_chunk_size_must_be_pow2(served):
    cfg, m, params = served
    eng = ServingEngine(m, params, max_seq=64, max_batch=2)
    with pytest.raises(ValueError, match="power of two"):
        eng.serve([Request(prompt=np.arange(4), max_new_tokens=2)], chunk_size=6)


def test_chunked_rejects_unsupported_family(served):
    """``prefill_chunk`` is gated to families whose decode-cache path
    is pad-safe AND position-indifferent; an SSM hybrid is neither."""
    cfg, m, params = served
    ssm_cfg = dataclasses.replace(cfg, family="ssm")
    assert ssm_cfg.family not in CHUNKED_PREFILL_FAMILIES
    ssm = Model(ssm_cfg)
    with pytest.raises(ValueError, match="chunked prefill"):
        ssm.prefill_chunk(
            params, m.init_cache(1, 16), jnp.zeros((1, 4), jnp.int32),
            jnp.asarray([4], jnp.int32),
        )


def test_chunked_rejects_patch_embeds(served):
    """VLM patch embeddings ride the monolithic path only: submitting
    one to a chunked scheduler is refused (and counted)."""
    cfg, m, params = served
    eng = ServingEngine(m, params, max_seq=64, max_batch=2)
    sched = eng.scheduler(2, chunk_size=8)
    with pytest.raises(ValueError, match="chunk"):
        sched.submit(
            Request(
                prompt=np.arange(4), max_new_tokens=2,
                patch_embeds=np.zeros((2, cfg.d_model), np.float32),
            )
        )
    assert eng.stats.rejected_submissions == 1
