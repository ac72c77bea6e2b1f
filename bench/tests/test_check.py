"""The comparison that decides ``correct``, at a size a test can hold:
the program passes, the fp8 control fails, and so does each fault the
served path can have."""
import jax.numpy as jnp
import numpy as np
import pytest

from bench import compare, run, weights
from bench.blocks import llama
from bench.tests import tiny

# Tiny-size readings (CPU, seeds 1-6): the program's widest gap
# 0-0.024, the fp8 control's 0.148-0.389. The limit lies between.
LIMIT = 0.08


def one_run(break_program=None, seed=11):
    return run.run_cell(
        tiny.cell(LIMIT), tiny.conf(), tiny.mix(), seed=seed, seconds=1.5,
        trace=False, metrics=tiny.E2E, peaks=tiny.PEAKS, backend="interpret",
        break_program=break_program,
    )


def test_program_is_correct():
    out = one_run()
    assert out["correct"], out["checks"]
    assert out["checks"]["requests_checked"]["value"] >= 2
    assert set(out["metrics"]) == {"ttft_p90_ms", "tpot_p90_ms", "output_tokens_per_s", "setup_s"}


def test_control_fails_the_limit():
    cell, conf, mix = tiny.cell(LIMIT), tiny.conf(), tiny.mix()
    engine, sizes, block = run.build(cell, conf, 12, backend="interpret")
    ctx = run.Ctx(
        cell=cell, config=conf, mix=mix, sizes=sizes, peaks=tiny.PEAKS, block=block, seconds=1.5
    )
    sub = run.serve(engine, ctx, seed=12, trace=False, counter=run.CompileCounter())
    v = compare.judge(
        block.logits, engine.params, run.sample_of(cell, sub, 12), sizes=sizes, max_seq=64,
        window=16, control=True,
    )
    assert v["max_logit_gap"] <= LIMIT < v["control_max_logit_gap"]


def altered_token(sched):
    """Every sampled token moved to its neighbour in the vocabulary."""
    vocab = tiny.TINY_SIZES["vocab_size"]
    sched._sample_row = lambda logits, key: (jnp.argmax(logits, axis=-1) + 1) % vocab


def state_unchanged(sched):
    """The decode step hands back the pool it was given: no new K/V."""
    step = sched._decode_paged
    sched._decode_paged = lambda p, pool, *a: (step(p, pool, *a)[0], pool)


@pytest.mark.parametrize("fault", [altered_token, state_unchanged])
def test_fault_is_not_correct(fault):
    out = one_run(break_program=fault)
    assert not out["correct"]
    assert out["checks"]["max_logit_gap"]["value"] > LIMIT


def test_weights_from_large_seeds_differ():
    shapes = {"embed": jnp.zeros((8, 4), jnp.bfloat16)}
    sizes = {"d_model": 4, "vocab": 6, "d_ff": 8, "n_heads": 1, "head_dim": 4}
    a = np.asarray(weights.make(shapes, sizes, 2**31 + 5, llama)["embed"], np.float32)
    b = np.asarray(weights.make(shapes, sizes, 2**31 + 6, llama)["embed"], np.float32)
    assert not np.array_equal(a, b)
    assert np.all(a[6:] == 0)  # pad rows past the published vocabulary
