"""The stablelm block: LayerNorm and rotary over a share of each head,
and a reference that holds the program to them."""
import jax
import numpy as np
import pytest

from bench import run, spec
from bench.blocks import llama, stablelm
from bench.tests import tiny
from bench.tests.test_check import LIMIT


def test_layer_norm_centres_each_row():
    x = jax.random.normal(jax.random.key(0), (5, 64)) * 3.0 + 0.7
    y = np.asarray(stablelm.layer_norm(x, 1.0, 1e-5))
    np.testing.assert_allclose(y.mean(-1), 0.0, atol=1e-5)
    np.testing.assert_allclose(y.var(-1), 1.0, rtol=1e-3)
    np.testing.assert_allclose(y, np.asarray(stablelm.layer_norm(x + 5.0, 1.0, 1e-5)), atol=1e-4)
    centred = x - x.mean(-1, keepdims=True)
    np.testing.assert_allclose(
        np.asarray(stablelm.layer_norm(centred, 2.0, 1e-5)),
        np.asarray(llama.rms_norm(centred, 2.0, 1e-5)), rtol=1e-5, atol=1e-5,
    )


def test_partial_rope_turns_only_its_share():
    conf = spec.load_config("stablelm-3b")
    sz = stablelm.sizes(conf)
    assert sz["rotary_dims"] == 20 and sz["head_dim"] == 80
    x = jax.random.normal(jax.random.key(1), (7, 2, 80))
    pos = np.arange(7) + 100
    y = np.asarray(stablelm.partial_rope(x, pos, sz))
    np.testing.assert_array_equal(y[..., 20:], np.asarray(x)[..., 20:])
    np.testing.assert_allclose(y[..., :20], np.asarray(llama.rope(x[..., :20], pos, sz)))
    assert not np.allclose(y[..., :20], np.asarray(x)[..., :20])
    whole = dict(sz, rotary_dims=80)
    np.testing.assert_allclose(
        np.asarray(stablelm.partial_rope(x, pos, whole)), np.asarray(llama.rope(x, pos, sz))
    )


@pytest.mark.parametrize("block, correct", [("llama", True), ("stablelm", False)])
def test_reference_holds_the_program_to_its_block(block, correct):
    """The program's dense family computes the llama block. Served on the
    tiny cell and judged by each block's reference, it passes the llama
    reference and fails the stablelm one: a configuration that names the
    stablelm block cannot be correct on this program."""
    conf = dict(tiny.conf(), block=block, partial_rotary_factor=0.25)
    out = run.run_cell(
        tiny.cell(LIMIT), conf, tiny.mix(), seed=21, seconds=1.5, trace=False,
        metrics=tiny.E2E, peaks=tiny.PEAKS, backend="interpret",
    )
    assert out["correct"] is correct, out["checks"]
    if not correct:
        assert out["checks"]["max_logit_gap"]["value"] > 3 * LIMIT
