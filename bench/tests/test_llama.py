"""The llama block: operation and byte counts against counts made by hand,
and its weights and reference logits pinned bit for bit."""
import hashlib

import jax
import numpy as np

from bench import run, spec, weights
from bench.blocks import llama, stablelm
from bench.tests import tiny

SMOLLM = llama.sizes(spec.load_config("smollm-135m"))
STABLELM = stablelm.sizes(spec.load_config("stablelm-3b"))  # the llama block's counts

# Read from the tree before the block was its own module (bench/reference.py,
# bench/weights.py), on this tiny configuration: the same seed must give
# the same weights and the reference the same logits, to the bit.
WEIGHTS_SHA256 = "c1bd9d349e599fa1c69ed2b18ce7371843b97c9a97f211c2bbfecf13f7a24cbe"
LOGITS_SHA256 = "12e371617ba2ae3c1ab4822aa9bfe90003250312993647b994201986c0b5a1c5"
FP8_LOGITS_SHA256 = "a37099da0aea2f23b3cec14adfc2ebcae7f6ee589aa70b1614d5221fe60d722d"


def test_matmul_params_by_hand():
    # smollm: per layer q 576*576, k and v 576*192 each, o 576*576,
    # mlp 3*576*1536; 30 layers; head 576*49152
    layer = 576 * 576 + 2 * 576 * 192 + 576 * 576 + 3 * 576 * 1536
    assert layer == 3_538_944
    assert llama.matmul_params(SMOLLM) == 30 * 3_538_944 + 576 * 49152
    # stablelm: MHA, q k v o 2560*2560 each, mlp 3*2560*6912, untied head
    layer = 4 * 2560 * 2560 + 3 * 2560 * 6912
    assert llama.matmul_params(STABLELM) == 32 * layer + 2560 * 50304
    assert 2.6e9 < llama.matmul_params(STABLELM) < 2.7e9  # 2.8B with the embedding


def test_decode_flops_by_hand():
    # two live rows at 100 and 200 positions: 2*params each, plus
    # 4*H*hd*ctx per layer for q.k and p.v
    want = 2 * 2 * llama.matmul_params(SMOLLM) + 30 * 4 * 9 * 64 * (100 + 200)
    assert llama.decode_flops(SMOLLM, [100, 200]) == want
    assert llama.decode_flops(SMOLLM, []) == 0


def test_paged_attn_cost_by_hand():
    f, b = llama.paged_attn_cost(SMOLLM, [1000])
    assert f == 30 * 4 * 9 * 64 * 1000
    # K and V: 1000 positions * 3 kv heads * 64 * 2 bytes each; q and o
    # 9 * 64 * 2 bytes each; every layer
    assert b == 30 * (2 * 1000 * 3 * 64 * 2 + 2 * 9 * 64 * 2)
    f, b = llama.paged_attn_cost(STABLELM, [10, 20])
    assert f == 32 * 4 * 32 * 80 * 30
    assert b == 32 * (2 * 30 * 32 * 80 + 2 * 2 * 32 * 80) * 2
    # the kernel reads 2 bytes per 2 flops of each K and V row: bound by HBM
    assert f / b < 197e12 / 819e9


def _sha(arrays) -> str:
    h = hashlib.sha256()
    for a in arrays:
        h.update(np.ascontiguousarray(a).tobytes())
    return h.hexdigest()


def test_tiny_weights_and_reference_logits_are_pinned():
    from repro.models.model import Model

    conf = tiny.conf()
    pcfg, sizes, block = run.program_config(conf)
    assert block is llama
    params = weights.make(Model(pcfg).abstract_params()[0], sizes, 2**31 + 77, llama)
    assert _sha(np.asarray(w).view(np.uint16) for w in jax.tree.leaves(params)) == WEIGHTS_SHA256
    rng = np.random.default_rng(3)
    prompt = rng.integers(0, 256, size=21).astype(np.int32)
    served = rng.integers(0, 256, size=9).astype(np.int32)
    kw = dict(sizes=sizes, max_seq=64, window=16)
    assert _sha([llama.logits(params, prompt, served, **kw)]) == LOGITS_SHA256
    assert _sha([llama.logits(params, prompt, served, quant="fp8", **kw)]) == FP8_LOGITS_SHA256
