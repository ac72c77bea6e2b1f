"""Operation and byte counts against counts made by hand."""
from bench import flops, reference, spec

SMOLLM = reference.sizes_from_config(spec.load_config("smollm-135m"))
STABLELM = reference.sizes_from_config(spec.load_config("stablelm-3b"))


def test_matmul_params_by_hand():
    # smollm: per layer q 576*576, k and v 576*192 each, o 576*576,
    # mlp 3*576*1536; 30 layers; head 576*49152
    layer = 576 * 576 + 2 * 576 * 192 + 576 * 576 + 3 * 576 * 1536
    assert layer == 3_538_944
    assert flops.matmul_params(SMOLLM) == 30 * 3_538_944 + 576 * 49152
    # stablelm: MHA, q k v o 2560*2560 each, mlp 3*2560*6912, untied head
    layer = 4 * 2560 * 2560 + 3 * 2560 * 6912
    assert flops.matmul_params(STABLELM) == 32 * layer + 2560 * 50304
    assert 2.6e9 < flops.matmul_params(STABLELM) < 2.7e9  # 2.8B with the embedding


def test_decode_flops_by_hand():
    # two live rows at 100 and 200 positions: 2*params each, plus
    # 4*H*hd*ctx per layer for q.k and p.v
    want = 2 * 2 * flops.matmul_params(SMOLLM) + 30 * 4 * 9 * 64 * (100 + 200)
    assert flops.decode_flops(SMOLLM, [100, 200]) == want
    assert flops.decode_flops(SMOLLM, []) == 0


def test_paged_attn_cost_by_hand():
    f, b = flops.paged_attn_cost(SMOLLM, [1000])
    assert f == 30 * 4 * 9 * 64 * 1000
    # K and V: 1000 positions * 3 kv heads * 64 * 2 bytes each; q and o
    # 9 * 64 * 2 bytes each; every layer
    assert b == 30 * (2 * 1000 * 3 * 64 * 2 + 2 * 9 * 64 * 2)
    f, b = flops.paged_attn_cost(STABLELM, [10, 20])
    assert f == 32 * 4 * 32 * 80 * 30
    assert b == 32 * (2 * 30 * 32 * 80 + 2 * 2 * 32 * 80) * 2
    # the kernel reads 2 bytes per 2 flops of each K and V row: bound by HBM
    assert f / b < 197e12 / 819e9

