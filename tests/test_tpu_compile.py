"""Compile-only checks of the paged attention kernel and the paged
decode/verify programs for a TPU v5e.

The TPU compiler is installed even where no chip is attached, and it
compiles for a described topology. Interpret-mode tests cannot see
Mosaic's tiling rules or the layouts XLA assigns; these can. Each
kernel case lowers ``paged_decode_attention`` at smollm-135m's serving
widths (9 query heads, 3 kv heads, head_dim 64, bf16) on the pool as it
is stored for one v5e chip and asserts the kernel reached the program
as a ``tpu_custom_call`` under its stable name,
``paged_decode_attention``. The program cases compile smollm-135m's
whole paged decode and verify steps and assert the pool goes through
them in place. Nothing runs.

The topology is described inside a module fixture, never at import:
only one process may load the TPU library, and every test worker
imports this file.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest

from repro.kernels.paged_decode_attention import paged_decode_attention
from repro.models.attention import tokens_per_row

H, KV, HD = 9, 3, 64  # smollm-135m
B, NB, MB, L = 8, 321, 40, 30


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.mark.parametrize(
    "t,int8,partials,bs",
    [
        (1, False, False, 16),  # decode
        (5, False, False, 16),  # speculative verify, K=4
        (1, True, False, 16),  # int8 pool, in-kernel dequantization
        (5, True, False, 8),  # int8 verify at the smallest block
        (1, False, True, 16),  # sequence-split partials
        (5, True, True, 8),  # partials of an int8 verify
    ],
    ids=["decode", "verify", "int8", "int8-verify-bs8", "partials", "partials-int8-verify"],
)
def test_paged_kernel_compiles_for_v5e(one_chip, t, int8, partials, bs):
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    p = tokens_per_row(bs, HD)  # 2 at block 16: [8, 128] tiles; 1 at block 8
    pool_dt = jnp.int8 if int8 else jnp.bfloat16
    stored = (L, NB, KV, bs // p, p * HD)
    args = [
        sds((B, t, H, HD), jnp.bfloat16),
        sds(stored, pool_dt),
        sds(stored, pool_dt),
        sds((B, MB), jnp.int32),
        sds((B,), jnp.int32),
        sds((), jnp.int32),
    ]
    scales = [sds((L, NB, KV, bs // p, p), jnp.bfloat16)] * 2 if int8 else [None, None]
    owned = [sds((B, MB), jnp.int32)] if partials else []

    def f(q, k, v, tables, lens, layer, ks, vs, *own):
        return paged_decode_attention(
            q, k, v, tables, lens, layer=layer, k_scale=ks, v_scale=vs,
            owned=own[0] if own else None, partials=partials,
        )

    compiled = jax.jit(f).lower(*args, *scales, *owned).compile()
    kernels = [ln for ln in compiled.as_text().splitlines() if "tpu_custom_call" in ln]
    assert kernels
    # the kernel keeps its stable name in the compiled program (and so
    # on the device's op line of a profile)
    assert all(ln.lstrip().startswith("%paged_decode_attention") for ln in kernels), kernels


_DTYPE_BYTES = {"bf16": 2, "f32": 4, "s32": 4, "s8": 1, "u8": 1, "u32": 4, "pred": 1}
_OP = re.compile(r"\s*(?:ROOT )?%([\w.\-]+) = ((\w+)\[([\d,]*)\]\S*) ([\w\-]+)\(")
_MOVES = ("copy", "dynamic-slice", "transpose")
_WRITES = ("scatter", "dynamic-update-slice")


def _is(op: str, name: str, kinds: tuple) -> bool:
    return op in kinds or (op == "fusion" and name.startswith(kinds))


def pool_moves(text: str, layer_bytes: int, pool_types: set, exempt: set) -> list[str]:
    """The ops of an optimized HLO module that move pool bytes around:
    a copy, dynamic slice or transpose (or a fusion named for one) whose
    output is as large as one layer of a pool leaf, and a scatter or
    dynamic-update-slice as large whose output is not in a pool
    parameter's type (shape and layout), that is, one that writes a
    relaid-out pool rather than the donated buffer in place.
    ``exempt``: sorted shapes of other arrays at least as large (the
    tied embedding, whose transpose feeds the head)."""
    found = []
    for ln in text.splitlines():
        m = _OP.match(ln)
        if not m:
            continue
        name, ty, dt, dims, op = m.groups()
        shape = [int(d) for d in dims.split(",") if d]
        n = 1
        for d in shape:
            n *= d
        if n * _DTYPE_BYTES.get(dt, 4) < layer_bytes or tuple(sorted(shape)) in exempt:
            continue
        if _is(op, name, _MOVES) or (_is(op, name, _WRITES) and ty not in pool_types):
            found.append(ln.strip()[:160])
    return found


@pytest.mark.parametrize("name,t", [("decode_step_paged", 1), ("verify_step_paged", 5)],
                         ids=["decode", "verify"])
def test_paged_step_keeps_pool_in_place(one_chip, name, t):
    """smollm-135m's paged decode and verify programs, compiled for a
    v5e at the chat cell's rows and block size (and a smaller pool),
    take the pool as it is stored and hand it back in the same buffer:
    no copy, dynamic slice or transpose as large as a layer of the pool
    (the relayouts to and from the kernel's layout, and the per-layer
    slice of the stack), and each pool leaf aliased to an output."""
    from repro.configs import get_config
    from repro.models.model import Model

    rows, max_seq, bs, nb = 64, 2048, 16, 1031
    m = Model(get_config("smollm-135m"))

    def sds(tree):
        return jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip), tree
        )

    pool = sds(jax.eval_shape(lambda: m.init_paged_cache(nb, bs)))
    assert pool["k"].shape == (L, nb, KV, 8, 128)  # tile-exact: two tokens a row
    ints = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)
    text = m.jit_step(name, "kernel").lower(
        sds(m.abstract_params()[0]), pool, ints(rows, max_seq // bs), ints(rows), ints(rows, t)
    ).compile().as_text()
    layer_bytes = pool["k"].size * pool["k"].dtype.itemsize // L
    pool_params = re.findall(r"%args_1___\w+?__[.\d]* = (\S+) parameter\((\d+)\)", text)
    embed = tuple(sorted(m.abstract_params()[0]["embed"].shape))
    assert pool_moves(text, layer_bytes, {ty for ty, _ in pool_params}, {embed}) == []
    header = text.split("\n", 1)[0]
    aliased = {int(i) for i in re.findall(r"\(\s*(\d+), \{\}, (?:may|must)-alias\)", header)}
    assert len(pool_params) == 2 and {int(i) for _, i in pool_params} <= aliased, (
        pool_params, header[:300]
    )
