"""Compile-only checks of the paged attention kernel for a TPU v5e.

The TPU compiler is installed even where no chip is attached, and it
compiles for a described topology. Interpret-mode tests cannot see
Mosaic's tiling rules; these can. Each case lowers
``paged_decode_attention`` at smollm-135m's serving widths (9 query
heads, 3 kv heads, head_dim 64, bf16) for one v5e chip and asserts the
kernel reached the program as a ``tpu_custom_call`` under its stable
name, ``paged_decode_attention``. Nothing runs.

The topology is described inside a module fixture, never at import:
only one process may load the TPU library, and every test worker
imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels.paged_decode_attention import paged_decode_attention

H, KV, HD = 9, 3, 64  # smollm-135m
B, NB, MB = 8, 321, 40


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

    pool_dt = jnp.int8 if int8 else jnp.bfloat16
    args = [
        sds((B, t, H, HD), jnp.bfloat16),
        sds((NB, bs, KV, HD), pool_dt),
        sds((NB, bs, KV, HD), pool_dt),
        sds((B, MB), jnp.int32),
        sds((B,), jnp.int32),
    ]
    scales = [sds((NB, bs, KV), jnp.bfloat16)] * 2 if int8 else [None, None]
    owned = [sds((B, MB), jnp.int32)] if partials else []

    def f(q, k, v, tables, lens, ks, vs, *own):
        return paged_decode_attention(
            q, k, v, tables, lens, k_scale=ks, v_scale=vs,
            owned=own[0] if own else None, partials=partials,
        )

    compiled = jax.jit(f).lower(*args, *scales, *owned).compile()
    kernels = [ln for ln in compiled.as_text().splitlines() if "tpu_custom_call" in ln]
    assert kernels
    # the kernel keeps its stable name in the compiled program (and so
    # on the device's op line of a profile)
    assert all(ln.lstrip().startswith("%paged_decode_attention") for ln in kernels), kernels
