"""The StableLM block (``StableLmForCausalLM``, as stablelm-3b-4e1t
publishes it): the llama block's decoder with two departures, written
out in its plain reference.

- Every norm is LayerNorm with a bias: ``(x - mean) / sqrt(var + eps)``
  times the scale plus the bias. The weights hold no bias leaf, so the
  bias is 0, as the published initialisation sets it; the scales are 1.
- Rotary positions turn only the first ``partial_rotary_factor`` of each
  head (20 of 80 dimensions), by the same half-split rotation over those
  dimensions; the rest of the head passes through.

The program's dense family can state neither (its config has no norm
kind and no rotary fraction), so ``program_fields`` asks only for what
it can state, and the reference is what holds the program to the
published block. The weight rule and the counts are the llama block's:
the rotation and the norms are no matmul.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from bench.blocks import llama
from bench.blocks.llama import ONES, decode_flops, fan_in, paged_attn_cost  # noqa: F401


def sizes(conf: dict) -> dict:
    sz = llama.sizes(conf)
    sz["rotary_dims"] = int(sz["head_dim"] * conf["partial_rotary_factor"])
    return sz


def program_fields(sz: dict, conf: dict) -> dict:
    return llama.program_fields(sz, conf)


def layer_norm(x, scale, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * scale


def partial_rope(x, pos, sz):
    r = sz["rotary_dims"]
    return jnp.concatenate([llama.rope(x[..., :r], pos, sz), x[..., r:]], axis=-1)


logits = functools.partial(llama.logits, norm=layer_norm, rope=partial_rope)
