"""Random weights made on the device from the seed, in one jitted call.

The benchmark makes the weights, not the program: the program is handed
them as it would be handed a checkpoint, and the plain reference reads
the same arrays. The tree has the layout the program's ``Model.init``
returns (its shapes are read from ``jax.eval_shape``; nothing is
computed by the program). Each leaf is drawn in the type it is served
in, by the configuration's block's rule (``ONES``, ``fan_in``): ones, or
N(0, fan_in^-0.5); the vocabulary's pad rows are zeros.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def seed_key(seed: int):
    """A PRNG key from a seed of any size, wider than 32 bits too."""
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def _path_name(path) -> str:
    return "/".join(str(getattr(p, "key", p)) for p in path)


def make(shapes, sizes: dict, seed: int, block):
    """Fill the tree of ``jax.ShapeDtypeStruct`` ``shapes`` from ``seed``
    by ``block``'s weight rule.

    ``sizes`` is the block's, with ``vocab`` (the published vocabulary:
    rows past it are pad)."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    names = [_path_name(p) for p, _ in flat]
    structs = [s for _, s in flat]

    def build(key):
        out = []
        for i, (name, s) in enumerate(zip(names, structs)):
            leaf = name.rsplit("/", 1)[-1]
            if leaf in block.ONES:
                out.append(jnp.ones(s.shape, s.dtype))
                continue
            k = jax.random.fold_in(key, i)
            w = jax.random.normal(k, s.shape, s.dtype)
            w = w * jnp.asarray(block.fan_in(name, sizes) ** -0.5, s.dtype)
            if leaf == "embed":
                rows = jnp.arange(s.shape[0])[:, None] < sizes["vocab"]
                w = jnp.where(rows, w, jnp.zeros((), s.dtype))
            elif leaf == "lm_head":
                cols = jnp.arange(s.shape[1])[None, :] < sizes["vocab"]
                w = jnp.where(cols, w, jnp.zeros((), s.dtype))
            out.append(w)
        return out

    leaves = jax.jit(build)(seed_key(seed))
    return jax.tree_util.tree_unflatten(treedef, leaves)
