"""2-stage GPipe over the 'pod' axis (inter-pod pipeline parallelism).

The multi-pod mesh's slow hop is pod↔pod: pure data parallelism pays a
full cross-pod gradient all-reduce per step, while pipeline parallelism
moves one activation handoff per microbatch through the slow link — the
standard placement at 1000+ nodes. This module stages a scanned layer
stack across the pod axis.

Schedule (2 stages, M microbatches, M+1 ticks):

  tick t : stage0 runs microbatch t (t < M);
           stage1 runs the activation received at tick t-1 (t ≥ 1);
           one collective_permute hands stage0's output forward.

Bubble fraction = 1/(M+1). The implementation is family-agnostic: it
wraps any ``layer_fn(stage_params, x) → x``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import PartitionSpec as P


def pipelined_apply(layer_fn, stage_params, x, *, mesh, n_micro: int, axis: str = "pod"):
    """Run a 2-stage pipeline over `axis`; returns layer_fn∘layer_fn (x).

    stage_params: stacked leaves [n_stages, ...], sharded over `axis`
                  (stage i's sub-stack at index i).
    x: [B, ...], microbatched along B into n_micro chunks.
    """
    n_stages = mesh.shape[axis]
    assert n_stages == 2, "demo pipeline is 2-stage (pod axis)"
    b = x.shape[0]
    assert b % n_micro == 0
    mb = b // n_micro

    def body(params_loc, x_loc):
        params_stage = jax.tree.map(lambda p: p[0], params_loc)
        stage = lax.axis_index(axis)
        micro = x_loc.reshape((n_micro, mb) + x_loc.shape[1:])
        fwd = [(0, 1)]  # stage0 → stage1 handoff

        def step(inflight, t):
            t_clamped = jnp.minimum(t, n_micro - 1)
            mb_t = lax.dynamic_index_in_dim(micro, t_clamped, 0, keepdims=False)
            x_in = jnp.where(stage == 0, mb_t, inflight)
            y = layer_fn(params_stage, x_in)
            nxt = lax.ppermute(y, axis, fwd)  # stage1's copy drops off ring
            return nxt, y

        init = jnp.zeros((mb,) + x_loc.shape[1:], x_loc.dtype)
        _, ys = lax.scan(step, init, jnp.arange(n_micro + 1))
        # on stage 1, ys[1:] are the finished microbatches; replicate back
        outs = ys[1:].reshape((b,) + x_loc.shape[1:])
        outs_from_1 = lax.ppermute(outs, axis, [(1, 0)])
        return jnp.where(stage == 1, outs, outs_from_1)

    fn = shard_map(
        body,
        mesh=mesh,
        in_specs=(P(axis), P()),
        out_specs=P(),
        check_vma=False,
    )
    return fn(stage_params, x)
