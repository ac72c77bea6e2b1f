"""Jitted train step: loss → grads → (optional accumulation) → AdamW.

Gradient accumulation is a ``lax.scan`` over microbatches; the optional
cross-pod int8-compressed gradient reduction (parallel/compression.py)
replaces the pod-axis portion of the all-reduce on the slow link.
"""
from __future__ import annotations

import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.parallel.compression import compressed_psum
from repro.train.optimizer import AdamW, OptState


def make_train_step(
    model,
    opt: AdamW,
    *,
    accum_steps: int = 1,
    compress_pod_grads: bool = False,
    zero2_axes=None,
):
    """Returns step(params, opt_state, batch[, err]) → (params, opt_state,
    metrics[, err]). batch leaves have leading [accum, micro...] when
    accum_steps > 1.

    zero2_axes: the params' logical-axes tree. When set (FSDP configs),
    parameters are sharding-constrained to the TP layout ONCE at step
    entry — XLA hoists a single all-gather out of the accumulation loop
    and transposes it to one reduce-scatter of the gradients (ZeRO-2),
    instead of re-gathering every microbatch and remat segment.
    """
    gather_once = None
    if zero2_axes is not None and model.rules is not None:
        tp_rules = model.rules.tp_view()

        def gather_once(params):
            return jax.tree.map(
                lambda p, ax: tp_rules.constrain(p, ax),
                params,
                zero2_axes,
                is_leaf=lambda x: isinstance(x, tuple),
            )

    def loss_fn(params, batch):
        loss, metrics = model.loss(params, batch)
        return loss, metrics

    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

    def compute_grads_plain(params, batch):
        if accum_steps == 1:
            (loss, metrics), grads = grad_fn(params, batch)
            return loss, metrics, grads

        def micro(carry, mb):
            acc_loss, acc_grads = carry
            (loss, metrics), grads = grad_fn(params, mb)
            acc_grads = jax.tree.map(jnp.add, acc_grads, grads)
            return (acc_loss + loss, acc_grads), metrics

        zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, p.dtype), params)
        (loss, grads), metrics = jax.lax.scan(micro, (0.0, zeros), batch)
        inv = 1.0 / accum_steps
        return (
            loss * inv,
            jax.tree.map(lambda m: m[-1], metrics),
            jax.tree.map(lambda g: g * inv, grads),
        )

    def compute_grads_zero2(params, batch):
        """ZeRO-2: differentiate through the whole accumulation with the
        parameters gathered ONCE at entry. The gather's autodiff transpose
        is a single gradient reduce-scatter at the end; the micro body is
        checkpointed so activations stay bounded."""
        if accum_steps == 1:
            def total1(p):
                return loss_fn(gather_once(p), batch)

            (loss, metrics), grads = jax.value_and_grad(total1, has_aux=True)(params)
            return loss, metrics, grads

        def total(p):
            pg = gather_once(p)

            @functools.partial(
                jax.checkpoint, policy=jax.checkpoint_policies.nothing_saveable
            )
            def micro(mb):
                return loss_fn(pg, mb)

            def body(acc, mb):
                l, m = micro(mb)
                return acc + l, m

            tot, metrics = jax.lax.scan(body, 0.0, batch)
            return tot / accum_steps, jax.tree.map(lambda m: m[-1], metrics)

        (loss, metrics), grads = jax.value_and_grad(total, has_aux=True)(params)
        return loss, metrics, grads

    compute_grads = compute_grads_zero2 if gather_once is not None else compute_grads_plain

    def step(params, opt_state: OptState, batch):
        loss, metrics, grads = compute_grads(params, batch)
        params, opt_state, opt_metrics = opt.update(grads, opt_state, params)
        metrics = dict(metrics, loss=loss, **opt_metrics)
        return params, opt_state, metrics

    def step_compressed(params, opt_state: OptState, batch, err):
        """Variant for multi-pod meshes: batch is sharded over
        ('pod','data'); the pod-axis share of the gradient reduction is
        int8-compressed with error feedback."""
        mesh = model.rules.mesh

        loss, metrics, grads = compute_grads(params, batch)

        def pod_reduce(g, e):
            def body(gl, el):
                return compressed_psum(gl, "pod", el)

            # grads are already averaged over the full batch by autodiff;
            # XLA's all-reduce includes the pod axis. To show the slow-link
            # compression explicitly we re-reduce the pod axis on the
            # per-pod partial gradients instead.
            return shard_map(
                body,
                mesh=mesh,
                in_specs=(P(), P()),
                out_specs=(P(), P()),
                check_vma=False,
            )(g, e)

        outs = jax.tree.map(pod_reduce, grads, err)
        grads = jax.tree.map(lambda t: t[0], outs, is_leaf=lambda t: isinstance(t, tuple))
        err = jax.tree.map(lambda t: t[1], outs, is_leaf=lambda t: isinstance(t, tuple))
        params, opt_state, opt_metrics = opt.update(grads, opt_state, params)
        metrics = dict(metrics, loss=loss, **opt_metrics)
        return params, opt_state, metrics, err

    return step_compressed if compress_pod_grads else step
