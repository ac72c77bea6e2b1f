"""SSD (Mamba2) chunked scan as a Pallas TPU kernel.

One program instance owns one (batch, head) pair; the chunk axis is the
sequential grid dim, carrying the [hd, N] state in VMEM scratch. Within a
chunk the recurrence is the quadratic SSD contraction (MXU work); between
chunks only the state survives — the DMA stream prefetches the next
chunk's x/B/C blocks while the MXU processes the current one (the same
Relic pair-scheduling as relic_matmul, applied to a recurrence).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(x_ref, a_ref, b_ref, c_ref, dt_ref, y_ref, state_ref, *, Q):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    x = x_ref[0, 0, 0].astype(jnp.float32)  # [Q, hd]
    a = a_ref[0, 0, 0].astype(jnp.float32)  # [Q, 1] decay per step
    b = b_ref[0, 0].astype(jnp.float32)  # [Q, N]
    c = c_ref[0, 0].astype(jnp.float32)  # [Q, N]
    dt = dt_ref[0, 0, 0].astype(jnp.float32)  # [Q, 1]

    la = jnp.cumsum(jnp.log(jnp.maximum(a, 1e-20)), axis=0)  # [Q,1]
    # intra-chunk causal quadratic: att[i,j] = (c_i·b_j)·exp(la_i-la_j)·dt_j
    seg = jnp.exp(jnp.clip(la - la.T, -60.0, 0.0))  # [Q,Q]
    cb = jax.lax.dot_general(
        c, b, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    causal = (
        jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
        >= jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    )
    att = jnp.where(causal, cb * seg * dt.T, 0.0)
    y = jax.lax.dot_general(
        att, x, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    # inter-chunk: y_i += exp(la_i) · c_i · S_prev
    s_prev = state_ref[...]  # [N, hd]
    y += jnp.exp(la) * jax.lax.dot_general(
        c, s_prev, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    # state update: S' = exp(la_end)·S + Σ_j (b_j·dt_j·exp(la_end-la_j)) ⊗ x_j
    w = dt * jnp.exp(jnp.clip(la[-1:] - la, -60.0, 0.0))  # [Q,1]
    state_ref[...] = jnp.exp(la[-1]) * s_prev + jax.lax.dot_general(
        b * w, x, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )  # [N, hd]
    y_ref[0, 0, 0] = y.astype(y_ref.dtype)


def ssd_scan(
    xh: jax.Array,
    a: jax.Array,
    b: jax.Array,
    c: jax.Array,
    dt: jax.Array,
    *,
    chunk: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """xh [B,S,H,hd]; a,dt [B,S,H]; b,c [B,S,N] → y [B,S,H,hd]."""
    B, S, H, hd = xh.shape
    N = b.shape[-1]
    Q = min(chunk, S)
    assert S % Q == 0
    nc = S // Q

    xr = xh.transpose(0, 2, 1, 3).reshape(B, H, nc, Q, hd)
    ar = a.transpose(0, 2, 1).reshape(B, H, nc, Q, 1)
    dtr = dt.transpose(0, 2, 1).reshape(B, H, nc, Q, 1)
    br = b.reshape(B, nc, Q, N)
    cr = c.reshape(B, nc, Q, N)

    grid = (B, H, nc)
    out = pl.pallas_call(
        functools.partial(_ssd_kernel, Q=Q),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, 1, Q, hd), lambda ib, ih, ic: (ib, ih, ic, 0, 0)),
            pl.BlockSpec((1, 1, 1, Q, 1), lambda ib, ih, ic: (ib, ih, ic, 0, 0)),
            pl.BlockSpec((1, 1, Q, N), lambda ib, ih, ic: (ib, ic, 0, 0)),
            pl.BlockSpec((1, 1, Q, N), lambda ib, ih, ic: (ib, ic, 0, 0)),
            pl.BlockSpec((1, 1, 1, Q, 1), lambda ib, ih, ic: (ib, ih, ic, 0, 0)),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, 1, Q, hd), lambda ib, ih, ic: (ib, ih, ic, 0, 0)
        ),
        out_shape=jax.ShapeDtypeStruct((B, H, nc, Q, hd), xh.dtype),
        scratch_shapes=[pltpu.VMEM((N, hd), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(xr, ar, br, cr, dtr)
    return out.reshape(B, H, S, hd).transpose(0, 2, 1, 3)
