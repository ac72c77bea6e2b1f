"""relic_matmul — the Relic SMT-pair analogue on one TensorCore.

The paper's Relic runtime co-schedules a *memory-bound* and a *compute-
bound* microtask stream onto the two hardware threads of one SMT core.
The TPU-native translation: a Pallas grid pipeline in which the DMA engine
(HBM→VMEM block prefetch, the "memory thread") runs concurrently with the
MXU contraction on the previously fetched block (the "compute thread").
Pallas double-buffers each BlockSpec'd operand across sequential grid
steps, so grid step k computes x[i,k]·w[k,j] while k+1's blocks stream in
— exactly the paired-stream structure of Relic, with the block shape as
the task granularity (the paper's Figs. 1–2 sweep; see
core/overlap_model.py for the granularity band this implies).

Block shapes are MXU-aligned (multiples of 128 on contraction/lane dims)
and sized so 2 in-flight copies of each operand block + the fp32
accumulator fit VMEM (~16 MB budget is checked in ops.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _matmul_kernel(x_ref, w_ref, o_ref, acc_ref):
    # "compute thread": contract the block the DMA stream fetched last step
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        x_ref[...], w_ref[...], preferred_element_type=jnp.float32
    )

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def relic_matmul(
    x: jax.Array,
    w: jax.Array,
    *,
    bm: int = 256,
    bk: int = 512,
    bn: int = 256,
    interpret: bool = False,
) -> jax.Array:
    """x [M,K] @ w [K,N] with explicit double-buffered block pipeline."""
    M, K = x.shape
    K2, N = w.shape
    assert K == K2, (x.shape, w.shape)
    bm, bk, bn = min(bm, M), min(bk, K), min(bn, N)
    assert M % bm == 0 and K % bk == 0 and N % bn == 0, (x.shape, w.shape, (bm, bk, bn))
    grid = (M // bm, N // bn, K // bk)
    return pl.pallas_call(
        _matmul_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(x, w)


def _gemv_kernel(x_ref, w_ref, o_ref, acc_ref):
    """Decode GEMV: tall-skinny activation block × weight panel.

    The memory stream (weight panels, the dominant bytes at batch≲8) hides
    behind the MXU stream — the latency-critical decode case of the paper.
    """
    @pl.when(pl.program_id(1) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        x_ref[...], w_ref[...], preferred_element_type=jnp.float32
    )

    @pl.when(pl.program_id(1) == pl.num_programs(1) - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def relic_gemv(
    x: jax.Array, w: jax.Array, *, bk: int = 1024, bn: int = 512, interpret: bool = False
) -> jax.Array:
    """x [B,K] @ w [K,N] for small B (decode): grid streams weight panels."""
    B, K = x.shape
    K2, N = w.shape
    bk, bn = min(bk, K), min(bn, N)
    assert K % bk == 0 and N % bn == 0
    grid = (N // bn, K // bk)
    return pl.pallas_call(
        _gemv_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((B, bk), lambda j, k: (0, k)),
            pl.BlockSpec((bk, bn), lambda j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((B, bn), lambda j, k: (0, j)),
        out_shape=jax.ShapeDtypeStruct((B, N), x.dtype),
        scratch_shapes=[pltpu.VMEM((B, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        interpret=interpret,
    )(x, w)
