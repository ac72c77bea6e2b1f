"""jit'd public wrappers for the Pallas kernels + the backend registry.

Each wrapper validates shapes, checks the VMEM working-set budget implied
by the chosen block shapes (double-buffered operands + scratch must fit),
and dispatches kernel vs. pure-jnp reference:

  on TPU            → the Pallas kernel (compiled by Mosaic)
  on CPU, testing   → the kernel in interpret mode (correctness)
  on CPU, dry-run   → the jnp reference (so SPMD partitioning & the
                      roofline read clean HLO; see DESIGN.md §2)

Dispatch is resolved ONCE (DESIGN.md §4): the generic kernel wrappers
resolve their default ``mode`` from ``REPRO_KERNEL_MODE`` + the
platform on first use, and the serving-attention wrappers resolve the
*attention backend* (``"reference" | "kernel" | "interpret"``) from
``REPRO_ATTENTION_BACKEND`` / ``set_attention_backend()`` the same way
— both log the resolution once and fail loudly, listing the valid
choices, on a bad override. Per-call ``mode=``/``backend=`` arguments
always win over the resolved default.
"""
from __future__ import annotations

import functools
import logging
import os
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import ref as ref_ops
from repro.kernels.decode_attention import decode_attention as _decode_kernel
from repro.kernels.flash_attention import flash_attention as _flash_kernel
from repro.kernels.paged_decode_attention import (
    paged_decode_attention as _paged_kernel,
)
from repro.kernels.relic_matmul import relic_gemv, relic_matmul
from repro.kernels.ssd_scan import ssd_scan as _ssd_kernel

VMEM_BYTES = 16 * 2**20  # v5e per-core VMEM budget

log = logging.getLogger("repro.kernels")


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _flight_note(event: str, counter: str, **args) -> None:
    """Mirror a dispatch resolution onto the serving flight recorder
    (DESIGN.md §8) as a counter + instant event on the backend lane.
    Looks the telemetry module up in ``sys.modules`` rather than
    importing it: enabling telemetry requires importing it, so a
    never-imported module means the recorder is off — and the kernel
    layer never pulls ``repro.serve`` in on its own."""
    import sys

    mod = sys.modules.get("repro.serve.telemetry")
    if mod is None:
        return
    tel = mod.get_telemetry()
    if not tel.enabled:
        return
    tel.count(counter)
    tel.tracer.instant(event, "backend", tid=mod.TID_BACKEND, args=args or None)


# ---------------------------------------------------------------------------
# dispatch resolution — once per process, not per call

KERNEL_MODES = ("ref", "kernel", "interpret")
_DEFAULT_MODE: Optional[str] = None  # resolved lazily, cached


def default_kernel_mode() -> str:
    """The ``mode="auto"`` resolution for the generic kernel wrappers:
    ``REPRO_KERNEL_MODE`` if set (bad values fail loudly), else
    ``"kernel"`` on TPU and ``"ref"`` elsewhere. Resolved and logged
    once — callers no longer re-check ``jax.default_backend()`` per
    call."""
    global _DEFAULT_MODE
    if _DEFAULT_MODE is None:
        raw = os.environ.get("REPRO_KERNEL_MODE", "auto")
        if raw not in KERNEL_MODES + ("auto",):
            raise ValueError(
                f"REPRO_KERNEL_MODE={raw!r} is not a valid kernel mode; "
                f"choose one of {('auto',) + KERNEL_MODES}"
            )
        _DEFAULT_MODE = ("kernel" if _on_tpu() else "ref") if raw == "auto" else raw
        log.info(
            "kernel mode resolved once: %s (REPRO_KERNEL_MODE=%s, platform=%s)",
            _DEFAULT_MODE, raw, jax.default_backend(),
        )
        _flight_note(
            "kernel-mode-resolved", "backend.resolutions",
            resolved=_DEFAULT_MODE, source=raw,
        )
    return _DEFAULT_MODE


ATTENTION_BACKENDS = ("reference", "kernel", "interpret")
_ATTN_BACKEND: Optional[str] = None  # resolved lazily, cached


def _validate_backend(name: str, source: str) -> str:
    if name not in ATTENTION_BACKENDS + ("auto",):
        raise ValueError(
            f"{source}={name!r} is not a valid attention backend; "
            f"choose one of {('auto',) + ATTENTION_BACKENDS}"
        )
    return ("kernel" if _on_tpu() else "reference") if name == "auto" else name


def set_attention_backend(name: Optional[str]) -> None:
    """Config-time override of the process-default attention backend
    (``None``/``"auto"`` restores env/platform resolution on next use).
    Jitted step families bind the backend statically at build time (the
    serving engine resolves through here before jitting), so changing
    the default never silently retargets an existing trace."""
    global _ATTN_BACKEND
    if name is not None:
        _validate_backend(name, "backend")  # fail loudly even for "auto"
    _ATTN_BACKEND = None if name in (None, "auto") else name


def resolve_attention_backend(
    backend: Optional[str] = None, mesh=None
) -> str:
    """Per-call override → config override → ``REPRO_ATTENTION_BACKEND``
    → platform default (``"kernel"`` on TPU, ``"reference"`` elsewhere).
    An explicit ``"auto"`` defers to the same default chain as ``None``
    (so the env override is never silently bypassed). Resolution happens
    once and is logged once; bad names fail loudly with the valid
    choices.

    ``mesh`` makes the resolution mesh-aware for sharded serving
    (DESIGN.md §5): under ``shard_map`` the paged kernel runs per-shard
    on local heads, so "kernel" composes with a mesh instead of falling
    back to reference — but un-lowered Pallas cannot run on host
    devices, so on a non-TPU mesh "kernel" resolves to "interpret"
    (the same kernel code, interpreted). TPU meshes keep "kernel"."""
    if backend is not None and backend != "auto":
        resolved = _validate_backend(backend, "backend")
    else:
        global _ATTN_BACKEND
        if _ATTN_BACKEND is None:
            raw = os.environ.get("REPRO_ATTENTION_BACKEND", "auto")
            _ATTN_BACKEND = _validate_backend(raw, "REPRO_ATTENTION_BACKEND")
            log.info(
                "attention backend resolved once: %s (REPRO_ATTENTION_BACKEND=%s, "
                "platform=%s)",
                _ATTN_BACKEND, raw, jax.default_backend(),
            )
            _flight_note(
                "attention-backend-resolved", "backend.resolutions",
                resolved=_ATTN_BACKEND, source=raw,
            )
        resolved = _ATTN_BACKEND
    if mesh is not None and resolved == "kernel" and not _on_tpu():
        log.info(
            "attention backend 'kernel' on a %s mesh → 'interpret' "
            "(Pallas runs per-shard; host devices interpret it)",
            jax.default_backend(),
        )
        _flight_note(
            "attention-backend-fallback", "backend.fallbacks",
            wanted="kernel", resolved="interpret",
            platform=jax.default_backend(),
        )
        return "interpret"
    return resolved


def vmem_working_set(block_bytes: dict[str, int], buffering: int = 2) -> int:
    """Bytes of VMEM a block schedule claims (double-buffered operands)."""
    return sum(buffering * b for b in block_bytes.values())


def check_vmem(block_bytes: dict[str, int], buffering: int = 2) -> None:
    ws = vmem_working_set(block_bytes, buffering)
    if ws > VMEM_BYTES:
        raise ValueError(
            f"block schedule needs {ws/2**20:.1f} MiB VMEM > {VMEM_BYTES/2**20:.0f} MiB: {block_bytes}"
        )


# ---------------------------------------------------------------------------


def _paged_blocks(q, k_pool, v_pool, k_scale) -> dict[str, int]:
    """VMEM bytes per buffer of the paged kernel's block schedule: one
    whole pool block ``[BS, KV, hd]`` per K/V step (every kv group), the
    ``[KV, T·g, hd]`` query block, output block (counted at f32, its
    partials-mode dtype) and f32 accumulator, and the per-group
    ``[T·g, BS]`` score tile."""
    T, H, hd = q.shape[1], q.shape[2], q.shape[3]
    BS, KV = k_pool.shape[1], k_pool.shape[2]
    rows = T * (H // KV)
    blocks = {
        "q": KV * rows * hd * jnp.dtype(q.dtype).itemsize,
        "o": KV * rows * hd * 4,
        "k": BS * KV * hd * jnp.dtype(k_pool.dtype).itemsize,
        "v": BS * KV * hd * jnp.dtype(v_pool.dtype).itemsize,
        "acc": KV * rows * hd * 4,
        "s": rows * BS * 4,
    }
    if k_scale is not None:
        blocks["scales"] = 2 * BS * KV * jnp.dtype(k_scale.dtype).itemsize
    return blocks


@functools.partial(jax.jit, static_argnames=("bm", "bk", "bn", "mode"))
def matmul(x, w, *, bm=256, bk=512, bn=256, mode="auto"):
    """Double-buffered block matmul (Relic pair-scheduling on one core)."""
    if mode == "auto":
        mode = default_kernel_mode()
    if mode == "ref":
        return ref_ops.matmul_ref(x, w)
    itemsize = jnp.dtype(x.dtype).itemsize
    check_vmem(
        {
            "x": bm * bk * itemsize,
            "w": bk * bn * itemsize,
            "o": bm * bn * itemsize,
            "acc": bm * bn * 4,
        }
    )
    return relic_matmul(x, w, bm=bm, bk=bk, bn=bn, interpret=mode == "interpret")


@functools.partial(jax.jit, static_argnames=("bk", "bn", "mode"))
def gemv(x, w, *, bk=1024, bn=512, mode="auto"):
    if mode == "auto":
        mode = default_kernel_mode()
    if mode == "ref":
        return ref_ops.matmul_ref(x, w)
    return relic_gemv(x, w, bk=bk, bn=bn, interpret=mode == "interpret")


@functools.partial(jax.jit, static_argnames=("causal", "bq", "bk", "mode"))
def flash_attention(q, k, v, *, causal=True, bq=256, bk=512, mode="auto"):
    if mode == "auto":
        mode = default_kernel_mode()
    if mode == "ref":
        return ref_ops.attention_ref(q, k, v, causal=causal)
    g = q.shape[2] // k.shape[2]
    hd = q.shape[3]
    itemsize = jnp.dtype(q.dtype).itemsize
    check_vmem(
        {
            "q": g * bq * hd * itemsize,
            "k": bk * hd * itemsize,
            "v": bk * hd * itemsize,
            "acc": g * bq * hd * 4,
            "s": g * bq * bk * 4,
        }
    )
    return _flash_kernel(q, k, v, causal=causal, bq=bq, bk=bk, interpret=mode == "interpret")


@functools.partial(jax.jit, static_argnames=("bk", "mode"))
def decode_attention(q, k_cache, v_cache, cache_len, *, bk=512, mode="auto"):
    if mode == "auto":
        mode = default_kernel_mode()
    if mode == "ref":
        return ref_ops.decode_attention_ref(q, k_cache, v_cache, cache_len)
    return _decode_kernel(q, k_cache, v_cache, cache_len, bk=bk, interpret=mode == "interpret")


def paged_attention(
    q, k_pool, v_pool, block_tables, lengths, k_scale=None, v_scale=None, *, mode="auto"
):
    """Block-paged decode/verify attention straight off the block pool.

    q [B,T,H,hd] (T static: 1 = decode, K+1 = speculative verify);
    pools [NB,BS,KV,hd]; ``block_tables`` [B,MB] physical block ids per
    decode row; ``lengths`` [B] committed lengths (query t attends
    positions < lengths + t + 1). int8 pools pass per-vector
    ``k_scale``/``v_scale`` [NB,BS,KV] and dequantize in-kernel. The
    kernel walks the (scalar-prefetched) tables — no dense
    ``gather_block_rows`` materialization; ``"ref"``/``"reference"`` is
    the dense-gather oracle the differential tests compare against.
    ``mode="auto"`` resolves through the ATTENTION registry
    (``REPRO_ATTENTION_BACKEND``/``set_attention_backend``), not the
    generic ``REPRO_KERNEL_MODE`` — this is the serving-attention
    surface. Resolution happens here, OUTSIDE the jit boundary, so a
    later registry change is honored on the next call rather than
    silently replaying the first trace; bad modes fail loudly."""
    if mode == "ref":
        mode = "reference"  # the sibling wrappers' kernel-mode spelling
    mode = resolve_attention_backend(mode)  # validates; auto → the chain
    if mode == "reference":
        mode = "ref"
    return _paged_attention_impl(
        q, k_pool, v_pool, block_tables, lengths, k_scale, v_scale, mode=mode
    )


def paged_attention_partials(
    q, k_pool, v_pool, block_tables, lengths, owned,
    k_scale=None, v_scale=None, *, mode="auto",
):
    """Partials-emitting sibling of ``paged_attention`` for the
    kv-sequence-split serving path: same inputs on a LOCAL pool shard
    plus ``owned`` [B, MB] block ownership, returning the unnormalized
    flash triple ``(m, l, acc)`` for ``collectives.distributed_softmax``
    to combine across the seq mesh axis. Kernel-backend only — the
    reference partials live in ``models/attention.paged_flash_partials``
    (this wrapper is reached with the registry resolved to a kernel
    mode). Called inside shard_map bodies, so unlike ``paged_attention``
    there is no jit wrapper of its own — the enclosing step is the jit
    boundary."""
    if mode == "ref":
        mode = "reference"
    mode = resolve_attention_backend(mode)
    if mode == "reference":
        raise ValueError(
            "paged_attention_partials is the kernel-backend surface; the "
            "reference partials are models/attention.paged_flash_partials"
        )
    check_vmem(_paged_blocks(q, k_pool, v_pool, k_scale))
    return _paged_kernel(
        q, k_pool, v_pool, block_tables, lengths,
        k_scale=k_scale, v_scale=v_scale, owned=owned, partials=True,
        interpret=mode == "interpret",
    )


@functools.partial(jax.jit, static_argnames=("mode",))
def _paged_attention_impl(
    q, k_pool, v_pool, block_tables, lengths, k_scale, v_scale, *, mode
):
    if mode == "ref":
        return ref_ops.paged_attention_ref(
            q, k_pool, v_pool, block_tables, lengths, k_scale, v_scale
        )
    check_vmem(_paged_blocks(q, k_pool, v_pool, k_scale))
    return _paged_kernel(
        q, k_pool, v_pool, block_tables, lengths,
        k_scale=k_scale, v_scale=v_scale, interpret=mode == "interpret",
    )


@functools.partial(jax.jit, static_argnames=("chunk", "mode"))
def ssd(xh, a, b, c, dt, *, chunk=128, mode="auto"):
    if mode == "auto":
        mode = default_kernel_mode()
    if mode == "ref":
        return ref_ops.ssd_ref(xh, a, b, c, dt)
    N, hd = b.shape[-1], xh.shape[-1]
    itemsize = jnp.dtype(xh.dtype).itemsize
    check_vmem(
        {
            "x": chunk * hd * itemsize,
            "b": chunk * N * itemsize,
            "c": chunk * N * itemsize,
            "att": chunk * chunk * 4,
            "state": N * hd * 4,
        }
    )
    return _ssd_kernel(xh, a, b, c, dt, chunk=chunk, interpret=mode == "interpret")
