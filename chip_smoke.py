#!/usr/bin/env python3
"""Bring-up smoke test: serve smollm-135m at full width on a TPU.

Run from the root of a checkout, on a host with a TPU:

    python3 chip_smoke.py              # one chip
    python3 chip_smoke.py --chips 4    # the ("seq",)=4 sequence split only

One chip runs four phases through the entry points a user calls:

1. device: a TPU must be the default device; no CPU fallback.
2. serve: ``Model.init`` -> ``ServingEngine(kv_layout="paged")`` ->
   ``engine.serve`` on 8 open-loop requests with 64-512 token prompts,
   at the published widths in bfloat16, random weights from ``--seed``.
   The attention backend must resolve to the compiled Pallas kernel.
3. kernel vs reference: one paged decode step and one paged verify step
   on the same pool, through the kernel and through the reference
   attention, logits compared under a stated tolerance.
4. aira: one suite benchmark advised through ``repro.core``; its
   restructured (``relic_pfor``) result must equal the serial one.

``--chips 4`` serves the same requests on one chip and over a
four-device ``("seq",)`` mesh (paged pool split by blocks, partials
kernel under ``shard_map``, ``distributed_softmax`` combine), reports
which served streams are greedy-identical, and requires the mesh's
paged decode and verify logits to match one chip's under a stated
tolerance.

Every check that fails exits non-zero. The last line of standard output
is one JSON object naming the device; it is printed only when every
phase passed.
"""
from __future__ import annotations

import argparse
import json
import math
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "smollm-135m"
BLOCK_SIZE = 16
MAX_BATCH = 8
N_REQUESTS = 8
NEW_TOKENS = 16
PROMPT_LENS = (64, 128, 256, 512)
RATE_RPS = 20.0
VERIFY_K = 4  # the verify step scores K drafts + the pending token
BACKEND = "kernel"  # what the attention backend must resolve to on a TPU
# Row capacity: the longest prompt, its budget and a verify's overhang.
MAX_SEQ = BLOCK_SIZE * math.ceil(
    (max(PROMPT_LENS) + NEW_TOKENS + VERIFY_K) / BLOCK_SIZE
)
# Kernel and reference attention share the bf16 pool, weights and
# projections. They differ in where the scores are rounded: the
# reference rounds q·k to bf16 before its f32 softmax, the kernel keeps
# it f32. One bf16 rounding of an O(1) score is ~4e-3 relative; carried
# through 30 residual layers into O(1)-scale logits (tied embedding,
# RMS-normed input) that stays within a few hundredths. A wrong mask,
# block walk or kv-group mapping moves logits by O(1). The bound is on
# max |kernel - reference| over every logit, divided by max |reference|.
LOGIT_REL_TOL = 5e-2
# The ("seq",)=4 split runs the same kernel on each device's share of
# the blocks and merges the f32 partials; the merge reassociates the
# softmax sums and the sharded program rounds its bf16 intermediates
# at its own points, so logits move by a few bf16 ulps of the largest
# (a 2-layer bf16 rehearsal on 4 CPU devices read 1.3e-2). A shard
# that counts a block it does not own, or drops one it does, moved
# them by 0.35-1.3 in that rehearsal.
SEQ_SPLIT_REL_TOL = 5e-2
# The restructured Aira plan sums per-stream float32 partials, so the
# total is the serial one reassociated.
AIRA_RTOL = 1e-4


class SmokeFailure(SystemExit):
    def __init__(self, msg: str):
        super().__init__(f"chip_smoke: FAIL: {msg}")


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise SmokeFailure(msg)


def say(msg: str) -> None:
    print(msg, flush=True)


def device_phase(chips: int):
    import importlib.metadata as md

    import jax
    import jaxlib

    devices = jax.devices()
    platform = devices[0].platform
    check(
        platform == "tpu",
        f"no TPU: jax reports platform {platform!r} ({devices[0].device_kind}); "
        "this smoke test runs only on a TPU and never falls back",
    )
    check(
        len(devices) >= chips,
        f"--chips {chips} needs {chips} TPU devices, jax reports {len(devices)}",
    )
    try:
        libtpu = md.version("libtpu")
    except md.PackageNotFoundError:
        libtpu = "not installed as a package"
    say(
        f"device: platform={platform} kind={devices[0].device_kind} "
        f"count={len(devices)} jax={jax.__version__} jaxlib={jaxlib.__version__} "
        f"libtpu={libtpu}"
    )
    return devices


def say_model(cfg) -> None:
    say(
        f"model: {cfg.name} layers={cfg.num_layers} d_model={cfg.d_model} "
        f"heads={cfg.n_heads} kv_heads={cfg.n_kv_heads} head_dim={cfg.head_dim} "
        f"d_ff={cfg.d_ff} vocab={cfg.vocab_size} dtype={cfg.dtype}"
    )


def make_workload(vocab: int, seed: int):
    """A factory of fresh request lists (serving mutates requests): the
    same prompts, budgets and arrivals on every call."""
    import numpy as np

    from repro.serve.load import make_requests

    def fresh():
        return make_requests(
            N_REQUESTS, RATE_RPS, vocab=vocab, max_new_tokens=NEW_TOKENS,
            prompt_lens=PROMPT_LENS, rng=np.random.default_rng(seed),
        )

    return fresh


def pool_blocks(reqs) -> int:
    """Blocks the workload can hold at once: every prompt with the
    largest budget and a verify's overhang, in whole blocks."""
    return sum(
        math.ceil((len(r.prompt) + NEW_TOKENS + VERIFY_K) / BLOCK_SIZE)
        for r in reqs
    )


def build(seed: int, mesh=None, *, model=None, params=None):
    import jax

    from repro.configs import get_config
    from repro.models import Model
    from repro.serve import ServingEngine

    cfg = get_config(ARCH)
    if model is None:
        model = Model(cfg)
        params, _ = model.init(jax.random.key(seed))
    workload = make_workload(cfg.vocab_size, seed)
    engine = ServingEngine(
        model, params, max_seq=MAX_SEQ, kv_layout="paged",
        block_size=BLOCK_SIZE, num_blocks=pool_blocks(workload()),
        max_batch=MAX_BATCH, mesh=mesh,
    )
    return cfg, engine, workload


def serve_phase(engine, workload, seed: int):
    """Serve the workload twice through one engine (the first pass
    compiles every trace family); both passes must give the same
    tokens, every request its whole budget."""
    import numpy as np

    cfg = engine.model.cfg
    say_model(cfg)
    check(
        engine.attention_backend == BACKEND,
        f"attention_backend={engine.attention_backend!r}, expected {BACKEND!r}",
    )
    say(f"attention_backend={engine.attention_backend}")
    t0 = time.perf_counter()
    warm_reqs = workload()
    warm = engine.serve(warm_reqs, seed=seed)
    warm_s = time.perf_counter() - t0
    reqs = workload()
    t0 = time.perf_counter()
    out = engine.serve(reqs, seed=seed)
    serve_s = time.perf_counter() - t0
    streams = [np.asarray(out[r.rid]) for r in reqs]
    for r, w, s in zip(reqs, warm_reqs, streams):
        check(r.finished, f"request {r.rid} did not finish")
        check(
            len(s) == r.max_new_tokens,
            f"request {r.rid}: {len(s)} tokens for a budget of {r.max_new_tokens}",
        )
        check(
            bool(((s >= 0) & (s < cfg.vocab_size)).all()),
            f"request {r.rid}: token outside the vocabulary: {s}",
        )
        check(
            np.array_equal(np.asarray(warm[w.rid]), s),
            f"request {r.rid}: two serves of one workload differ: "
            f"{np.asarray(warm[w.rid])} vs {s}",
        )
    n_tok = sum(len(s) for s in streams)
    prompts = [len(r.prompt) for r in reqs]
    say(
        f"serve: requests={len(reqs)} prompt_lens={prompts} tokens={n_tok} "
        f"pool_blocks={engine.num_blocks} block_size={engine.block_size} "
        f"first_pass_with_compile_s={warm_s:.3f} second_pass_s={serve_s:.3f} "
        f"(set-up facts, not metrics)"
    )


def resident(engine, workload, seed: int):
    """Admit every request of ``workload`` (all arrive at 0) and return
    the paged step inputs over their prompts: pool, block tables,
    lengths, tokens, and each request's row, in request order.

    The admitting step also runs one decode, which writes each row's
    first sampled token at the prompt's end. The lengths are wound back
    over it, so the scored token (drawn from ``seed``, as are the verify
    drafts) takes that position: two engines then score the same tokens
    over the same prompts, whatever first token each drew."""
    import numpy as np

    from repro.serve import Request

    sched = engine.scheduler(MAX_BATCH, seed=seed)
    reqs = [Request(prompt=r.prompt, max_new_tokens=NEW_TOKENS) for r in workload()]
    for r in reqs:
        sched.submit(r)
    sched.step()
    for r in reqs:
        check(
            r.slot is not None and len(r.tokens) == 2,
            f"request {r.rid} not resident after one step (slot={r.slot}, "
            f"tokens={len(r.tokens)})",
        )
        sched.kv.ensure_tail_n(r.slot, VERIFY_K + 1)
    pool, tables, _ = sched.kv.kernel_inputs()
    rows = [r.slot for r in reqs]
    lens = np.array(sched.kv.cache_len, np.int32)
    lens[rows] -= 1
    draw = np.random.default_rng(seed + 1).integers(
        0, engine.model.cfg.vocab_size, size=(len(reqs), VERIFY_K + 1)
    )
    toks = np.zeros((MAX_BATCH, VERIFY_K + 1), np.int32)
    toks[rows] = draw
    return pool, tables, lens, toks, rows


def score(engine, backend: str, inputs):
    """Logits of one paged decode (T=1) and one paged verify (T=K+1)
    through ``engine``'s step family for ``backend`` (sharded over its
    mesh, if it has one), rows in request order. The steps take the
    pool donated, so each call is handed its own copy of ``inputs``'
    pool, which stays live for the next."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    pool, tables, lens, toks, rows = inputs
    model, mesh = engine.model, engine.mesh
    out = []
    for name, tok in (("decode_step_paged", toks[:, :1]), ("verify_step_paged", toks)):
        step = (
            model.jit_step(name, backend) if mesh is None
            else model.sharded_paged_step(name, mesh, backend)
        )
        logits, _ = step(engine.params, jax.tree.map(jnp.copy, pool), tables, lens, tok)
        out.append(np.asarray(logits, np.float32)[rows])
    return out


def compare(label: str, got, want, tol: float) -> None:
    """Fail unless max |got - want| over every logit, divided by
    max |want|, is within ``tol``; print it and the greedy agreement."""
    import numpy as np

    worst = 0.0
    for name, k, r in zip(("decode", "verify"), got, want):
        check(bool(np.isfinite(k).all()), f"{label} {name}: non-finite logits")
        check(bool(np.isfinite(r).all()), f"{label} {name}: non-finite reference logits")
        diff = float(np.abs(k - r).max())
        scale = float(np.abs(r).max())
        agree = float((k.argmax(-1) == r.argmax(-1)).mean())
        say(
            f"{label}: {name} T={k.shape[1] if k.ndim == 3 else 1} "
            f"logits{tuple(k.shape)}: max|diff|={diff:.6g} max|ref|={scale:.6g} "
            f"rel={diff / scale:.6g} (tol {tol}) greedy_agreement={agree:.4f}"
        )
        worst = max(worst, diff / scale)
    check(worst <= tol, f"{label}: logits differ by {worst:.4g} relative > tolerance {tol}")


def backends_phase(engine, workload, seed: int):
    """One paged decode and one paged verify over the same pool, kernel
    attention against reference attention."""
    inputs = resident(engine, workload, seed)
    compare(
        "kernel vs reference",
        score(engine, BACKEND, inputs),
        score(engine, "reference", inputs),
        LOGIT_REL_TOL,
    )


def aira_phase(name: str = "GeoSpatial"):
    import numpy as np

    from repro.bench_suite import BENCHMARKS
    from repro.core import advise_suite

    b = BENCHMARKS[name]
    entry = advise_suite(benchmarks={name: b})[name]
    d = entry.decision
    want = np.asarray(b.serial_value(entry.data, combine=b.combine))
    got = np.asarray(b.parallel_value(entry.data, combine=b.combine))
    check(got.shape == want.shape, f"aira {name}: shape {got.shape} vs {want.shape}")
    err = float(np.abs(got - want).max())
    bound = AIRA_RTOL * max(1.0, float(np.abs(want).max()))
    check(err <= bound, f"aira {name}: restructured differs from serial by {err}")
    if entry.accepted:
        plan_out = np.asarray(d.parallel_fn())
        perr = float(np.abs(plan_out - want).max())
        check(perr <= bound, f"aira {name}: accepted plan differs by {perr}")
    say(
        f"aira: {name} accepted={entry.accepted} "
        f"schedule={d.schedule.describe() if entry.accepted else 'none'} "
        f"max|restructured-serial|={err:.6g} (bound {bound:.3g})"
    )


def four_chip_phase(seed: int, devices):
    """The same workload on one chip and over a ("seq",)=4 mesh.

    The mesh must be kept and the pool split by blocks over four
    devices, and one paged decode and one paged verify must give the
    one-chip logits within ``SEQ_SPLIT_REL_TOL``. The served token
    streams are compared and reported but not required equal: with
    random weights a row's top two logits can sit closer than the
    split's rounding, and one flipped token changes the rest of its
    stream."""
    import jax
    import numpy as np

    cfg, one, workload = build(seed)
    say_model(cfg)
    mesh = jax.make_mesh(
        (4,), ("seq",), axis_types=(jax.sharding.AxisType.Auto,),
        devices=devices[:4],
    )
    _, eng, _ = build(seed, mesh, model=one.model, params=one.params)
    check(eng.mesh is not None, "the ('seq',) mesh was dropped: serving replicated")
    check(
        eng.attention_backend == BACKEND,
        f"attention_backend={eng.attention_backend!r} on the mesh, "
        f"expected {BACKEND!r}",
    )
    ref_reqs = workload()
    ref = one.serve(ref_reqs, seed=seed)
    sched = eng.scheduler(MAX_BATCH, seed=seed)
    reqs = workload()
    t0 = time.perf_counter()
    out = sched.run(reqs)
    wall = time.perf_counter() - t0
    for name in ("k", "v"):
        leaf = sched.kv.pool[name]
        devs = {s.device for s in leaf.addressable_shards}
        blocks = {s.data.shape[1] for s in leaf.addressable_shards}
        check(
            len(devs) == 4,
            f"pool leaf {name!r} has shards on {len(devs)} device(s), expected 4",
        )
        check(
            blocks == {leaf.shape[1] // 4},
            f"pool leaf {name!r} is not split by blocks: shard block dims "
            f"{blocks} of {leaf.shape[1]}",
        )
    say(
        f"mesh: axes={dict(eng.mesh.shape)} pool k{tuple(leaf.shape)} "
        f"on {len(devs)} devices, {leaf.shape[1] // 4} blocks each; "
        f"attention_backend={eng.attention_backend}"
    )
    n_same = 0
    for a, b in zip(ref_reqs, reqs):
        x, y = np.asarray(ref[a.rid]), np.asarray(out[b.rid])
        check(
            b.finished and len(y) == b.max_new_tokens,
            f"seq=4 request {b.rid}: {len(y)} tokens for a budget of {b.max_new_tokens}",
        )
        if np.array_equal(x, y):
            n_same += 1
        else:
            first = int(np.argmax(x != y))
            say(
                f"  request {b.rid}: first differs at token {first} of {len(x)}: "
                f"one chip {x.tolist()} vs seq=4 {y.tolist()}"
            )
    say(
        f"served: {n_same}/{len(reqs)} requests greedy-identical to one chip, "
        f"tokens={sum(len(out[r.rid]) for r in reqs)} "
        f"seq4_serve_with_compile_s={wall:.3f} (set-up fact, not a metric)"
    )
    compare(
        "seq=4 vs one chip",
        score(eng, BACKEND, resident(eng, workload, seed)),
        score(one, BACKEND, resident(one, workload, seed)),
        SEQ_SPLIT_REL_TOL,
    )


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    from repro import compile_cache

    say(f"compile cache: {compile_cache.enable()}")
    devices = device_phase(args.chips)
    if args.chips == 4:
        four_chip_phase(args.seed, devices)
    else:
        _, engine, workload = build(args.seed)
        serve_phase(engine, workload, args.seed)
        backends_phase(engine, workload, args.seed)
        aira_phase()
    d = devices[0]
    print(json.dumps({
        "ok": True,
        "device": {"platform": d.platform, "kind": d.device_kind, "count": len(devices)},
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
