"""Latency-critical serving example on the continuous-batching core.

Two modes:

* fixed batch (default): ``generate()`` decodes a full batch through the
  slot-pool scheduler and prints per-step latency percentiles;
* open loop (``--open-loop N``): N requests with Poisson arrivals
  (``--rate`` req/s), random prompt lengths, and random token budgets
  are admitted into a ``--batch``-slot pool as slots free up — the
  continuous-batching path — and per-request TTFT percentiles are
  reported.

Either mode optionally runs with the int8 KV cache, with the block-
paged KV cache + prefix reuse (``--paged``, attention families), with
speculative decoding (``--spec K``: the n-gram prompt-lookup drafter
proposes K tokens per verify step; greedy token streams are unchanged
by construction, and the run reports the measured acceptance rate —
DESIGN.md §3.2), and optionally advised by Aira (``--aira`` exposes the
decode step as a Region, advises it, and routes decoding through the
accepted RegionPlan — masked over the active slots in open-loop mode;
slotted layout only, and mutually exclusive with ``--spec``).

``--backend`` picks the decode/verify attention backend (DESIGN.md §4):
``reference`` is the pure-jnp path (paged decode gathers a dense view),
``kernel`` the block-paged Pallas kernel compiled for TPU (attention
walks the block tables — no dense gather), ``interpret`` the same
kernel code interpreted on CPU (token-identical by the CI differential
contract), and ``auto`` (default) resolves per platform via the ops
registry (``REPRO_ATTENTION_BACKEND`` overrides).

``--online`` attaches the closed-loop ``OnlineAdviser`` in open-loop
mode (DESIGN.md §9): ``engine.prime()`` pre-jits and price-measures the
K × backend grid, the controller re-decides the speculation depth (and
admission budget under pool pressure) every few steps from the
telemetry windows, and the decision audit trail is printed when the run
finishes. Switching is retrace-free — every arm is a trace-cache hit
after priming — and token streams stay exactly greedy. Mutually
exclusive with ``--aira`` (both rewrite how the decode step is driven);
with ``--spec K`` the controller's candidate depths cap at K.

``--chunk N`` turns on chunked prefill in open-loop mode: at most N
prompt tokens of prefill are admitted per decode step, so a long
prompt's prefill interleaves with running decodes instead of stalling
them (DESIGN.md §3.3; token streams are unchanged by construction).

``--trace PATH`` arms the serving flight recorder (DESIGN.md §8)
before the engine is built and exports Chrome/Perfetto trace-event
JSON to PATH when the run finishes — request lifecycle spans, per-step
phase timings, and any adviser/backend events, loadable in
ui.perfetto.dev or chrome://tracing. Recording is observation only:
token streams are unchanged (the observability benchmark pins this).

``--mesh N`` serves through the tensor-parallel sharded path
(DESIGN.md §5): the paged pool's KV leaves are head-partitioned over an
N-way ``("model",)`` mesh and decode/verify run per-shard under
``shard_map`` — token streams are bitwise those of the single-device
paged path. Requires ``--paged`` and ``--open-loop`` (the mesh is wired
through ``engine.serve(mesh=)``); on a CPU host the script forces an
N-device host platform for you (that flag acts on the CPU platform
only; an accelerator host serves on its real devices). The first line
names the platform and device count the run resolved, so a chip run
can be told from a CPU one. Architectures whose
kv-head count the mesh does not divide fall back to replicated serving
with a logged warning.

  PYTHONPATH=src python examples/serve_decode.py [--arch zamba2-2.7b]
      [--int8-kv] [--paged] [--spec 4] [--tokens 32] [--batch 4]
      [--aira] [--open-loop 8] [--rate 20] [--backend interpret]
      [--chunk 16] [--mesh 2] [--online] [--trace serve_trace.json]
"""
import argparse
import dataclasses
import os
import sys

# --mesh on a CPU host needs the forced device count set BEFORE jax
# initializes, so peek at argv ahead of the jax import. The flag acts
# on the CPU host platform only; a TPU host ignores it and meshes its
# real chips
if "--mesh" in sys.argv[:-1]:
    _n = int(sys.argv[sys.argv.index("--mesh") + 1])
    _flags = os.environ.get("XLA_FLAGS", "")
    if _n > 1 and "xla_force_host_platform_device_count" not in _flags:
        os.environ["XLA_FLAGS"] = (
            _flags + f" --xla_force_host_platform_device_count={_n}"
        ).strip()

import jax
import numpy as np

from repro.configs import get_config
from repro.models import Model
from repro.serve import ServingEngine


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="zamba2-2.7b")
    ap.add_argument("--tokens", type=int, default=32)
    ap.add_argument("--batch", type=int, default=4,
                    help="fixed batch size / open-loop slot-pool size")
    ap.add_argument("--int8-kv", action="store_true")
    ap.add_argument("--paged", action="store_true",
                    help="block-paged KV cache with shared-prefix reuse")
    ap.add_argument("--spec", type=int, default=0, metavar="K",
                    help="speculative decoding: K n-gram draft tokens per verify "
                         "(0 = off; token streams stay exactly greedy)")
    ap.add_argument("--backend", default="auto",
                    choices=("auto", "reference", "kernel", "interpret"),
                    help="decode/verify attention backend (DESIGN.md §4): "
                         "the block-paged Pallas kernel ('kernel'/'interpret') "
                         "or the pure-jnp reference path")
    ap.add_argument("--aira", action="store_true",
                    help="advise the decode step and serve through its RegionPlan")
    ap.add_argument("--open-loop", type=int, default=0, metavar="N",
                    help="serve N Poisson-arrival requests instead of one fixed batch")
    ap.add_argument("--rate", type=float, default=20.0,
                    help="open-loop arrival rate (requests/second)")
    ap.add_argument("--chunk", type=int, default=0, metavar="N",
                    help="chunked prefill: admit at most N prompt tokens of "
                         "prefill per decode step (pow2; 0 = monolithic). "
                         "Long prompts stop stalling co-resident decodes "
                         "(DESIGN.md §3.3)")
    ap.add_argument("--mesh", type=int, default=0, metavar="N",
                    help="tensor-parallel serving: head-partition the paged "
                         "KV pool over an N-way ('model',) mesh and run "
                         "decode/verify per-shard (DESIGN.md §5; requires "
                         "--paged and --open-loop; token streams stay "
                         "bitwise single-device)")
    ap.add_argument("--online", action="store_true",
                    help="closed-loop serving: prime the K × backend grid, "
                         "attach the OnlineAdviser (live K/admission "
                         "re-decision from telemetry windows, retrace-free), "
                         "and print the decision audit trail (DESIGN.md §9; "
                         "requires --open-loop)")
    ap.add_argument("--trace", metavar="PATH", default=None,
                    help="arm the serving flight recorder and export "
                         "Chrome/Perfetto trace-event JSON to PATH "
                         "(DESIGN.md §8; load in ui.perfetto.dev)")
    args = ap.parse_args()
    print(
        f"platform={jax.default_backend()} devices={len(jax.devices())} "
        f"kind={jax.devices()[0].device_kind}"
    )

    if args.trace:
        from repro.serve.telemetry import configure

        # arm the module-global recorder before the engine is built so
        # the scheduler's cached metric handles are live for the run
        configure(enabled=True)

    cfg = get_config(args.arch).reduced()
    if args.int8_kv:
        cfg = dataclasses.replace(cfg, kv_quant=True)
    if args.spec and args.aira:
        raise SystemExit("--spec and --aira both rewrite the decode step; pick one")
    if args.online and args.aira:
        raise SystemExit("--online and --aira both re-decide the decode step; pick one")
    if args.online and not args.open_loop:
        raise SystemExit("--online rides the serve() path; add --open-loop N")
    mesh = None
    if args.mesh > 1:
        if not args.paged:
            raise SystemExit("--mesh shards the paged pool; add --paged")
        if not args.open_loop:
            raise SystemExit("--mesh rides the serve() path; add --open-loop N")
        if len(jax.devices()) < args.mesh:
            raise SystemExit(
                f"--mesh {args.mesh} needs {args.mesh} devices, have "
                f"{len(jax.devices())} (on CPU the script sets "
                f"xla_force_host_platform_device_count for you — is "
                f"XLA_FLAGS already pinning a smaller count?)"
            )
        mesh = jax.make_mesh(
            (args.mesh,), ("model",), axis_types=(jax.sharding.AxisType.Auto,)
        )
    model = Model(cfg)
    params, _ = model.init(jax.random.key(0))
    from repro.serve import SpecConfig

    engine = ServingEngine(
        model, params, max_seq=256,
        kv_layout="paged" if args.paged else "slot",
        spec=SpecConfig(k=args.spec, drafter="ngram") if args.spec else None,
        attention_backend=args.backend,
    )

    prompts = jax.random.randint(jax.random.key(1), (args.batch, 16), 0, cfg.vocab_size)

    if args.aira:
        from repro.core import Aira, Workload

        region = engine.decode_region(prompts, force=True)
        report = Aira().advise(Workload("serve-decode", lambda: None, [region]))
        print(report.render())
        d = report.decisions[0]
        if d.accepted:
            engine.set_decode_plan(d.plan)
            print("decode routed through RegionPlan:", d.plan.describe())

    print(
        f"arch={args.arch} int8_kv={args.int8_kv} paged={args.paged} "
        f"spec_k={args.spec} aira={args.aira} backend={engine.attention_backend}"
        + (f" mesh={args.mesh}" if mesh is not None else "")
    )
    if args.open_loop > 0:
        from repro.serve.load import make_requests

        reqs = make_requests(
            args.open_loop,
            args.rate,
            vocab=cfg.vocab_size,
            max_new_tokens=args.tokens,
            rng=np.random.default_rng(0),
        )
        controller = None
        if args.online:
            from repro.serve import OnlineAdviser

            # pre-jit + price-measure the candidate grid: every live
            # switch the controller makes is a trace-cache hit
            ks = (0, args.spec) if args.spec else (0, 2, 4)
            primed = engine.prime(args.batch, ks=ks)
            controller = OnlineAdviser(
                ks=primed["ks"], decision_interval=4, window=8, dwell=1,
            )
            controller.seed_costs(primed)
            cells = primed["cells"][engine.attention_backend]
            print(
                "primed: "
                + " ".join(f"K={k}:{ms:.2f}ms" for k, ms in sorted(cells.items()))
            )
        outputs = engine.serve(
            reqs, max_batch=args.batch, chunk_size=args.chunk, mesh=mesh,
            controller=controller,
        )
        for r in reqs:
            print(
                f"  req {r.rid}: arrive={r.arrival_time*1e3:7.1f}ms "
                f"prompt={len(np.asarray(r.prompt)):2d} tokens={len(r.tokens):2d} "
                f"ttft={r.ttft_ms:7.1f}ms e2e={r.e2e_ms:7.1f}ms"
            )
        assert all(len(outputs[r.rid]) == len(r.tokens) for r in reqs)
        print(f"open-loop serving: {engine.stats.summary()}")
        if controller is not None:
            info = engine.stats.serving_summary().get("controller", {})
            print(
                f"online adviser: {info.get('decisions', 0)} decisions, "
                f"{info.get('switches', 0)} switches, final K={info.get('k')} "
                f"backend={info.get('backend')}"
            )
            for d in controller.audit_trail():
                print(
                    f"  step {d['step']:>3}: k={d['k']}"
                    + (" [probe]" if d["probe"] else "")
                    + f" — {d['reason']}"
                )
    else:
        out = engine.generate(prompts, args.tokens)
        print(f"generated {out.shape} tokens; first row: {out[0][:12].tolist()}")
        print(f"decode latency: {engine.stats.summary()}")
    if args.spec:
        # absent when no verify round ever ran (e.g. every request
        # retired on its prefill token)
        s = engine.stats.serving_summary().get("speculative")
        if s is not None:
            print(
                f"speculative: K={s['k']} acceptance={s['acceptance_rate']:.2f} "
                f"({s['accepted']}/{s['proposed']} draft tokens; "
                f"draft p50={s['p50_draft_ms']:.2f}ms verify p50={s['p50_verify_ms']:.2f}ms)"
            )
    if args.trace:
        from repro.serve.telemetry import get_telemetry, validate_chrome_trace

        tracer = get_telemetry().tracer
        counts = validate_chrome_trace(tracer.export(args.trace))
        print(
            f"trace: {counts['events']} events ({counts['spans']} spans, "
            f"{counts['async_spans']} request spans) → {args.trace}"
        )


if __name__ == "__main__":
    main()
