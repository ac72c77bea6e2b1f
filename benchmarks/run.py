"""Benchmark harness — one section per paper figure/table.

  Fig.1/Fig.2  granularity sweeps (PFL compute-bound, CC memory-bound)
  Fig.3/Fig.4  Aira end-to-end over the 10 latency-critical benchmarks
  §Roofline    per-(arch × shape) roofline terms from the dry-run
  µbench       CPU wall-clock of each benchmark's serial JAX kernel
               (``name,us_per_call,derived`` CSV)
  §Serving     open-loop Poisson-arrival load on the continuous-batching
               serving core (p50/p99 TTFT, per-token latency), plus the
               shared-prefix reuse-on/off TTFT comparison on the paged
               KV cache and the speculative-decode K-sweep (n-gram
               drafter vs the K=0 baseline, acceptance rate + advised
               depth)

Every run writes ``BENCH_aira.json`` — per-benchmark predicted/realized
gain plus the µbench wall-clock — so the perf trajectory is machine-
readable across PRs. ``--fast`` skips the restructured-vs-serial timing
comparison but still emits the summary (fewer µbench reps).

Usage: PYTHONPATH=src python -m benchmarks.run [--fast]
"""
from __future__ import annotations

import json
import sys
import time

import jax


def _microbench(print_fn=print, reps: int = 5) -> dict[str, float]:
    from repro.bench_suite import BENCHMARKS

    print_fn("# µbench — serial kernel wall-clock (CPU, one iteration)")
    print_fn("name,us_per_call,derived")
    out = {}
    for name, b in BENCHMARKS.items():
        data = b.build()
        f = jax.jit(b.serial_value)
        jax.block_until_ready(f(data))
        t0 = time.perf_counter()
        for _ in range(reps):
            jax.block_until_ready(f(data))
        us = (time.perf_counter() - t0) / reps * 1e6
        n = jax.tree.leaves(b.items(data))[0].shape[0]
        print_fn(f"{name},{us:.1f},items={n}")
        out[name] = us
    return out


def write_summary(rows, gm_pos, gm_all, ubench_us, serving=None, path="BENCH_aira.json") -> None:
    """Machine-readable per-PR perf summary (predicted gains are the
    calibrated overlap model; µbench is measured CPU wall-clock;
    ``serving`` is the open-loop load test's p50/p99 TTFT + per-token
    latency from benchmarks/serving_load.py, including the
    ``shared_prefix`` reuse-on/off comparison on the paged engine, the
    ``speculative`` K-sweep vs the K=0 greedy baseline, and the
    ``attention_backend`` sweep — p50 TPOT and per-step attention time
    per (KV layout × backend) plus the KernelAdvisorTool's measured
    backend decision — and the ``sharded`` mesh sweep's per-step
    latency at mesh sizes {1,2,4} under bitwise token identity, plus
    the ``online_adviser`` drift benchmark — closed-loop controller
    p50 TPOT vs every static K arm and the per-phase-best oracle)."""
    summary = {
        "benchmarks": [
            {
                "name": r["name"],
                "accepted": r["accepted"],
                "schedule": r["schedule"],
                "predicted_gain": r["predicted"],
                "realized_gain_model": r["realized"],
                # predicted-vs-realized sign gate (fig34_aira.flag_
                # regressions): accepted on a positive prediction but
                # realized negative — Fig. 4's forced rows carry it
                "regressed": r.get("regressed", False),
                "ubench_serial_us": ubench_us.get(r["name"]),
            }
            for r in rows
        ],
        "geomean_positive": gm_pos,
        "geomean_all_discard_negative": gm_all,
    }
    if serving is not None:
        summary["serving"] = serving
    with open(path, "w") as f:
        json.dump(summary, f, indent=2, default=float)
    print(f"wrote {path}")


def main() -> None:
    fast = "--fast" in sys.argv
    from benchmarks import fig12_granularity, fig34_aira, roofline, serving_load
    from repro import compile_cache

    compile_cache.enable()

    fig12_granularity.run()
    print()
    rows, gm_pos, gm_all = fig34_aira.run(timing=not fast)
    print()
    roofline.run()
    print()
    ubench_us = _microbench(reps=2 if fast else 5)
    print()
    serving = serving_load.run(
        n_requests=6 if fast else 12, tokens=4 if fast else 8
    )
    print()
    # not reduced under --fast: the reuse-on/off TTFT comparison needs
    # enough requests for stable percentiles, and runs in seconds anyway
    serving["shared_prefix"] = serving_load.run_shared_prefix()
    print()
    # likewise un-reduced: the K-sweep's token-identity and nonzero-
    # acceptance asserts are the tracked speculative-decode contract
    serving["speculative"] = serving_load.run_speculative()
    print()
    # attention-backend sweep: reference vs the block-paged kernel (in
    # interpret mode on CPU CI), token-identity asserted per layout,
    # advised backend from the measured per-step cost (DESIGN.md §4)
    serving["attention_backend"] = serving_load.run_backend_sweep()
    print()
    # SLO-attainment goodput under overload: chunked vs monolithic
    # prefill on the mixed-priority workload, preemption pressure on —
    # the chunked-p99-step and nonzero-goodput asserts are the tracked
    # scheduling contract (DESIGN.md §3.3)
    serving["slo"] = serving_load.run_slo(overload=True)
    print()
    # mesh-sharded paged decode at mesh sizes {1,2,4} on one workload:
    # bitwise token identity vs the single-device paged path (plain,
    # speculative, chunked) plus per-step latency per mesh size — the
    # tracked tensor-parallel serving contract (DESIGN.md §5). On a CPU
    # host with one device it runs in a forced multi-device CPU
    # subprocess (the normal CI case); an accelerator host runs it
    # in-process when it has the devices, and otherwise records why it
    # did not run — never a child that would need the parent's chip.
    need = serving_load.sharded_devices_needed()
    if jax.default_backend() == "cpu" or len(jax.devices()) >= need:
        serving["sharded"] = serving_load.run_sharded()
    else:
        serving["sharded"] = {
            "not_run": f"needs {need} devices, this "
                       f"{jax.default_backend()} host has {len(jax.devices())}"
        }
        print(f"§Serving sharded: not run — {serving['sharded']['not_run']}")
    print()
    # flight-recorder contract: telemetry-on serves the same workload
    # token-identically through the same warmed engine, the paired-rep
    # p50-step overhead stays under the pinned factor, and the exported
    # trace validates (DESIGN.md §8)
    serving["observability"] = serving_load.run_observability()
    print()
    # online adaptive adviser on the drifting-draftability workload:
    # the closed-loop controller must beat the worst static K arm's p50
    # TPOT, track the per-phase-best oracle within tolerance, and
    # switch retrace-free through the primed step grid (DESIGN.md §9)
    serving["online_adviser"] = serving_load.run_drift()
    write_summary(rows, gm_pos, gm_all, ubench_us, serving=serving)


if __name__ == "__main__":
    main()
