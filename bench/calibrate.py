"""Readings a cell's settings are made from, on the chip, in one process.

    python3 -m bench.calibrate <cell> sweep --rates 1 2 3 --seconds 20 --seed N
    python3 -m bench.calibrate <cell> gaps --seeds N1 N2 ... --seconds 10

Run by hand, not by the benchmark. Both set up the cell once and keep
its compiled programs for every window they serve.

``sweep`` offers the cell's traffic at each rate in turn, on a fresh
scheduler each time, and prints one line per rate: requests due and
finished, the backlog (due but not yet admitted) at the middle and at
the close of the window, latencies and tokens per second. The knee is
the highest rate whose backlog does not grow over the window; a cell's
``rate_rps`` is set below it.

``gaps`` serves a window per seed, with that seed's weights and
traffic, and prints the widest logit gap of what the program served
(against the reference of the configuration's block) beside the
control's: the fp8 reference's gap at the same positions. A cell's ``limits`` are set from
these readings.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time

import numpy as np

from bench import compare, run, spec, weights


def backlog_at(recs, t: float) -> int:
    return sum(r["arrival"] <= t and (r["admit"] is None or r["admit"] > t) for r in recs)


def sweep_line(rate, ctx) -> dict:
    recs = ctx.records
    ttft = [(r["first"] - r["arrival"]) * 1e3 for r in recs if r["first"] is not None]
    tpot = [
        (r["end"] - r["first"]) / (r["tokens"] - 1) * 1e3
        for r in recs if r["first"] is not None and r["tokens"] >= 2
    ]
    live = [len(c) for _, _, c in ctx.decode_calls]
    pct = lambda v, q: float(np.percentile(v, q)) if v else None  # noqa: E731
    return {
        "rate_rps": rate,
        "due": len(recs),
        "finished": sum(r["finished"] for r in recs),
        "backlog_mid": backlog_at(recs, ctx.seconds / 2),
        "backlog_close": backlog_at(recs, ctx.elapsed),
        "ttft_p50_ms": pct(ttft, 50),
        "ttft_p90_ms": pct(ttft, 90),
        "tpot_p50_ms": pct(tpot, 50),
        "tpot_p90_ms": pct(tpot, 90),
        "tokens_per_s": ctx.tokens_in_window / ctx.elapsed,
        "step_ms_mean": float(np.mean(ctx.step_ms)) if ctx.step_ms else None,
        "live_rows_mean": float(np.mean(live)) if live else None,
        "drain_s": ctx.drain_end - ctx.elapsed,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("cell")
    ap.add_argument("what", choices=("sweep", "gaps"))
    ap.add_argument("--rates", type=float, nargs="*", default=[])
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--drain", type=float, default=None)
    args = ap.parse_args(argv)

    cell = spec.load_cell(args.cell)
    if args.drain is not None:
        cell["drain_s"] = args.drain
    conf = spec.load_config(cell["config"])
    mix = spec.load_mix(cell["traffic"])
    dev = run.check_device(int(cell["chips"]))
    peaks = spec.load_peaks(dev.device_kind)
    run.use_compile_cache()
    seeds = args.seeds or [args.seed]
    engine, sizes, block = run.build(cell, conf, seeds[0])
    counter = run.CompileCounter()

    def window(seed, rate=None):
        ctx = run.Ctx(
            cell=cell, config=conf, mix=mix, sizes=sizes, peaks=peaks, block=block,
            seconds=args.seconds,
        )
        ctx.backlog = mix["kind"] == "backlog"
        counter.seen.clear()
        submitted = run.serve(engine, ctx, seed=seed, rate=rate, trace=False, counter=counter)
        gc.collect()
        return ctx, submitted

    if args.what == "sweep":
        for rate in args.rates:
            t = time.perf_counter()
            ctx, _ = window(args.seed, rate)
            line = sweep_line(rate, ctx)
            line["compiles"] = len(ctx.window_compiles)
            line["wall_s"] = time.perf_counter() - t
            print("SWEEP " + json.dumps(line), flush=True)
        return 0

    for i, seed in enumerate(seeds):
        if i:
            engine.params = None
            gc.collect()
            engine.params = weights.make(engine.model.abstract_params()[0], sizes, seed, block)
        ctx, submitted = window(seed)
        sample = run.sample_of(cell, submitted, seed)
        del submitted
        gc.collect()
        t = time.perf_counter()
        v = compare.judge(
            block.logits, engine.params, sample, sizes=sizes, max_seq=int(cell["max_seq"]),
            window=int(mix["output"]["hi"]), control=True,
        )
        v.update(
            seed=seed, requests=len(sample), judge_s=time.perf_counter() - t,
            compiles=len(ctx.window_compiles), due=len(ctx.records),
        )
        print("GAPS " + json.dumps(v), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
