"""Run one benchmark cell on the chip and print its result line.

    python3 -m bench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout. One process: set up (weights made on the
device from the seed, the engine, every program the cell's traffic
reaches compiled or read from the compile cache at
``<checkout>/.jax_cache``), measure a window of ``--seconds`` in which
requests arrive open-loop on the harness's clock, let the requests due
in the window finish (``drain_s`` at most, rate cells only), read the
device's peak memory, free the program's state, and judge what it
served against the plain reference of the configuration's block
(``blocks/<block>.py``, found by ``spec.load_block``).

With ``--trace 0`` the result's metrics are the cell's end-to-end
metrics from ``BENCHMARK.json``; with ``--trace 1`` its per-layer
metrics, read from a profiler trace of part of the window, from the
program's counters and from the harness's spans. Each metric is
``read(ctx)`` in ``metrics/<name>.py``.

The last line of standard output is one JSON object; the last lines of
standard error give each number compared beside its limit. A run that
finds no TPU, or fewer chips than the cell asks for, or a chip missing
from ``peaks.json``, exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()  # set-up runs from here

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from collections import deque  # noqa: E402
from contextlib import nullcontext  # noqa: E402

import numpy as np  # noqa: E402

from bench import compare, spec, traffic, weights  # noqa: E402
from bench import trace as trace_mod  # noqa: E402

spec.use_program()
ROOT = spec.ROOT
CACHE_DIR = ROOT / ".jax_cache"
TRACE_DIR = ROOT / ".bench_trace"
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


class NoDevice(SystemExit):
    pass


@dataclasses.dataclass
class Ctx:
    """What a metric reader may read."""

    cell: dict
    config: dict
    mix: dict
    sizes: dict
    peaks: dict
    block: object  # the configuration's block module (spec.load_block)
    seconds: float  # the window asked for
    elapsed: float = 0.0  # the window as run (its last step ends it)
    setup_s: float = 0.0
    backlog: bool = False
    records: list = dataclasses.field(default_factory=list)  # due in the window
    tokens_in_window: int = 0
    drain_end: float = 0.0  # seconds from the window's start
    step_ms: list = dataclasses.field(default_factory=list)
    decode_calls: list = dataclasses.field(default_factory=list)  # (t0, t1, ctx lengths)
    traced_span: tuple = ()  # (t0, t1) harness clock of the traced part
    trace: object = None  # trace.Trace of the traced part
    prompt_tokens: int = 0
    prefix_hit_tokens: int = 0
    window_compiles: list = dataclasses.field(default_factory=list)
    notes: dict = dataclasses.field(default_factory=dict)  # reader → extra words

    def traced_decode_calls(self) -> list:
        if not self.traced_span:
            return []
        a, b = self.traced_span
        return [c for c in self.decode_calls if c[0] >= a and c[1] <= b]


# --------------------------------------------------------------------------
# device and program


def check_device(chips: int):
    import jax

    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        raise NoDevice(
            f"bench.run: no TPU: jax reports platform {d.platform!r} "
            f"({d.device_kind}, {len(devs)} device(s)); the benchmark runs only on a TPU"
        )
    if len(devs) < chips:
        raise NoDevice(f"bench.run: the cell needs {chips} chips, jax reports {len(devs)}")
    return d


def use_compile_cache() -> None:
    """JAX's persistent compile cache: where ``JAX_COMPILATION_CACHE_DIR``
    says (JAX reads it itself), else ``<checkout>/.jax_cache``; every
    program is kept, however quickly it compiled."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def program_config(conf: dict):
    """The program's config for a configuration file, checked against the
    file's published sizes as its block reads them. Returns (program
    config, sizes, block)."""
    spec.use_program()
    from repro.configs import get_config

    block = spec.load_block(conf)
    pcfg = dataclasses.replace(get_config(conf["registry"]), **conf.get("overrides", {}))
    sizes = block.sizes(conf)
    want = block.program_fields(sizes, conf)
    got = {k: getattr(pcfg, k) for k in want}
    if got != want:
        raise spec.SpecError(f"program config {pcfg.name} differs from {conf['name']}: {got} != {want}")
    return pcfg, sizes, block


class CompileCounter:
    """Counts the executables JAX builds (compiled or read from the
    cache) while ``on``."""

    def __init__(self):
        import jax

        self.on = False
        self.seen: list[str] = []
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, event, duration, **kw):
        if self.on and event == BACKEND_COMPILE:
            self.seen.append(str(kw.get("fun_name", "?")))


# --------------------------------------------------------------------------
# the window


def _annotate(on: bool, name: str):
    if not on:
        return nullcontext()
    import jax

    return jax.profiler.TraceAnnotation(name)


def drive(sched, items, ctx: Ctx, *, drain_s: float, trace: bool, counter: CompileCounter):
    """Offer ``items`` open-loop for ``ctx.seconds`` on the harness's
    clock, then let what is due finish. Returns the submitted requests."""
    import jax

    from repro.serve.request import Request

    sched_decode = sched._decode_paged

    def timed_decode(params, pool, tables, lens, tok):
        kv = sched.kv
        live = kv.live_rows()
        c = (kv.cache_len[live] + 1).tolist()
        a = time.perf_counter()
        out = sched_decode(params, pool, tables, lens, tok)
        jax.block_until_ready(out[0])
        ctx.decode_calls.append((a - t0, time.perf_counter() - t0, c))
        return out

    sched._decode_paged = timed_decode
    pending = deque(items)
    submitted = []
    seconds = ctx.seconds
    tr_on, tr_off = (0.25 * seconds, min(seconds, 0.25 * seconds + 10.0)) if trace else (None, None)
    tracing = None
    n_steps0 = len(sched.stats.step_ms)
    t0 = time.perf_counter()
    sched._t0 = t0  # the scheduler's clock is the harness's
    counter.on = True

    def now():
        return time.perf_counter() - t0

    while True:
        t = now()
        if t >= seconds:
            break
        if trace and tracing is None and t >= tr_on:
            TRACE_DIR.exists() and shutil.rmtree(TRACE_DIR)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0  # host spans are the TraceMe's, not every call
            jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
            tracing = jax.profiler.TraceAnnotation(trace_mod.WINDOW_SPAN)
            tracing.__enter__()
            ctx.traced_span = (now(), None)
        if tracing is not None and ctx.traced_span[1] is None and t >= tr_off:
            ctx.traced_span = (ctx.traced_span[0], now())
            tracing.__exit__(None, None, None)
            jax.profiler.stop_trace()
        while pending and pending[0].arrival <= t:
            it = pending.popleft()
            req = Request(prompt=it.prompt, max_new_tokens=it.max_new, arrival_time=it.arrival)
            sched.submit(req)
            submitted.append(req)
        with _annotate(trace, "bench.step"):
            did = sched.step()
        if not did:
            nxt = pending[0].arrival if pending else seconds
            if trace and tr_off is not None and ctx.traced_span and ctx.traced_span[1] is None:
                nxt = min(nxt, tr_off)
            with _annotate(trace, "bench.wait"):
                time.sleep(max(0.0, min(nxt, seconds) - now()))
    ctx.elapsed = now()
    # requests that came due while the last step ran are due in the window
    while pending and pending[0].arrival < seconds:
        it = pending.popleft()
        req = Request(prompt=it.prompt, max_new_tokens=it.max_new, arrival_time=it.arrival)
        sched.submit(req)
        submitted.append(req)
    if tracing is not None and ctx.traced_span[1] is None:
        ctx.traced_span = (ctx.traced_span[0], now())
        tracing.__exit__(None, None, None)
        jax.profiler.stop_trace()
    counter.on = False
    ctx.window_compiles = list(counter.seen)
    ctx.tokens_in_window = sum(len(r.tokens) for r in submitted)
    ctx.step_ms = list(sched.stats.step_ms[n_steps0:])
    if not ctx.backlog:
        while any(not r.finished for r in submitted) and now() < ctx.elapsed + drain_s:
            if not sched.step():
                break
    ctx.drain_end = now()
    return submitted


def records(submitted, ctx: Ctx) -> list:
    """One dict per request due in the window (every admitted one, for a
    backlog), on the harness's clock."""
    out = []
    for r in submitted:
        if ctx.backlog and r.t_first_admit is None:
            continue
        end = r.t_finish if r.finished else (ctx.elapsed if ctx.backlog else ctx.drain_end)
        out.append(
            {
                "arrival": r.arrival_time,
                "admit": r.t_first_admit,
                "first": r.t_first,
                "end": end,
                "finished": r.finished,
                "tokens": len(r.tokens),
                "prompt": int(np.asarray(r.prompt).shape[0]),
                "prefix_hit": r.prefix_hit,
            }
        )
    return out


def check_sample(submitted, seed: int, *, want_tokens: int, max_requests: int) -> list:
    """Requests to judge, drawn from the seed: the one that served the
    most tokens, then others at random until ``want_tokens`` served
    tokens or ``max_requests`` requests."""
    served = [r for r in submitted if r.tokens]
    if not served:
        return []
    served.sort(key=lambda r: (r.finished, len(r.tokens)), reverse=True)
    pick = [served[0]]
    rest = served[1:]
    rng = np.random.default_rng([seed, 4])
    for i in rng.permutation(len(rest)):
        if sum(len(r.tokens) for r in pick) >= want_tokens or len(pick) >= max_requests:
            break
        pick.append(rest[i])
    return [(np.asarray(r.prompt), np.asarray(r.tokens)) for r in pick]


def device_memory_peak() -> int:
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", 0))


# --------------------------------------------------------------------------
# one cell


def build(cell: dict, conf: dict, seed: int, *, trace: bool = False, backend: str = "kernel"):
    """The system under test: the program's model and serving engine at
    the cell's sizes, holding the weights the benchmark made from the
    seed. Returns (engine, sizes, block)."""
    import jax

    from repro.models.model import Model
    from repro.serve.engine import ServingEngine
    from repro.serve.telemetry import Telemetry

    pcfg, sizes, block = program_config(conf)
    model = Model(pcfg)
    params = weights.make(model.abstract_params()[0], sizes, seed, block)
    jax.block_until_ready(params)
    tel = Telemetry(enabled=True, xla_annotations=True) if trace else None
    engine = ServingEngine(
        model, params, max_seq=int(cell["max_seq"]), temperature=0.0, kv_layout="paged",
        block_size=int(cell["block_size"]), num_blocks=cell.get("num_blocks"),
        prefix_cache=True, attention_backend=backend, telemetry=tel,
    )
    return engine, sizes, block


def serve(engine, ctx: Ctx, *, seed: int, rate=None, trace: bool, counter, break_program=None):
    """Warm the cell's programs on a fresh scheduler, then measure a
    window on it. Returns the submitted requests; fills ``ctx``."""
    from repro.serve.request import Request

    cell, mix = ctx.cell, ctx.mix
    max_seq = int(cell["max_seq"])
    sched = engine.scheduler(int(cell["max_batch"]), seed=seed)
    if break_program is not None:
        break_program(sched)
    warm = traffic.warmup_items(mix, vocab=ctx.sizes["vocab"], seed=seed, max_seq=max_seq)
    sched.run([Request(prompt=w.prompt, max_new_tokens=w.max_new) for w in warm])
    items = traffic.generate(
        mix, rate=rate if rate is not None else cell.get("rate_rps"),
        seconds=ctx.seconds, vocab=ctx.sizes["vocab"], seed=seed,
    )
    engine.stats.reset()
    ctx.setup_s = time.perf_counter() - T_PROCESS
    submitted = drive(
        sched, items, ctx, drain_s=float(cell.get("drain_s", 60)), trace=trace, counter=counter
    )
    ctx.records = records(submitted, ctx)
    ctx.prompt_tokens = sum(rec["prompt"] for rec in ctx.records)
    ctx.prefix_hit_tokens = sum(rec["prefix_hit"] for rec in ctx.records)
    return submitted


def sample_of(cell: dict, submitted, seed: int) -> list:
    return check_sample(
        submitted, seed,
        want_tokens=int(cell.get("check_tokens", 256)),
        max_requests=int(cell.get("check_requests", 8)),
    )


def run_cell(
    cell: dict,
    conf: dict,
    mix: dict,
    *,
    seed: int,
    seconds: float,
    trace: bool,
    metrics: list,
    peaks: dict,
    device=None,
    backend: str = "kernel",
    break_program=None,
) -> dict:
    """Set up, measure, judge; returns the result's fields.
    ``break_program(sched)`` plants a fault in the timed path (tests)."""
    import jax

    counter = CompileCounter()
    engine, sizes, block = build(cell, conf, seed, trace=trace, backend=backend)
    ctx = Ctx(
        cell=cell, config=conf, mix=mix, sizes=sizes, peaks=peaks, block=block,
        seconds=float(seconds),
    )
    ctx.backlog = mix["kind"] == "backlog"
    submitted = serve(
        engine, ctx, seed=seed, trace=trace, counter=counter, break_program=break_program
    )
    peak = device_memory_peak()
    sample = sample_of(cell, submitted, seed)
    attempted = len(ctx.records)
    failed = 0 if ctx.backlog else sum(not rec["finished"] for rec in ctx.records)
    params = engine.params
    del engine, submitted
    gc.collect()  # the program's pool and programs go before the reference runs

    if trace:
        ctx.trace = trace_mod.load(trace_mod.find_xplane(str(TRACE_DIR)))
    result_metrics = {}
    for m in metrics:
        val = spec.metric_reader(m["name"])(ctx)
        if val is not None:
            result_metrics[m["name"]] = {"value": float(val), "unit": m["unit"]}

    t_check = time.perf_counter()
    verdict = compare.judge(
        block.logits, params, sample, sizes=sizes, max_seq=int(cell["max_seq"]),
        window=int(mix["output"]["hi"]),
    )
    verdict["check_s"] = time.perf_counter() - t_check
    limit = float(cell["limits"]["max_logit_gap"])
    correct = bool(sample) and verdict["max_logit_gap"] <= limit

    dev = device if device is not None else jax.devices()[0]
    out = {
        "correct": correct,
        "attempted": attempted,
        "failed": failed,
        "metrics": result_metrics,
        "device": {
            "platform": dev.platform,
            "kind": dev.device_kind,
            "count": int(cell["chips"]),
            "memory_peak_bytes": peak,
        },
    }
    if trace:
        out["device"]["busy_s"] = trace_mod.device_busy_s(ctx.trace)
        out["device"]["window_s"] = trace_mod.window_s(ctx.trace)
        out["breakdown"] = trace_mod.breakdown(ctx.trace)
    out["checks"] = {
        "max_logit_gap": {"value": verdict["max_logit_gap"], "limit": limit},
        "requests_checked": {"value": len(sample), "limit": 1},
    }
    recs = ctx.records
    ttft = [(r["first"] - r["arrival"]) * 1e3 for r in recs if r["first"] is not None]
    tpot = [
        (r["end"] - r["first"]) / (r["tokens"] - 1) * 1e3
        for r in recs if r["first"] is not None and r["tokens"] >= 2
    ]
    out["_aux"] = {
        "requests": len(recs),
        "finished": sum(r["finished"] for r in recs),
        "ttft_p50_ms": float(np.median(ttft)) if ttft else None,
        "tpot_p50_ms": float(np.median(tpot)) if tpot else None,
        "verdict": verdict,
        "notes": ctx.notes,
        "window_compiles": ctx.window_compiles,
        "elapsed": ctx.elapsed,
        "drain_end": ctx.drain_end,
        "tokens_in_window": ctx.tokens_in_window,
        "steps": len(ctx.step_ms),
        "decode_calls": len(ctx.decode_calls),
        "layout": ctx.trace.layout if trace else None,
        "inventory": trace_mod.inventory(ctx.trace) if trace else None,
        "idle_by_label": trace_mod.idle_by_label(ctx.trace) if trace else None,
    }
    return out


def emit(out: dict) -> None:
    aux = out.pop("_aux")
    print(json.dumps({"aux": aux}, default=str), flush=True)
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    print(json.dumps(out), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = spec.load_cell(args.workload)
    conf = spec.load_config(cell["config"])
    mix = spec.load_mix(cell["traffic"])
    bench = spec.load_benchmark()
    spec.cell_entry(bench, args.workload)
    metrics = spec.cell_metrics(bench, args.workload, bool(args.trace))
    for m in metrics:
        spec.metric_reader(m["name"])
    try:
        dev = check_device(int(cell["chips"]))
        peaks = spec.load_peaks(dev.device_kind)
    except (NoDevice, spec.SpecError) as e:
        print(str(e), file=sys.stderr)
        return 3
    use_compile_cache()
    out = run_cell(
        cell, conf, mix, seed=args.seed, seconds=args.seconds, trace=bool(args.trace),
        metrics=metrics, peaks=peaks, device=dev,
    )
    emit(out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
