"""The harness reaches a configuration's architecture only through the
block it names: a spy block that wraps llama sees every piece a run
takes from it."""
import sys
import types
from collections import Counter

from bench import run, spec
from bench.blocks import llama
from bench.metrics import paged_attn_roofline
from bench.tests import tiny
from bench.tests.test_check import LIMIT
from bench.tests.test_trace import hand_trace

PIECES = ("sizes", "program_fields", "fan_in", "logits", "decode_flops", "paged_attn_cost")


def spy_block(calls: Counter):
    spy = types.ModuleType("bench.blocks.spy")

    def wrap(name):
        inner = getattr(llama, name)

        def counted(*a, **kw):
            calls[name] += 1
            return inner(*a, **kw)

        return counted

    for name in PIECES:
        setattr(spy, name, wrap(name))
    spy.ONES = llama.ONES
    return spy


def test_run_cell_takes_every_piece_from_the_named_block(monkeypatch):
    calls = Counter()
    spy = spy_block(calls)
    monkeypatch.setitem(sys.modules, "bench.blocks.spy", spy)
    conf = dict(tiny.conf(), block="spy")
    metrics = tiny.E2E + [{"name": "decode_mfu", "unit": "%"}]
    out = run.run_cell(
        tiny.cell(LIMIT), conf, tiny.mix(), seed=13, seconds=1.5, trace=False,
        metrics=metrics, peaks=tiny.PEAKS, backend="interpret",
    )
    assert out["correct"], out["checks"]
    assert "decode_mfu" in out["metrics"]
    # the block is looked up once and read once for its sizes and fields;
    # fan_in once per drawn leaf while the weights' program traces; the
    # reference once per judged request; a count per decode
    assert calls["sizes"] == calls["program_fields"] == 1
    assert calls["fan_in"] >= 4
    assert calls["logits"] == out["checks"]["requests_checked"]["value"] >= 1
    assert calls["decode_flops"] >= 1
    assert calls["paged_attn_cost"] == 0  # untraced: no kernel to read

    ctx = run.Ctx(
        cell=tiny.cell(), config=conf, mix=tiny.mix(), sizes=llama.sizes(conf),
        peaks=tiny.PEAKS, block=spec.load_block(conf), seconds=1.0,
    )
    assert ctx.block is spy
    ctx.trace, ctx.traced_span = hand_trace(), (0.0, 10.0)
    ctx.decode_calls = [(1.0, 2.0, [10, 20])]
    assert paged_attn_roofline.read(ctx) > 0
    assert calls["paged_attn_cost"] == 1
