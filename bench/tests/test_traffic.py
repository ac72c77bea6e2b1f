"""The traffic generator: one seed, one set of requests; every seed the
same work in another order; lengths inside their clips; system prompts
in their shares; a warm-up that reaches every prefill bucket."""
import json

import numpy as np
import pytest

from bench import spec, traffic
from bench.tests import tiny

BENCH = json.loads((spec.ROOT / "BENCHMARK.json").read_text())
MIXES = sorted({w["traffic"] for w in BENCH["workloads"]})
SYSTEM = dict(
    tiny.mix(system=True),
    prompt={"median": 96, "sigma": 0.6, "lo": 16, "hi": 512},
    system_prompts={"length": 1024, "shares": [0.4, 0.3, 0.2, 0.1]},
)


BACKLOG = dict(tiny.mix("backlog"), count=64)


def load(name):
    return {"system": SYSTEM, "backlog": BACKLOG}.get(name) or spec.load_mix(name)


def gen(mix, seed, rate=2.0, seconds=30.0, vocab=1000):
    return traffic.generate(mix, rate=rate, seconds=seconds, vocab=vocab, seed=seed)


@pytest.mark.parametrize("name", MIXES + ["system", "backlog"])
def test_same_seed_same_requests(name):
    mix = load(name)
    a, b = gen(mix, 2**31 + 17), gen(mix, 2**31 + 17)
    assert len(a) == len(b)
    for x, y in zip(a, b):
        assert x.arrival == y.arrival and x.max_new == y.max_new
        assert np.array_equal(x.prompt, y.prompt)


@pytest.mark.parametrize("name", MIXES + ["system", "backlog"])
def test_every_seed_offers_the_same_work(name):
    mix = load(name)
    a, b = gen(mix, 3), gen(mix, 4)
    assert [(i.arrival, len(i.prompt), i.max_new, i.system) for i in a] == [
        (i.arrival, len(i.prompt), i.max_new, i.system) for i in b
    ]
    assert not all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))


@pytest.mark.parametrize("name", MIXES + ["system", "backlog"])
def test_lengths_inside_their_clips(name):
    mix = load(name)
    items = gen(mix, 9)
    head = mix["system_prompts"]["length"] if mix.get("system_prompts") else 0
    p, o = mix["prompt"], mix["output"]
    assert all(p["lo"] + head <= len(i.prompt) <= p["hi"] + head for i in items)
    assert all(o["lo"] <= i.max_new <= o["hi"] for i in items)
    assert all(0 <= int(i.prompt.min()) and int(i.prompt.max()) < 1000 for i in items)
    arrivals = [i.arrival for i in items]
    assert arrivals == sorted(arrivals)
    if mix["kind"] == "backlog":
        assert len(items) == mix["count"] and set(arrivals) == {0.0}


def test_system_prompt_shares():
    mix = SYSTEM
    items = gen(mix, 5, seconds=60.0)
    counts = np.bincount([i.system for i in items], minlength=4) / len(items)
    assert np.allclose(counts, mix["system_prompts"]["shares"], atol=1.0 / len(items))
    heads = traffic.system_prompts(mix, 1000, 5)
    for i in items:
        assert np.array_equal(i.prompt[: len(heads[i.system])], heads[i.system])


def test_exact_shares_by_largest_remainder():
    labels = traffic.exact_shares([0.4, 0.3, 0.2, 0.1], 7)
    assert np.bincount(labels).tolist() == [3, 2, 1, 1]


@pytest.mark.parametrize("name", MIXES + ["system", "backlog"])
def test_warmup_reaches_every_bucket(name):
    mix = load(name)
    warm = traffic.warmup_items(mix, vocab=1000, seed=1, max_seq=4096)
    window = gen(mix, 1)
    head = mix["system_prompts"]["length"] if mix.get("system_prompts") else 0
    # cold prompts pad to pow2 buckets; on a prefix hit only the turn does
    bucket = lambda n: 1 << max(0, n - 1).bit_length()  # noqa: E731
    if head:
        want = {bucket(len(i.prompt) - head) for i in window}
        got = {bucket(len(i.prompt) - head) for i in warm if i.system == 0}
    else:
        want = {bucket(len(i.prompt)) for i in window}
        got = {bucket(len(i.prompt)) for i in warm}
    assert want <= got
    assert all(i.arrival == 0.0 and i.max_new == 2 for i in warm)


def test_tiny_mix_is_a_valid_mix():
    items = traffic.generate(tiny.mix(), rate=20.0, seconds=2.0, vocab=256, seed=1)
    assert items and all(len(i.prompt) <= 24 for i in items)
