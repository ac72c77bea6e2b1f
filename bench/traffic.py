"""The one traffic generator: every mix is a data file it reads.

Sizes, arrival times and system-prompt choices come from the mix alone
(drawn from a fixed stream), and the run's seed only fills the prompts
with tokens. So every seed offers the same work on the same schedule:
two seeds differ in what they send, not in how much or when, and the
spread between runs is the system's own.

A mix (``traffic/<mix>.json``) names its ``kind``:

- ``poisson``: open-loop arrivals at the cell's ``rate_rps``;
- ``backlog``: ``count`` requests, all due at t = 0.

``prompt`` and ``output`` are lognormal lengths (``median``, ``sigma``)
clipped to ``[lo, hi]``. ``system_prompts`` (optional) puts one of a few
fixed prefixes of ``length`` tokens before each prompt, chosen in the
given ``shares``; the prompt lengths are then the user turn's.
"""
from __future__ import annotations

import math
from dataclasses import dataclass

import numpy as np

SHAPE_SEED = 20250901  # the fixed stream that sizes and gaps come from


@dataclass
class Item:
    arrival: float  # seconds from the window's start
    prompt: np.ndarray  # int32 token ids
    max_new: int
    system: int = -1  # which system prompt leads the prompt, -1 for none


def lognormal_lengths(rng, dist: dict, n: int) -> np.ndarray:
    draw = dist["median"] * np.exp(dist["sigma"] * rng.standard_normal(n))
    return np.clip(np.rint(draw), dist["lo"], dist["hi"]).astype(np.int64)


def request_count(mix: dict, rate: float | None, seconds: float) -> int:
    """How many requests the window can see: the backlog, or enough
    Poisson arrivals to outlast the window with room to spare."""
    if mix["kind"] == "backlog":
        return int(mix["count"])
    return int(math.ceil(rate * seconds * 1.5)) + 16


def exact_shares(shares, n: int) -> np.ndarray:
    """``n`` labels in the given shares, by largest remainder."""
    shares = np.asarray(shares, float) / np.sum(shares)
    counts = np.floor(shares * n).astype(int)
    rest = np.argsort(-(shares * n - counts), kind="stable")[: n - counts.sum()]
    counts[rest] += 1
    return np.repeat(np.arange(len(shares)), counts)


def system_prompts(mix: dict, vocab: int, seed: int) -> list[np.ndarray]:
    sp = mix.get("system_prompts")
    if not sp:
        return []
    rng = np.random.default_rng([seed, 1])
    return [
        rng.integers(0, vocab, size=sp["length"]).astype(np.int32)
        for _ in sp["shares"]
    ]


def generate(mix: dict, *, rate: float | None, seconds: float, vocab: int, seed: int):
    """The run's requests, sorted by arrival."""
    n = request_count(mix, rate, seconds)
    shape = np.random.default_rng(SHAPE_SEED)
    prompt_lens = lognormal_lengths(shape, mix["prompt"], n)
    out_lens = lognormal_lengths(shape, mix["output"], n)
    if mix["kind"] == "poisson":
        gaps = shape.exponential(1.0 / rate, size=n)
    else:
        gaps = np.zeros(n)
    systems = (
        exact_shares(mix["system_prompts"]["shares"], n)
        if mix.get("system_prompts")
        else np.full(n, -1)
    )
    systems = shape.permutation(systems)
    arrivals = np.cumsum(gaps) if mix["kind"] == "poisson" else gaps
    heads = system_prompts(mix, vocab, seed)
    tokens = np.random.default_rng([seed, 2])
    items = []
    for i in range(n):
        user = tokens.integers(0, vocab, size=int(prompt_lens[i])).astype(np.int32)
        s = int(systems[i])
        prompt = np.concatenate([heads[s], user]) if s >= 0 else user
        items.append(Item(float(arrivals[i]), prompt, int(out_lens[i]), s))
    return items


def pow2_buckets(lo: int, hi: int) -> list[int]:
    """Every power of two a length in ``[lo, hi]`` pads up to."""
    b = 1 << max(0, int(lo) - 1).bit_length()
    out = []
    while True:
        out.append(b)
        if b >= hi:
            return out
        b *= 2


def warmup_items(mix: dict, *, vocab: int, seed: int, max_seq: int) -> list[Item]:
    """Requests that reach every program the mix's traffic reaches, and
    no other: one prompt per pow2 prefill bucket its lengths fall in
    (after the system prompt, where there is one), each system prompt
    served once so the prefix cache starts warm, and two tokens each so
    the decode step and the sampler run too. All due at t = 0."""
    rng = np.random.default_rng([seed, 3])
    heads = system_prompts(mix, vocab, seed)
    p = mix["prompt"]
    items = []
    if heads:
        # each system prompt once (cold), then every suffix bucket on a
        # hit; a cold prompt of any turn length pads to the same bucket
        # as the first ones, since the system prompt fills half of it
        for s, head in enumerate(heads):
            user = rng.integers(0, vocab, size=p["lo"]).astype(np.int32)
            items.append(Item(0.0, np.concatenate([head, user]), 2, s))
        for b in pow2_buckets(p["lo"], p["hi"]):
            n = min(b, p["hi"])
            user = rng.integers(0, vocab, size=n).astype(np.int32)
            items.append(Item(0.0, np.concatenate([heads[0], user]), 2, 0))
        return items
    for b in pow2_buckets(p["lo"], p["hi"]):
        n = min(b, p["hi"], max_seq - 2)
        items.append(Item(0.0, rng.integers(0, vocab, size=n).astype(np.int32), 2))
    return items
