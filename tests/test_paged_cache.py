"""Paged KV cache: BlockAllocator/PrefixCache property tests, paged vs
slotted vs fixed-batch differential equivalence under greedy decode,
prefix-cache semantics (hit length, token identity, eviction restores
the cold path), and block-granular admission control."""
import random

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.configs import get_config
from repro.models import Model
from repro.models.attention import is_scale, to_stored
from repro.serve import (
    BlockAllocator,
    PagedKVCache,
    PrefixCache,
    Request,
    ServingEngine,
)


@pytest.fixture(scope="module")
def served():
    cfg = get_config("smollm-135m").reduced()
    m = Model(cfg)
    params, _ = m.init(jax.random.key(0))
    prompts = jax.random.randint(jax.random.key(1), (6, 16), 0, cfg.vocab_size)
    return cfg, m, params, prompts


# ---------------------------------------------------------------------------
# BlockAllocator property tests


@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 10_000), num_blocks=st.integers(1, 12))
def test_block_allocator_random_ops_conserve_refcounts(seed, num_blocks):
    """Under random alloc/share/free(park)/evict sequences: refcounts
    equal the references the driver holds, {free, parked, live} stay a
    partition (no block both free and referenced), and LRU eviction
    only ever takes refcount-0 blocks."""
    rng = random.Random(seed)
    alloc = BlockAllocator(num_blocks)
    held: list[int] = []  # our referents, with multiplicity
    for _ in range(250):
        ops = []
        if alloc.n_available:
            ops += ["alloc"] * 2
        if held:
            ops += ["share", "free", "park"]
        if alloc.n_parked:
            ops.append("evict")
        op = rng.choice(ops)
        if op == "alloc":
            held.append(alloc.alloc())
        elif op == "share":
            b = rng.choice(held)
            alloc.share(b)
            held.append(b)
        elif op in ("free", "park"):
            b = held.pop(rng.randrange(len(held)))
            alloc.free(b, park=op == "park")
        elif op == "evict":
            parked = [b for b in range(num_blocks) if alloc.is_parked(b)]
            alloc.evict(rng.choice(parked))
        alloc.check_invariants()
        counts = [held.count(b) for b in range(num_blocks)]
        assert counts == alloc.refcount, "refcounts not conserved"
    # evicting a referenced block is impossible
    if not held:
        held.append(alloc.alloc())
    with pytest.raises(RuntimeError, match="refcount"):
        alloc.evict(held[0])
    # and so are double free / sharing a free block
    b = held.pop()
    alloc.free(b)
    if b not in held:
        with pytest.raises(RuntimeError, match="double free"):
            alloc.free(b)
        with pytest.raises(RuntimeError, match="free block"):
            alloc.share(b)


def test_block_allocator_lru_eviction_order_and_exhaustion():
    alloc = BlockAllocator(3)
    a, b, c = alloc.alloc(), alloc.alloc(), alloc.alloc()
    with pytest.raises(RuntimeError, match="no free KV block"):
        alloc.alloc()
    alloc.free(b, park=True)  # parked first → LRU victim
    alloc.free(a, park=True)
    assert alloc.alloc() == b  # evicts least-recently-parked, reuses it
    alloc.share(a)  # reactivate the parked survivor
    assert alloc.refcount[a] == 1 and not alloc.is_parked(a)
    alloc.check_invariants()


def test_eviction_under_pressure_takes_leaves_not_chain_roots(served):
    """Reclaiming one block under memory pressure evicts the oldest
    parked *leaf*, so a cached prefix chain shrinks from its divergence
    tail inward instead of being cascaded away root-first."""
    _, m, _, _ = served
    kv = PagedKVCache(m, max_batch=2, max_seq=16, block_size=4, num_blocks=5)
    row, _ = kv.try_admit(0, tuple(range(16)), 1)
    kv.free_row(row)  # 4 prompt blocks registered, parked; 1 reserve freed
    assert kv.allocator.n_parked == 4
    # a new unrelated request needs 2 fresh blocks → evicts 1-2 leaves
    row2, hits = kv.try_admit(1, tuple(range(100, 105)), 3)
    assert hits == []
    kv.check_invariants()
    # the surviving chain still matches from the root
    survivors = kv.lookup(tuple(range(16)))
    assert len(survivors) >= 1, "root of the cached chain was evicted"
    assert survivors == [b for b in survivors if kv.prefix.registered(b)]


@settings(max_examples=12, deadline=None)
@given(seed=st.integers(0, 10_000), block_size=st.integers(1, 4))
def test_prefix_trie_random_ops_match_reference(seed, block_size):
    """Under random insert/match/cascade-drop sequences the trie agrees
    with a brute-force reference (block id → its full key-chain path):
    match returns exactly the longest registered chain prefix (capped so
    one suffix token remains), insert registers only the novel tail of
    a chain, and dropping a block drops precisely its subtree."""
    rng = random.Random(seed)
    pc = PrefixCache(block_size)
    chains: dict[int, tuple] = {}  # block → key-chain path (ref model)
    next_block = 0

    def ref_match(tokens):
        keys = [
            tuple(tokens[j * block_size : (j + 1) * block_size])
            for j in range((len(tokens) - 1) // block_size)
        ]
        out = []
        by_path = {path: b for b, path in chains.items()}
        for j in range(len(keys)):
            b = by_path.get(tuple(keys[: j + 1]))
            if b is None:
                break
            out.append(b)
        return out

    for _ in range(120):
        op = rng.choice(["insert", "match", "match", "drop"] if chains else ["insert", "match"])
        tokens = tuple(rng.randrange(4) for _ in range(rng.randint(0, 4 * block_size)))
        if op == "insert":
            n_blocks = rng.randint(0, len(tokens) // block_size)
            keys = [
                tuple(tokens[j * block_size : (j + 1) * block_size])
                for j in range(n_blocks)
            ]
            by_path = {path: b for b, path in chains.items()}
            ids = []
            for j in range(n_blocks):
                path = tuple(keys[: j + 1])
                if path in by_path:
                    ids.append(by_path[path])  # existing chain keeps its block
                else:
                    ids.append(next_block)
                    chains[next_block] = path
                    by_path[path] = next_block
                    next_block += 1
            pc.insert(tokens, ids)
        elif op == "match":
            assert pc.match(tokens) == ref_match(tokens)
        else:
            b = rng.choice(sorted(chains))
            bpath = chains[b]
            dropped = pc.drop_block(b)
            want = {d for d, p in chains.items() if p[: len(bpath)] == bpath and d != b}
            assert set(dropped) == want, "cascade != subtree"
            for d in list(chains):
                if chains[d][: len(bpath)] == bpath:
                    del chains[d]
        assert pc.n_blocks == len(chains)
        for b in chains:
            assert pc.registered(b)
            has_children = any(
                p[: len(chains[b])] == chains[b] and d != b for d, p in chains.items()
            )
            assert pc.is_leaf(b) == (not has_children)


def test_prefix_trie_match_insert_drop_cascade():
    """match walks full-block chains only (capped so one suffix token
    remains); dropping an interior block drops its whole subtree."""
    pc = PrefixCache(4)
    t = tuple(range(12))
    pc.insert(t, [10, 11, 12])
    assert pc.match(t + (99,)) == [10, 11, 12]
    assert pc.match(t) == [10, 11]  # cap: (12-1)//4 = 2 blocks
    assert pc.match((0, 1, 2, 3, 7, 7, 7, 7, 9)) == [10]  # diverges at block 1
    assert pc.match((5, 5, 5, 5, 5)) == []
    # dropping the middle block orphans — and drops — its subtree
    assert pc.drop_block(11) == [12]
    assert pc.match(t + (99,)) == [10]
    assert not pc.registered(11) and not pc.registered(12)
    assert pc.drop_block(999) == []  # unknown block: no-op


# ---------------------------------------------------------------------------
# paged pool invariants


def test_paged_cache_admission_lifecycle_invariants(served):
    """Random admit/decode-advance/finish traffic against PagedKVCache
    keeps rows, tables, refcounts, and reservations consistent."""
    _, m, _, _ = served
    kv = PagedKVCache(m, max_batch=3, max_seq=16, block_size=4, num_blocks=9)
    rng = random.Random(0)
    live: dict[int, int] = {}  # row → remaining budget
    rid = 0
    for _ in range(200):
        if rng.random() < 0.4:
            S, budget = rng.randint(1, 8), rng.randint(1, 4)
            tokens = tuple(rng.randrange(7) for _ in range(S))
            got = kv.try_admit(rid, tokens, budget)
            if got is not None:
                row, _hits = got
                assert kv.owner(row) == rid
                live[row] = budget
                rid += 1
        elif live:
            row = rng.choice(sorted(live))
            if rng.random() < 0.5 and live[row] > 0:
                kv.ensure_tail(row)  # decode writes one token
                kv.advance(row)
                live[row] -= 1
            else:
                kv.free_row(row)
                del live[row]
        kv.check_invariants()
    if not live:
        row, _ = kv.try_admit(rid, (1, 2), 1)
    else:
        row = next(iter(live))
    kv.free_row(row)
    with pytest.raises(RuntimeError, match="double free"):
        kv.free_row(row)


def test_kernel_inputs_hoists_invariant_device_views(served):
    """``kernel_inputs()`` re-uploads only what actually changed: across
    pure decode steps (advance only) the device block-table view is the
    SAME object — zero per-step host allocations beyond the lengths
    vector — and table mutations (admit / lazy tail claim / truncate /
    free) each invalidate exactly the table view."""
    _, m, _, _ = served
    kv = PagedKVCache(m, max_batch=2, max_seq=16, block_size=4, num_blocks=8)
    row, _ = kv.try_admit(0, (1, 2, 3), budget=8)
    _, t0, l0 = kv.kernel_inputs()
    # same state → identical objects, no re-upload at all
    _, t1, l1 = kv.kernel_inputs()
    assert t1 is t0 and l1 is l0
    # steady decode inside a block: lengths refresh, tables do not
    kv.ensure_tail(row)  # block 0 already covers position 3
    kv.advance(row)
    _, t2, l2 = kv.kernel_inputs()
    assert t2 is t0, "pure advance must not re-upload the block tables"
    assert l2 is not l0
    np.testing.assert_array_equal(np.asarray(l2), kv.cache_len)
    # crossing a block boundary claims a tail block → tables invalidate
    kv.advance(row)  # len 5: next write position enters block 1
    kv.ensure_tail(row)
    _, t3, _ = kv.kernel_inputs()
    assert t3 is not t0
    np.testing.assert_array_equal(np.asarray(t3), kv.block_tables)
    # speculative rewind releases the claimed tail block → tables invalidate
    kv.advance_n(row, 3)
    kv.truncate_row(row, 4)
    _, t4, l4 = kv.kernel_inputs()
    assert t4 is not t3
    np.testing.assert_array_equal(np.asarray(t4), kv.block_tables)
    np.testing.assert_array_equal(np.asarray(l4), kv.cache_len)
    # retire → tables and lengths both invalidate
    kv.free_row(row)
    _, t5, l5 = kv.kernel_inputs()
    assert t5 is not t4 and l5 is not l4
    np.testing.assert_array_equal(np.asarray(t5), kv.block_tables)


_MUT = {}


def _mutation_fixture():
    """Module memo, built once for every ``@given`` example: one
    reduced model plus one batch=1 dense prefill
    reused as the ``write_prefill`` payload."""
    if not _MUT:
        cfg = get_config("smollm-135m").reduced()
        m = Model(cfg)
        params, _ = m.init(jax.random.key(0))
        prompt = jnp.arange(6, dtype=jnp.int32)[None]
        _, dense = jax.jit(lambda p, t: m.prefill(p, t, 16))(params, prompt)
        _MUT["m"], _MUT["dense"] = m, dense
    return _MUT["m"], _MUT["dense"]


@settings(max_examples=5, deadline=None)
@given(seed=st.integers(0, 10_000))
def test_any_mutation_refreshes_kernel_inputs(seed):
    """Version-counter property: after ANY public mutation — admit,
    prefill install, lazy tail claim, decode/verify advance, rewind,
    preempt, retire — the next ``kernel_inputs()`` device views equal
    the host tables/lengths exactly, and an un-mutated re-read returns
    the identical cached objects. A missed ``_tables_version`` /
    ``_len_version`` bump anywhere in the mutation surface fails this
    under some op sequence."""
    m, dense = _mutation_fixture()
    rng = random.Random(seed)
    kv = PagedKVCache(m, max_batch=3, max_seq=16, block_size=4, num_blocks=12)
    rid = 0
    live: dict[int, tuple] = {}  # row -> (prompt tokens, length limit)

    def check():
        _, t, l = kv.kernel_inputs()
        np.testing.assert_array_equal(np.asarray(t), kv.block_tables)
        np.testing.assert_array_equal(np.asarray(l), kv.cache_len)
        _, t2, l2 = kv.kernel_inputs()  # no mutation in between
        assert t2 is t and l2 is l, "un-mutated re-read must hit the cache"

    for _ in range(120):
        ops = []
        if kv.n_free:
            ops += ["admit"]
        if live:
            ops += ["decode", "verify", "prefill", "free", "preempt"]
        op = rng.choice(ops)
        if op == "admit":
            plen = rng.randint(1, 6)
            toks = tuple(rng.randrange(50) for _ in range(plen))
            budget = rng.randint(1, 6)
            r = kv.try_admit(rid, toks, budget=budget)
            rid += 1
            if r is not None:
                live[r[0]] = (toks, plen + budget)
        elif op == "decode":
            row = rng.choice(sorted(live))
            if int(kv.cache_len[row]) < live[row][1]:
                kv.ensure_tail(row)
                check()
                kv.advance(row)
        elif op == "verify":
            # verify-style burst: claim + advance n, rewind a rejected tail
            row = rng.choice(sorted(live))
            room = live[row][1] - int(kv.cache_len[row])
            if room > 0:
                n = rng.randint(1, min(3, room))
                kv.ensure_tail_n(row, n)
                check()
                kv.advance_n(row, n)
                check()
                k = rng.randint(0, n)
                if k:
                    kv.truncate_row(row, k)
        elif op == "prefill":
            row = rng.choice(sorted(live))
            kv.write_prefill(row, dense)
        elif op == "free":
            row = rng.choice(sorted(live))
            kv.free_row(row)
            del live[row]
        elif op == "preempt":
            row = rng.choice(sorted(live))
            kv.preempt_row(row, tokens=live[row][0] if rng.random() < 0.5 else None)
            del live[row]
        check()
        kv.check_invariants()


# ---------------------------------------------------------------------------
# write_prefill: the device install against the numpy install it replaced


def _host_install(kv, pool, row, dense, skip_blocks):
    """The numpy install ``write_prefill`` used to run (dense cache to
    the host, slice, pad by repeating the last block with its own
    payload, scatter): the bits the device program must reproduce."""
    bs = kv.block_size
    n_prompt = kv.blocks_for(int(kv.cache_len[row]))
    ids = kv._row_blocks[row][skip_blocks:n_prompt]
    out = {name: np.array(leaf) for name, leaf in pool.items()}
    if not ids:
        return out
    pad = kv.blocks_per_row - len(ids)
    idx = kv._slot[np.array(list(ids) + [ids[-1]] * pad, np.int32)]
    for name, leaf in out.items():
        d = np.asarray(dense[name])
        if is_scale(name):
            d = d[..., None]
        L, _, Sd = d.shape[:3]
        src = d.reshape((L, Sd // bs, bs) + d.shape[3:])[:, skip_blocks:n_prompt]
        src = np.concatenate([src, np.repeat(src[:, -1:], pad, axis=1)], axis=1)
        leaf[:, idx] = np.asarray(to_stored(src, leaf.shape[-1] // d.shape[-1])).astype(leaf.dtype)
    return out


def _random_like(tree, rng):
    """Random values in each leaf's shape and dtype (int8 leaves over
    their whole range), so a misplaced or missing write cannot hide
    behind zeros."""
    def one(leaf):
        if jnp.issubdtype(leaf.dtype, jnp.integer):
            return jnp.asarray(rng.integers(-128, 128, leaf.shape).astype(leaf.dtype))
        return jnp.asarray(rng.standard_normal(leaf.shape), leaf.dtype)
    return {name: one(leaf) for name, leaf in tree.items()}


_INSTALL_CASES = ["boundary", "mid_block", "prefix_hit", "straddle", "pool_end"]


@pytest.mark.parametrize("kv_quant", [False, True], ids=["", "int8"])
@pytest.mark.parametrize("case", _INSTALL_CASES)
def test_device_install_matches_host_install(case, kv_quant):
    """Every pool leaf after ``write_prefill`` equals, bit for bit, what
    the numpy host install made of the same pool: the fresh blocks hold
    the dense cache's blocks, and the shared prefix blocks of a hit,
    other rows' blocks, the null block and the spare slot are untouched
    (padded entries write nothing). Prompts end on a block boundary and
    mid-block; int8-KV pools install four leaves. In a pool of 300
    blocks, the row's blocks straddle block 128, or sit near the pool's
    end."""
    import dataclasses

    cfg = dataclasses.replace(get_config("smollm-135m").reduced(), kv_quant=kv_quant)
    m = Model(cfg)
    bs, max_seq = 4, 32
    rng = np.random.default_rng(7)
    big = case in ("straddle", "pool_end")
    kv = PagedKVCache(m, max_batch=3, max_seq=max_seq, block_size=bs,
                      num_blocks=300 if big else 20)
    kv.pool = _random_like(kv.pool, rng)
    if big:  # the allocator hands out the lowest free ids: skip past some
        for _ in range(123 if case == "straddle" else 280):
            kv.allocator.alloc()
    assert len(kv.pool) == (4 if kv_quant else 2)
    # another row holds data first, so its blocks must survive the install
    other = tuple(range(100, 100 + 3 * bs))
    r0, _ = kv.try_admit(0, other, budget=4)
    kv.write_prefill(r0, _random_like(m.init_cache(1, max_seq), rng))
    plen = {"mid_block": 3 * bs + 3, "prefix_hit": 3 * bs + 2}.get(case, 3 * bs)
    prompt = tuple(range(1, plen + 1))
    if case == "prefix_hit":
        ra, _ = kv.try_admit(1, prompt[: 2 * bs] + (999,) * bs, budget=4)
        kv.write_prefill(ra, _random_like(m.init_cache(1, max_seq), rng))
    row, hit = kv.try_admit(2, prompt, budget=4)
    skip = len(hit)
    assert skip == (2 if case == "prefix_hit" else 0)
    dense = _random_like(m.init_cache(1, max_seq), rng)
    want = _host_install(kv, kv.pool, row, dense, skip)
    before = {name: np.array(leaf) for name, leaf in kv.pool.items()}
    kv.write_prefill(row, dense, skip_blocks=skip)
    fresh = kv._slot[kv._row_blocks[row][skip:]]
    if case == "straddle":
        assert fresh.min() < 128 <= fresh.max()
    if case == "pool_end":
        assert fresh.min() >= 256
    for name, leaf in kv.pool.items():
        got = np.asarray(leaf)
        np.testing.assert_array_equal(got, want[name], err_msg=name)
        untouched = np.setdiff1d(np.arange(kv.total_blocks), fresh)
        np.testing.assert_array_equal(got[:, untouched], before[name][:, untouched])
        assert not np.array_equal(got[:, fresh], before[name][:, fresh]), name


class _NumpyRefusingDeviceArrays:
    """``numpy`` as ``serve/kv_cache.py`` sees it, refusing to convert a
    ``jax.Array``: the CPU backend does not enforce the transfer guard."""

    def __getattr__(self, name):
        return getattr(np, name)

    def asarray(self, a, *args, **kw):
        assert not isinstance(a, jax.Array), "device-to-host copy"
        return np.asarray(a, *args, **kw)

    array = asarray


def test_device_install_no_host_trip_one_compile(monkeypatch):
    """``write_prefill`` never copies device data to the host, and one
    compiled program serves every prompt length and prefix-hit skip."""
    from repro.serve import kv_cache

    m, dense = _mutation_fixture()  # prefill cache over max_seq 16
    kv = PagedKVCache(m, max_batch=4, max_seq=16, block_size=4, num_blocks=24)
    kv._install.clear_cache()  # jit wrappers of one function share a cache
    monkeypatch.setattr(kv_cache, "np", _NumpyRefusingDeviceArrays())
    with jax.transfer_guard_device_to_host("disallow"):
        for rid, plen in enumerate((3, 4, 9, 16)):
            row, hit = kv.try_admit(rid, tuple(range(plen)), budget=0)
            kv.write_prefill(row, dense, skip_blocks=len(hit))
        # a prefix hit over two blocks of rid 2's prompt
        kv.free_row(0)
        row, hit = kv.try_admit(9, tuple(range(8)) + (40, 41, 42), budget=0)
        assert len(hit) == 2
        kv.write_prefill(row, dense, skip_blocks=len(hit))
    assert kv._install._cache_size() == 1


def test_paged_cache_rejects_non_attention_family():
    cfg = get_config("mamba2-370m").reduced()
    m = Model(cfg)
    with pytest.raises(ValueError, match="attention family"):
        PagedKVCache(m, max_batch=2, max_seq=16, block_size=4)


def test_prefill_with_prefix_rejects_token_divergent_families():
    """The model-level guard mirrors PREFIX_FAMILIES: MoE capacity
    routing (and VLM patch rows) would make suffix prefill diverge from
    the cold run, so a direct call must fail loudly, like int8-KV."""
    moe = Model(get_config("granite-moe-1b-a400m").reduced())
    with pytest.raises(ValueError, match="token-identical"):
        moe.prefill_with_prefix(None, None, None, None, 16)


# ---------------------------------------------------------------------------
# differential: paged == slotted == fixed-batch generate (greedy)


def _trace(prompts, lens, budgets, eos=None, eos_req=None):
    return [
        Request(
            prompt=np.asarray(prompts[i, : lens[i]]),
            max_new_tokens=budgets[i],
            arrival_time=0.01 * i,
            eos_id=eos if i == eos_req else None,
        )
        for i in range(len(lens))
    ]


@pytest.mark.parametrize("seed", [0, 3])
def test_differential_paged_vs_slotted_vs_generate(served, seed):
    """Randomized open-loop trace — staggered arrivals, divergent prompt
    lengths and budgets, one EOS early finish — decodes token-identical
    through the paged engine, the slotted engine, and the per-request
    fixed-batch ``generate()`` baseline."""
    _, m, params, prompts = served
    rng = np.random.default_rng(seed)
    n = 5
    lens = rng.integers(3, 16, size=n)
    budgets = rng.integers(2, 7, size=n)

    # per-request fixed-batch baselines (and an EOS from request 0's
    # stream so one request finishes early through a real token match)
    eng = ServingEngine(m, params, max_seq=64)
    bases = [
        np.asarray(eng.generate(prompts[i : i + 1, : lens[i]], n_steps=int(budgets[i]))[0])
        for i in range(n)
    ]
    eos = int(bases[0][min(1, budgets[0] - 1)])
    cut = int(np.argmax(bases[0] == eos))  # first occurrence
    expected = [b if i != 0 else b[: cut + 1] for i, b in enumerate(bases)]

    slotted = ServingEngine(m, params, max_seq=64)
    out_slot = slotted.serve(_trace(prompts, lens, budgets, eos, 0), max_batch=3)
    paged = ServingEngine(m, params, max_seq=64, kv_layout="paged", block_size=4)
    reqs = _trace(prompts, lens, budgets, eos, 0)
    sched = paged.scheduler(3)
    out_paged = sched.run(reqs)
    sched.kv.check_invariants()

    # rids increment in creation order, so sorting aligns with expected
    for i, (_rid, out) in enumerate(sorted(out_slot.items())):
        np.testing.assert_array_equal(out, expected[i])
    for i, req in enumerate(reqs):
        np.testing.assert_array_equal(out_paged[req.rid], expected[i])
        assert req.finished and req.ttft_ms is not None


def test_paged_matches_slotted_with_int8_kv(served):
    """The paged gather/scatter treats every seq-indexed leaf uniformly,
    so the int8 KV cache (values + scales) pages bit-identically; with
    distinct prompts the prefix cache (now live for int8 too) never
    hits, so reuse cannot perturb this differential."""
    import dataclasses

    cfg, _, _, prompts = served
    qcfg = dataclasses.replace(cfg, kv_quant=True)
    m = Model(qcfg)
    params, _ = m.init(jax.random.key(0))
    base = ServingEngine(m, params, max_seq=32).generate(prompts[:2, :8], n_steps=4)
    eng = ServingEngine(m, params, max_seq=32, kv_layout="paged", block_size=8)
    reqs = [
        Request(prompt=prompts[i, :8], max_new_tokens=4, arrival_time=0.01 * i)
        for i in range(2)
    ]
    sched = eng.scheduler(2)
    out = sched.run(reqs)
    assert sched.kv.prefix is not None  # int8 KV participates in reuse now
    assert all(r.prefix_hit == 0 for r in reqs)  # …but distinct prompts miss
    for i, r in enumerate(reqs):
        np.testing.assert_array_equal(out[r.rid], np.asarray(base[i]))


def test_int8_prefix_reuse_matches_cold_prefill(served):
    """Shared-prefix reuse on the int8 cache: hit blocks dequantize into
    the suffix path (a ≤1/254 relative perturbation vs the fp rows the
    cold run attended — approximate by design, see DESIGN.md §3.1) and
    the refill requantizes idempotently. On this config the greedy
    tokens match a cold, reuse-off run of the same int8 engine."""
    import dataclasses

    cfg, _, _, prompts = served
    qcfg = dataclasses.replace(cfg, kv_quant=True)
    m = Model(qcfg)
    params, _ = m.init(jax.random.key(0))
    p1 = np.asarray(prompts[0])  # 16 tokens
    p2 = np.concatenate([p1[:12], np.asarray(prompts[1, :4])])  # 75% shared

    cold = ServingEngine(
        m, params, max_seq=64, kv_layout="paged", block_size=4, prefix_cache=False
    )
    r = Request(prompt=p1, max_new_tokens=4)
    cold1 = cold.serve([r], max_batch=2)[r.rid]
    r = Request(prompt=p2, max_new_tokens=4)
    cold2 = cold.serve([r], max_batch=2)[r.rid]

    eng = ServingEngine(m, params, max_seq=64, kv_layout="paged", block_size=4)
    sched = eng.scheduler(2)
    r1 = Request(prompt=p1, max_new_tokens=4)
    r2 = Request(prompt=p2, max_new_tokens=4)
    out = sched.run([r1, r2])
    sched.kv.check_invariants()
    assert r1.prefix_hit == 0 and r2.prefix_hit == 12
    np.testing.assert_array_equal(out[r1.rid], cold1)
    np.testing.assert_array_equal(out[r2.rid], cold2)


def test_paged_eviction_under_block_pressure_stays_correct(served):
    """A pool with barely enough blocks forces LRU eviction of cached
    prompt blocks while serving; outputs still match the baselines and
    admission never deadlocks."""
    _, m, params, prompts = served
    lens, budgets = (12, 8, 14), (4, 6, 3)
    eng = ServingEngine(m, params, max_seq=32)
    bases = [
        np.asarray(eng.generate(prompts[i : i + 1, : lens[i]], n_steps=budgets[i])[0])
        for i in range(3)
    ]
    paged = ServingEngine(
        m, params, max_seq=32, kv_layout="paged", block_size=4, num_blocks=6
    )
    reqs = _trace(prompts, lens, budgets)
    sched = paged.scheduler(2)
    out = sched.run(reqs)
    sched.kv.check_invariants()
    for i, r in enumerate(reqs):
        np.testing.assert_array_equal(out[r.rid], bases[i])


# ---------------------------------------------------------------------------
# prefix-cache semantics


def test_shared_prefix_hit_length_tokens_and_eviction(served):
    """Two requests sharing a 75% prefix: the second reports the expected
    block-granular hit, decodes token-identical to its cold run, and
    evicting the cached blocks restores the cold path."""
    _, m, params, prompts = served
    p1 = np.asarray(prompts[0])  # 16 tokens
    p2 = np.concatenate([p1[:12], np.asarray(prompts[1, :4])])  # 75% shared

    cold = ServingEngine(m, params, max_seq=64, kv_layout="paged", prefix_cache=False)
    cold1 = cold.serve([r := Request(prompt=p1, max_new_tokens=4)], max_batch=2)[r.rid]
    cold2 = cold.serve([r := Request(prompt=p2, max_new_tokens=4)], max_batch=2)[r.rid]
    assert r.prefix_hit == 0

    eng = ServingEngine(m, params, max_seq=64, kv_layout="paged", block_size=4)
    sched = eng.scheduler(2)
    r1 = Request(prompt=p1, max_new_tokens=4)
    r2 = Request(prompt=p2, max_new_tokens=4)
    out = sched.run([r1, r2])  # same wave: r2 admits after r1 registers
    assert r1.prefix_hit == 0
    assert r2.prefix_hit == 12  # 3 shared blocks of 4 = the 75% prefix
    np.testing.assert_array_equal(out[r1.rid], cold1)
    np.testing.assert_array_equal(out[r2.rid], cold2)
    assert eng.stats.n_prefix_hits == 1
    assert eng.stats.prefix_hit_tokens == 12
    assert eng.stats.serving_summary()["prefix_hit_rate"] == pytest.approx(12 / 32)

    # retired prompts stay cached (parked): a re-run of p2 hits its own
    # full-block prefix now, not just the shared 12
    r3 = Request(prompt=p2, max_new_tokens=4)
    out3 = sched.run([r3])
    assert r3.prefix_hit == 12  # cap (16-1)//4 = 3 blocks
    np.testing.assert_array_equal(out3[r3.rid], cold2)

    # eviction after free restores the cold path exactly
    assert sched.kv.drop_cached() > 0
    sched.kv.check_invariants()
    r4 = Request(prompt=p2, max_new_tokens=4)
    out4 = sched.run([r4])
    assert r4.prefix_hit == 0
    np.testing.assert_array_equal(out4[r4.rid], cold2)


def test_prefix_reuse_across_staggered_arrivals_drops_prefill_cost(served):
    """Later arrivals over a common prompt header hit the cache while the
    first holder is still decoding (live sharing, refcount > 1)."""
    _, m, params, prompts = served
    head = np.asarray(prompts[2])  # 16-token shared header
    reqs = [
        Request(
            prompt=np.concatenate([head, np.asarray(prompts[3 + i, :4])]),
            max_new_tokens=6,
            arrival_time=0.005 * i,
        )
        for i in range(3)
    ]
    eng = ServingEngine(m, params, max_seq=64, kv_layout="paged", block_size=4)
    sched = eng.scheduler(4)
    sched.run(reqs)
    assert reqs[0].prefix_hit == 0
    assert all(r.prefix_hit == 16 for r in reqs[1:])  # whole shared header
    assert eng.stats.prefix_hit_rate == pytest.approx(32 / 60)
    sched.kv.check_invariants()


# ---------------------------------------------------------------------------
# block-granular admission control


def test_submit_rejects_block_budget_beyond_pool(served):
    """A request whose block need can never fit the pool is rejected at
    submit() — in blocks, not tokens — instead of deadlocking the FIFO
    queue."""
    _, m, params, _ = served
    eng = ServingEngine(
        m, params, max_seq=32, kv_layout="paged", block_size=4, num_blocks=4
    )
    req = Request(prompt=jnp.ones((20,), jnp.int32), max_new_tokens=8)
    with pytest.raises(ValueError, match=r"needs 7 KV blocks .* 4 blocks total"):
        eng.serve([req], max_batch=1)
    # row capacity still guards first (max_seq semantics preserved)
    with pytest.raises(ValueError, match="max_seq"):
        eng.serve([Request(prompt=jnp.ones((30,), jnp.int32), max_new_tokens=8)], max_batch=1)


def test_paged_scheduler_rejects_decode_plan(served):
    _, m, params, _ = served
    from repro.core.plan import plan_for

    plan = plan_for("paged-no-plan", lambda x: x, jnp.arange(4.0), granularity=1)
    eng = ServingEngine(m, params, max_seq=32, kv_layout="paged")
    eng.set_decode_plan(plan)
    with pytest.raises(ValueError, match="slotted layout"):
        eng.scheduler(2)
