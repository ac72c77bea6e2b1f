"""KV-cache pools for continuous batching: slotted and block-paged.

``SlotKVCache`` owns a fixed pool of ``max_batch`` decode-cache slots
(one ``Model.init_cache(max_batch, max_seq)`` allocation, made once).
Slots are allocated when a request is admitted and freed when it
finishes or hits EOS; the decode step always runs over the *whole*
pool, so its jit shape never changes — liveness is the ``live_mask``
the masked plan execution consumes (DESIGN.md §3).

``PagedKVCache`` replaces the slot's monolithic ``max_seq`` reservation
with block-granular memory: a ``BlockAllocator`` pool of fixed-size
blocks, a per-row *block table* mapping logical token positions to
physical blocks, and (for dense/audio families) a trie-based
``PrefixCache`` that lets requests whose prompts share a token prefix
alias the same immutable blocks instead of recomputing them. Admission
charges blocks (worst case reserved, physical blocks allocated lazily
as decode crosses block boundaries), so footprint scales with actual
lengths, not ``max_seq`` (DESIGN.md §3 "Paged cache & prefix reuse").

All per-family cache logic rides on ``Model`` metadata
(``cache_batch_axes`` / ``read_cache_slot`` / ``write_cache_slot`` for
slots, ``init_paged_cache`` / ``paged_view`` / ``decode_step_paged``
for pages), so this module never inspects cache leaves itself.
"""
from __future__ import annotations

import bisect
import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import attention as attn
from repro.models.model import PREFIX_FAMILIES
from repro.serve.block import BlockAllocator, PrefixCache


def local_table_view(tables, nb_loc: int, rank):
    """Per-rank view of the replicated block tables under the kv-sequence
    split (jit/shard_map-traceable; called from ``attention_block``).

    ``PagedKVCache`` lays pool slots out in ``seq_shards`` contiguous
    shards of ``nb_loc`` slots, each ending in one reserved scratch slot
    (never the image of any allocator/null id). Rank ``r`` owns global
    slots ``[r·nb_loc, (r+1)·nb_loc)``; its local view maps owned
    entries to their in-shard offset and redirects every unowned entry
    to the rank's scratch slot ``nb_loc - 1`` — a safe DMA source (its
    positions are skipped via ``owned``) and a safe write target (the
    owner rank writes the real data; everyone else clobbers scratch).
    Returns ``(local_tables [B, MB], owned [B, MB] bool)``."""
    owned = (tables // nb_loc) == rank
    local = jnp.where(owned, tables % nb_loc, nb_loc - 1)
    return local.astype(tables.dtype), owned


def install_prefill_blocks(pool, dense, src, dst, *, seq_axis=None):
    """Copy blocks ``src`` of a batch=1 dense cache into pool slots
    ``dst`` (traced under ``PagedKVCache._install``, pool donated).

    Dense leaves ``[L, 1, S, KV(, hd)]`` are cut into blocks ``[L, S/BS,
    BS, KV(, hd)]``; the ``src`` blocks are taken and put in the pool's
    stored form (``attention.to_stored``: a prompt-sized relayout, never
    a pool-sized one), then scattered whole into the pool at ``dst``.
    ``src``/``dst`` are int32 ``[n]``; a ``dst`` past the pool's last
    slot is a pad that writes nothing. A stored leaf keeps its block
    axis major (DESIGN.md §4), so each block written is one contiguous
    run of the leaf per layer and the rest of the pool stays where it
    is. Under the kv-sequence split (``seq_axis``, inside the pool's
    ``shard_map``) each rank installs the blocks of its own shard."""
    out = {}
    for name, leaf in pool.items():
        d = dense[name]
        if attn.is_scale(name):
            d = d[..., None]
        L, _, S = d.shape[:3]
        R, W = leaf.shape[3:]
        p = W // d.shape[-1]
        blocks = d.reshape((L, S // (R * p), R * p) + d.shape[3:])
        payload = attn.to_stored(jnp.take(blocks, src, axis=1, mode="clip"), p)
        nb = leaf.shape[1]
        local = dst if seq_axis is None else dst - jax.lax.axis_index(seq_axis) * nb
        local = jnp.where((local >= 0) & (local < nb), local, nb)  # the rest drop
        out[name] = leaf.at[:, local].set(payload.astype(leaf.dtype), mode="drop")
    return out


class SlotKVCache:
    """Fixed pool of cache slots: allocate on admit, free on finish."""

    def __init__(self, model, max_batch: int, max_seq: int, dtype=None):
        self.model = model
        self.max_batch = int(max_batch)
        self.max_seq = int(max_seq)
        if self.max_batch < 1:
            raise ValueError("max_batch must be >= 1")
        self.cache = model.init_cache(self.max_batch, self.max_seq, dtype=dtype)
        self._free: list[int] = list(range(self.max_batch))  # ascending
        self._owner: list[Optional[int]] = [None] * self.max_batch  # slot → rid

    # ------------------------------------------------------------------
    # occupancy
    @property
    def n_free(self) -> int:
        return len(self._free)

    @property
    def n_live(self) -> int:
        return self.max_batch - len(self._free)

    @property
    def occupancy(self) -> float:
        """Pool-pressure gauge in [0, 1]: fraction of slots live (the
        slotted layout's only capacity axis)."""
        return self.n_live / self.max_batch

    def owner(self, slot: int) -> Optional[int]:
        return self._owner[slot]

    def live_mask(self):
        """[max_batch] bool — which slots hold live requests."""
        import numpy as np

        return np.array([o is not None for o in self._owner])

    def live_slots(self) -> list[int]:
        return [i for i, o in enumerate(self._owner) if o is not None]

    # ------------------------------------------------------------------
    # slot lifecycle
    def alloc(self, rid: int) -> int:
        """Claim the lowest free slot for request ``rid``."""
        if not self._free:
            raise RuntimeError("no free cache slot (pool exhausted)")
        slot = self._free.pop(0)
        self._owner[slot] = rid
        return slot

    def free(self, slot: int) -> None:
        if not 0 <= slot < self.max_batch:
            raise IndexError(f"slot {slot} out of range")
        if self._owner[slot] is None:
            raise RuntimeError(f"double free of slot {slot}")
        self._owner[slot] = None
        bisect.insort(self._free, slot)

    # ------------------------------------------------------------------
    # cache I/O (family-agnostic, via the model's batch-axis metadata)
    def write(self, slot: int, slot_cache) -> None:
        """Install a batch=1 cache (a request's prefill) into ``slot``."""
        if self._owner[slot] is None:
            raise RuntimeError(f"write into free slot {slot}")
        self.cache = self.model.write_cache_slot(self.cache, slot_cache, slot)

    def read(self, slot: int):
        """Slot ``slot`` as a batch=1 cache."""
        return self.model.read_cache_slot(self.cache, slot)

    # ------------------------------------------------------------------
    # speculative rollback
    def truncate_row(self, slot: int, n_rejected: int) -> None:
        """Rewind ``n_rejected`` rejected speculative entries off slot
        ``slot``: the committed length drops; the stale KV rows past it
        are masked off by ``len`` and overwritten by later writes, so
        the values themselves need no cleanup (DESIGN.md §3.2)."""
        if self._owner[slot] is None:
            raise RuntimeError(f"truncate of free slot {slot}")
        new_len = jnp.maximum(self.cache["len"][slot] - int(n_rejected), 0)
        self.cache["len"] = self.cache["len"].at[slot].set(new_len)

    def truncate_rows(self, n_rejected) -> None:
        """Vectorized rewind: ``n_rejected`` [max_batch] entries come
        off every row's length in one update (dead and just-retired
        rows pass the full verify width, so their lengths return to the
        pre-verify value and never drift)."""
        vec = jnp.asarray(np.asarray(n_rejected, np.int32))
        self.cache["len"] = jnp.maximum(self.cache["len"] - vec, 0)

    # ------------------------------------------------------------------
    # preemption (uniform scheduler call; the slotted layout has no
    # prefix trie, so eviction just frees — resume is a cold re-prefill)
    def preempt_row(self, slot: int, tokens=None) -> None:
        del tokens  # no trie to register committed work into
        self.free(slot)

    # ------------------------------------------------------------------
    def check_invariants(self) -> None:
        """Free slots and live slots partition the pool; the free list is
        sorted and duplicate-free (used by the property tests)."""
        live = {i for i, o in enumerate(self._owner) if o is not None}
        free = set(self._free)
        assert len(self._free) == len(free), "duplicate in free list"
        assert not (free & live), "slot both free and live"
        assert free | live == set(range(self.max_batch)), "slot leaked"
        assert self._free == sorted(self._free), "free list unsorted"


class PagedKVCache:
    """Block-paged KV cache with shared-prefix reuse.

    ``max_batch`` decode *rows* (the fixed jit batch, like slots) map
    through per-row block tables into a pool of ``num_blocks`` physical
    blocks of ``block_size`` tokens. Admission charges the worst-case
    block budget (so lazy tail-block allocation can never fail
    mid-decode), but physical blocks are claimed only as the request
    actually reaches them — footprint scales with real lengths, and
    prefix-shared blocks are charged once.
    """

    def __init__(
        self,
        model,
        max_batch: int,
        max_seq: int,
        *,
        block_size: int = 8,
        num_blocks: Optional[int] = None,
        prefix_cache: bool = True,
        dtype=None,
        mesh=None,
        metrics=None,
    ):
        self.model = model
        self.max_batch = int(max_batch)
        self.max_seq = int(max_seq)
        self.block_size = int(block_size)
        if self.max_batch < 1:
            raise ValueError("max_batch must be >= 1")
        if self.max_seq % self.block_size:
            raise ValueError(
                f"max_seq={max_seq} must be a multiple of block_size={block_size}"
            )
        self.blocks_per_row = self.max_seq // self.block_size
        if num_blocks is None:
            num_blocks = self.max_batch * self.blocks_per_row
        self.num_blocks = int(num_blocks)
        # Physical slot layout. The allocator hands out ids [0, num_blocks)
        # plus the null id num_blocks; ``_slot`` maps ids onto pool slots.
        # Single-shard (no seq axis): identity, one spare slot past the
        # allocator's range — unowned block-table entries point here, so
        # dead rows' decode writes land in scratch. kv-sequence split
        # (mesh with a "seq" axis of size sp > 1): the pool's block dim is
        # partitioned over sp contiguous shards, and ids are laid out so
        # every shard ends in one reserved scratch slot that is never the
        # image of any id — per-rank table views (``local_table_view``)
        # redirect unowned entries there, so foreign-rank writes always
        # land in rank-local scratch (DESIGN.md §5).
        self.mesh = mesh
        sp = int(mesh.shape.get("seq", 1)) if mesh is not None else 1
        self.seq_shards = sp
        ids = self.num_blocks + 1  # allocator range + the null id
        if sp > 1:
            d = math.ceil(ids / sp)  # data slots per shard
            arange = np.arange(ids, dtype=np.int32)
            self._slot = ((arange // d) * (d + 1) + arange % d).astype(np.int32)
            self.total_blocks = sp * (d + 1)
        else:
            self._slot = np.arange(ids, dtype=np.int32)
            self.total_blocks = ids
        self.null_block = int(self._slot[self.num_blocks])
        self.pool = model.init_paged_cache(
            self.total_blocks, self.block_size, dtype=dtype
        )
        # serving mesh (DESIGN.md §5): allocate the pool sharded over the
        # mesh once — head-partitioned on the kv-head dim ("model") and/or
        # block-partitioned on the block dim ("seq"); the sharded step's
        # donation keeps every subsequent new_pool on the same
        # NamedSharding, so KV bytes never migrate between ranks.
        # Tables/lengths stay host-side numpy (they are data, replicated
        # on upload by the step's in_specs). One program installs every
        # prefill (write_prefill), the pool donated; under a mesh each
        # rank installs into its own pool shard, and the result is pinned
        # to the pool's shardings so the donated leaves stay where they
        # were.
        install, out_shardings = install_prefill_blocks, None
        if mesh is not None:
            from jax import shard_map
            from jax.sharding import NamedSharding
            from jax.sharding import PartitionSpec as P

            tp = int(mesh.shape.get("model", 1))
            specs = model.paged_pool_specs(
                "model" if tp > 1 else None, "seq" if sp > 1 else None
            )
            self.pool = {
                name: jax.device_put(leaf, NamedSharding(mesh, specs[name]))
                for name, leaf in self.pool.items()
            }
            head = "model" if tp > 1 else None
            dense_specs = {
                name: P(None, None, None, head) if attn.is_scale(name)
                else P(None, None, None, head, None)
                for name in specs
            }
            install = shard_map(
                functools.partial(install_prefill_blocks, seq_axis="seq" if sp > 1 else None),
                mesh=mesh,
                in_specs=(specs, dense_specs, P(), P()),
                out_specs=specs,
                check_vma=False,
            )
            out_shardings = {name: leaf.sharding for name, leaf in self.pool.items()}
        self._install = jax.jit(install, donate_argnums=0, out_shardings=out_shardings)
        cfg = model.cfg
        # PREFIX_FAMILIES lives next to the model's prefill_with_prefix,
        # which enforces the same exclusions — the two layers can't
        # drift. int8-KV participates: gather_prefix dequantizes hit
        # blocks for the suffix path, and the suffix prefill requantizes
        # (idempotently) on the way back.
        self.prefix = (
            PrefixCache(self.block_size)
            if prefix_cache and cfg.family in PREFIX_FAMILIES
            else None
        )
        # optional telemetry.MetricsRegistry (DESIGN.md §8): the
        # allocator records alloc/share/park/evict rates; this layer
        # adds trie lookup/hit counters. None (telemetry off) keeps the
        # uninstrumented path.
        self._m_lookups = metrics.counter("prefix.lookups") if metrics else None
        self._m_hit_blocks = metrics.counter("prefix.hit_blocks") if metrics else None
        self.allocator = BlockAllocator(
            self.num_blocks,
            on_evict=self.prefix.drop_block if self.prefix is not None else None,
            is_leaf=self.prefix.is_leaf if self.prefix is not None else None,
            metrics=metrics,
        )
        self.block_tables = np.full(
            (self.max_batch, self.blocks_per_row), self.null_block, np.int32
        )
        self.cache_len = np.zeros((self.max_batch,), np.int32)
        # kernel_inputs() device views, invalidated by version counter:
        # tables mutate only on admission / tail claim / truncate / free,
        # so steady-state decode re-uploads ONLY the lengths vector
        self._tables_version = 0
        self._dev_tables = None
        self._dev_tables_version = -1
        self._len_version = 0
        self._dev_len = None
        self._dev_len_version = -1
        self._row_free: list[int] = list(range(self.max_batch))  # ascending
        self._row_owner: list[Optional[int]] = [None] * self.max_batch
        self._row_blocks: list[list[int]] = [[] for _ in range(self.max_batch)]
        self._row_outstanding = [0] * self.max_batch  # reserved, unallocated
        self._outstanding_total = 0

    # ------------------------------------------------------------------
    # occupancy (row API mirrors SlotKVCache so the scheduler is shared)
    @property
    def n_free(self) -> int:
        return len(self._row_free)

    @property
    def n_live(self) -> int:
        return self.max_batch - len(self._row_free)

    @property
    def n_free_blocks(self) -> int:
        """Blocks an arriving request could claim right now: free +
        LRU-evictable cached, minus live rows' outstanding reservations."""
        return self.allocator.n_available - self._outstanding_total

    @property
    def occupancy(self) -> float:
        """Pool-pressure gauge in [0, 1]: fraction of physical blocks
        holding data (live + parked; outstanding reservations excluded
        — they are a promise, not bytes)."""
        return 1.0 - self.allocator.n_free / self.num_blocks

    def owner(self, row: int) -> Optional[int]:
        return self._row_owner[row]

    def live_mask(self):
        return np.array([o is not None for o in self._row_owner])

    def live_rows(self) -> list[int]:
        return [i for i, o in enumerate(self._row_owner) if o is not None]

    def blocks_for(self, n_tokens: int) -> int:
        return math.ceil(n_tokens / self.block_size)

    # ------------------------------------------------------------------
    # admission
    def lookup(self, tokens) -> list[int]:
        """Prefix-cache hit: block ids covering the longest cached full-
        block prefix of ``tokens`` (empty when prefix reuse is off)."""
        if self.prefix is None:
            return []
        hits = self.prefix.match(tokens)
        if self._m_lookups is not None:
            self._m_lookups.inc()
            self._m_hit_blocks.inc(len(hits))
        return hits

    def try_admit(
        self,
        rid: int,
        tokens,
        budget: int,
        n_tokens: Optional[int] = None,
        register: bool = True,
    ):
        """Admit ``rid`` into a free row if the block budget fits:
        returns (row, hit_ids) or None. Shared prefix blocks alias
        (refcount++); fresh prompt blocks are allocated now; the decode
        tail is only *reserved* (allocated lazily by ``ensure_tail``).
        ``n_tokens`` overrides the cache-row count when the prefill
        occupies more rows than ``tokens`` (VLM patch embeddings).
        ``register=False`` defers trie registration (chunked prefill:
        the prompt blocks hold no KV yet at admission — the scheduler
        calls ``register_prompt`` once the last chunk has committed, so
        a concurrent admission can never alias half-written blocks)."""
        if not self._row_free:
            return None
        S = len(tokens) if n_tokens is None else int(n_tokens)
        hit_ids = self.lookup(tokens)
        n_total = self.blocks_for(S + budget)
        n_prompt = self.blocks_for(S)
        n_parked_hits = sum(self.allocator.is_parked(b) for b in hit_ids)
        # after reactivating parked hits, enough must remain for this
        # request's fresh blocks AND every live row's reservations
        need = (n_total - len(hit_ids)) + n_parked_hits
        if self.allocator.n_available < self._outstanding_total + need:
            return None
        for b in hit_ids:  # reactivate/alias FIRST so eviction can't take them
            self.allocator.share(b)
        blocks = list(hit_ids)
        for _ in range(n_prompt - len(hit_ids)):
            blocks.append(self.allocator.alloc())
        row = self._row_free.pop(0)
        self._row_owner[row] = rid
        self._row_blocks[row] = blocks
        self._row_outstanding[row] = n_total - n_prompt
        self._outstanding_total += self._row_outstanding[row]
        self.block_tables[row, : len(blocks)] = self._slot[blocks]
        self.cache_len[row] = S
        self._tables_version += 1
        self._len_version += 1
        if register and self.prefix is not None and len(tokens) == S:
            # register the prompt's immutable full blocks (decode never
            # writes before position S, so blocks < S // bs stay frozen)
            self.prefix.insert(tokens, blocks[: S // self.block_size])
        return row, hit_ids

    def register_prompt(self, row: int, tokens) -> None:
        """Register a live row's now-written prompt blocks in the trie
        (the deferred half of ``try_admit(register=False)``). ``tokens``
        must be the prompt whose KV the row's leading blocks hold."""
        if self._row_owner[row] is None:
            raise RuntimeError(f"register_prompt on free row {row}")
        if self.prefix is None:
            return
        n_full = min(len(tokens) // self.block_size, len(self._row_blocks[row]))
        if n_full:
            self.prefix.insert(tuple(tokens)[: n_full * self.block_size],
                               self._row_blocks[row][:n_full])

    # ------------------------------------------------------------------
    # cache I/O
    def kernel_inputs(self):
        """The pool in the attention kernel's expected layout:
        ``(pool, block_tables, cache_len)`` with tables/lengths as
        device int32 arrays. Pool leaves are stored layer-stacked
        ``[L, NB+1, KV, BS/P, P·hd]`` (``Model.init_paged_cache``), which
        is exactly what ``Model.decode_step_paged``/``verify_step_paged``
        (and the block-paged Pallas kernel underneath) consume; the
        extra block is the null block dead rows write into. The steps
        take the pool donated: the caller installs the pool they return
        and never touches this one again.

        The device views are cached against mutation-version counters:
        block tables change only on admission / lazy tail claim /
        truncate / free, so a steady decode step re-uploads nothing but
        the per-row lengths vector — O(max_batch) int32 per step, not
        O(max_batch · blocks_per_row) (the regression test asserts
        table-object identity across pure-decode steps)."""
        if self._dev_tables_version != self._tables_version:
            self._dev_tables = jnp.asarray(self.block_tables)
            self._dev_tables_version = self._tables_version
        if self._dev_len_version != self._len_version:
            self._dev_len = jnp.asarray(self.cache_len)
            self._dev_len_version = self._len_version
        return (self.pool, self._dev_tables, self._dev_len)

    def gather_prefix(self, hit_ids: list[int]):
        """(k, v) [L, 1, h, KV, hd] — a hit chain's post-RoPE KV rows,
        dense, for ``Model.prefill_with_prefix``. int8 pools dequantize
        here (per-vector scales live beside the values), so the suffix
        prefill always sees dense K/V whatever the cache dtype."""
        # pad the chain to blocks_per_row (repeating the last id) so the
        # gather runs at ONE fixed shape whatever the hit length — hit
        # lengths vary request to request, and a per-length eager
        # compile would land in the serving window; the padded tail is
        # sliced off on the host
        h = len(hit_ids) * self.block_size
        ids = list(hit_ids) + [hit_ids[-1]] * (self.blocks_per_row - len(hit_ids))
        table = jnp.asarray(self._slot[np.array(ids, np.int32)][None, :])
        view = self.model.paged_view(self.pool, table)
        k, v = view["k"], view["v"]
        if self.model.cfg.kv_quant:
            dt = jnp.dtype(self.model.cfg.dtype)
            k = attn.dequantize_kv(k, view["k_scale"], dt)
            v = attn.dequantize_kv(v, view["v_scale"], dt)
        return np.asarray(k)[:, :, :h], np.asarray(v)[:, :, :h]

    def write_prefill(self, row: int, dense_cache, skip_blocks: int = 0) -> None:
        """Install a request's batch=1 dense prefill cache into its fresh
        prompt blocks. ``skip_blocks`` leading blocks are a prefix hit —
        already in the pool, shared, and immutable, so they are not
        rewritten. The copy runs on the device (``install_prefill_blocks``,
        the pool donated) and the host never waits for it."""
        if self._row_owner[row] is None:
            raise RuntimeError(f"write into free row {row}")
        n_prompt = self.blocks_for(int(self.cache_len[row]))
        ids = self._row_blocks[row][skip_blocks:n_prompt]
        if not ids:
            return
        # fixed-length block vectors, so every install — any prompt
        # length, any prefix-hit skip — runs the one compiled program:
        # pad entries read dense block 0 and point past the pool's end,
        # so they write nothing
        src = np.zeros(self.blocks_per_row, np.int32)
        dst = np.full(self.blocks_per_row, self.total_blocks, np.int32)
        src[: len(ids)] = np.arange(skip_blocks, n_prompt)
        dst[: len(ids)] = self._slot[ids]
        src, dst = jax.device_put((src, dst))
        dense = {name: dense_cache[name] for name in self.pool}
        self.pool = self._install(self.pool, dense, src, dst)

    def ensure_tail(self, row: int) -> None:
        """Make sure the row's next decode write position has a physical
        block, claiming one lazily from its reservation if not."""
        self.ensure_tail_n(row, 1)

    def ensure_tail_n(self, row: int, n: int) -> None:
        """Claim tail blocks so the row's next ``n`` write positions
        (``cache_len .. cache_len+n-1`` — a speculative verify writes
        the pending token plus K drafts at once) are all physically
        backed, drawing lazily on the admission reservation."""
        need = self.blocks_for(int(self.cache_len[row]) + n)
        while len(self._row_blocks[row]) < need:
            bi = len(self._row_blocks[row])
            assert bi < self.blocks_per_row
            assert self._row_outstanding[row] > 0, "tail block was not reserved"
            b = self.allocator.alloc()
            self._row_blocks[row].append(b)
            self.block_tables[row, bi] = self._slot[b]
            self._tables_version += 1
            self._row_outstanding[row] -= 1
            self._outstanding_total -= 1

    def advance(self, row: int) -> None:
        self.cache_len[row] += 1
        self._len_version += 1

    def advance_n(self, row: int, n: int) -> None:
        """Account ``n`` KV entries written by one verify call (the
        pending token + K drafts); ``truncate_row`` then rewinds the
        rejected tail."""
        self.cache_len[row] += n
        self._len_version += 1

    def truncate_row(self, row: int, n_rejected: int) -> None:
        """Rewind ``n_rejected`` rejected draft entries off the row's
        tail: the committed length drops, and claimed tail blocks past
        the new length are un-claimed — returned to the allocator with
        their worst-case reservation restored, so a later verify can
        claim them again. Only exclusively-owned, unregistered tail
        blocks can ever be released: verify writes land strictly past
        the prompt, so the rewind is bounded above the shared/registered
        prefix blocks (asserted)."""
        if self._row_owner[row] is None:
            raise RuntimeError(f"truncate of free row {row}")
        new_len = int(self.cache_len[row]) - int(n_rejected)
        assert new_len >= 0, "truncate below zero"
        self.cache_len[row] = new_len
        self._len_version += 1
        keep = self.blocks_for(new_len)
        while len(self._row_blocks[row]) > keep:
            b = self._row_blocks[row].pop()
            assert self.allocator.refcount[b] == 1 and (
                self.prefix is None or not self.prefix.registered(b)
            ), "truncate reached a shared/registered block"
            self.allocator.free(b)
            self.block_tables[row, len(self._row_blocks[row])] = self.null_block
            self._tables_version += 1
            self._row_outstanding[row] += 1
            self._outstanding_total += 1

    # ------------------------------------------------------------------
    def free_row(self, row: int) -> None:
        """Retire a request: drop one referent per block (shared prefix
        blocks survive under their other referents; registered blocks
        with no referents park in the LRU bench for future prefix hits),
        release the unclaimed reservation, reset the table row."""
        if not 0 <= row < self.max_batch:
            raise IndexError(f"row {row} out of range")
        if self._row_owner[row] is None:
            raise RuntimeError(f"double free of row {row}")
        for b in self._row_blocks[row]:
            self.allocator.free(
                b, park=self.prefix is not None and self.prefix.registered(b)
            )
        self._outstanding_total -= self._row_outstanding[row]
        self._row_outstanding[row] = 0
        self._row_blocks[row] = []
        self._row_owner[row] = None
        self.block_tables[row, :] = self.null_block
        self.cache_len[row] = 0
        self._tables_version += 1
        self._len_version += 1
        bisect.insort(self._row_free, row)

    def preempt_row(self, row: int, tokens=None) -> None:
        """Evict a live row under block pressure, keeping its work.

        ``tokens`` (prompt + committed generated tokens) registers the
        row's full blocks in the prefix trie *before* the row frees, so
        they park instead of vanishing: resumption prefix-matches the
        whole committed history and recomputes only the partial tail
        block — suffix-only recompute, not a cold prefill. Blocks whose
        chain already exists in the trie keep their first registration
        (``PrefixCache.insert`` dedups); such duplicates stay private
        and return to the free list. Without ``tokens`` (or without a
        trie) this is a plain eviction."""
        if self._row_owner[row] is None:
            raise RuntimeError(f"preempt of free row {row}")
        if self.prefix is not None and tokens is not None:
            n_full = min(len(tokens) // self.block_size, len(self._row_blocks[row]))
            if n_full:
                self.prefix.insert(
                    tuple(tokens)[: n_full * self.block_size],
                    self._row_blocks[row][:n_full],
                )
        self.free_row(row)

    def drop_cached(self) -> int:
        """Evict every parked (cached, unreferenced) block — test/ops
        hook that restores the cold path. Returns how many were evicted."""
        n = 0
        while self.allocator.n_parked:
            self.allocator.evict(self.allocator.parked_lru()[0])
            n += 1
        return n

    # ------------------------------------------------------------------
    def check_invariants(self) -> None:
        """Rows and blocks stay consistent: the allocator partition
        holds, per-row tables mirror the owned-block lists, every block
        referent is exactly one row, and reservations never exceed what
        the allocator can still provide."""
        self.allocator.check_invariants()
        live_rows = {i for i, o in enumerate(self._row_owner) if o is not None}
        free_rows = set(self._row_free)
        assert not (free_rows & live_rows), "row both free and live"
        assert free_rows | live_rows == set(range(self.max_batch)), "row leaked"
        refs = [0] * self.num_blocks
        for row in range(self.max_batch):
            blocks = self._row_blocks[row]
            if row not in live_rows:
                assert not blocks and self._row_outstanding[row] == 0
            for j, b in enumerate(blocks):
                assert self.block_tables[row, j] == self._slot[b], (
                    "table/block-list skew"
                )
                refs[b] += 1
            assert (self.block_tables[row, len(blocks):] == self.null_block).all()
        assert refs == self.allocator.refcount, "refcounts not conserved"
        assert self._outstanding_total == sum(self._row_outstanding)
        assert self.allocator.n_available >= self._outstanding_total, (
            "reserved more blocks than the pool can still provide"
        )
