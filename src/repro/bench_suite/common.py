"""Shared benchmark scaffolding + array-encoded linked data structures.

Each paper benchmark provides:
  build(key)        → data dict (index-array encoded linked structures)
  items(data)       → leading-axis work items of one iteration
  item_fn(data)     → per-item function (the annotated region)
  cost(data)        → per-item Microtask parameters (flops, bytes, chain)
  trace(data)       → MemoryTrace of dynamic accesses (DynamoRIO analogue)
  realized_* fields → the Relic-API granularity floor + locality penalty
                      used when a region is force-parallelized below its
                      band (paper's 1-Hop/BVH outcome)

Pointer-chasing on TPU: linked structures are index arrays, traversals
are bounded ``lax.scan``/``while_loop`` over node indices (DESIGN.md §2).
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

BENCHMARKS: dict[str, "Benchmark"] = {}


@dataclass
class Benchmark:
    name: str
    domain: str
    build: Callable
    items: Callable
    item_fn: Callable
    cost: Callable  # data -> dict(flops, bytes, chain, vector)
    trace: Optional[Callable] = None
    combine: str = "sum"
    # paper §VII outcome modeling:
    force: bool = False  # 1-Hop/BVH: applied despite the band
    realized_granularity: int = 0  # Relic API floor when forced (0 = free)
    locality_penalty: float = 0.0  # chain/bytes inflation when forced

    def region(self, data, combine: str | None = None):
        """The benchmark's annotated region, ready for the tool pipeline.
        combine=None → the benchmark's declared combine mode."""
        from repro.core.adviser import Region

        c = self.cost(data)
        return Region(
            name=self.name,
            fn=self.item_fn(data),
            items=self.items(data),
            task_flops=c["flops"],
            task_bytes=c["bytes"],
            task_chain=c["chain"],
            vector=c.get("vector", True),
            trace=self.trace(data) if self.trace else None,
            force=self.force,
            combine=self.combine if combine is None else combine,
        )

    def workload(self, data, combine: str | None = None):
        from repro.core.adviser import Workload

        return Workload(
            name=self.name,
            serial_fn=lambda: self.serial_value(data, combine=combine),
            regions=[self.region(data, combine=combine)],
        )

    def serial_value(self, data, combine: str | None = None):
        """One measurement iteration, serial semantics. combine="sum"
        reduces per-item results the way the declared region does."""
        fn = self.item_fn(data)
        its = self.items(data)
        out = jax.lax.map(fn, its)
        if combine == "sum":
            return jax.tree.map(lambda y: y.sum(axis=0), out)
        return out

    def parallel_value(self, data, granularity=8, combine: str | None = None):
        """The restructured iteration, through the cached plan layer.

        combine=None → "stack" (item order preserved, elementwise-
        comparable to serial_value). Under an outer trace the plan cache
        is bypassed — caching a closure over tracers would leak them.
        """
        from repro.core import plan as plan_mod
        from repro.core.relic import relic_pfor

        fn = self.item_fn(data)
        its = self.items(data)
        comb = combine or "stack"
        if any(isinstance(l, jax.core.Tracer) for l in jax.tree.leaves((its, data))):
            return relic_pfor(fn, its, granularity=granularity, combine=comb)
        plan = plan_mod.plan_for(
            self.name,
            fn,
            its,
            granularity=granularity,
            combine=comb,
            salt=plan_mod.data_fingerprint(data),
        )
        return plan.execute(its)


def register(b: Benchmark) -> Benchmark:
    BENCHMARKS[b.name] = b
    return b


# ---------------------------------------------------------------------------
# array-encoded structures (numpy build side)


def build_kdtree(points: np.ndarray):
    """Balanced KD-tree as arrays: returns dict(point, left, right, axis)."""
    n = len(points)
    left = np.full(n, -1, np.int32)
    right = np.full(n, -1, np.int32)
    axis = np.zeros(n, np.int32)
    pts = np.asarray(points)
    order = np.empty(n, np.int32)  # tree-node id -> point id
    slot = [0]

    def rec(idx, depth):
        if len(idx) == 0:
            return -1
        ax = depth % pts.shape[1]
        idx = idx[np.argsort(pts[idx, ax], kind="stable")]
        mid = len(idx) // 2
        me = slot[0]
        slot[0] += 1
        order[me] = idx[mid]
        axis[me] = ax
        l = rec(idx[:mid], depth + 1)
        r = rec(idx[mid + 1 :], depth + 1)
        left[me], right[me] = l, r
        return me

    root = rec(np.arange(n, dtype=np.int64), 0)
    return {
        "point": pts[order],
        "left": left,
        "right": right,
        "axis": axis,
        "root": np.int32(root),
        "perm": order,
    }


def build_bst(keys: np.ndarray, values: np.ndarray):
    """Balanced BST over sorted keys (arrays left/right/key/value)."""
    order = np.argsort(keys)
    keys, values = np.asarray(keys)[order], np.asarray(values)[order]
    n = len(keys)
    left = np.full(n, -1, np.int32)
    right = np.full(n, -1, np.int32)
    okey = np.empty_like(keys)
    oval = np.empty_like(values)
    slot = [0]

    def rec(lo, hi):
        if lo >= hi:
            return -1
        mid = (lo + hi) // 2
        me = slot[0]
        slot[0] += 1
        okey[me], oval[me] = keys[mid], values[mid]
        left[me] = rec(lo, mid)
        right[me] = rec(mid + 1, hi)
        return me

    root = rec(0, n)
    return {"key": okey, "value": oval, "left": left, "right": right, "root": np.int32(root)}


def build_linked_lists(rng, n_lists: int, min_len: int, max_len: int):
    """Pool of singly linked lists: head[i] → chain via nxt, payload val."""
    lens = rng.integers(min_len, max_len + 1, n_lists)
    total = int(lens.sum())
    nxt = np.full(total, -1, np.int32)
    val = rng.normal(size=total).astype(np.float32)
    head = np.zeros(n_lists, np.int32)
    pos = 0
    perm = rng.permutation(total).astype(np.int32)  # scatter nodes (cache-hostile)
    for i, L in enumerate(lens):
        ids = perm[pos : pos + L]
        head[i] = ids[0]
        for a, b in zip(ids[:-1], ids[1:]):
            nxt[a] = b
        pos += L
    return {"head": head, "nxt": nxt, "val": val, "len": lens.astype(np.int32)}


def bst_lookup(bst, key, depth: int):
    """Fixed-depth BST search (bounded scan — TPU-honest traversal)."""

    def step(node, _):
        k = bst["key"][jnp.maximum(node, 0)]
        go_left = key < k
        nxt = jnp.where(go_left, bst["left"][jnp.maximum(node, 0)], bst["right"][jnp.maximum(node, 0)])
        hit = jnp.logical_and(node >= 0, k == key)
        keep = jnp.where(hit, node, -1)
        node = jnp.where(node < 0, node, nxt)
        return node, keep

    _, hits = jax.lax.scan(step, bst["root"], None, length=depth)
    found = jnp.max(hits)
    return found  # node id or -1


def list_sum(lists, head, max_hops: int):
    """Traverse one linked list, summing payloads (dependent loads)."""

    def step(carry, _):
        node, acc = carry
        v = jnp.where(node >= 0, lists["val"][jnp.maximum(node, 0)], 0.0)
        nxt = jnp.where(node >= 0, lists["nxt"][jnp.maximum(node, 0)], -1)
        return (nxt, acc + v), None

    (_, acc), _ = jax.lax.scan(step, (head, 0.0), None, length=max_hops)
    return acc
