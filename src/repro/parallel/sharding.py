"""Logical-axis sharding rules with divisibility fallback.

Every parameter / activation carries a tuple of *logical* axis names.
``ShardingRules`` maps logical names onto mesh axes, dropping any mapping
whose dimension does not divide evenly by the mesh-axis size. Each dropped
mapping is recorded — the adviser (core/adviser.py) treats fallbacks exactly
like the paper treats "kernel too fine-grained for this scheduling strategy"
and picks the next strategy in the band (DESIGN.md §6.1).
"""
from __future__ import annotations

import math
from typing import Optional, Sequence, Tuple, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig

MeshAxes = Union[None, str, Tuple[str, ...]]


def _axis_size(mesh: Mesh, axes: MeshAxes) -> int:
    if axes is None:
        return 1
    if isinstance(axes, str):
        axes = (axes,)
    return math.prod(mesh.shape[a] for a in axes)


class ShardingRules:
    """cfg + mesh → PartitionSpecs for logical-axis-annotated arrays."""

    def __init__(self, mesh: Mesh, cfg: ModelConfig):
        self.mesh = mesh
        self.cfg = cfg
        self.fallbacks: list[str] = []
        has_pod = "pod" in mesh.shape
        batch_axes: MeshAxes = ("pod", "data") if has_pod else ("data",)
        fsdp = cfg.param_sharding == "fsdp"

        model = mesh.shape.get("model", 1)
        heads_ok = cfg.n_heads and cfg.n_heads % model == 0
        kv_ok = cfg.n_kv_heads and cfg.n_kv_heads % model == 0
        self.kv_heads_ok = bool(kv_ok)

        self.table: dict[str, MeshAxes] = {
            # parameter axes
            "layers": None,
            "groups": None,
            "embed": ("data",) if fsdp else None,
            "mlp": "model",
            "heads": "model" if heads_ok else None,
            "kv_heads": "model" if kv_ok else None,
            # q heads within a kv group always travel with their group's kv
            # head (serving TP shards contiguous head blocks), never alone
            "q_heads_per_group": None,
            "head_dim": None,
            "qdim": "model",  # flattened h·hd projection dim (attn_flat_tp)
            "vocab": "model",
            "experts": "model",
            "expert_mlp": ("data",) if fsdp else None,  # FSDP axis on expert F
            "ssm_heads": "model",
            "ssm_inner": "model",
            "state": None,
            "conv": None,
            # activation axes
            "batch": batch_axes,
            "seq": None,
            # sequence-parallel fallback: queries over 'model' when heads
            # cannot shard (DESIGN.md §6.1)
            "seq_sp": "model" if not heads_ok else None,
            # decode KV-cache sequence axis: shard over 'model' when the
            # kv-head axis cannot (flash-decode partial-softmax combine)
            "kv_seq": None if kv_ok else "model",
            # paged-pool block dim over the serving 'seq' mesh axis
            # (kv-sequence split; per-rank flash partials combined by
            # collectives.distributed_softmax — DESIGN.md §5)
            "kv_blocks": "seq",
            "tokens_ep": (batch_axes + ("model",))
            if isinstance(batch_axes, tuple)
            else (batch_axes, "model"),
        }

    # ------------------------------------------------------------------
    def spec(
        self, axes: Sequence[Optional[str]], shape: Sequence[int]
    ) -> P:
        """PartitionSpec for an array with the given logical axes + shape.

        Any logical→mesh mapping that does not divide the dimension evenly
        is dropped (recorded in ``self.fallbacks``). Mesh axes already used
        by an earlier dimension are also dropped (a mesh axis may shard at
        most one dim).
        """
        assert len(axes) == len(shape), (axes, shape)
        used: set[str] = set()
        out = []
        for name, dim in zip(axes, shape):
            mesh_axes = self.table.get(name) if name else None
            if mesh_axes is None:
                out.append(None)
                continue
            if isinstance(mesh_axes, str):
                mesh_axes = (mesh_axes,)
            # Mesh axes the mesh does not define are not candidates at all
            # (e.g. 'data' on a serving-only ('model',) mesh) — skipping them
            # is not a fallback event. Axes already consumed by an EARLIER
            # dimension of this array are dropped and recorded under the
            # logical name of the dimension being dropped (the later one),
            # then divisibility is checked progressively.
            keep = []
            for a in mesh_axes:
                if a not in self.mesh.shape:
                    continue
                if a in used:
                    self.fallbacks.append(
                        f"{name}:{dim} mesh axis {a} already used by an "
                        f"earlier dim; dropped {a}"
                    )
                    continue
                keep.append(a)
            cand = tuple(keep)
            while cand and dim % _axis_size(self.mesh, cand) != 0:
                dropped = cand[-1]
                cand = cand[:-1]
                self.fallbacks.append(
                    f"{name}:{dim} ∤ mesh{dropped}; dropped {dropped}"
                )
            if not cand:
                out.append(None)
                continue
            used.update(cand)
            out.append(cand[0] if len(cand) == 1 else cand)
        return P(*out)

    def sharding(self, axes, shape) -> NamedSharding:
        return NamedSharding(self.mesh, self.spec(axes, shape))

    def constrain(self, x: jax.Array, axes: Sequence[Optional[str]]):
        """with_sharding_constraint by logical axes (inside jit)."""
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(self.mesh, self.spec(axes, x.shape))
        )

    # ------------------------------------------------------------------
    def tp_view(self) -> "ShardingRules":
        """Rules with the FSDP ('data') parameter axes dropped — the
        compute-time layout of ZeRO-2: storage stays FSDP-sharded, the
        train step gathers ONCE per step (EXPERIMENTS.md §Perf #phi3)."""
        import copy

        clone = copy.copy(self)
        clone.table = dict(self.table)
        for k in ("embed", "expert_mlp"):
            clone.table[k] = None
        clone.fallbacks = self.fallbacks
        return clone

    # ------------------------------------------------------------------
    def tree_shardings(self, params, axes_tree):
        """Shardings for a (params, logical-axes) tree pair."""

        def one(p, ax):
            shape = p.shape if hasattr(p, "shape") else ()
            return self.sharding(ax, shape)

        return jax.tree.map(
            one, params, axes_tree, is_leaf=lambda x: isinstance(x, tuple)
        )


def tree_shardings(mesh: Mesh, cfg: ModelConfig, params, axes_tree):
    return ShardingRules(mesh, cfg).tree_shardings(params, axes_tree)


def paged_pool_specs(
    axis: Optional[str] = "model",
    seq_axis: Optional[str] = None,
    *,
    quantized: bool = False,
) -> dict:
    """PartitionSpecs for the stored paged KV pool leaves ``[L, NB, KV,
    R, W]`` (``Model.init_paged_cache``).

    The serving mesh shards at most two pool dimensions: the kv-head dim
    (2) over ``axis`` — PR 7's head-partitioned tensor parallelism,
    bitwise-preserving — and the block dim (1) over ``seq_axis`` — the
    kv-sequence split, where each rank holds a contiguous range of
    physical blocks, attends over only the positions it owns, and the
    per-rank flash partials are combined by
    ``collectives.distributed_softmax`` (rounding-level, DESIGN.md §5).
    Either axis may be ``None``; quantized pools carry per-token scale
    leaves ``[L, NB, KV, R, P]`` that shard the same way.
    """
    kv = P(None, seq_axis, axis, None, None)
    specs = {"k": kv, "v": kv}
    if quantized:
        specs["k_scale"] = kv
        specs["v_scale"] = kv
    return specs
