"""Property tests for the ShardingRules drop-rule over random (cfg, mesh)
pairs: both drop paths — divisibility and already-used mesh axis — must
leave a correctly-named fallback record, and the resulting specs must
never double-assign a mesh axis or assign a non-dividing one.

Runs against a duck-typed mesh (only ``mesh.shape`` is consulted by
``spec()``), so the random mesh shapes need no real devices."""
import dataclasses
import math

from hypothesis import given, settings, strategies as st

from repro.configs import get_config
from repro.parallel.sharding import ShardingRules


class _FakeMesh:
    """shape-only stand-in (spec() never touches devices)."""

    def __init__(self, shape):
        self.shape = dict(shape)


def _cfg(n_heads, n_kv_heads):
    return dataclasses.replace(
        get_config("smollm-135m").reduced(),
        n_heads=n_heads, n_kv_heads=n_kv_heads,
    )


def _flat_axes(entry):
    if entry is None:
        return ()
    return entry if isinstance(entry, tuple) else (entry,)


@settings(max_examples=40, deadline=None)
@given(
    n_heads=st.integers(1, 16),
    n_kv=st.integers(1, 8),
    model=st.sampled_from([1, 2, 3, 4, 8]),
    data=st.sampled_from([1, 2, 4]),
    mult=st.integers(1, 3),
)
def test_spec_invariants_random_cfg_mesh(n_heads, n_kv, model, data, mult):
    """Over random (cfg, mesh): every emitted spec assigns each mesh axis
    at most once, every assignment divides its dimension, and every drop
    is recorded under the dropped dimension's own logical name."""
    cfg = _cfg(n_heads, n_kv)
    mesh = _FakeMesh({"data": data, "model": model})
    annotations = [
        (("embed", "heads", "head_dim"), (cfg.d_model, n_heads, 16)),
        (("embed", "kv_heads", "head_dim"), (cfg.d_model, n_kv, 16)),
        ((None, "batch", "kv_seq", "kv_heads", None), (2, 4, 32, n_kv, 16)),
        (("batch", "seq_sp", "heads", None), (data * 2, model * mult, n_heads, 16)),
        (("mlp", "vocab"), (model * mult, model * mult)),
    ]
    for axes, shape in annotations:
        rules = ShardingRules(mesh, cfg)
        before = len(rules.fallbacks)
        spec = rules.spec(axes, shape)
        seen = []
        for entry, dim, name in zip(spec, shape, axes):
            flat = _flat_axes(entry)
            seen.extend(flat)
            if flat:
                size = math.prod(mesh.shape[a] for a in flat)
                assert dim % size == 0, (axes, shape, spec)
        assert len(seen) == len(set(seen)), f"mesh axis assigned twice: {spec}"
        # every recorded drop names a logical axis of THIS array
        for rec in rules.fallbacks[before:]:
            logical = rec.split(":", 1)[0]
            assert logical in [a for a in axes if a], rec


@settings(max_examples=25, deadline=None)
@given(model=st.sampled_from([1, 2, 3, 4, 8]), mult=st.integers(1, 3))
def test_already_used_drop_records_later_axis_name(model, mult):
    """The already-used drop path: when an earlier dimension consumed the
    mesh axis, the LATER logical axis is dropped — and the record must
    carry the later axis's name (the satellite bug: it reported the
    wrong one)."""
    cfg = _cfg(4, 2)
    rules = ShardingRules(_FakeMesh({"data": 2, "model": model}), cfg)
    dim = model * mult
    spec = rules.spec(("mlp", "vocab"), (dim, dim))
    assert spec[0] == "model" and spec[1] is None
    recs = [r for r in rules.fallbacks if "already used" in r]
    assert recs, rules.fallbacks
    assert recs[0].startswith(f"vocab:{dim}"), recs
    assert "mlp" not in recs[0], recs


@settings(max_examples=25, deadline=None)
@given(model=st.sampled_from([2, 3, 4, 8]), off=st.integers(1, 3))
def test_divisibility_drop_records_axis_name(model, off):
    """The divisibility drop path: a dimension the mesh axis does not
    divide falls back to unsharded with a record naming that dimension."""
    cfg = _cfg(4, 2)
    rules = ShardingRules(_FakeMesh({"data": 1, "model": model}), cfg)
    dim = model + off if (model + off) % model else model + off + 1
    assert dim % model != 0
    spec = rules.spec(("mlp",), (dim,))
    assert spec[0] is None
    assert any(r.startswith(f"mlp:{dim}") and "∤" in r for r in rules.fallbacks), (
        rules.fallbacks
    )


def test_mesh_without_data_axis_is_not_a_fallback():
    """A serving-only ('model',) mesh simply lacks the 'data'/'pod' axes:
    batch stays unsharded with NO fallback record and NO KeyError."""
    cfg = _cfg(4, 4)
    rules = ShardingRules(_FakeMesh({"model": 2}), cfg)
    spec = rules.spec(("batch", "seq", None), (8, 16, 32))
    assert tuple(spec) == (None, None, None)
    assert rules.fallbacks == []
    # the head axes still shard normally on the same mesh
    spec = rules.spec(("embed", "kv_heads", "head_dim"), (64, 4, 16))
    assert spec[1] == "model"
