"""Find the benchmark's pieces by name.

A cell, a configuration, a traffic mix and a metric are each one file,
so adding one never edits another:

- ``cells/<cell>.json``: the configuration, the mix, the engine's sizes
  and the offered rate;
- ``configs/<config>.json``: the published sizes, the registry name the
  program knows them by, what was changed or assumed, and the ``block``
  that reads them (``llama`` where the file names none);
- ``blocks/<block>.py``: an architecture's sizes, program fields, weight
  rule, plain reference and counts (``blocks/__init__.py``);
- ``traffic/<mix>.json``: the generator's parameters;
- ``metrics/<metric>.py``: one ``read(ctx)`` per metric;
- ``peaks.json``: the chip's peaks, keyed by ``device_kind``.
"""
from __future__ import annotations

import importlib
import json
import re
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


def use_program() -> None:
    """Make the program under test (``<checkout>/src``) importable."""
    src = str(ROOT / "src")
    if src not in sys.path:
        sys.path.insert(0, src)


class SpecError(ValueError):
    """A name that does not resolve to a file, or a file that does not
    hold what its name promises."""


def _load_json(path: Path) -> dict:
    if not path.is_file():
        raise SpecError(f"no such file: {path.relative_to(ROOT)}")
    return json.loads(path.read_text())


def load_cell(name: str) -> dict:
    cell = _load_json(HERE / "cells" / f"{name}.json")
    cell["name"] = name
    for key in ("config", "traffic", "chips", "max_batch", "max_seq", "block_size"):
        if key not in cell:
            raise SpecError(f"cell {name!r} lacks {key!r}")
    return cell


def load_config(name: str) -> dict:
    cfg = _load_json(HERE / "configs" / f"{name}.json")
    cfg["name"] = name
    return cfg


DEFAULT_BLOCK = "llama"
BLOCK_API = (
    "sizes", "program_fields", "ONES", "fan_in", "logits", "decode_flops", "paged_attn_cost",
)


def load_block(conf: dict):
    """The block module ``blocks/<name>.py`` that the configuration names
    (``"block": "<name>"``; ``llama`` where it names none)."""
    name = conf.get("block", DEFAULT_BLOCK)
    if not isinstance(name, str) or not re.fullmatch(r"[A-Za-z0-9_]+", name):
        raise SpecError(f"config {conf.get('name')!r}: bad block name {name!r}")
    module = f"bench.blocks.{name}"
    try:
        block = importlib.import_module(module)
    except ModuleNotFoundError as e:
        if e.name != module:
            raise
        raise SpecError(
            f"config {conf.get('name')!r}: no block {name!r} (bench/blocks/{name}.py)"
        ) from None
    missing = [a for a in BLOCK_API if not hasattr(block, a)]
    if missing:
        raise SpecError(f"block {name!r} lacks {missing}")
    return block


def load_mix(name: str) -> dict:
    mix = _load_json(HERE / "traffic" / f"{name}.json")
    mix["name"] = name
    if mix.get("kind") not in ("poisson", "backlog"):
        raise SpecError(f"traffic {name!r}: unknown kind {mix.get('kind')!r}")
    return mix


def load_peaks(device_kind: str) -> dict:
    """The chip's peaks. A kind missing from the table is an error,
    never a default."""
    table = _load_json(HERE / "peaks.json")
    if device_kind not in table["devices"]:
        raise SpecError(
            f"device_kind {device_kind!r} is not in bench/peaks.json "
            f"(have {sorted(table['devices'])})"
        )
    return table["devices"][device_kind]


def load_benchmark() -> dict:
    return _load_json(ROOT / "BENCHMARK.json")


def metric_reader(name: str):
    """The ``read(ctx)`` function of ``metrics/<name>.py``."""
    if not (HERE / "metrics" / f"{name}.py").is_file():
        raise SpecError(f"no reader for metric {name!r} (bench/metrics/{name}.py)")
    return importlib.import_module(f"bench.metrics.{name}").read


def cell_metrics(benchmark: dict, cell: str, trace: bool) -> list[dict]:
    """The metrics this cell reports: its end-to-end metrics untraced,
    its per-layer metrics traced. A metric without a ``workloads`` list
    belongs to every cell."""
    group = benchmark["per_layer" if trace else "end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]


def cell_entry(benchmark: dict, cell: str) -> dict:
    for w in benchmark["workloads"]:
        if w["name"] == cell:
            return w
    raise SpecError(f"cell {cell!r} is not in BENCHMARK.json")
