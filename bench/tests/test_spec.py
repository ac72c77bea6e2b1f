"""Every piece of the benchmark is found by its name, and BENCHMARK.json
keeps the contract's shape."""
import json
import re

import pytest

from bench import spec
from bench.blocks import llama, stablelm

BENCH = json.loads((spec.ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCH["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.mark.parametrize("name", CELLS)
def test_cell_resolves_by_name(name):
    entry = spec.cell_entry(BENCH, name)
    cell = spec.load_cell(name)
    assert cell["config"] == entry["config"] and cell["traffic"] == entry["traffic"]
    assert cell["chips"] == entry["chips"] == 1
    assert name == f"{cell['config']}.{cell['traffic']}"
    conf = spec.load_config(cell["config"])
    mix = spec.load_mix(cell["traffic"])
    assert cell["max_seq"] % cell["block_size"] == 0
    longest = mix["prompt"]["hi"] + mix["output"]["hi"]
    longest += mix["system_prompts"]["length"] if mix.get("system_prompts") else 0
    assert longest <= cell["max_seq"] <= conf["max_position_embeddings"]
    assert float(cell["limits"]["max_logit_gap"]) > 0
    if mix["kind"] == "poisson":
        assert cell["rate_rps"] > 0
    for m in spec.cell_metrics(BENCH, name, False) + spec.cell_metrics(BENCH, name, True):
        assert callable(spec.metric_reader(m["name"]))


@pytest.mark.parametrize("conf", BENCH["configs"], ids=lambda c: c["name"])
def test_config_file_matches_its_entry(conf):
    data = spec.load_config(conf["name"])
    assert conf["file"] == f"bench/configs/{conf['name']}.json"
    assert data["source"] == conf["source"]
    assert data["reduced"] == conf["reduced"]
    sizes = spec.load_block(data).sizes(data)
    assert sizes["d_model"] % sizes["n_heads"] == 0
    assert any(w["config"] == conf["name"] for w in BENCH["workloads"])


def test_every_metric_has_a_reader_and_a_sound_entry():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace") and 0.01 <= m["bound"] <= 0.25
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert NAME.match(m["name"]) and m["better"] in ("lower", "higher")
        spec.metric_reader(m["name"])
    for m in BENCH["per_layer"]:
        moved = e2e[m["moves"]]
        for cell in m.get("workloads", CELLS):
            assert cell in CELLS
            assert cell in moved.get("workloads", CELLS), (m["name"], cell)


@pytest.mark.parametrize("name", CELLS)
def test_every_cell_reports_enough(name):
    e2e = [m["name"] for m in spec.cell_metrics(BENCH, name, False)]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert spec.cell_metrics(BENCH, name, True)


def test_peaks_table_refuses_an_unknown_chip():
    assert spec.load_peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(spec.SpecError):
        spec.load_peaks("cpu")


def test_unknown_names_are_refused():
    with pytest.raises(spec.SpecError):
        spec.load_cell("no-such.cell")
    with pytest.raises(spec.SpecError):
        spec.metric_reader("no_such_metric")


def test_block_lookup():
    assert spec.load_block(spec.load_config("smollm-135m")) is llama  # no "block" key
    assert spec.load_block(spec.load_config("stablelm-3b")) is stablelm
    assert spec.load_block({"name": "x", "block": "llama"}) is llama
    for bad in ("no_such_block", "../llama", "llama.x", "", 3):
        with pytest.raises(spec.SpecError):
            spec.load_block({"name": "x", "block": bad})
