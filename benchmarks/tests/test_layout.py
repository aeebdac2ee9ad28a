"""BENCHMARK.json and the files it names agree, so a later PR that adds
an entry and its files needs no edit anywhere else."""

import importlib.util
import json
from pathlib import Path

import pytest

import run
import traffic

BENCH = Path(__file__).resolve().parents[1]
SPEC = json.loads((BENCH.parent / "BENCHMARK.json").read_text())


@pytest.mark.parametrize("metric", SPEC["per_layer"], ids=lambda m: m["name"])
def test_every_per_layer_metric_has_its_reader(metric):
    path = BENCH / "layer_metrics" / f"{metric['name']}.py"
    spec = importlib.util.spec_from_file_location("reader", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    assert callable(module.read)  # BENCHMARK.json is the one record of the rest
    assert metric["moves"] in {m["name"] for m in SPEC["end_to_end"]}


@pytest.mark.parametrize("cell", SPEC["workloads"], ids=lambda c: c["name"])
def test_every_cell_finds_its_files(cell):
    config = json.loads((BENCH / "configs" / f"{cell['config']}.json").read_text())
    listed = {c["name"]: c for c in SPEC["configs"]}[cell["config"]]
    assert config["source"] == listed["source"]
    assert config["reduced"] == listed["reduced"]
    mix = traffic.load_mix(cell["traffic"])
    assert mix["arrivals"]["loop"] in ("open", "closed")
    if mix["arrivals"]["loop"] == "open":
        cell_file = json.loads((BENCH / "cells" / f"{cell['name']}.json").read_text())
        assert cell_file["arrivals"]["rate_rps"] > 0
    names = {m["name"] for m in SPEC["end_to_end"] if cell["name"] in m.get("workloads", [cell["name"]])}
    assert "setup_s" in names and len(names) >= 2
    moved = {m["moves"] for m in SPEC["per_layer"] if cell["name"] in m.get("workloads", [cell["name"]])}
    assert moved and moved <= names  # what a reader moves is reported in this cell


@pytest.mark.parametrize("config", SPEC["configs"], ids=lambda c: c["name"])
def test_every_configuration_finds_its_architecture_module(config):
    model = json.loads((BENCH.parent / config["file"]).read_text())
    arch = run.load_arch(model)  # exits, naming the file or the function that is missing
    assert Path(arch.__file__) == BENCH / "arch" / f"{model.get('arch', 'llama')}.py"
    assert all(callable(getattr(arch, f)) for f in run.ARCH_EXPORTS)
    assert arch.decode_step_bytes(model, model["engine"], 0) > 0
    assert arch.prefill_flops(model, 1, 1) > 0


def test_peaks_name_their_source():
    peaks = json.loads((BENCH / "peaks.json").read_text())
    assert "TPU v5e" in peaks["source"]
    assert peaks["TPU v5 lite"]["hbm_bytes_per_s"] == 819e9
    assert peaks["TPU v5 lite"]["bf16_flops"] == 197e12
