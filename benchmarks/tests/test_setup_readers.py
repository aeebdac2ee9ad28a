"""The readers of the layer "engine set-up": on a made-up report of the
program each gives the hand-computed value, ``other`` entries are left
out, and a program without the record reads ``None``."""

import importlib.util
import json
from pathlib import Path

import pytest

import setup_lib

BENCH = Path(__file__).resolve().parents[1]

# A warm set-up that found 47 of its 50 executables: 9 asked for inside
# Scheduler.__init__, 41 by the tick thread while the warm-up ran; the
# reference check's prefill (``other``) came after the window.
REPORT = {
    "compile": {"compile_s": 61.0, "cache_hits": 48, "cache_misses": 4},
    "setup": {"builds": 1, "build_s": 30.5, "params_s": 6.0, "state_s": 0.5,
              "programs_s": 24.0},
    "executables": {
        "build": {"executables": 9, "hit": 9, "miss": 0, "off": 0,
                  "trace_s": 8.0, "lower_s": 4.0, "backend_s": 11.0},
        "tick": {"executables": 41, "hit": 38, "miss": 3, "off": 0,
                 "trace_s": 12.0, "lower_s": 6.0, "backend_s": 40.0},
        "other": {"executables": 2, "hit": 1, "miss": 1, "off": 0,
                  "trace_s": 3.0, "lower_s": 1.0, "backend_s": 10.0},
    },
}
EXPECTED = {
    "setup_build_s": 30.5,
    "setup_executables": 50,
    "setup_trace_lower_s": 30.0,
    "setup_backend_s": 51.0,
    "setup_cache_hit_pct": 94.0,
}


def reader(name):
    path = BENCH / "layer_metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location("reader_under_test", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_gives_the_hand_computed_value(name, monkeypatch):
    module = reader(name)
    monkeypatch.setattr(module, "report", lambda: REPORT)
    assert module.read({}) == pytest.approx(EXPECTED[name])
    # The window's counters and the trace are none of its sources.
    assert module.read({"trace_counters": None, "trace": None}) == pytest.approx(
        EXPECTED[name]
    )


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_other_entries_are_left_out(name, monkeypatch):
    module = reader(name)
    more = json.loads(json.dumps(REPORT))
    more["executables"]["other"] = {
        k: v * 10 for k, v in REPORT["executables"]["other"].items()
    }
    monkeypatch.setattr(module, "report", lambda: more)
    assert module.read({}) == pytest.approx(EXPECTED[name])


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_a_program_without_the_record_reads_none(name, monkeypatch):
    module = reader(name)
    monkeypatch.setattr(module, "report", lambda: None)
    assert module.read({}) is None


def test_report_is_none_where_runtime_report_lacks_the_record(monkeypatch):
    from generativeaiexamples_tpu.utils import jax_runtime

    parents = {k: REPORT[k] for k in ("compile",)}  # the parent commit's keys
    monkeypatch.setattr(jax_runtime, "runtime_report", lambda: parents)
    assert setup_lib.report() is None
    monkeypatch.setattr(jax_runtime, "runtime_report", lambda: REPORT)
    assert setup_lib.report() is REPORT


def test_no_lookup_reads_no_hit_share():
    off = json.loads(json.dumps(REPORT))
    for totals in off["executables"].values():
        totals["off"] += totals["hit"] + totals["miss"]
        totals["hit"] = totals["miss"] = 0
    assert setup_lib.hit_pct(off) is None
    assert setup_lib.total(off, "executables") == 50
    assert setup_lib.total({"executables": {"build": {}, "tick": {}}}, "hit") is None


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_benchmark_json_has_the_entry_once_for_every_cell(name):
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    (entry,) = [m for m in bench["per_layer"] if m["name"] == name]
    assert entry["layer"] == "engine set-up" and entry["moves"] == "setup_s"
    assert entry["source"] == "program_counter"
    assert entry["better"] == ("higher" if name == "setup_cache_hit_pct" else "lower")
    assert set(entry) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
    assert set(entry["workloads"]) == {w["name"] for w in bench["workloads"]}
