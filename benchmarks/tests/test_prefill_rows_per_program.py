"""``prefill_rows_per_program`` on a made-up ``ctx``: the hand-computed
ratio, and nothing to read without the traced window's counters, in a
window with no chunk, and from a program that does not count its chunk
programs."""

import json

import pytest
from test_counter_readers import BENCH, reader

# Between the markers: 120 chunks in 45 programs (a group of four, one of
# three and a lone first chunk a tick, say).
COUNTERS = {"busy_ticks": 15, "prefill_chunks": 120, "prefill_chunk_programs": 45}


def read(counters):
    return reader("prefill_rows_per_program")(
        {"trace": None, "trace_counters": counters, "counters": dict(COUNTERS)}
    )


@pytest.mark.parametrize("programs, expected", [(45, 120 / 45), (120, 1.0), (30, 4.0)])
def test_chunks_a_program(programs, expected):
    assert read({**COUNTERS, "prefill_chunk_programs": programs}) == pytest.approx(expected)


@pytest.mark.parametrize("counters", [
    None,                                                            # --trace 0
    {"busy_ticks": 15, "prefill_chunks": 0, "prefill_chunk_programs": 0},  # no chunk
    {"busy_ticks": 15, "prefill_chunks": 120},                       # the parent
], ids=["untraced", "no_chunk", "parent"])
def test_nothing_to_read(counters):
    assert read(counters) is None


def test_benchmark_json_lists_it_for_every_cell():
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    (entry,) = [m for m in bench["per_layer"] if m["name"] == "prefill_rows_per_program"]
    assert entry["source"] == "program_counter" and entry["layer"] == "scheduler"
    assert entry["moves"] == "itl_p95_ms" and entry["better"] == "higher"
    assert set(entry["workloads"]) == {w["name"] for w in bench["workloads"]}
