"""``decode_ahead_pct`` on a made-up ``ctx``: the hand-computed share,
and nothing to read without the traced window's counters, in a window
with no decode chunk, and from a program that sends none ahead."""

import json

import pytest
from test_counter_readers import BENCH, reader

# Between the markers: 57 decode chunks, 51 of them sent before the chunk
# in front of them was fetched.
COUNTERS = {"busy_ticks": 57, "decode_chunks": 57, "decode_chunks_ahead": 51}


def read(counters):
    return reader("decode_ahead_pct")(
        {"trace": None, "trace_counters": counters, "counters": dict(COUNTERS)}
    )


@pytest.mark.parametrize("ahead, expected", [(51, 51 / 57 * 100), (0, 0.0), (57, 100.0)])
def test_the_share_of_chunks_sent_ahead(ahead, expected):
    assert read({**COUNTERS, "decode_chunks_ahead": ahead}) == pytest.approx(expected)


@pytest.mark.parametrize("counters", [
    None,                                        # --trace 0
    {**COUNTERS, "decode_chunks": 0},            # a window with no decode chunk
    {"busy_ticks": 57, "decode_chunks": 57},     # the parent: no such counter
], ids=["untraced", "no_chunk", "parent"])
def test_nothing_to_read(counters):
    assert read(counters) is None


def test_benchmark_json_lists_it_for_the_cells_that_judge_itl():
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    (entry,) = [m for m in bench["per_layer"] if m["name"] == "decode_ahead_pct"]
    assert entry["moves"] == "itl_p95_ms" and entry["better"] == "higher"
    assert entry["layer"] == "scheduler (host)" and entry["source"] == "program_counter"
    assert set(entry["workloads"]) == {w["name"] for w in bench["workloads"]}
