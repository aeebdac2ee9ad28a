"""``prefill_ahead_pct`` on a made-up ``ctx``: the hand-computed share,
and nothing to read without the traced window's counters, in a window
with no chunk, and from a program that sends nothing ahead."""

import json

import pytest
from test_counter_readers import BENCH, reader

# Between the markers: 120 chunks, 96 of them sent behind a decode chunk.
COUNTERS = {"busy_ticks": 40, "prefill_chunks": 120, "prefill_chunks_ahead": 96}


def read(counters):
    return reader("prefill_ahead_pct")(
        {"trace": None, "trace_counters": counters, "counters": dict(COUNTERS)}
    )


@pytest.mark.parametrize("ahead, expected", [(96, 80.0), (0, 0.0), (120, 100.0)])
def test_the_share_of_chunks_sent_ahead(ahead, expected):
    assert read({**COUNTERS, "prefill_chunks_ahead": ahead}) == pytest.approx(expected)


@pytest.mark.parametrize("counters", [
    None,                                         # --trace 0
    {**COUNTERS, "prefill_chunks": 0},            # a window with no chunk
    {"busy_ticks": 40, "prefill_chunks": 120},    # the parent: no such counter
], ids=["untraced", "no_chunk", "parent"])
def test_nothing_to_read(counters):
    assert read(counters) is None


def test_benchmark_json_lists_it_for_the_cells_that_judge_itl():
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    (entry,) = [m for m in bench["per_layer"] if m["name"] == "prefill_ahead_pct"]
    assert entry == bench["per_layer"][-1]
    assert entry["moves"] == "itl_p95_ms" and entry["better"] == "higher"
    assert entry["layer"] in {m["layer"] for m in bench["per_layer"][:-1]}
    assert set(entry["workloads"]) == {w["name"] for w in bench["workloads"]}
