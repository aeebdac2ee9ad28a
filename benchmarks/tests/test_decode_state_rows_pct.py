"""``decode_state_rows_pct`` on a made-up ``ctx``: the hand-computed share,
and nothing to read without the traced window's counters, in a window
with no decode step, and from a program that lacks the dense counter."""

import json

import pytest
from test_counter_readers import BENCH, reader

# Between the markers: 85 decode chunks of 8 steps over six KDA layers
# and 32 slots, of which 12 decode.
DENSE = 85 * 8 * 6 * 32
COUNTERS = {"attn_rows_read_state_decode": DENSE * 12 // 32, "attn_rows_dense_state_decode": DENSE}


def read(counters):
    return reader("decode_state_rows_pct")(
        {"trace": None, "trace_counters": counters, "counters": dict(COUNTERS)}
    )


@pytest.mark.parametrize("slots_read, expected", [(DENSE * 12 // 32, 37.5), (DENSE, 100.0), (DENSE // 32, 3.125)])
def test_the_share_of_the_slots_whose_state_a_step_read(slots_read, expected):
    assert read({**COUNTERS, "attn_rows_read_state_decode": slots_read}) == pytest.approx(expected)


@pytest.mark.parametrize("counters", [
    None,                                             # --trace 0
    {**COUNTERS, "attn_rows_dense_state_decode": 0},  # a window with no decode step
    {"attn_rows_read_state_decode": DENSE},           # no such counter
    {},                                               # the parent: neither
], ids=["untraced", "no_step", "no_dense", "parent"])
def test_nothing_to_read(counters):
    assert read(counters) is None


def test_benchmark_json_lists_it_for_the_cell_with_kda_layers():
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    (entry,) = [m for m in bench["per_layer"] if m["name"] == "decode_state_rows_pct"]
    assert entry["moves"] == "itl_p95_ms" and entry["better"] == "lower" and entry["unit"] == "%"
    assert entry["layer"] == "step programs" and entry["source"] == "program_counter"
    assert entry["workloads"] == ["ling-3.0-flash-vl-l7e128.rag-closed"]
