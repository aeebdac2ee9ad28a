"""``decode_full_rows_pct`` on a made-up ``ctx``: the hand-computed share,
and nothing to read without the traced window's counters, in a window
with no decode step, and from a program that lacks the dense counter."""

import json

import pytest
from test_counter_readers import BENCH, reader

# Between the markers: 60 decode chunks of 8 steps over two full layers,
# 32 slots under a window of 8,192, of which the walks copied a third.
DENSE = 60 * 8 * 2 * 32 * 8192
COUNTERS = {"attn_rows_read_full_decode": DENSE // 3, "attn_rows_dense_full_decode": DENSE}


def read(counters):
    return reader("decode_full_rows_pct")(
        {"trace": None, "trace_counters": counters, "counters": dict(COUNTERS)}
    )


@pytest.mark.parametrize("copied, expected", [(DENSE // 3, 100 / 3), (DENSE, 100.0), (DENSE // 8, 12.5)])
def test_the_share_of_the_window_the_walks_copied(copied, expected):
    assert read({**COUNTERS, "attn_rows_read_full_decode": copied}) == pytest.approx(expected)


@pytest.mark.parametrize("counters", [
    None,                                            # --trace 0
    {**COUNTERS, "attn_rows_dense_full_decode": 0},  # a window with no decode step
    {"attn_rows_read_full_decode": DENSE},           # the parent: no such counter
], ids=["untraced", "no_step", "parent"])
def test_nothing_to_read(counters):
    assert read(counters) is None


def test_benchmark_json_lists_it_last_for_the_two_cells_with_full_layers():
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    entry = bench["per_layer"][-1]
    assert entry["name"] == "decode_full_rows_pct"
    assert entry["moves"] == "itl_p95_ms" and entry["better"] == "lower" and entry["unit"] == "%"
    assert entry["layer"] == "step programs" and entry["source"] == "program_counter"
    assert entry["workloads"] == [
        "mellum2-12b-a2.5b-l12.rag-long-closed", "k-exaone-236b-a23b-l5e16.reason-closed",
    ]
