"""``prefill_full_rows_pct`` on a made-up ``ctx``: the hand-computed share,
and nothing to read without the traced window's counters, in a window
with no prefill program, and from a program that lacks the dense counter."""

import json

import pytest
from test_counter_readers import BENCH, reader

# Between the markers: 70 chunk programs of 4 rows over three full layers
# under a window of 8,192, of which the chunks' walks copied two fifths.
DENSE = 70 * 3 * 4 * 8192
COUNTERS = {"attn_rows_read_full_prefill": DENSE * 2 // 5, "attn_rows_dense_full_prefill": DENSE}


def read(counters):
    return reader("prefill_full_rows_pct")(
        {"trace": None, "trace_counters": counters, "counters": dict(COUNTERS)}
    )


@pytest.mark.parametrize("copied, expected", [(DENSE * 2 // 5, 40.0), (DENSE, 100.0), (DENSE // 8, 12.5)])
def test_the_share_of_the_windows_the_chunks_copied(copied, expected):
    assert read({**COUNTERS, "attn_rows_read_full_prefill": copied}) == pytest.approx(expected)


@pytest.mark.parametrize("counters", [
    None,                                             # --trace 0
    {**COUNTERS, "attn_rows_dense_full_prefill": 0},  # a window with no prefill program
    {"attn_rows_read_full_prefill": DENSE},           # a program with no such counter
], ids=["untraced", "no_program", "no_counter"])
def test_nothing_to_read(counters):
    assert read(counters) is None


def test_benchmark_json_lists_it_for_the_cell_that_judges_ttft():
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    entry = next(m for m in bench["per_layer"] if m["name"] == "prefill_full_rows_pct")
    assert entry["moves"] == "ttft_p50_ms" and entry["better"] == "lower" and entry["unit"] == "%"
    assert entry["layer"] == "step programs" and entry["source"] == "program_counter"
    assert entry["workloads"] == ["mellum2-12b-a2.5b-l12.rag-long-closed"]
