"""``expert_streams_per_touched`` on a made-up ``ctx``: the hand-computed
ratio, and nothing to read without the traced window's counters, in a
window in which no expert received a row, and from a program that lacks
the counter (the parent of the PR that brought it)."""

import json

import pytest
from test_counter_readers import BENCH, reader

NAME = "expert_streams_per_touched"
# Between the markers: 600 decode steps and 40 chunk programs of 12 expert
# layers; a step touches 45 experts a layer once each, a chunk program all
# 64, a row tile's boundary in the middle of most of them.
TOUCHED = 12 * (600 * 45 + 40 * 64)
COUNTERS = {"moe_experts_touched": TOUCHED, "moe_expert_streams": TOUCHED + 12 * 40 * 47}
CELLS = [
    "ling-3.0-flash-vl-l7e128.rag-closed", "mellum2-12b-a2.5b-l12.rag-long-closed",
    "k-exaone-236b-a23b-l5e16.reason-closed", "mistral-small-4-119b-l6e32.doc-long-closed",
    "zaya1-8b-l20.reason-closed", "nemotron-3-super-120b-a12b-l11e128.reason-closed",
]


def read(counters):
    return reader(NAME)({"trace": None, "trace_counters": counters, "counters": dict(COUNTERS)})


@pytest.mark.parametrize("streams, expected", [
    (TOUCHED, 1.0),                                        # every expert streamed once
    (COUNTERS["moe_expert_streams"], 1 + 12 * 40 * 47 / TOUCHED),
    (2 * TOUCHED, 2.0),                                    # two k tiles, every group on two row tiles
])
def test_streams_over_the_experts_that_received_a_row(streams, expected):
    assert read({**COUNTERS, "moe_expert_streams": streams}) == pytest.approx(expected)


@pytest.mark.parametrize("counters", [
    None,                                    # --trace 0
    {**COUNTERS, "moe_experts_touched": 0},  # a window in which no expert received a row
    {"moe_experts_touched": TOUCHED},        # the parent: no such counter
], ids=["untraced", "no_expert", "parent"])
def test_nothing_to_read(counters):
    assert read(counters) is None


def test_benchmark_json_lists_it_for_the_six_cells_with_grouped_experts():
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    (entry,) = [m for m in bench["per_layer"] if m["name"] == NAME]
    assert entry == {
        "name": NAME, "unit": "ratio", "better": "lower", "source": "program_counter",
        "layer": "step programs", "moves": "itl_p95_ms", "workloads": CELLS,
    }
    (load,) = [m for m in bench["per_layer"] if m["name"] == "expert_load_max_over_mean"]
    assert entry["workloads"] == load["workloads"]
    judged = {m["name"]: m for m in bench["end_to_end"]}["itl_p95_ms"]
    assert "workloads" not in judged  # every cell reports what it moves
