"""``admit_lone_pct`` and ``prefill_pad_pct.itl`` on a made-up ``ctx``:
the hand-computed shares, nothing to read without the traced window's
counters, in a window that has nothing to divide by, and from a program
that lacks the counters (the parent of the PR that brought them); and
their entries in ``BENCHMARK.json``."""

import json

import pytest
from test_counter_readers import BENCH, reader

CELL = "mistral-7b.chat-closed"
# Between the markers: 67 admissions, 16 of them prompts over a chunk;
# of the 51 others, 47 went alone and 4 as one batch padded to 8 rows of
# 128.  The lone ones' own buckets and the chunks' hold 14,000 tokens and
# 5,200 positions of padding, the batch 340 tokens in 1,024 positions.
COUNTERS = {
    "admits_lone": 47, "admits_batched": 4,
    "prefill_tokens_dispatched": 14000 + 340,
    "prefill_tokens_padded": 5200 + 1024 - 340,
}


def read(name, counters):
    return reader(name)({"trace": None, "trace_counters": counters, "counters": dict(COUNTERS)})


@pytest.mark.parametrize("lone, batched, expected", [
    (47, 4, 100 * 47 / 51), (51, 0, 100.0), (0, 51, 0.0),
])
def test_share_of_the_short_cold_admissions_that_went_alone(lone, batched, expected):
    counters = {**COUNTERS, "admits_lone": lone, "admits_batched": batched}
    assert read("admit_lone_pct", counters) == pytest.approx(expected)


def test_the_padding_is_that_of_prefill_pad_pct():
    expected = 100 * (5200 + 684) / (5200 + 684 + 14340)
    assert read("prefill_pad_pct.itl", dict(COUNTERS)) == pytest.approx(expected)
    assert read("prefill_pad_pct.itl", dict(COUNTERS)) == read("prefill_pad_pct", dict(COUNTERS))


@pytest.mark.parametrize("name, counters", [
    ("admit_lone_pct", None),                                               # --trace 0
    ("admit_lone_pct", {**COUNTERS, "admits_lone": 0, "admits_batched": 0}),  # no short cold prompt
    ("admit_lone_pct", {"prefill_tokens_dispatched": 14340, "prefill_tokens_padded": 5884}),  # the parent
    ("prefill_pad_pct.itl", None),
    ("prefill_pad_pct.itl", {**COUNTERS, "prefill_tokens_dispatched": 0, "prefill_tokens_padded": 0}),
    ("prefill_pad_pct.itl", {"admits_lone": 47}),
], ids=["lone-untraced", "lone-none_admitted", "lone-parent", "pad-untraced", "pad-no_prefill", "pad-no_counter"])
def test_nothing_to_read(name, counters):
    assert read(name, counters) is None


@pytest.mark.parametrize("name, layer, better", [
    ("prefill_pad_pct.itl", "step programs", "lower"),
    ("admit_lone_pct", "scheduler", "higher"),
])
def test_benchmark_json_lists_it_for_the_chat_cell(name, layer, better):
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    (entry,) = [m for m in bench["per_layer"] if m["name"] == name]
    assert entry == {
        "name": name, "unit": "%", "better": better, "source": "program_counter",
        "layer": layer, "moves": "itl_p95_ms", "workloads": [CELL],
    }
    (pad,) = [m for m in bench["per_layer"] if m["name"] == "prefill_pad_pct"]
    assert CELL not in pad["workloads"] and pad["layer"] == "step programs"
