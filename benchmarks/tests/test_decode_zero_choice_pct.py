"""``decode_zero_choice_pct`` on a made-up ``ctx``: the hand-computed
share, ``None`` without ``trace_counters``, on a zero denominator and on a
program that lacks the counter (the parent commit)."""

import json
from pathlib import Path

import pytest

from metrics_lib import load_reader

NAME = "decode_zero_choice_pct"
# Between the markers: 50 decode chunks of 8 steps over 13 rows and 40
# chunk programs of 1,024 tokens, 12 choices a token in each of four expert
# layers, of which a third fell on identity experts.
TOKENS = 50 * 8 * 13 + 40 * 1024
COUNTERS = {"moe_choices_routed": 4 * 12 * TOKENS, "moe_choices_zero": 4 * 4 * TOKENS}


def read(counters):
    ctx = {"counters": dict(COUNTERS), "trace": None}
    if counters is not None:
        ctx["trace_counters"] = counters
    return load_reader(NAME)(ctx)


def test_reads_the_share():
    assert read(dict(COUNTERS)) == pytest.approx(100 / 3)
    assert read({**COUNTERS, "moe_choices_zero": 0}) == 0.0  # a router with no identity output chosen


@pytest.mark.parametrize(
    "counters",
    [None, {**COUNTERS, "moe_choices_routed": 0}, {"busy_ticks": 3}, {"moe_choices_routed": 7}],
    ids=["untraced", "zero-denominator", "no-counters", "the-parent's-counters"],
)
def test_reads_nothing(counters):
    assert read(counters) is None


def test_the_entry():
    spec = json.loads((Path(__file__).resolve().parents[2] / "BENCHMARK.json").read_text())
    (entry,) = [m for m in spec["per_layer"] if m["name"] == NAME]
    assert entry == {
        "name": NAME, "unit": "%", "better": "higher", "source": "program_counter",
        "layer": "step programs", "moves": "out_tok_s",
        "workloads": ["longcat-flash-chat-l4e16.doc-reason-closed"],
    }
    assert spec["per_layer"][-1] is entry  # appended, not put in the middle
