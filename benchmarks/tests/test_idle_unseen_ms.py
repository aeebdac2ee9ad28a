"""``idle_unseen_ms`` and the dispatch stages' readers on a made-up
``ctx``: the hand-computed values, nothing to read without the trace or
the traced window's counters, and ``None`` from a program that lacks the
counters (the parent of the PR that added them)."""

import json

import pytest
from test_counter_readers import BENCH, reader

# Between the markers: 10 s of which the device ran 9.4 s, 50 busy ticks,
# and a clock that booked 0.55 of the 0.6 idle seconds as starved.
TRACE = {"window_s": 10.0, "busy_s": 9.4, "modules": {}}
COUNTERS = {
    "busy_ticks": 50,
    "device_starved_s": 0.55,
    "tick_phase_dispatch_s": 0.9,
    "dispatch_sites": 150,
    "dispatch_h2d_s": 0.3,
    "dispatch_call_s": 0.45,
}
STAGES = {
    "dispatch_ms_per_site": 6.0,
    "dispatch_ms_per_site.h2d": 2.0,
    "dispatch_ms_per_site.call": 3.0,
}


def read(name, counters, trace=TRACE):
    return reader(name)({"trace": trace, "trace_counters": counters, "counters": {}})


@pytest.mark.parametrize("starved_s, expected", [
    (0.55, 1.0),    # a twelfth of the idle time unseen: 50 ms over 50 ticks
    (0.6, 0.0),     # a clock that sees every gap
    (0.7, -2.0),    # one that counts 0.1 s twice
    (0.0, 12.0),    # a blind one: all of the idle time per tick
])
def test_the_idle_time_a_tick_that_the_clock_does_not_book(starved_s, expected):
    got = read("idle_unseen_ms", {**COUNTERS, "device_starved_s": starved_s})
    assert got == pytest.approx(expected)


@pytest.mark.parametrize("counters, trace", [
    (dict(COUNTERS), None),                       # --trace 0
    (None, TRACE),                                # no traced window's counters
    (dict(COUNTERS), {**TRACE, "window_s": 0.0}),  # an empty window
    ({**COUNTERS, "busy_ticks": 0}, TRACE),       # no tick touched the device
    ({"busy_ticks": 50}, TRACE),                  # a program without the counter
], ids=["untraced", "no_counters", "empty_window", "no_busy_tick", "no_counter"])
def test_nothing_to_read(counters, trace):
    assert read("idle_unseen_ms", counters, trace) is None


@pytest.mark.parametrize("name", sorted(STAGES))
def test_a_dispatch_site_by_its_stages(name):
    assert read(name, dict(COUNTERS)) == pytest.approx(STAGES[name])
    assert read(name, None) is None
    assert read(name, {**COUNTERS, "dispatch_sites": 0}) is None
    # The parent: the phase's sum and no site count, no stage sums.
    assert read(name, {"busy_ticks": 50, "tick_phase_dispatch_s": 0.9}) is None


def test_the_stages_stay_inside_the_whole():
    assert STAGES["dispatch_ms_per_site.h2d"] + STAGES["dispatch_ms_per_site.call"] <= (
        STAGES["dispatch_ms_per_site"]
    )


def test_benchmark_json_ends_with_the_four_in_order():
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    cells = [w["name"] for w in bench["workloads"]]
    last = bench["per_layer"][-4:]
    assert [m["name"] for m in last] == ["idle_unseen_ms", *sorted(STAGES, key=len)]
    assert all(m["layer"] == "scheduler (host)" and m["moves"] == "itl_p95_ms"
               and m["unit"] == "ms" and m["better"] == "lower" for m in last)
    assert last[0]["source"] == "device_trace"
    assert last[0]["workloads"] == [
        "mistral-7b.rag-open", "mixtral-8x7b-l4.rag-closed",
        "ling-3.0-flash-vl-l7e128.rag-closed",
    ]
    for m in last[1:]:
        assert m["source"] == "program_counter" and m["workloads"] == cells
