"""The readers that read the scheduler's own counters: on a made-up
``ctx`` each gives the hand-computed value, ``None`` without
``trace_counters``, ``None`` on a zero denominator and ``None`` on a
program that lacks the counter."""

import importlib.util
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]

# Between the markers: 40 busy ticks in 10 s, 120 prefill chunks, 30
# requests claimed and 28 first tokens.
COUNTERS = {
    "busy_ticks": 40,
    "tick_count": 40,
    "prefill_chunks": 120,
    "tick_phase_idle_s": 0.0,
    "tick_phase_plan_s": 0.2,
    "tick_phase_dispatch_s": 0.8,
    "tick_phase_wait_device_s": 8.0,
    "tick_phase_emit_s": 0.9,
    "tick_phase_telemetry_s": 0.1,
    "device_starved_s": 1.2,
    "device_starved_plan_s": 0.15,
    "device_starved_dispatch_s": 0.25,
    "device_starved_emit_s": 0.7,
    "device_starved_telemetry_s": 0.1,
    "queue_wait_s_sum": 6.0,
    "queue_wait_count": 30,
    "warm_s_sum": 28.0,
    "warm_count": 28,
    "prefill_tokens_dispatched": 30000,
    "prefill_tokens_padded": 10000,
}
TRACE = {
    "modules": {
        "jit__prefill_suffix": {"count": 118.5, "dev_s": 3.0},
        "jit__prefill_some": {"count": 2.0, "dev_s": 0.75},
        "jit_decode_chunk": {"count": 39.9, "dev_s": 5.6},
    }
}
EXPECTED = {
    "queue_wait_ms": 200.0,
    "warm_ms": 1000.0,
    "tick_ms": 250.0,
    "prefill_chunks_per_tick": 3.0,
    "host_starve_ms": 30.0,
    "host_starve_ms.plan": 3.75,
    "host_starve_ms.dispatch": 6.25,
    "host_starve_ms.emit": 17.5,
    "host_starve_ms.telemetry": 2.5,
    "prefill_pad_pct": 25.0,
    "prefill_dev_tok_s.counted": 8000.0,
}
# What each reader divides by: zero there reads as nothing to read.
DENOMINATORS = {
    "queue_wait_ms": ["queue_wait_count"],
    "warm_ms": ["warm_count"],
    "prefill_pad_pct": ["prefill_tokens_padded", "prefill_tokens_dispatched"],
    "prefill_dev_tok_s.counted": ["prefill_tokens_dispatched"],
}


def reader(name):
    path = BENCH / "layer_metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location("reader_under_test", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def ctx(counters):
    return {"trace": TRACE, "trace_counters": counters, "counters": dict(COUNTERS)}


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_gives_the_hand_computed_value(name):
    assert reader(name)(ctx(dict(COUNTERS))) == pytest.approx(EXPECTED[name])


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_reads_nothing_without_the_traced_windows_counters(name):
    # --trace 0: only the whole window's counters are there.
    assert reader(name)(ctx(None)) is None
    assert reader(name)({"trace": None, "trace_counters": None}) is None


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_reads_nothing_on_a_zero_denominator(name):
    zeroed = dict(COUNTERS)
    for key in DENOMINATORS.get(name, ["busy_ticks"]):
        zeroed[key] = 0
    assert reader(name)(ctx(zeroed)) is None


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_reads_nothing_from_a_program_without_its_counters(name):
    """The parent of the PR that added the counters has ``prefill_chunks``
    and ``tick_count`` and none of the rest: the metric is left out of the
    line, nothing raises."""
    old = {"prefill_chunks": 120, "tick_count": 40, "prefix_tokens_reused": 9}
    assert reader(name)(ctx(old)) is None


def test_the_starved_parts_add_up_to_the_whole():
    whole = reader("host_starve_ms")(ctx(dict(COUNTERS)))
    parts = [
        reader(f"host_starve_ms.{p}")(ctx(dict(COUNTERS)))
        for p in ("plan", "dispatch", "emit", "telemetry")
    ]
    assert sum(parts) == pytest.approx(whole)


def test_device_time_is_that_of_the_prefill_modules_alone():
    no_prefill = {"modules": {"jit_decode_chunk": {"count": 39.9, "dev_s": 5.6}}}
    c = {"trace": no_prefill, "trace_counters": dict(COUNTERS)}
    assert reader("prefill_dev_tok_s.counted")(c) is None
