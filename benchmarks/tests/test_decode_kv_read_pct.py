"""``decode_kv_read_pct`` on a made-up ``ctx``: the hand-computed ratio,
``None`` without ``trace_counters``, on a zero denominator and on a
program that lacks the counters."""

import importlib.util
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]

# 40 decode chunks between the markers: 14 rows of 3 or 4 blocks of 512
# read beside 32 slots x 2,048 dense.
COUNTERS = {
    "decode_chunks": 40,
    "decode_kv_tokens_read": 40 * 14 * 1792,
    "decode_kv_tokens_dense": 40 * 32 * 2048,
}


def read(counters):
    path = BENCH / "layer_metrics" / "decode_kv_read_pct.py"
    spec = importlib.util.spec_from_file_location("reader_under_test", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    ctx = {"counters": dict(COUNTERS)}
    if counters is not None:
        ctx["trace_counters"] = counters
    return module.read(ctx)


def test_reads_the_ratio():
    assert read(dict(COUNTERS)) == pytest.approx(100 * 14 * 1792 / (32 * 2048))


@pytest.mark.parametrize(
    "counters",
    [
        None,
        {**COUNTERS, "decode_kv_tokens_dense": 0},
        {"decode_chunks": 40},
        {"decode_chunks": 40, "decode_kv_tokens_dense": 40 * 32 * 2048},
    ],
    ids=["untraced", "zero-denominator", "no-counters", "one-counter"],
)
def test_reads_nothing(counters):
    assert read(counters) is None
