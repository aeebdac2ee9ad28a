"""``verify_gather_pct`` on a made-up ``ctx``: the hand-computed ratio,
``None`` without ``trace_counters``, on a zero denominator and on a
program that lacks the counters (the parent commit)."""

import pytest

from metrics_lib import load_reader

# 50 verify chunks of 8 steps between the markers: 16 slots x 2 positions x
# 2,048 rows gathered in each of six blocks; 14 slots decode and their two
# positions' sets overlap in all but 40 rows.
STEPS = 50 * 8
COUNTERS = {
    "decode_chunks": 50,
    "attn_rows_gathered_verify": STEPS * 6 * 16 * 2 * 2048,
    "attn_rows_needed_verify": STEPS * 6 * 14 * (2048 + 40),
}


def read(counters):
    ctx = {"counters": dict(COUNTERS)}
    if counters is not None:
        ctx["trace_counters"] = counters
    return load_reader("verify_gather_pct")(ctx)


def test_reads_the_ratio():
    assert read(dict(COUNTERS)) == pytest.approx(100 * 16 * 2 * 2048 / (14 * 2088))
    assert read(dict(COUNTERS)) > 200


@pytest.mark.parametrize(
    "counters",
    [None, {**COUNTERS, "attn_rows_needed_verify": 0}, {"decode_chunks": 50},
     {"decode_chunks": 50, "attn_rows_gathered_verify": 7}],
    ids=["untraced", "zero-denominator", "no-counters", "one-counter"],
)
def test_reads_nothing(counters):
    assert read(counters) is None
