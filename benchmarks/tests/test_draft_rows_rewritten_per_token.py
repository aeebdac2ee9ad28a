"""``draft_rows_rewritten_per_token`` on a made-up ``ctx``: the
hand-computed ratio, ``None`` without ``trace_counters``, on a zero
denominator and on a program that lacks the counters."""

import pytest

from metrics_lib import load_reader

# 50 verify chunks of 8 steps, 14 rows decoding: 3 drafts kept in all, so
# 5,597 rows of each of five layers are written again for 5,603 tokens.
STEPS = 50 * 8
COUNTERS = {
    "draft_proposed": STEPS * 14, "draft_accepted": 3,
    "decode_tokens_emitted": STEPS * 14 + 3,
    "draft_rows_rewritten": 5 * (STEPS * 14 - 3),
}


def read(counters):
    ctx = {"counters": dict(COUNTERS)}
    if counters is not None:
        ctx["trace_counters"] = counters
    return load_reader("draft_rows_rewritten_per_token")(ctx)


def test_reads_the_ratio():
    assert read(dict(COUNTERS)) == pytest.approx(5 * 5597 / 5603)
    assert read({**COUNTERS, "draft_rows_rewritten": 0}) == 0.0


@pytest.mark.parametrize(
    "counters",
    [None, {**COUNTERS, "decode_tokens_emitted": 0}, {"draft_proposed": 9},
     {"draft_proposed": 9, "draft_rows_rewritten": 7}],
    ids=["untraced", "zero-denominator", "no-counters", "one-counter"],
)
def test_reads_nothing(counters):
    assert read(counters) is None
