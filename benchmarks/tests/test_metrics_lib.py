"""The arithmetic between the client's records, the counters and the
per-layer readers, on hand-made records with known answers."""

import pytest

import metrics_lib


def rec(sent, first, prompt_len, n_tokens=3, gap=0.1):
    return {
        "sent": sent, "due": None, "end": first + n_tokens * gap, "prompt_len": prompt_len,
        "tokens": [first + i * gap for i in range(n_tokens)],
    }


def test_tokens_prefilled_inside_the_traced_window():
    ctx = {
        "trace_window": (10.0, 20.0),
        # 1,000 prompt tokens were sent between the markers, 250 came from the cache
        "trace_counters": {"prefix_tokens_reused": 250},
        "records": [
            rec(11.0, 12.0, 600),  # wholly inside
            rec(19.5, 20.5, 400),  # sent inside, half its prefill after the end
            rec(9.0, 11.0, 800),   # sent before, half its prefill inside
            rec(2.0, 3.0, 500),    # wholly outside
        ],
    }
    tokens, pairs = metrics_lib.prefilled_in_trace(ctx)
    # a quarter of every prompt is cached: 450 + 300 / 2 + 600 / 2
    assert tokens == pytest.approx(450 + 150 + 300)
    whole = lambda n: (n * (n + 1) - (n // 4) * (n // 4 + 1)) / 2.0  # noqa: E731
    assert pairs == pytest.approx(whole(600) + whole(400) / 2 + whole(800) / 2)


def test_idle_share_and_reuse_share():
    assert metrics_lib.idle_pct({"trace": None}) is None
    assert metrics_lib.idle_pct({"trace": {"busy_s": 8.5, "window_s": 10.0}}) == pytest.approx(15.0)
    ctx = {"counters": {"prefix_tokens_reused": 300}, "records": [rec(0, 1, 600), rec(1, 2, 400)]}
    assert metrics_lib.reuse_share(ctx) == pytest.approx(0.3)


def test_percentile_and_gaps():
    assert metrics_lib.percentile([1, 2, 3, 4, 5], 50) == 3
    assert metrics_lib.percentile(list(range(101)), 90) == pytest.approx(90)
    assert metrics_lib.token_gaps_ms([rec(0, 1, 10, n_tokens=3, gap=0.1)]) == pytest.approx([100, 100])
    assert metrics_lib.ttfts_ms([rec(0.5, 1.5, 10)], from_due=True) == pytest.approx([1000])
