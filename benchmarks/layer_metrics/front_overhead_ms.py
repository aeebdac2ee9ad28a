"""Mean client TTFT (from the send) minus the scheduler's own mean TTFT
(submit to first token fetched) over the window: what the HTTP front,
the JSON body, the token bridge and the SSE write add."""

from metrics_lib import mean, ttfts_ms


def read(ctx):
    c = ctx["counters"]
    client = ttfts_ms(ctx["records"], from_due=False)
    if not client or not c["ttft_count"]:
        return None
    return mean(client) - c["ttft_sum_ms"] / c["ttft_count"]
