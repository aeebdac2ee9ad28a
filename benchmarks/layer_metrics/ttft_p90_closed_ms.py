"""90th percentile of the client's time from send to first streamed token
in a closed-loop cell.  Not an end-to-end metric there: a closed loop's
run settles on one of a few trajectories (a request admitted one tick
earlier or later shifts every later send), and the thirteen or so samples
beyond p90 follow the trajectory, so runs of one tree read 1,565-1,655 ms
in steps.  It stands beside the cell's ``ttft_p50_ms``, which is judged."""

from metrics_lib import percentile, ttfts_ms


def read(ctx):
    ttft = ttfts_ms(ctx["records"], from_due=False)
    return percentile(ttft, 90) if ttft else None
