"""90th percentile of the client's time from due to first streamed token
in an open-loop cell.  Not an end-to-end metric there since PR 26: about
125 requests fit the window, so a dozen lie beyond p90, and in one run in
four a single tick waits 1-2.7 s for a device result (``wait_device``),
which delays every warming slot at once and moves p90 by 12 % while p50
moves by 3 %: sets of 6 spread 7.6-8.2 %, more than any bound allowed
could hold (PERF.md section 2).  It stands beside the cell's
``ttft_p50_ms``, which is judged."""

from metrics_lib import percentile, ttfts_ms


def read(ctx):
    ttft = ttfts_ms(ctx["records"], from_due=True)
    return percentile(ttft, 90) if ttft else None
