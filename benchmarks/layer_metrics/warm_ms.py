"""Mean time from the claim of a slot to the first token fetched (the
prefill, one chunk a tick), of the requests whose first token came inside
the traced window (``Scheduler._note_first_token``)."""

from counter_lib import ratio


def read(ctx):
    return ratio(ctx, ["warm_s_sum"], ["warm_count"], 1000.0)
