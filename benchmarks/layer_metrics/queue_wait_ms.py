"""Mean wait from submit to the claim of a slot, of the requests claimed
inside the traced window (``Scheduler._note_claim``)."""

from counter_lib import ratio


def read(ctx):
    return ratio(ctx, ["queue_wait_s_sum"], ["queue_wait_count"], 1000.0)
