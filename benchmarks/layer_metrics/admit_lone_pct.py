"""Share of the cold prompts of at most one prefill chunk whose admission
went out as a chunk of one row of its own, and not as a row of a
``_prefill_some`` batch padded to its bucket: 100 x d ``admits_lone`` /
d (``admits_lone`` + ``admits_batched``), the scheduler's counters of
``Scheduler._admit_cold`` over the traced window.  Nothing to read from a
program without the counters, or in a window that admitted no such
prompt."""

from counter_lib import ratio


def read(ctx):
    return ratio(ctx, ["admits_lone"], ["admits_lone", "admits_batched"], 100.0)
