"""Host time of one dispatch site: the tick thread's ``dispatch`` phase
(``Stats.tick_phase_dispatch_s``) over the sites it returned from
(``Stats.dispatch_sites``: a decode chunk, a prefill program with what is
enqueued behind it, a graft).  Its stages are ``.h2d`` and ``.call``;
the rest is the site's counters under the stats' lock."""

from counter_lib import ratio


def read(ctx):
    return ratio(ctx, ["tick_phase_dispatch_s"], ["dispatch_sites"], 1000.0)
