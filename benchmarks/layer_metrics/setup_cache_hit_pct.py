"""Of those executables, the share the persistent cache had: 0 on a cold
set-up, 100 on a warm one, and in between where a later run finds only
part of what an earlier one wrote."""

from setup_lib import hit_pct, report


def read(ctx):
    return hit_pct(report())
