"""Seconds those executables spent becoming a jaxpr and then StableHLO:
Python on the host, which no compile cache saves."""

from setup_lib import report, total


def read(ctx):
    return total(report(), "trace_s", "lower_s")
