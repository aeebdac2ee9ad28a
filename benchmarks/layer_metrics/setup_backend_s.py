"""Seconds those executables spent in the backend: reads of the
persistent cache on a warm set-up, XLA's compiles on a cold one."""

from setup_lib import report, total


def read(ctx):
    return total(report(), "backend_s")
