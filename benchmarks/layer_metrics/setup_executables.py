"""Executables that set-up asked JAX for, found in the cache or built:
those ``Scheduler.__init__`` asked for and those the tick thread asked
for while the warm-up ran every step program for the first time."""

from setup_lib import report, total


def read(ctx):
    return total(report(), "executables")
