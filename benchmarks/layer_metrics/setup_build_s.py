"""Seconds in ``Scheduler.__init__``: the parameters made, quantized and
placed, the slots' state, and the family of programs it compiles itself
(``runtime_report()["setup"]["build_s"]``, the sum of the ``setup/params``,
``setup/state`` and ``setup/programs`` spans)."""

from setup_lib import build_s, report


def read(ctx):
    return build_s(report())
