"""Device time of ONE PASS of the stack in a decode step: the decode-chunk
XLA module's time in the trace over the passes of the stack the scheduler
dispatched between the trace's two markers (``Stats.decode_stack_passes``:
decode steps x ``ut_steps``).  For a stack that a token passes once it is
``decode_step_dev_ms``; for a looped one it is what a pass of the layers
costs, the number that stays put when fewer passes run and falls when a
pass gets faster.  ``None`` on a program that lacks the counter (the commit
before the one that added it)."""

from reduce_trace import modules_matching

MODULES = ("decode_chunk",)


def read(ctx):
    if ctx["trace"] is None or ctx.get("trace_counters") is None:
        return None
    passes = ctx["trace_counters"].get("decode_stack_passes")
    dev_s = modules_matching(ctx["trace"], MODULES)["dev_s"]
    if not passes or not dev_s:
        return None
    return 1000.0 * dev_s / passes
