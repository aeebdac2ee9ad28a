"""Prompt tokens prefilled per second of device time of the prefill
modules (batched cold prefill and chunk / suffix prefill) in the trace."""

from metrics_lib import prefilled_in_trace
from reduce_trace import modules_matching

MODULES = ("_prefill_some", "_prefill_suffix")


def read(ctx):
    if ctx["trace"] is None:
        return None
    m = modules_matching(ctx["trace"], MODULES)
    tokens, _ = prefilled_in_trace(ctx)
    if not m["dev_s"] or not tokens:
        return None
    return tokens / m["dev_s"]
