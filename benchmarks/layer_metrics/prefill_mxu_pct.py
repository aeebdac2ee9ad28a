"""Share of the bf16 matrix peak the prefill programs reach: operations
the prefilled tokens need (``prefill_flops`` of the configuration's
architecture module, ``ctx["arch"]``: the experts a token uses, not those
the dispatch computes) over the prefill modules' device
time x the chip's peak (peaks.json)."""

from metrics_lib import prefilled_in_trace
from reduce_trace import modules_matching

MODULES = ("_prefill_some", "_prefill_suffix")


def read(ctx):
    if ctx["trace"] is None:
        return None
    m = modules_matching(ctx["trace"], MODULES)
    tokens, pairs = prefilled_in_trace(ctx)
    if not m["dev_s"] or not tokens:
        return None
    need = ctx["arch"].prefill_flops(ctx["model"], tokens, pairs)
    return 100.0 * need / (m["dev_s"] * ctx["peaks"]["bf16_flops"])
