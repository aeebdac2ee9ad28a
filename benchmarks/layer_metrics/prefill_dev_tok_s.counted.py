"""Prompt tokens per second of device time of the prefill modules, with
the tokens counted by the scheduler at each dispatch between the trace
markers (``prefill_dev_tok_s`` estimates them from the client's records)."""

from reduce_trace import modules_matching

MODULES = ("_prefill_some", "_prefill_suffix")


def read(ctx):
    c = ctx.get("trace_counters")
    if ctx.get("trace") is None or c is None:
        return None
    tokens = c.get("prefill_tokens_dispatched")
    dev_s = modules_matching(ctx["trace"], MODULES)["dev_s"]
    if not tokens or not dev_s:
        return None
    return tokens / dev_s
