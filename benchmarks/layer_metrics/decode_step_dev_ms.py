"""Device time of one decode step: the decode-chunk XLA module's time in
the trace over its executions x steps per chunk."""

from reduce_trace import modules_matching

MODULES = ("decode_chunk",)


def read(ctx):
    if ctx["trace"] is None:
        return None
    m = modules_matching(ctx["trace"], MODULES)
    if not m["count"]:
        return None
    return 1000.0 * m["dev_s"] / (m["count"] * ctx["engine"]["decode_chunk_size"])
