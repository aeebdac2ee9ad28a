"""Share of the HBM peak one decode step reaches: the bytes it must read
(weights once, the K/V of every live token; ``decode_step_bytes`` of the
configuration's architecture module, ``ctx["arch"]``) over its
device time x the chip's peak bytes/s (peaks.json).  The whole step
stands for the kernels until they have names in the trace."""

from metrics_lib import live_kv_tokens
from reduce_trace import modules_matching

MODULES = ("decode_chunk",)


def read(ctx):
    if ctx["trace"] is None:
        return None
    m = modules_matching(ctx["trace"], MODULES)
    if not m["count"]:
        return None
    step_s = m["dev_s"] / (m["count"] * ctx["engine"]["decode_chunk_size"])
    need = ctx["arch"].decode_step_bytes(
        ctx["model"], ctx["engine"], live_kv_tokens(ctx)
    )
    return 100.0 * need / (step_s * ctx["peaks"]["hbm_bytes_per_s"])
