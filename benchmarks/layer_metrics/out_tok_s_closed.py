"""Tokens received inside the window / window, in a closed-loop cell
whose bound on ``out_tok_s`` its runs cannot keep (PERF.md section 2):
the same number, reported per layer and unbounded.  A traced run reads
it lower than an untraced one."""

from metrics_lib import tokens_in_window


def read(ctx):
    if not ctx["records"]:
        return None
    return tokens_in_window(ctx["records"], ctx["window_s"]) / ctx["window_s"]
