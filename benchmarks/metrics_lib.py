"""Arithmetic shared by the end-to-end metrics and the per-layer readers.

A reader gets one ``ctx`` dict: ``trace`` (``reduce_trace.summarize``'s
result, or None without ``--trace 1``), ``trace_window`` ((start, end)
seconds from the window's start), ``counters`` (scheduler counter deltas
over the window), ``trace_counters`` (the same between the traced
window's two markers, or None), ``records`` (the load generator's per-request
records), ``model`` and ``engine`` (the configuration), ``arch`` (its
architecture module: ``arch/llama.py`` says what it exports), ``peaks`` (this
device's row of ``peaks.json``), ``window_s``.
"""

from __future__ import annotations

import importlib.util
import statistics
from pathlib import Path


def load_module(path: Path):
    """A Python file that is named by data (a metric's or an
    architecture's name, which may hold ``.`` or ``-``), as a module."""
    name = f"{path.parent.name}_{path.stem}".replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_reader(name: str):
    """``layer_metrics/<name>.py`` -> its ``read`` function.  A reader
    that gives an existing reading under a second name (for cells that
    judge another end-to-end metric) takes the first one's through this."""
    return load_module(Path(__file__).resolve().parent / "layer_metrics" / f"{name}.py").read


def percentile(values: list, q: float) -> float:
    """Nearest-rank-with-interpolation percentile, q in [0, 100]."""
    if not values:
        raise ValueError("no samples")
    xs = sorted(values)
    pos = (len(xs) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def ttfts_ms(records: list, from_due: bool) -> list:
    """Time to first token of every request that got one: from the
    instant it was due (open loop) or sent."""
    out = []
    for r in records:
        if r["tokens"]:
            t_from = r["due"] if (from_due and r["due"] is not None) else r["sent"]
            out.append((r["tokens"][0] - t_from) * 1000.0)
    return out


def token_gaps_ms(records: list) -> list:
    """All gaps between consecutive streamed tokens, pooled."""
    out = []
    for r in records:
        t = r["tokens"]
        out.extend((b - a) * 1000.0 for a, b in zip(t, t[1:]))
    return out


def tokens_in_window(records: list, window_s: float) -> int:
    return sum(1 for r in records for t in r["tokens"] if t < window_s)


def mean(values: list) -> float:
    return statistics.fmean(values)


def reuse_share(ctx: dict) -> float:
    """Share of the prompt tokens sent that the prefix cache supplied."""
    sent = sum(r["prompt_len"] for r in ctx["records"] if r["tokens"])
    return ctx["counters"]["prefix_tokens_reused"] / sent if sent else 0.0


def idle_pct(ctx: dict):
    """Share of the traced window in which no operation ran on the device:
    1 - union of the device-operation intervals / window."""
    t = ctx["trace"]
    if t is None or not t["window_s"]:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])


def prefilled_in_trace(ctx: dict) -> tuple[float, float]:
    """(tokens, attention pairs) prefilled inside the traced window.

    No counter says which tokens a prefill program held, so this spreads
    each request's new tokens evenly over [sent, first token] and takes
    the part inside the traced window.  The cached share of a prompt is
    that of the traced window itself: the scheduler's reused-token count
    between the two markers over the prompt tokens sent between them.
    Exact for requests wholly inside; a request that straddles an edge
    is counted by the share of its prefill time inside, which is right
    on average and not for each one: with a 10 s window and prefills of
    1-1.5 s that is a few requests of about thirty (PERF.md section 3).
    """
    from model_math import causal_pairs

    a, b = ctx["trace_window"]
    sent = sum(
        r["prompt_len"] for r in ctx["records"]
        if r["sent"] is not None and a <= r["sent"] < b
    )
    share = ctx["trace_counters"]["prefix_tokens_reused"] / sent if sent else 0.0
    tokens = pairs = 0.0
    for r in ctx["records"]:
        if not r["tokens"]:
            continue
        start, end = r["sent"], r["tokens"][0]
        if end <= start:
            continue
        part = max(0.0, min(end, b) - max(start, a)) / (end - start)
        if part <= 0:
            continue
        cached = int(share * r["prompt_len"])
        tokens += part * (r["prompt_len"] - cached)
        pairs += part * causal_pairs(cached, r["prompt_len"])
    return tokens, pairs


def live_kv_tokens(ctx: dict) -> float:
    """K/V tokens the decode step at the middle of the traced window had
    to read: prompt plus tokens so far, of every request decoding then."""
    a, b = ctx["trace_window"]
    mid = (a + b) / 2.0
    live = 0
    for r in ctx["records"]:
        t = r["tokens"]
        if t and t[0] <= mid and (r["end"] is None or r["end"] >= mid) and t[-1] >= mid:
            live += r["prompt_len"] + sum(1 for x in t if x <= mid)
    return float(live)
