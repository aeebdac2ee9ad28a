"""From a profiler trace to numbers.

``read_xplane`` turns an ``.xplane.pb`` into plain event tuples
``(plane, line, name, start_ns, dur_ns)`` for the device planes, plus the
benchmark's own window markers from the host planes.  ``summarize`` works
on those tuples alone, so it is checked on a small recorded trace kept as
JSON (``tests/data``).

On a TPU plane the profiler writes a line of whole XLA modules (one
event per execution of a jitted program, named ``jit_<fn>(<id>)``) and a
line of the operations inside them.  Busy time is the union of the
operation intervals; a module's device time is the sum of its module
events.  A device that is not in the trace is an error, not a zero.
"""

from __future__ import annotations

import re
from pathlib import Path

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
MODULE_LINE = "XLA Modules"
OP_LINE = "XLA Ops"
MARK_START = "bench_trace_window_start"
MARK_END = "bench_trace_window_end"
_ID_SUFFIX = re.compile(r"\(\d+\)$")


def find_xplane(trace_dir: str | Path) -> Path:
    found = sorted(Path(trace_dir).glob("plugins/profile/*/*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def read_xplane(
    path: str | Path, device_plane=DEVICE_PLANE, every_line_is_ops: bool = False
) -> dict:
    """Device events and window markers of one trace file.

    ``every_line_is_ops`` is for the CPU rehearsal alone: the host plane
    has threads, not module and operation lines, so its events are all
    taken as operations to drive the same code."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    events = []
    marks = {}
    plane_names = []
    for plane in data.planes:
        plane_names.append(plane.name)
        is_device = bool(device_plane.match(plane.name))
        for line in plane.lines:
            if is_device and not every_line_is_ops and line.name not in (
                MODULE_LINE, OP_LINE
            ):
                continue
            line_name = OP_LINE if every_line_is_ops else line.name
            for ev in line.events:
                if ev.name in (MARK_START, MARK_END):
                    marks[ev.name] = float(ev.start_ns)
                elif is_device:
                    events.append(
                        (plane.name, line_name, ev.name, float(ev.start_ns), float(ev.duration_ns))
                    )
    return {"events": events, "marks": marks, "planes": plane_names}


def sample(trace: dict, seconds: float = 0.5, min_op_ns: float = 50e3, limit: int = 3000) -> dict:
    """A small piece of a trace, to keep as a recorded fixture: of the
    first ``seconds`` after the window opens, every module event and the
    operations that last at least ``min_op_ns``."""
    events = trace["events"]
    if not events:
        return {"events": [], "marks": {}}
    t0 = trace["marks"].get(MARK_START, min(e[3] for e in events))
    kept = sorted(
        (
            e for e in events
            if t0 <= e[3] < t0 + seconds * 1e9
            and (e[1] == MODULE_LINE or e[4] >= min_op_ns)
        ),
        key=lambda e: e[3],
    )[:limit]
    return {
        "events": [[e[0], e[1], e[2], e[3] - t0, e[4]] for e in kept],
        "marks": {},
    }


_LAYOUT = re.compile(r"\{[^{}]*\}")


def short_op(name: str, width: int = 110) -> str:
    """An operation's HLO text without layouts, cut to ``width``: the
    trace names an operation by its whole instruction."""
    return _LAYOUT.sub("", name)[:width]


def _leaves(intervals: list) -> list:
    """Of nested (start, end, name) intervals on one line, those that
    contain no other: a scan's ``while`` holds the operations of its
    body, and only the body says where the time goes."""
    ordered = sorted(intervals, key=lambda x: (x[0], -x[1]))
    out = []
    for i, cur in enumerate(ordered):
        nxt = ordered[i + 1] if i + 1 < len(ordered) else None
        if nxt is None or nxt[0] >= cur[1]:
            out.append(cur)
    return out


def module_key(name: str) -> str:
    """``jit__prefill_suffix(1234)`` -> ``jit__prefill_suffix``."""
    return _ID_SUFFIX.sub("", name.strip())


def _union(intervals: list) -> list:
    """Merge (start, end, name) intervals; keeps the name that ends last."""
    merged: list[list] = []
    for start, end, name in sorted(intervals):
        if merged and start <= merged[-1][1]:
            if end > merged[-1][1]:
                merged[-1][1] = end
                merged[-1][2] = name
        else:
            merged.append([start, end, name, name])  # [start, end, last name, first name]
    return merged


def summarize(trace: dict, top: int = 10) -> dict:
    """Busy time, per-module device time, the heaviest operations and the
    longest idle gaps of the traced window."""
    events = trace["events"]
    planes = sorted({e[0] for e in events})
    if not planes:
        raise ValueError(
            f"no device plane in the trace (planes: {trace.get('planes')})"
        )
    marks = trace.get("marks", {})
    if MARK_START in marks and MARK_END in marks:
        w0, w1 = marks[MARK_START], marks[MARK_END]
        window_from = "markers"
    else:
        w0 = min(e[3] for e in events)
        w1 = max(e[3] + e[4] for e in events)
        window_from = "device_extent"
    busy_by_plane = []
    modules: dict[str, dict] = {}
    ops: dict[str, float] = {}
    gaps = []
    for plane in planes:
        mine = [e for e in events if e[0] == plane]
        has_ops = any(e[1] == OP_LINE for e in mine)
        busy_line = OP_LINE if has_ops else MODULE_LINE
        clipped = []
        for _, line, name, start, dur in mine:
            s, e = max(start, w0), min(start + dur, w1)
            if e <= s:
                continue
            if line == MODULE_LINE:
                m = modules.setdefault(module_key(name), {"count": 0.0, "dev_s": 0.0})
                # An execution that straddles an edge of the window counts
                # by the part of it inside, as its time does, so that time
                # per execution has no edge error.
                m["count"] += (e - s) / dur
                m["dev_s"] += (e - s) / 1e9
            if line == busy_line:
                clipped.append((s, e, name))
        if has_ops:
            for s, e, name in _leaves(clipped):
                ops[name] = ops.get(name, 0.0) + (e - s) / 1e9
        merged = _union(clipped)
        busy_by_plane.append(sum(m[1] - m[0] for m in merged) / 1e9)
        edges = [[w0, w0, "window start", "window start"]] + merged + [[w1, w1, "window end", "window end"]]
        for before, after in zip(edges, edges[1:]):
            gap = (after[0] - before[1]) / 1e9
            if gap > 0:
                gaps.append(
                    (
                        f"t+{(before[1] - w0) / 1e9:.3f}s {plane[-5:]} after "
                        f"{short_op(before[2], 40)} before {short_op(after[3], 40)}",
                        gap,
                    )
                )
    if not has_ops:
        ops = {k: v["dev_s"] for k, v in modules.items()}
    return {
        "window_s": (w1 - w0) / 1e9,
        "window_from": window_from,
        "devices": len(planes),
        "busy_s": sum(busy_by_plane) / len(busy_by_plane),
        "modules": modules,
        "device_ops": [
            [short_op(k), v]
            for k, v in sorted(ops.items(), key=lambda kv: -kv[1])[:top]
        ],
        "idle_gaps": [
            [k, v] for k, v in sorted(gaps, key=lambda kv: -kv[1])[:top]
        ],
    }


def modules_matching(summary: dict, patterns) -> dict:
    """Executions (fractional at the window's edges) and device seconds
    of the modules whose name contains one of ``patterns``."""
    count, dev_s = 0.0, 0.0
    for name, m in summary["modules"].items():
        if any(p in name for p in patterns):
            count += m["count"]
            dev_s += m["dev_s"]
    return {"count": count, "dev_s": dev_s}
