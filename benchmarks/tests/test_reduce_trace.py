"""The reduction from trace events to numbers: on a hand-made trace with
known answers, and on a piece recorded on the v5e (decode-step operations
of mistral-7b, 10 ms; ``reduce_trace.sample`` wrote it)."""

import json
from pathlib import Path

import pytest

import reduce_trace as rt

DATA = Path(__file__).resolve().parent / "data"
MS = 1e6  # ns


def ev(line, name, start_ms, dur_ms, plane="/device:TPU:0"):
    return (plane, line, name, start_ms * MS, dur_ms * MS)


def hand_made():
    return {
        "marks": {rt.MARK_START: 0.0, rt.MARK_END: 100 * MS},
        "events": [
            ev(rt.MODULE_LINE, "jit_decode_chunk(11)", 10, 30),
            ev(rt.MODULE_LINE, "jit_decode_chunk(11)", 60, 30),
            ev(rt.MODULE_LINE, "jit__prefill_suffix(7)", 45, 10),
            ev(rt.MODULE_LINE, "jit__prefill_suffix(9)", 95, 10),  # half outside
            # a scan's while holds its body's operations
            ev(rt.OP_LINE, "%while.1 = (s32[]{:T(128)}) while(...)", 10, 30),
            ev(rt.OP_LINE, "%fusion.2 = bf16[8]{0} fusion(...)", 10, 10),
            ev(rt.OP_LINE, "%fusion.3 = bf16[8]{0} fusion(...)", 22, 18),
            ev(rt.OP_LINE, "%fusion.2 = bf16[8]{0} fusion(...)", 60, 30),
            ev(rt.OP_LINE, "%fusion.9 = bf16[8]{0} fusion(...)", 45, 10),
            ev(rt.OP_LINE, "%fusion.9 = bf16[8]{0} fusion(...)", 95, 10),
        ],
    }


def test_known_busy_share_and_module_times():
    s = rt.summarize(hand_made())
    assert s["window_s"] == pytest.approx(0.100)
    assert s["window_from"] == "markers"
    # busy: 10-40, 45-55, 60-90, 95-100 = 75 ms of 100
    assert s["busy_s"] == pytest.approx(0.075)
    dec = rt.modules_matching(s, ("decode_chunk",))
    assert dec["count"] == 2 and dec["dev_s"] == pytest.approx(0.060)
    pre = rt.modules_matching(s, ("_prefill_some", "_prefill_suffix"))
    # the second is half outside: half an execution, half its time
    assert pre["count"] == pytest.approx(1.5) and pre["dev_s"] == pytest.approx(0.015)
    ops = dict(s["device_ops"])
    assert ops["%fusion.2 = bf16[8] fusion(...)"] == pytest.approx(0.040)  # layouts stripped
    assert not any(k.startswith("%while") for k in ops)  # a parent is not a leaf
    gaps = s["idle_gaps"]
    assert [round(g[1] * 1000) for g in gaps[:2]] == [10, 5]  # window start -> first op; 40-45 etc.
    assert gaps[0][0].startswith("t+0.000s")


def test_without_markers_the_device_extent_is_the_window():
    t = hand_made()
    t["marks"] = {}
    s = rt.summarize(t)
    assert s["window_from"] == "device_extent"
    assert s["window_s"] == pytest.approx(0.095)  # 10 ms .. 105 ms


def test_no_device_plane_is_an_error():
    with pytest.raises(ValueError):
        rt.summarize({"events": [], "marks": {}, "planes": ["/host:CPU"]})


def test_recorded_v5e_piece():
    raw = json.loads((DATA / "v5e_decode_ops_10ms.json").read_text())
    trace = {"events": [tuple(e) for e in raw["events"]], "marks": {}}
    s = rt.summarize(trace)
    # Independent count: paint every operation onto a 1 us grid.
    lo = min(e[3] for e in trace["events"])
    hi = max(e[3] + e[4] for e in trace["events"])
    grid = bytearray(int((hi - lo) / 1e3) + 2)
    for _, _, _, start, dur in trace["events"]:
        a, b = int((start - lo) / 1e3), int((start + dur - lo) / 1e3)
        grid[a : b + 1] = b"\x01" * (b + 1 - a)
    painted = sum(grid) * 1e-6
    assert s["busy_s"] == pytest.approx(painted, rel=0.02)
    assert s["busy_s"] / s["window_s"] > 0.95  # inside a decode chunk the chip is busy
    # The decode step is the attention kernel and three weight matmuls.
    top = [name for name, _ in s["device_ops"][:4]]
    assert top[0].startswith("%decode_gqa_attention")
    assert sum("fusion(s8[32,4096," in n or "s8[32,14336,4096]" in n for n in top[1:]) >= 2
    assert all("{" not in name and len(name) <= 110 for name, _ in s["device_ops"])
