"""The rate sweep that fixes an open-loop cell's rate, made once.

    python3 benchmarks/sweep.py --workload <cell> --rates 1.0,1.5,2.0 --seconds 40

Runs ``run.py`` once per rate (each a process of its own: this parent
never touches JAX, so the chip is free for the child) and prints one
table row per rate.  A rate is sustained when nothing failed or was
refused, no more requests are in flight at the window's end than at its
middle (give or take 3, see below), and the mean TTFT of the requests
sent in the last third of the window is at most 1.1 x that of the
middle third.  The cell then runs at 0.8 of the highest sustained rate,
which the last line gives; the table goes into PERF.md and the rate into
the cell's file.
"""

from __future__ import annotations

import argparse
import json
import subprocess
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
# Just above the knee the queue grows all through the window: at 4.0
# requests/s the last third's TTFT was 1.21 x the middle third's and the
# median twice that of 3.5 requests/s, where it was 0.94 x (PERF.md).
TTFT_GROWTH = 1.1


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--workload", required=True)
    parser.add_argument("--rates", required=True)
    parser.add_argument("--seconds", type=float, default=40.0)
    parser.add_argument("--seed", type=int, default=1)
    args = parser.parse_args(argv)
    rows = []
    for rate in (float(x) for x in args.rates.split(",")):
        proc = subprocess.run(
            [sys.executable, str(HERE / "run.py"), "--workload", args.workload,
             "--seed", str(args.seed), "--seconds", str(args.seconds),
             "--trace", "0", "--rate", str(rate)],
            stdout=subprocess.PIPE, text=True,
        )
        lines = [json.loads(x) for x in proc.stdout.splitlines() if x.startswith("{")]
        window = next((x for x in lines if x.get("bench") == "window"), None)
        if proc.returncode != 0 or window is None:
            rows.append({"rate_rps": rate, "error": proc.returncode})
            continue
        result = lines[-1]
        e2e = window["end_to_end"]
        row = {
            "rate_rps": rate,
            "sent": window["requests_sent"],
            "failed": result["failed"],
            "in_flight_mid": window["in_flight_mid"],
            "in_flight_end": window["in_flight_end"],
            "ttft_p50_ms": e2e["ttft_p50_ms"],
            "ttft_p90_ms": e2e["ttft_p90_ms"],
            "itl_p95_ms": e2e["itl_p95_ms"],
            "out_tok_s": e2e["out_tok_s"],
            "late_p95_ms": window["loadgen"]["late_p95_ms"],
            "correct": result["correct"],
        }
        row["ttft_mean_mid_third_ms"] = window["ttft_mean_mid_third_ms"]
        row["ttft_mean_last_third_ms"] = window["ttft_mean_last_third_ms"]
        # Poisson arrivals make the in-flight count itself wander by a few,
        # so it may end 3 above the middle; a growing queue also shows as
        # a TTFT that is still rising in the last third.
        row["sustained"] = bool(
            result["failed"] == 0
            and row["in_flight_end"] <= row["in_flight_mid"] + 3
            and row["ttft_mean_last_third_ms"] <= TTFT_GROWTH * row["ttft_mean_mid_third_ms"]
        )
        rows.append(row)
        print(json.dumps({"sweep": row}), flush=True)
    print(json.dumps({"sweep_table": rows}), flush=True)
    knee = max((r["rate_rps"] for r in rows if r.get("sustained")), default=None)
    print(json.dumps({"knee_rps": knee, "cell_rate_rps": knee and round(0.8 * knee, 2)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
