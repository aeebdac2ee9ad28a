"""``--control`` serves through the program's int8-activation path, the
control of the reference check (run.py, ``CONTROL_ENGINE``).  What the
check says of it is read on the chip at the cells' own sizes (PERF.md
section 6): at the rehearsal's 64-wide, two-layer size the int8
activations move no greedy token, so here only the switch is tested."""

import json
import os
import subprocess
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]


def test_control_switches_the_w8a8_path_on_and_marks_the_result():
    done = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", "mistral-7b.chat-closed", "--seed", "9",
         "--seconds", "3", "--trace", "0", "--rehearse", "--control"],
        cwd=REPO, env={**os.environ, "JAX_PLATFORMS": "cpu"}, text=True, timeout=900,
        stdout=subprocess.PIPE, stderr=subprocess.PIPE,
    )
    assert done.returncode == 0, done.stderr[-3000:]
    lines = done.stdout.splitlines()
    built = next(json.loads(l) for l in lines if l.startswith('{"bench": "scheduler built"'))
    assert built["matmul_kernel"] == "pallas_w8a8"
    result = json.loads(lines[-1])
    assert "control" in result and "rehearsal" in result
    checks = next(json.loads(l) for l in lines if l.startswith('{"bench": "checks"'))
    assert checks["reference_check"]["of"] == 8  # the reference reads the blocked weights
