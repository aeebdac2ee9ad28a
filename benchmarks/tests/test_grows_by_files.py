"""An architecture, its configuration and a cell arrive as new files and
appended entries: in a copy of the benchmark, with no file that is there
edited, the loaders, both roofline readers, the copy's own test_layout.py
and a CPU rehearsal of the new cell take a toy architecture whose
reference and counts differ visibly from the default's."""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
REPO = BENCH.parent

TOY_ARCH = '''"""A toy family: expert count under ``num_experts``, top-k routing
weights NOT renormalised, experts served in int8."""
import functools

import model_math
import reference
from arch import llama

llama_config = llama.llama_config
prefill_flops = model_math.prefill_flops
last_logits = functools.partial(reference.last_logits, norm_topk=False)


def decode_step_bytes(model, engine, live_kv_tokens):
    return model_math.decode_step_bytes(
        model, {**engine, "expert_weight_dtype": "int8"}, live_kv_tokens
    )
'''
CELL = "toy-moe.rag-closed"


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    """The copy, with the toy added.  Returns its root and the files the
    copy had before, with their contents."""
    root = tmp_path_factory.mktemp("grown")
    shutil.copy(REPO / "BENCHMARK.json", root / "BENCHMARK.json")
    shutil.copytree(
        BENCH, root / "benchmarks",
        ignore=shutil.ignore_patterns("out", "__pycache__", ".pytest_cache"),
    )
    before = {p: p.read_bytes() for p in (root / "benchmarks").rglob("*") if p.is_file()}

    config = json.loads((BENCH / "configs" / "mixtral-8x7b-l4.json").read_text())
    config["arch"] = "toy"
    config["source"] = "https://example.org/toy-moe/config.json"
    config["num_experts"] = config.pop("num_local_experts")
    tiny = config["rehearse"]["model"]
    tiny["num_experts"] = tiny.pop("num_local_experts")
    # All four tiny experts a token: their softmax weights already sum
    # to one, so the program (which renormalises) and this family's
    # reference (which does not) agree, and the rehearsal can be correct.
    tiny["num_experts_per_tok"] = 4
    (root / "benchmarks" / "configs" / "toy-moe.json").write_text(json.dumps(config, indent=1))
    (root / "benchmarks" / "arch" / "toy.py").write_text(TOY_ARCH)

    bench = json.loads((root / "BENCHMARK.json").read_text())
    old = json.loads(json.dumps(bench))
    bench["configs"].append({
        "name": "toy-moe", "source": config["source"], "file": "benchmarks/configs/toy-moe.json",
        "reduced": config["reduced"], "why": "a toy family for the test",
    })
    bench["workloads"].append({
        "name": CELL, "config": "toy-moe", "traffic": "rag-closed", "chips": 1,
        "why": "the Mixtral cell's traffic on the toy family",
    })
    twin = "mixtral-8x7b-l4.rag-closed"  # named wherever its twin is
    for metric in bench["end_to_end"] + bench["per_layer"]:
        if twin in metric.get("workloads", []):
            metric["workloads"].append(CELL)
    (root / "BENCHMARK.json").write_text(json.dumps(bench, indent=1))

    # Entries were appended, none changed.
    for key in ("configs", "workloads"):
        assert bench[key][: len(old[key])] == old[key]
    for key in ("end_to_end", "per_layer"):
        for was, now in zip(old[key], bench[key]):
            assert {**now, "workloads": None} == {**was, "workloads": None}
            assert now.get("workloads", [])[: len(was.get("workloads", []))] == was.get("workloads", [])
    return root, before


def run_in(root, *argv, timeout=900):
    # The program itself is not copied: the copy finds it on PYTHONPATH.
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": str(REPO)}
    return subprocess.run(
        [sys.executable, *argv], cwd=root, env=env, text=True, timeout=timeout,
        stdout=subprocess.PIPE, stderr=subprocess.PIPE,
    )


LOADERS = '''
import json, sys
sys.path.insert(0, "benchmarks")
import run
spec = run.load_cell(sys.argv[1])
model, engine = spec["config"], spec["config"]["engine"]
arch = run.load_arch(model)
cfg = arch.llama_config(model, engine)
trace = {"modules": {
    "jit_decode_chunk": {"count": 10, "dev_s": 1.0},
    "jit__prefill_suffix": {"count": 4, "dev_s": 0.5},
}}
ctx = {"trace": trace, "trace_window": (0.0, 10.0), "counters": {},
       "trace_counters": {"prefix_tokens_reused": 300},
       "records": [{"sent": 1.0, "tokens": [2.0, 6.0], "end": 9.0, "prompt_len": 1500, "max_tokens": 64,
                    "due": None, "finish": None}],
       "model": model, "engine": engine, "arch": arch, "window_s": 10.0,
       "peaks": json.load(open("benchmarks/peaks.json"))["TPU v5 lite"]}
print(json.dumps({
    "arch": arch.__file__, "n_experts": cfg.n_experts, "per_layer": [m["name"] for m in spec["per_layer"]],
    "decode_hbm_pct": run.load_reader("decode_hbm_pct")(ctx),
    "prefill_mxu_pct": run.load_reader("prefill_mxu_pct")(ctx),
    "step_bytes": arch.decode_step_bytes(model, engine, 0),
}))
'''


def test_loaders_and_roofline_readers_take_the_toy(tree):
    root, _ = tree
    toy = run_in(root, "-c", LOADERS, CELL)
    twin = run_in(root, "-c", LOADERS, "mixtral-8x7b-l4.rag-closed")
    assert toy.returncode == 0 and twin.returncode == 0, toy.stderr[-2000:] + twin.stderr[-2000:]
    toy, twin = json.loads(toy.stdout.splitlines()[-1]), json.loads(twin.stdout.splitlines()[-1])
    assert toy["arch"] == str(root / "benchmarks" / "arch" / "toy.py")
    assert twin["arch"] == str(root / "benchmarks" / "arch" / "llama.py")
    assert toy["n_experts"] == 8  # read from the family's own key
    assert toy["per_layer"] == twin["per_layer"] and "decode_hbm_pct" in toy["per_layer"]
    # The toy's experts count one byte a weight: 4 layers x 8 experts x 3 matrices fewer bytes.
    assert twin["step_bytes"] - toy["step_bytes"] == 4 * 8 * 3 * 4096 * 14336
    assert 0 < toy["decode_hbm_pct"] < twin["decode_hbm_pct"]
    assert toy["prefill_mxu_pct"] == twin["prefill_mxu_pct"] > 0  # the same operations


def test_the_copys_own_layout_test_passes(tree):
    root, _ = tree
    done = run_in(root, "-m", "pytest", "benchmarks/tests/test_layout.py", "-q", "-p", "no:cacheprovider")
    assert done.returncode == 0, done.stdout[-3000:]
    assert "toy-moe" in run_in(
        root, "-m", "pytest", "benchmarks/tests/test_layout.py", "-q", "--collect-only",
        "-p", "no:cacheprovider",
    ).stdout


def test_rehearsal_is_correct_on_the_toys_reference(tree):
    root, _ = tree
    done = run_in(
        root, "benchmarks/run.py", "--workload", CELL, "--seed", str(2**31 + 3),
        "--seconds", "6", "--trace", "1", "--rehearse",
    )
    assert done.returncode == 0, done.stderr[-3000:]
    lines = done.stdout.splitlines()
    checks = next(json.loads(l) for l in lines if l.startswith('{"bench": "checks"'))
    assert checks["arch"] == "toy" and checks["reference_check"]["ok"]
    result = json.loads(lines[-1])
    assert result["correct"] is True and result["failed"] == 0
    assert "tick_ms" in result["metrics"]


def test_no_file_that_was_there_changed(tree):
    root, before = tree
    for path, content in before.items():
        assert path.read_bytes() == content, path
