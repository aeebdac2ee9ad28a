"""What ``benchmarks/`` (the benchmark the driver runs: ``BENCHMARK.json:
command``) reads from the program, held by tier-1.

A per-layer reader returns ``None`` on a program that lacks its counter
(``benchmarks/counter_lib.py``) and a device-trace reader sums nothing
where no module bears its name, so a renamed key of ``Stats.snapshot()``
or a renamed step program would pass every other test and blind the
ledger.  Here it fails, by the metric's name.  The cases are read from
``BENCHMARK.json`` at collection: a new metric or cell brings its case.
Nothing under ``benchmarks/`` is edited; its own tests
(``benchmarks/tests/``) are outside tier-1.
"""

import ast
import functools
import json
import os
import subprocess
import sys
from numbers import Number
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[1]
BENCH = REPO / "benchmarks"
SPEC = json.loads((REPO / "BENCHMARK.json").read_text())
CELLS = {w["name"]: w for w in SPEC["workloads"]}
COMMAND = SPEC["command"][1:]  # without the interpreter


def _cells_of(metric: dict) -> list:
    return metric.get("workloads", list(CELLS))


@functools.lru_cache(maxsize=None)
def _config(cell: str) -> dict:
    return json.loads(
        (BENCH / "configs" / f"{CELLS[cell]['config']}.json").read_text()
    )


def _families(metric: dict) -> list:
    """The program's tiny presets whose scheduler must serve the metric:
    ``<arch>-tiny`` for each architecture among the cells that list it."""
    archs = {_config(c).get("arch", "llama") for c in _cells_of(metric)}
    return sorted(f"{a}-tiny" for a in archs)


def _modules(metric: dict) -> tuple:
    """The ``MODULES`` tuple of a device-trace reader, from its source."""
    path = BENCH / "layer_metrics" / f"{metric['name']}.py"
    for node in ast.parse(path.read_text()).body:
        if isinstance(node, ast.Assign) and any(
            getattr(t, "id", None) == "MODULES" for t in node.targets
        ):
            return ast.literal_eval(node.value)
    return ()


# The readers of ``Stats.snapshot()``.  Those of the layer "engine
# set-up" read ``runtime_report()`` instead, which a snapshot set to ones
# does not feed: ``tests/test_setup_tracing.py`` holds them to the
# program the same way, in a process that built a scheduler.
# ``idle_unseen_ms`` holds the clock's starved seconds to the trace: a
# device-trace reader that reads the snapshot too.
COUNTER_METRICS = [
    m for m in SPEC["per_layer"]
    if (m["source"] == "program_counter" and m["layer"] != "engine set-up")
    or m["name"] == "idle_unseen_ms"
]
MODULE_CASES = sorted(
    {
        (module, family)
        for m in SPEC["per_layer"]
        if m["source"] == "device_trace"
        for module in _modules(m)
        for family in _families(m)
    }
)


@pytest.fixture(scope="module")
def schedulers():
    """One fresh scheduler a family, at the preset's size."""
    from generativeaiexamples_tpu.engine.scheduler import Scheduler
    from generativeaiexamples_tpu.models import hybrid, llama

    built = {}

    def get(family: str):
        if family not in built:
            presets = {**llama.PRESETS, **hybrid.PRESETS}
            if family not in presets:
                pytest.fail(
                    f"a cell's configuration has an arch with no preset "
                    f"{family!r} among the program's {sorted(presets)}"
                )
            built[family] = Scheduler(
                presets[family](), None, max_batch=2, max_len=128
            )
        return built[family]

    yield get
    for scheduler in built.values():
        scheduler.stop()


@pytest.fixture(scope="module")
def load_reader():
    """``benchmarks/metrics_lib.py::load_reader``, with ``benchmarks/`` on
    the path while this file's tests run (the readers import their
    neighbours by bare name)."""
    sys.path.append(str(BENCH))
    try:
        import metrics_lib

        yield metrics_lib.load_reader
    finally:
        sys.path.remove(str(BENCH))


@pytest.mark.parametrize("metric", COUNTER_METRICS, ids=lambda m: m["name"])
def test_counter_reader_finds_its_counters_in_the_scheduler(
    metric, schedulers, load_reader
):
    """Every numeric key of a fresh scheduler's snapshot, set to 1, is
    enough for the reader to give a number: it reads no key the program
    does not write."""
    cell = _cells_of(metric)[0]
    families = _families(metric)  # llama's, unless only another lists it
    family = "llama-tiny" if "llama-tiny" in families else families[0]
    snapshot = schedulers(family).stats.snapshot()
    ones = {k: 1 for k, v in snapshot.items() if isinstance(v, Number)}
    ones["ttft_sum_ms"] = 1  # run.py::counters derives it from the snapshot
    model = _config(cell)
    ctx = {
        "trace": {"modules": {}, "window_s": 10.0, "busy_s": 9.0},
        "trace_window": (20.0, 30.0),
        "counters": dict(ones),
        "trace_counters": dict(ones),
        "records": [
            {"prompt_len": 8, "due": None, "sent": 0.0, "end": 1.0,
             "tokens": [0.5, 0.6]},
        ],
        "model": model,
        "engine": model["engine"],
        "arch": None,
        "peaks": None,
        "window_s": 45.0,
    }
    value = load_reader(metric["name"])(ctx)
    assert isinstance(value, Number), (
        f"{metric['name']} reads a counter that Stats.snapshot() of "
        f"{family} does not have"
    )


@pytest.mark.parametrize("module, family", MODULE_CASES)
def test_traced_module_is_a_step_program_of_the_scheduler(
    module, family, schedulers
):
    """A device-trace reader sums the modules whose name contains its
    pattern; XLA names a module ``jit_<__name__>`` of the jitted function."""
    import jax

    programs = {
        f"jit_{fn.__name__}"
        for fn in vars(schedulers(family)).values()
        if isinstance(fn, jax.stages.Wrapped)
    }
    assert f"jit_{module}" in programs, sorted(programs)


def _run(*argv):
    """The benchmark's command on the CPU, as the driver starts it.  A
    rehearsal takes one to two minutes alone and little more at the run's
    end, three at once (``tests/conftest.py``: 59-134 s each in a whole
    run of PR 52's tree); beside five other workers it took four to five
    times that, and the requests missed their deadline long before this
    limit, which only turns a child that hangs into one red case."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    # The tests' own programs are compiled without optimisation
    # (``tests/conftest.py``); a rehearsal's requests wait 20 s for a first
    # token, and code that slow misses it.
    env["XLA_FLAGS"] = env.get("XLA_FLAGS", "").replace("--xla_backend_optimization_level=0", "")
    return subprocess.run(
        [sys.executable, *COMMAND, *argv],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=300,
    )


@pytest.fixture(scope="module")
def compile_cache(tmp_path_factory):
    """One compile cache for the file's rehearsals: two cells of one
    configuration compile its programs once."""
    return str(tmp_path_factory.mktemp("jax_cache"))


# A rehearsal takes one to two minutes, so the layer-kind families' cells
# rehearse in a second file, ``tests/test_benchmark_contract_layer_kinds.py``
# (the same body over their cases), on another worker than this file's.
def cells_of(layer_kinds: bool) -> list:
    """The cells whose configuration names an ``arch`` (a layer-kind
    family's), or the llama-shaped ones, which name none."""
    return [cell for cell in CELLS if ("arch" in _config(cell)) == layer_kinds]


def rehearse(cell, compile_cache, monkeypatch):
    """Tiny sizes, three seconds: the harness and the program still meet,
    and the last line holds every end-to-end metric the cell lists.  One
    exception, decided by the run's own record: in a closed loop in which
    no request ended inside the window, every client's first token can
    come after the window's end (RAG prompts on a CPU: 3.6 s for Ling
    alone, 4.6 s for Mixtral beside ten busy processes), and the load
    generator cuts as soon as the last has one, so there is no gap between
    two tokens and the harness leaves ``itl_p95_ms`` out, as it says it
    does.  ``correct`` is not asserted (too few requests end) and no
    number of the line is a measurement."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", compile_cache)
    proc = _run(
        "--workload", cell, "--seed", "3", "--seconds", "3",
        "--trace", "0", "--rehearse",
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = [json.loads(ln) for ln in proc.stdout.strip().splitlines()]
    line = lines[-1]
    (window,) = [ln for ln in lines if ln.get("bench") == "window"]
    # A red rehearsal says which request failed: the harness's own record
    # of the window (finishes by client, the counters, the longest ticks).
    assert line["failed"] == 0 and line["attempted"] > 0, json.dumps(window)[:6000]
    assert line["device"]["platform"] == "cpu" and "rehearsal" in line
    listed = {m["name"] for m in SPEC["end_to_end"] if cell in _cells_of(m)}
    assert window["ttft_samples"] == line["attempted"]
    closed_and_none_ended = (
        window["rate_rps"] is None and window["complete"] == 0
    )
    if closed_and_none_ended and window["gap_samples"] == 0:
        listed.discard("itl_p95_ms")
    assert set(line["metrics"]) == listed
    assert all(isinstance(m["value"], Number) for m in line["metrics"].values())


@pytest.mark.parametrize("cell", cells_of(False))
def test_cell_rehearses_end_to_end_on_the_cpu(cell, compile_cache, monkeypatch):
    rehearse(cell, compile_cache, monkeypatch)
