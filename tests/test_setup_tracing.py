"""Set-up from the inside: the record of every executable the process
asked JAX for (``utils.jax_runtime.EXECUTABLES``), who asked, the three
stages of ``Scheduler.__init__``, and what serving shows of it
(``Stats``, the tick record, ``/metrics``, ``/debug/executables``,
``/health``).  CPU, tiny llama and tiny hybrid."""

import asyncio
import json
import os
import subprocess
import sys
import threading
import time
from numbers import Number
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from aiohttp.test_utils import TestClient, TestServer

from generativeaiexamples_tpu.engine.replica import EnginePool
from generativeaiexamples_tpu.engine.scheduler import (
    TICK_RECORD_FIELDS,
    Scheduler,
)
from generativeaiexamples_tpu.engine.tokenizer import ByteTokenizer
from generativeaiexamples_tpu.models import hybrid, llama
from generativeaiexamples_tpu.utils import jax_runtime
from generativeaiexamples_tpu.utils.jax_runtime import (
    EXECUTABLES,
    ExecutableRecord,
)

REPO = Path(__file__).resolve().parents[1]
CFG = llama.llama_tiny(dtype="float32", max_seq_len=128)
SETUP_METRICS = [
    m["name"]
    for m in json.loads((REPO / "BENCHMARK.json").read_text())["per_layer"]
    if m["layer"] == "engine set-up"
]
# The tokens-a-tick average nothing read (in two parts: ``git grep`` of
# the whole name is to find it in no file of the tree).
REMOVED = "tick_tokens" + "_ewma"
TRACE = "/jax/core/compile/jaxpr_trace_duration"
LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
BACKEND = "/jax/core/compile/backend_compile_duration"
CACHE = "/jax/compilation_cache/"


@pytest.fixture(autouse=True)
def watching():
    """JAX's events reach the record (an entry point's
    ``enable_compile_cache`` does this; the cache itself stays off)."""
    jax_runtime._watch_compiles()


def _since(t: float) -> list:
    return [e for e in EXECUTABLES.newest() if e["t"] >= t]


# -- the record ---------------------------------------------------------------


def test_a_first_call_makes_one_entry_and_a_second_call_none():
    @jax.jit
    def doubled_plus_one(x):
        return x * 2 + 1

    t = time.perf_counter()
    doubled_plus_one(jnp.ones(7)).block_until_ready()
    mine = [e for e in _since(t) if e["fun_name"] == "doubled_plus_one"]
    assert len(mine) == 1
    (entry,) = mine
    assert entry["trace_s"] > 0 and entry["lower_s"] > 0 and entry["backend_s"] > 0
    assert entry["asked_by"] == "other" and entry["cache"] in ("off", "miss", "hit")
    assert t <= entry["t"] <= time.perf_counter()
    t = time.perf_counter()
    doubled_plus_one(jnp.ones(7)).block_until_ready()
    assert not [e for e in _since(t) if e["fun_name"] == "doubled_plus_one"]


def test_listeners_are_registered_once_however_often_asked():
    from jax._src import monitoring

    jax_runtime._watch_compiles()
    jax_runtime._watch_compiles()
    assert monitoring._event_listeners.count(EXECUTABLES.on_event) == 1
    assert (
        monitoring._event_duration_secs_listeners.count(EXECUTABLES.on_duration)
        == 1
    )


def _feed(record, fun="step", hit=None, trace=0.5, lower=0.25, backend=2.0):
    """The events of one executable, as JAX sends them."""
    record.on_duration(TRACE, 0.125, fun_name="inner")
    record.on_duration(TRACE, trace, fun_name=fun)
    record.on_duration(TRACE, 0.0, fun_name=fun)  # the cached jaxpr, again
    record.on_duration(LOWER, lower, fun_name=f"jit({fun})")
    if hit is not None:
        record.on_event(CACHE + "compile_requests_use_cache")
    if hit:
        record.on_event(CACHE + "cache_hits")
        record.on_duration(CACHE + "compile_time_saved_sec", 30.0)
        record.on_duration(CACHE + "cache_retrieval_time_sec", 1.5)
    record.on_duration(BACKEND, backend, fun_name=f"jit({fun})")


@pytest.fixture
def cache_dir_named():
    """``set(path)`` names a compile-cache directory to JAX's config (no
    executable is made while it is named, so none is written there)."""
    before = jax.config.jax_compilation_cache_dir

    def name(path):
        jax.config.update("jax_compilation_cache_dir", path)

    yield name
    name(before)


def test_an_entry_is_assembled_from_the_events_between_two_backend_compiles(
    cache_dir_named,
):
    record = ExecutableRecord()
    cache_dir_named("/somewhere")
    _feed(record, hit=True)
    _feed(record, fun="other_step", hit=False, trace=1.0)
    cache_dir_named(None)
    _feed(record, fun="uncached", hit=False)  # asked, with nowhere to look
    first, second, third = record.newest(10)
    assert first == {
        "fun_name": "step", "trace_s": 0.5, "lower_s": 0.25, "backend_s": 2.0,
        "cache": "hit", "retrieval_s": 1.5, "saved_s": 30.0, "t": first["t"],
        "asked_by": "other",
    }
    # Nothing of the first leaks into the second.
    assert second["cache"] == "miss" and second["trace_s"] == 1.0
    assert second["retrieval_s"] == second["saved_s"] == 0.0
    assert third["cache"] == "off"
    other = record.report()["executables"]["other"]
    assert other == {
        "executables": 3, "hit": 1, "miss": 1, "off": 1,
        "trace_s": 2.0, "lower_s": 0.75, "backend_s": 6.0,
    }


def test_who_asked_is_the_threads_own_and_ends_with_its_block():
    record = ExecutableRecord(limit=4)
    seen = []
    with record.asking("tick", lambda: {"tick": 7, "program": "decode_chunk",
                                        "kv_bucket": 512}, seen.append):
        _feed(record)
        elsewhere = threading.Thread(target=_feed, args=(record, "elsewhere"))
        elsewhere.start()
        elsewhere.join(10)
        assert not elsewhere.is_alive()
    _feed(record, "afterwards")
    by_name = {e["fun_name"]: e for e in record.newest(10)}
    assert by_name["step"]["asked_by"] == "tick"
    assert by_name["step"]["kv_bucket"] == 512 and by_name["step"]["tick"] == 7
    assert by_name["elsewhere"]["asked_by"] == "other"
    assert by_name["afterwards"]["asked_by"] == "other"
    assert "tick" not in by_name["afterwards"]
    assert [e["fun_name"] for e in seen] == ["step"]
    with pytest.raises(ValueError, match="asked_by"):
        with record.asking("somebody"):
            pass
    # The list is bounded; the totals are of all.
    for i in range(6):
        _feed(record, f"more{i}")
    assert len(record.newest(100)) == 4
    assert record.report()["executables"]["other"]["executables"] == 8


def test_listeners_cost_microseconds_an_executable():
    record = ExecutableRecord()
    t = time.perf_counter()
    for _ in range(1000):
        _feed(record, hit=True)
    per_executable = (time.perf_counter() - t) / 1000
    assert per_executable < 200e-6  # 5 us measured; a loaded worker is slower


# -- a second process finds what the first wrote -------------------------------

CHILD = """
import json, sys
sys.path[:0] = [{bench!r}, {repo!r}]
import jax, jax.numpy as jnp
from generativeaiexamples_tpu.utils import jax_runtime
jax_runtime.enable_compile_cache()
jax_runtime.enable_compile_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)

@jax.jit
def mixed(x):
    return jnp.tanh(x @ x.T).sum(axis=0) * 3

mixed(jnp.ones((64, 64))).block_until_ready()
from generativeaiexamples_tpu.engine.scheduler import Scheduler
from generativeaiexamples_tpu.models import llama
Scheduler(llama.llama_tiny(dtype="float32", max_seq_len=128), max_batch=2, max_len=128)
import metrics_lib
report = jax_runtime.runtime_report()
print(json.dumps({{
    "entry": [e for e in jax_runtime.EXECUTABLES.newest() if e["fun_name"] == "mixed"],
    "compile": report["compile"], "executables": report["executables"],
    "readers": {{name: metrics_lib.load_reader(name)({{}}) for name in {names!r}}},
}}))
"""


@pytest.fixture(scope="module")
def cold_and_warm(tmp_path_factory):
    """The same process twice on one compile cache: an executable of its
    own, a tiny scheduler built, and the benchmark's five readers."""
    env = {
        **os.environ, "JAX_PLATFORMS": "cpu",
        "JAX_COMPILATION_CACHE_DIR": str(tmp_path_factory.mktemp("jax_cache")),
    }
    script = CHILD.format(
        bench=str(REPO / "benchmarks"), repo=str(REPO), names=SETUP_METRICS
    )
    runs = []
    for _ in range(2):
        proc = subprocess.run(
            [sys.executable, "-c", script], env=env, cwd=REPO,
            capture_output=True, text=True, timeout=300,
        )
        assert proc.returncode == 0, proc.stderr[-3000:]
        runs.append(json.loads(proc.stdout.strip().splitlines()[-1]))
    return runs


def test_a_second_process_reads_what_the_first_compiled(cold_and_warm):
    (cold,), (warm,) = (run["entry"] for run in cold_and_warm)
    assert cold["cache"] == "miss" and warm["cache"] == "hit"
    assert warm["backend_s"] < cold["backend_s"]
    assert warm["retrieval_s"] > 0 and cold["retrieval_s"] == 0
    assert warm["trace_s"] > 0 and warm["lower_s"] > 0  # no cache saves these


def test_the_three_sums_of_health_are_the_records_totals(cold_and_warm):
    for run in cold_and_warm:
        totals = run["executables"].values()
        assert run["compile"]["cache_hits"] == sum(t["hit"] for t in totals)
        assert run["compile"]["cache_misses"] == sum(t["miss"] for t in totals)
        assert run["compile"]["compile_s"] == pytest.approx(
            sum(t["backend_s"] for t in totals), abs=0.05
        )
        # Two calls of enable_compile_cache, and every executable once.
        assert sum(t["executables"] for t in totals) == (
            run["compile"]["cache_hits"] + run["compile"]["cache_misses"]
        )
    cold, warm = cold_and_warm
    assert cold["compile"]["cache_hits"] == 0 < cold["compile"]["cache_misses"]
    assert warm["compile"]["cache_misses"] == 0 < warm["compile"]["cache_hits"]


@pytest.mark.parametrize("name", SETUP_METRICS)
def test_setup_reader_finds_its_record_in_the_program(name, cold_and_warm):
    """What ``test_benchmark_contract.py`` holds the readers of the
    scheduler's counters to: in a process that built a scheduler each
    gives a number, so it reads no key the program does not write."""
    cold, warm = (run["readers"][name] for run in cold_and_warm)
    assert isinstance(cold, Number) and isinstance(warm, Number)
    if name == "setup_cache_hit_pct":
        assert (cold, warm) == (0.0, 100.0)
    elif name == "setup_executables":
        assert cold == warm > 0
    else:
        assert cold > 0 and warm > 0


def test_there_are_five_setup_metrics_in_every_cell():
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    cells = {w["name"] for w in spec["workloads"]}
    mine = [m for m in spec["per_layer"] if m["layer"] == "engine set-up"]
    assert len(mine) == 5
    for m in mine:
        assert m["moves"] == "setup_s" and m["source"] == "program_counter"
        assert set(m["workloads"]) == cells


# -- who asked: build ---------------------------------------------------------


@pytest.mark.parametrize("family", ["llama-tiny", "ling-tiny"])
def test_scheduler_init_is_a_build_in_three_stages(family):
    presets = {**llama.PRESETS, **hybrid.PRESETS}
    before = EXECUTABLES.report()
    t = time.perf_counter()
    # (Sizes no other test builds: what an earlier test of this process
    # compiled, JAX hands back without an executable asked for.)
    scheduler = Scheduler(presets[family](), None, max_batch=3, max_len=80)
    wall = time.perf_counter() - t
    scheduler.stop()
    after = EXECUTABLES.report()
    spent = {k: after["setup"][k] - before["setup"][k] for k in after["setup"]}
    assert spent["builds"] == 1
    stages = spent["params_s"] + spent["state_s"] + spent["programs_s"]
    # (Within 2 %; the tiny llama builds in half a second, so beside busy
    # workers one preemption between two clock reads is more than that.)
    assert stages == pytest.approx(spent["build_s"], rel=0.02, abs=0.05)
    assert spent["build_s"] == pytest.approx(wall, rel=0.02, abs=0.05)
    assert stages <= spent["build_s"] <= wall
    assert spent["params_s"] > 0 and spent["state_s"] > 0 and spent["programs_s"] > 0
    made = _since(t)
    assert made and all(e["asked_by"] == "build" for e in made)
    built = after["executables"]["build"]["executables"]
    assert built - before["executables"]["build"]["executables"] == len(made)
    if family == "ling-tiny":
        # The family of chunk programs is compiled in there, by name.
        programs = [e for e in made if e["fun_name"] == "_prefill_suffix_rows"]
        assert len(programs) >= 2
        assert spent["programs_s"] >= sum(
            e["trace_s"] + e["lower_s"] + e["backend_s"] for e in programs
        ) * 0.98
    # Outside the constructor nobody is building.
    assert EXECUTABLES.asker.who == "other" and EXECUTABLES.asker.stage is None


@pytest.mark.parametrize("layout", ["scattered", "paged"])
def test_a_build_that_raises_still_ends(layout):
    before = EXECUTABLES.report()["setup"]["builds"]
    with pytest.raises(ValueError, match="kv_layout"):
        Scheduler(CFG, max_batch=2, max_len=128, kv_layout=layout)
    assert EXECUTABLES.report()["setup"]["builds"] == before + 1
    assert EXECUTABLES.asker.who == "other" and EXECUTABLES.asker.stage is None


# -- who asked: tick, and what serving shows -------------------------------------


@pytest.fixture
def engine_client():
    from generativeaiexamples_tpu.engine.server import create_engine_app

    scheduler = Scheduler(CFG, max_batch=2, max_len=128, decode_chunk_size=4)
    scheduler.start()
    app = create_engine_app(scheduler, ByteTokenizer(), model_name="llama-tiny")
    loop = asyncio.new_event_loop()
    client = TestClient(TestServer(app), loop=loop)
    loop.run_until_complete(client.start_server())
    yield client, loop, scheduler
    loop.run_until_complete(client.close())
    loop.close()
    scheduler.stop()


def _complete(client, loop, prompt, max_tokens):
    async def go():
        resp = await client.post(
            "/v1/completions",
            json={"prompt": prompt, "temperature": 0.0, "max_tokens": max_tokens},
        )
        assert resp.status == 200
        await resp.read()

    loop.run_until_complete(go())


def _get(client, loop, path, as_json=True):
    async def go():
        resp = await client.get(path)
        return resp.status, await (resp.json() if as_json else resp.text())

    return loop.run_until_complete(go())


def _metric(text, line_start):
    (line,) = [ln for ln in text.splitlines() if ln.startswith(line_start)]
    return float(line.rsplit(" ", 1)[1])


def test_a_compile_after_warm_up_shows_in_ticks_executables_and_metrics(
    engine_client,
):
    client, loop, scheduler = engine_client
    _complete(client, loop, "hello", max_tokens=6)  # the warm-up
    _complete(client, loop, "hello", max_tokens=6)
    warm = scheduler.stats.snapshot()
    assert warm["executables_requested"] > 0
    _complete(client, loop, "hello", max_tokens=6)  # warmed: asks for nothing
    assert scheduler.stats.snapshot()["executables_requested"] == (
        warm["executables_requested"]
    )
    t = time.perf_counter()
    # 70 prompt tokens: a kv_bucket no request has had.
    _complete(client, loop, "a long question " * 4 + "indeed", max_tokens=6)
    after = scheduler.stats.snapshot()
    new = after["executables_requested"] - warm["executables_requested"]
    assert new > 0
    for stage in ("trace", "lower", "backend"):
        assert after[f"executable_{stage}_s"] > warm[f"executable_{stage}_s"]

    status, body = _get(client, loop, "/debug/executables?limit=1000")
    assert status == 200 and body["count"] == len(body["entries"])
    mine = [e for e in body["entries"] if e["t"] >= t and e["asked_by"] == "tick"]
    assert len(mine) == new
    decode = [e for e in mine if e.get("program") == "decode_chunk"]
    assert decode and all(e["kv_bucket"] == 128 for e in decode)
    assert all(e["phase"] == "dispatch" and e["lanes"] == 1 for e in decode)
    assert {e["fun_name"] for e in decode} == {"decode_chunk"}
    assert body["executables"]["tick"]["executables"] >= after["executables_requested"]
    assert body["setup"]["build_s"] > 0

    # The tick that asked is marked, beside its long dispatch.
    status, ticks = _get(client, loop, "/debug/ticks?limit=4096")
    ticks = ticks["ticks"]
    assert all(list(r) == list(TICK_RECORD_FIELDS) for r in ticks)
    assert TICK_RECORD_FIELDS[-1] == "executables"
    by_tick = {}
    for e in mine:
        by_tick[e["tick"]] = by_tick.get(e["tick"], 0) + 1
    # (The tick that admitted the request was polling the queue when it
    # came: it started before ``t``.)
    marked = {
        r["tick"]: r["executables"] for r in ticks
        if r["tick"] >= min(by_tick) and r["executables"]
    }
    assert marked == by_tick and sum(marked.values()) == new

    status, text = _get(client, loop, "/metrics", as_json=False)
    assert "# TYPE engine_executables_total counter" in text
    assert "# TYPE engine_executable_seconds_total counter" in text
    by_cache = {
        c: _metric(text, f'engine_executables_total{{cache="{c}"}}')
        for c in ("hit", "miss", "off")
    }
    assert sum(by_cache.values()) == after["executables_requested"]
    assert by_cache["miss"] == after["executables_missed"]
    assert _metric(
        text, 'engine_executable_seconds_total{stage="backend"}'
    ) == pytest.approx(after["executable_backend_s"], abs=1e-5)

    status, _ = _get(client, loop, "/debug/executables?limit=x")
    assert status == 422
    status, none = _get(client, loop, "/debug/executables?limit=0")
    assert none["entries"] == []


def test_health_carries_setup_and_the_totals_by_who_asked(engine_client):
    client, loop, _ = engine_client
    status, body = _get(client, loop, "/health")
    assert status == 200
    runtime = body["runtime"]
    assert set(runtime["compile"]) == {
        "compile_s", "cache_hits", "cache_misses", "cache_dir",
    }
    assert set(runtime["setup"]) == {
        "builds", "build_s", "params_s", "state_s", "programs_s",
    }
    assert set(runtime["executables"]) == {"build", "tick", "other"}
    totals = runtime["executables"].values()
    assert runtime["compile"]["cache_hits"] == sum(t["hit"] for t in totals)
    assert runtime["compile"]["cache_misses"] == sum(t["miss"] for t in totals)
    assert runtime["compile"]["compile_s"] == pytest.approx(
        sum(t["backend_s"] for t in totals), abs=0.5
    )


def test_a_pool_sums_the_new_counters_and_sorts_entries_by_replica():
    from generativeaiexamples_tpu.engine.server import create_engine_app

    # The record is the process's: a pool of an earlier test file in this
    # worker left entries under replicas 0 and 1 too.
    t = time.perf_counter()
    scheds = [
        Scheduler(CFG, max_batch=2, max_len=128, decode_chunk_size=4)
        for _ in range(2)
    ]
    pool = EnginePool(scheds, policy="least_loaded", health_interval=None)
    pool.start()
    app = create_engine_app(pool, ByteTokenizer(), model_name="llama-tiny")
    loop = asyncio.new_event_loop()
    client = TestClient(TestServer(app), loop=loop)
    loop.run_until_complete(client.start_server())
    try:
        for _ in range(3):
            _complete(client, loop, "hello", max_tokens=4)
        agg = pool.snapshot()
        status, body = _get(client, loop, "/debug/executables?limit=1000")
    finally:
        loop.run_until_complete(client.close())
        loop.close()
        pool.stop()
    keys = ("executables_requested", "executables_hit", "executables_missed",
            "executable_trace_s", "executable_lower_s", "executable_backend_s")
    for key in keys:
        assert agg[key] == pytest.approx(sum(r[key] for r in agg["replicas"]))
    assert agg["executables_requested"] > 0
    for snap in [agg, *agg["replicas"], scheds[0].stats.snapshot()]:
        assert REMOVED not in snap
    assert status == 200 and [r["replica"] for r in body["replicas"]] == [0, 1]
    for r, snap in zip(body["replicas"], agg["replicas"]):
        assert all(e["replica"] == r["replica"] for e in r["entries"])
        assert len([e for e in r["entries"] if e["t"] >= t]) == snap["executables_requested"]


def test_what_nothing_read_is_gone():
    from generativeaiexamples_tpu.engine.scheduler import Stats

    assert not hasattr(Stats(), REMOVED)
    assert not hasattr(jax_runtime, "COMPILE_STATS")
