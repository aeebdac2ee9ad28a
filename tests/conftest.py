"""Test bootstrap: force an 8-device virtual CPU mesh before JAX initializes.

This mirrors the survey's test strategy (SURVEY.md §4): pjit/sharding logic
is validated hermetically on a virtual multi-device CPU platform; real-TPU
runs happen only in ``benchmarks/run.py`` and ``chip_smoke.py``.
"""

import os
import shutil
import statistics
import tempfile
import time
from collections import Counter

os.environ.setdefault("JAX_PLATFORMS", "cpu")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    flags += " --xla_force_host_platform_device_count=8"
# Seven tenths of a serving test's seconds are XLA compiling for the CPU a
# program that then runs a few steps at a tiny size: the tests take the
# code as LLVM first emits it.  Every comparison is between programs
# compiled alike, and no tolerance knows of the level.  Child processes
# inherit it with the device count (the notebooks' scripts); the
# benchmark's CPU rehearsals take it out again, their requests have a
# deadline (``tests/test_benchmark_contract.py::_run``).
if "xla_backend_optimization_level" not in flags:
    flags += " --xla_backend_optimization_level=0"
os.environ["XLA_FLAGS"] = flags.strip()
# Keep test runs hermetic and quiet.
os.environ.setdefault("TOKENIZERS_PARALLELISM", "false")
os.environ.setdefault("HF_HUB_OFFLINE", "1")
os.environ.setdefault("TRANSFORMERS_OFFLINE", "1")
# ``tests/test_chip_compile*.py`` are five files, so under ``-n`` several
# workers describe the chip at once, each loading the TPU's library; without
# this the files behind the first find its lock held and skip.
os.environ.setdefault("ALLOW_MULTIPLE_LIBTPU_LOAD", "1")

import jax  # noqa: E402
import pytest  # noqa: E402

# Pin at config level too, so a JAX_PLATFORMS inherited from the shell
# cannot move the tests off the virtual 8-device CPU platform.
jax.config.update("jax_platforms", "cpu")

# XLA CPU lowers f32 matmuls to a reduced-precision path by default, which
# makes results shape-dependent (prefill vs decode differ ~4e-3). Tests
# force full f32 accumulation so consistency checks can use tight tolerances.
jax.config.update("jax_default_matmul_precision", "highest")


COMPILE_CACHE = pytest.StashKey[str]()


def pytest_configure(config):
    # Tier-1 CI runs ``-m 'not slow'`` (ROADMAP.md): heavy parity sweeps
    # opt out of the time-budgeted lane but still run in full sweeps.
    config.addinivalue_line(
        "markers", "slow: long-running sweep, excluded from tier-1 runs"
    )
    # Many files lower the same tiny presets' programs, and a scheduler
    # built anew lowers its own again: one persistent compile cache a run,
    # made by the controller, the same for its workers and gone with the
    # run, so that whichever worker comes first compiles a program for
    # all.  Set in JAX's config, not the environment: a child process (a
    # rehearsal, ``chip_smoke.py``) names its own.  A test that reads
    # compile counts or the cache's directory names its own too
    # (``tests/test_setup_tracing.py``, ``tests/test_chip_smoke.py``,
    # ``tests/chip_compile_lib.py``).
    if hasattr(config, "workerinput"):
        cache = config.workerinput["compile_cache"]
    else:
        cache = config.stash[COMPILE_CACHE] = tempfile.mkdtemp(prefix="tests_jax_cache_")
    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


@pytest.hookimpl(optionalhook=True)
def pytest_configure_node(node):
    node.workerinput["compile_cache"] = node.config.stash[COMPILE_CACHE]


def pytest_unconfigure(config):
    if COMPILE_CACHE in config.stash:
        shutil.rmtree(config.stash[COMPILE_CACHE], ignore_errors=True)


# Seconds a file's cases take on one worker: the sum of their ``time`` in
# the junit of tier-1's command (PR 52's tree, the builder's machine, six
# workers: 6,389 s in all, 1,235 s by the clock; renewed by hand).
# ``--dist loadfile`` hands out whole files, those with the MOST TESTS
# first, so the long files started wherever their count put them and the
# run ended on a few slow ones with workers idle.  Here the slowest go
# first and the short ones end the queue, so that the workers reach the
# rehearsals together.  A file not listed counts as the table's median: a
# new file starts in the middle of the queue, not at its end.  A stale
# number costs balance, nothing else.
FILE_SECONDS = {
    "tests/test_chip_smoke.py": 457,
    "tests/test_hybrid_serving.py": 427,
    "tests/test_chip_compile_chunk_attention.py": 346,
    "tests/test_chip_compile_group_programs.py": 340,
    "tests/test_benchmark_contract.py": 327,
    "tests/test_exaone_moe_model.py": 324,
    "tests/test_notebooks.py": 258,
    "tests/test_gqa_ring_chunk_kernel.py": 207,
    "tests/test_gqa_chunk_kernel.py": 187,
    "tests/test_own_draft_serving.py": 154,
    "tests/test_chip_compile_decode_chunks.py": 140,
    "tests/test_tick_ahead.py": 139,
    "tests/test_latent_chunk.py": 138,
    "tests/test_scheduler.py": 132,
    "tests/test_dots3_note_model.py": 122,
    "tests/test_zaya_model.py": 114,
    "tests/test_hybrid_model.py": 111,
    "tests/test_kda_chunk_kernel.py": 105,
    "tests/test_hybrid_ops.py": 99,
    "tests/test_gqa_decode_kernel.py": 92,
    "tests/test_qmm.py": 84,
    "tests/test_deepseek_v32_model.py": 74,
    "tests/test_longcat_flash_model.py": 45,
    "tests/test_ouro_model.py": 84,
    "tests/test_speech.py": 77,
    "tests/test_chip_compile.py": 76,
    "tests/test_decode_attention.py": 73,
    "tests/test_kda_step_kernel.py": 73,
    "tests/test_llama.py": 67,
    "tests/test_mellum_model.py": 62,
    "tests/test_retrieval.py": 58,
    "tests/test_chip_compile_llama.py": 58,
    "tests/test_nemotron_h_model.py": 57,
    "tests/test_latent_decode.py": 46,
    "tests/test_llama_serving_rows.py": 57,
    "tests/test_admit_alone.py": 55,
    "tests/test_mistral4_model.py": 49,
    "tests/test_setup_tracing.py": 47,
    "tests/test_weights.py": 47,
    "tests/test_engine.py": 45,
    "tests/test_tick_tracing.py": 38,
    "tests/test_pipeline.py": 37,
    "tests/test_experimental.py": 29,
    "tests/test_fetch_and_convert.py": 29,
    "tests/test_router.py": 27,
    "tests/test_ssm_ops.py": 22,
    "tests/test_ring_attention.py": 18,
    "tests/test_retriever_customization.py": 18,
    "tests/test_multimodal.py": 15,
    "tests/test_elastic.py": 11,
    "tests/test_lora.py": 9,
    "tests/test_gray.py": 9,
    "tests/test_fleet_obs.py": 9,
    "tests/test_moe_tiles.py": 8,
    "tests/test_flash_attention.py": 7,
    "tests/test_fabric.py": 7,
    "tests/test_durability.py": 7,
    "tests/test_streaming.py": 5,
    "tests/test_chains.py": 4,
    "tests/test_cache.py": 1,
    "tests/test_resilience.py": 1,
    "tests/test_external_stores.py": 1,
    "tests/test_server.py": 1,
    "tests/test_microbatch.py": 1,
    "tests/test_tools.py": 1,
    "tests/test_obs.py": 1,
    "tests/test_metrics_exposition.py": 1,
    "tests/test_ingest_pipeline.py": 1,
    "tests/test_frontend.py": 1,
    "tests/test_native_tokenizer.py": 1,
    "tests/test_openapi.py": 1,
    "tests/test_ingest.py": 1,
    "tests/test_config.py": 1,
    "tests/test_prefix_cache.py": 1,
    "tests/test_dev_chatbot.py": 1,
}
UNLISTED_SECONDS = statistics.median(FILE_SECONDS.values())


# The layer-kind cells' rehearsals are units of their own, handed out
# LAST, to three workers: each is a process that compiles on every core it
# finds and then holds its requests to the load generator's 20 s for a
# first token.  Beside five busy workers three to five of seven missed it;
# six at once, with nothing else running, four of eight did, and four at
# once one (PR 52, this machine's 8 cores: 210 s and 222 s by the clock,
# so the width buys nothing: their sum is the cores' work).  Their seconds
# at the run's end, by cell (same junit).
REHEARSALS = "tests/test_benchmark_contract_layer_kinds.py"
REHEARSALS_AT_ONCE = 3
REHEARSAL_SECONDS = {
    "mellum2-12b-a2.5b-l12.rag-long-closed": 133,
    "k-exaone-236b-a23b-l5e16.reason-closed": 103,
    "nemotron-3-super-120b-a12b-l11e128.reason-closed": 89,
    "ling-3.0-flash-vl-l7e128.rag-closed": 88,
    "zaya1-8b-l20.reason-closed": 74,
    "dots3-note-prev-l6e32.doc-mid-closed": 74,
    "deepseek-v3.2-l5e16.doc-reason-closed": 70,
    "longcat-flash-chat-l4e16.doc-reason-closed": 70,
    "ouro-2.6b.chat-short-closed": 71,
    "mistral-small-4-119b-l6e32.doc-long-closed": 59,
}


def unit_seconds(unit: str) -> float:
    """The queue's key for a unit of ``--dist loadfile``: a file, or one
    rehearsal (``<REHEARSALS>::test...[<cell>]``)."""
    if unit.startswith(REHEARSALS):
        cell = unit.rpartition("[")[2].rstrip("]")
        return REHEARSAL_SECONDS.get(cell, max(REHEARSAL_SECONDS.values()))
    return FILE_SECONDS.get(unit, UNLISTED_SECONDS)


@pytest.hookimpl(optionalhook=True)
def pytest_xdist_make_scheduler(config, log):
    """``--dist loadfile`` with the slowest files first (``FILE_SECONDS``)
    and the rehearsals last, shared out among ``REHEARSALS_AT_ONCE``
    workers; every other mode is xdist's own."""
    if config.getvalue("dist") != "loadfile":
        return None
    from xdist.scheduler import LoadFileScheduling

    class SlowestFilesFirst(LoadFileScheduling):
        queue_is_ordered = False
        took = 0  # workers that have their share of the rehearsals

        def _split_scope(self, nodeid):
            file = super()._split_scope(nodeid)
            return nodeid if file == REHEARSALS else file

        def _assign_work_unit(self, node):
            # The first unit handed out: the queue is whole, in xdist's order.
            if not self.queue_is_ordered:
                self.queue_is_ordered = True
                units = sorted(
                    self.workqueue.items(),
                    key=lambda unit: (
                        unit[0].startswith(REHEARSALS), -unit_seconds(unit[0])
                    ),
                )
                self.workqueue.clear()
                self.workqueue.update(units)
            super()._assign_work_unit(node)

        def _reschedule(self, node):
            """Only rehearsals are left: ``REHEARSALS_AT_ONCE`` workers
            share them out and the others end.  xdist would hand a worker
            a unit after each test it ends while two or fewer wait (a
            worker runs a test once it knows the next), so which worker
            ran how many was a matter of which ran low first.  A share is
            the rehearsals left over the workers still to take one: the
            early workers take the shortest, the last the longest."""
            queue = self.workqueue
            if (
                node.shutting_down
                or not queue
                or not next(iter(queue)).startswith(REHEARSALS)
            ):
                return super()._reschedule(node)
            if self._pending_of(self.assigned_work[node]) > 2:
                return None
            waiting = sum(not n.shutting_down for n in self.nodes)
            share = -(-len(queue) // max(1, min(waiting, REHEARSALS_AT_ONCE - self.took)))
            self.took += 1
            shortest = share < len(queue)  # the last to take has the rest
            for _ in range(share):
                if shortest:
                    queue.move_to_end(next(reversed(queue)), last=False)
                super()._assign_work_unit(node)
            node.shutdown()
            return None

    return SlowestFilesFirst(config, log)


# -- where the run's time went -------------------------------------------------
# The controller's own clock and the seconds of every report it receives,
# by file: printed at the session's end, so that the log of a run that is
# cut, or nearly, names its heaviest files (tier-1's command has 1,470 s).
STARTED = time.monotonic()
SECONDS_BY_FILE = Counter()


def pytest_runtest_logreport(report):
    SECONDS_BY_FILE[report.nodeid.partition("::")[0]] += report.duration


def pytest_terminal_summary(terminalreporter):
    if not SECONDS_BY_FILE:
        return
    terminalreporter.write_sep("=", "where the run's time went")
    terminalreporter.write_line(
        f"{time.monotonic() - STARTED:.0f} s by the clock, "
        f"{sum(SECONDS_BY_FILE.values()):.0f} s of tests in {len(SECONDS_BY_FILE)} files; the slowest:"
    )
    for file, seconds in SECONDS_BY_FILE.most_common(5):
        terminalreporter.write_line(f"{seconds:8.0f} s  {file}")


@pytest.fixture
def clean_app_env(monkeypatch):
    """Remove APP_* env vars and reset the config cache around a test."""
    from generativeaiexamples_tpu.core import configuration

    for key in list(os.environ):
        if key.startswith("APP_"):
            monkeypatch.delenv(key, raising=False)
    configuration.reset_config_cache()
    yield monkeypatch
    configuration.reset_config_cache()
