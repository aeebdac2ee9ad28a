"""Test bootstrap: force an 8-device virtual CPU mesh before JAX initializes.

This mirrors the survey's test strategy (SURVEY.md §4): pjit/sharding logic
is validated hermetically on a virtual multi-device CPU platform; real-TPU
runs happen only in ``benchmarks/run.py`` and ``chip_smoke.py``.
"""

import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
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


def pytest_configure(config):
    # Tier-1 CI runs ``-m 'not slow'`` (ROADMAP.md): heavy parity sweeps
    # opt out of the time-budgeted lane but still run in full sweeps.
    config.addinivalue_line(
        "markers", "slow: long-running sweep, excluded from tier-1 runs"
    )


# Seconds a file's cases take on one worker, for the files over 40 s (the
# junit of tier-1's command on PR 51's tree, the builder's machine: 6,640 s
# in all on six workers, 1,346 s by the clock; renewed by hand from a
# junit).
# ``--dist loadfile`` hands out whole files, those with the MOST TESTS
# first, so the long files started wherever their count put them and the
# run ended on a few slow ones with workers idle.  Here the slowest go
# first.  A file not listed keeps xdist's order behind these; a stale
# number costs balance, nothing else.
FILE_SECONDS = {
    "tests/test_chip_smoke.py": 460,
    "tests/test_hybrid_serving.py": 380,
    "tests/test_exaone_moe_model.py": 270,
    "tests/test_gqa_ring_chunk_kernel.py": 260,
    "tests/test_benchmark_contract.py": 260,
    "tests/test_chip_compile_group_programs.py": 240,
    "tests/test_notebooks.py": 230,
    "tests/test_chip_compile_chunk_attention.py": 230,
    "tests/test_scheduler.py": 220,
    "tests/test_speculative.py": 220,
    "tests/test_latent_chunk.py": 200,
    "tests/test_tick_ahead.py": 180,
    "tests/test_gqa_chunk_kernel.py": 160,
    "tests/test_zaya_model.py": 140,
    "tests/test_hybrid_ops.py": 120,
    "tests/test_dots3_note_model.py": 120,
    "tests/test_chip_compile_decode_chunks.py": 120,
    "tests/test_qmm.py": 100,
    "tests/test_kda_chunk_kernel.py": 100,
    "tests/test_gqa_decode_kernel.py": 100,
    "tests/test_engine.py": 100,
    "tests/test_tick_tracing.py": 90,
    "tests/test_nemotron_h_model.py": 90,
    "tests/test_kda_step_kernel.py": 90,
    "tests/test_mistral4_model.py": 90,
    "tests/test_speech.py": 80,
    "tests/test_chip_compile.py": 80,
    "tests/test_decode_attention.py": 80,
    "tests/test_ouro_model.py": 125,
    "tests/test_hybrid_model.py": 80,
    "tests/test_weights.py": 80,
    "tests/test_spec_serving.py": 70,
    "tests/test_llama_serving_rows.py": 70,
    "tests/test_retrieval.py": 70,
    "tests/test_llama.py": 70,
    "tests/test_router.py": 60,
    "tests/test_chip_compile_llama.py": 60,
    "tests/test_mellum_model.py": 50,
    "tests/test_pipeline.py": 40,
    "tests/test_admit_alone.py": 40,
}


# The layer-kind cells' rehearsals are units of their own, handed out
# LAST: each holds its requests to the load generator's 20 s for a first
# token and misses it beside five busy workers (three to five of the seven
# red in three runs), but not at the run's end, where only rehearsals are
# left (all green in 66-102 s each).  It costs about two minutes: xdist
# gives a worker its next unit while it has two tests or fewer to go, so
# some of them wait behind another on one worker.
REHEARSALS = "tests/test_benchmark_contract_layer_kinds.py"


@pytest.hookimpl(optionalhook=True)
def pytest_xdist_make_scheduler(config, log):
    """``--dist loadfile`` with the slowest files first (``FILE_SECONDS``)
    and the rehearsals last; every other mode is xdist's own."""
    if config.getvalue("dist") != "loadfile":
        return None
    from xdist.scheduler import LoadFileScheduling

    def seconds(unit: str) -> int:
        return -1 if unit.startswith(REHEARSALS) else FILE_SECONDS.get(unit, 0)

    class SlowestFilesFirst(LoadFileScheduling):
        queue_is_ordered = False

        def _split_scope(self, nodeid):
            file = super()._split_scope(nodeid)
            return nodeid if file == REHEARSALS else file

        def _assign_work_unit(self, node):
            # The first unit handed out: the queue is whole, in xdist's order.
            if not self.queue_is_ordered:
                self.queue_is_ordered = True
                units = sorted(self.workqueue.items(), key=lambda unit: -seconds(unit[0]))
                self.workqueue.clear()
                self.workqueue.update(units)
            super()._assign_work_unit(node)

    return SlowestFilesFirst(config, log)


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
