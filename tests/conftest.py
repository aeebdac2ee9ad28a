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
