"""chip_smoke.py's control flow rehearsed on the CPU, and the rules that
keep a run without a chip from passing for one: the smoke, the bench
and the engine server each refuse, by exit code, to stand in for the
TPU; the compile cache goes where it is told.

Only the rehearsals pass ``expect="cpu"`` — an argument of
``chip_smoke.run``, not a command-line option: the script as the driver
runs it always expects a TPU.
"""

import json
import os
import shutil
import subprocess
import sys
import tempfile

import jax
import pytest

import chip_smoke
from generativeaiexamples_tpu.utils import jax_runtime

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _phases(capsys) -> dict:
    lines = [
        json.loads(ln)
        for ln in capsys.readouterr().out.splitlines()
        if ln.startswith("{")
    ]
    return {ln["phase"]: ln for ln in lines}


def test_smoke_rehearsal_on_cpu(capsys, monkeypatch, tmp_path):
    """Every phase of the one-chip run at tiny sizes: engine server and
    chain server as child processes, retrieval and the opt-in paths as
    children that exit before the next starts."""
    # One device, as on the one-chip machine (conftest's eight virtual
    # devices would have the server build a tensor=8 mesh).
    monkeypatch.delenv("XLA_FLAGS", raising=False)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    # The servers' logs and the chain server's uploads go under one
    # directory of the run's own, in TMPDIR, and leave with the phase.
    scratch = tmp_path / "tmp"
    scratch.mkdir()
    monkeypatch.setenv("TMPDIR", str(scratch))
    monkeypatch.setattr(tempfile, "tempdir", None)
    started = {}

    class Recorded(chip_smoke.Server):
        def __init__(self, name, argv, env, logdir):
            started[name] = (env, logdir)
            super().__init__(name, argv, env, logdir)

    monkeypatch.setattr(chip_smoke, "Server", Recorded)
    device = chip_smoke.run(0, chip_smoke.TINY, expect="cpu")
    assert device["platform"] == "cpu"
    env, logdir = started["chain"]
    assert env["GAIE_UPLOAD_DIR"] == os.path.join(logdir, "uploads")
    assert os.path.dirname(logdir) == str(scratch)
    assert not [d for d in os.listdir(scratch) if d.startswith("chip-smoke-")]
    phases = _phases(capsys)
    assert list(phases) == [
        "device", "serve.engine", "serve.chain", "retrieval",
        "optin.w8a8",
    ]
    engine = phases["serve.engine"]
    assert set(engine["chat_finish"]) == {"length"}
    assert engine["tokens_returned"] == 3 * chip_smoke.TINY.new_tokens
    chain = phases["serve.chain"]
    assert chain["generate_context_chunks"] >= 1
    assert chain["engine_requests_for_generate"] == 1
    assert chain["engine_tokens_for_generate"] == chip_smoke.TINY.new_tokens
    assert phases["retrieval"]["ids_equal_numpy"]
    assert phases["optin.w8a8"]["matches_xla_twin"]


def test_four_chip_rehearsal_on_virtual_devices(
    capsys, monkeypatch, tmp_path
):
    monkeypatch.setenv(
        "XLA_FLAGS", "--xla_force_host_platform_device_count=4"
    )
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    device = chip_smoke.run(
        0, chip_smoke.TINY, expect="cpu", four_chips=True
    )
    assert device["count"] == 4
    phases = _phases(capsys)
    assert list(phases) == ["four.tensor_parallel", "four.replicas"]
    assert len(set(phases["four.replicas"]["replica_devices"])) == 4
    assert any(
        "x4" in v
        for v in phases["four.tensor_parallel"]["sharded_leaves"].values()
    )


def _run(argv, **env):
    full = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    full.update(env)
    return subprocess.run(
        [sys.executable, *argv],
        cwd=REPO,
        env=full,
        capture_output=True,
        text=True,
        timeout=300,
    )


def test_smoke_without_a_chip_fails_and_prints_no_result():
    proc = _run(["chip_smoke.py"], JAX_PLATFORMS="cpu")
    assert proc.returncode != 0
    assert "no tpu device" in proc.stderr and "'cpu'" in proc.stderr
    assert proc.stdout.strip() == ""


def test_smoke_outside_a_checkout_fails(tmp_path):
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    proc = subprocess.run(
        [sys.executable, "chip_smoke.py"],
        cwd=tmp_path,
        env={k: v for k, v in os.environ.items() if k != "PYTHONPATH"},
        capture_output=True,
        text=True,
        timeout=120,
    )
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout


def test_benchmark_without_a_chip_fails_and_prints_no_result():
    """The driver's command (``BENCHMARK.json: command``) measures on the
    chip or not at all: only ``--rehearse`` may meet the CPU."""
    proc = _run(
        ["benchmarks/run.py", "--workload", "mistral-7b.rag-open"],
        JAX_PLATFORMS="cpu",
    )
    assert proc.returncode != 0
    assert "no TPU found" in proc.stderr and "'cpu'" in proc.stderr
    assert proc.stdout.strip() == ""


def test_engine_server_refuses_to_start_without_a_tpu():
    """No ``JAX_PLATFORMS=cpu`` in its environment and no TPU to find:
    the server says so and exits instead of serving from the host."""
    proc = _run(
        ["-m", "generativeaiexamples_tpu.engine.server",
         "--model", "llama-tiny", "--port", "0"]
    )
    assert proc.returncode != 0
    assert "no TPU found" in proc.stderr


def test_require_accelerator_accepts_an_explicit_cpu(monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert jax_runtime.require_accelerator("test")["platform"] == "cpu"
    monkeypatch.delenv("JAX_PLATFORMS")
    with pytest.raises(SystemExit, match="no TPU found"):
        jax_runtime.require_accelerator("test")


@pytest.fixture
def restore_cache_dir():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_compile_cache_follows_the_environment(
    monkeypatch, tmp_path, restore_cache_dir
):
    """Placed from outside: the helper sets no directory of its own."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    was = jax.config.jax_compilation_cache_dir
    assert jax_runtime.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == was


def test_compile_cache_defaults_to_the_checkout(
    monkeypatch, restore_cache_dir
):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = os.path.join(REPO, ".jax_cache")
    assert jax_runtime.enable_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == want


def test_hybrid_phase_rehearsal_and_its_control(capsys):
    """``--hybrid`` at the tiny size, in process: the served logits stay
    inside the limits (float32 here, far inside), and the control — the
    reference with W8A8 MLP products — leaves them."""
    chip_smoke.child_hybrid(0, chip_smoke.TINY)
    sound = _phases(capsys)["hybrid"]
    assert sound["within_limits"] and sound["control"] is None
    assert sound["positions"] == {"prefill": 41, "decode": 8}
    assert sound["prefill_p10_share"] < 1e-4 and sound["decode_p10_share"] < 1e-4
    assert sound["prefill_p90_share"] < 1e-3 and sound["decode_p90_share"] < 1e-3
    assert sorted(sound["limits"]) == sorted(
        f"{part}_{q}_share" for part in ("prefill", "decode") for q in ("p10", "p50", "p90"))
    # The limits are set for the chip's size and precision (bf16
    # activations read 0.012); at this size an expert is a small part of a
    # layer, so the control is only held to read far above the sound run,
    # and the phase to say so if it stayed inside the limits.
    from generativeaiexamples_tpu.models import hybrid_reference as chip_smoke_reference

    plain = chip_smoke_reference._swiglu  # the control replaces it for its process
    try:
        chip_smoke.child_hybrid(0, chip_smoke.TINY, control="w8a8_mlp")
    except chip_smoke.SmokeFailure as e:
        assert "stayed inside" in str(e)
    finally:
        chip_smoke_reference._swiglu = plain
        jax.clear_caches()
    control = _phases(capsys)["hybrid"]
    assert control["control"] == "w8a8_mlp"
    assert control["decode_p10_share"] > 20 * sound["decode_p10_share"]


@pytest.mark.parametrize("control", ["", "no_window", "no_yarn"])
def test_hybrid_phase_rehearsal_of_the_window_model_and_its_controls(control, capsys):
    """``--hybrid --model mellum`` at the tiny size, in process: a prompt of
    75 tokens under a window of 16 (the rings wrap four times), then 8
    decode steps.  The served logits stay far inside the limits; with the
    program's window layers attending to everything, or its full layers
    on the plain frequencies, they leave them."""
    chip_smoke.child_hybrid(0, chip_smoke.TINY, control=control, model="mellum")
    line = _phases(capsys)["hybrid"]
    assert line["model"] == "mellum-tiny" and line["control"] == (control or None)
    assert line["positions"] == {"prefill": 75, "decode": 8}
    assert any(site.startswith("attn_window") for site in line["kernel_paths"])
    if not control:
        assert line["within_limits"]
        assert line["prefill_p90_share"] < 1e-3 and line["decode_p90_share"] < 1e-3
    else:
        assert not line["within_limits"]
        assert line["decode_p10_share"] > line["limits"]["decode_p10_share"]


EXAONE_TINY_LIMITS = {"p10": 1e-3, "p50": 1e-3, "p90": 1e-3, "accept_p50": 1e-3,
                      "reject_p50": 1e-3, "module_p50": 1e-3}


@pytest.mark.parametrize(
    "control", ["", "no_qk_norm", "rope_on_full", "no_window", "stale_reject", "w8a8_mlp"])
def test_hybrid_phase_rehearsal_of_the_drafting_model_and_its_controls(control, capsys, monkeypatch, tmp_path):
    """``--hybrid --model exaone_moe`` at the tiny size, in process: a
    prompt of 60 tokens under a window of 8 (seven and a half windows:
    every ring wraps), chunks of 16, its last 8 positions through the
    verify step on true and on wrong drafts, by the benchmark's own
    comparison.  The limits are the configuration's, set for the chip's
    size and precision; float32 at this size reads 1e-6, so the rehearsal
    holds it to limits of its own, which the sound run is far inside and
    each control leaves."""
    config = json.loads(open(os.path.join(REPO, chip_smoke.EXAONE_CONFIG)).read())
    config["reference"]["logit_share_limits"] = EXAONE_TINY_LIMITS
    tiny = tmp_path / "config.json"
    tiny.write_text(json.dumps(config))
    monkeypatch.setattr(chip_smoke, "EXAONE_CONFIG", str(tiny))
    # A sound run inside the limits and a control outside them both return.
    chip_smoke.child_hybrid(0, chip_smoke.TINY, control=control, model="exaone_moe")
    line = _phases(capsys)["hybrid"]
    assert line["model"] == "exaone_moe-tiny" and line["control"] == (control or None)
    assert line["positions"] == {"prefill": 52, "accept": 8, "reject": 7, "module": 12}
    assert any(site.startswith("attn_window") for site in line["kernel_paths"])
    assert any(site.startswith("mtp_attn_full") for site in line["kernel_paths"])
    assert line["within_limits"] == (not control)
    if control == "stale_reject":  # the reject branch alone sees it
        assert line["reject_p50"] > 0.1 > line["accept_p50"]
    elif control == "w8a8_mlp":  # the precision: every part moves, by little
        assert 1e-3 < line["p10"] < 0.2
    elif control:
        assert line["p50"] > 0.05


MISTRAL4_TINY_LIMITS = {"p10": 1e-3, "p50": 1e-3, "p90": 1e-3, "decode_p50": 1e-3}


@pytest.mark.parametrize(
    "control", ["", "no_attn_scale", "plain_rope", "no_mscale", "no_q_norm", "w8a8_mlp"])
def test_hybrid_phase_rehearsal_of_the_latent_model_and_its_controls(control, capsys, monkeypatch, tmp_path):
    """``--hybrid --model mistral4`` at the tiny size, in process: a prompt
    of 75 tokens over an original context of 32 (``a(p)`` takes three
    values, YaRN's ramp is crossed), chunks of 16 through the chunk
    programs' in-place block attention (the last padded), its last 8
    positions through the absorbed decode step over the slots' state, by
    the benchmark's own comparison.  The limits are the
    configuration's, set for the chip's size and precision; float32 at
    this size reads 1e-6, so the rehearsal holds it to limits of its own,
    which the sound run is far inside and each control leaves."""
    config = json.loads(open(os.path.join(REPO, chip_smoke.MISTRAL4_CONFIG)).read())
    config["reference"]["logit_share_limits"] = MISTRAL4_TINY_LIMITS
    tiny = tmp_path / "config.json"
    tiny.write_text(json.dumps(config))
    monkeypatch.setattr(chip_smoke, "MISTRAL4_CONFIG", str(tiny))
    # A sound run inside the limits and a control outside them both return.
    chip_smoke.child_hybrid(0, chip_smoke.TINY, control=control, model="mistral4")
    line = _phases(capsys)["hybrid"]
    assert line["model"] == "mistral4-tiny" and line["control"] == (control or None)
    assert line["positions"] == {"prefill": 67, "decode": 8}
    # The shapes of the scheduler's programs: a chunk beside a pad row over
    # the whole slot (256 rows here), a decode step over both slots.
    assert "attn_latent_chunk b=2 s=16 t=256" in line["kernel_paths"]
    assert "attn_latent_decode b=2 t=256" in line["kernel_paths"]
    assert line["within_limits"] == (not control)
    if control == "no_attn_scale":  # a(p) is 1 below the original context: the lowest tenth is sound
        assert line["p10"] < 1e-4 and line["p50"] > 1e-2 and line["decode_p50"] > 1e-2
    elif control == "w8a8_mlp":  # the precision: every position moves, by little
        assert 1e-3 < line["p10"] < 0.2
    elif control:
        assert line["p50"] > 0.05


LONGCAT_CONTROLS = ["no_latent_rescale", "no_zero_identity", "shortcut_early", "renormed_weights", "w8a8_mlp"]


# The sound run and the controls that no other tier-1 test makes of the
# reference (the branch moved, the identity term dropped and the rescale
# left out are tests/test_longcat_flash_model.py's and the benchmark's own).
@pytest.mark.parametrize("control", ["", "w8a8_mlp", "renormed_weights"])
def test_hybrid_phase_rehearsal_of_the_shortcut_model_and_its_controls(control, capsys, monkeypatch, tmp_path):
    """``--hybrid --model longcat_flash`` at the tiny size, in process: a
    prompt of 75 tokens in chunks of 16 through the chunk program beside a
    pad row (two published layers: the expert branch crosses a sublayer
    twice), its last 8 positions through the decode step over both slots,
    by the benchmark's own comparison.  The limits are the configuration's,
    set for the chip's size and precision; float32 at this size reads 1e-6,
    so the rehearsal holds it to limits of its own, which the sound run is
    far inside and each control leaves."""
    config = json.loads(open(os.path.join(REPO, chip_smoke.LONGCAT_CONFIG)).read())
    config["reference"]["logit_share_limits"] = MISTRAL4_TINY_LIMITS
    tiny = tmp_path / "config.json"
    tiny.write_text(json.dumps(config))
    monkeypatch.setattr(chip_smoke, "LONGCAT_CONFIG", str(tiny))
    # A sound run inside the limits and a control outside them both return.
    chip_smoke.child_hybrid(0, chip_smoke.TINY, control=control, model="longcat_flash")
    line = _phases(capsys)["hybrid"]
    assert line["model"] == "longcat_flash-tiny" and line["control"] == (control or None)
    assert line["positions"] == {"prefill": 67, "decode": 8}
    assert "attn_latent_chunk b=2 s=16 t=256" in line["kernel_paths"]
    assert "attn_latent_decode b=2 t=256" in line["kernel_paths"]
    assert any(site.startswith("moe_experts") for site in line["kernel_paths"])
    assert line["within_limits"] == (not control)
    if control == "w8a8_mlp":  # the precision: every position moves, by little
        assert 1e-3 < line["p10"] < 0.2
    elif control:
        assert line["p50"] > 1e-2 and line["decode_p50"] > 1e-2
    else:
        assert max(line[k] for k in MISTRAL4_TINY_LIMITS) < 1e-5


ZAYA_CONTROLS = ["no_value_shift", "no_qk_mean", "no_conv", "no_router_average", "renormed_top1", "w8a8"]


@pytest.mark.parametrize("control", ["", *ZAYA_CONTROLS])
def test_hybrid_phase_rehearsal_of_the_cca_model_and_its_controls(control, capsys, monkeypatch, tmp_path):
    """``--hybrid --model zaya`` at the tiny size, in process: a prompt of
    45 tokens in chunks of 16 through the chunk program beside a pad row
    (the last chunk padded; every chunk after the first continues from the
    slot's tails), its last 8 positions through the decode step over both
    slots, by the benchmark's own comparison.  The limits are the
    configuration's, set for the chip's size and precision; float32 at this
    size reads 1e-7, so the rehearsal holds it to limits of its own, which
    the sound run is far inside and each control leaves."""
    config = json.loads(open(os.path.join(REPO, chip_smoke.ZAYA_CONFIG)).read())
    config["reference"]["logit_share_limits"] = MISTRAL4_TINY_LIMITS
    tiny = tmp_path / "config.json"
    tiny.write_text(json.dumps(config))
    monkeypatch.setattr(chip_smoke, "ZAYA_CONFIG", str(tiny))
    # A sound run inside the limits and a control outside them both return.
    chip_smoke.child_hybrid(0, chip_smoke.TINY, control=control, model="zaya")
    line = _phases(capsys)["hybrid"]
    assert line["model"] == "zaya-tiny" and line["control"] == (control or None)
    assert line["positions"] == {"prefill": 37, "decode": 8}
    # The shapes of the scheduler's programs: a chunk beside a pad row over
    # the whole slot (256 rows here), a decode step over both slots.
    assert "attn_cca_chunk b=2 s=16 t=256" in line["kernel_paths"]
    assert "attn_cca b=2 s=1 t=256" in line["kernel_paths"]
    assert line["within_limits"] == (not control)
    if control == "w8a8":  # the precision: every position moves, by little
        assert 1e-3 < line["p10"] < 0.2
    elif control == "no_router_average":  # positions whose choice flips in some layer
        assert line["p90"] > 1e-2
    elif control:  # a mechanism of the mixer or the weighting: every position, by much
        assert line["p10"] > 1e-2 and line["decode_p50"] > 1e-2


NEMOTRON_CONTROLS = list(chip_smoke.HYBRID_CONTROLS["nemotron_h"])


# The nine against the reference alone: tests/test_nemotron_h_model.py.
@pytest.mark.parametrize("control", ["", "w8a8_mlp", "state_bf16", "norm_whole", "rope_on"])
def test_hybrid_phase_rehearsal_of_the_mamba_model_and_its_controls(control, capsys, monkeypatch, tmp_path):
    """``--hybrid --model nemotron_h`` at the tiny size, in process: a prompt
    of 45 tokens in chunks of 16 (two scan blocks of 8) through the chunk
    program beside a pad row (every chunk after the first continues from
    the slot's ``S`` and tail), its last 8 positions through the decode step
    over both slots, by the benchmark's own comparison, held to limits of
    the rehearsal's own (float32 at this size reads 1e-7) which the sound
    run is far inside and each control leaves (four of the nine here)."""
    config = json.loads(open(os.path.join(REPO, chip_smoke.NEMOTRON_CONFIG)).read())
    config["reference"]["logit_share_limits"] = {"p10": 2e-4, "p50": 2e-4, "p90": 2e-4, "decode_p50": 2e-4}
    tiny = tmp_path / "config.json"
    tiny.write_text(json.dumps(config))
    monkeypatch.setattr(chip_smoke, "NEMOTRON_CONFIG", str(tiny))
    chip_smoke.child_hybrid(0, chip_smoke.TINY, control=control, model="nemotron_h")
    line = _phases(capsys)["hybrid"]
    assert line["model"] == "nemotron_h-tiny" and line["control"] == (control or None)
    assert line["positions"] == {"prefill": 37, "decode": 8}
    # The shapes of the scheduler's programs: a chunk beside a pad row, a
    # decode step over both slots.
    for site in ("ssm_scan b=2 s=16", "ssm_step b=2 h=8", "attn_full_chunk b=2 s=16 t=256", "attn_full b=2 s=1 t=256"):
        assert line["kernel_paths"][site] == "xla"
    assert line["within_limits"] == (not control)
    if not control:
        assert line["p90"] < 1e-5
    elif control == "state_bf16":  # a rounding a token: little, and more the longer the state has run
        assert line["decode_p50"] > 2e-4
    else:
        assert line["p50"] > 1e-3


DOTS3_CONTROLS = list(chip_smoke.HYBRID_CONTROLS["dots3_note"])


# The seven mechanisms against the reference alone: tests/test_dots3_note_model.py.
@pytest.mark.parametrize("control", ["", "w8a8_mlp", "last_2048"])
def test_hybrid_phase_rehearsal_of_the_indexed_latent_model_and_its_controls(control, capsys, monkeypatch, tmp_path):
    """``--hybrid --model dots3_note`` at the tiny size, in process: a
    prompt of 75 tokens (three times ``index_topk`` 24, five windows of 13)
    in chunks of 16 through the chunk program beside a pad row, its last 8
    positions through the decode step over both slots, by the benchmark's
    own comparison (the logit shares and ``index_overlap``), held to limits
    of the rehearsal's own (float32 at this size reads 1e-6 and an overlap
    of 1) which the sound run is far inside and each control leaves (the
    precision and a selection of other rows here)."""
    config = json.loads(open(os.path.join(REPO, chip_smoke.DOTS3_CONFIG)).read())
    config["reference"]["logit_share_limits"] = {"p10": 2e-4, "p50": 2e-4, "p90": 2e-4, "decode_p50": 2e-4}
    config["reference"]["index_overlap_floor"] = 0.999
    tiny = tmp_path / "config.json"
    tiny.write_text(json.dumps(config))
    monkeypatch.setattr(chip_smoke, "DOTS3_CONFIG", str(tiny))
    chip_smoke.child_hybrid(0, chip_smoke.TINY, control=control, model="dots3_note")
    line = _phases(capsys)["hybrid"]
    assert line["model"] == "dots3_note-tiny" and line["control"] == (control or None)
    assert line["positions"] == {"prefill": 67, "decode": 8}
    # The shapes of the scheduler's programs: a chunk beside a pad row, a
    # decode step over both slots.
    for site in ("index_scores b=2 s=16 t=256", "attn_latent_chunk b=2 s=16 t=256 k=24",
                 "index_scores b=2 s=1 t=256", "attn_latent_sparse_decode b=2 t=256 k=24"):
        assert line["kernel_paths"][site] == "xla"
    assert line["within_limits"] == (not control)
    if not control:
        assert line["p90"] < 1e-5 and line["index_overlap"] == 1.0
    elif control == "w8a8_mlp":  # the precision: every position moves, by little; the sets hardly
        assert 1e-3 < line["p10"] < 0.2 and line["index_overlap"] > 0.8
    else:  # other rows kept: the first 24 positions agree, the rest do not
        assert line["p90"] > 1e-2 and line["decode_p50"] > 1e-2 and line["index_overlap"] < 0.8


DEEPSEEK_CONTROLS = list(chip_smoke.HYBRID_CONTROLS["deepseek_v32"])


# The mechanisms against the reference alone: tests/test_deepseek_v32_model.py.
@pytest.mark.parametrize("control", ["", "stale_reject", "draft_shares_set"])
def test_hybrid_phase_rehearsal_of_the_drafting_indexed_model_and_its_controls(control, capsys, monkeypatch, tmp_path):
    """``--hybrid --model deepseek_v32`` at the tiny size, in process: a
    prompt of 76 tokens (three times ``index_topk`` 24) in chunks of 16
    through the chunk program beside a pad row, its last 12 positions
    through the verify step over both slots on true and on wrong drafts,
    by the benchmark's own comparison (the logit shares of the stack and
    of the module, and both index overlaps), held to limits of the
    rehearsal's own which the sound run is far inside and each control
    leaves: a rejected draft's stale row read as a token's, and a verify
    step's second position given the first's set."""
    config = json.loads(open(os.path.join(REPO, chip_smoke.DEEPSEEK_CONFIG)).read())
    config["reference"]["logit_share_limits"] = {
        k: 2e-4 for k in ("p10", "p50", "p90", "accept_p50", "reject_p50", "module_p50")}
    config["reference"]["index_overlap_floors"] = {"stack": 0.999, "module": 0.999}
    tiny = tmp_path / "config.json"
    tiny.write_text(json.dumps(config))
    monkeypatch.setattr(chip_smoke, "DEEPSEEK_CONFIG", str(tiny))
    chip_smoke.child_hybrid(0, chip_smoke.TINY, control=control, model="deepseek_v32")
    line = _phases(capsys)["hybrid"]
    assert line["model"] == "deepseek_v32-tiny" and line["control"] == (control or None)
    assert line["positions"] == {"prefill": 64, "accept": 12, "reject": 11, "module": 18}
    # The shapes of the scheduler's programs: a chunk beside a pad row, a
    # verify step over both slots, in the stack and in the module's block.
    for site in ("index_scores b=2 s=16 t=256", "attn_latent_chunk b=2 s=16 t=256 k=24",
                 "index_scores b=2 s=2 t=256", "attn_latent_sparse_verify b=2 t=256 k=24",
                 "mtp_attn_latent_chunk b=2 s=16 t=256 k=24", "mtp_attn_latent_sparse_verify b=2 t=256 k=24",
                 "mtp_attn_latent_sparse_decode b=2 t=256 k=24"):
        assert line["kernel_paths"][site] == "xla"
    assert line["within_limits"] == (not control)
    if not control:
        assert max(line[k] for k in config["reference"]["logit_share_limits"]) < 1e-5
        assert line["index_overlap_stack"] == line["index_overlap_module"] == 1.0
    elif control == "stale_reject":  # the prefill and the accept pass are sound
        assert line["p90"] < 1e-5 and line["accept_p50"] < 1e-5 and line["reject_p50"] > 1e-2
    else:  # every second position attends another set, prefilled or verified
        assert line["p50"] > 1e-2 and line["accept_p50"] > 1e-2 and line["index_overlap_stack"] < 0.9


def test_hybrid_phase_names_a_child_for_every_model_and_control():
    assert sorted(n for n in chip_smoke.CHILDREN if n.startswith("hybrid")) == [
        "hybrid_deepseek_v32", *(f"hybrid_deepseek_v32_{c}" for c in sorted(DEEPSEEK_CONTROLS)),
        "hybrid_dots3_note", *(f"hybrid_dots3_note_{c}" for c in sorted(DOTS3_CONTROLS)),
        "hybrid_exaone_moe", "hybrid_exaone_moe_no_qk_norm", "hybrid_exaone_moe_no_window",
        "hybrid_exaone_moe_rope_on_full", "hybrid_exaone_moe_stale_reject", "hybrid_exaone_moe_w8a8_mlp",
        "hybrid_ling", "hybrid_ling_w8a8_mlp",
        "hybrid_longcat_flash", *(f"hybrid_longcat_flash_{c}" for c in sorted(LONGCAT_CONTROLS)),
        "hybrid_mellum", "hybrid_mellum_no_window",
        "hybrid_mellum_no_yarn", "hybrid_mellum_w8a8_mlp",
        "hybrid_mistral4", "hybrid_mistral4_no_attn_scale", "hybrid_mistral4_no_mscale",
        "hybrid_mistral4_no_q_norm", "hybrid_mistral4_plain_rope", "hybrid_mistral4_w8a8_mlp",
        "hybrid_nemotron_h", *(f"hybrid_nemotron_h_{c}" for c in sorted(NEMOTRON_CONTROLS)),
        "hybrid_zaya", *(f"hybrid_zaya_{c}" for c in sorted(ZAYA_CONTROLS)),
    ]
    with pytest.raises(chip_smoke.SmokeFailure, match="has no control 'no_yarn'"):
        chip_smoke.run(0, chip_smoke.TINY, expect="cpu", hybrid=("ling", "no_yarn"))
