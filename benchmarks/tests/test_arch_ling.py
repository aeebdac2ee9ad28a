"""``arch/ling.py``: the mapping at both sizes (golden values), the counts
against the configuration's table worked by hand, the new counter readers
on a made-up ``ctx``, the benchmark's copy of the reference against the
program's, the logit-level comparison behind ``last_logits``, and a CPU
rehearsal of the new cell."""

import dataclasses
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

import run
from metrics_lib import load_reader

BENCH = Path(__file__).resolve().parents[1]
REPO = BENCH.parent
NAME = "ling-3.0-flash-vl-l7e128"
CELL = f"{NAME}.rag-closed"
KINDS = (("kda", "dense"),) + (("kda", "experts"),) * 3 + (("mla", "experts"),) + (("kda", "experts"),) * 2


def sizes(rehearse):
    model = json.loads((BENCH / "configs" / f"{NAME}.json").read_text())
    engine = dict(model["engine"])
    if rehearse:  # as run.py lays them over
        model.update(model["rehearse"]["model"])
        engine.update(model["rehearse"]["engine"])
    return model, engine


GOLDEN = {
    False: dict(
        vocab_size=39296, d_model=2560, layer_kinds=KINDS, n_heads=32, kda_head_dim=128,
        conv_kernel=4, kda_gate_floor=-5.0, kv_lora_rank=512, qk_nope_head_dim=128,
        qk_rope_head_dim=64, v_head_dim=128, rope_theta=6e6, d_ff=6144, moe_d_ff=768,
        shared_d_ff=768, n_experts=512, experts_held=128, expert_offset=0,
        n_experts_per_tok=8, n_group=8, topk_group=4, routed_scaling=2.5, norm_topk=True,
        norm_eps=1e-6, max_seq_len=2048, dtype="bfloat16", kv_dtype="bfloat16",
    ),
    True: dict(
        vocab_size=512, d_model=64, layer_kinds=KINDS, n_heads=4, kda_head_dim=16,
        conv_kernel=4, kda_gate_floor=-5.0, kv_lora_rank=32, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16, rope_theta=6e6, d_ff=128, moe_d_ff=32,
        shared_d_ff=32, n_experts=32, experts_held=8, expert_offset=0,
        n_experts_per_tok=4, n_group=8, topk_group=4, routed_scaling=2.5, norm_topk=True,
        norm_eps=1e-6, max_seq_len=2048, dtype="float32", kv_dtype="float32",
    ),
}


@pytest.mark.parametrize("rehearse", [False, True])
def test_mapping_golden_values(rehearse):
    model, engine = sizes(rehearse)
    arch = run.load_arch(model)
    assert Path(arch.__file__).name == "ling.py"
    assert dataclasses.asdict(arch.llama_config(model, engine)) == GOLDEN[rehearse]
    assert arch.layer_kinds(model) == list(KINDS)


def test_the_file_keeps_every_published_width_and_lists_its_cuts():
    model, _ = sizes(False)
    row = next(
        json.loads(l) for l in open("/opt/skills/guides/model-configs/architectures.jsonl")
        if '"name": "Ling-3.0-flash-VL"' in l
    ) if Path("/opt/skills/guides/model-configs/architectures.jsonl").exists() else None
    assert model["reduced"] == ["num_hidden_layers", "first_k_dense_replace", "num_experts", "vocab_size"]
    assert model["reduced_from"] == {"num_hidden_layers": 42, "first_k_dense_replace": 2,
                                     "num_experts": 512, "vocab_size": 157184}
    assert model["num_experts_published"] == 512  # the router's outputs
    assert len(model["assumed"]) >= 8 and "tower" in model["stands_for"]
    if row is not None:
        assert model["source"] == row["source_url"]
        for key, value in row["config"].items():
            if key not in model["reduced"]:
                assert model[key] == value, key


def test_parameter_counts_are_the_tables():
    model, _ = sizes(False)
    p = run.load_arch(model).part_params(model)
    # By hand, from the published widths (ISSUE 27's table).
    assert p["expert"] == 3 * 2560 * 768 == 5_898_240
    assert p["kda"] == (2560 * 12288 + 4 * 12288 + 2 * 2560 * 4096 + 2560 * 32
                        + 32 + 4096 + 128 + 4096 * 2560)
    assert round(p["kda"] / 1e6, 1) == 63.0 and round(p["mla"] / 1e6, 1) == 32.0
    assert p["mla"] == 2560 * 6144 + 2560 * 576 + 512 + 512 * 8192 + 2560 * 32 + 4096 * 2560
    assert p["dense"] == 3 * 2560 * 6144 and p["router"] == 2560 * 512
    assert p["head"] == 2560 * 39296
    weights = (6 * 128 * p["expert"] + 6 * (p["shared"] + p["router"]) + 6 * p["kda"]
               + p["mla"] + p["dense"] + 2 * p["head"])
    assert round(weights * 2 / 1e9, 2) == 10.46  # GB in bf16, the table's total


def test_decode_step_bytes_by_hand():
    model, engine = sizes(False)
    arch = run.load_arch(model)
    p = arch.part_params(model)
    once = 6 * p["kda"] + p["mla"] + p["dense"] + 6 * (p["shared"] + p["router"]) + p["head"]
    # 13 rows x 8 choices over 512 experts: a given expert is missed by a
    # row with probability 63/64.
    touched = 128 * (1 - (63 / 64) ** 13)
    assert arch.experts_touched(model, 13) == pytest.approx(touched) and 23 < touched < 24
    state = 6 * (32 * 128 * 128 * 4 + 3 * 12288 * 2)
    assert arch.state_bytes_per_row(model, engine) == state
    want = 2 * (once + 6 * touched * p["expert"]) + 2 * 13 * state + 20000 * 576 * 2
    assert arch.decode_step_bytes(model, engine, 20000) == pytest.approx(want)
    assert 3.2e9 < want < 3.6e9  # about 1.2 GB once, 1.7 GB of experts, 0.34 GB of state
    # More rows touch more experts and stream more state; none, only what
    # is read once.
    assert arch.decode_step_bytes(model, {**engine, "roofline_decode_rows": 32}, 0) > want
    assert arch.decode_step_bytes(model, {**engine, "roofline_decode_rows": 0}, 0) == 2 * once


def test_prefill_flops_by_hand():
    model, _ = sizes(False)
    arch = run.load_arch(model)
    p = arch.part_params(model)
    # 8 choices a token, a quarter of them here: 2 experts' worth a layer.
    active = 6 * p["kda"] + p["mla"] + p["dense"] + 6 * (p["router"] + p["shared"] + 2 * p["expert"])
    kda = 32 * (6 * 128 * 128 + 7 * 16 * 128)
    assert arch.kda_flops_per_token(model) == kda
    pair = 2 * 32 * (128 + 64 + 128)
    assert arch.prefill_flops(model, 256, 1000) == pytest.approx(
        2 * active * 256 + 6 * kda * 256 + pair * 1000)
    assert arch.local_share(model) == 0.25


# Between the markers of a traced window.
COUNTERS = {
    "moe_choices_routed": 48000, "moe_choices_local": 11800, "moe_experts_touched": 9000,
    "moe_expert_rows_max": 300,
    "prefix_tokens_matched": 9000, "prefix_tokens_reused": 5120,
}
READERS = {
    "expert_local_pct": 100.0 * 11800 / 48000,
    "expert_load_max_over_mean": 128 * 300 / 11800,
    "prefix_snapshot_loss_pct": 100.0 * (9000 - 5120) / 9000,
}
ZERO = {"expert_local_pct": "moe_choices_routed", "expert_load_max_over_mean": "moe_choices_local",
        "prefix_snapshot_loss_pct": "prefix_tokens_matched"}


@pytest.mark.parametrize("name", sorted(READERS))
def test_new_counter_readers(name):
    model, engine = sizes(False)
    read = load_reader(name)
    ctx = {"trace_counters": dict(COUNTERS), "counters": {}, "model": model, "engine": engine,
           "trace": None}
    assert read(ctx) == pytest.approx(READERS[name])
    assert read({**ctx, "trace_counters": None}) is None  # --trace 0
    assert read({**ctx, "trace_counters": {**COUNTERS, ZERO[name]: 0}}) is None
    # A program without the counter (the parent): nothing to read, no error.
    assert read({**ctx, "trace_counters": {"busy_ticks": 3, "prefix_tokens_reused": 5}}) is None


@pytest.fixture(scope="module")
def tiny():
    """The rehearsal's model with the program's random parameters."""
    import jax
    from generativeaiexamples_tpu.models import hybrid

    model, engine = sizes(True)
    arch = run.load_arch(model)
    cfg = arch.llama_config(model, engine)
    return arch, cfg, hybrid.init_params(cfg, jax.random.PRNGKey(5))


def test_the_benchmarks_reference_agrees_with_the_programs(tiny):
    """Two files, each free to change in form; the same logits."""
    import numpy as np
    import ling_reference
    from generativeaiexamples_tpu.models import hybrid_reference

    _, cfg, params = tiny
    tokens = list(range(7, 47))
    np.testing.assert_allclose(
        ling_reference.all_logits(params, cfg, tokens),
        hybrid_reference.all_logits(params, cfg, tokens), rtol=1e-5, atol=1e-5)


def test_last_logits_holds_the_programs_logits_to_the_reference(tiny, monkeypatch, capsys):
    import numpy as np
    import ling_reference

    arch, cfg, params = tiny
    tokens = [3 + 5 * i % 500 for i in range(300)]
    got = np.asarray(arch.last_logits(params, cfg, tokens, 384))
    want = np.asarray(ling_reference.all_logits(params, cfg, tokens))[-1]
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)  # float32 both: the reference's
    line = json.loads(capsys.readouterr().out.splitlines()[-1])
    assert line["outside"] == [] and line["p90"] < 1e-3  # chunks of 256 + 128, 84 of them padding
    # A program whose second chunk answers for other positions: the
    # first chunk's logits are right, the last 44 of 300 are not.
    sound = arch.program_logits

    def misplaces(params, cfg, tokens, pad_to):
        logits = np.array(sound(params, cfg, tokens, pad_to))
        logits[256:] = logits[: pad_to - 256]
        return logits

    monkeypatch.setattr(arch, "program_logits", misplaces)
    vetoed = np.asarray(arch.last_logits(params, cfg, tokens, 384))
    line = json.loads(capsys.readouterr().out.splitlines()[-1])
    assert line["outside"] == ["p90"] and line["p10"] < 1e-3, line
    # One entry more than the vocabulary holds the maximum: gap 1 for any served token.
    assert vetoed.shape == (cfg.vocab_size + 1,) and vetoed.argmax() == cfg.vocab_size
    assert float(vetoed.max() - vetoed[: cfg.vocab_size].max()) / float(np.abs(vetoed).max()) == 1.0


@pytest.mark.parametrize("outside", ["p10", "p50", "p90"])
def test_each_logit_share_limit_is_held(tiny, monkeypatch, capsys, outside):
    import numpy as np

    arch, cfg, params = tiny
    limits = sizes(False)[0]["reference"]["logit_share_limits"]
    assert sorted(limits) == ["p10", "p50", "p90"] and limits["p10"] < limits["p50"] < limits["p90"]
    made_up = {k: 0.5 * v for k, v in limits.items()}
    made_up[outside] = 1.01 * limits[outside]
    monkeypatch.setattr(arch, "logit_shares", lambda share: dict(made_up))
    assert np.asarray(arch.last_logits(params, cfg, list(range(9, 60)), 64)).shape == (cfg.vocab_size + 1,)
    assert json.loads(capsys.readouterr().out.splitlines()[-1])["outside"] == [outside]


def test_rehearsal_of_the_new_cell_is_correct():
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": str(REPO)}
    done = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", CELL, "--seed", str(2**31 + 11),
         "--seconds", "40", "--trace", "1", "--rehearse"],
        cwd=REPO, env=env, text=True, timeout=1500, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
    )
    assert done.returncode == 0, done.stderr[-3000:]
    lines = done.stdout.splitlines()
    checks = next(json.loads(l) for l in lines if l.startswith('{"bench": "checks"'))
    assert checks["arch"] == "ling" and checks["reference_check"]["ok"], checks
    assert checks["checks"]["no_compile_in_window"], checks["compiled_in_window"]
    result = json.loads(lines[-1])
    assert result["correct"] is True and result["failed"] == 0
    for name in ("expert_local_pct", "expert_load_max_over_mean", "prefix_snapshot_loss_pct",
                 "prefix_reuse_pct", "decode_kv_read_pct", "tick_ms"):
        assert name in result["metrics"], name
    assert result["metrics"]["prefix_reuse_pct"]["value"] > 0  # snapshots are hit
    assert 15 < result["metrics"]["expert_local_pct"]["value"] < 35
