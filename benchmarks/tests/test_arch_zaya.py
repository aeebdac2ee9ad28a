"""``arch/zaya.py``: the mapping at both sizes, the file against the
catalog's row, the configuration's golden ``dataclasses.asdict``, the
counts against the table of the configuration's cut worked by hand, the
new counter reader on canned counters, the benchmark's copy of the
reference against the program's, and the logit-level comparison behind
``last_logits`` (sound, through the calls the measured window makes, and
with a mechanism left out of the reference)."""

import dataclasses
import json
from pathlib import Path

import numpy as np
import pytest

import run
from metrics_lib import load_reader

BENCH = Path(__file__).resolve().parents[1]
REPO = BENCH.parent
NAME = "zaya1-8b-l20"
CELL = f"{NAME}.reason-closed"
KEXAONE = "k-exaone-236b-a23b-l5e16.reason-closed"
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")
CONTROLS = ("w8a8", "w8a8_mlp", "no_value_shift", "no_qk_mean", "no_conv", "no_router_average", "renormed_top1")


def sizes(rehearse):
    model = json.loads((BENCH / "configs" / f"{NAME}.json").read_text())
    engine = dict(model["engine"])
    if rehearse:  # as run.py lays them over
        model.update(model["rehearse"]["model"])
        engine.update(model["rehearse"]["engine"])
    return model, engine


def test_mapping_at_the_published_and_the_rehearsal_sizes(capsys):
    model, engine = sizes(False)
    arch = run.load_arch(model)
    assert Path(arch.__file__).name == "zaya.py"
    cfg = arch.llama_config(model, engine)
    assert type(cfg).__name__ == "CcaConfig" and cfg.layer_kinds == (("cca", "experts"),) * 20
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    # What the build holds (ISSUE 40's table): 9.38 GB of weights, 5.37 GB
    # of K/V rows, 3.4 MB of tails of which a snapshot takes one slot's.
    assert line["weight_bytes"] == 9_376_946_208 and line["tied_head"]
    assert line["state_bytes_full"] == 32 * 8192 * 1024 * 20 == 5_368_709_120
    assert line["state_bytes_tails"] == 32 * 20 * 5376 and line["snapshot_bytes"] == 107_520
    assert line["state_bytes_window"] == 0
    assert (line["params_attention"], line["params_router"]) == (5_575_682, 660_513)
    assert (line["params_experts"], line["params_embedding"]) == (201_326_592, 537_133_056)
    tiny_model, tiny_engine = sizes(True)
    tiny = arch.llama_config(tiny_model, tiny_engine)
    # The ratios at a size a CPU runs: the router's state is handed on once,
    # the grouped mean and the value shift have two heads each.
    assert len(tiny.layer_kinds) == 2 and (tiny.n_heads, tiny.n_kv_heads) == (4, 2)
    assert (tiny.n_experts, tiny.experts_held, tiny.n_experts_per_tok, tiny.vocab_size) == (4, 4, 1, 512)
    assert (tiny.dtype, tiny.kv_dtype, tiny.tie_embeddings, tiny.max_seq_len) == ("float32", "float32", True, 8192)
    with pytest.raises(ValueError, match="experts_held"):
        arch.llama_config(model, {**engine, "experts_held": 8})
    with pytest.raises(ValueError, match="bf16"):
        arch.llama_config(model, {**engine, "weight_dtype": "int8"})


def test_the_configuration_is_the_golden_one():
    """``dataclasses.asdict`` of what the program is handed, field by
    field: a later change to the mapping or to a default shows here."""
    model, engine = sizes(False)
    cfg = run.load_arch(model).llama_config(model, engine)
    rope = {"theta": 5e6, "rope_type": "default", "factor": 1.0, "original_max": 0, "beta_fast": 32.0,
            "beta_slow": 1.0, "attention_factor": 1.0, "truncate": True}
    assert dataclasses.asdict(cfg) == {
        "vocab_size": 262272, "d_model": 2048, "layer_kinds": (("cca", "experts"),) * 20, "n_heads": 8,
        # Ling's KDA and MLA sizes at their defaults: no layer here reads them.
        "kda_head_dim": 128, "conv_kernel": 4, "kda_gate_floor": -5.0, "kv_lora_rank": 512,
        "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "v_head_dim": 128, "rope_theta": 6e6,
        "d_ff": 2048, "moe_d_ff": 2048, "shared_d_ff": 0, "n_experts": 16, "experts_held": 16,
        "expert_offset": 0, "n_experts_per_tok": 1, "n_group": 1, "topk_group": 1, "routed_scaling": 1.0,
        "norm_topk": False, "norm_eps": 1e-5, "max_seq_len": 8192, "dtype": "bfloat16", "kv_dtype": "bfloat16",
        "score_function": "softmax", "router_bias": True, "n_kv_heads": 2, "attn_head_dim": 128,
        "rope_full": rope, "rotary_dim": 64, "conv_time0": 2, "conv_time1": 2, "router_hidden": 256,
        "tie_embeddings": True,
    }


def test_the_file_keeps_every_published_width_and_lists_its_cut():
    model, engine = sizes(False)
    assert model["reduced"] == ["num_hidden_layers"] and model["reduced_from"] == {"num_hidden_layers": 40}
    assert (model["num_experts"], model["vocab_size"], model["tie_word_embeddings"]) == (16, 262272, True)
    assert model["arch"] == "zaya" and model["num_hidden_layers"] == 20
    assumed = " ".join(model["assumed"])
    assert len(model["assumed"]) >= 10
    for needle in ("zero", "bias", "sqrt(head_dim)", "half-split", "PREVIOUS", "EXACT (erf) GELU", "gamma",
                   "argmax(p + beta)", "arXiv:2510.04476", "arXiv:2511.17127"):
        assert needle in assumed, needle
    unserved = " ".join(model["not_served"])
    for needle in ("residual scaling", "MoD", "hybrid_sliding", "paged"):
        assert needle in unserved, needle
    for needle in ("TWO pipeline stages", "layers 0-19", "rho_19", "9.38 GB", "5.37 GB", "415 MB", "107,520 B",
                   "87 %", "one in 41"):
        assert needle in model["stands_for"], needle
    assert engine == {**engine, "weight_dtype": "bfloat16", "kv_dtype": "bfloat16", "max_len": 8192,
                      "decode_chunk_size": 8, "prefill_chunk_tokens": 256, "prefix_cache": "shared",
                      "kv_layout": "contiguous", "experts_held": 16, "expert_offset": 0}
    assert engine["max_batch"] in (32, 28, 24)  # 32 unless set-up on the chip could not hold it
    assert model["expect_paths"]["moe_experts"] == "pallas"
    ref = model["reference"]
    assert (ref["prompts"], ref["decode_positions"]) == (6, 16) and ref["min_within"] >= 5
    assert set(ref["logit_share_limits"]) == {"p10", "p50", "p90", "decode_p50"}
    for control in CONTROLS:
        assert control in ref["why"], control
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    entry = next(c for c in spec["configs"] if c["name"] == NAME)
    assert entry == spec["configs"][-1]  # appended
    assert entry["reduced"] == model["reduced"] and entry["source"] == model["source"]
    assert entry["file"] == f"benchmarks/configs/{NAME}.json"
    if CATALOG.exists():
        row = next(json.loads(l) for l in open(CATALOG) if '"name": "ZAYA1-8B"' in l)
        assert model["source"] == row["source_url"]
        for key, value in row["config"].items():
            if key not in model["reduced"]:
                assert model[key] == value, key
    rehearse = model["rehearse"]["model"]
    assert (rehearse["num_hidden_layers"], rehearse["num_attention_heads"], rehearse["num_key_value_heads"],
            rehearse["num_experts"], rehearse["vocab_size"]) == (2, 4, 2, 4, 512)


def test_parameter_counts_are_the_issues_table():
    model, _ = sizes(False)
    p = run.load_arch(model).part_params(model)
    # By hand, from the published widths (ISSUE 40): W_q, W_k, W_v1 + W_v2, W_o, tau.
    assert p["attention"] == 2_097_152 + 524_288 + 524_288 + 2_097_152 + 2
    # w0 and b0 (3 x 1,280), W1 (2 x 10 x 128 x 128), b1.
    assert p["convolutions"] == 3_840 + 327_680 + 1_280 == 332_800
    assert round((p["attention"] + p["convolutions"]) / 1e6, 2) == 5.58
    assert p["router"] == 524_288 + 256 + 1 + 256 + 2 * (65_536 + 256) + 4_096 + 16 + 16 == 660_513
    assert p["norms"] == 4096 and p["expert"] == 3 * 2048 * 2048 == 12_582_912
    assert p["head"] == 262272 * 2048 == 537_133_056
    layer = p["attention"] + p["convolutions"] + p["router"] + p["norms"] + 16 * p["expert"]
    assert layer == 207_566_883 and round(layer * 2 / 1e6) == 415
    total = 20 * layer + p["head"] + 2048  # and the final norm
    assert round(total * 2 / 1e9, 2) == 9.38


def test_decode_step_bytes_by_hand():
    model, engine = sizes(False)
    arch = run.load_arch(model)
    p = arch.part_params(model)
    rows = engine["roofline_decode_rows"]
    touched = 16 * (1 - (15 / 16) ** rows)
    assert arch.experts_touched(model, rows) == pytest.approx(touched)
    assert arch.experts_touched(model, 1) == pytest.approx(1.0)
    assert arch.experts_touched(model, 30) == pytest.approx(13.69, abs=0.01)  # 86 % of 16
    assert arch.experts_touched(model, 5) == pytest.approx(4.41, abs=0.01)  # 28 %
    assert arch.kv_bytes_per_row(model, engine) == 1024 and arch.tail_bytes(model, engine) == 5376
    once = 20 * (p["attention"] + p["convolutions"] + p["router"] + p["norms"]) + p["head"]
    live = rows * 2000
    want = 2 * (once + 20 * touched * p["expert"]) + 20 * live * 1024 + 20 * rows * 5376
    assert arch.decode_step_bytes(model, engine, live) == pytest.approx(want)
    # ISSUE 40's reckoning at 30 rows: experts 6.9 GB, the tied matrix as the
    # head 1.07, rows ~1.2, mixers and routers 0.25.
    assert round(2 * 20 * arch.experts_touched(model, 30) * p["expert"] / 1e9, 1) == 6.9
    assert round(2 * p["head"] / 1e9, 2) == 1.07 and round(20 * 30 * 2000 * 1024 / 1e9, 1) == 1.2
    assert round(2 * 20 * (p["attention"] + p["convolutions"] + p["router"]) / 1e9, 2) == 0.25
    fewer = arch.decode_step_bytes(model, {**engine, "roofline_decode_rows": 5}, live)
    assert fewer < want or rows <= 5


def test_prefill_flops_by_hand():
    model, _ = sizes(False)
    arch = run.load_arch(model)
    p = arch.part_params(model)
    active = 20 * (p["attention"] + p["convolutions"] + p["router"] + p["expert"])
    assert round(active / 1e6) == 376  # of the 0.76 B a token of the whole model: half the layers, no head
    pair = 4 * 8 * 128
    pairs = sum(i + 1 for i in range(1000, 1256))
    assert arch.prefill_flops(model, 256, pairs) == pytest.approx(2 * active * 256 + 20 * pair * pairs)
    assert arch.prefill_flops(model, 0, 0) == 0


COUNTERS = {"moe_experts_touched_decode": 60 * 8 * 20 * 12, "moe_expert_layer_steps_decode": 60 * 8 * 20}


def test_the_new_counter_reader():
    """Between the markers: 60 decode chunks of 8 steps over 20 expert
    layers, each step's layer touching 12 of the 16 experts held."""
    model, engine = sizes(False)
    read = load_reader("decode_experts_touched_pct")
    ctx = {"trace_counters": dict(COUNTERS), "counters": {}, "model": model, "engine": engine, "trace": None}
    assert read(ctx) == pytest.approx(75.0)
    assert read({**ctx, "trace_counters": None}) is None  # --trace 0
    assert read({**ctx, "trace_counters": {**COUNTERS, "moe_expert_layer_steps_decode": 0}}) is None
    # A program without the counters (the parent): nothing to read, no error.
    assert read({**ctx, "trace_counters": {"busy_ticks": 3, "moe_experts_touched": 5}}) is None
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    entry = spec["per_layer"][-1]
    assert entry == {"name": "decode_experts_touched_pct", "unit": "%", "better": "lower",
                     "source": "program_counter", "layer": "step programs", "moves": "itl_p95_ms",
                     "workloads": [CELL]}


def test_the_cell_is_listed_where_k_exaones_is_but_for_its_drafts_and_windows():
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    cell = spec["workloads"][-1]
    assert cell == {**cell, "name": CELL, "config": NAME, "traffic": "reason-closed", "chips": 1}
    judged = {m["name"] for m in spec["end_to_end"] if CELL in m.get("workloads", [CELL])}
    # ``out_tok_s`` is not judged here: six runs on six seeds spread 1.0 %
    # where half its bound is 0.5 % (a step streams the experts its rows
    # touch, so its time follows the seed's weights and tokens: PERF.md
    # section 2).  The cell reports it per layer, chat's way.
    assert judged == {"itl_p95_ms", "setup_s"}
    by_name = {m["name"]: m for m in spec["per_layer"]}
    listed = {name for name, m in by_name.items() if CELL in m.get("workloads", [CELL])}
    theirs = {name for name, m in by_name.items() if KEXAONE in m.get("workloads", [KEXAONE])}
    itl_twins = {"out_tok_s_closed", "decode_lanes_mean.itl", "decode_hbm_pct.itl", "device_idle_pct.itl"}
    assert listed - theirs == {"decode_experts_touched_pct"} | itl_twins
    # Every expert is here, no layer has a window, nothing drafts; and what
    # moves ``out_tok_s`` is listed under its twin that moves ``itl_p95_ms``.
    assert theirs - listed == {"expert_local_pct", "decode_window_rows_pct", "draft_accept_pct",
                               "verify_positions_per_token", "decode_lanes_mean", "decode_hbm_pct",
                               "device_idle_pct.closed"}
    assert all(by_name[name]["moves"] in judged for name in listed)
    for m in spec["end_to_end"] + spec["per_layer"]:
        if CELL in m.get("workloads", []):
            assert m["workloads"][-1] == CELL  # appended at the end of each list


def test_the_benchmarks_reference_is_the_programs_file():
    ours = (BENCH / "zaya_reference.py").read_text()
    theirs = (REPO / "generativeaiexamples_tpu" / "models" / "zaya_reference.py").read_text()
    assert ours == theirs
    imports = [l for l in ours.splitlines() if l.startswith(("import ", "from "))]
    assert not any("generativeaiexamples_tpu" in l or "ops" in l or "hybrid" in l for l in imports), imports


@pytest.fixture(scope="module")
def tiny():
    """The program's tiny preset with its random parameters, and the
    architecture module set up for chunks of 16 and 8 decoded positions."""
    import jax
    from generativeaiexamples_tpu.models import hybrid

    model, _ = sizes(False)
    arch = run.load_arch(model)
    cfg = hybrid.PRESETS["zaya-tiny"]()
    arch._CHECK.update(limits={"p10": 1e-3, "p50": 1e-3, "p90": 1e-3, "decode_p50": 1e-3}, decode=8, chunk=16)
    params = hybrid.init_params(cfg, jax.random.PRNGKey(0))
    tokens = np.random.RandomState(0).randint(1, cfg.vocab_size, size=45).tolist()
    return arch, cfg, params, tokens


def test_last_logits_hands_on_the_references_when_the_program_agrees(tiny, capsys):
    arch, cfg, params, tokens = tiny
    from generativeaiexamples_tpu.models import zaya_reference

    got = arch.last_logits(params, cfg, tokens, 64)
    want = np.asarray(zaya_reference.all_logits(params, cfg, tokens))[-1]
    np.testing.assert_allclose(got, want, atol=1e-4)
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["outside"] == [] and line["decode_p50"] < 1e-5 and line["p90"] < 1e-5
    share, _ = arch.logit_shares(params, cfg, tokens, 64)
    assert share.shape == (45,)  # 37 prefilled in chunks of 16 (the last padded), 8 decoded


def test_the_check_runs_what_the_measured_window_runs_at_its_shapes(tiny, monkeypatch):
    """The comparison goes through the scheduler's calls: ``prefill_rows``
    at the chunk programs' widest window, the prompt's chunk in the last
    slot of a state of ``max_len`` rows a slot beside a pad row, then
    ``decode_step`` over every slot at the widest decode window."""
    arch, cfg, params, tokens = tiny
    from generativeaiexamples_tpu.engine.serving_models import HybridServing

    seen = []
    rows_of, step = HybridServing.prefill_rows, HybridServing.decode_step

    def rows(self, params, cache, tokens, start, suffix_len, slots, window):
        seen.append(("rows", tokens.shape, cache[0]["k"].shape[:2], window))
        return rows_of(self, params, cache, tokens, start, suffix_len, slots, window)

    def one(self, params, cache, tokens, lengths, counts, window):
        seen.append(("step", tokens.shape, cache[0]["k"].shape[:2], window))
        return step(self, params, cache, tokens, lengths, counts, window)

    monkeypatch.setattr(HybridServing, "prefill_rows", rows)
    monkeypatch.setattr(HybridServing, "decode_step", one)
    arch._programs.cache_clear()  # traced anew, through the two above
    try:
        arch.logit_shares(params, cfg, tokens, 64)
    finally:
        arch._programs.cache_clear()
    T = cfg.max_seq_len
    assert set(seen) == {("rows", (2, 16), (2, T), T), ("step", (2,), (2, T), T)}


@pytest.mark.parametrize("control, stand_in", [
    ("no_value_shift", lambda now, late: np.concatenate([now, late], axis=-1)),
    ("no_router_average", lambda rho, prev, gamma: rho),
], ids=["no_value_shift", "no_router_average"])
def test_a_mechanism_left_out_of_the_reference_is_handed_on_as_no_agreement(tiny, control, stand_in, monkeypatch, capsys):
    """Two of the six controls (``tests/test_zaya_model.py`` holds all six
    to the program's logits; ``chip_smoke.py --hybrid --model zaya`` runs
    them on the chip): the reference without the mechanism, and what
    ``last_logits`` does with such readings."""
    import jax
    import jax.numpy as jnp

    arch, cfg, params, tokens = tiny
    name = {"no_value_shift": "_shift_values", "no_router_average": "_router_average"}[control]
    if control == "no_value_shift":
        stand_in = lambda now, late: jnp.concatenate([now, late], axis=-1)
    monkeypatch.setattr(arch.zaya_reference, name, stand_in)
    jax.clear_caches()
    try:
        got = arch.last_logits(params, cfg, tokens, 64)
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["outside"] and set(line["outside"]) <= {"p10", "p50", "p90", "decode_p50"}
    # One entry more than the vocabulary and the maximum there: no served token agrees.
    assert got.shape == (cfg.vocab_size + 1,) and got.argmax() == cfg.vocab_size
