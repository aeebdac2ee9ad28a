"""``arch/nemotron_h.py``: the mapping at both sizes, the file against the
catalog's row, the configuration's golden ``dataclasses.asdict`` (and that
the other families' did not move), the counts against the table of the
configuration's cut worked by hand, the two new counter readers on canned
counters, the benchmark's copy of the reference against the program's, and
the logit-level comparison behind ``last_logits`` (sound, through the calls
the measured window makes, and with a mechanism left out of the
reference)."""

import dataclasses
import json
from pathlib import Path

import numpy as np
import pytest

import run
from metrics_lib import load_reader

BENCH = Path(__file__).resolve().parents[1]
REPO = BENCH.parent
NAME = "nemotron-3-super-120b-a12b-l11e128"
CELL = f"{NAME}.reason-closed"
KEXAONE = "k-exaone-236b-a23b-l5e16.reason-closed"
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")
CONTROLS = ("w8a8_mlp", "state_bf16", "no_conv", "no_d_skip", "norm_whole", "gate_after_norm",
            "relu_not_relu2", "no_routed_scale", "rope_on")
PATTERN = "MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEM*EMEMEMEME"


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
    assert Path(arch.__file__).name == "nemotron_h.py"
    cfg = arch.llama_config(model, engine)
    assert type(cfg).__name__ == "MambaConfig"
    assert cfg.layer_kinds == (("mamba", "experts"),) * 3 + (("mamba", "none"), ("full", "experts"), ("mamba", "experts"))
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    # What the build holds (ISSUE 44's table): 9.30 GB of weights, 268 MB
    # of K/V rows, 681 MB of S and tails of which a snapshot takes one slot's.
    assert line["weight_bytes"] == 9_296_336_384 and line["letters"] == "MEMEMEM*EME"
    assert line["state_bytes_full"] == 32 * 8192 * 1024 == 268_435_456 and line["state_bytes_window"] == 0
    assert line["state_bytes_recurrent"] == 32 * 5 * (4_194_304 + 61_440) == 680_919_040
    assert line["snapshot_bytes"] == 21_278_720
    assert (line["params_mamba"], line["params_attention"]) == (109_640_064, 35_655_680)
    assert (line["params_experts_outside"], line["params_experts_held"]) == (54_530_560, 704_643_072)
    assert line["params_embedding"] == line["params_head"] == 32768 * 4096
    tiny_model, tiny_engine = sizes(True)
    tiny = arch.llama_config(tiny_model, tiny_engine)
    # The ratios at a size a CPU runs: all three kinds, a mixer-only pair.
    assert tiny.layer_kinds == (("mamba", "experts"), ("mamba", "none"), ("full", "experts"), ("mamba", "none"))
    assert (tiny.mamba_heads, tiny.mamba_head_dim, tiny.mamba_groups, tiny.ssm_state, tiny.ssm_block) == (8, 8, 2, 16, 8)
    assert (tiny.n_heads, tiny.n_kv_heads, tiny.moe_latent, tiny.d_model) == (4, 2, 16, 32)
    assert (tiny.n_experts, tiny.experts_held, tiny.n_experts_per_tok, tiny.vocab_size) == (8, 4, 3, 512)
    assert (tiny.dtype, tiny.kv_dtype, tiny.max_seq_len) == ("float32", "float32", 8192)
    with pytest.raises(ValueError, match="experts_held"):
        arch.llama_config(model, {**engine, "experts_held": 64})
    with pytest.raises(ValueError, match="bf16"):
        arch.llama_config(model, {**engine, "weight_dtype": "int8"})


def test_the_configuration_is_the_golden_one():
    """``dataclasses.asdict`` of what the program is handed, field by
    field: a later change to the mapping or to a default shows here."""
    model, engine = sizes(False)
    cfg = run.load_arch(model).llama_config(model, engine)
    # ``rope.NO_ROPE``: the attention layers are not rotated.
    rope = {"theta": 1.0, "rope_type": "none", "factor": 1.0, "original_max": 0, "beta_fast": 32.0,
            "beta_slow": 1.0, "attention_factor": 1.0, "truncate": True}
    assert dataclasses.asdict(cfg) == {
        "vocab_size": 32768, "d_model": 4096,
        "layer_kinds": (("mamba", "experts"),) * 3 + (("mamba", "none"), ("full", "experts"), ("mamba", "experts")),
        "n_heads": 32,
        # Ling's KDA and MLA sizes at their defaults: no layer here reads them.
        "kda_head_dim": 128, "conv_kernel": 4, "kda_gate_floor": -5.0, "kv_lora_rank": 512,
        "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "v_head_dim": 128, "rope_theta": 6e6,
        "d_ff": 2688, "moe_d_ff": 2688, "shared_d_ff": 5376, "n_experts": 512, "experts_held": 128,
        "expert_offset": 0, "n_experts_per_tok": 22, "n_group": 1, "topk_group": 1, "routed_scaling": 5.0,
        "norm_topk": True, "norm_eps": 1e-5, "max_seq_len": 8192, "dtype": "bfloat16", "kv_dtype": "bfloat16",
        "score_function": "sigmoid", "router_bias": True, "n_kv_heads": 2, "attn_head_dim": 128,
        "rope_full": rope, "mamba_heads": 128, "mamba_head_dim": 64, "mamba_groups": 8, "ssm_state": 128,
        "ssm_block": 128, "dt_init": (0.001, 0.1, 0.0001), "moe_latent": 1024, "expert_act": "relu2",
    }


@pytest.mark.parametrize("preset, fields, held", [
    ("ling-3.0-flash-vl-l7e128", 27, {"n_experts": 512, "experts_held": 128, "n_experts_per_tok": 8}),
    ("mellum2-12b-a2.5b-l12", 36, {"n_experts": 64, "sliding_window": 1024, "qk_norm": False}),
    ("k-exaone-236b-a23b-l5e16", 36, {"n_experts": 128, "experts_held": 16, "mtp_layers": 1}),
    ("mistral-small-4-119b-l6e32", 36, {"q_lora_rank": 1024, "latent_block": 1024, "experts_held": 32}),
    ("zaya1-8b-l20", 37, {"router_hidden": 256, "tie_embeddings": True, "conv_time0": 2}),
])
def test_the_other_families_configurations_did_not_move(preset, fields, held):
    """The new family's fields are its subclass's; on ``HybridConfig`` they
    are constants, so the other families' ``dataclasses.asdict`` (held
    field by field in their own files here) keep their fields."""
    from generativeaiexamples_tpu.models import hybrid

    got = dataclasses.asdict(hybrid.PRESETS[preset]())
    assert len(got) == fields, sorted(got)
    assert not {"moe_latent", "expert_act", "mamba_heads", "ssm_state", "dt_init"} & set(got)
    assert {k: got[k] for k in held} == held
    cfg = hybrid.PRESETS[preset]()
    assert (cfg.moe_latent, cfg.expert_act) == (0, "swiglu")


def test_the_file_keeps_every_published_width_and_lists_its_cut():
    model, engine = sizes(False)
    assert model["reduced"] == ["num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert model["reduced_from"] == {"num_hidden_layers": 88, "n_routed_experts": 512, "vocab_size": 131072}
    assert model["arch"] == "nemotron_h" and model["hybrid_override_pattern"] == PATTERN and len(PATTERN) == 88
    assert (model["num_hidden_layers"], model["n_routed_experts"], model["num_experts"],
            model["num_experts_published"], model["vocab_size"]) == (11, 128, 128, 512, 32768)
    # Every published width, unchanged.
    assert (model["hidden_size"], model["mamba_num_heads"], model["mamba_head_dim"], model["n_groups"],
            model["ssm_state_size"], model["conv_kernel"], model["chunk_size"]) == (4096, 128, 64, 8, 128, 4, 128)
    assert (model["num_attention_heads"], model["num_key_value_heads"], model["head_dim"]) == (32, 2, 128)
    assert (model["moe_latent_size"], model["moe_intermediate_size"], model["moe_shared_expert_intermediate_size"],
            model["num_experts_per_tok"], model["routed_scaling_factor"]) == (1024, 2688, 5376, 22, 5)
    assumed = " ".join(model["assumed"])
    assert len(model["assumed"]) >= 8
    for needle in ("NO rotary embedding", "rope_theta 10,000", "(z 8,192 ; xBC 10,240 ; dt 128)", "not clamped",
                   "gates BEFORE it norms", "1,024 channels", "full hidden state", "neither bias nor norm",
                   "relu2", "moe_shared_expert_overlap", "log of uniform(1, 16)", "arXiv:2405.21060",
                   "arXiv:2504.03624"):
        assert needle in assumed, needle
    unserved = " ".join(model["not_served"])
    for needle in ("multi-token-prediction", "rolled back", "int8", "more than one device"):
        assert needle in unserved, needle
    for needle in ("FIRST of EIGHT pipeline stages", "FOUR chips", "layers 0-10", "MEMEMEM*EME", "9.30 GB",
                   "219.3 MB", "1,518 MB", "71.3 MB", "0.95 GB", "21,278,720 B", "60.6 %", "one in 89",
                   "a quarter of the rows"):
        assert needle in model["stands_for"], needle
    assert engine == {**engine, "weight_dtype": "bfloat16", "kv_dtype": "bfloat16", "max_batch": 32,
                      "max_len": 8192, "decode_chunk_size": 8, "prefill_chunk_tokens": 256,
                      "prefix_cache": "shared", "kv_layout": "contiguous", "matmul_kernel": "xla",
                      "experts_held": 128, "expert_offset": 0}
    assert 1 <= engine["roofline_decode_rows"] <= 32
    assert model["expect_paths"]["moe_experts"] == "pallas"
    ref = model["reference"]
    assert (ref["prompts"], ref["min_within"], ref["decode_positions"]) == (12, 9, 16)
    assert set(ref["logit_share_limits"]) == {"p10", "p50", "p90", "decode_p50"}
    for control in CONTROLS:
        assert control in ref["why"], control
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    entry = next(c for c in spec["configs"] if c["name"] == NAME)
    assert entry == spec["configs"][-1]  # appended
    assert entry["reduced"] == model["reduced"] and entry["source"] == model["source"]
    assert entry["file"] == f"benchmarks/configs/{NAME}.json"
    if CATALOG.exists():
        row = next(json.loads(l) for l in open(CATALOG) if '"name": "NVIDIA-Nemotron-3-Super-120B-A12B-BF16"' in l)
        assert model["source"] == row["source_url"]
        for key, value in row["config"].items():
            if key not in model["reduced"]:
                assert model[key] == value, key
    rehearse = model["rehearse"]["model"]
    assert rehearse["hybrid_override_pattern"] == "MEM*EM" and rehearse["num_hidden_layers"] == 6
    assert (rehearse["mamba_num_heads"] * rehearse["mamba_head_dim"]) == 2 * rehearse["hidden_size"]


def test_parameter_counts_are_the_issues_table():
    model, _ = sizes(False)
    arch = run.load_arch(model)
    p = arch.part_params(model)
    # By hand, from the published widths (ISSUE 44): W_in 4,096 x 18,560, W_out 8,192 x 4,096.
    assert p["mamba_proj"] == 76_021_760 + 33_554_432
    # the convolution (5 x 10,240), A_log, dt_bias, D (3 x 128), the norm's gain (8,192)
    assert p["mamba_rest"] == 51_200 + 384 + 8_192 == 59_776
    assert p["attention"] == 4096 * 36 * 128 + 4096 * 4096 == 35_651_584
    # the router 2.10 M and its bias, the latent pair 8.39 M, the shared expert 44.04 M
    assert p["experts_outside"] == 2_097_152 + 512 + 8_388_608 + 44_040_192 == 54_526_464
    assert p["expert"] == 2 * 1024 * 2688 == 5_505_024 and p["head"] == 4096 * 32768
    by_letter = arch.layer_params(model)
    assert by_letter == {"M": 109_640_064, "*": 35_655_680, "E": 54_530_560}
    assert round(by_letter["M"] * 2 / 1e6, 1) == 219.3 and round(by_letter["*"] * 2 / 1e6, 1) == 71.3
    e_layer = by_letter["E"] + 128 * p["expert"]
    assert round(e_layer / 1e6, 1) == 759.2 and round(e_layer * 2 / 1e6) == 1518
    assert arch.letters(model) == "MEMEMEM*EME"
    total = 5 * by_letter["M"] + by_letter["*"] + 5 * e_layer + 2 * p["head"] + 4096  # and the final norm
    # bf16, but for A_log, dt_bias, D (3 x 128 a mixer) and the selection bias (512 a router): float32.
    assert total * 2 + 2 * 5 * (384 + 512) == 9_296_336_384 and round(total * 2 / 1e9, 2) == 9.30


def test_decode_step_bytes_by_hand():
    model, engine = sizes(False)
    arch = run.load_arch(model)
    p, by_letter = arch.part_params(model), arch.layer_params(model)
    assert arch.experts_touched(model, 1) == pytest.approx(128 * 22 / 512)  # 5.5 local choices a row
    assert arch.experts_touched(model, 30) == pytest.approx(128 * (1 - (490 / 512) ** 30))
    assert arch.experts_touched(model, 30) == pytest.approx(93.7, abs=0.1)  # 73 % of the 128 held
    assert arch.kv_bytes_per_row(model, engine) == 1024
    assert arch.ssm_state_bytes(model, engine) == 4_194_304 + 61_440
    rows, live = 30, 30 * 2000
    once = 5 * by_letter["M"] + by_letter["*"] + 5 * by_letter["E"] + p["head"]
    want = (2 * (once + 5 * arch.experts_touched(model, rows) * p["expert"])
            + 5 * rows * 2 * 4_255_744 + live * 1024)
    got = arch.decode_step_bytes(model, {**engine, "roofline_decode_rows": rows}, live)
    assert got == pytest.approx(want)
    # ISSUE 44's reckoning at 30 rows: the experts touched 5.2 GB, the M
    # layers' weights 1.1 and their state, read and written, 1.3, the head
    # 0.27: about 8.5 GB, 10.3 ms at the chip's 819 GB/s.
    assert round(2 * 5 * arch.experts_touched(model, 30) * p["expert"] / 1e9, 1) == 5.2
    assert round(2 * 5 * by_letter["M"] / 1e9, 1) == 1.1 and round(5 * 30 * 2 * 4_255_744 / 1e9, 2) == 1.28
    assert round(2 * p["head"] / 1e9, 2) == 0.27 and round(got / 1e9, 1) == 8.5
    assert got / 819e9 * 1e3 == pytest.approx(10.35, abs=0.01)
    assert arch.decode_step_bytes(model, {**engine, "roofline_decode_rows": 5}, live) < got


def test_prefill_flops_by_hand():
    model, _ = sizes(False)
    arch = run.load_arch(model)
    p = arch.part_params(model)
    scan = arch.scan_flops_per_token(model)
    # C.B a group (2 x 128 x 128 x 8), the block's sum (2 x 128 x 64 x 128), S C and the update (4 x 64 x 128 x 128).
    assert scan == 262_144 + 2_097_152 + 4_194_304
    m_layer = 2 * p["mamba_proj"] + scan
    e_layer = 2 * (p["experts_outside"] + 5.5 * p["expert"])
    assert round(m_layer / 1e6) == 226 and round(scan / 1e6) == 7
    assert round(e_layer / 1e6) == 170 and round(2 * 5.5 * p["expert"] / 1e6) == 61
    token = 5 * m_layer + 5 * e_layer + 2 * p["attention"]
    assert round(token / 1e9, 2) == 2.05
    pair = 4 * 32 * 128
    pairs = sum(i + 1 for i in range(1000, 1256))
    assert arch.prefill_flops(model, 256, pairs) == pytest.approx(token * 256 + pair * pairs)
    assert arch.prefill_flops(model, 0, 0) == 0


def test_the_two_new_counter_readers():
    """Between the markers: 60 decode chunks of 8 steps over 5 expert
    layers, each step's layer touching 90 of the 128 experts held with 165
    local choices; prefill programs of 2 blocks a row, a third of them
    padding."""
    model, engine = sizes(False)
    steps = 60 * 8 * 5
    counters = {"moe_experts_touched_decode": steps * 90, "moe_expert_layer_steps_decode": steps,
                "moe_choices_local_decode": steps * 165,
                "attn_rows_ssm_tokens_prefill": 5 * 40 * 2 * 128 * 2 // 3, "attn_rows_ssm_blocks_prefill": 5 * 40 * 2,
                "attn_rows_read_state_decode": 60 * 8 * 5 * 32, "attn_rows_dense_state_decode": 60 * 8 * 5 * 32}
    ctx = {"trace_counters": dict(counters), "counters": {}, "model": model, "engine": engine, "trace": None}
    rows, fill = load_reader("decode_rows_per_expert"), load_reader("prefill_ssm_block_fill_pct")
    assert rows(ctx) == pytest.approx(165 / 90) and fill(ctx) == pytest.approx(100 * 2 / 3, rel=1e-3)
    # The accepted readers the cell joins read the same counters here.
    assert load_reader("decode_experts_touched_pct")(ctx) == pytest.approx(100 * 90 / 128)
    assert load_reader("decode_state_rows_pct")(ctx) == pytest.approx(100.0)
    for read in (rows, fill):
        assert read({**ctx, "trace_counters": None}) is None  # --trace 0
        # A program without the counters (the parent): nothing to read, no error.
        assert read({**ctx, "trace_counters": {"busy_ticks": 3, "moe_experts_touched": 5}}) is None
    assert rows({**ctx, "trace_counters": {**counters, "moe_experts_touched_decode": 0}}) is None
    assert fill({**ctx, "trace_counters": {**counters, "attn_rows_ssm_blocks_prefill": 0}}) is None
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    assert [m["name"] for m in spec["per_layer"][-2:]] == ["decode_rows_per_expert", "prefill_ssm_block_fill_pct"]
    for entry, unit in zip(spec["per_layer"][-2:], ("ratio", "%")):
        assert entry == {"name": entry["name"], "unit": unit, "better": "higher", "source": "program_counter",
                         "layer": "step programs", "moves": "itl_p95_ms", "workloads": [CELL]}


def test_the_cell_is_listed_where_the_issue_says():
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    cell = spec["workloads"][-1]
    assert cell == {**cell, "name": CELL, "config": NAME, "traffic": "reason-closed", "chips": 1}
    assert len(cell["why"]) <= 200
    judged = {m["name"] for m in spec["end_to_end"] if CELL in m.get("workloads", [CELL])}
    assert {"itl_p95_ms", "setup_s"} <= judged <= {"itl_p95_ms", "setup_s", "out_tok_s"}
    by_name = {m["name"]: m for m in spec["per_layer"]}
    listed = {name for name, m in by_name.items() if CELL in m.get("workloads", [CELL])}
    theirs = {name for name, m in by_name.items() if KEXAONE in m.get("workloads", [KEXAONE])}
    new = {"decode_rows_per_expert", "prefill_ssm_block_fill_pct", "decode_experts_touched_pct",
           "decode_state_rows_pct"}
    itl_twins = {"out_tok_s_closed", "decode_lanes_mean.itl", "decode_hbm_pct.itl", "device_idle_pct.itl"}
    out_side = {"decode_lanes_mean", "decode_hbm_pct", "device_idle_pct.closed", "expert_local_pct"}
    # Nothing here drafts and no layer has a window.
    never = {"decode_window_rows_pct", "draft_accept_pct", "verify_positions_per_token"}
    if "out_tok_s" in judged:  # listed as K-EXAONE's cell is
        assert listed - theirs == new and theirs - listed == never
    else:  # listed as ZAYA's is: what moves ``out_tok_s`` under its twin that moves ``itl_p95_ms``
        assert listed - theirs == new | itl_twins and theirs - listed == never | out_side
    assert all(by_name[name]["moves"] in judged for name in listed)
    for m in spec["end_to_end"] + spec["per_layer"]:
        if CELL in m.get("workloads", []):
            assert m["workloads"][-1] == CELL  # appended at the end of each list


def test_the_benchmarks_reference_is_the_programs_file():
    ours = (BENCH / "nemotron_h_reference.py").read_text()
    theirs = (REPO / "generativeaiexamples_tpu" / "models" / "nemotron_h_reference.py").read_text()
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
    cfg = hybrid.PRESETS["nemotron_h-tiny"]()
    arch._CHECK.update(limits={"p10": 1e-3, "p50": 1e-3, "p90": 1e-3, "decode_p50": 1e-3}, decode=8, chunk=16)
    params = hybrid.init_params(cfg, jax.random.PRNGKey(0))
    tokens = np.random.RandomState(0).randint(1, cfg.vocab_size, size=45).tolist()
    return arch, cfg, params, tokens


def test_last_logits_hands_on_the_references_when_the_program_agrees(tiny, capsys):
    arch, cfg, params, tokens = tiny
    from generativeaiexamples_tpu.models import nemotron_h_reference

    got = arch.last_logits(params, cfg, tokens, 64)
    want = np.asarray(nemotron_h_reference.all_logits(params, cfg, tokens))[-1]
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
        seen.append(("rows", tokens.shape, cache[2]["k"].shape[:2], cache[0]["ssm"].shape[:2], window))
        return rows_of(self, params, cache, tokens, start, suffix_len, slots, window)

    def one(self, params, cache, tokens, lengths, counts, window):
        seen.append(("step", tokens.shape, cache[2]["k"].shape[:2], cache[0]["ssm"].shape[:2], window))
        return step(self, params, cache, tokens, lengths, counts, window)

    monkeypatch.setattr(HybridServing, "prefill_rows", rows)
    monkeypatch.setattr(HybridServing, "decode_step", one)
    arch._programs.cache_clear()  # traced anew, through the two above
    try:
        arch.logit_shares(params, cfg, tokens, 64)
    finally:
        arch._programs.cache_clear()
    T = cfg.max_seq_len
    assert set(seen) == {("rows", (2, 16), (2, T), (2, 8), T), ("step", (2,), (2, T), (2, 8), T)}


@pytest.mark.parametrize("control", ["no_d_skip", "state_bf16"])
def test_a_mechanism_left_out_of_the_reference_is_handed_on_as_no_agreement(tiny, control, monkeypatch, capsys):
    """Two of the nine controls (``tests/test_nemotron_h_model.py`` holds
    all nine to the program's logits; ``chip_smoke.py --hybrid --model
    nemotron_h`` runs them on the chip): the reference without the
    mechanism, and what ``last_logits`` does with such readings."""
    import jax
    import jax.numpy as jnp

    arch, cfg, params, tokens = tiny
    name, stand_in = {
        "no_d_skip": ("_skip", lambda y, d, xs: y),
        "state_bf16": ("_keep", lambda state: jax.lax.reduce_precision(state, exponent_bits=8, mantissa_bits=7)),
    }[control]
    monkeypatch.setattr(arch.nemotron_h_reference, name, stand_in)
    if control == "state_bf16":  # a rounding a token reads 1e-4 at 45 tokens: limits to match
        monkeypatch.setitem(arch._CHECK, "limits", {"p10": 1e-5, "p50": 1e-5, "p90": 1e-5, "decode_p50": 1e-5})
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
