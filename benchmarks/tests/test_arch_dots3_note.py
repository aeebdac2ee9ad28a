"""``arch/dots3_note.py``: the mapping at both sizes, the file against the
catalog's row, the configuration's golden ``dataclasses.asdict`` (and that
the other families' did not move), the counts against the table of the
configuration's cut worked by hand, the traffic mix's lengths and what its
warm-up drives, the new counter readers on canned counters, the
benchmark's copy of the reference against the program's, and the
comparison behind ``last_logits`` (sound, and with the selection changed)."""

import dataclasses
import json
from pathlib import Path

import numpy as np
import pytest

import run
import traffic
from metrics_lib import load_reader

BENCH = Path(__file__).resolve().parents[1]
REPO = BENCH.parent
NAME = "dots3-note-prev-l6e32"
CELL = f"{NAME}.doc-mid-closed"
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")


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
    assert Path(arch.__file__).name == "dots3_note.py"
    cfg = arch.llama_config(model, engine)
    assert type(cfg).__name__ == "IndexedLatentConfig"
    assert cfg.layer_kinds == (
        ("mla", "dense"), ("mla", "experts"), ("mla_window", "experts"), ("mla_window", "experts"),
        ("mla_window", "experts"), ("mla", "experts"))
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    # What the build holds: 10.02 GB of weights, 1.21 GB of latent rows and
    # index keys, 0.06 GB of rings: 1.27 GB of state.
    assert line["weight_bytes"] == 10_022_188_544
    assert line["state_bytes_full"] == 1_207_959_552 and line["state_bytes_window"] == 56_733_696
    assert line["state_bytes_full"] + line["state_bytes_window"] == pytest.approx(1.265e9, rel=1e-3)
    assert (line["latent_row_bytes_used"], line["latent_row_bytes_stored"], line["index_key_bytes"]) == (1152, 1280, 256)
    assert line["snapshot_bytes"] == 3_545_856
    tiny_model, tiny_engine = sizes(True)
    tiny = arch.llama_config(tiny_model, tiny_engine)
    # One layer a kind at a size a CPU prefills 100k tokens of in seconds;
    # the window as published, the selection from 8,192 rows on (at 2,048
    # ``select_mask`` alone takes a CPU 23 s of the mix's sixteen prompts).
    assert tiny.layer_kinds == (("mla", "dense"), ("mla_window", "experts")) and tiny.max_seq_len == 16384
    assert (tiny.index_topk, tiny.sliding_window, tiny.n_experts, tiny.experts_held) == (8192, 513, 16, 4)
    assert tiny.latent_sizes("mla") != tiny.latent_sizes("mla_window")
    assert (tiny.dtype, tiny.kv_dtype) == ("float32", "float32")
    with pytest.raises(ValueError, match="experts_held"):
        arch.llama_config(model, {**engine, "experts_held": 16})


def test_the_configuration_is_what_the_program_is_handed():
    """``dataclasses.asdict`` of what the program is handed, field by
    field."""
    model, engine = sizes(False)
    cfg = run.load_arch(model).llama_config(model, engine)
    kinds = [["mla", "dense"], ["mla", "experts"], ["mla_window", "experts"], ["mla_window", "experts"],
             ["mla_window", "experts"], ["mla", "experts"]]
    got = json.loads(json.dumps(dataclasses.asdict(cfg)))
    assert got == {
        "vocab_size": 19008, "d_model": 5120, "layer_kinds": kinds, "n_heads": 128,
        "kda_head_dim": 128, "conv_kernel": 4, "kda_gate_floor": -5.0,
        "kv_lora_rank": 512, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "v_head_dim": 128,
        "rope_theta": 80000000.0, "d_ff": 13824, "moe_d_ff": 1536, "shared_d_ff": 1536,
        "n_experts": 256, "experts_held": 32, "expert_offset": 0, "n_experts_per_tok": 8,
        "n_group": 1, "topk_group": 1, "routed_scaling": 1.0, "norm_topk": True, "norm_eps": 1e-05,
        "max_seq_len": 16384, "dtype": "bfloat16", "kv_dtype": "bfloat16",
        "score_function": "sigmoid", "router_bias": True, "q_lora_rank": 1024, "rope_latent": None,
        "attn_scale_beta": 0.0, "softmax_mscale": 1.0, "mla_out_gate": True,
        "latent_block": 1024, "latent_decode_block": 2048,
        "index_n_heads": 64, "index_head_dim": 128, "index_topk": 2048, "latent_rescale": True,
        "sliding_window": 513,
        "window_latent": {"n_heads": 64, "q_lora_rank": 1024, "kv_lora_rank": 1024,
                          "qk_nope_head_dim": 192, "qk_rope_head_dim": 64, "v_head_dim": 128,
                          "rope_theta": 50000.0},
    }


@pytest.mark.parametrize("preset, fields, held", [
    ("ling-3.0-flash-vl-l7e128", 27, {"n_experts": 512, "experts_held": 128, "n_experts_per_tok": 8}),
    ("mellum2-12b-a2.5b-l12", 36, {"n_experts": 64, "sliding_window": 1024, "qk_norm": False}),
    ("k-exaone-236b-a23b-l5e16", 36, {"n_experts": 128, "experts_held": 16, "mtp_layers": 1}),
    ("mistral-small-4-119b-l6e32", 36, {"q_lora_rank": 1024, "latent_block": 1024, "experts_held": 32}),
    ("zaya1-8b-l20", 37, {"router_hidden": 256, "tie_embeddings": True, "conv_time0": 2}),
    ("nemotron-3-super-120b-a12b-l11e128", 40, {"moe_latent": 1024, "mamba_heads": 128, "experts_held": 128}),
])
def test_the_other_families_configurations_did_not_move(preset, fields, held):
    """The new family's fields are its subclass's; on ``HybridConfig`` they
    are constants, so the other families' ``dataclasses.asdict`` (held
    field by field in their own files here) keep their fields."""
    from generativeaiexamples_tpu.models import hybrid

    cfg = hybrid.PRESETS[preset]()
    got = dataclasses.asdict(cfg)
    assert len(got) == fields, sorted(got)
    assert not {"index_topk", "index_n_heads", "latent_rescale", "window_latent"} & set(got)
    assert {k: got[k] for k in held} == held
    assert (cfg.index_topk, cfg.latent_rescale, cfg.window_latent) == (0, False, None)


def test_the_file_keeps_every_published_width_and_lists_its_cut():
    model, engine = sizes(False)
    assert model["reduced"] == ["num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert model["reduced_from"] == {"num_hidden_layers": 46, "n_routed_experts": 256, "vocab_size": 152064}
    assert model["arch"] == "dots3_note" and model["model_type"] == "dots3_note"
    assert (model["num_hidden_layers"], model["n_routed_experts"], model["num_experts"],
            model["num_experts_published"], model["vocab_size"]) == (6, 32, 32, 256, 19008)
    assert len(model["layer_types"]) == 46 and model["layer_types"].count("full_attention") == 13
    assert model["layer_types"][:6] == ["full_attention"] * 2 + ["sliding_attention"] * 3 + ["full_attention"]
    # Every published width, unchanged.
    assert (model["hidden_size"], model["num_attention_heads"], model["qk_nope_head_dim"],
            model["qk_rope_head_dim"], model["v_head_dim"], model["q_lora_rank"], model["kv_lora_rank"]) == (
        5120, 128, 128, 64, 128, 1024, 512)
    assert (model["index_n_heads"], model["index_head_dim"], model["index_topk"]) == (64, 128, 2048)
    assert (model["swa_num_attention_heads"], model["swa_qk_nope_head_dim"], model["swa_qk_rope_head_dim"],
            model["swa_v_head_dim"], model["swa_q_lora_rank"], model["swa_kv_lora_rank"],
            model["sliding_window_size"]) == (64, 192, 64, 128, 1024, 1024, 513)
    assert (model["intermediate_size"], model["moe_intermediate_size"], model["num_experts_per_tok"],
            model["n_shared_experts"], model["rope_theta"], model["swa_rope_theta"]) == (
        13824, 1536, 8, 1, 80000000, 50000)
    assumed = " ".join(model["assumed"])
    assert len(model["assumed"]) >= 7 and len(model["not_served"]) == 2
    for needle in ("(hidden_size / q_lora_rank)^1/2", "headwise", "Hadamard", "FP8", "full layers only",
                   "n_group = topk_group = 1", "no mscale", "LayerNorm"):
        assert needle in assumed, needle
    assert "vision tower" in model["not_served"][0] and "prediction module" in model["not_served"][0]
    for needle in ("EIGHT chips", "64 chips", "10.02 GB", "1.208 GB", "3.55 MB", "67 %", "13 : 33", "eight pipeline stages"):
        assert needle in model["stands_for"], needle
    assert engine == {**engine, "weight_dtype": "bfloat16", "kv_dtype": "bfloat16", "max_batch": 16,
                      "max_len": 16384, "decode_chunk_size": 8, "prefill_chunk_tokens": 256,
                      "prefix_cache": "shared", "kv_layout": "contiguous", "experts_held": 32,
                      "expert_offset": 0}
    assert model["expect_paths"] == {"moe_experts": "pallas", "index_scores": "xla",
                                     "attn_latent_sparse": "xla", "attn_latent_ring": "xla"}
    ref = model["reference"]
    assert (ref["prompts"], ref["min_within"], ref["decode_positions"]) == (4, 3, 16)
    assert set(ref["logit_share_limits"]) == {"p10", "p50", "p90", "decode_p50"}
    assert 0.5 < ref["index_overlap_floor"] < 1.0
    for control in ("w8a8_mlp", "no_selection", "last_2048", "no_index_relu", "no_rescale", "no_gate",
                    "window_512", "full_sizes_in_window"):
        assert control in ref["why"], control
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    entry = next(c for c in spec["configs"] if c["name"] == NAME)
    assert entry["reduced"] == model["reduced"] and entry["source"] == model["source"]
    assert entry["file"] == f"benchmarks/configs/{NAME}.json"
    if CATALOG.exists():
        row = next(json.loads(l) for l in open(CATALOG) if '"name": "dots3-note-prev"' in l)
        assert model["source"] == row["source_url"]
        for key, value in row["config"].items():
            if key not in model["reduced"]:
                assert model[key] == value, key
    widths = ("hidden_size", "intermediate_size", "moe_intermediate_size", "q_lora_rank", "kv_lora_rank",
              "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "index_head_dim", "index_n_heads",
              "index_topk", "num_experts_per_tok", "num_attention_heads", "sliding_window_size")
    assert not set(widths) & set(model["reduced"])


def test_parameter_counts_are_the_issues_table():
    model, _ = sizes(False)
    arch = run.load_arch(model)
    p = arch.part_params(model)
    assert p == {"full": 134_676_480, "sliding": 90_832_896, "indexer": 9_371_648, "dense": 212_336_640,
                 "router": 1_310_720, "expert": 23_592_960, "shared": 23_592_960, "head": 97_320_960}
    assert arch.layer_counts(model) == {"full": 3, "sliding": 3, "dense": 1, "experts": 5}
    # Layers 1 and 5: 923.93 M; layers 2-4: 870.71 M; layer 0: 356.39 M.
    assert p["full"] + p["indexer"] + p["router"] + p["shared"] + 32 * p["expert"] == pytest.approx(923.93e6, rel=1e-4)
    assert p["sliding"] + p["router"] + p["shared"] + 32 * p["expert"] == pytest.approx(870.71e6, rel=1e-4)
    assert p["full"] + p["indexer"] + p["dense"] == pytest.approx(356.39e6, rel=1e-4)
    assert arch.row_bytes(model, model["engine"]) == {"full": 1152.0, "index": 256.0, "sliding": 2176.0}


def test_decode_step_bytes_by_hand():
    model, engine = sizes(False)
    arch = run.load_arch(model)
    p = arch.part_params(model)
    rows = float(engine["roofline_decode_rows"])
    once = 3 * (p["full"] + p["indexer"]) + 3 * p["sliding"] + p["dense"] + 5 * (p["router"] + p["shared"]) + p["head"]
    touched = 5 * 32 * (1 - (1 - 8 / 256) ** rows) * p["expert"]
    live = rows * 7000.0  # rows of 7,000 tokens: past index_topk and the window
    state = 3 * (live * 256 + rows * 2048 * 1152) + 3 * rows * 513 * 2176
    assert arch.decode_step_bytes(model, engine, live) == pytest.approx(2 * (once + touched) + state)
    # A slot that holds fewer rows than the selection keeps reads them all.
    short = rows * 1000.0
    state = 3 * (short * 256 + short * 1152) + 3 * rows * 513 * 2176
    assert arch.decode_step_bytes(model, engine, short) == pytest.approx(2 * (once + touched) + state)
    # Reading every held row instead would be 3 x 7,000 x 1,152 B a row.
    assert 3 * (7000 * 256 + 2048 * 1152) < 0.5 * 3 * 7000 * 1152 * 1.2


def test_prefill_flops_by_hand():
    model, _ = sizes(False)
    arch = run.load_arch(model)
    p = arch.part_params(model)
    active = (3 * (p["full"] + p["indexer"]) + 3 * p["sliding"] + p["dense"]
              + 5 * (p["router"] + p["shared"] + 1.0 * p["expert"]))
    # One prompt of 6,400 tokens from 0: causal pairs n (n + 1) / 2.
    n = 6400.0
    pairs = n * (n + 1) / 2
    kept = 2048 * 2049 / 2 + (n - 2048) * 2048
    ring = 513 * 514 / 2 + (n - 513) * 513
    want = (2 * active * n + 3 * (2 * 64 * 128 * pairs + 2 * 128 * 320 * kept) + 3 * 2 * 64 * 384 * ring)
    assert arch.prefill_flops(model, n, pairs) == pytest.approx(want, rel=1e-6)
    # A prompt no longer than the selection (and a chunk of it) attends every pair.
    short = 1500.0
    got = arch.prefill_flops(model, short, short * (short + 1) / 2)
    ring = 513 * 514 / 2 + (short - 513) * 513
    assert got == pytest.approx(
        2 * active * short + 3 * (2 * 64 * 128 + 2 * 128 * 320) * short * (short + 1) / 2 + 3 * 2 * 64 * 384 * ring,
        rel=1e-6)
    assert arch.prefill_flops(model, 0.0, 0.0) == 0.0
    # A token of prefill at the mix's mean: the products are ~2.3 GFLOP.
    assert 2 * active / 1e9 == pytest.approx(2.33, abs=0.05)


def test_the_mix_holds_the_issues_parameters_and_lengths():
    mix = traffic.load_mix("doc-mid-closed")
    assert mix["arrivals"] == {"loop": "closed", "clients": 16}
    assert traffic.load_mix("doc-mid")["arrivals"] == {"loop": "open"}
    assert mix["prefix_tokens"] == 256 and "docs" not in mix and "reask_share" not in mix
    assert mix["unique"] == {"dist": "lognormal", "median": 6144, "sigma": 0.5, "lo": 2560, "hi": 14080}
    assert mix["max_tokens"] == {"dist": "uniform", "lo": 64, "hi": 256}
    assert (mix["temperature"], mix["top_p"], mix["max_total"]) == (0.2, 0.7, 14592)
    assert (mix["spec_requests"], mix["reference_len"]) == (240, [4352, 4864])
    others = {traffic.load_mix(m)["shape_seed"] for m in (
        "rag-closed", "rag-long-closed", "reason-closed", "chat-closed", "doc-long-closed")}
    assert mix["shape_seed"] not in others  # its own
    shapes = traffic.request_shapes(mix)
    prompts = 256 + shapes["unique"]
    assert prompts.min() == 2816 and prompts.max() == 14336 and 6_000 < np.median(prompts) < 6_800
    assert (prompts > 2048).all()  # the selection cuts in every prompt
    assert (prompts + shapes["max_tokens"]).max() <= 14592 < 16384 - 8  # the scheduler's admit limit
    # Every reference prompt is past twice index_topk: under half of the rows kept at its end.
    assert mix["reference_len"][0] - 16 > 2 * 2048 and mix["reference_len"][1] % 256 == 0
    need = int(mix["supply_rps"] * 45) + 16 + 8
    requests = traffic.generate(mix, 2**31 + 5, 19008, need)
    assert len(requests) == need and all(r["prompt"][:256] == requests[0]["prompt"][:256] for r in requests)
    assert max(max(r["prompt"]) for r in requests[:8]) < 19008 and min(min(r["prompt"]) for r in requests[:8]) >= 256


def test_the_warm_up_drives_every_program_shape_of_the_mix():
    from generativeaiexamples_tpu.utils.buckets import bucket_size

    mix = traffic.load_mix("doc-mid-closed")
    windows, grafts, cold = set(), set(), False
    first = mix["warmup"][0]["requests"][0]
    for burst in mix["warmup"]:
        for r in burst["requests"]:
            assert r["shared"] <= 4096
            plen = r["shared"] + r["fresh"]
            assert plen + r["max_tokens"] <= 14592
            if r["shared"] and r is not first:
                grafts.add(bucket_size(r["shared"], minimum=16, dense=True))
            cold |= r["shared"] == 0
            if r["max_tokens"] > 1:
                windows |= {bucket_size(plen + n + 8 + 1, maximum=16384) for n in (0, r["max_tokens"])}
    shapes = traffic.request_shapes(mix)
    need = set()
    for unique, out in zip(shapes["unique"], shapes["max_tokens"]):
        need |= {bucket_size(256 + int(unique) + n + 8 + 1, maximum=16384) for n in (0, int(out))}
    assert need <= windows == {4096, 8192, 16384}
    assert grafts == {256} and cold
    assert max(len(b["requests"]) for b in mix["warmup"]) == 8  # a full group of chunk rows


COUNTERS = {
    "attn_rows_read_selected_prefill": 1150, "attn_rows_seen_latent_prefill": 1000,
    "attn_rows_read_index_decode": 16 * 8192, "attn_rows_read_selected_decode": 16 * 2048,
    "attn_rows_seen_latent_decode": 5 * 7000,
}
# (16 x 8,192 x 256 B + 16 x 2,048 x 1,280 B) / (5 x 7,000 x 1,280 B)
READERS = {"prefill_selected_rows_pct": 115.0,
           "decode_index_rows_pct": 100.0 * (16 * 8192 * 256 + 16 * 2048 * 1280) / (35000 * 1280)}
MOVES = {"prefill_selected_rows_pct": "ttft_p50_ms", "decode_index_rows_pct": "itl_p95_ms"}


@pytest.mark.parametrize("name", sorted(READERS))
def test_new_counter_readers(name):
    model, engine = sizes(False)
    read = load_reader(name)
    ctx = {"trace_counters": dict(COUNTERS), "counters": {}, "model": model, "engine": engine, "trace": None}
    assert read(ctx) == pytest.approx(READERS[name])
    assert read({**ctx, "trace_counters": None}) is None  # --trace 0
    zero = {**COUNTERS, "attn_rows_seen_latent_prefill": 0, "attn_rows_seen_latent_decode": 0}
    assert read({**ctx, "trace_counters": zero}) is None  # a window with no such program
    # A program without the counters (the parent): nothing to read, no error.
    assert read({**ctx, "trace_counters": {"busy_ticks": 3, "prefix_tokens_reused": 5}}) is None
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    (entry,) = [m for m in spec["per_layer"] if m["name"] == name]
    assert entry["workloads"] == [CELL] and entry["better"] == "lower" and entry["unit"] == "%"
    assert entry["layer"] == "step programs" and entry["source"] == "program_counter"
    assert entry["moves"] == MOVES[name]


def test_the_cell_is_listed_where_its_readers_find_something():
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    (cell,) = [w for w in spec["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (NAME, "doc-mid-closed", 1)
    assert spec["workloads"][-1] == cell and spec["configs"][-1]["name"] == NAME and len(cell["why"]) <= 200
    judged = {m["name"] for m in spec["end_to_end"] if CELL in m.get("workloads", [CELL])}
    assert judged == set(JUDGED)
    by_name = {m["name"]: m for m in spec["per_layer"]}
    listed = {name for name, m in by_name.items() if CELL in m.get("workloads", [CELL])}
    assert {"prefill_selected_rows_pct", "decode_index_rows_pct", "decode_latent_rows_pct",
            "prefill_latent_rows_pct", "prefill_window_rows_pct", "decode_window_rows_pct",
            "prefix_snapshot_loss_pct", "expert_load_max_over_mean", "expert_streams_per_touched",
            "prefill_mxu_pct", "prefix_reuse_pct", "decode_step_dev_ms", "prefill_rows_per_program",
            "setup_executables", "decode_experts_touched_pct", "out_tok_s_closed",
            "decode_lanes_mean.itl", "decode_hbm_pct.itl", "device_idle_pct.itl"} <= listed
    assert all(by_name[name]["moves"] in judged for name in listed)
    # What reads K/V rows, drafts or recurrent state has nothing to read here.
    assert not {"decode_full_rows_pct", "prefill_full_rows_pct", "draft_accept_pct",
                "decode_state_rows_pct", "prefill_ssm_block_fill_pct"} & listed
    # `out_tok_s` is not judged (the reading is the count of replies the mix's `shape_seed` fixes,
    # the same in every run, and moves in steps of a bound's size), so what moves it is not listed.
    assert not {"decode_lanes_mean", "decode_hbm_pct", "device_idle_pct.closed", "expert_local_pct"} & listed


# The end-to-end metrics the cell judges (PERF.md section 2 says why).
JUDGED = ("ttft_p50_ms", "itl_p95_ms", "setup_s")


def test_the_benchmarks_reference_is_the_programs_file():
    ours = (BENCH / "dots3_note_reference.py").read_text()
    theirs = (REPO / "generativeaiexamples_tpu" / "models" / "dots3_note_reference.py").read_text()
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
    cfg = hybrid.PRESETS["dots3_note-tiny"]()
    arch._CHECK.update(limits={"p10": 1e-3, "p50": 1e-3, "p90": 1e-3, "decode_p50": 1e-3}, decode=8,
                       chunk=16, overlap_floor=0.99)
    params = hybrid.init_params(cfg, jax.random.PRNGKey(0))
    tokens = np.random.RandomState(0).randint(1, cfg.vocab_size, size=75).tolist()
    return arch, cfg, params, tokens


def test_last_logits_hands_on_the_references_when_the_program_agrees(tiny, capsys):
    arch, cfg, params, tokens = tiny
    from generativeaiexamples_tpu.models import dots3_note_reference

    got = arch.last_logits(params, cfg, tokens, 96)
    want = np.asarray(dots3_note_reference.all_logits(params, cfg, tokens))[-1]
    np.testing.assert_allclose(got, want, atol=1e-5)
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["outside"] == [] and line["decode_p50"] < 1e-4 and line["p90"] < 1e-4
    assert line["index_overlap"] == 1.0  # float32: the sets are the reference's
    share, _, overlap = arch.logit_shares(params, cfg, tokens, 96)
    assert share.shape == (75,) and overlap == 1.0


def test_the_check_runs_what_the_measured_window_runs_at_its_shapes(tiny, monkeypatch):
    arch, cfg, params, tokens = tiny
    from generativeaiexamples_tpu.engine.serving_models import HybridServing

    seen = []
    in_place, step = HybridServing._prefill_rows_in_place, HybridServing.decode_step

    def rows(self, params, cache, tokens, start, suffix_len, slots, window):
        seen.append(("rows", tokens.shape, cache[0]["index_k"].shape[:2], window))
        return in_place(self, params, cache, tokens, start, suffix_len, slots, window)

    def one(self, params, cache, tokens, lengths, counts, window):
        seen.append(("step", tokens.shape, cache[0]["index_k"].shape[:2], window))
        return step(self, params, cache, tokens, lengths, counts, window)

    monkeypatch.setattr(HybridServing, "_prefill_rows_in_place", rows)
    monkeypatch.setattr(HybridServing, "decode_step", one)
    arch._programs.cache_clear()  # traced anew, through the two above
    try:
        arch.logit_shares(params, cfg, tokens, 96)
    finally:
        arch._programs.cache_clear()
    T = cfg.max_seq_len
    assert set(seen) == {("rows", (2, 16), (2, T), T), ("step", (2,), (2, T), T)}


@pytest.mark.parametrize("control", ["no_selection", "short_selection"])
def test_a_selection_changed_is_handed_on_as_no_agreement(tiny, control, capsys):
    """The program run with another selection than the reference's (every
    row kept; 8 rows fewer kept): the logits leave their limits, and the
    sets the second keeps are the reference's but for what it drops."""
    arch, cfg, params, tokens = tiny
    served = dataclasses.replace(cfg, index_topk={"no_selection": 256, "short_selection": 16}[control])
    share, _, overlap = arch.logit_shares(params, cfg, tokens, 96, served=served)
    readings = arch.share_quantiles(share, 8)
    assert readings["p90"] > 1e-2 and readings["decode_p50"] > 1e-2
    assert "decode_p50" in arch.outside_limits(readings, overlap)
    got = arch.last_logits(params, served, tokens, 96)  # the harness's call, on a program that selects otherwise
    assert got.shape == (cfg.vocab_size,)  # ... compared with ITS reference: agrees
    assert arch.outside_limits({"p10": 0, "p50": 0, "p90": 0, "decode_p50": 0}, 0.5) == ["index_overlap"]
