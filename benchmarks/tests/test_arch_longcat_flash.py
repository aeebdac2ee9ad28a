"""``arch/longcat_flash.py``: the mapping at both sizes, the file against
the catalog's row, the counts against the table of the configuration's cut
worked by hand, what the mix's warm-up drives for a model without a verify
step, the cell's lists, the benchmark's copy of the reference against the
program's, and the logit-level comparison behind ``last_logits`` (sound,
and with the branch moved or the identity term dropped)."""

import json
from pathlib import Path

import numpy as np
import pytest

import run
import traffic

BENCH = Path(__file__).resolve().parents[1]
REPO = BENCH.parent
NAME = "longcat-flash-chat-l4e16"
CELL = f"{NAME}.doc-reason-closed"
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
    assert Path(arch.__file__).name == "longcat_flash.py"
    cfg = arch.llama_config(model, engine)
    assert type(cfg).__name__ == "ShortcutLatentConfig"
    assert cfg.layer_kinds == (("mla", "shortcut"), ("mla", "dense_add")) * 4
    assert (cfg.d_model, cfg.n_heads, cfg.q_lora_rank, cfg.kv_lora_rank) == (6144, 64, 1536, 512)
    assert (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim) == (128, 64, 128)
    assert (cfg.n_experts, cfg.zero_experts, cfg.router_outputs) == (512, 256, 768)
    assert (cfg.experts_held, cfg.expert_offset, cfg.n_experts_per_tok) == (16, 0, 12)
    assert (cfg.d_ff, cfg.moe_d_ff, cfg.shared_d_ff, cfg.vocab_size, cfg.max_seq_len) == (12288, 2048, 0, 16384, 16384)
    assert (cfg.score_function, cfg.router_bias, cfg.norm_topk, cfg.n_group, cfg.routed_scaling) == (
        "softmax", True, False, 1, 6.0)
    assert cfg.latent_rescale and not cfg.mla_out_gate and cfg.rope_latent is None and cfg.rope_theta == 1e7
    assert (cfg.dtype, cfg.kv_dtype, cfg.latent_block, cfg.latent_width) == ("bfloat16", "bfloat16", 1024, 640)
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    # What the build holds: 10.35 GB of weights, 2.68 GB of latent rows of
    # 1,280 B of which 1,152 are the latent's and the rope key's.
    assert line["weight_bytes"] == pytest.approx(10.35e9, rel=0.005)
    assert line["state_bytes_full"] == 16 * 16384 * 1280 * 8 == 2_684_354_560
    assert (line["latent_row_bytes_used"], line["latent_row_bytes_stored"]) == (1152, 1280)
    assert (line["sublayers"], line["router_outputs"]) == (8, 768)
    tiny_model, tiny_engine = sizes(True)
    tiny = arch.llama_config(tiny_model, tiny_engine)
    # The cut's ratios at a size a CPU prefills 110k tokens of in seconds.
    assert tiny.layer_kinds == (("mla", "shortcut"), ("mla", "dense_add")) and tiny.max_seq_len == 16384
    assert (tiny.n_experts, tiny.experts_held, tiny.zero_experts, tiny.n_experts_per_tok) == (16, 4, 8, 3)
    assert tiny.q_lora_rank < tiny.d_model and (tiny.dtype, tiny.kv_dtype) == ("float32", "float32")
    with pytest.raises(ValueError, match="experts_held"):
        arch.llama_config(model, {**engine, "experts_held": 32})


def test_the_file_keeps_every_published_width_and_lists_its_cuts():
    model, engine = sizes(False)
    assert model["reduced"] == ["num_layers", "n_routed_experts", "vocab_size"]
    assert model["reduced_from"] == {"num_layers": 28, "n_routed_experts": 512, "vocab_size": 131072}
    assert (model["num_experts_published"], model["zero_expert_num"], model["moe_topk"]) == (512, 256, 12)
    assert model["arch"] == model["model_type"] == "longcat_flash"
    assumed = " ".join(model["assumed"])
    assert len(model["assumed"]) >= 8
    for needle in ("NOT renormalised", "adjacent pairs", "untied head", "NORMED", "h4 + m", "not served",
                   "balance_router_biases"):
        assert needle in assumed, needle
    for needle in ("32 chips", "224 chips", "10.35 GB", "2.68 GB", "identity", "1/32"):
        assert needle in model["stands_for"], needle
    assert engine == {**engine, "weight_dtype": "bfloat16", "kv_dtype": "bfloat16", "max_batch": 16,
                      "max_len": 16384, "decode_chunk_size": 4, "prefill_chunk_tokens": 256,
                      "prefix_cache": "shared", "kv_layout": "contiguous", "matmul_kernel": "xla",
                      "experts_held": 16, "expert_offset": 0}
    assert "draft" not in engine
    assert model["expect_paths"] == {
        "moe_experts": "pallas", "attn_latent_chunk": "pallas", "attn_latent_decode": "pallas"}
    ref = model["reference"]
    assert (ref["prompts"], ref["min_within"], ref["decode_positions"]) == (4, 3, 16)
    assert set(ref["logit_share_limits"]) == {"p10", "p50", "p90", "decode_p50"}
    for control in ("w8a8_mlp", "no_latent_rescale", "no_zero_identity", "shortcut_early", "renormed_weights"):
        assert control in ref["why"], control
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    assert spec["configs"][-1]["name"] == NAME and spec["workloads"][-1]["name"] == CELL
    entry = spec["configs"][-1]
    assert entry["reduced"] == model["reduced"] and entry["source"] == model["source"]
    assert entry["file"] == f"benchmarks/configs/{NAME}.json"
    if CATALOG.exists():
        row = next(json.loads(l) for l in open(CATALOG) if '"name": "LongCat-Flash-Chat"' in l)
        assert model["source"] == row["source_url"]
        for key, value in row["config"].items():
            if key not in model["reduced"]:
                assert model[key] == value, key
    # No width among the cuts: a rehearsal may change widths, the cell may not.
    widths = ("hidden_size", "ffn_hidden_size", "expert_ffn_hidden_size", "q_lora_rank", "kv_lora_rank",
              "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "moe_topk", "num_attention_heads",
              "zero_expert_num")
    assert not set(widths) & set(model["reduced"])


def test_parameter_counts_are_the_issues_table():
    model, _ = sizes(False)
    p = run.load_arch(model).part_params(model)
    # By hand, from the published widths (ISSUE 57): W_qa, W_qb, W_kva, W_kvb, W_o.
    assert p["attention"] == 9_437_184 + 18_874_368 + 3_538_944 + 8_388_608 + 50_331_648 == 90_570_752
    assert p["dense"] == 3 * 6144 * 12288 == 226_492_416
    assert p["router"] == 6144 * 768 == 4_718_592  # the identity outputs are router columns too
    assert p["expert"] == 3 * 6144 * 2048 == 37_748_736
    assert p["head"] == 16384 * 6144 == 100_663_296
    outside = 2 * p["attention"] + 2 * p["dense"] + p["router"]
    assert round(outside / 1e6, 1) == 638.8 and round(outside * 2 / 1e9, 3) == 1.278
    layer = outside + 16 * p["expert"]
    assert round(16 * p["expert"] / 1e6, 1) == 604.0 and round(layer * 2 / 1e9, 3) == 2.486
    assert round(4 * layer * 2 / 1e9, 2) == 9.94
    assert round((4 * layer + 2 * p["head"]) * 2 / 1e9, 2) == 10.35  # GB in bf16; norms and biases are 0.0003


def test_decode_step_bytes_by_hand():
    model, engine = sizes(False)
    arch = run.load_arch(model)
    p = arch.part_params(model)
    once = 4 * (2 * p["attention"] + 2 * p["dense"] + p["router"]) + p["head"]
    rows = engine["roofline_decode_rows"]
    # A row's 12 choices of 768 miss a given real expert with probability 63/64.
    touched = 16 * (1 - (63 / 64) ** rows)
    assert arch.experts_touched(model, rows) == pytest.approx(touched)
    assert arch.experts_touched(model, 1) == pytest.approx(0.25) == arch.local_choices(model)  # 12 x 16 / 768
    assert arch.experts_touched(model, 16) == pytest.approx(3.56, abs=0.01)  # ISSUE 57: 3.6 at a full house
    assert arch.latent_bytes_per_row(model, engine) == (512 + 64) * 2 == 1152
    live = rows * 8_000
    want = 2 * (once + 4 * touched * p["expert"]) + 8 * live * 1152
    assert arch.decode_step_bytes(model, engine, live) == pytest.approx(want)
    # 5.31 GB outside the routed experts (5.11 of dense weights, 0.20 of head),
    # and every live token's row in EIGHT sublayers; an identity choice reads nothing.
    assert round(2 * once / 1e9, 2) == 5.31 and round(2 * (once - p["head"]) / 1e9, 2) == 5.11
    assert arch.decode_step_bytes(model, engine, live) - arch.decode_step_bytes(model, engine, 0) == pytest.approx(
        8 * live * 1152)
    none = {**model, "zero_expert_num": 0}  # a 512-wide router sends every choice to a real expert
    assert arch.experts_touched(none, rows) > touched
    more = arch.decode_step_bytes(model, {**engine, "roofline_decode_rows": 16}, live)
    assert more > want or rows >= 16


def test_prefill_flops_by_hand():
    model, _ = sizes(False)
    arch = run.load_arch(model)
    p = arch.part_params(model)
    # Of a token's 12 choices a quarter of one lands on the 16 experts held, on
    # average; four fall on identity experts and cost nothing.
    active = 4 * (2 * p["attention"] + 2 * p["dense"] + p["router"] + 0.25 * p["expert"])
    assert round(active / 1e6) == 2593
    pair = 2 * 64 * (192 + 128)  # QK^T over 128 + 64, PV over 128, a head
    assert pair == 40_960
    pairs = sum(i + 1 for i in range(5_000, 5_256))
    assert arch.prefill_flops(model, 256, pairs) == pytest.approx(2 * active * 256 + 8 * pair * pairs)
    assert arch.prefill_flops(model, 0, 0) == 0


def test_the_mix_is_deepseeks_and_its_warm_up_drives_this_models_shapes():
    """The cell takes ``doc-reason-closed`` as it is.  Its warm-up was
    written for a verify step (a window of the power of two over the
    longest decoding row + 17); this model's decode chunk asks for the
    power of two over the longest decoding row + 9, and the same bursts
    drive every one of those windows, the graft of the template's rows and
    a cold prompt of the reference check's length."""
    from generativeaiexamples_tpu.utils.buckets import bucket_size

    mix = traffic.load_mix("doc-reason-closed")
    assert mix["arrivals"] == {"loop": "closed", "clients": 20} and mix["shape_seed"] == 20261004
    assert mix["unique"] == {"dist": "lognormal", "median": 5120, "sigma": 0.4, "lo": 2560, "hi": 10240}
    assert mix["max_tokens"] == {"dist": "lognormal", "median": 1024, "sigma": 0.6, "lo": 256, "hi": 3072}
    assert (mix["prefix_tokens"], mix["temperature"], mix["max_total"]) == (256, 0.0, 13824)
    windows, grafts, cold = set(), set(), False
    first = mix["warmup"][0]["requests"][0]
    for burst in mix["warmup"]:
        for r in burst["requests"]:
            plen = r["shared"] + r["fresh"]
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
    requests = traffic.generate(mix, 2**31 + 5, 16384, 40, 0.0)
    assert max(max(r["prompt"]) for r in requests[:8]) < 16384  # ids from this cell's slice


def test_the_cell_is_listed_where_its_readers_find_something():
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    cell = bench["workloads"][-1]
    assert (cell["name"], cell["config"], cell["traffic"], cell["chips"]) == (CELL, NAME, "doc-reason-closed", 1)
    assert "expert_local_pct" in cell["why"] and len(cell["why"]) <= 200
    judged = {m["name"] for m in bench["end_to_end"] if "workloads" not in m or CELL in m["workloads"]}
    assert judged == {"itl_p95_ms", "out_tok_s", "setup_s"}
    listed = {m["name"]: m for m in bench["per_layer"] if CELL in m.get("workloads", [])}
    for name in ("decode_hbm_pct", "decode_lanes_mean", "device_idle_pct.closed", "decode_latent_rows_pct",
                 "decode_latent_kernel_pct", "decode_experts_touched_pct", "expert_local_pct",
                 "expert_load_max_over_mean", "expert_streams_per_touched", "prefill_pad_pct.itl", "tick_ms",
                 "host_starve_ms", "dispatch_ms_per_site", "setup_executables", "decode_zero_choice_pct"):
        assert name in listed, name
    # A reading reaches the cell only where the cell judges what it moves,
    # and always at the end of its list.
    assert {m["moves"] for m in listed.values()} <= judged
    assert all(m["workloads"][-1] == CELL for m in listed.values())
    # What reads an indexer, a draft, windows, rings, K/V rows or snapshots has nothing to read here.
    assert not {"decode_index_rows_pct", "draft_accept_pct", "verify_gather_pct", "decode_window_rows_pct",
                "decode_full_rows_pct", "prefix_snapshot_loss_pct", "prefill_latent_rows_pct"} & set(listed)


def test_the_benchmarks_reference_is_the_programs_file():
    ours = (BENCH / "longcat_flash_reference.py").read_text()
    theirs = (REPO / "generativeaiexamples_tpu" / "models" / "longcat_flash_reference.py").read_text()
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
    cfg = hybrid.PRESETS["longcat_flash-tiny"]()
    arch._CHECK.update(limits={"p10": 1e-3, "p50": 1e-3, "p90": 1e-3, "decode_p50": 1e-3}, decode=8, chunk=16)
    key = jax.random.PRNGKey(0)
    params = hybrid.balance_router_biases(hybrid.init_params(cfg, key), cfg, jax.random.fold_in(key, 1))
    tokens = np.random.RandomState(0).randint(1, cfg.vocab_size, size=75).tolist()
    return arch, cfg, params, tokens


def test_last_logits_hands_on_the_references_when_the_program_agrees(tiny, capsys):
    arch, cfg, params, tokens = tiny
    from generativeaiexamples_tpu.models import longcat_flash_reference

    got = arch.last_logits(params, cfg, tokens, 96)
    want = np.asarray(longcat_flash_reference.all_logits(params, cfg, tokens))[-1]
    np.testing.assert_allclose(got, want, atol=1e-5)
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["outside"] == [] and line["decode_p50"] < 1e-4 and line["p90"] < 1e-4
    share, _ = arch.logit_shares(params, cfg, tokens, 96)
    assert share.shape == (75,)  # 67 prefilled in chunks of 16 (the last padded), 8 decoded


@pytest.mark.parametrize("control", ["shortcut_early", "no_zero_identity", "no_latent_rescale"])
def test_a_mechanism_moved_or_dropped_is_handed_on_as_no_agreement(tiny, control, monkeypatch, capsys):
    """The reference with one of its own steps changed (what
    ``chip_smoke.py``'s controls of those names change) against the sound
    program: every reading leaves the limits, and ``last_logits`` hands on
    logits no served token agrees with."""
    arch, cfg, params, tokens = tiny
    ref = arch.longcat_flash_reference
    if control == "shortcut_early":
        # ``m`` added before the second sublayer, where a plain expert layer adds it.
        real = ref.layer

        def early(x, first, second, dims_t):
            eps = dict(dims_t)["eps"]
            x = ref._attend(x, first, dims_t)
            x = ref._dense(x, first, eps) + ref._experts(x, first, dims_t)
            return ref._dense(ref._attend(x, second, dims_t), second, eps)

        monkeypatch.setattr(ref, "layer", early)
        assert real is not early
    elif control == "no_zero_identity":
        monkeypatch.setattr(ref, "_identity", lambda u, w_zero: 0.0 * u)
    else:
        monkeypatch.setattr(ref, "_rescale", lambda c, d_model, rank: c)
    for fn in (ref._attend, ref._experts, ref._dense):
        fn.clear_cache()  # traced anew, through the function above
    try:
        share, _ = arch.logit_shares(params, cfg, tokens, 96)
        readings = arch.share_quantiles(share, 8)
        assert readings["p50"] > 1e-2 and readings["decode_p50"] > 1e-2
        got = arch.last_logits(params, cfg, tokens, 96)
        assert got.shape == (cfg.vocab_size + 1,) and got.argmax() == cfg.vocab_size
        assert json.loads(capsys.readouterr().out.strip().splitlines()[-1])["outside"]
    finally:
        for fn in (ref._attend, ref._experts, ref._dense):
            fn.clear_cache()
