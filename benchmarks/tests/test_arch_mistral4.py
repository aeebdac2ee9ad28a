"""``arch/mistral4.py``: the mapping at both sizes, the file against the
catalog's row, the counts against the table of the configuration's cut
worked by hand, the traffic mix's lengths and what its warm-up drives,
the new counter readers on canned counters, the benchmark's copy of the
reference against the program's, and the logit-level comparison behind
``last_logits`` (sound, and with each mechanism switched off)."""

import dataclasses
import json
import math
from pathlib import Path

import numpy as np
import pytest

import run
import traffic
from metrics_lib import load_reader

BENCH = Path(__file__).resolve().parents[1]
REPO = BENCH.parent
NAME = "mistral-small-4-119b-l6e32"
CELL = f"{NAME}.doc-long-closed"
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
    assert Path(arch.__file__).name == "mistral4.py"
    cfg = arch.llama_config(model, engine)
    assert type(cfg).__name__ == "LatentConfig" and cfg.layer_kinds == (("mla", "experts"),) * 6
    assert (cfg.d_model, cfg.n_heads, cfg.q_lora_rank, cfg.kv_lora_rank) == (4096, 32, 1024, 256)
    assert (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim) == (64, 64, 128)
    assert (cfg.n_experts, cfg.experts_held, cfg.expert_offset, cfg.n_experts_per_tok) == (128, 32, 0, 4)
    assert (cfg.moe_d_ff, cfg.shared_d_ff, cfg.vocab_size, cfg.max_seq_len) == (2048, 2048, 32768, 32768)
    assert (cfg.score_function, cfg.router_bias, cfg.norm_topk, cfg.n_group, cfg.routed_scaling) == (
        "softmax", False, True, 1, 1.0)
    spec = cfg.rope_latent
    assert (spec.rope_type, spec.theta, spec.factor, spec.original_max, spec.beta_fast, spec.beta_slow) == (
        "yarn", 10000.0, 128.0, 8192, 32.0, 1.0)
    assert spec.attention_factor == 1.0 and cfg.attn_scale_beta == 0.1 and not cfg.mla_out_gate
    assert cfg.softmax_mscale == pytest.approx(0.1 * math.log(128) + 1)
    assert (cfg.dtype, cfg.kv_dtype, cfg.latent_block, cfg.latent_width) == ("bfloat16", "bfloat16", 1024, 384)
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    # What the build holds: 10.85 GB of weights, 2.01 GB of latents and
    # rope keys in rows of 768 B of which 640 are theirs.
    assert line["weight_bytes"] == pytest.approx(10.85e9, rel=0.01)
    assert line["state_bytes_full"] == 16 * 32768 * 768 * 6 and line["snapshot_bytes"] == 0
    assert (line["latent_row_bytes_used"], line["latent_row_bytes_stored"]) == (640, 768)
    assert 16 * 32768 * 640 * 6 == pytest.approx(2.01e9, rel=0.005)
    tiny_model, tiny_engine = sizes(True)
    tiny = arch.llama_config(tiny_model, tiny_engine)
    # The cut's ratios at a size a CPU prefills 210k tokens of in seconds.
    assert len(tiny.layer_kinds) >= 1 and tiny.max_seq_len == 32768
    assert (tiny.n_experts, tiny.experts_held, tiny.n_experts_per_tok) == (32, 8, 2)
    assert tiny.q_lora_rank < tiny.d_model and tiny.rope_latent.original_max == 2048
    assert (tiny.dtype, tiny.kv_dtype, tiny.attn_scale_beta) == ("float32", "float32", 0.1)
    with pytest.raises(ValueError, match="experts_held"):
        arch.llama_config(model, {**engine, "experts_held": 16})


def test_the_file_keeps_every_published_width_and_lists_its_cuts():
    model, engine = sizes(False)
    assert model["reduced"] == ["num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert model["reduced_from"] == {"num_hidden_layers": 36, "n_routed_experts": 128, "vocab_size": 131072}
    assert model["num_experts_published"] == 128 and model["arch"] == "mistral4"
    assumed = " ".join(model["assumed"])
    assert len(model["assumed"]) >= 5
    for needle in ("softmax", "m^2", "after the rotation", "truncated"):
        assert needle in assumed, needle
    for needle in ("four chips", "24 chips", "10.85 GB", "2.01 GB", "six layers"):
        assert needle in model["stands_for"], needle
    assert engine == {**engine, "weight_dtype": "bfloat16", "kv_dtype": "bfloat16", "max_batch": 16,
                      "max_len": 32768, "decode_chunk_size": 8, "prefill_chunk_tokens": 256,
                      "prefix_cache": "shared", "kv_layout": "contiguous", "experts_held": 32,
                      "expert_offset": 0}
    assert model["expect_paths"] == {"moe_experts": "pallas"}
    ref = model["reference"]
    assert (ref["prompts"], ref["min_within"], ref["decode_positions"]) == (4, 3, 16)
    assert set(ref["logit_share_limits"]) == {"p10", "p50", "p90", "decode_p50"}
    for control in ("w8a8_mlp", "no_attn_scale", "plain_rope", "no_mscale", "no_q_norm"):
        assert control in ref["why"], control
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    entry = next(c for c in spec["configs"] if c["name"] == NAME)
    assert entry["reduced"] == model["reduced"] and entry["source"] == model["source"]
    assert entry["file"] == f"benchmarks/configs/{NAME}.json"
    if CATALOG.exists():
        row = next(json.loads(l) for l in open(CATALOG) if '"name": "Mistral-Small-4-119B-2603"' in l)
        assert model["source"] == row["source_url"]
        for key, value in row["config"].items():
            if key not in model["reduced"]:
                assert model[key] == value, key
    # No width among the cuts: a rehearsal may change widths, the cell may not.
    widths = ("hidden_size", "moe_intermediate_size", "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
              "qk_rope_head_dim", "v_head_dim", "num_experts_per_tok", "num_attention_heads")
    assert not set(widths) & set(model["reduced"])


def test_parameter_counts_are_the_issues_table():
    model, _ = sizes(False)
    p = run.load_arch(model).part_params(model)
    # By hand, from the published widths (ISSUE 38): W_qa, W_qb, W_kva, W_kvb, W_o.
    assert p["attention"] == 4_194_304 + 4_194_304 + 1_310_720 + 1_572_864 + 16_777_216 == 28_049_408
    assert p["router"] == 4096 * 128 == 524_288
    assert p["expert"] == p["shared"] == 3 * 4096 * 2048 == 25_165_824
    assert p["head"] == 32768 * 4096 == 134_217_728
    outside = p["attention"] + p["router"] + p["shared"]
    assert round(outside / 1e6, 2) == 53.74  # ISSUE 38 rounds the parts first: 53.75
    layer = outside + 32 * p["expert"]
    assert round(layer / 1e6, 1) == 859.0 and round(layer * 2 / 1e9, 3) == 1.718
    assert round((6 * layer + 2 * p["head"]) * 2 / 1e9, 2) == 10.85  # GB in bf16; norms are 0.0002


def test_decode_step_bytes_by_hand():
    model, engine = sizes(False)
    arch = run.load_arch(model)
    p = arch.part_params(model)
    once = 6 * (p["attention"] + p["router"] + p["shared"]) + p["head"]
    rows = engine["roofline_decode_rows"]
    # A row's 4 choices of 128 miss a given expert with probability 31/32.
    touched = 32 * (1 - (31 / 32) ** rows)
    assert arch.experts_touched(model, rows) == pytest.approx(touched)
    assert arch.experts_touched(model, 1) == pytest.approx(1.0)  # 4 x 32 / 128
    assert arch.latent_bytes_per_row(model, engine) == (256 + 64) * 2 == 640
    live = rows * 14_000
    want = 2 * (once + 6 * touched * p["expert"]) + 6 * live * 640
    assert arch.decode_step_bytes(model, engine, live) == pytest.approx(want)
    assert arch.decode_step_bytes(model, engine, 0) == pytest.approx(2 * (once + 6 * touched * p["expert"]))
    # 0.91 GB outside the routed experts, and every live token's row in six layers.
    assert round(2 * once / 1e9, 2) == 0.91
    assert arch.decode_step_bytes(model, engine, live) - arch.decode_step_bytes(model, engine, 0) == pytest.approx(
        6 * live * 640)
    more = arch.decode_step_bytes(model, {**engine, "roofline_decode_rows": 16}, live)
    assert more > want or rows >= 16


def test_prefill_flops_by_hand():
    model, _ = sizes(False)
    arch = run.load_arch(model)
    p = arch.part_params(model)
    # Of a token's 4 choices one lands on the 32 experts held, on average.
    active = 6 * (p["attention"] + p["router"] + p["shared"] + 1 * p["expert"])
    assert round(active / 1e6) == 473
    pair = 2 * 32 * (128 + 128)  # QK^T over 64 + 64, PV over 128, a head
    assert pair == 16_384
    pairs = sum(i + 1 for i in range(12_000, 12_256))
    assert arch.prefill_flops(model, 256, pairs) == pytest.approx(2 * active * 256 + 6 * pair * pairs)
    assert arch.prefill_flops(model, 0, 0) == 0
    # ISSUE 38's arithmetic: at ~7k visible keys a token attention is ~115 MFLOP a
    # layer against 157 for every matrix product of the layer.
    assert round(pair * 7000 / 1e6) == 115 and round(2 * active / 6 / 1e6) == 158


def test_the_mix_holds_the_issues_parameters_and_lengths():
    mix = traffic.load_mix("doc-long-closed")
    assert mix["arrivals"] == {"loop": "closed", "clients": 16}
    assert traffic.load_mix("doc-long")["arrivals"] == {"loop": "open"}
    assert mix["prefix_tokens"] == 256 and "docs" not in mix and "reask_share" not in mix
    assert mix["unique"] == {"dist": "lognormal", "median": 12288, "sigma": 0.5, "lo": 3840, "hi": 28160}
    assert mix["max_tokens"] == {"dist": "uniform", "lo": 64, "hi": 256}
    assert (mix["temperature"], mix["top_p"], mix["max_total"]) == (0.2, 0.7, 28672)
    assert (mix["spec_requests"], mix["reference_len"]) == (240, [8320, 8960])
    others = {traffic.load_mix(m)["shape_seed"] for m in ("rag-closed", "rag-long-closed", "reason-closed", "chat-closed")}
    assert mix["shape_seed"] not in others  # its own
    shapes = traffic.request_shapes(mix)
    prompts = 256 + shapes["unique"]
    assert prompts.min() == 4096 and prompts.max() == 28416 and 12_000 < np.median(prompts) < 12_600
    assert 13_000 < prompts.mean() < 13_500 and 0.25 < (prompts > 16384).mean() < 0.35
    assert (prompts + shapes["max_tokens"]).max() <= 28672 < 32768 - 8  # the scheduler's admit limit
    # Every reference prompt crosses the original context of 8,192.
    assert mix["reference_len"][0] - 16 > 8192 and mix["reference_len"][1] % 256 == 0
    # Every request a run can need is generated, from a seed past 2**31 too.
    need = int(mix["supply_rps"] * 45) + 16 + 8
    requests = traffic.generate(mix, 2**31 + 5, 32768, need)
    assert len(requests) == need and all(r["prompt"][:256] == requests[0]["prompt"][:256] for r in requests)
    assert max(max(r["prompt"]) for r in requests[:8]) < 32768 and min(min(r["prompt"]) for r in requests[:8]) >= 256


def test_the_warm_up_drives_every_program_shape_of_the_mix():
    """The chunk programs are the scheduler's own family (one window for
    this model); what traffic can still ask for is a decode window (the
    power of two over the longest decoding row + 9), a graft of the
    template's rows (one bucket) and a cold prompt (the reference
    check's).  No shared part passes the 4,096-token warm-up base."""
    from generativeaiexamples_tpu.utils.buckets import bucket_size

    mix = traffic.load_mix("doc-long-closed")
    windows, grafts, cold = set(), set(), False
    first = mix["warmup"][0]["requests"][0]
    for burst in mix["warmup"]:
        for r in burst["requests"]:
            assert r["shared"] <= 4096
            plen = r["shared"] + r["fresh"]
            assert plen + r["max_tokens"] <= 28672
            if r["shared"] and r is not first:
                grafts.add(bucket_size(r["shared"], minimum=16, dense=True))
            cold |= r["shared"] == 0
            if r["max_tokens"] > 1:
                windows |= {bucket_size(plen + n + 8 + 1, maximum=32768) for n in (0, r["max_tokens"])}
    shapes = traffic.request_shapes(mix)
    need = set()
    for unique, out in zip(shapes["unique"], shapes["max_tokens"]):
        need |= {bucket_size(256 + int(unique) + n + 8 + 1, maximum=32768) for n in (0, int(out))}
    assert need <= windows == {4096, 8192, 16384, 32768}
    assert grafts == {256} and cold
    assert max(len(b["requests"]) for b in mix["warmup"]) == 8  # a full group of chunk rows


# Between the markers of a traced window: 60 decode chunks of 8 steps over
# six layers, 16 slots under a window of 32,768, read whole; 400 chunk
# programs' rows over a window of 32,768 of which a quarter's blocks were read.
DENSE_DECODE = 60 * 8 * 6 * 16 * 32768
DENSE_PREFILL = 400 * 6 * 4 * 32768
COUNTERS = {
    "attn_rows_read_latent_decode": DENSE_DECODE, "attn_rows_dense_latent_decode": DENSE_DECODE,
    "attn_rows_read_latent_prefill": DENSE_PREFILL // 4, "attn_rows_dense_latent_prefill": DENSE_PREFILL,
}
READERS = {"decode_latent_rows_pct": ("decode", 100.0), "prefill_latent_rows_pct": ("prefill", 25.0)}


@pytest.mark.parametrize("name", sorted(READERS))
def test_new_counter_readers(name):
    model, engine = sizes(False)
    read = load_reader(name)
    phase, expected = READERS[name]
    ctx = {"trace_counters": dict(COUNTERS), "counters": {}, "model": model, "engine": engine, "trace": None}
    assert read(ctx) == pytest.approx(expected)
    half = {**COUNTERS, f"attn_rows_read_latent_{phase}": COUNTERS[f"attn_rows_dense_latent_{phase}"] // 2}
    assert read({**ctx, "trace_counters": half}) == pytest.approx(50.0)
    assert read({**ctx, "trace_counters": None}) is None  # --trace 0
    zero = {**COUNTERS, f"attn_rows_dense_latent_{phase}": 0}
    assert read({**ctx, "trace_counters": zero}) is None  # a window with no such program
    # A program without the counters (the parent): nothing to read, no error.
    assert read({**ctx, "trace_counters": {"busy_ticks": 3, "prefix_tokens_reused": 5}}) is None
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    (entry,) = [m for m in spec["per_layer"] if m["name"] == name]  # once, wherever it stands
    assert entry["workloads"] == [CELL] and entry["better"] == "lower" and entry["unit"] == "%"
    assert entry["layer"] == "step programs" and entry["source"] == "program_counter"
    assert entry["moves"] == {"decode": "itl_p95_ms", "prefill": "ttft_p50_ms"}[phase]


def test_the_cell_is_listed_where_its_readers_find_something():
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    (cell,) = [w for w in spec["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (NAME, "doc-long-closed", 1)
    judged = {m["name"] for m in spec["end_to_end"] if CELL in m.get("workloads", [CELL])}
    # ``out_tok_s`` is not judged here: two sets of six runs spread 0.42 and
    # 0.67 % where half its bound is 0.5 % (PERF.md section 2).  The cell
    # reports it per layer, and beside it the readings that qualify a tick.
    assert judged == {"ttft_p50_ms", "itl_p95_ms", "setup_s"}
    by_name = {m["name"]: m for m in spec["per_layer"]}
    listed = {name for name, m in by_name.items() if CELL in m.get("workloads", [CELL])}
    assert {"decode_latent_rows_pct", "prefill_latent_rows_pct", "out_tok_s_closed",
            "expert_load_max_over_mean", "decode_hbm_pct.itl", "prefill_mxu_pct", "prefix_reuse_pct",
            "decode_lanes_mean.itl", "device_idle_pct.itl", "prefill_rows_per_program",
            "setup_executables"} <= listed
    assert all(by_name[name]["moves"] in judged for name in listed)
    # What reads windows, rings, K/V rows or snapshots has nothing to read here.
    assert not {"decode_window_rows_pct", "prefill_window_rows_pct", "decode_full_rows_pct",
                "prefix_snapshot_loss_pct", "draft_accept_pct"} & listed


def test_the_benchmarks_reference_is_the_programs_file():
    ours = (BENCH / "mistral4_reference.py").read_text()
    theirs = (REPO / "generativeaiexamples_tpu" / "models" / "mistral4_reference.py").read_text()
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
    cfg = hybrid.PRESETS["mistral4-tiny"]()
    arch._CHECK.update(limits={"p10": 1e-3, "p50": 1e-3, "p90": 1e-3, "decode_p50": 1e-3}, decode=8, chunk=16)
    params = hybrid.init_params(cfg, jax.random.PRNGKey(0))
    tokens = np.random.RandomState(0).randint(1, cfg.vocab_size, size=75).tolist()
    return arch, cfg, params, tokens


def test_last_logits_hands_on_the_references_when_the_program_agrees(tiny, capsys):
    arch, cfg, params, tokens = tiny
    from generativeaiexamples_tpu.models import mistral4_reference

    got = arch.last_logits(params, cfg, tokens, 96)
    want = np.asarray(mistral4_reference.all_logits(params, cfg, tokens))[-1]
    np.testing.assert_allclose(got, want, atol=1e-5)
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["outside"] == [] and line["decode_p50"] < 1e-4 and line["p90"] < 1e-4
    share, _ = arch.logit_shares(params, cfg, tokens, 96)
    assert share.shape == (75,)  # 67 prefilled in chunks of 16 (the last padded), 8 decoded


def test_the_check_runs_what_the_measured_window_runs_at_its_shapes(tiny, monkeypatch):
    """The comparison goes through the scheduler's calls: ``prefill_rows``
    in place over the whole slot's window, the prompt's chunk in the last
    slot of the state beside a pad row, then ``decode_step`` over every
    slot at the widest decode window (not a program of the check's own
    over a window of the prompt's length)."""
    arch, cfg, params, tokens = tiny
    from generativeaiexamples_tpu.engine.serving_models import HybridServing

    seen = []
    in_place, step = HybridServing._prefill_rows_in_place, HybridServing.decode_step

    def rows(self, params, cache, tokens, start, suffix_len, slots, window):
        seen.append(("rows", tokens.shape, cache[0]["latent"].shape[:2], window))
        return in_place(self, params, cache, tokens, start, suffix_len, slots, window)

    def one(self, params, cache, tokens, lengths, counts, window):
        seen.append(("step", tokens.shape, cache[0]["latent"].shape[:2], window))
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


@pytest.mark.parametrize("control", ["no_attn_scale", "plain_rope", "no_mscale"])
def test_a_mechanism_switched_off_is_handed_on_as_no_agreement(tiny, control, capsys):
    arch, cfg, params, tokens = tiny
    from generativeaiexamples_tpu.ops.rope import RopeSpec

    served = {
        "no_attn_scale": dataclasses.replace(cfg, attn_scale_beta=0.0),
        "plain_rope": dataclasses.replace(
            cfg, rope_latent=RopeSpec(theta=cfg.rope_latent.theta, original_max=cfg.rope_latent.original_max)),
        "no_mscale": dataclasses.replace(cfg, softmax_mscale=1.0),
    }[control]
    share, _ = arch.logit_shares(params, cfg, tokens, 96, served=served)
    readings = arch.share_quantiles(share, 8)
    assert readings["p50"] > 1e-2 and readings["decode_p50"] > 1e-2
    # What ``last_logits`` does with such readings: one entry more than
    # the vocabulary and the maximum there, so no served token agrees.
    outside = sorted(k for k, v in readings.items() if not v <= arch._CHECK["limits"][k])
    assert outside and set(outside) <= {"p10", "p50", "p90", "decode_p50"}
