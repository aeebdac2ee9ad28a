"""``arch/exaone_moe.py``: the mapping at both sizes, the counts against
the table of the configuration's cut worked by hand, the traffic mix's
parameters and lengths, the warm-up plan against the program shapes the
mix needs, the new counter readers on a made-up ``ctx``, the benchmark's
copy of the reference against the program's, the logit-level comparison
behind ``last_logits`` (sound, and with each mechanism switched off), and
a CPU rehearsal of the new cell."""

import dataclasses
import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

import run
import traffic
from metrics_lib import load_reader

BENCH = Path(__file__).resolve().parents[1]
REPO = BENCH.parent
NAME = "k-exaone-236b-a23b-l5e16"
CELL = f"{NAME}.reason-closed"
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")
KINDS = (("window", "dense"), ("window", "experts"), ("window", "experts"),
         ("full", "experts"), ("window", "experts"))


def sizes(rehearse):
    model = json.loads((BENCH / "configs" / f"{NAME}.json").read_text())
    engine = dict(model["engine"])
    if rehearse:  # as run.py lays them over
        model.update(model["rehearse"]["model"])
        engine.update(model["rehearse"]["engine"])
    return model, engine


def test_mapping_at_the_published_and_the_rehearsal_sizes():
    model, engine = sizes(False)
    arch = run.load_arch(model)
    assert Path(arch.__file__).name == "exaone_moe.py"
    cfg = arch.llama_config(model, engine)
    assert cfg.layer_kinds == KINDS and arch.layer_kinds(model) == list(KINDS)
    assert (cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.attn_head_dim) == (6144, 64, 8, 128)
    assert (cfg.d_ff, cfg.moe_d_ff, cfg.shared_d_ff) == (18432, 2048, 2048)
    assert (cfg.n_experts, cfg.experts_held, cfg.expert_offset, cfg.n_experts_per_tok) == (128, 16, 0, 8)
    assert (cfg.sliding_window, cfg.vocab_size, cfg.max_seq_len) == (128, 19200, 8192)
    assert (cfg.score_function, cfg.router_bias, cfg.norm_topk, cfg.routed_scaling) == ("sigmoid", True, True, 2.5)
    assert (cfg.n_group, cfg.topk_group, cfg.qk_norm, cfg.norm_eps) == (1, 1, True, 1e-5)
    assert cfg.rope_full.rope_type == "none" and cfg.rope_window.rope_type == "default"
    assert cfg.rope_window.theta == 1e6 and (cfg.dtype, cfg.kv_dtype) == ("bfloat16", "bfloat16")
    assert (cfg.mtp_layers, cfg.draft) == (1, "mtp")
    # The draft is part of the model: without engine.draft the module is not held.
    off = arch.llama_config(model, {k: v for k, v in engine.items() if k != "draft"})
    assert (off.mtp_layers, off.draft) == (0, "") and off.layer_kinds == KINDS
    tiny_model, tiny_engine = sizes(True)
    tiny = arch.llama_config(tiny_model, tiny_engine)
    assert tiny.layer_kinds == KINDS and tiny.sliding_window == 16 < tiny_engine["prefill_chunk_tokens"]
    assert (tiny.n_experts, tiny.experts_held, tiny.n_experts_per_tok, tiny.mtp_layers) == (16, 4, 2, 1)
    assert (tiny.dtype, tiny.kv_dtype, tiny.max_seq_len) == ("float32", "float32", 8192)
    with pytest.raises(ValueError, match="disagree"):
        arch.llama_config(model, {**engine, "experts_held": 8})


def test_the_file_keeps_every_published_width_and_lists_its_cuts():
    model, engine = sizes(False)
    assert model["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
    assert model["reduced_from"] == {"num_hidden_layers": 48, "num_experts": 128, "vocab_size": 153600}
    assumed = " ".join(model["assumed"])
    for item in ("pre-norm", "QK-norm", "not rotated", "selection bias", "embedding's half first",
                 "AFTER its final norm", "SPARSE MLP", "tie goes to the lower index"):
        assert item in assumed, item
    for item in ("EIGHT chips", "9.09 GB", "2.15 GB", "0.07 GB", "FOUR CAVEATS", "4 : 1", "one in 49",
                 "host's share", "one row's worth", "paged layout", "LoRA", "int8"):
        assert item in model["stands_for"], item
    assert (model["hidden_size"], model["num_attention_heads"], model["num_key_value_heads"],
            model["head_dim"], model["intermediate_size"], model["moe_intermediate_size"],
            model["num_experts_published"], model["num_experts_per_tok"], model["routed_scaling_factor"],
            model["sliding_window"], model["num_nextn_predict_layers"]) == (
        6144, 64, 8, 128, 18432, 2048, 128, 8, 2.5, 128, 1)
    assert (model["num_hidden_layers"], model["num_experts"], model["vocab_size"]) == (5, 16, 19200)
    assert len(model["layer_types"]) == len(model["mlp_layer_types"]) == len(model["sliding_windows"]) == 48
    assert engine == {**engine, "weight_dtype": "bfloat16", "kv_dtype": "bfloat16", "max_batch": 32,
                      "max_len": 8192, "decode_chunk_size": 8, "prefill_chunk_tokens": 256,
                      "prefix_cache": "shared", "kv_layout": "contiguous", "matmul_kernel": "xla",
                      "draft": "mtp", "experts_held": 16, "expert_offset": 0}
    assert model["expect_paths"] == {"moe_experts": "pallas"}
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    entry = next(c for c in spec["configs"] if c["name"] == NAME)
    assert entry["reduced"] == model["reduced"] and entry["source"] == model["source"]
    assert spec["configs"][-1] == entry and spec["workloads"][-1]["name"] == CELL
    assert spec["workloads"][-1] == {**spec["workloads"][-1], "config": NAME, "traffic": "reason-closed", "chips": 1}
    if CATALOG.exists():
        row = next(json.loads(l) for l in open(CATALOG) if '"name": "K-EXAONE-236B-A23B"' in l)
        assert model["source"] == row["source_url"]
        for key, value in row["config"].items():
            if key not in model["reduced"]:
                assert model[key] == value, key


def test_parameter_counts_are_the_tables():
    model, engine = sizes(False)
    arch = run.load_arch(model)
    p = arch.part_params(model)
    # By hand, from the published widths (ISSUE 33's arithmetic).
    assert p["attention"] == 6144 * 8192 + 2 * 6144 * 1024 + 8192 * 6144 == 113_246_208
    assert p["router"] == 6144 * 128 == 786_432
    assert p["expert"] == p["shared"] == 3 * 6144 * 2048 == 37_748_736
    assert p["dense_mlp"] == 3 * 6144 * 18432 == 339_738_624
    assert p["eh_proj"] == 12288 * 6144 == 75_497_472 and p["head"] == 19200 * 6144 == 117_964_800
    outside = p["attention"] + p["router"] + p["shared"]
    assert round(outside / 1e6, 1) == 151.8
    share = outside + 16 * p["expert"]
    assert round(share / 1e6, 1) == 755.8 and round(share * 2 / 1e9, 3) == 1.512
    dense = p["attention"] + p["dense_mlp"]
    assert round(dense / 1e6, 1) == 453.0 and round(dense * 2 / 1e9, 3) == 0.906
    module = p["eh_proj"] + share
    assert round(module * 2 / 1e9, 3) == 1.663
    total = dense + 4 * share + module + 2 * p["head"]
    assert round(total * 2 / 1e9, 2) == 9.09  # GB in bf16: the cut's weights
    assert arch.modules_held(engine) == 1 and arch.modules_held({}) == 0


def test_decode_step_bytes_counts_a_verify_step_by_hand():
    model, engine = sizes(False)
    arch = run.load_arch(model)
    p = arch.part_params(model)
    assert engine["roofline_decode_rows"] == 30
    once = p["head"] + (p["attention"] + p["dense_mlp"]) + 4 * (p["attention"] + p["router"] + p["shared"]) \
        + (p["eh_proj"] + p["attention"] + p["router"] + p["shared"])
    assert once == 1_405_353_984
    # 30 rows x 2 positions x 8 choices over 128 outputs: a position misses
    # a given expert with probability 15/16; the module routes one a row.
    stack, module = 16 * (1 - (15 / 16) ** 60), 16 * (1 - (15 / 16) ** 30)
    assert arch.experts_touched(model, 60) == pytest.approx(stack) and 15.6 < stack < 15.7
    assert arch.experts_touched(model, 30) == pytest.approx(module) and 13.6 < module < 13.8
    assert arch.kv_bytes_per_row(model, engine) == 2 * 8 * 128 * 2 == 4096
    # 30 rows of 2,500 tokens: the full layer and the module's read every
    # token, the 4 window layers the last 128 of each row.
    live = 30 * 2500
    want = 2 * (once + (4 * stack + module) * p["expert"]) + (2 * live + 4 * 30 * 128) * 4096
    assert arch.decode_step_bytes(model, engine, live) == pytest.approx(want)
    assert 9.0e9 < want < 9.3e9  # 2.81 GB once, 5.77 GB of experts, 0.61 + 0.06 GB of K/V
    # Rows shorter than the window read what they have.
    short = arch.decode_step_bytes(model, engine, 30 * 100) - arch.decode_step_bytes(model, engine, 0)
    assert short == pytest.approx(6 * 30 * 100 * 4096)
    # With the draft off: one position a row, no module.
    plain = {k: v for k, v in engine.items() if k != "draft"}
    off = once - (p["eh_proj"] + p["attention"] + p["router"] + p["shared"])
    assert arch.decode_step_bytes(model, plain, live) == pytest.approx(
        2 * (off + 4 * 16 * (1 - (15 / 16) ** 30) * p["expert"]) + (live + 4 * 30 * 128) * 4096)


def test_prefill_flops_by_hand():
    model, engine = sizes(False)
    arch = run.load_arch(model)
    p = arch.part_params(model)
    sparse = p["attention"] + p["router"] + p["shared"] + 1 * p["expert"]  # 8 x 16 / 128 = one local choice
    active = (p["attention"] + p["dense_mlp"]) + 4 * sparse + (p["eh_proj"] + sparse)
    assert active == 1_476_132_864
    pair = 2 * 64 * (128 + 128)
    # 256 new positions at 3,000-3,255: the full layer and the module's see
    # i + 1 keys, a window layer 128.
    pairs = sum(i + 1 for i in range(3000, 3256))
    assert arch.prefill_flops(model, 256, pairs) == pytest.approx(
        2 * active * 256 + pair * (2 * pairs + 4 * 256 * 128))
    # A cold 64: every position sees fewer keys than the window.
    cold = sum(i + 1 for i in range(64))
    assert arch.prefill_flops(model, 64, cold) == pytest.approx(2 * active * 64 + pair * 6 * cold)
    assert arch.prefill_flops(model, 0, 0) == 0
    # With the draft off the module's projection and block are not counted.
    plain = {k: v for k, v in engine.items() if k != "draft"}
    assert arch.prefill_flops(model, 64, cold, plain) == pytest.approx(
        2 * (active - p["eh_proj"] - sparse) * 64 + pair * 5 * cold)


def test_the_mix_holds_the_issues_parameters_and_lengths():
    mix = traffic.load_mix("reason-closed")
    assert mix["arrivals"] == {"loop": "closed", "clients": 40}
    assert mix["prefix_tokens"] == 256 and "docs" not in mix and "reask_share" not in mix
    assert mix["unique"] == {"dist": "lognormal", "median": 640, "sigma": 0.9, "lo": 64, "hi": 4096}
    assert mix["max_tokens"] == {"dist": "lognormal", "median": 768, "sigma": 0.7, "lo": 128, "hi": 3072}
    assert (mix["temperature"], mix["top_p"], mix["max_total"]) == (0.0, 1.0, 7424)
    assert (mix["spec_requests"], mix["reference_len"]) == (256, [700, 900])
    others = {traffic.load_mix(n)["shape_seed"] for n in ("rag-closed", "rag-long-closed", "chat-closed")}
    assert mix["shape_seed"] not in others  # its own
    shapes = traffic.request_shapes(mix)
    prompts, out = 256 + shapes["unique"], shapes["max_tokens"]
    # What the parameters allow, and what this shape_seed draws.
    assert 256 + 64 == 320 and 256 + 4096 == 4352 and 4352 + 3072 == 7424
    assert (prompts.min(), int(np.median(prompts)), prompts.max()) == (321, 918, 4352)
    assert (out.min(), int(np.median(out)), out.max()) == (128, 728, 3072)
    assert ((prompts + out).min(), (prompts + out).max()) == (533, 7030)
    assert (prompts + out).max() <= 7424 < 8192 - 16  # the scheduler's admit limit
    # Output tokens are about half of all tokens (a tenth in the RAG cells).
    assert out.sum() / (prompts.sum() + out.sum()) == pytest.approx(0.433, abs=0.001)
    # Every request a run can need is generated (run.py asks for 4 a second).
    assert len(traffic.generate(mix, 2**31 + 5, 19200, 40 + 4 * 45 + 8)) == 228


def test_the_warm_up_drives_every_program_shape_of_the_mix():
    """A prompt that warms (a cold one, and a hit's suffix over one chunk)
    goes through the family of chunk programs that ``Scheduler.__init__``
    compiles; traffic has to drive the ``_prefill_suffix`` of a hit whose
    suffix is at most one chunk (suffix bucket x kv bucket over 256 + s),
    the graft of the 256 shared rows, and the verify chunk at every
    decode window (the power of two over the longest row + 2 x 8 + 1)."""
    from generativeaiexamples_tpu.utils.buckets import bucket_size

    mix = traffic.load_mix("reason-closed")

    def suffix_shape(depth, plen):
        if plen - depth > 256:
            return set()  # warms through the family
        s = bucket_size(plen - depth, minimum=16, dense=True)
        return {(s, bucket_size(depth + s, maximum=8192, dense=True))}

    warmed, windows, grafts = set(), set(), set()
    for i, burst in enumerate(mix["warmup"]):
        for r in burst["requests"]:
            depth = r["shared"] // 256 * 256 if i else 0  # the first burst is the cold base
            plen = r["shared"] + r["fresh"]
            warmed |= suffix_shape(depth, plen) if depth else set()
            if depth:
                grafts.add(bucket_size(depth, minimum=16, dense=True))
            if r["max_tokens"] > 1:
                windows.add(bucket_size(plen + 2 * 8 + 1, maximum=8192))
    assert mix["warmup"][0]["requests"][0]["shared"] >= 256  # the base leaves a snapshot at 256
    shapes = traffic.request_shapes(mix)
    needed, need_windows = set(), set()
    for unique, out in zip(shapes["unique"], shapes["max_tokens"]):
        plen = 256 + int(unique)
        needed |= suffix_shape(256, plen)
        need_windows |= {bucket_size(plen + n + 2 * 8 + 1, maximum=8192) for n in (0, int(out))}
    # Whatever the unique part's length (64-4,096), not only this seed's.
    for unique in (64, 65, 128, 129, 256):
        needed |= suffix_shape(256, 256 + unique)
    assert needed <= warmed == {(64, 384), (128, 384), (256, 512)}, sorted(needed - warmed)
    assert need_windows <= windows == {512, 1024, 2048, 4096, 8192}
    assert grafts == {256}  # the one depth a hit of this mix is restored at


# Between the markers of a traced window.
COUNTERS = {
    "draft_proposed": 30 * 8 * 60, "draft_accepted": 3, "verify_positions": 2 * 30 * 8 * 60,
    "decode_tokens_emitted": 30 * 8 * 60 + 3, "draft_rows_rewritten": 30 * 8 * 60 - 3,
}
READERS = {"draft_accept_pct": 100.0 * 3 / 14400, "verify_positions_per_token": 28800 / 14403}


@pytest.mark.parametrize("name", sorted(READERS))
def test_new_counter_readers(name):
    model, engine = sizes(False)
    read = load_reader(name)
    ctx = {"trace_counters": dict(COUNTERS), "counters": {}, "model": model, "engine": engine,
           "trace": None}
    assert read(ctx) == pytest.approx(READERS[name])
    assert read({**ctx, "trace_counters": None}) is None  # --trace 0
    zero = {**COUNTERS, "draft_proposed": 0, "decode_tokens_emitted": 0}
    assert read({**ctx, "trace_counters": zero}) is None
    # A program without the counter (the parent): nothing to read, no error.
    assert read({**ctx, "trace_counters": {"busy_ticks": 3, "prefix_tokens_reused": 5}}) is None
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    entry = next(m for m in spec["per_layer"] if m["name"] == name)
    assert entry["workloads"] == [CELL] and entry["source"] == "program_counter"
    assert entry in spec["per_layer"][-2:]  # appended, not inserted


def test_the_cell_reports_what_the_issue_lists():
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    e2e = {m["name"] for m in spec["end_to_end"] if CELL in m.get("workloads", [CELL])}
    assert e2e == {"itl_p95_ms", "out_tok_s", "setup_s"}  # not ttft: the wait for a slot
    listed = {m["name"] for m in spec["per_layer"] if CELL in m.get("workloads", [CELL])}
    assert listed == {
        "decode_lanes_mean", "decode_step_dev_ms", "decode_hbm_pct", "device_idle_pct.closed", "tick_ms",
        "prefill_chunks_per_tick", "host_starve_ms", "host_starve_ms.plan", "host_starve_ms.dispatch",
        "host_starve_ms.emit", "host_starve_ms.telemetry", "decode_kv_read_pct", "expert_local_pct",
        "expert_load_max_over_mean", "prefill_ahead_pct", "decode_ahead_pct", "decode_window_rows_pct",
        "prefill_rows_per_program", "draft_accept_pct", "verify_positions_per_token",
    }
    for m in spec["per_layer"]:
        if CELL in m.get("workloads", []):
            assert m["workloads"][-1] == CELL  # appended at the end of each list


def test_the_benchmarks_reference_is_the_programs_file():
    ours = (BENCH / "exaone_moe_reference.py").read_text()
    theirs = (REPO / "generativeaiexamples_tpu" / "models" / "exaone_moe_reference.py").read_text()
    assert ours == theirs
    imports = [l for l in ours.splitlines() if l.startswith(("import ", "from "))]
    assert not any("generativeaiexamples_tpu" in l or "ops" in l or "hybrid" in l for l in imports), imports


@pytest.fixture(scope="module")
def tiny():
    """The rehearsal's model with the program's served parameters."""
    from generativeaiexamples_tpu.engine.serving_models import serving_model

    model, engine = sizes(True)
    arch = run.load_arch(model)
    cfg = arch.llama_config(model, engine)
    params = serving_model(cfg, None, 256).prepare_params(
        None, quantize=False, matmul_kernel="xla", seed=5)
    return arch, cfg, params


TOKENS = [3 + 7 * i % 500 for i in range(600)]  # 37 windows of 16; two chunks and a padded one, 32 verified


def test_last_logits_holds_prefill_the_verify_step_and_the_module_to_the_reference(tiny, capsys):
    import exaone_moe_reference

    arch, cfg, params = tiny
    got = np.asarray(arch.last_logits(params, cfg, TOKENS, 768))
    want = np.asarray(exaone_moe_reference.all_logits(params, cfg, TOKENS)[0])[-1]
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)  # float32 both: the reference's
    line = json.loads(capsys.readouterr().out.splitlines()[-1])
    assert line["outside"] == [] and line["p90"] < 1e-3
    assert max(line["accept_p50"], line["reject_p50"], line["module_p50"]) < 1e-3
    shares, _ = arch.logit_shares(params, cfg, TOKENS, 768)
    # 568 positions prefilled; 32 verified two at a time, 31 one at a time
    # (the last has no next token); the module's: 1 + 16 + 31.
    assert {k: len(v) for k, v in shares.items()} == {"prefill": 568, "accept": 32, "reject": 31, "module": 48}


@pytest.mark.parametrize("control", ["no_qk_norm", "rope_on_full", "no_window", "stale_reject"])
def test_a_program_with_a_mechanism_switched_off_is_outside_a_limit(tiny, control):
    """The comparison sees a norm, a rotation, a window and a rejected
    draft's row: the program serves another configuration than the
    reference computes, or steps on from a rejection as if its position
    had been written."""
    arch, cfg, params = tiny
    served = dataclasses.replace(cfg, **{
        "no_qk_norm": dict(qk_norm=False),
        "rope_on_full": dict(rope_full=cfg.rope_window),
        "no_window": dict(sliding_window=cfg.max_seq_len),
        "stale_reject": {},
    }[control])
    shares, _ = arch.logit_shares(
        params, cfg, TOKENS, 768, served=served, stale_reject=control == "stale_reject")
    got = arch.share_quantiles(shares)
    if control == "stale_reject":
        # The prefill and the accept pass never reject: sound; the reject pass is not.
        assert got["reject_p50"] > 0.1 and max(got["p90"], got["accept_p50"]) < 1e-3, got
    else:
        assert min(got["p50"], got["accept_p50"], got["reject_p50"], got["module_p50"]) > 0.05, got


@pytest.mark.parametrize("outside", ["p10", "p50", "p90", "accept_p50", "reject_p50", "module_p50"])
def test_each_logit_share_limit_is_held(tiny, monkeypatch, capsys, outside):
    arch, cfg, params = tiny
    limits = sizes(False)[0]["reference"]["logit_share_limits"]
    assert sorted(limits) == ["accept_p50", "module_p50", "p10", "p50", "p90", "reject_p50"]
    assert limits["p10"] < limits["p50"] < limits["p90"]
    made_up = {k: 0.5 * v for k, v in limits.items()}
    made_up[outside] = 1.01 * limits[outside]
    monkeypatch.setattr(arch, "share_quantiles", lambda shares: dict(made_up))
    assert np.asarray(arch.last_logits(params, cfg, TOKENS[:90], 256)).shape == (cfg.vocab_size + 1,)
    assert json.loads(capsys.readouterr().out.splitlines()[-1])["outside"] == [outside]


def test_rehearsal_of_the_new_cell_is_correct():
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": str(REPO)}
    done = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", CELL, "--seed", str(2**31 + 33),
         "--seconds", "30", "--trace", "1", "--rehearse"],
        cwd=REPO, env=env, text=True, timeout=1500, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
    )
    assert done.returncode == 0, done.stderr[-3000:]
    lines = done.stdout.splitlines()
    checks = next(json.loads(l) for l in lines if l.startswith('{"bench": "checks"'))
    assert checks["arch"] == "exaone_moe" and checks["reference_check"]["ok"], checks
    assert checks["checks"]["no_compile_in_window"], checks["compiled_in_window"]
    state = next(json.loads(l) for l in lines if l.startswith('{"bench": "state bytes"'))
    assert state["state_bytes_window"] == 4 * 40 * 2 * 16 * 2 * 16 * 4  # rings of 16 at max_len 8192, 40 slots
    assert state["state_bytes_draft"] == 40 * (2 * 8192 * 2 * 16 * 4 + 64 * 4)
    result = json.loads(lines[-1])
    assert result["correct"] is True and result["failed"] == 0
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    listed = {m["name"] for m in spec["per_layer"] if CELL in m.get("workloads", [CELL])}
    on_the_cpu = listed - {"decode_hbm_pct", "decode_step_dev_ms"}  # peaks.json and module names: the chip's
    assert on_the_cpu <= set(result["metrics"]), sorted(on_the_cpu - set(result["metrics"]))
    metrics = {k: v["value"] for k, v in result["metrics"].items()}
    assert 1.9 <= metrics["verify_positions_per_token"] <= 2.0 and metrics["draft_accept_pct"] < 2
    # A full house goes ahead under the draft too: the lengths ride the device.
    assert metrics["decode_window_rows_pct"] < 10 and metrics["decode_ahead_pct"] > 80
    assert metrics["decode_lanes_mean"] > 16
