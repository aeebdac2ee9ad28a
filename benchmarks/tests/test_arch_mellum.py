"""``arch/mellum.py``: the mapping at both sizes, the counts against the
table of the configuration's cut worked by hand, the traffic mix's
lengths, the new counter readers on a made-up ``ctx``, the benchmark's
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
NAME = "mellum2-12b-a2.5b-l12"
CELL = f"{NAME}.rag-long-closed"
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")
KINDS = ((("window", "experts"),) * 3 + (("full", "experts"),)) * 3


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
    assert Path(arch.__file__).name == "mellum.py"
    cfg = arch.llama_config(model, engine)
    assert cfg.layer_kinds == KINDS and arch.layer_kinds(model) == [m for m, _ in KINDS]
    assert (cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.attn_head_dim) == (2304, 32, 4, 128)
    assert (cfg.n_experts, cfg.experts_held, cfg.n_experts_per_tok, cfg.moe_d_ff) == (64, 64, 8, 896)
    assert (cfg.sliding_window, cfg.vocab_size, cfg.max_seq_len) == (1024, 98304, 8192)
    assert (cfg.score_function, cfg.router_bias, cfg.norm_topk, cfg.shared_d_ff) == ("softmax", False, True, 0)
    assert (cfg.rope_full.rope_type, cfg.rope_full.factor, cfg.rope_full.original_max) == ("yarn", 16.0, 8192)
    assert cfg.rope_full.attention_factor == 1.2772588722239782 and cfg.rope_window.rope_type == "default"
    assert (cfg.dtype, cfg.kv_dtype) == ("bfloat16", "bfloat16")
    tiny_model, tiny_engine = sizes(True)
    tiny = arch.llama_config(tiny_model, tiny_engine)
    assert tiny.layer_kinds == KINDS[:4] and tiny.sliding_window == 64 and tiny.max_seq_len == 8192
    assert (tiny.n_experts, tiny.n_experts_per_tok, tiny.dtype, tiny.kv_dtype) == (8, 3, "float32", "float32")
    assert tiny.rope_full.original_max == 512


def test_the_file_keeps_every_published_width_and_lists_its_cuts():
    model, engine = sizes(False)
    assert model["reduced"] == ["num_hidden_layers"] and model["reduced_from"] == {"num_hidden_layers": 28}
    assert len(model["assumed"]) >= 8 and "MTP" in model["stands_for"] and "10.93 GB" in model["stands_for"]
    assert (model["hidden_size"], model["num_attention_heads"], model["num_key_value_heads"],
            model["head_dim"], model["moe_intermediate_size"], model["num_experts"],
            model["num_experts_per_tok"], model["sliding_window"], model["vocab_size"]) == (
        2304, 32, 4, 128, 896, 64, 8, 1024, 98304)
    assert engine == {**engine, "weight_dtype": "bfloat16", "kv_dtype": "bfloat16", "max_len": 8192,
                      "decode_chunk_size": 8, "prefill_chunk_tokens": 256, "prefix_cache": "shared",
                      "kv_layout": "contiguous", "matmul_kernel": "xla"}
    assert 16 <= engine["max_batch"] <= 32
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    entry = next(c for c in spec["configs"] if c["name"] == NAME)
    assert entry["reduced"] == model["reduced"] and entry["source"] == model["source"]
    if CATALOG.exists():
        row = next(json.loads(l) for l in open(CATALOG) if '"name": "Mellum2-12B-A2.5B-Instruct"' in l)
        assert model["source"] == row["source_url"]
        for key, value in row["config"].items():
            if key not in model["reduced"]:
                assert model[key] == value, key


def test_parameter_counts_are_the_tables():
    model, _ = sizes(False)
    p = run.load_arch(model).part_params(model)
    # By hand, from the published widths (ISSUE 31's table).
    assert p["attention"] == 2304 * 4096 + 2 * 2304 * 512 + 4096 * 2304 == 21_233_664
    assert p["router"] == 2304 * 64 == 147_456
    assert p["expert"] == 3 * 2304 * 896 == 6_193_152 and 64 * p["expert"] == 396_361_728
    assert p["head"] == 98304 * 2304 == 226_492_416
    layer = p["attention"] + p["router"] + 64 * p["expert"]
    assert round(layer / 1e6, 1) == 417.7 and round(layer * 2 / 1e9, 3) == 0.835
    assert round((12 * layer + 2 * p["head"]) * 2 / 1e9, 2) == 10.93  # GB in bf16, the table's total


def test_decode_step_bytes_by_hand():
    model, engine = sizes(False)
    arch = run.load_arch(model)
    p = arch.part_params(model)
    once = 12 * (p["attention"] + p["router"]) + p["head"]
    # 13 rows x 8 choices over 64 experts: a row misses a given expert
    # with probability 7/8.
    touched = 64 * (1 - (7 / 8) ** 13)
    assert arch.experts_touched(model, 13) == pytest.approx(touched) and 52 < touched < 53
    assert arch.kv_bytes_per_row(model, engine) == 2 * 4 * 128 * 2 == 2048
    # 13 rows of 3,500 tokens: 3 full layers read every token, 9 window
    # layers the last 1,024 of each row.
    live = 13 * 3500
    want = 2 * (once + 12 * touched * p["expert"]) + (3 * live + 9 * 13 * 1024) * 2048
    assert arch.decode_step_bytes(model, engine, live) == pytest.approx(want)
    assert 9.2e9 < want < 9.5e9  # 0.97 GB once, 7.83 GB of experts, 0.28 + 0.25 GB of K/V
    # Rows shorter than the window read what they have.
    short = arch.decode_step_bytes(model, engine, 13 * 500) - arch.decode_step_bytes(model, engine, 0)
    assert short == pytest.approx(12 * 13 * 500 * 2048)
    assert arch.decode_step_bytes(model, {**engine, "roofline_decode_rows": 32}, 32 * 1024) > want - 3 * live * 2048


def test_prefill_flops_by_hand():
    model, _ = sizes(False)
    arch = run.load_arch(model)
    p = arch.part_params(model)
    active = 12 * (p["attention"] + p["router"] + 8 * p["expert"])  # all 8 experts are here
    assert round(active / 1e6) == 851
    pair = 2 * 32 * (128 + 128)
    # 256 new positions at 3,000-3,255: a full layer sees i + 1 keys, a
    # window layer 1,024.
    pairs = sum(i + 1 for i in range(3000, 3256))
    assert arch.prefill_flops(model, 256, pairs) == pytest.approx(
        2 * active * 256 + pair * (3 * pairs + 9 * 256 * 1024))
    # A cold 256: every position sees fewer keys than the window.
    cold = sum(i + 1 for i in range(256))
    assert arch.prefill_flops(model, 256, cold) == pytest.approx(2 * active * 256 + pair * 12 * cold)
    assert arch.prefill_flops(model, 0, 0) == 0


def test_the_mix_holds_the_issues_parameters_and_lengths():
    mix = traffic.load_mix("rag-long-closed")
    assert mix["arrivals"] == {"loop": "closed", "clients": 16}
    assert (mix["prefix_tokens"], mix["reask_share"], mix["reask_back"]) == (192, 0.2, 8)
    assert mix["docs"] == {"pool": 64, "per_request": 6, "len": {"lo": 256, "hi": 352}, "zipf_s": 1.0}
    assert mix["unique"] == {"dist": "lognormal", "median": 768, "sigma": 1.1, "lo": 16, "hi": 5120}
    assert mix["max_tokens"] == {"dist": "uniform", "lo": 64, "hi": 256}
    assert (mix["temperature"], mix["top_p"], mix["max_total"]) == (0.2, 0.7, 7680)
    assert (mix["spec_requests"], mix["reference_len"]) == (240, [1300, 1536])
    assert mix["shape_seed"] != traffic.load_mix("rag-closed")["shape_seed"]  # its own
    shapes = traffic.request_shapes(mix)
    shared = 192 + np.array([sum(int(shapes["doc_lens"][d]) for d in p) for p in shapes["picks"]])
    prompts = shared + shapes["unique"]
    # What the parameters allow, and what this shape_seed draws.
    assert 192 + 6 * 256 + 16 == 1744 and 192 + 6 * 352 + 5120 == 7424
    assert 1744 <= prompts.min() == 2019 and prompts.max() == 7193 <= 7424
    assert 1728 <= shared.min() and shared.max() <= 2304 < 4096  # under the warm-up base
    assert int(np.median(prompts)) == 2820
    assert (prompts > 4096).mean() == pytest.approx(0.1875) and (prompts > 6144).mean() == pytest.approx(1 / 15)
    assert (prompts + shapes["max_tokens"]).max() <= 7680 < 8192 - 8  # the scheduler's admit limit
    # Every request a run can need is generated (run.py asks for 8 a second).
    assert len(traffic.generate(mix, 2**31 + 5, 98304, 16 + 8 * 45 + 8)) == 384


def test_the_warm_up_drives_every_program_shape_of_the_mix():
    """Chunks start at multiples of 256 (a cold prompt's, and a hit's,
    restored at a snapshot boundary).  The plan's (suffix bucket,
    kv_bucket) pairs cover those of every request of the mix whatever its
    hit depth, its decode windows, and the reference check's."""
    from generativeaiexamples_tpu.utils.buckets import bucket_size

    mix = traffic.load_mix("rag-long-closed")

    def chunk_shapes(start, plen):
        out = set()
        for pos in range(start, plen, 256):
            s = bucket_size(min(256, plen - pos), minimum=16, dense=True)
            out.add((s, bucket_size(pos + s, maximum=8192, dense=True)))
        return out

    warmed, windows, grafts = set(), set(), set()
    for burst in mix["warmup"]:
        for r in burst["requests"]:
            depth = r["shared"] // 256 * 256 if r is not mix["warmup"][0]["requests"][0] else 0
            plen = r["shared"] + r["fresh"]
            warmed |= chunk_shapes(depth, plen)
            if depth:
                grafts.add(bucket_size(depth, minimum=16, dense=True))
            if r["max_tokens"] > 1:
                windows.add(bucket_size(plen + 8 + 1, maximum=8192))
    warmed |= chunk_shapes(0, mix["reference_len"][1] - 4)  # run.py's own warm request
    shapes = traffic.request_shapes(mix)
    needed, need_windows, need_grafts = set(), set(), set()
    for picks, unique, out in zip(shapes["picks"], shapes["unique"], shapes["max_tokens"]):
        shared = 192 + sum(int(shapes["doc_lens"][d]) for d in picks)
        plen = shared + int(unique)
        for depth in range(0, shared + 1, 256):  # any snapshot may have been pushed out
            needed |= chunk_shapes(depth, plen)
            if depth:
                need_grafts.add(bucket_size(depth, minimum=16, dense=True))
        need_windows |= {bucket_size(plen + n + 8 + 1, maximum=8192) for n in (0, int(out))}
    for plen in range(mix["reference_len"][0], mix["reference_len"][1] + 1):
        needed |= chunk_shapes(0, plen)
    assert needed <= warmed, sorted(needed - warmed)
    assert need_windows <= windows == {2048, 4096, 8192}
    assert need_grafts <= grafts, sorted(need_grafts - grafts)


# Between the markers of a traced window.
COUNTERS = {
    "attn_rows_read_window_decode": 9 * 32 * 1024 * 80, "attn_rows_dense_window_decode": 9 * 32 * 4096 * 80,
    "attn_rows_read_full_decode": 3 * 32 * 4096 * 80,
    "attn_rows_read_window_prefill": 9 * 1024 * 50, "attn_rows_dense_window_prefill": 9 * 3072 * 50,
    "attn_rows_read_full_prefill": 3 * 3072 * 50,
}
READERS = {"decode_window_rows_pct": 25.0, "prefill_window_rows_pct": 100.0 / 3}


@pytest.mark.parametrize("name", sorted(READERS))
def test_new_counter_readers(name):
    model, engine = sizes(False)
    read = load_reader(name)
    ctx = {"trace_counters": dict(COUNTERS), "counters": {}, "model": model, "engine": engine,
           "trace": None}
    assert read(ctx) == pytest.approx(READERS[name])
    assert read({**ctx, "trace_counters": None}) is None  # --trace 0
    phase = name.split("_")[0]
    zero = {**COUNTERS, f"attn_rows_dense_window_{phase}": 0}
    assert read({**ctx, "trace_counters": zero}) is None
    # A program without the counter (the parent): nothing to read, no error.
    assert read({**ctx, "trace_counters": {"busy_ticks": 3, "prefix_tokens_reused": 5}}) is None
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    entry = next(m for m in spec["per_layer"] if m["name"] == name)
    assert entry["workloads"] == [CELL] and entry["better"] == "lower" and entry["layer"] == "step programs"


def test_the_benchmarks_reference_is_the_programs_file():
    ours = (BENCH / "mellum_reference.py").read_text()
    theirs = (REPO / "generativeaiexamples_tpu" / "models" / "mellum_reference.py").read_text()
    assert ours == theirs
    imports = [l for l in ours.splitlines() if l.startswith(("import ", "from "))]
    assert not any("generativeaiexamples_tpu" in l or "ops" in l or "hybrid" in l for l in imports), imports


@pytest.fixture(scope="module")
def tiny():
    """The rehearsal's model with the program's random parameters."""
    import jax
    from generativeaiexamples_tpu.models import hybrid

    model, engine = sizes(True)
    arch = run.load_arch(model)
    cfg = arch.llama_config(model, engine)
    return arch, cfg, hybrid.init_params(cfg, jax.random.PRNGKey(5))


TOKENS = [3 + 7 * i % 500 for i in range(600)]  # nine windows of 64; two chunks and a padded one


def test_last_logits_holds_prefill_and_decode_to_the_reference(tiny, capsys):
    import mellum_reference

    arch, cfg, params = tiny
    got = np.asarray(arch.last_logits(params, cfg, TOKENS, 768))
    want = np.asarray(mellum_reference.all_logits(params, cfg, TOKENS))[-1]
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)  # float32 both: the reference's
    line = json.loads(capsys.readouterr().out.splitlines()[-1])
    assert line["outside"] == [] and line["p90"] < 1e-3 and line["decode_p50"] < 1e-3
    share, _ = arch.logit_shares(params, cfg, TOKENS, 768)
    assert share.shape == (600,)  # 584 positions prefilled, 16 decoded


@pytest.mark.parametrize("control", ["no_window", "no_yarn", "top_k_not_renormalised"])
def test_a_program_with_a_mechanism_switched_off_is_outside_a_limit(tiny, capsys, control):
    """The comparison sees a window, a frequency and a routing weight: the
    program serves another configuration than the reference computes."""
    arch, cfg, params = tiny
    served = dataclasses.replace(cfg, **{
        "no_window": dict(sliding_window=cfg.max_seq_len),
        "no_yarn": dict(rope_full=cfg.rope_window),
        "top_k_not_renormalised": dict(norm_topk=False),
    }[control])
    real = arch._programs
    try:
        arch._programs = lambda _cfg, window: real(served, window)
        vetoed = np.asarray(arch.last_logits(params, cfg, TOKENS, 768))
    finally:
        arch._programs = real
    line = json.loads(capsys.readouterr().out.splitlines()[-1])
    assert "p50" in line["outside"] and "decode_p50" in line["outside"], line
    # One entry more than the vocabulary holds the maximum: gap 1 for any served token.
    assert vetoed.shape == (cfg.vocab_size + 1,) and vetoed.argmax() == cfg.vocab_size


@pytest.mark.parametrize("outside", ["p10", "p50", "p90", "decode_p50"])
def test_each_logit_share_limit_is_held(tiny, monkeypatch, capsys, outside):
    arch, cfg, params = tiny
    limits = sizes(False)[0]["reference"]["logit_share_limits"]
    assert sorted(limits) == ["decode_p50", "p10", "p50", "p90"] and limits["p10"] < limits["p50"] < limits["p90"]
    made_up = {k: 0.5 * v for k, v in limits.items()}
    made_up[outside] = 1.01 * limits[outside]
    monkeypatch.setattr(arch, "share_quantiles", lambda share, n: dict(made_up))
    assert np.asarray(arch.last_logits(params, cfg, TOKENS[:90], 256)).shape == (cfg.vocab_size + 1,)
    assert json.loads(capsys.readouterr().out.splitlines()[-1])["outside"] == [outside]


def test_rehearsal_of_the_new_cell_is_correct():
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": str(REPO)}
    done = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", CELL, "--seed", str(2**31 + 31),
         "--seconds", "40", "--trace", "1", "--rehearse"],
        cwd=REPO, env=env, text=True, timeout=1500, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
    )
    assert done.returncode == 0, done.stderr[-3000:]
    lines = done.stdout.splitlines()
    checks = next(json.loads(l) for l in lines if l.startswith('{"bench": "checks"'))
    assert checks["arch"] == "mellum" and checks["reference_check"]["ok"], checks
    assert checks["checks"]["no_compile_in_window"], checks["compiled_in_window"]
    state = next(json.loads(l) for l in lines if l.startswith('{"bench": "state bytes"'))
    assert state["state_bytes_window"] == 3 * 32 * 2 * 2 * 64 * 16 * 4  # a ring of 64 at max_len 8192
    result = json.loads(lines[-1])
    assert result["correct"] is True and result["failed"] == 0
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    listed = [m["name"] for m in spec["per_layer"] if CELL in m.get("workloads", [CELL])]
    assert "expert_local_pct" not in listed and {"decode_window_rows_pct", "prefill_window_rows_pct"} <= set(listed)
    on_the_cpu = set(listed) - {"decode_hbm_pct", "prefill_mxu_pct"}  # peaks.json has no CPU
    assert on_the_cpu <= set(result["metrics"]), sorted(on_the_cpu - set(result["metrics"]))
    assert result["metrics"]["prefix_reuse_pct"]["value"] > 0  # ring snapshots are hit
    assert result["metrics"]["decode_window_rows_pct"]["value"] < 60
