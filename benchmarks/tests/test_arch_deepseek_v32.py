"""``arch/deepseek_v32.py``: the mapping at both sizes, the file against the
catalog's row, the counts against the table of the configuration's cut
worked by hand, the traffic mix's lengths, the cell where its readers find
something, and the benchmark's copy of the reference against the
program's."""

import json
from pathlib import Path

import numpy as np
import pytest

import run
import traffic

BENCH = Path(__file__).resolve().parents[1]
REPO = BENCH.parent
NAME = "deepseek-v3.2-l5e16"
CELL = f"{NAME}.doc-reason-closed"
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")
M = 1e6


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
    assert Path(arch.__file__).name == "deepseek_v32.py"
    cfg = arch.llama_config(model, engine)
    assert type(cfg).__name__ == "PredictingLatentConfig"
    assert cfg.layer_kinds == (("mla", "dense"),) + (("mla", "experts"),) * 4
    assert (cfg.mtp_layers, cfg.mtp_kind, cfg.draft) == (1, ("mla", "experts"), "mtp")
    assert (cfg.n_experts, cfg.experts_held, cfg.expert_offset, cfg.n_group, cfg.topk_group) == (256, 16, 0, 8, 4)
    assert cfg.softmax_mscale == pytest.approx(1.3689, abs=1e-4) and cfg.rope_latent.factor == 40
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    # What the build holds: 11.38 GB of weights, 2.42 GB of latent rows and index keys.
    assert line["weight_bytes"] == 11_379_802_112  # 5,689,899,776 parameters in bf16, five router biases in float32
    assert line["state_bytes_full"] == 2_013_265_920 and line["state_bytes_draft"] == 402_882_560
    assert (line["latent_row_bytes_used"], line["latent_row_bytes_stored"], line["index_key_bytes"]) == (1152, 1280, 256)
    assert line["snapshot_bytes"] == 14_336 and line["state_bytes_window"] == 0
    tiny_model, tiny_engine = sizes(True)
    tiny = arch.llama_config(tiny_model, tiny_engine)
    # One expert layer and the module: two indexed blocks, at a size a CPU
    # prefills 110k tokens of in seconds; one reference prompt, one verify step a tick.
    assert tiny.layer_kinds == (("mla", "experts"),) and tiny.max_seq_len == 16384
    assert (tiny_engine["max_batch"], tiny_engine["decode_chunk_size"]) == (20, 1)
    assert tiny_model["reference"]["prompts"] == 1
    assert set(tiny_model["reference"]) >= {"min_within", "tolerance", "verify_positions",
                                            "logit_share_limits", "index_overlap_floors"}
    assert (tiny.index_topk, tiny.n_experts, tiny.experts_held, tiny.n_group, tiny.mtp_layers) == (8192, 16, 4, 2, 1)
    assert (tiny.dtype, tiny.kv_dtype) == ("float32", "float32")
    with pytest.raises(ValueError, match="experts_held"):
        arch.llama_config(model, {**engine, "experts_held": 32})


def test_the_file_keeps_every_published_key_and_lists_its_cut():
    model, engine = sizes(False)
    row = next(r for r in map(json.loads, CATALOG.read_text().splitlines()) if r["name"] == "DeepSeek-V3.2")
    assert model["source"] == row["source_url"]
    assert model["reduced"] == ["num_hidden_layers", "first_k_dense_replace", "n_routed_experts", "vocab_size"]
    assert model["reduced_from"] == {k: row["config"][k] for k in model["reduced"]}
    for key, value in row["config"].items():
        assert key in model, key
        if key not in model["reduced"]:
            assert model[key] == value, key
    assert (model["num_hidden_layers"], model["first_k_dense_replace"], model["n_routed_experts"],
            model["num_experts_published"], model["vocab_size"], model["num_nextn_predict_layers"]) == (
        5, 1, 16, 256, 16160, 1)
    assert (engine["max_batch"], engine["max_len"], engine["draft"], engine["experts_held"]) == (16, 16384, "mtp", 16)
    assert engine["decode_chunk_size"] == 4 and "0.5 %" in engine["decode_chunk_size_note"]
    for key in ("assumed", "not_served", "stands_for", "expect_paths", "reference", "rehearse"):
        assert model[key], key
    assert "16 chips" in model["stands_for"] or "SIXTEEN chips" in model["stands_for"]


def test_parameter_counts_are_the_issues_table():
    model, _ = sizes(False)
    p = run.load_arch(model).part_params(model)
    assert p["attention"] / M == pytest.approx(187.11, abs=1e-2)  # W_qa 11.01, W_qb 37.75, W_kva 4.13, W_kvb 16.78, W_o 117.44
    assert p["indexer"] / M == pytest.approx(13.96, abs=1e-2)  # W_qI 12.58, W_kI 0.92, W_w 0.46
    assert (p["attention"] + p["indexer"]) / M == pytest.approx(201.07, abs=1e-2)
    assert p["dense"] / M == pytest.approx(396.36, abs=1e-2)
    assert p["router"] / M == pytest.approx(1.84, abs=1e-2) and p["expert"] / M == pytest.approx(44.04, abs=1e-2)
    assert p["shared"] == p["expert"] and p["eh_proj"] / M == pytest.approx(102.76, abs=1e-2)
    assert 2 * p["head"] / M == pytest.approx(231.7, abs=0.1)
    mixer = p["attention"] + p["indexer"]
    layer = mixer + p["router"] + p["shared"] + 16 * p["expert"]
    total = mixer + p["dense"] + 4 * layer + layer + p["eh_proj"] + 2 * p["head"]
    assert total / M == pytest.approx(5689.8, abs=0.2)


def test_decode_step_bytes_by_hand():
    model, engine = sizes(False)
    arch = run.load_arch(model)
    p = arch.part_params(model)
    rows, live = 14.0, 14 * 7000.0
    engine = {**engine, "roofline_decode_rows": 14}
    mixer = p["attention"] + p["indexer"]
    once = 5 * mixer + p["dense"] + 4 * (p["router"] + p["shared"]) + p["head"]
    once += p["eh_proj"] + mixer + p["router"] + p["shared"]
    miss = 1 - 8 / 256
    touched = 4 * 16 * (1 - miss**28) + 16 * (1 - miss**14)
    # Six blocks: the index key of every live token, and ONE position's set plus one row a slot.
    state = 6 * (live * 256 + rows * 2049 * 1152)
    want = 2 * (once + touched * p["expert"]) + state
    assert arch.decode_step_bytes(model, engine, live) == pytest.approx(want, rel=1e-12)
    assert 8.0e9 < want < 8.6e9  # 8.28 GB: 10 ms at the HBM peak
    # A slot shorter than index_topk reads what it holds, and one row more.
    short = arch.decode_step_bytes(model, engine, 14 * 1000.0)
    assert want - short == pytest.approx(6 * (14 * 6000 * 256 + 14 * 1048 * 1152), rel=1e-9)
    # The draft off: no module, one position a row.
    off = arch.decode_step_bytes(model, {**engine, "draft": ""}, live)
    assert off < want - 2 * (p["eh_proj"] + mixer)


def test_prefill_flops_by_hand():
    model, _ = sizes(False)
    arch = run.load_arch(model)
    p = arch.part_params(model)
    n = 4096.0
    pairs = n * (n + 1) / 2
    mixer = p["attention"] + p["indexer"]
    sparse = mixer + p["router"] + p["shared"] + 0.5 * p["expert"]  # 8 x 16 / 256 choices land here
    active = mixer + p["dense"] + 4 * sparse + p["eh_proj"] + sparse
    attended = 2048 * 2049 / 2 + (n - 2048) * 2048
    want = 2 * active * n + 6 * (2 * 64 * 128 * pairs + 2 * 128 * (128 + 64 + 128) * attended)
    assert arch.prefill_flops(model, n, pairs) == pytest.approx(want, rel=1e-9)
    # Under index_topk every pair is attended.
    small = 1024.0
    few = small * (small + 1) / 2
    assert arch.prefill_flops(model, small, few) == pytest.approx(
        2 * active * small + 6 * (2 * 64 * 128 + 2 * 128 * 320) * few, rel=1e-9)


def test_the_mix_holds_the_issues_parameters_and_lengths():
    mix = traffic.load_mix("doc-reason-closed")
    assert mix["arrivals"] == {"loop": "closed", "clients": 20}
    assert (mix["prefix_tokens"], mix["temperature"], mix["top_p"], mix["max_total"]) == (256, 0.0, 1.0, 13824)
    assert mix["unique"] == {"dist": "lognormal", "median": 5120, "sigma": 0.4, "lo": 2560, "hi": 10240}
    assert mix["max_tokens"] == {"dist": "lognormal", "median": 1024, "sigma": 0.6, "lo": 256, "hi": 3072}
    assert mix["reference_len"] == [4352, 4864] and "docs" not in mix and not mix.get("reask_share")
    requests = traffic.generate(mix, 7, 16160, 200, 0.0)
    prompts = np.array([len(r["prompt"]) for r in requests])
    answers = np.array([r["max_tokens"] for r in requests])
    assert prompts.min() >= 2816 and prompts.max() <= 10496 and (prompts > 2048).all()
    assert answers.min() >= 256 and answers.max() <= 3072 and (prompts + answers).max() <= 13824
    assert 4800 < np.median(prompts) < 5900 and 900 < np.median(answers) < 1200


def test_the_cell_is_listed_where_its_readers_find_something():
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (NAME, "doc-reason-closed", 1)
    judged = {m["name"] for m in bench["end_to_end"] if "workloads" not in m or CELL in m["workloads"]}
    assert judged == {"itl_p95_ms", "out_tok_s", "setup_s"}
    listed = {m["name"]: m for m in bench["per_layer"] if CELL in m.get("workloads", [])}
    for name in ("decode_hbm_pct", "decode_lanes_mean", "device_idle_pct.closed", "decode_index_rows_pct",
                 "decode_latent_rows_pct", "decode_experts_touched_pct", "expert_local_pct",
                 "expert_load_max_over_mean", "expert_streams_per_touched", "draft_accept_pct",
                 "verify_positions_per_token", "prefill_pad_pct.itl", "tick_ms", "decode_kv_read_pct",
                 "verify_gather_pct", "draft_rows_rewritten_per_token"):
        assert name in listed, name
    # A reading reaches the cell only where the cell judges what it moves.
    assert {m["moves"] for m in listed.values()} <= judged
    assert bench["per_layer"][-2]["name"] == "verify_gather_pct"
    assert bench["per_layer"][-1]["name"] == "draft_rows_rewritten_per_token"
    for m in bench["per_layer"][-2:]:
        assert m["workloads"] == [CELL] and (BENCH / "layer_metrics" / f"{m['name']}.py").exists()


def test_the_benchmarks_reference_is_the_programs_file():
    mine = (BENCH / "deepseek_v32_reference.py").read_bytes()
    theirs = (REPO / "generativeaiexamples_tpu" / "models" / "deepseek_v32_reference.py").read_bytes()
    assert mine == theirs
