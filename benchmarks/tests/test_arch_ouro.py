"""``arch/ouro.py``: the mapping at both sizes, the file against the
catalog's row, the counts against ISSUE 51's table worked by hand, the
cell's entries, the new reader on canned counters and a canned trace, the
benchmark's copy of the reference against the program's, and what the
parent commit does with the cell (it fails at once)."""

import dataclasses
import json
from pathlib import Path

import pytest

import run
from metrics_lib import load_reader

BENCH = Path(__file__).resolve().parents[1]
REPO = BENCH.parent
NAME = "ouro-2.6b"
CELL = f"{NAME}.chat-short-closed"
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")


def sizes(rehearse):
    model = json.loads((BENCH / "configs" / f"{NAME}.json").read_text())
    engine = dict(model["engine"])
    if rehearse:  # as run.py lays them over
        model.update(model["rehearse"]["model"])
        engine.update(model["rehearse"]["engine"])
    return model, engine


def test_mapping_at_the_published_and_the_rehearsal_sizes(capsys):
    from generativeaiexamples_tpu.models import llama

    model, engine = sizes(False)
    arch = run.load_arch(model)
    assert Path(arch.__file__).name == "ouro.py"
    cfg = arch.llama_config(model, engine)
    # What ``engine.server --model ouro-2.6b --kv-dtype int8 --max-len 768`` builds.
    assert cfg == llama.PRESETS["ouro-2.6b"](max_seq_len=768, kv_dtype="int8")
    assert (cfg.n_layers, cfg.ut_steps, cfg.cache_planes, cfg.sandwich_norm) == (48, 4, 192, True)
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["cache_planes"] == 192 and line["kv_bytes_per_token"] == 798_720
    assert line["slot_bytes"] == 16 * 768 * 798_720 == 9_814_671_360  # 9.81 GB
    assert round(line["stack_weight_bytes"] / 1e9, 2) == 2.47 and round(line["head_weight_bytes"] / 1e9, 2) == 0.10
    tiny = arch.llama_config(*sizes(True))
    assert (tiny.n_layers, tiny.ut_steps, tiny.cache_planes, tiny.vocab_size) == (2, 4, 8, 512)
    assert (tiny.n_heads, tiny.n_kv_heads, tiny.head_dim, tiny.d_model) == (4, 4, 16, 64)


def test_the_parent_fails_the_cell_at_once(monkeypatch):
    """A program whose ``LlamaConfig`` has no ``ut_steps`` cannot run the
    configuration: the mapping says so and exits, before anything is built."""
    from generativeaiexamples_tpu.models import llama

    fields = [(f.name, f.type, f) for f in dataclasses.fields(llama.LlamaConfig)
              if f.name not in ("ut_steps", "sandwich_norm", "early_exit_threshold")]
    parent = dataclasses.make_dataclass("LlamaConfig", fields, frozen=True)
    monkeypatch.setattr(llama, "LlamaConfig", parent)
    model, engine = sizes(False)
    with pytest.raises(SystemExit, match="no looped stack"):
        run.load_arch(model).llama_config(model, engine)


def test_the_file_holds_the_published_config_and_cuts_nothing():
    model, engine = sizes(False)
    assert model["reduced"] == [] and model["arch"] == "ouro"
    assert (model["num_hidden_layers"], model["total_ut_steps"], model["early_exit_threshold"]) == (48, 4, 1)
    assert (model["hidden_size"], model["intermediate_size"], model["vocab_size"]) == (2048, 5632, 49152)
    assumed = " ".join(model["assumed"])
    for needle in ("four RMSNorms", "EVERY pass", "(pass, layer)", "sigmoid", "early_exit_threshold",
                   "no bias", "rotate_half", "silu GATED", "arXiv:2510.25741", "modeling_ouro.py"):
        assert needle in assumed, needle
    for needle in ("2.67 GB", "192 planes", "798,720 B", "9.81 GB", "--max-batch 16 --max-len 768"):
        assert needle in model["stands_for"], needle
    assert engine == {"weight_dtype": "int8", "kv_dtype": "int8", "max_batch": 16, "max_len": 768,
                      "decode_chunk_size": 8, "prefill_chunk_tokens": 256, "prefix_cache": "shared",
                      "kv_layout": "contiguous", "matmul_kernel": "xla"}
    assert model["expect_paths"] == {"decode_attention": "pallas"}
    ref = model["reference"]
    assert ref["decode_positions"] % engine["decode_chunk_size"] == 0
    assert set(ref["logit_share_limits"]) == {"p50", "p90", "decode_p50", "kernel_decode_p50", "kernel_row_max"}
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    entry = next(c for c in spec["configs"] if c["name"] == NAME)
    assert entry["reduced"] == [] and entry["source"] == model["source"]
    assert entry["file"] == f"benchmarks/configs/{NAME}.json"
    if CATALOG.exists():
        row = next(json.loads(l) for l in open(CATALOG) if '"name": "Ouro-2.6B"' in l)
        assert model["source"] == row["source_url"]
        for key, value in row["config"].items():
            assert model[key] == value, key
    rehearse = model["rehearse"]["model"]
    assert (rehearse["num_hidden_layers"], rehearse["hidden_size"], rehearse["vocab_size"]) == (2, 64, 512)
    assert "total_ut_steps" not in rehearse  # the four passes are kept


def test_the_cell_and_its_mix():
    import traffic

    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    cell = next(w for w in spec["workloads"] if w["name"] == CELL)
    assert cell == {"name": CELL, "config": NAME, "traffic": "chat-short-closed", "chips": 1, "why": cell["why"]}
    assert len(cell["why"]) <= 200 and "24 clients on 16 slots" in cell["why"]
    judged = {m["name"] for m in spec["end_to_end"] if CELL in m.get("workloads", [CELL])}
    assert judged == {"itl_p95_ms", "setup_s"}
    mix = traffic.load_mix("chat-short-closed")
    assert mix["arrivals"] == {"loop": "closed", "clients": 24}
    assert (mix["prefix_tokens"], mix["temperature"], mix["top_p"], mix["max_total"]) == (24, 0.2, 0.7, 728)
    shapes = traffic.request_shapes(mix)
    prompts = mix["prefix_tokens"] + shapes["unique"]
    assert (prompts.min(), prompts.max()) == (40, 408)
    assert (shapes["max_tokens"].min(), shapes["max_tokens"].max()) == (32, 320)
    # Rows pass 503, so the decode window of the whole slot is entered.
    assert (prompts + shapes["max_tokens"]).max() > 512 - 9
    lo, hi = mix["reference_len"]
    assert lo <= 256 < hi  # both admission paths
    listed = {m["name"] for m in spec["per_layer"] if CELL in m.get("workloads", [])}
    assert {"decode_pass_dev_ms", "decode_hbm_pct.itl", "decode_kv_read_pct", "out_tok_s_closed",
            "decode_lanes_mean.itl", "device_idle_pct.itl", "admit_lone_pct", "tick_ms"} <= listed
    assert not any("roofline" in n or "mfu" in n for n in listed)


def test_the_counts_are_the_issues_table():
    model, engine = sizes(False)
    arch = run.load_arch(model)
    layer = 4 * 2048**2 + 3 * 2048 * 5632  # the projections (int8: a byte each); 4 x 2,048 gains besides
    assert layer + 4 * 2048 == 51_388_416
    assert arch.stack_weight_bytes(model, engine) == 48 * layer == 2_466_250_752
    assert arch.head_weight_bytes(model, engine) == 2048 * 49152 == 100_663_296
    # A token's cache: 192 planes x (K, V) x 16 heads x (128 int8 + one bf16 scale).
    assert arch.kv_bytes_per_token(model, engine) == 192 * 2 * 16 * 130 == 798_720
    # A step at ~15 lanes of ~350 rows: 4 x 2.47 GB + 0.10 + 5,250 x 0.80 MB = 14.2 GB.
    step = arch.decode_step_bytes(model, engine, 5250)
    assert step == 4 * 48 * layer + 100_663_296 + 5250 * 798_720
    assert round(step / 1e9, 1) == 14.2 and round(5250 * 798_720 / step, 2) == 0.30
    assert arch.decode_step_bytes(model, engine, 0) == 4 * 48 * layer + 100_663_296
    # A 256-token chunk from position 0: 4 x 2 x 2.47 G x 256 = 5.05 TFLOP and the pairs.
    pairs = 256 * 257 / 2
    want = 4 * (2 * 48 * layer * 256 + 4 * 48 * 16 * 128 * pairs)
    assert arch.prefill_flops(model, 256, pairs) == pytest.approx(want)
    assert round(4 * 2 * 48 * layer * 256 / 1e12, 2) == 5.05
    assert arch.prefill_flops(model, 0, 0) == 0


def test_the_new_reader():
    """Between the markers: 40 decode chunks of 8 steps of 4 passes, 9.6 s
    of ``decode_chunk`` on the device."""
    model, engine = sizes(False)
    read = load_reader("decode_pass_dev_ms")
    trace = {"modules": {"jit_decode_chunk": {"count": 40.0, "dev_s": 9.6}, "jit__prefill_suffix": {"count": 9.0, "dev_s": 0.3}}}
    ctx = {"trace": trace, "trace_counters": {"decode_stack_passes": 40 * 8 * 4}, "counters": {},
           "model": model, "engine": engine}
    assert read(ctx) == pytest.approx(7.5)
    assert load_reader("decode_step_dev_ms")(ctx) == pytest.approx(30.0)  # four passes a step
    assert read({**ctx, "trace": None}) is None and read({**ctx, "trace_counters": None}) is None
    # A program without the counter (the parent): nothing to read, no error.
    assert read({**ctx, "trace_counters": {"decode_chunks": 40}}) is None
    assert read({**ctx, "trace": {"modules": {}}}) is None
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    assert spec["per_layer"][-1] == {
        "name": "decode_pass_dev_ms", "unit": "ms", "better": "lower", "source": "device_trace",
        "layer": "step programs", "moves": "itl_p95_ms", "workloads": ["mistral-7b.chat-closed", CELL]}


def test_the_benchmarks_reference_is_the_programs_byte_for_byte():
    ours = REPO / "generativeaiexamples_tpu" / "models" / "ouro_reference.py"
    assert (BENCH / "ouro_reference.py").read_bytes() == ours.read_bytes()
