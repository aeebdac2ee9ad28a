"""bench.py glue smoke: every phase runs end to end at tiny scale on CPU.

The bench is the driver's headline artifact and may get exactly ONE shot
on real hardware per round — a Python-level bug in any phase (a renamed
scheduler kwarg, a changed stats key) must fail HERE, not there.  Scales
are shrunk to seconds; numbers are not asserted, only the contract
(phases complete, expected keys present, sane types).
"""

import json
import os

import numpy as np
import pytest

import bench
from generativeaiexamples_tpu.models import llama


@pytest.fixture()
def tiny_bench(monkeypatch):
    monkeypatch.setattr(bench, "BATCH", 4)
    monkeypatch.setattr(bench, "MAX_LEN", 64)
    monkeypatch.setattr(bench, "PROMPT_LEN", 16)
    monkeypatch.setattr(bench, "DECODE_STEPS", 8)
    monkeypatch.setattr(bench, "SPEC_BATCH", 4)
    monkeypatch.setattr(bench, "SPEC_GAMMA", 2)
    monkeypatch.setattr(bench, "SERVING_SLOTS", 4)
    monkeypatch.setattr(bench, "SERVING_CHUNK", 4)
    monkeypatch.setattr(bench, "SERVING_SECONDS", 2.0)
    # The real draft preset is 1B-sized; tests use a 1-layer tiny draft.
    monkeypatch.setattr(
        llama,
        "llama32_1b",
        lambda **kw: llama.llama_tiny(
            dtype="float32", n_layers=1,
            max_seq_len=kw.get("max_seq_len", 64),
        ),
    )
    cfg = llama.llama_tiny(dtype="float32", max_seq_len=64)
    from generativeaiexamples_tpu.engine.generator import LlamaGenerator

    gen = LlamaGenerator(
        cfg, max_batch=4, max_len=64, decode_chunk_size=4, seed=0
    )
    return cfg, gen.params


@pytest.mark.parametrize("draft_mode", ["self:1", "1b", "ngram"])
def test_bench_speculative_phase(tiny_bench, monkeypatch, draft_mode):
    """Both draft branches must run: the self-speculation default and
    the independent-draft (GAIE_SPEC_DRAFT=1b) floor measurement."""
    monkeypatch.setenv("GAIE_SPEC_DRAFT", draft_mode)
    cfg, params = tiny_bench
    out = bench.bench_speculative(cfg, params)
    assert out["spec_tokens_per_sec"] > 0
    assert out["spec_baseline_tokens_per_sec"] > 0
    assert 0.0 <= out["spec_accept_rate"] <= 1.0
    assert 0.0 <= out["spec_sampled_accept_rate"] <= 1.0
    assert out["spec_gamma"] == 2
    if draft_mode.startswith("self:"):
        assert "self-speculation" in out["spec_draft"]


def test_bench_serving_phase(tiny_bench):
    cfg, params = tiny_bench
    out = bench.bench_serving(cfg, params, offline_tps=50.0)
    for key in (
        "serving_tokens_per_sec",
        "serving_ttft_p50_ms",
        "serving_overload_ttft_p95_ms",
        "serving_rejected_frac",
        "serving_mean_active_slots",
    ):
        assert key in out, key
    assert out["serving_tokens_per_sec"] > 0


def test_bench_shared_prefix_phase(monkeypatch):
    """The shared-prefix + chunked-prefill phase must run end to end and
    report the round-6 headline keys (scales shrunk to seconds)."""
    monkeypatch.setattr(bench, "SHARED_PREFIX_LEN", 48)
    monkeypatch.setattr(bench, "SHARED_SUFFIX_LEN", 8)
    monkeypatch.setattr(bench, "SHARED_REQS", 2)
    monkeypatch.setattr(bench, "SHARED_MAX_LEN", 128)
    monkeypatch.setattr(bench, "SHARED_SLOTS", 4)
    monkeypatch.setattr(bench, "SHARED_DECODE", 4)
    monkeypatch.setattr(bench, "SHARED_PREFILL_CHUNK", 16)
    monkeypatch.setattr(bench, "LONG_PROMPT", 40)
    cfg = llama.llama_tiny(dtype="float32", max_seq_len=128)
    out = bench.bench_shared_prefix(None, cfg=cfg)
    for key in (
        "shared_prefix_ttft_p50_ms",
        "shared_prefix_cold_ttft_p50_ms",
        "shared_prefix_speedup",
        "prefill_chunks",
        "chunked_prefill_max_decode_gap_ms",
        "chunked_prefill_admit_ttft_ms",
    ):
        assert key in out, key
    assert out["shared_prefix_hits"] == 2
    assert out["shared_prefix_ttft_p50_ms"] > 0
    assert out["shared_prefix_cold_ttft_p50_ms"] > 0
    assert out["prefill_chunks"] > 0


def test_bench_spec_serving_phase(monkeypatch):
    """The spec-serving phase must run end to end through the online
    scheduler at tiny concurrency.  Training is replaced with random
    init (contract smoke, not an acceptance measurement) — which makes
    the bit-identity key a REAL assertion: even a worthless draft may
    never change greedy output."""
    import jax

    def fake_pair():
        tcfg = llama.llama_tiny(dtype="float32", max_seq_len=128)
        dcfg = llama.llama_tiny(
            dtype="float32", max_seq_len=128, n_layers=1
        )
        return (
            tcfg,
            dcfg,
            llama.init_params(tcfg, jax.random.PRNGKey(0)),
            llama.init_params(dcfg, jax.random.PRNGKey(1)),
            [0.0, 0.0],
            np.arange(10, 10 + bench.SPEC_PAIR_PERIOD),
            bench.SPEC_PAIR_PERIOD,
        )

    monkeypatch.setattr(bench, "_train_spec_pair", fake_pair)
    monkeypatch.setenv("GAIE_BENCH_SPEC_C", "6")
    out = bench.bench_spec_serving()
    for key in (
        "spec_serving_speedup",
        "spec_serving_ttft_ratio",
        "spec_serving_accept_rate",
        "spec_serving_adaptive_random_ratio",
        "spec_serving_random_gamma",
    ):
        assert key in out, key
    assert out["spec_serving_concurrency"] == 6
    assert out["spec_serving_bit_identical"] is True
    assert out["spec_serving_tokens_per_sec"] > 0
    assert out["spec_serving_baseline_tokens_per_sec"] > 0


def test_compact_headline_fits_and_parses(tmp_path, monkeypatch):
    """_publish writes the FULL result to a file and prints a <=1 KB
    single-line JSON headline (the driver's tail capture round-5 failure
    mode was one giant unparseable line)."""
    import io
    import json
    from contextlib import redirect_stdout

    path = tmp_path / "full.json"
    monkeypatch.setenv("GAIE_BENCH_RESULT_PATH", str(path))
    result = bench._base_result()
    result.update(
        {
            "value": 4366.0,
            "vs_baseline": 1.75,
            "serving_tokens_per_sec": 2900.0,
            "serving_ttft_p50_ms": 370.0,
            "long_tokens_per_sec": 1160.0,
            "shared_prefix_ttft_p50_ms": 120.0,
            "error": "x" * 5000,
            # Bulky non-headline detail that must go to the file only.
            "serving_mean_active_slots": [300.0] * 50,
            "spec_note": "y" * 3000,
        }
    )
    buf = io.StringIO()
    with redirect_stdout(buf):
        bench._publish(result)
    lines = buf.getvalue().strip().splitlines()
    headline = json.loads(lines[-1])
    assert len(lines[-1].encode()) <= 1024
    assert headline["value"] == 4366.0
    assert headline["full_results"] == str(path)
    assert "serving_mean_active_slots" not in headline
    full = json.loads(path.read_text())
    assert full["serving_mean_active_slots"] == [300.0] * 50
    assert full["value"] == 4366.0


def test_error_line_contract():
    """_emit_error always yields one parseable JSON object preserving
    already-measured fields."""
    import io
    import json
    from contextlib import redirect_stdout

    buf = io.StringIO()
    with redirect_stdout(buf):
        bench._emit_error("stage", "boom", partial={"value": 42.0})
    d = json.loads(buf.getvalue().strip())
    assert d["value"] == 42.0 and d["error"].startswith("stage:")
    assert bench._last_json_line("junk\n" + buf.getvalue()) == d
    assert bench._last_json_line("{truncated") is None


def test_bench_rag_phase(monkeypatch):
    """The end-to-end RAG retrieval phase must run at tiny scale on CPU
    (HashEmbedder + small corpus) and report the round-8 contract keys."""
    from generativeaiexamples_tpu.engine.embedder import HashEmbedder
    from generativeaiexamples_tpu.retrieval.memory import MemoryVectorStore

    monkeypatch.setattr(bench, "RAG_CORPUS_DOCS", 64)
    monkeypatch.setattr(bench, "RAG_CONCURRENCY", (1, 4))
    monkeypatch.setattr(bench, "RAG_REQS_PER_CLIENT", 2)
    monkeypatch.setattr(bench, "RAG_MAX_BATCH", 8)
    monkeypatch.setattr(bench, "RAG_MAX_WAIT_MS", 25.0)
    out = bench.bench_rag(
        embedder=HashEmbedder(dimensions=32),
        store=MemoryVectorStore(32),
    )
    for key in (
        "rag_qps_batched",
        "rag_qps_unbatched",
        "rag_p50_ms_batched",
        "rag_p95_ms_batched",
        "rag_p50_ms_unbatched",
        "rag_p95_ms_unbatched",
        "rag_batched_dispatches",
        "rag_requests",
        "rag_qps_batched_cmax",
        "rag_batch_speedup_cmax",
        "rag_p95_cmax_vs_c1_p50",
    ):
        assert key in out, key
    n_levels = len(out["rag_concurrency"])
    assert len(out["rag_qps_batched"]) == n_levels
    assert all(q > 0 for q in out["rag_qps_batched"])
    assert all(q > 0 for q in out["rag_qps_unbatched"])
    assert out["rag_corpus_docs"] == 64
    # The structural claim at every level: dispatches <= requests, and at
    # the concurrent level strictly fewer (coalescing happened).
    for d, n in zip(out["rag_batched_dispatches"], out["rag_requests"]):
        assert d <= n
    assert out["rag_batched_dispatches"][-1] < out["rag_requests"][-1]


def test_compact_headline_is_guaranteed_under_1kb():
    """Adversarial worst case: every headline key present and huge, a
    5 KB error, a long full-results path — the line must STILL come out
    <= 1 KB of valid JSON (the round-5 driver-capture failure mode)."""
    import json

    result = {k: "z" * 400 for k in bench._HEADLINE_KEYS}
    result.update(
        {
            "metric": "m" * 500,
            "value": 1234.5,
            "unit": "tokens/s",
            "error": "e" * 5000,
        }
    )
    line = bench._compact_headline(result, "/very/long/path/" + "p" * 300)
    assert len(line.encode()) <= 1024
    parsed = json.loads(line)
    assert parsed["value"] == 1234.5
    assert "error" in parsed


def test_bench_ingest_phase(monkeypatch):
    """The bulk-ingestion phase must run at tiny scale on CPU
    (HashEmbedder) and report the round-9 contract keys."""
    from generativeaiexamples_tpu.engine.embedder import HashEmbedder

    monkeypatch.setattr(bench, "INGEST_DOCS", 8)
    monkeypatch.setattr(bench, "INGEST_WORDS", 30)
    monkeypatch.setattr(bench, "INGEST_TTS_CORPUS", (512, 1024))
    monkeypatch.setattr(bench, "INGEST_TTS_APPEND", 32)
    monkeypatch.setattr(bench, "INGEST_CONCURRENT_SECONDS", 0.3)
    out = bench.bench_ingest(embedder=HashEmbedder(dimensions=32))
    for key in (
        "ingest_serial_docs_per_sec",
        "ingest_bulk_docs_per_sec",
        "ingest_bulk_speedup",
        "ingest_tts_ms_incremental",
        "ingest_tts_ms_rebuild",
        "ingest_sync_ms_incremental",
        "ingest_sync_ms_rebuild",
        "ingest_sync_scaling_incremental",
        "ingest_sync_scaling_rebuild",
        "ingest_search_p95_ms_during_bulk",
        "ingest_search_p95_ms_during_bulk_rebuild",
        "ingest_rows_during_window",
    ):
        assert key in out, key
    assert out["ingest_bulk_docs_per_sec"] > 0
    assert out["ingest_serial_docs_per_sec"] > 0
    assert len(out["ingest_tts_ms_incremental"]) == 2
    assert out["ingest_chunks"] > 0
    # Ingest kept flowing while searches ran.
    assert out["ingest_rows_during_window"] > 0


def test_bench_quant_phase():
    """The quantized-search phase must run at tiny scale on CPU and
    report the round-10 contract keys for every mode at every size."""
    out = bench.bench_quant(rows=(4096,), dim=64, n_queries=8)
    for mode in ("bf16", "int8", "pq"):
        for stem in ("p50_ms", "p95_ms", "scanned_mb", "gbps", "recall10"):
            key = f"quant_{stem}_{mode}"
            assert key in out, key
            assert len(out[key]) == 1
    for key in (
        "quant_int8_bytes_ratio",
        "quant_pq_bytes_ratio",
        "quant_int8_speedup",
        "quant_pq_speedup",
        "quant_recall10_int8_final",
        "quant_recall10_pq_final",
    ):
        assert key in out, key
    # Compressed scans must read fewer corpus bytes than full-width even
    # at tail-dominated tiny sizes; the 0.55x / 0.15x acceptance gates
    # apply at bench scale (100k+ rows) where the tail amortizes.
    assert out["quant_int8_bytes_ratio"] < 1.0
    assert out["quant_pq_bytes_ratio"] < out["quant_int8_bytes_ratio"]
    assert out["quant_recall10_int8_final"] >= 0.95
    assert out["quant_recall10_pq_final"] >= 0.90
    assert out["quant_rows"] == [4096]


def test_bench_chaos_phase(monkeypatch):
    """The chaos phase must run at tiny scale on CPU and report the
    round-11 contract keys; exact rates are the real capture's job."""
    monkeypatch.setattr(bench, "CHAOS_CORPUS_DOCS", 256)
    monkeypatch.setattr(bench, "CHAOS_DIM", 32)
    monkeypatch.setattr(bench, "CHAOS_CONCURRENCY", 4)
    monkeypatch.setattr(bench, "CHAOS_REQS_PER_CLIENT", 2)
    monkeypatch.setattr(bench, "CHAOS_DEADLINE_MS", 2_000.0)
    monkeypatch.setattr(
        bench, "CHAOS_FAULTS", "embedder:error=0.1;reranker:latency=20"
    )
    monkeypatch.setattr(
        bench, "CHAOS_FAULTS_RERANK_DOWN", "embedder:error=0.1;reranker:error=1.0"
    )
    monkeypatch.setattr(bench, "CHAOS_OVERHEAD_ITERS", 8)
    out = bench.bench_chaos()
    for key in (
        "chaos_success_protected",
        "chaos_success_unprotected",
        "chaos_clean_success",
        "chaos_protected_p50_ms",
        "chaos_p99_protected_ms",
        "chaos_clean_overhead_ms",
        "chaos_clean_overhead_pct",
        "chaos_degraded_frac_rerank_down",
        "chaos_protected_retries",
        "chaos_deadline_ms",
        "chaos_faults",
    ):
        assert key in out, key
    # Clean path with no faults armed must not fail at all.
    assert out["chaos_clean_success"] == 1.0
    assert 0.0 <= out["chaos_success_unprotected"] <= 1.0
    assert out["chaos_success_protected"] >= out["chaos_success_unprotected"]
    # Reranker hard-down: every successful request degraded to vector order.
    assert out["chaos_degraded_frac_rerank_down"] > 0.9
    assert out["chaos_p99_protected_ms"] > 0
    # Faults must never leak out of the phase.
    from generativeaiexamples_tpu.resilience.faults import get_fault_injector

    assert get_fault_injector().active_sites() == []


def test_bench_cache_phase(monkeypatch):
    """The semantic-cache phase must run at tiny scale on CPU and report
    the round-12 contract keys; real rates are the committed capture's
    job (perf/captures/bench_cache_cpu_r12.json)."""
    monkeypatch.setattr(bench, "CACHE_CORPUS_DOCS", 256)
    monkeypatch.setattr(bench, "CACHE_DIM", 32)
    monkeypatch.setattr(bench, "CACHE_CONCURRENCY", 4)
    monkeypatch.setattr(bench, "CACHE_REQS_PER_CLIENT", 4)
    monkeypatch.setattr(bench, "CACHE_UNIQUE_QUERIES", 8)
    monkeypatch.setattr(bench, "CACHE_PARAPHRASES_PER_CLASS", 4)
    out = bench.bench_cache()
    for key in (
        "cache_off_qps",
        "cache_off_p50_ms",
        "cache_on_qps",
        "cache_on_p50_ms",
        "cache_hit_rate",
        "cache_speedup_p50",
        "cache_speedup_qps",
        "cache_exact_zero_dispatch",
        "cache_on_pipeline_requests",
        "cache_semantic_hitrate_t90_reorder",
        "cache_semantic_hitrate_t98_two_fillers",
    ):
        assert key in out, key
    # Warm cache + every unique admitted: the timed window must be all
    # hits served without a single pipeline dispatch.
    assert out["cache_hit_rate"] == 1.0
    assert out["cache_on_pipeline_requests"] == 0
    assert out["cache_exact_zero_dispatch"] == 1
    assert out["cache_speedup_qps"] > 1.0
    # Word-reorder paraphrases have the identical bag-of-words vector:
    # they must hit at every threshold.
    assert out["cache_semantic_hitrate_t90_reorder"] == 1.0
    # The sweep must be monotone in the threshold for each class.
    assert (
        out["cache_semantic_hitrate_t90_two_fillers"]
        >= out["cache_semantic_hitrate_t98_two_fillers"]
    )
    # Phase-local metrics must not leak into process-wide counters.
    from generativeaiexamples_tpu.cache.metrics import cache_snapshot

    assert cache_snapshot()["misses"] == 0


def test_bench_obs_phase(monkeypatch):
    """The observability phase must run at tiny scale on CPU and report
    the round-13 contract keys; the real overhead number is the
    committed capture's job (perf/captures/bench_obs_cpu_r13.json)."""
    monkeypatch.setattr(bench, "OBS_CORPUS_DOCS", 256)
    monkeypatch.setattr(bench, "OBS_DIM", 32)
    monkeypatch.setattr(bench, "OBS_OVERHEAD_ITERS", 8)
    out = bench.bench_obs()
    for key in (
        "obs_raw_p50_ms",
        "obs_traced_p50_ms",
        "obs_overhead_ms",
        "obs_overhead_pct",
        "obs_overhead_ok",
        "obs_gate_pct",
        "obs_stage_samples",
        "obs_recorder_entries",
    ):
        assert key in out, key
    assert out["obs_raw_p50_ms"] > 0
    # Warmup + 8 timed iterations, 3 stages each, all finished into the
    # phase-local recorder.
    assert out["obs_recorder_entries"] == 9
    assert out["obs_stage_samples"] == 27
    assert out["obs_overhead_ok"] in (0, 1)
    # Phase-local samples must not leak into the process-wide
    # histograms that /metrics exports.
    from generativeaiexamples_tpu.obs.metrics import obs_snapshot

    snap = obs_snapshot()
    assert all(v["count"] == 0 for v in snap["stage"].values())
    assert all(v["count"] == 0 for v in snap["request"].values())


def test_bench_slo_phase(monkeypatch):
    """The SLO phase must run at tiny scale on CPU and report the
    round-14 contract keys; the real overhead number is the committed
    capture's job (perf/captures/bench_slo_cpu_r14.json)."""
    monkeypatch.setattr(bench, "OBS_CORPUS_DOCS", 256)
    monkeypatch.setattr(bench, "OBS_DIM", 32)
    monkeypatch.setattr(bench, "SLO_OVERHEAD_ITERS", 8)
    monkeypatch.setattr(bench, "SLO_DRILL_REQUESTS", 16)
    from generativeaiexamples_tpu.obs.tsdb import get_tsdb

    # Earlier suites in this worker may have served real requests into
    # the global tsdb: only what the phase itself adds is a leak.
    before = set(get_tsdb().names())
    out = bench.bench_slo()
    for key in (
        "slo_raw_p50_ms",
        "slo_fed_p50_ms",
        "slo_overhead_ms",
        "slo_overhead_pct",
        "slo_overhead_ok",
        "slo_gate_pct",
        "slo_clean_ok",
        "slo_alert_fired",
        "slo_alert_clear_ok",
        "slo_burn_rate_fast",
        "slo_transitions",
    ):
        assert key in out, key
    assert out["slo_raw_p50_ms"] > 0
    assert out["slo_overhead_ok"] in (0, 1)
    # The drill contract: clean traffic never pages, the fault burst
    # flips the fast-burn rule within one evaluation, recovery clears it,
    # and both directions were pinned as transitions.
    assert out["slo_clean_ok"] == 1
    assert out["slo_alert_fired"] == 1
    assert out["slo_burn_rate_fast"] >= 14.4
    assert out["slo_alert_clear_ok"] == 1
    assert out["slo_transitions"] >= 2
    # Phase-local state must not leak into the process-wide singletons.
    from generativeaiexamples_tpu.obs.slo import get_slo_engine
    from generativeaiexamples_tpu.resilience.faults import get_fault_injector

    leaked = [
        n
        for n in get_tsdb().names()
        if n not in before
        and (n.startswith("slo.") or n.startswith("chain."))
    ]
    assert leaked == []
    assert get_slo_engine().evaluate(force=True)["fast_burn_firing"] is False
    assert get_fault_injector().active_sites() == []


def test_bench_elastic_phase(monkeypatch):
    """The elasticity phase must run at tiny overhead scale on CPU and
    prove the full closed loop (the simulation timeline itself stays at
    production shape — it is synthetic-timestamp driven, so it costs
    iterations, not wall-clock); the committed capture is
    perf/captures/bench_elastic_cpu_r15.json."""
    monkeypatch.setattr(bench, "OBS_CORPUS_DOCS", 256)
    monkeypatch.setattr(bench, "OBS_DIM", 32)
    monkeypatch.setattr(bench, "ELASTIC_OVERHEAD_ITERS", 8)
    out = bench.bench_elastic()
    for key in (
        "elastic_fast_burn_fired",
        "elastic_fire_latency_s",
        "elastic_scaled_to",
        "elastic_scale_ups",
        "elastic_scale_downs",
        "elastic_pinned_scale_events",
        "elastic_alert_resolved",
        "elastic_post_p95_ms",
        "elastic_slo_ok",
        "elastic_interactive_success",
        "elastic_shed_only_low",
        "elastic_admission_overhead_pct",
        "elastic_admission_overhead_ok",
    ):
        assert key in out, key
    # The acceptance contract end to end: the 4x step pages, the pool
    # grows, the page clears, post-recovery latency is inside the SLO,
    # and every shed request was batch/ingest.
    assert out["elastic_fast_burn_fired"] == 1
    assert 0 <= out["elastic_fire_latency_s"] <= 60
    assert out["elastic_scaled_to"] >= 2
    assert out["elastic_scale_ups"] >= 1
    assert out["elastic_scale_downs"] >= 1
    assert (
        out["elastic_pinned_scale_events"]
        == out["elastic_scale_ups"] + out["elastic_scale_downs"]
    )
    assert out["elastic_alert_resolved"] == 1
    assert out["elastic_slo_ok"] == 1
    assert out["elastic_interactive_success"] >= 0.99
    assert out["elastic_shed_interactive"] == 0
    assert out["elastic_shed_batch"] + out["elastic_shed_ingest"] > 0
    assert out["elastic_shed_only_low"] == 1
    assert out["elastic_admission_overhead_ok"] in (0, 1)
    # Phase-local state must not leak into the process-wide singletons.
    from generativeaiexamples_tpu.obs.slo import get_slo_engine
    from generativeaiexamples_tpu.obs.tsdb import get_tsdb
    from generativeaiexamples_tpu.resilience.admission import (
        get_admission_controller,
    )

    leaked = [
        n
        for n in get_tsdb().names()
        if n.startswith("admission.") or n.startswith("autoscale.")
    ]
    assert leaked == []
    assert get_slo_engine().evaluate(force=True)["fast_burn_firing"] is False
    snap = get_admission_controller().snapshot()
    assert sum(snap["shed_total"].values()) == 0


def test_bench_durability_phase(monkeypatch):
    """The durability phase must run at tiny overhead scale on CPU and
    report the round-16 contract keys; the kill-restart drill runs at
    its real (already-small) scale because the child is a subprocess and
    cannot see monkeypatched constants.  The committed capture is
    perf/captures/bench_durability_cpu_r16.json."""
    monkeypatch.setattr(bench, "DUR_PREFILL_ROWS", 512)
    monkeypatch.setattr(bench, "DUR_OVERHEAD_ITERS", 8)
    out = bench.bench_durability()
    for key in (
        "durability_overhead_raw_p50_ms",
        "durability_overhead_ms",
        "durability_overhead_pct",
        "durability_overhead_ok",
        "durability_gate_pct",
        "durability_wal_rows",
        "durability_snapshot_ms",
        "durability_bootstrap_ms",
        "durability_bootstrap_rows",
        "durability_bootstrap_ok",
        "durability_drill_resumed",
        "durability_drill_no_dup_no_loss",
        "durability_drill_search_equivalent",
        "durability_drill_job_complete",
        "durability_recovery_ms",
        "durability_drill_ok",
    ):
        assert key in out, key
    assert out["durability_overhead_raw_p50_ms"] > 0
    # The gate verdict is the capture's job at full scale; here only the
    # plumbing is asserted.
    assert out["durability_overhead_ok"] in (0, 1)
    assert out["durability_bootstrap_rows"] == out["durability_wal_rows"]
    assert out["durability_bootstrap_ok"] == 1
    # The drill contract end to end: the SIGKILLed ingest resumed from
    # the journal and converged to the uninterrupted control run.
    assert out["durability_drill_resumed"] == 1
    assert out["durability_drill_no_dup_no_loss"] == 1
    assert out["durability_drill_search_equivalent"] == 1
    assert out["durability_drill_job_complete"] == 1
    assert out["durability_drill_ok"] == 1
    # Phase-local state must not leak into the process-wide counters.
    from generativeaiexamples_tpu.durability.metrics import (
        durability_snapshot,
    )

    snap = durability_snapshot()
    assert sum(snap["wal_records"].values()) == 0
    assert snap["recoveries"] == 0


def test_bench_gray_phase(monkeypatch):
    """The gray-failure phase must run at tiny scale on CPU and report
    the round-17 contract keys; the gate verdicts themselves are the
    full-scale capture's job (perf/captures/bench_gray_cpu_r17.json).
    The drill's dwell clocks are real time, so the shrunk waves keep the
    smoke to a few seconds of pumping plus the tiny-model requests."""
    monkeypatch.setattr(bench, "GRAY_WARM_REQS", 2)
    monkeypatch.setattr(bench, "GRAY_CLEAN_REQS", 12)
    monkeypatch.setattr(bench, "GRAY_BRIDGE_REQS", 4)
    monkeypatch.setattr(bench, "GRAY_MEASURED_REQS", 12)
    monkeypatch.setattr(bench, "GRAY_OVERHEAD_ITERS", 4)
    # The eject and recover waits are waits on a condition: they keep
    # bench.py's own deadlines (45 s / 90 s), which a loaded six-worker
    # host needs and a quiet one never reaches.
    out = bench.bench_gray()
    for key in (
        "gray_ejected",
        "gray_eject_latency_s",
        "gray_readmitted",
        "gray_recovered",
        "gray_recovery_s",
        "gray_clean_p99_ms",
        "gray_faulted_p99_ms",
        "gray_p99_ratio",
        "gray_p99_ok",
        "gray_fast_burn_fired",
        "gray_hedge_eligible",
        "gray_hedge_fired",
        "gray_hedge_extra_load_pct",
        "gray_hedge_load_ok",
        "gray_pinned_transitions",
        "gray_overhead_pct",
        "gray_overhead_ok",
    ):
        assert key in out, key
    # The state machine must complete even at smoke scale: the straggler
    # is quarantined, then re-admitted once the fault clears.
    assert out["gray_ejected"] == 1
    assert out["gray_readmitted"] == 1
    assert out["gray_recovered"] == 1
    assert out["gray_clean_p99_ms"] > 0
    assert out["gray_hedge_eligible"] > 0
    assert out["gray_overhead_ok"] in (0, 1)
    # The phase must disarm its fault site no matter how it exits.
    from generativeaiexamples_tpu.resilience.faults import (
        get_fault_injector,
    )

    assert get_fault_injector().active_sites() == []


def test_bench_fused_phase(monkeypatch):
    """The fused-W8A8 phase's glue must run at tiny smoke scale on CPU:
    microbench keys, kernel-vs-twin tile bit-identity (interpret mode),
    and the tile-once loading contract.  The full phase (decode parity +
    spec on/off through the scheduler) is exercised in tests/test_qmm.py;
    on the chip, chip_smoke.py decodes through the kernel."""
    monkeypatch.setenv("GAIE_FUSED_TINY", "1")
    monkeypatch.setenv("GAIE_FUSED_SMOKE", "1")
    out = bench.bench_fused()
    for key in (
        "fused_platform",
        "fused_tile_mkn",
        "fused_kernel_gbps",
        "fused_xla_gbps",
        "fused_kernel_engaged",
        "fused_tile_bit_identical",
        "fused_block_events_per_load",
        "fused_block_events_flat",
    ):
        assert key in out, key
    assert out["fused_smoke"] is True
    assert out["fused_tile_bit_identical"] is True
    assert out["fused_block_events_per_load"] == 4
    assert out["fused_block_events_flat"] is True


def test_bench_shard_phase():
    """The sharded-fabric phase must run at tiny scale on CPU and report
    the round-20 contract keys; the 1M-row gates are the capture's job,
    but exactness and recall hold at every scale."""
    out = bench.bench_shard(rows=4096, dim=32, n_queries=8, num_shards=2)
    for key in (
        "shard_rows",
        "shard_num",
        "shard_base_p95_ms",
        "shard_exact_p95_ms",
        "shard_exact_bit_identical",
        "shard_p95_under_ingest_ratio",
        "shard_ingest_rows_during_window",
        "shard_recall10_int8",
        "shard_recall10_pq",
        "shard_cold_shards",
        "shard_scan_host_mb",
        "shard_scan_hbm_mb",
        "shard_cold_host_ratio",
        "shard_pass_bit_identical",
        "shard_pass_recall_int8",
        "shard_pass_recall_pq",
        "shard_pass_cold_bytes",
        "shard_pass_p95_under_ingest",
    ):
        assert key in out, key
    assert out["shard_rows"] == 4096
    assert out["shard_num"] == 2
    assert out["shard_exact_bit_identical"] is True
    assert out["shard_recall10_int8"] >= 0.95
    assert out["shard_recall10_pq"] >= 0.95
    assert out["shard_cold_shards"] >= 1
    # The cold tier's host scans read PQ codes, not f32 rows.
    assert out["shard_cold_host_ratio"] < 1.0


@pytest.mark.slow
def test_bench_paged_phase(monkeypatch):
    """The paged-KV phase's glue must run at smoke scale on CPU: the
    round-21 four-gate contract keys, with the deterministic gates
    (parity, shared-bytes from page gauges, zero leaks, zero-dispatch
    graft) actually holding.  The throughput gate keys must exist but
    their thresholds are asserted only on captures — one-rep CPU smoke
    timings are noise.  The full parity matrix lives in
    tests/test_paged_kv.py; on the chip, chip_smoke.py decodes through
    the paged kernel."""
    monkeypatch.setenv("GAIE_PAGED_SMOKE", "1")
    out = bench.bench_paged()
    for key in (
        "paged_platform",
        "paged_page_tokens",
        "paged_batches",
        "paged_parity_paths",
        "paged_pass_parity",
        "paged_decode_tokens_per_sec_skewed_b4",
        "contiguous_decode_tokens_per_sec_skewed_b4",
        "paged_decode_ratio_skewed",
        "paged_decode_ratio_uniform",
        "paged_attn_traffic_ratio_skewed",
        "paged_attn_traffic_ratio_uniform",
        "paged_pass_throughput",
        "paged_kv_bytes_per_step_b4",
        "contiguous_kv_bytes_per_step_b4",
        "paged_kv_bytes_ratio_max",
        "paged_shared_bytes_ratio",
        "paged_pass_shared_bytes",
        "paged_pass_leaks",
        "paged_gates_ok",
        "paged_graft_host_ms",
        "paged_graft_copy_ms",
        "paged_graft_zero_dispatch",
    ):
        assert key in out, key
    assert out["paged_smoke"] is True
    # Bit-parity through the full scheduler on every smoke path.
    assert out["paged_pass_parity"] is True
    assert out["paged_parity_paths"]["graft"] is True
    # 64-way shared prefix halves KV bytes by the page gauges, grafts
    # never touch device KV, and every pool drains leak-free.
    assert out["paged_pass_shared_bytes"] is True
    assert out["paged_graft_zero_dispatch"] is True
    assert out["paged_pass_leaks"] is True
    # The traffic ratios are computed from the workload's page/window
    # geometry, so they are deterministic even at one-rep smoke scale.
    assert out["paged_attn_traffic_ratio_skewed"] >= 1.3
    assert out["paged_attn_traffic_ratio_uniform"] >= 1.0


def test_bench_long4k_glue():
    """perf/bench_long4k.py runs end to end at tiny scale: the one-shot
    chip run must not die on Python-level glue."""
    import subprocess
    import sys

    env = {
        k: v
        for k, v in os.environ.items()
        # Hermeticity: ambient bench/engine knobs (BENCH_B=128 etc.)
        # must not scale the "tiny" run up.
        if not k.startswith(("BENCH_", "GAIE_"))
    }
    env.update({"JAX_PLATFORMS": "cpu", "GAIE_LONG4K_TINY": "1"})
    proc = subprocess.run(
        [sys.executable, os.path.join("perf", "bench_long4k.py")],
        capture_output=True,
        text=True,
        timeout=600,
        env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert len(result["windows"]) == 3
    for w in result["windows"]:
        assert w["decode_tps"] > 0 and w["prefill_batch_ms"] > 0
