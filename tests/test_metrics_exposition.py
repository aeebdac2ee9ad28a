"""Prometheus exposition-format validation of BOTH /metrics endpoints.

Six hand-rolled ``*_metrics_lines`` helpers plus two histogram families
compose each document; this suite parses the real outputs with the
in-tree validator (``obs/exposition.py``) so format drift — duplicate
series, TYPE after samples, unescaped labels, broken bucket cumulation —
fails in CI instead of in a scraper.
"""

import asyncio
import os

import pytest
from aiohttp.test_utils import TestClient, TestServer

from generativeaiexamples_tpu.core.configuration import reset_config_cache
from generativeaiexamples_tpu.obs.exposition import (
    ExpositionError,
    parse_exposition,
)


# -- validator unit tests ----------------------------------------------------


def test_validator_accepts_minimal_document():
    exp = parse_exposition(
        "# HELP x_total things\n"
        "# TYPE x_total counter\n"
        'x_total{kind="a"} 3\n'
        "x_total 1\n"
    )
    assert exp.value("x_total", kind="a") == 3
    assert exp.types["x_total"] == "counter"


def test_validator_rejects_duplicate_series():
    with pytest.raises(ExpositionError, match="duplicate series"):
        parse_exposition("# TYPE x gauge\nx 1\nx 2\n")


def test_validator_rejects_type_after_samples():
    with pytest.raises(ExpositionError, match="after its samples"):
        parse_exposition("x_total 1\n# TYPE x_total counter\n")


def test_validator_rejects_raw_label_escape_violations():
    with pytest.raises(ExpositionError, match="malformed labels"):
        parse_exposition('# TYPE x gauge\nx{a="un"quoted"} 1\n')
    with pytest.raises(ExpositionError, match="invalid escape"):
        parse_exposition('# TYPE x gauge\nx{a="bad\\q"} 1\n')


def test_validator_rejects_non_monotonic_histogram():
    doc = (
        "# TYPE h histogram\n"
        'h_bucket{le="1"} 5\n'
        'h_bucket{le="2"} 3\n'
        'h_bucket{le="+Inf"} 5\n'
        "h_sum 9\n"
        "h_count 5\n"
    )
    with pytest.raises(ExpositionError, match="not monotonic"):
        parse_exposition(doc)


def test_validator_rejects_missing_inf_terminal_and_count_mismatch():
    with pytest.raises(ExpositionError, match="missing terminal"):
        parse_exposition(
            "# TYPE h histogram\n"
            'h_bucket{le="1"} 1\n'
            "h_sum 1\nh_count 1\n"
        )
    with pytest.raises(ExpositionError, match="_count != "):
        parse_exposition(
            "# TYPE h histogram\n"
            'h_bucket{le="1"} 1\n'
            'h_bucket{le="+Inf"} 2\n'
            "h_sum 1\nh_count 3\n"
        )


# -- chain server /metrics ---------------------------------------------------


def _reset(monkeypatch, tmp_path):
    from generativeaiexamples_tpu.chains.factory import reset_factories

    for key in list(os.environ):
        if key.startswith("APP_") or key.startswith("GAIE_"):
            monkeypatch.delenv(key, raising=False)
    monkeypatch.setenv("APP_LLM_MODELENGINE", "echo")
    monkeypatch.setenv("APP_EMBEDDINGS_MODELENGINE", "hash")
    monkeypatch.setenv("APP_EMBEDDINGS_DIMENSIONS", "64")
    monkeypatch.setenv("APP_VECTORSTORE_NAME", "memory")
    monkeypatch.setenv("APP_RETRIEVER_SCORETHRESHOLD", "-1.0")
    monkeypatch.setenv("GAIE_UPLOAD_DIR", str(tmp_path / "uploads"))
    reset_config_cache()
    reset_factories()


@pytest.fixture
def client(monkeypatch, tmp_path):
    _reset(monkeypatch, tmp_path)
    from generativeaiexamples_tpu.server.app import create_app

    loop = asyncio.new_event_loop()
    client = TestClient(TestServer(create_app()), loop=loop)
    loop.run_until_complete(client.start_server())
    yield client, loop
    loop.run_until_complete(client.close())
    loop.close()
    reset_config_cache()
    from generativeaiexamples_tpu.chains.factory import reset_factories

    reset_factories()


def test_chain_server_metrics_is_valid_exposition(client, tmp_path):
    c, loop = client

    async def go():
        # Drive real traffic first so the histograms carry live samples,
        # then scrape.
        doc = tmp_path / "doc.txt"
        doc.write_text("Alpha one.\n\nBeta two.")
        with open(doc, "rb") as fh:
            assert (await c.post("/documents", data={"file": fh})).status == 200
        assert (
            await c.post("/search", json={"query": "alpha", "top_k": 1})
        ).status == 200
        resp = await c.get("/metrics")
        assert resp.status == 200
        return await resp.text()

    text = loop.run_until_complete(go())
    exp = parse_exposition(text)
    assert exp.types["rag_stage_latency_ms"] == "histogram"
    assert exp.types["rag_request_latency_ms"] == "histogram"
    assert exp.types["rag_cache_semantic_scan_ms"] == "summary"
    # The /search request above landed in the live histogram.
    assert exp.value("rag_request_latency_ms_count", route="/search") >= 1
    assert exp.value("rag_stage_latency_ms_bucket", stage="embed", le="+Inf") >= 1
    # From-zero families stay exported.
    assert exp.value("rag_stage_latency_ms_count", stage="llm_ttft") >= 0


# -- engine server /metrics --------------------------------------------------


class _StubStats:
    def snapshot(self):
        return {
            "requests_total": 3,
            "tokens_total": 120,
            "ttft_avg_ms": 12.5,
            "active_slots": 1,
            "queued": 0,
            "rejected_total": 0,
            "prefix_hits": 2,
            "prefix_tokens_reused": 64,
            "shared_prefix_hits": 1,
            "prefill_chunks": 4,
            "spec_rounds": 0,
            "spec_tokens": 0,
            "spec_proposed": 0,
            "spec_accepted": 0,
            "spec_acceptance_ewma": 0.0,
            "tick_ms_ewma": 0.0,
            "tick_ms_norm_ewma": 0.0,
        }


class _StubEngine:
    stats = _StubStats()

    def healthy(self):
        return True


def _scrape_engine_metrics():
    from generativeaiexamples_tpu.engine.server import create_engine_app

    app = create_engine_app(
        _StubEngine(), tokenizer=None, enable_profiler=False
    )
    loop = asyncio.new_event_loop()
    client = TestClient(TestServer(app), loop=loop)
    loop.run_until_complete(client.start_server())
    try:

        async def go():
            resp = await client.get("/metrics")
            assert resp.status == 200
            return await resp.text()

        return loop.run_until_complete(go())
    finally:
        loop.run_until_complete(client.close())
        loop.close()


def test_engine_server_metrics_is_valid_exposition():
    from generativeaiexamples_tpu.obs import reset_obs
    from generativeaiexamples_tpu.obs.metrics import observe_stage

    reset_obs()  # earlier suites (real scheduler runs) feed llm_ttft
    try:
        observe_stage("llm_ttft", 12.5)  # the scheduler's TTFT site
        text = _scrape_engine_metrics()
    finally:
        reset_obs()
    exp = parse_exposition(text)
    assert exp.value("engine_requests_total") == 3
    assert exp.types["rag_stage_latency_ms"] == "histogram"
    assert exp.value("rag_stage_latency_ms_count", stage="llm_ttft") == 1
    assert (
        exp.value("rag_stage_latency_ms_bucket", stage="llm_ttft", le="25") == 1
    )
    # The draft telemetry exports from zero (the stub's model does not
    # draft) in valid exposition format.
    assert exp.types["engine_spec_proposed_total"] == "counter"
    assert exp.value("engine_spec_proposed_total") == 0
    assert exp.value("engine_spec_accepted_total") == 0
    assert exp.types["engine_spec_acceptance_ewma"] == "gauge"
    assert exp.value("engine_spec_acceptance_ewma") == 0
    # One KV layout: no series speaks of pages.
    assert not [name for name in exp.types if "kv_page" in name or "kv_cow" in name]
    # Matmul-path info gauge exports from zero: the stub predates the
    # attribute, so it reports the xla default — both labels present,
    # exactly one carrying 1.
    assert exp.types["engine_matmul_kernel"] == "gauge"
    assert exp.value("engine_matmul_kernel", kernel="xla") == 1
    assert exp.value("engine_matmul_kernel", kernel="pallas_w8a8") == 0


def test_engine_matmul_kernel_gauge_tracks_fused_path():
    """An engine on the fused path flips the info gauge, including when
    the attribute lives on pool replicas rather than the engine."""

    class _FusedEngine(_StubEngine):
        matmul_kernel = "pallas_w8a8"

    class _Rep:
        def __init__(self):
            self.scheduler = _FusedEngine()

    class _PoolEngine(_StubEngine):
        replicas = [_Rep()]

    from generativeaiexamples_tpu.engine.server import create_engine_app

    for engine in (_FusedEngine(), _PoolEngine()):
        app = create_engine_app(engine, tokenizer=None, enable_profiler=False)
        loop = asyncio.new_event_loop()
        client = TestClient(TestServer(app), loop=loop)
        loop.run_until_complete(client.start_server())
        try:

            async def go():
                resp = await client.get("/metrics")
                return await resp.text()

            text = loop.run_until_complete(go())
        finally:
            loop.run_until_complete(client.close())
            loop.close()
        exp = parse_exposition(text)
        assert exp.value("engine_matmul_kernel", kernel="pallas_w8a8") == 1
        assert exp.value("engine_matmul_kernel", kernel="xla") == 0


@pytest.mark.parametrize(
    "family, label, values",
    [
        ("engine_executables_total", "cache", ("hit", "miss", "off")),
        ("engine_executable_seconds_total", "stage", ("trace", "lower", "backend")),
    ],
)
def test_engine_server_executable_families_export_from_zero(
    family, label, values
):
    """What the tick thread asked JAX for (``docs/observability.md``,
    "Set-up and executables"): both families are there, typed, with
    every label at zero, on an engine whose snapshot predates the keys."""
    exp = parse_exposition(_scrape_engine_metrics())
    assert exp.types[family] == "counter"
    for value in values:
        assert exp.value(family, **{label: value}) == 0


def test_engine_server_metrics_fleet_families_export_from_zero(
    monkeypatch, tmp_path
):
    """The ENGINE document carries the tick histogram and the SLO gauges
    before the first tick / request — scraped through the validator so a
    zero-state engine cannot drift out of exposition format either."""
    _reset(monkeypatch, tmp_path)
    from generativeaiexamples_tpu.obs import reset_obs

    reset_obs()
    try:
        text = _scrape_engine_metrics()
    finally:
        reset_obs()
    exp = parse_exposition(text)
    assert exp.types["engine_tick_duration_ms"] == "histogram"
    assert exp.value("engine_tick_duration_ms_count", loop="tick") == 0
    assert exp.value(
        "engine_tick_duration_ms_bucket", loop="tick", le="+Inf"
    ) == 0
    assert exp.types["rag_slo_burn_rate"] == "gauge"
    for route in ("/generate", "/search"):
        for window in ("fast", "slow"):
            assert (
                exp.value(
                    "rag_slo_burn_rate",
                    route=route,
                    slo="availability",
                    window=window,
                )
                == 0.0
            )
            assert (
                exp.value(
                    "rag_slo_alert_state",
                    route=route,
                    slo="availability",
                    window=window,
                )
                == 0.0
            )


def test_chain_server_every_family_exports_from_zero(client):
    """The from-zero contract, family by family: a FRESH chain server's
    very first scrape must already carry every series dashboards reference
    — obs histograms, cache counters, resilience gauges, and the SLO
    burn-rate surface — so panels need no existence checks."""
    from generativeaiexamples_tpu.obs.metrics import ROUTES, STAGES
    from generativeaiexamples_tpu.resilience.breaker import STANDARD_DEPS

    c, loop = client

    async def go():
        resp = await c.get("/metrics")
        assert resp.status == 200
        return await resp.text()

    exp = parse_exposition(loop.run_until_complete(go()))
    # obs/metrics.py histogram families, every known label from zero.
    for stage in STAGES:
        assert exp.value("rag_stage_latency_ms_count", stage=stage) == 0
    for route in ROUTES:
        assert exp.value("rag_request_latency_ms_count", route=route) == 0
    # cache/metrics.py.
    for tier in ("exact", "semantic"):
        assert exp.value("rag_cache_hits_total", tier=tier) == 0
    assert exp.value("rag_cache_misses_total") == 0
    assert exp.value("rag_cache_entries") == 0
    assert exp.value("rag_cache_invalidations_total") == 0
    assert exp.value("rag_cache_semantic_scan_ms_count") == 0
    # resilience/metrics.py.
    assert exp.value("rag_retries_total") == 0
    assert exp.value("rag_deadline_expired_total") == 0
    for stage in ("rerank", "shrink_k", "index_fallback", "cache_stale", "retrieval"):
        assert exp.value("rag_degraded_total", stage=stage) == 0
    for dep in STANDARD_DEPS:
        assert exp.value("rag_breaker_state", dep=dep) == 0
        assert exp.value("rag_breaker_open_total", dep=dep) == 0
    # obs/slo.py: every configured objective exports before any traffic.
    for route in ROUTES:
        assert (
            exp.value(
                "rag_slo_error_budget_remaining", route=route, slo="availability"
            )
            == 1.0
        )
        assert (
            exp.value(
                "rag_slo_error_budget_remaining", route=route, slo="latency"
            )
            == 1.0
        )
        for window in ("fast", "slow"):
            for slo in ("availability", "latency"):
                assert (
                    exp.value(
                        "rag_slo_burn_rate", route=route, slo=slo, window=window
                    )
                    == 0.0
                )
                assert (
                    exp.value(
                        "rag_slo_alert_state", route=route, slo=slo, window=window
                    )
                    == 0.0
                )
    # resilience/admission.py: per-class counters from zero.
    from generativeaiexamples_tpu.resilience.admission import CLASSES

    for cls in CLASSES:
        assert exp.value("rag_admission_admitted_total", **{"class": cls}) == 0
        assert exp.value("rag_admission_shed_total", **{"class": cls}) == 0
    # engine/autoscale.py pool gauges: the chain server hosts no engine,
    # so both export as zero rather than disappearing.
    assert exp.value("engine_pool_size") == 0
    assert exp.value("engine_pool_desired_replicas") == 0


def test_engine_server_metrics_admission_and_pool_families(
    monkeypatch, tmp_path
):
    """The ENGINE document's elasticity families: per-class admission
    counters from zero, and pool gauges reporting a bare scheduler as a
    pool of one."""
    _reset(monkeypatch, tmp_path)
    from generativeaiexamples_tpu.obs import reset_obs
    from generativeaiexamples_tpu.resilience.admission import CLASSES

    reset_obs()
    try:
        text = _scrape_engine_metrics()
    finally:
        reset_obs()
    exp = parse_exposition(text)
    for cls in CLASSES:
        assert exp.value("rag_admission_admitted_total", **{"class": cls}) == 0
        assert exp.value("rag_admission_shed_total", **{"class": cls}) == 0
    # _StubEngine has no pool_size(): exported as a pool of one.
    assert exp.value("engine_pool_size") == 1
    assert exp.value("engine_pool_desired_replicas") == 1


def test_chain_server_durability_families_export_from_zero(client):
    """The CHAIN document's rag_wal_* / rag_recovery_* families: every
    series from zero even with durability disabled (the default), so
    dashboards can reference them unconditionally."""
    c, loop = client

    async def go():
        resp = await c.get("/metrics")
        assert resp.status == 200
        return await resp.text()

    exp = parse_exposition(loop.run_until_complete(go()))
    for op in ("add", "delete", "index_swap"):
        assert exp.value("rag_wal_records_total", op=op) == 0
    assert exp.value("rag_wal_bytes_total") == 0
    assert exp.value("rag_wal_fsyncs_total") == 0
    assert exp.value("rag_wal_truncations_total") == 0
    assert exp.value("rag_wal_last_seq") == 0
    assert exp.value("rag_wal_snapshots_total") == 0
    assert exp.value("rag_wal_snapshot_last_duration_ms") == 0
    assert exp.value("rag_recovery_total") == 0
    assert exp.value("rag_recovery_replayed_records_total") == 0
    assert exp.value("rag_recovery_quarantined_records_total") == 0
    assert exp.value("rag_recovery_resumed_jobs_total") == 0
    assert exp.value("rag_recovery_last_duration_ms") == 0
    assert exp.value("rag_recovery_replica_bootstraps_total") == 0


def test_engine_server_durability_families_export_from_zero(
    monkeypatch, tmp_path
):
    """The ENGINE document carries the same durability schema from zero —
    a replica restored from snapshot must land its rag_recovery_* series
    on the scrape endpoint operators actually watch."""
    _reset(monkeypatch, tmp_path)
    from generativeaiexamples_tpu.durability.metrics import (
        reset_durability_metrics,
    )
    from generativeaiexamples_tpu.obs import reset_obs

    reset_obs()
    reset_durability_metrics()
    try:
        text = _scrape_engine_metrics()
    finally:
        reset_obs()
    exp = parse_exposition(text)
    for op in ("add", "delete", "index_swap"):
        assert exp.value("rag_wal_records_total", op=op) == 0
    assert exp.value("rag_recovery_total") == 0
    assert exp.value("rag_recovery_replica_bootstraps_total") == 0
    assert exp.types["rag_wal_records_total"] == "counter"
    assert exp.types["rag_wal_last_seq"] == "gauge"
    assert exp.types["rag_recovery_last_duration_ms"] == "gauge"


def test_chain_server_gray_families_export_from_zero(client):
    """The CHAIN document's gray-failure families (rag_hedge_*,
    ejection counters, the per-replica score gauge's type declaration):
    from zero with no engine pool in the process, so hedge/ejection
    dashboards and alerts can be written before the first brownout."""
    c, loop = client

    async def go():
        resp = await c.get("/metrics")
        assert resp.status == 200
        return await resp.text()

    exp = parse_exposition(loop.run_until_complete(go()))
    assert exp.value("rag_hedge_requests_total") == 0
    assert exp.value("rag_hedge_wins_total") == 0
    assert exp.value("rag_hedge_cancelled_total") == 0
    assert exp.value("rag_hedge_suppressed_total") == 0
    assert exp.value("engine_replica_ejections_total") == 0
    assert exp.value("engine_replica_readmissions_total") == 0
    assert exp.value("engine_pool_ejected_replicas") == 0
    # No replicas here, so no score samples — but the family's type is
    # declared, which is what dashboard queries validate against.
    assert exp.types["engine_replica_score"] == "gauge"
    assert exp.types["rag_hedge_requests_total"] == "counter"


def test_engine_server_gray_families_export_from_zero(monkeypatch, tmp_path):
    """The ENGINE document carries the same gray-failure schema from
    zero (a bare Scheduler engine exports the zeros; a pool adds
    per-replica scores)."""
    _reset(monkeypatch, tmp_path)
    from generativeaiexamples_tpu.obs import reset_obs

    reset_obs()
    try:
        text = _scrape_engine_metrics()
    finally:
        reset_obs()
    exp = parse_exposition(text)
    assert exp.value("rag_hedge_requests_total") == 0
    assert exp.value("rag_hedge_wins_total") == 0
    assert exp.value("rag_hedge_cancelled_total") == 0
    assert exp.value("rag_hedge_suppressed_total") == 0
    assert exp.value("engine_replica_ejections_total") == 0
    assert exp.value("engine_replica_readmissions_total") == 0
    assert exp.value("engine_pool_ejected_replicas") == 0
    assert exp.types["engine_replica_score"] == "gauge"


def test_gray_lines_with_pool_scores_are_valid_exposition():
    """gray_metrics_lines(engine) with per-replica scores stays a valid
    document (labeled gauge samples under the declared family)."""
    from generativeaiexamples_tpu.engine.health import gray_metrics_lines

    class _Pool:
        ejections_total = 3
        readmissions_total = 1

        def ejected_count(self):
            return 1

        def replica_scores(self):
            return {0: 1.0, 1: 0.4375}

    exp = parse_exposition("\n".join(gray_metrics_lines(_Pool())) + "\n")
    assert exp.value("engine_replica_ejections_total") == 3
    assert exp.value("engine_replica_readmissions_total") == 1
    assert exp.value("engine_pool_ejected_replicas") == 1
    assert exp.value("engine_replica_score", replica="0") == 1.0
    assert exp.value("engine_replica_score", replica="1") == 0.4375


# -- sharded-fabric / collection families ------------------------------------

_FABRIC_COUNTER_FAMILIES = (
    "rag_shard_searches_total",
    "rag_shard_queries_total",
    "rag_shard_fanout_requests_total",
    "rag_shard_fanout_batches_total",
    "rag_shard_replica_hydrations_total",
    "rag_coldtier_promotions_total",
    "rag_coldtier_demotions_total",
    "rag_coldtier_prefetches_total",
    "rag_coldtier_prefetch_bytes_total",
    "rag_collection_created_total",
    "rag_collection_dropped_total",
    "rag_collection_quota_rejections_total",
)
_FABRIC_GAUGE_FAMILIES = (
    "rag_shard_count",
    "rag_shard_hot",
    "rag_shard_cold",
    "rag_coldtier_host_bytes",
    "rag_scan_hbm_bytes_per_query",
    "rag_scan_host_bytes_per_query",
    "rag_collection_count",
)


def test_chain_server_fabric_families_export_from_zero(client):
    """The CHAIN document's rag_shard_* / rag_coldtier_* /
    rag_collection_* families: every series from zero with an unsharded
    memory store and no collection manager, so fabric dashboards can be
    written before the first shard exists."""
    c, loop = client

    async def go():
        resp = await c.get("/metrics")
        assert resp.status == 200
        return await resp.text()

    exp = parse_exposition(loop.run_until_complete(go()))
    for family in _FABRIC_COUNTER_FAMILIES:
        assert exp.value(family) == 0, family
        assert exp.types[family] == "counter", family
    for family in _FABRIC_GAUGE_FAMILIES:
        assert exp.value(family) == 0, family
        assert exp.types[family] == "gauge", family
    assert exp.types["rag_shard_merge_candidates"] == "summary"
    assert exp.value("rag_shard_merge_candidates_sum") == 0
    assert exp.value("rag_shard_merge_candidates_count") == 0


def test_engine_server_fabric_families_export_from_zero(
    monkeypatch, tmp_path
):
    """The ENGINE document carries the same fabric/collection schema from
    zero — the all-in-one process hosting a fabric store lands these
    series on the scrape endpoint operators actually watch."""
    _reset(monkeypatch, tmp_path)
    from generativeaiexamples_tpu.obs import reset_obs

    reset_obs()
    try:
        text = _scrape_engine_metrics()
    finally:
        reset_obs()
    exp = parse_exposition(text)
    for family in _FABRIC_COUNTER_FAMILIES:
        assert exp.value(family) == 0, family
    for family in _FABRIC_GAUGE_FAMILIES:
        assert exp.value(family) == 0, family
    assert exp.value("rag_shard_merge_candidates_count") == 0


def test_chain_server_fabric_metrics_live_with_fabric_store(
    monkeypatch, tmp_path
):
    """With the fabric backend configured and traffic flowing, the
    shard/collection families carry live values and the per-collection
    rag_store_rows{collection=...} series appears inside the aggregate's
    TYPE block."""
    _reset(monkeypatch, tmp_path)
    monkeypatch.setenv("APP_VECTORSTORE_NAME", "fabric")
    monkeypatch.setenv("APP_FABRIC_NUMSHARDS", "2")
    monkeypatch.setenv("APP_FABRIC_CHILDBACKEND", "memory")
    reset_config_cache()
    from generativeaiexamples_tpu.chains.factory import (
        get_collection_manager,
        get_store,
        reset_factories,
    )

    reset_factories()
    try:
        store = get_store()
        from generativeaiexamples_tpu.retrieval.base import Chunk

        store.add(
            [Chunk(text=f"t{i}", source="s") for i in range(8)],
            [[float(i)] * 64 for i in range(8)],
        )
        store.search([1.0] * 64, top_k=2)
        manager = get_collection_manager()
        manager.create("tenant-a")
        manager.add(
            "tenant-a",
            [Chunk(text="x", source="s2")],
            [[0.5] * 64],
        )

        from generativeaiexamples_tpu.server.app import create_app

        loop = asyncio.new_event_loop()
        client = TestClient(TestServer(create_app()), loop=loop)
        loop.run_until_complete(client.start_server())
        try:

            async def go():
                resp = await client.get("/metrics")
                assert resp.status == 200
                return await resp.text()

            text = loop.run_until_complete(go())
        finally:
            loop.run_until_complete(client.close())
            loop.close()
    finally:
        reset_config_cache()
        reset_factories()
    exp = parse_exposition(text)
    assert exp.value("rag_shard_count") == 2
    assert exp.value("rag_shard_hot") == 2
    assert exp.value("rag_shard_searches_total") >= 1
    assert exp.value("rag_scan_hbm_bytes_per_query") > 0
    assert exp.value("rag_collection_count") == 2  # default + tenant-a
    assert exp.value("rag_collection_created_total") == 1
    # Aggregate rows = fabric rows + tenant rows; the labeled series
    # reports the tenant alone, inside the same TYPE block.
    assert exp.value("rag_store_rows") == 9
    assert exp.value("rag_store_rows", collection="tenant-a") == 1
