"""Per-request telemetry: stage traces, latency histograms, flight
recorder, and the server wiring that ties them together.

Unit layer: RequestTrace / histogram families / FlightRecorder /
traced_stream / the ``traced`` generator fix / MicroBatcher trace
propagation across its worker thread.  HTTP layer: X-Request-Id and
Server-Timing on every response, from-zero histograms on ``/metrics``,
``GET /debug/requests`` including a fault-injected degraded request.
"""

import asyncio
import json
import os
import threading
import time

import pytest
from aiohttp.test_utils import TestClient, TestServer

from generativeaiexamples_tpu.core.configuration import reset_config_cache
from generativeaiexamples_tpu.obs import reset_obs
from generativeaiexamples_tpu.obs.metrics import (
    STAGES,
    obs_metrics_lines,
    obs_snapshot,
    observe_stage,
    reset_obs_metrics,
)
from generativeaiexamples_tpu.obs.recorder import (
    FlightRecorder,
    get_flight_recorder,
    reset_flight_recorder,
)
from generativeaiexamples_tpu.obs.trace import (
    RequestTrace,
    bind_request_trace,
    current_request_trace,
    trace_scope,
    traced_stream,
)


@pytest.fixture(autouse=True)
def _clean_obs():
    reset_obs()
    yield
    reset_obs()


# -- RequestTrace ------------------------------------------------------------


def test_trace_records_stages_and_attrs():
    trace = RequestTrace(request_id="abc", route="/search")
    trace.add_stage("embed", 12.5, batch_size=4)
    with trace.stage("search", fetch_k=16):
        pass
    trace.set_attr("store_version", 7)
    snap = trace.snapshot()
    assert snap["request_id"] == "abc"
    assert [s["stage"] for s in snap["stages"]] == ["embed", "search"]
    assert snap["stages"][0]["duration_ms"] == 12.5
    assert snap["stages"][0]["attrs"] == {"batch_size": 4}
    assert snap["attrs"]["store_version"] == 7
    # Stage observations landed in the histogram family too.
    hist = obs_snapshot()["stage"]
    assert hist["embed"]["count"] == 1
    assert hist["search"]["count"] == 1


def test_trace_finish_is_idempotent_and_feeds_request_histogram():
    trace = RequestTrace(route="/generate")
    snap1 = trace.finish(status=200)
    total1 = snap1["total_ms"]
    time.sleep(0.002)
    snap2 = trace.finish(status=500)
    assert snap2["total_ms"] == total1  # first finish wins
    assert snap2["status"] == 200
    assert obs_snapshot()["request"]["/generate"]["count"] == 1


def test_trace_error_and_degraded_lift_to_top_level():
    trace = RequestTrace(route="/generate")
    trace.mark_error(ValueError("boom"))
    trace.set_attr("degraded", ["retrieval"])
    snap = trace.finish(status=200)
    assert snap["error"] == "ValueError: boom"
    assert snap["degraded"] == ["retrieval"]


def test_trace_stage_cap():
    trace = RequestTrace()
    for _ in range(500):
        trace.add_stage("embed", 0.1)
    assert len(trace.snapshot()["stages"]) == 128


def test_server_timing_header_format():
    trace = RequestTrace(route="/search")
    trace.add_stage("embed", 3.25)
    trace.add_stage("search", 1.5)
    trace.finish(status=200)
    value = trace.server_timing()
    assert value.startswith("embed;dur=3.25, search;dur=1.5, total;dur=")


def test_trace_scope_and_bind():
    assert current_request_trace() is None
    trace = RequestTrace()
    with trace_scope(trace) as bound:
        assert bound is trace
        assert current_request_trace() is trace
    assert current_request_trace() is None


# -- histograms --------------------------------------------------------------


def test_histograms_export_from_zero():
    text = "\n".join(obs_metrics_lines())
    for stage in STAGES:
        assert f'rag_stage_latency_ms_bucket{{stage="{stage}",le="+Inf"}} 0' in text
    assert 'rag_request_latency_ms_bucket{route="/generate",le="+Inf"} 0' in text
    assert 'rag_request_latency_ms_sum{route="/search"} 0' in text


def test_histogram_buckets_are_cumulative():
    observe_stage("embed", 0.4)   # <= 0.5
    observe_stage("embed", 3.0)   # <= 5
    observe_stage("embed", 9999)  # only +Inf
    lines = [
        l for l in obs_metrics_lines() if 'stage="embed"' in l or "_count" in l
    ]
    text = "\n".join(lines)
    assert 'rag_stage_latency_ms_bucket{stage="embed",le="0.5"} 1' in text
    assert 'rag_stage_latency_ms_bucket{stage="embed",le="5"} 2' in text
    assert 'rag_stage_latency_ms_bucket{stage="embed",le="2500"} 2' in text
    assert 'rag_stage_latency_ms_bucket{stage="embed",le="+Inf"} 3' in text
    assert 'rag_stage_latency_ms_count{stage="embed"} 3' in text


def test_histogram_label_cardinality_folds_to_other():
    for i in range(200):
        observe_stage(f"weird_{i}", 1.0)
    snap = obs_snapshot()["stage"]
    assert len(snap) <= 65  # 64 labels + "other"
    assert snap["other"]["count"] > 0


def test_reset_obs_metrics_returns_to_known_zero():
    observe_stage("embed", 5.0)
    reset_obs_metrics()
    snap = obs_snapshot()["stage"]
    assert set(snap) == set(STAGES)
    assert all(v["count"] == 0 for v in snap.values())


# -- flight recorder ---------------------------------------------------------


def _snap(request_id, *, error=None, degraded=()):
    return {
        "request_id": request_id,
        "route": "/search",
        "status": 200,
        "error": error,
        "degraded": list(degraded),
        "total_ms": 1.0,
        "started_at": 0.0,
        "stages": [],
        "attrs": {},
    }


def test_recorder_orders_newest_first_and_limits():
    rec = FlightRecorder(capacity=8)
    for i in range(5):
        rec.record(_snap(f"r{i}"))
    out = rec.snapshot()
    assert [e["request_id"] for e in out] == ["r4", "r3", "r2", "r1", "r0"]
    assert [e["request_id"] for e in rec.snapshot(limit=2)] == ["r4", "r3"]


def test_recorder_pins_errors_and_degraded_against_eviction():
    rec = FlightRecorder(capacity=4, pinned_capacity=4)
    rec.record(_snap("bad", error="ValueError: boom"))
    rec.record(_snap("slow", degraded=["rerank"]))
    for i in range(20):  # healthy flood
        rec.record(_snap(f"ok{i}"))
    ids = {e["request_id"] for e in rec.snapshot()}
    assert "bad" in ids and "slow" in ids
    pinned = [e for e in rec.snapshot() if e.get("pinned")]
    assert {e["request_id"] for e in pinned} == {"bad", "slow"}


def test_recorder_singleton_sized_from_config(monkeypatch):
    monkeypatch.setenv("APP_OBSERVABILITY_FLIGHTRECORDERENTRIES", "3")
    reset_config_cache()
    reset_flight_recorder()
    try:
        rec = get_flight_recorder()
        assert rec.capacity == 3
        assert get_flight_recorder() is rec
    finally:
        monkeypatch.delenv("APP_OBSERVABILITY_FLIGHTRECORDERENTRIES")
        reset_config_cache()
        reset_flight_recorder()


# -- traced decorator (generator fix) ---------------------------------------


def test_traced_generator_stays_open_across_iteration():
    from generativeaiexamples_tpu.core.tracing import traced

    @traced("stream")
    def gen():
        yield 1
        yield 2

    out = list(gen())
    assert out == [1, 2]


def test_traced_generator_propagates_exceptions():
    from generativeaiexamples_tpu.core.tracing import traced

    @traced("stream")
    def gen():
        yield 1
        raise RuntimeError("mid-stream")

    g = gen()
    assert next(g) == 1
    with pytest.raises(RuntimeError, match="mid-stream"):
        next(g)


def test_traced_async_generator():
    from generativeaiexamples_tpu.core.tracing import traced

    @traced("astream")
    async def agen():
        yield "a"
        yield "b"

    async def collect():
        return [item async for item in agen()]

    assert asyncio.run(collect()) == ["a", "b"]


def test_traced_plain_and_async_functions_still_work():
    from generativeaiexamples_tpu.core.tracing import traced

    @traced("plain")
    def f(x):
        return x + 1

    @traced("coro")
    async def g(x):
        return x * 2

    assert f(1) == 2
    assert asyncio.run(g(3)) == 6


# -- traced_stream -----------------------------------------------------------


def test_traced_stream_records_ttft_and_stream_stages():
    trace = RequestTrace(route="/generate")

    def chunks():
        yield "a"
        yield "b"
        yield "c"

    assert list(traced_stream(chunks(), trace=trace)) == ["a", "b", "c"]
    stages = {s["stage"]: s for s in trace.snapshot()["stages"]}
    assert "llm_ttft" in stages
    assert stages["llm_stream"]["attrs"]["chunks"] == 3
    assert trace.snapshot()["attrs"]["llm_tokens_per_sec"] > 0


def test_traced_stream_without_trace_passes_through():
    assert list(traced_stream(iter("xyz"))) == ["x", "y", "z"]
    assert obs_snapshot()["stage"]["llm_ttft"]["count"] == 0


# -- MicroBatcher propagation ------------------------------------------------


def test_microbatcher_carries_traces_across_worker_thread():
    from generativeaiexamples_tpu.engine.microbatch import MicroBatcher

    batcher = MicroBatcher(
        lambda items: [x * 2 for x in items],
        max_batch=8,
        max_wait_ms=30.0,
        name="obs-test",
    )
    traces = [RequestTrace(request_id=f"t{i}") for i in range(3)]
    results = [None] * 3
    barrier = threading.Barrier(3)

    def worker(i):
        barrier.wait(10)
        with trace_scope(traces[i]):  # captured by submit(), not passed
            results[i] = batcher.call(i)

    threads = [threading.Thread(target=worker, args=(i,)) for i in range(3)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(10)
    batcher.close()
    assert results == [0, 2, 4]
    batch_ids = set()
    for trace in traces:
        stages = [s for s in trace.snapshot()["stages"] if s["stage"] == "queue_wait"]
        assert len(stages) == 1
        assert stages[0]["attrs"]["batch_size"] == 3
        batch_ids.add(stages[0]["attrs"]["batch_id"])
    assert len(batch_ids) == 1  # all three rode the same dispatch
    assert batch_ids.pop().startswith("obs-test-")


def test_microbatcher_error_isolation_keeps_batchmates_traces():
    from generativeaiexamples_tpu.engine.microbatch import MicroBatcher

    def fn(items):
        if any(x == "bad" for x in items):
            raise ValueError("poisoned batch")
        return [x.upper() for x in items]

    batcher = MicroBatcher(fn, max_batch=8, max_wait_ms=30.0, name="obs-iso")
    good = RequestTrace()
    bad = RequestTrace()
    futs = [
        batcher.submit("ok", trace=good),
        batcher.submit("bad", trace=bad),
    ]
    assert futs[0].result(timeout=5) == "OK"
    with pytest.raises(ValueError):
        futs[1].result(timeout=5)
    batcher.close()
    # Both members recorded their queue wait before the retry split.
    for trace in (good, bad):
        assert any(
            s["stage"] == "queue_wait" for s in trace.snapshot()["stages"]
        )


# -- HTTP layer --------------------------------------------------------------


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


def _run(loop, coro):
    return loop.run_until_complete(coro)


async def _ingest(c, tmp_path, text):
    doc = tmp_path / "obs_doc.txt"
    doc.write_text(text)
    with open(doc, "rb") as fh:
        resp = await c.post("/documents", data={"file": fh})
    assert resp.status == 200


def test_every_response_carries_request_id_and_server_timing(client):
    c, loop = client

    async def go():
        resp = await c.get("/health")
        assert resp.status == 200
        assert len(resp.headers["X-Request-Id"]) == 32
        assert "total;dur=" in resp.headers["Server-Timing"]
        # A client-supplied id is echoed, not replaced.
        resp = await c.get("/health", headers={"X-Request-Id": "my-id-42"})
        assert resp.headers["X-Request-Id"] == "my-id-42"

    _run(loop, go())


def test_search_response_headers_and_trace_stages(client, tmp_path):
    c, loop = client

    async def go():
        await _ingest(c, tmp_path, "TPUs multiply matrices.\n\nBees make honey.")
        resp = await c.post("/search", json={"query": "TPU", "top_k": 1})
        assert resp.status == 200
        timing = resp.headers["Server-Timing"]
        req_id = resp.headers["X-Request-Id"]
        debug = await (await c.get("/debug/requests")).json()
        return timing, req_id, debug

    timing, req_id, debug = _run(loop, go())
    assert "embed;dur=" in timing and "search;dur=" in timing
    record = next(
        r for r in debug["requests"] if r["request_id"] == req_id
    )
    assert record["route"] == "/search"
    assert record["status"] == 200
    stage_names = [s["stage"] for s in record["stages"]]
    for expected in ("cache_lookup", "queue_wait", "embed", "search"):
        assert expected in stage_names, stage_names
    assert record["total_ms"] > 0
    assert record["attrs"]["store_version"] >= 1


def test_generate_stream_carries_telemetry_headers(client):
    c, loop = client

    async def go():
        resp = await c.post(
            "/generate",
            json={
                "messages": [{"role": "user", "content": "ping"}],
                "use_knowledge_base": False,
            },
        )
        assert resp.status == 200
        assert len(resp.headers["X-Request-Id"]) == 32
        assert "Server-Timing" in resp.headers
        await resp.read()

    _run(loop, go())
    records = get_flight_recorder().snapshot()
    gen = next(r for r in records if r["route"] == "/generate")
    stage_names = [s["stage"] for s in gen["stages"]]
    assert "llm_ttft" in stage_names and "llm_stream" in stage_names
    assert gen["attrs"]["llm_tokens_per_sec"] > 0


def test_metrics_exports_stage_histograms_from_zero(client):
    c, loop = client

    async def go():
        resp = await c.get("/metrics")
        assert resp.status == 200
        return await resp.text()

    text = _run(loop, go())
    for stage in STAGES:
        assert f'rag_stage_latency_ms_bucket{{stage="{stage}",le="+Inf"}}' in text
    assert 'rag_request_latency_ms_bucket{route="/generate"' in text
    assert "rag_cache_semantic_scan_ms_count" in text


def test_metrics_histograms_count_served_requests(client, tmp_path):
    c, loop = client

    async def go():
        await _ingest(c, tmp_path, "Sharks are fish.\n\nWhales are mammals.")
        # Distinct queries: a repeat would serve from the exact cache and
        # legitimately skip the embed stage.
        for query in ("whales", "sharks"):
            resp = await c.post("/search", json={"query": query, "top_k": 1})
            assert resp.status == 200
        return await (await c.get("/metrics")).text()

    text = _run(loop, go())
    line = next(
        l for l in text.splitlines()
        if l.startswith('rag_request_latency_ms_count{route="/search"}')
    )
    assert int(line.rsplit(" ", 1)[1]) == 2
    embed_count = next(
        l for l in text.splitlines()
        if l.startswith('rag_stage_latency_ms_count{stage="embed"}')
    )
    assert int(embed_count.rsplit(" ", 1)[1]) >= 2


def test_concurrent_search_burst_shares_one_batch(client, tmp_path):
    c, loop = client

    async def go():
        await _ingest(
            c, tmp_path, "Alpha beta gamma.\n\nDelta epsilon zeta."
        )
        get_flight_recorder().reset()
        resps = await asyncio.gather(*[
            c.post("/search", json={"query": f"word {i}", "top_k": 1})
            for i in range(4)
        ])
        assert all(r.status == 200 for r in resps)
        return await (await c.get("/debug/requests")).json()

    debug = _run(loop, go())
    searches = [r for r in debug["requests"] if r["route"] == "/search"]
    assert len(searches) == 4
    batch_ids = set()
    for rec in searches:
        waits = [s for s in rec["stages"] if s["stage"] == "queue_wait"]
        assert len(waits) == 1
        batch_ids.add(waits[0]["attrs"]["batch_id"])
    # The burst coalesced: far fewer dispatches than requests (usually 1).
    assert len(batch_ids) < 4


def test_degraded_generate_is_pinned_with_rung_and_stages(client, monkeypatch):
    c, loop = client
    from generativeaiexamples_tpu.resilience.faults import get_fault_injector

    get_fault_injector().configure("embedder:error=1.0")
    try:

        async def go():
            resp = await c.post(
                "/generate",
                json={
                    "messages": [{"role": "user", "content": "anything"}],
                    "use_knowledge_base": True,
                },
            )
            assert resp.status == 200
            body = await resp.text()
            chunks = [
                json.loads(line[len("data: "):])
                for line in body.splitlines()
                if line.startswith("data: ")
            ]
            assert "retrieval" in chunks[-1]["degraded"]
            return await (await c.get("/debug/requests")).json()

        debug = _run(loop, go())
    finally:
        from generativeaiexamples_tpu.resilience.faults import reset_faults

        reset_faults()
    record = next(r for r in debug["requests"] if r["route"] == "/generate")
    assert record["pinned"] is True
    assert record["degraded"] == ["retrieval"]
    # The degraded request still answered (LLM-only ladder rung), so the
    # postmortem shows where its time went.
    stage_names = [s["stage"] for s in record["stages"]]
    assert "llm_stream" in stage_names
    assert all(s["duration_ms"] >= 0 for s in record["stages"])


def test_debug_requests_limit_and_validation(client):
    c, loop = client

    async def go():
        for _ in range(3):
            await c.get("/health")
        full = await (await c.get("/debug/requests")).json()
        limited = await (await c.get("/debug/requests?limit=1")).json()
        bad = await c.get("/debug/requests?limit=nope")
        return full, limited, bad.status

    full, limited, bad_status = _run(loop, go())
    assert full["count"] >= 3
    assert limited["count"] == 1
    # Newest first (the first /debug/requests scrape itself completes a
    # trace between the two reads, so >= rather than ==).
    assert limited["requests"][0]["seq"] >= max(
        r["seq"] for r in full["requests"]
    )
    assert bad_status == 422


def test_observability_disable_drops_traces_but_keeps_request_ids(
    monkeypatch, tmp_path
):
    _reset(monkeypatch, tmp_path)
    monkeypatch.setenv("APP_OBSERVABILITY_ENABLED", "false")
    reset_config_cache()
    from generativeaiexamples_tpu.server.app import create_app

    loop = asyncio.new_event_loop()
    client = TestClient(TestServer(create_app()), loop=loop)
    loop.run_until_complete(client.start_server())
    try:

        async def go():
            resp = await c_get(client, "/health")
            assert "X-Request-Id" in resp.headers
            assert "Server-Timing" not in resp.headers
            debug = await (await c_get(client, "/debug/requests")).json()
            assert debug["count"] == 0

        async def c_get(c, path):
            return await c.get(path)

        loop.run_until_complete(go())
    finally:
        loop.run_until_complete(client.close())
        loop.close()
        reset_config_cache()
        from generativeaiexamples_tpu.chains.factory import reset_factories

        reset_factories()
