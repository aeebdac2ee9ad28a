"""Fleet observability: W3C trace propagation across processes, the
in-process ring TSDB, the SLO burn-rate alert engine, and the server
surfaces that tie them together.

Unit layer: inject/extract trace headers, ring folding/wrap/downsampling,
``parse_window``, burn-rate math and alert transitions.  HTTP layer:
``/debug/timeseries`` on both servers, an end-to-end ``/generate`` whose
chain-side request id shows up on the ENGINE's ``/debug/requests``, and a
chaos run where an embedder fault burst flips the fast-burn alert
(``/metrics`` + ``/health`` + pinned flight-recorder transition) and a
clean recovery clears it.
"""

import asyncio
import os
import time

import pytest
from aiohttp.test_utils import TestClient, TestServer

from generativeaiexamples_tpu.core.configuration import reset_config_cache
from generativeaiexamples_tpu.core.tracing import (
    extract_trace_headers,
    inject_trace_headers,
)
from generativeaiexamples_tpu.obs import reset_obs
from generativeaiexamples_tpu.obs.recorder import get_flight_recorder
from generativeaiexamples_tpu.obs.slo import SloEngine, parse_latency_targets
from generativeaiexamples_tpu.obs.trace import RequestTrace, bind_request_trace
from generativeaiexamples_tpu.obs.tsdb import Series, Tsdb, parse_window
from generativeaiexamples_tpu.obs.exposition import parse_exposition


# -- trace header propagation -------------------------------------------------


RID = "0af7651916cd43dd8448eb211c80319c"


def test_inject_explicit_request_id_sets_both_headers():
    headers = inject_trace_headers({}, request_id=RID)
    assert headers["X-Request-Id"] == RID
    version, trace_id, span_id, flags = headers["traceparent"].split("-")
    assert (version, trace_id, flags) == ("00", RID, "01")
    assert len(span_id) == 16 and int(span_id, 16) != 0


def test_inject_uses_ambient_trace_and_preserves_existing_headers():
    trace = RequestTrace(request_id=RID, route="/search")
    bind_request_trace(trace)
    try:
        headers = inject_trace_headers({"Authorization": "Bearer x"})
    finally:
        bind_request_trace(None)
    assert headers["Authorization"] == "Bearer x"
    assert headers["X-Request-Id"] == RID
    assert headers["traceparent"].split("-")[1] == RID


def test_inject_without_any_request_id_is_a_noop():
    assert inject_trace_headers({}) == {}


def test_inject_non_hex_request_id_skips_traceparent():
    headers = inject_trace_headers({}, request_id="my-id-42")
    assert headers["X-Request-Id"] == "my-id-42"
    assert "traceparent" not in headers


def test_extract_round_trip_and_span_ids_differ_per_injection():
    h1 = inject_trace_headers({}, request_id=RID)
    h2 = inject_trace_headers({}, request_id=RID)
    rid, parent = extract_trace_headers(h1)
    assert rid == RID
    assert parent == h1["traceparent"].split("-")[2]
    # Each hop gets its own span id under the same trace id.
    assert h1["traceparent"] != h2["traceparent"]


@pytest.mark.parametrize(
    "raw",
    [
        "banana",
        "00-zz-17851af7651916cd-01",  # non-hex trace id
        f"00-{'0' * 32}-17851af7651916cd-01",  # all-zero trace id
        f"00-{RID}-{'0' * 16}-01",  # all-zero span id
        f"00-{RID}",  # too few fields
    ],
)
def test_extract_malformed_traceparent_falls_back(raw):
    rid, parent = extract_trace_headers({"traceparent": raw, "X-Request-Id": "fb1"})
    assert (rid, parent) == ("fb1", "")


def test_extract_empty_headers():
    assert extract_trace_headers({}) == ("", "")


# -- TSDB ---------------------------------------------------------------------


def test_series_window_stats_and_points():
    s = Series("lat")
    now = 1_000_000.0
    for i, v in enumerate([10.0, 20.0, 30.0]):
        s.record(v, ts=now - i)  # one point per second, newest first
    count, total = s.window_stats(10.0, now=now)
    assert (count, total) == (3, 60.0)
    count, total = s.window_stats(1.5, now=now)
    assert (count, total) == (2, 30.0)  # 30.0 fell out of the window
    pts = s.points(10.0, now=now)
    assert [p[0] for p in pts] == sorted(p[0] for p in pts)
    ts, count, total, mn, mx = pts[0]
    assert (count, total, mn, mx) == (1, 30.0, 30.0, 30.0)


def test_series_buckets_aggregate_within_step():
    s = Series("lat")
    now = 2_000_000.0
    for v in (5.0, 15.0, 10.0):
        s.record(v, ts=now + 0.2)
    ((_, count, total, mn, mx),) = s.points(5.0, now=now + 1)
    assert (count, total, mn, mx) == (3, 30.0, 5.0, 15.0)


def test_ring_wrap_evicts_dead_buckets():
    s = Series("w", fine_buckets=4, coarse_buckets=4, coarse_step=1.0)
    now = 3_000_000.0
    s.record(1.0, ts=now - 10)  # will be overwritten / out of live range
    s.record(2.0, ts=now)
    count, total = s.window_stats(100.0, now=now)
    # The 4-bucket ring only keeps 4 s of history: the old point is dead
    # even though the query window would cover it.
    assert (count, total) == (1, 2.0)


def test_long_windows_fall_back_to_coarse_ring():
    s = Series("c")
    now = 4_000_000.0
    s.record(1.0, ts=now - 3600)  # outside the 900 s fine ring
    s.record(1.0, ts=now)
    count, _ = s.window_stats(600.0, now=now)  # fine ring serves this
    assert count == 1
    count, total = s.window_stats(7200.0, now=now)  # needs the coarse ring
    assert (count, total) == (2, 2.0)


def test_tsdb_query_filters_exact_and_prefix():
    db = Tsdb()
    now = 5_000_000.0
    db.record("chain.requests./search", 1.0, kind="counter", ts=now)
    db.record("chain.requests./generate", 1.0, kind="counter", ts=now)
    db.record("engine.tick_ms", 0.5, ts=now)
    out = db.query(60.0, ["chain.requests.*", "engine.tick_ms", "nope"], now=now)
    assert sorted(out["series"]) == [
        "chain.requests./generate",
        "chain.requests./search",
        "engine.tick_ms",
    ]
    assert out["series"]["chain.requests./search"]["kind"] == "counter"
    assert out["columns"] == ["ts", "count", "sum", "min", "max"]
    everything = db.query(60.0, now=now)
    assert len(everything["series"]) == 3


def test_tsdb_series_cardinality_folds_to_other():
    db = Tsdb(max_series=2)
    db.record("a", 1.0)
    db.record("b", 1.0)
    db.record("c", 1.0)
    db.record("d", 1.0)
    assert db.names() == ["a", "b", "other"]


@pytest.mark.parametrize(
    "raw,expected",
    [("", 300.0), ("45", 45.0), ("500ms", 0.5), ("30s", 30.0), ("5m", 300.0), ("2h", 7200.0)],
)
def test_parse_window_units(raw, expected):
    assert parse_window(raw) == expected


@pytest.mark.parametrize("raw", ["soon", "-5", "0", "5x"])
def test_parse_window_rejects_garbage(raw):
    with pytest.raises(ValueError):
        parse_window(raw)


# -- SLO engine ---------------------------------------------------------------


def test_parse_latency_targets():
    assert parse_latency_targets("/generate=2500, /search=500") == {
        "/generate": 2500.0,
        "/search": 500.0,
    }
    assert parse_latency_targets("") == {}
    assert parse_latency_targets("bad,=,x=notanumber") == {}


class _Cfg:
    """Minimal slo-config stand-in for hermetic engine tests."""

    enabled = True
    availability_target = 0.999
    latency_p95_ms = "/search=100"
    fast_window_s = 60.0
    slow_window_s = 300.0
    fast_burn_threshold = 14.4
    slow_burn_threshold = 6.0
    evaluation_period_s = 0.0


class _Recorder:
    def __init__(self):
        self.entries = []

    def record(self, entry):
        self.entries.append(entry)


def _engine():
    return SloEngine(_Cfg(), tsdb=Tsdb(), recorder=_Recorder())


def test_burn_rate_math_and_budget():
    eng = _engine()
    now = 6_000_000.0
    for i in range(100):
        eng.note_request("/search", 10.0, error=(i < 2), ts=now - i * 0.01)
    verdict = eng.evaluate(now=now + 1, force=True)
    avail = verdict["routes"]["/search"]["availability"]
    # 2% bad over a 0.1% budget -> burn rate 20x on every window.
    fast = avail["windows"]["fast"]
    assert fast["burn_rate"] == pytest.approx(20.0, rel=0.01)
    assert fast["firing"] is True  # 20 >= 14.4 on both windows
    assert avail["windows"]["slow"]["firing"] is True
    assert avail["error_budget_remaining"] == pytest.approx(-1.0)


def test_alert_fires_and_resolves_with_pinned_transitions():
    eng = _engine()
    now = 7_000_000.0
    for i in range(50):
        eng.note_request("/search", 10.0, error=True, ts=now + i * 0.01)
    verdict = eng.evaluate(now=now + 1, force=True)
    assert verdict["fast_burn_firing"] is True
    assert "/search:availability" in verdict["firing"]["fast"]
    firing = [
        e for e in eng._recorder.entries if e["attrs"]["state"] == "firing"
    ]
    assert any(
        e["attrs"]["slo_alert"] == "/search:availability:fast" for e in firing
    )
    # All transition entries are valid flight-recorder records: the
    # degraded rung is what pins them against eviction.
    assert all(isinstance(e["degraded"], list) and e["degraded"] for e in firing)

    # Clean traffic after the windows have drained -> alert resolves.
    later = now + 4000  # beyond fast (60 s) and its 12x confirmation window
    for i in range(50):
        eng.note_request("/search", 10.0, error=False, ts=later + i * 0.01)
    verdict = eng.evaluate(now=later + 1, force=True)
    assert verdict["fast_burn_firing"] is False
    resolved = [
        e for e in eng._recorder.entries if e["attrs"]["state"] == "resolved"
    ]
    assert any(
        e["attrs"]["slo_alert"] == "/search:availability:fast" for e in resolved
    )


def test_latency_slo_burns_only_over_target():
    eng = _engine()
    now = 8_000_000.0
    for i in range(10):
        # Half the requests exceed the 100 ms /search budget.
        eng.note_request("/search", 200.0 if i % 2 else 50.0, ts=now + i * 0.01)
    verdict = eng.evaluate(now=now + 1, force=True)
    lat = verdict["routes"]["/search"]["latency"]
    assert lat["windows"]["fast"]["burn_rate"] == pytest.approx(500.0, rel=0.01)
    # Routes without a latency target only track availability.
    eng.note_request("/other-route", 10_000.0, ts=now)
    verdict = eng.evaluate(now=now + 1, force=True)
    assert "latency" not in verdict["routes"]["/other-route"]


def test_single_window_spike_does_not_fire():
    """Multi-window rule: a burst that is bad NOW but fine over the 12x
    confirmation window must not page (the stale/blip suppressor)."""
    eng = _engine()
    now = 9_000_000.0
    # 12x window (720 s) holds lots of good traffic...
    for i in range(500):
        eng.note_request("/search", 10.0, ts=now - 700 + i)
    # ...then a 5-request bad blip in the fast window.
    for i in range(5):
        eng.note_request("/search", 10.0, error=True, ts=now + i * 0.01)
    verdict = eng.evaluate(now=now + 1, force=True)
    fast = verdict["routes"]["/search"]["availability"]["windows"]["fast"]
    assert fast["burn_rate"] >= 14.4  # short window alone would page
    assert fast["firing"] is False  # confirmation window vetoes it


def test_route_cardinality_folds_to_other():
    eng = _engine()
    now = 9_500_000.0
    for i in range(40):
        eng.note_request(f"/route-{i}", 1.0, ts=now)
    verdict = eng.evaluate(now=now + 1, force=True)
    assert "other" in verdict["routes"]
    assert len(verdict["routes"]) <= 17  # 16 + the overflow route


def test_metrics_lines_export_configured_routes_from_zero():
    eng = _engine()
    exp = parse_exposition("\n".join(eng.metrics_lines(now=10_000_000.0)) + "\n")
    assert (
        exp.value("rag_slo_error_budget_remaining", route="/search", slo="latency")
        == 1.0
    )
    for window in ("fast", "slow"):
        assert (
            exp.value(
                "rag_slo_burn_rate",
                route="/search",
                slo="availability",
                window=window,
            )
            == 0.0
        )
        assert (
            exp.value(
                "rag_slo_alert_state",
                route="/search",
                slo="availability",
                window=window,
            )
            == 0.0
        )


def test_disabled_slo_is_inert():
    class _Off(_Cfg):
        enabled = False

    eng = SloEngine(_Off(), tsdb=Tsdb(), recorder=_Recorder())
    eng.note_request("/search", 10.0, error=True)
    assert eng.tsdb.names() == []
    assert eng.evaluate(force=True) == {
        "enabled": False,
        "routes": {},
        "fast_burn_firing": False,
    }
    assert eng.metrics_lines() == []


# -- HTTP layer ---------------------------------------------------------------


def _reset(monkeypatch, tmp_path, extra=()):
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
    for key, value in extra:
        monkeypatch.setenv(key, value)
    reset_config_cache()
    reset_factories()


def _start(loop, app):
    client = TestClient(TestServer(app), loop=loop)
    loop.run_until_complete(client.start_server())
    return client


def _teardown(loop, *clients):
    for client in clients:
        loop.run_until_complete(client.close())
    loop.close()
    reset_config_cache()
    from generativeaiexamples_tpu.chains.factory import reset_factories

    reset_factories()


@pytest.fixture
def chain_client(monkeypatch, tmp_path):
    _reset(monkeypatch, tmp_path)
    from generativeaiexamples_tpu.server.app import create_app

    loop = asyncio.new_event_loop()
    client = _start(loop, create_app())
    yield client, loop
    _teardown(loop, client)


def test_debug_timeseries_endpoint(chain_client):
    c, loop = chain_client

    async def go():
        for _ in range(2):
            await c.post("/search", json={"query": "tpu", "top_k": 1})
        full = await (await c.get("/debug/timeseries")).json()
        filtered = await (
            await c.get("/debug/timeseries?series=chain.requests.*&window=1m")
        ).json()
        bad = await c.get("/debug/timeseries?window=soon")
        return full, filtered, bad.status

    full, filtered, bad_status = loop.run_until_complete(go())
    assert bad_status == 422
    assert full["columns"] == ["ts", "count", "sum", "min", "max"]
    assert "chain.requests./search" in full["series"]
    assert "chain.request_ms./search" in full["series"]
    assert "slo.total./search" in full["names"]
    # Scrape/health probes never show up as request series.
    assert not any("/debug" in name for name in full["names"])
    assert list(filtered["series"]) == ["chain.requests./search"]
    assert filtered["window_s"] == 60.0
    pts = filtered["series"]["chain.requests./search"]["points"]
    assert sum(p[1] for p in pts) == 2


def test_chain_health_and_metrics_carry_slo_surface(chain_client):
    c, loop = chain_client

    async def go():
        health = await (await c.get("/health")).json()
        metrics = await (await c.get("/metrics")).text()
        return health, metrics

    health, metrics = loop.run_until_complete(go())
    assert health["status"] == "ok"
    assert health["slo"] == {"degraded": False, "firing": {"fast": [], "slow": []}}
    exp = parse_exposition(metrics)
    # Configured objectives export from zero, before any traffic.
    assert (
        exp.value("rag_slo_burn_rate", route="/generate", slo="availability", window="fast")
        == 0.0
    )
    assert (
        exp.value("rag_slo_error_budget_remaining", route="/search", slo="latency")
        == 1.0
    )


# -- end-to-end: chain -> engine trace propagation ----------------------------


@pytest.fixture
def fleet(monkeypatch, tmp_path):
    """A chain server whose "openai" LLM backend is our own engine server:
    the smallest real two-server fleet."""
    from generativeaiexamples_tpu.engine.scheduler import Scheduler
    from generativeaiexamples_tpu.engine.server import create_engine_app
    from generativeaiexamples_tpu.engine.tokenizer import ByteTokenizer
    from generativeaiexamples_tpu.models import llama

    _reset(
        monkeypatch,
        tmp_path,
        extra=[
            ("APP_LLM_MODELENGINE", "openai"),
            ("APP_LLM_MODELNAME", "llama-tiny"),
        ],
    )
    cfg = llama.llama_tiny(dtype="float32", max_seq_len=1024)
    sched = Scheduler(cfg, max_batch=2, max_len=1024, decode_chunk_size=8)
    loop = asyncio.new_event_loop()
    engine = _start(
        loop, create_engine_app(sched, ByteTokenizer(), model_name="llama-tiny")
    )
    monkeypatch.setenv("APP_LLM_SERVERURL", str(engine.make_url("/v1")))
    reset_config_cache()
    from generativeaiexamples_tpu.chains.factory import reset_factories

    reset_factories()
    # Only now: the tick thread's first pass through its fault point
    # loads the config, and a load that began before the URL was set and
    # ended after the cache was cleared would leave the chain a config
    # without it (the failure under six workers: "Connection refused").
    sched.start()
    from generativeaiexamples_tpu.server.app import create_app

    chain = _start(loop, create_app())
    yield chain, engine, loop
    _teardown(loop, chain, engine)
    sched.stop()


def test_generate_request_id_spans_chain_and_engine(fleet):
    chain, engine, loop = fleet

    async def go():
        resp = await chain.post(
            "/generate",
            json={
                "messages": [{"role": "user", "content": "ping"}],
                "use_knowledge_base": False,
                "max_tokens": 4,
            },
        )
        assert resp.status == 200
        req_id = resp.headers["X-Request-Id"]
        await resp.read()

        def recorded(debug, route):
            return any(
                r["route"] == route and r["request_id"] == req_id
                for r in debug["requests"]
            )

        # Both flight recorders and the tick loop's TSDB are fed from
        # other threads after the response is out: wait until all three
        # show the request, not for a time that a loaded host outruns.
        deadline = time.monotonic() + 60.0
        while True:
            chain_debug = await (await chain.get("/debug/requests")).json()
            engine_debug = await (await engine.get("/debug/requests")).json()
            series = await (
                await engine.get("/debug/timeseries?series=engine.*")
            ).json()
            ticks = series["series"].get("engine.tick_ms", {"points": []})
            if (
                recorded(chain_debug, "/generate")
                and recorded(engine_debug, "/v1/chat/completions")
                and sum(p[1] for p in ticks["points"]) > 0
            ) or time.monotonic() > deadline:
                break
            await asyncio.sleep(0.05)
        return req_id, chain_debug, engine_debug, series

    req_id, chain_debug, engine_debug, series = loop.run_until_complete(go())
    assert len(req_id) == 32

    chain_rec = next(
        r
        for r in chain_debug["requests"]
        if r["route"] == "/generate" and r["request_id"] == req_id
    )
    assert chain_rec["status"] == 200

    # The engine-side trace JOINED the chain server's W3C context: same
    # request id, with the caller's span recorded as the parent.
    engine_rec = next(
        r
        for r in engine_debug["requests"]
        if r["route"] == "/v1/chat/completions" and r["request_id"] == req_id
    )
    assert engine_rec["attrs"]["propagated"] is True
    parent_span = engine_rec["attrs"]["parent_span_id"]
    assert len(parent_span) == 16 and int(parent_span, 16) != 0

    # The scheduler tick loop feeds the engine-side TSDB.
    assert "engine.tick_ms" in series["series"]
    assert sum(p[1] for p in series["series"]["engine.tick_ms"]["points"]) > 0


def test_engine_metrics_and_health_carry_fleet_surface(fleet):
    _, engine, loop = fleet

    async def go():
        health = await (await engine.get("/health")).json()
        metrics = await (await engine.get("/metrics")).text()
        return health, metrics

    health, metrics = loop.run_until_complete(go())
    assert health["status"] == "ok"
    assert health["slo"]["degraded"] is False
    exp = parse_exposition(metrics)
    assert exp.value("engine_tick_duration_ms_count", loop="tick") >= 0.0
    assert (
        exp.value("rag_slo_burn_rate", route="/generate", slo="availability", window="fast")
        == 0.0
    )


# -- chaos: fault burst -> fast-burn alert -> recovery ------------------------


@pytest.fixture
def chaos_client(monkeypatch, tmp_path):
    _reset(
        monkeypatch,
        tmp_path,
        extra=[
            # Tiny windows so fire/clear cycles fit a test: fast rule
            # 1 s / 12 s confirmation, evaluated fresh on every read.
            ("APP_SLO_FASTWINDOWS", "1.0"),
            ("APP_SLO_SLOWWINDOWS", "3.0"),
            ("APP_SLO_EVALUATIONPERIODS", "0"),
        ],
    )
    from generativeaiexamples_tpu.server.app import create_app

    loop = asyncio.new_event_loop()
    client = _start(loop, create_app())
    yield client, loop
    from generativeaiexamples_tpu.resilience.faults import reset_faults

    reset_faults()
    _teardown(loop, client)


def test_fault_burst_flips_fast_burn_alert_and_recovery_clears_it(chaos_client):
    c, loop = chaos_client
    from generativeaiexamples_tpu.resilience.faults import (
        get_fault_injector,
        reset_faults,
    )

    async def burst(n):
        for _ in range(n):
            resp = await c.post(
                "/generate",
                json={
                    "messages": [{"role": "user", "content": "hi"}],
                    "use_knowledge_base": True,
                },
            )
            assert resp.status == 200
            await resp.read()

    async def read_surface():
        health = await (await c.get("/health")).json()
        metrics = await (await c.get("/metrics")).text()
        return health, parse_exposition(metrics)

    def burn(exp, window):
        return exp.value(
            "rag_slo_burn_rate", route="/generate", slo="availability", window=window
        )

    def state(exp, window):
        return exp.value(
            "rag_slo_alert_state", route="/generate", slo="availability", window=window
        )

    # Phase 1 — chaos: every /generate degrades (retrieval rung) and burns
    # the availability budget; the alert must flip within one evaluation.
    get_fault_injector().configure("embedder:error=1.0")
    try:
        loop.run_until_complete(burst(6))
        health, exp = loop.run_until_complete(read_surface())
    finally:
        reset_faults()
    assert burn(exp, "fast") >= 14.4
    assert state(exp, "fast") == 1.0
    assert health["status"] == "degraded"
    assert health["slo"]["degraded"] is True
    assert "/generate:availability" in health["slo"]["firing"]["fast"]
    assert exp.value("rag_slo_error_budget_remaining", route="/generate", slo="availability") == -1.0

    # The transition is pinned into the flight recorder for postmortems.
    records = get_flight_recorder().snapshot()
    firing = next(
        r
        for r in records
        if r.get("attrs", {}).get("slo_alert") == "/generate:availability:fast"
        and r["attrs"]["state"] == "firing"
    )
    assert firing["pinned"] is True
    # ...and /debug/requests can render it (schema-valid record).
    debug = loop.run_until_complete(_fetch_debug(c))
    assert any(
        r.get("attrs", {}).get("slo_alert") == "/generate:availability:fast"
        for r in debug["requests"]
    )

    # Phase 2 — recovery: clean traffic after the fast window drains.
    # The embedder breaker opened during the burst; clear it too, or the
    # "clean" requests would keep degrading (and keep burning budget).
    from generativeaiexamples_tpu.resilience.breaker import reset_breakers

    reset_breakers()
    time.sleep(2.3)
    loop.run_until_complete(burst(4))
    health, exp = loop.run_until_complete(read_surface())
    assert burn(exp, "fast") == 0.0
    assert state(exp, "fast") == 0.0
    assert health["status"] == "ok"
    assert health["slo"]["degraded"] is False
    records = get_flight_recorder().snapshot()
    assert any(
        r.get("attrs", {}).get("slo_alert") == "/generate:availability:fast"
        and r["attrs"]["state"] == "resolved"
        for r in records
    )


async def _fetch_debug(c):
    return await (await c.get("/debug/requests")).json()
