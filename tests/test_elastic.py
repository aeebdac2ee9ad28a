"""Closed-loop elasticity tests: admission control + autoscaler + pool.

Covers the three layers PR 11 couples together — the priority-class
``AdmissionController`` (quota / weighted-share / deadline shedding with
pinned onset-resolve transitions), the SLO-driven ``Autoscaler``
decision loop (hysteresis, cooldowns, clamps, pinned scale events), and
the live ``EnginePool`` actuation path (scale-up mid-traffic via the
scheduler factory, the drain-during-scale-down race with an in-flight
generation, per-replica TSDB series cleanup, the engine 429
``Retry-After`` hint, ``/admin/scale``, and chain-server admission
end-to-end over HTTP).
"""

import asyncio
import os
import threading
import time

import pytest
from aiohttp.test_utils import TestClient, TestServer

from generativeaiexamples_tpu.core.configuration import (
    AdmissionConfig,
    AutoscaleConfig,
    reset_config_cache,
)
from generativeaiexamples_tpu.engine.autoscale import (
    Autoscaler,
    pool_metrics_lines,
)
from generativeaiexamples_tpu.obs.tsdb import Tsdb
from generativeaiexamples_tpu.resilience.admission import (
    CLASSES,
    AdmissionController,
)


class _Recorder:
    """Flight-recorder stand-in capturing every transition record."""

    def __init__(self):
        self.records = []

    def record(self, entry):
        self.records.append(entry)


def _ctrl(recorder=None, **kw):
    cfg = AdmissionConfig(**kw)
    return AdmissionController(
        cfg, recorder=recorder or _Recorder(), tsdb=Tsdb()
    )


# -- admission: classification ----------------------------------------------


class TestClassify:
    def test_header_wins_case_insensitive(self):
        ctrl = _ctrl()
        assert ctrl.classify({"X-Traffic-Class": "Batch"}) == "batch"
        assert ctrl.classify({"x-traffic-class": "ingest"}) == "ingest"

    def test_unknown_header_value_falls_through(self):
        ctrl = _ctrl()
        # A typo must not change priority: treated as absent.
        assert ctrl.classify({"X-Traffic-Class": "premium"}) == "interactive"
        assert (
            ctrl.classify({"X-Traffic-Class": "premium"}, default="ingest")
            == "ingest"
        )

    def test_route_default_then_config_default(self):
        ctrl = _ctrl(default_class="batch")
        assert ctrl.classify({}) == "batch"
        assert ctrl.classify({}, default="ingest") == "ingest"
        assert ctrl.classify(None) == "batch"


# -- admission: the three gates ---------------------------------------------


class TestAdmissionGates:
    def test_quota_sheds_over_rate_class_only(self):
        ctrl = _ctrl(rates="batch=1", burst_s=1.0)
        assert ctrl.try_admit("batch", now=100.0).admitted
        decision = ctrl.try_admit("batch", now=100.0)
        assert not decision.admitted
        assert decision.reason == "quota"
        assert decision.retry_after_s >= 1.0
        # Unquota'd classes are untouched even while batch sheds.
        assert ctrl.try_admit("interactive", now=100.0).admitted
        assert ctrl.try_admit("ingest", now=100.0).admitted
        # Tokens regenerate: a second later batch is admitted again.
        assert ctrl.try_admit("batch", now=101.5).admitted

    def test_share_sheds_lowest_class_first(self):
        # weights 70/20/10 over max_inflight=10: caps are
        # interactive=10, batch=3, ingest=1 (cumulative-from-below).
        ctrl = _ctrl(max_inflight=10)
        assert ctrl.try_admit("ingest").admitted
        shed = ctrl.try_admit("ingest")
        assert not shed.admitted and shed.reason == "share"
        for _ in range(3):
            assert ctrl.try_admit("batch").admitted
        assert ctrl.try_admit("batch").reason == "share"
        # Interactive can still consume the whole remaining budget —
        # lower classes never displace it.
        for _ in range(6):
            assert ctrl.try_admit("interactive").admitted
        # ...until the total budget itself is gone.
        assert ctrl.try_admit("interactive").reason == "share"

    def test_share_gate_disabled_when_max_inflight_zero(self):
        ctrl = _ctrl(max_inflight=0)
        for _ in range(50):
            assert ctrl.try_admit("ingest").admitted

    def test_deadline_shed_uses_ewma_queue_estimate(self):
        ctrl = _ctrl(parallel_hint=1)
        # Teach the EWMA a 1 s service time (alpha=0.2 from 0 -> 200ms).
        assert ctrl.try_admit("interactive").admitted
        ctrl.release("interactive", duration_ms=1000.0)
        assert ctrl.snapshot()["ewma_ms"]["interactive"] == 200.0
        # Two requests already inflight => est wait 400 ms.
        assert ctrl.try_admit("interactive").admitted
        assert ctrl.try_admit("interactive").admitted
        doomed = ctrl.try_admit("interactive", deadline_ms=100.0)
        assert not doomed.admitted and doomed.reason == "deadline"
        assert ctrl.try_admit("interactive", deadline_ms=10_000.0).admitted

    def test_disabled_controller_is_passthrough(self):
        ctrl = _ctrl(enabled=False, rates="batch=1", max_inflight=1)
        for _ in range(5):
            assert ctrl.try_admit("batch").admitted
        snap = ctrl.snapshot()
        assert snap["admitted_total"] == {c: 0 for c in CLASSES}
        assert snap["shed_total"] == {c: 0 for c in CLASSES}

    def test_release_decrements_and_never_goes_negative(self):
        ctrl = _ctrl(max_inflight=4)
        assert ctrl.try_admit("batch").admitted
        ctrl.release("batch")
        ctrl.release("batch")  # extra release must not corrupt state
        assert ctrl.snapshot()["inflight"]["batch"] == 0


# -- admission: pinned transitions with hysteresis --------------------------


class TestShedTransitions:
    def test_onset_once_and_resolve_after_quiet_period(self):
        rec = _Recorder()
        ctrl = _ctrl(recorder=rec, rates="batch=1", burst_s=1.0)
        assert ctrl.try_admit("batch", now=0.0).admitted
        assert not ctrl.try_admit("batch", now=0.1).admitted  # onset
        assert not ctrl.try_admit("batch", now=0.2).admitted  # same episode
        assert len(rec.records) == 1
        onset = rec.records[0]
        assert onset["degraded"] == ["admission:batch:shedding"]
        assert onset["attrs"]["reason"] == "quota"
        assert onset["error"] is None and onset["status"] is None
        # An admit during the 10 s hysteresis window does NOT resolve —
        # token buckets admit/refuse in alternation under bursts.
        assert ctrl.try_admit("batch", now=2.0).admitted
        assert len(rec.records) == 1
        # An admit after a quiet 10 s does.
        assert ctrl.try_admit("batch", now=20.0).admitted
        assert len(rec.records) == 2
        assert rec.records[1]["degraded"] == ["admission:batch:resolved"]
        assert ctrl.snapshot()["shedding"]["batch"] is False


# -- autoscaler decision loop -----------------------------------------------


class _StubPool:
    def __init__(self, size=1):
        self.size = size
        self.desired_replicas = size
        self.calls = []

    def pool_size(self):
        return self.size

    def scale_to(self, n):
        self.calls.append(n)
        self.size = n
        self.desired_replicas = n
        return {"size": n, "added": [], "drained": []}


class _StubSlo:
    def __init__(self):
        self.fast = False

    def evaluate(self, now=None, force=False):
        return {"fast_burn_firing": self.fast}


def _scaler(pool, db=None, slo=None, rec=None, **kw):
    base = dict(
        enabled=True,
        min_replicas=1,
        max_replicas=3,
        interval_s=1.0,
        window_s=30.0,
        queue_high=4.0,
        queue_low=0.5,
        tick_high_ms=0.0,
        scale_on_fast_burn=True,
        down_checks=2,
        up_cooldown_s=10.0,
        down_cooldown_s=60.0,
    )
    base.update(kw)
    return Autoscaler(
        pool,
        AutoscaleConfig(**base),
        tsdb=db if db is not None else Tsdb(),
        slo=slo or _StubSlo(),
        recorder=rec or _Recorder(),
    )


def _feed_queue(db, depth, *, until, start=0.0):
    for t in range(int(start), int(until)):
        db.record("engine.queued", float(depth), ts=float(t))


class TestAutoscalerDecisions:
    def test_scales_up_on_queue_high_and_pins_transition(self):
        db, rec, pool = Tsdb(), _Recorder(), _StubPool(1)
        scaler = _scaler(pool, db=db, rec=rec)
        # now starts past up_cooldown_s: _last_up is 0.0 at boot.
        _feed_queue(db, 10, until=100, start=94)
        event = scaler.tick(now=100.0)
        assert pool.calls == [2]
        assert event["direction"] == "up" and event["to"] == 2
        assert "queue_high" in event["signals"]["reasons"]
        assert scaler.scale_ups_total == 1
        pinned = rec.records[-1]
        assert pinned["degraded"] == ["autoscale:up:1->2"]
        assert pinned["attrs"]["from"] == 1 and pinned["attrs"]["to"] == 2
        assert "queue_high" in pinned["attrs"]["reason"]
        # The scale event also lands in the TSDB for /debug/timeseries.
        count, total = db.window_stats("autoscale.scale_events", 60.0, 100.0)
        assert count == 1 and total == 1.0

    def test_up_cooldown_blocks_consecutive_ups(self):
        db, pool = Tsdb(), _StubPool(1)
        scaler = _scaler(pool, db=db)
        _feed_queue(db, 10, until=130, start=80)
        assert scaler.tick(now=100.0) is not None
        assert scaler.tick(now=108.0) is None  # inside up_cooldown_s=10
        assert pool.calls == [2]
        assert scaler.tick(now=120.0) is not None
        assert pool.calls == [2, 3]

    def test_max_replicas_clamps(self):
        db, pool = Tsdb(), _StubPool(3)
        scaler = _scaler(pool, db=db, max_replicas=3)
        _feed_queue(db, 50, until=100, start=94)
        assert scaler.tick(now=100.0) is None  # already at ceiling
        assert pool.calls == []

    def test_fast_burn_triggers_up_without_queue_signal(self):
        slo, pool = _StubSlo(), _StubPool(1)
        slo.fast = True
        scaler = _scaler(pool, slo=slo)
        event = scaler.tick(now=100.0)
        assert pool.calls == [2]
        assert "fast_burn" in event["signals"]["reasons"]
        # scale_on_fast_burn=False ignores the page.
        pool2 = _StubPool(1)
        scaler2 = _scaler(pool2, slo=slo, scale_on_fast_burn=False)
        assert scaler2.tick(now=100.0) is None
        assert pool2.calls == []

    def test_dead_band_holds(self):
        db, pool = Tsdb(), _StubPool(2)
        scaler = _scaler(pool, db=db)
        # 2.0 per replica: inside the dead band between low and high.
        _feed_queue(db, 4, until=100, start=94)
        assert scaler.tick(now=100.0) is None
        assert pool.calls == []
        assert scaler.last_decision["target"] == 2

    def test_down_needs_streak_then_cooldown(self):
        pool = _StubPool(2)
        scaler = _scaler(pool, down_checks=2, down_cooldown_s=60.0)
        # Empty TSDB window -> queue 0 <= queue_low: a down verdict.
        assert scaler.tick(now=100.0) is None  # streak 1 of 2
        assert scaler.tick(now=101.0) is not None  # streak met, cooldown ok
        assert pool.calls == [1]
        assert scaler.scale_downs_total == 1

    def test_scale_up_restarts_the_down_clock(self):
        db, pool = Tsdb(), _StubPool(1)
        scaler = _scaler(pool, db=db, down_checks=1, down_cooldown_s=60.0)
        _feed_queue(db, 10, until=100, start=94)
        assert scaler.tick(now=100.0) is not None  # up: 1 -> 2
        # Queue collapses immediately; the fresh replica must not be
        # given straight back.
        assert scaler.tick(now=140.0) is None  # 140 - 100 < down_cooldown
        assert scaler.tick(now=170.0) is not None  # cooldown elapsed
        assert pool.calls == [2, 1]

    def test_min_replicas_floor(self):
        pool = _StubPool(1)
        scaler = _scaler(pool, down_checks=1)
        assert scaler.tick(now=100.0) is None  # size == min: hold
        assert pool.calls == []

    def test_fast_burn_vetoes_scale_down(self):
        slo = _StubSlo()
        slo.fast = True
        pool = _StubPool(2)
        # Queue empty (down signal) but the page is firing: the target
        # resolves UP, not down.
        scaler = _scaler(pool, slo=slo, down_checks=1)
        event = scaler.tick(now=100.0)
        assert event is not None and event["direction"] == "up"
        assert pool.calls == [3]


class TestClosedLoop:
    def test_load_step_pages_scales_out_recovers_and_sheds_low_classes(self):
        """The three controllers closed over each other, for real, on a
        simulated clock: a 4x load step on a one-replica pool (10
        requests/s a replica) fires the fast-burn page within a minute,
        the autoscaler grows the pool and shrinks it after, the page
        clears, the last five minutes' p95 is inside the latency SLO,
        and every shed request was batch or ingest."""
        from generativeaiexamples_tpu.core.configuration import SLOConfig
        from generativeaiexamples_tpu.obs.slo import SloEngine

        db, rec = Tsdb(), _Recorder()
        slo = SloEngine(
            SLOConfig(latency_p95_ms="/generate=2500", evaluation_period_s=0.0),
            tsdb=db,
            recorder=rec,
        )
        admission = AdmissionController(
            AdmissionConfig(
                weights="interactive=70,batch=20,ingest=10",
                rates="batch=3,ingest=2",  # ~1.5x their baseline share
                burst_s=2.0,
                max_inflight=0,
            ),
            recorder=rec,
            tsdb=db,
        )
        pool = _StubPool(1)
        scaler = _scaler(
            pool, db=db, slo=slo, rec=rec, max_replicas=4, down_checks=3
        )
        mix = (("interactive", 0.60), ("batch", 0.25), ("ingest", 0.15))
        t0, t_step, t_back, t_end = 1e6, 1e6 + 600, 1e6 + 900, 1e6 + 1500
        queue, carry = [], dict.fromkeys(CLASSES, 0.0)
        arrived, served = dict(carry), dict(carry)
        fired_at, late, directions = None, [], []
        t = t0
        while t < t_end:
            rps = 8 * (4 if t_step <= t < t_back else 1)
            for cls, share in mix:
                carry[cls] += rps * share
                n, carry[cls] = int(carry[cls]), carry[cls] % 1
                for _ in range(n):
                    arrived[cls] += 1
                    if admission.try_admit(cls, now=t, route="/generate").admitted:
                        queue.append((cls, t))
                    else:  # a deliberate 429: fast, and no error
                        slo.note_request("/generate", 1.0, ts=t)
            for cls, t_in in queue[: pool.size * 10]:
                ms = (t - t_in) * 1000.0 + 100.0
                slo.note_request("/generate", ms, ts=t)
                admission.release(cls, duration_ms=ms)
                served[cls] += 1
                if t >= t_end - 300:
                    late.append(ms)
            del queue[: pool.size * 10]
            db.record("engine.queued", float(len(queue)), ts=t)
            if fired_at is None and t >= t_step:
                if slo.evaluate(now=t, force=True)["fast_burn_firing"]:
                    fired_at = t
            event = scaler.tick(now=t)
            if event is not None:
                directions.append(event["direction"])
            t += 1.0

        assert fired_at is not None and fired_at - t_step <= 60
        assert "up" in directions and "down" in directions
        assert max(pool.calls) >= 2
        assert any((e.get("attrs") or {}).get("autoscale") for e in rec.records)
        assert not slo.evaluate(now=t_end, force=True)["fast_burn_firing"]
        assert 0 < sorted(late)[int(len(late) * 0.95)] <= 2500.0
        assert served["interactive"] / arrived["interactive"] >= 0.99
        shed = admission.snapshot()["shed_total"]
        assert shed.get("interactive", 0) == 0
        assert shed.get("batch", 0) + shed.get("ingest", 0) > 0


class TestPoolMetricsLines:
    def test_three_shapes(self):
        doc = "\n".join(pool_metrics_lines(None))
        assert "engine_pool_size 0" in doc
        assert "engine_pool_desired_replicas 0" in doc

        class _Bare:  # a Scheduler: no pool_size attr -> a pool of one
            pass

        doc = "\n".join(pool_metrics_lines(_Bare()))
        assert "engine_pool_size 1" in doc
        assert "engine_pool_desired_replicas 1" in doc
        pool = _StubPool(2)
        pool.desired_replicas = 3
        doc = "\n".join(pool_metrics_lines(pool))
        assert "engine_pool_size 2" in doc
        assert "engine_pool_desired_replicas 3" in doc

    def test_autoscaler_target_overrides_desired(self):
        pool = _StubPool(2)
        scaler = _scaler(pool)
        scaler.last_decision = {"target": 3}
        doc = "\n".join(pool_metrics_lines(pool, autoscaler=scaler))
        assert "engine_pool_desired_replicas 3" in doc


# -- live pool: scale actuation under traffic (CPU, tiny model) --------------

import queue  # noqa: E402

from generativeaiexamples_tpu.engine.replica import (  # noqa: E402
    DETACHED,
    DRAINING,
    EnginePool,
)
from generativeaiexamples_tpu.engine.sampler import SamplingParams  # noqa: E402
from generativeaiexamples_tpu.engine.scheduler import (  # noqa: E402
    Request,
    Scheduler,
)
from generativeaiexamples_tpu.engine.tokenizer import ByteTokenizer  # noqa: E402
from generativeaiexamples_tpu.models import llama  # noqa: E402

CFG = llama.llama_tiny(dtype="float32", max_seq_len=128)


def _sched(**kw):
    base = dict(max_batch=2, max_len=128, decode_chunk_size=4)
    base.update(kw)
    return Scheduler(CFG, **base)


def _elastic_pool(n=1, sched_kw=None, **kw):
    kw.setdefault("health_interval", None)
    sk = sched_kw or {}
    return EnginePool(
        [_sched(**sk) for _ in range(n)],
        scheduler_factory=lambda: _sched(**sk),
        **kw,
    )


def _request(prompt, rid, *, max_tokens=3, on_token=None):
    done: "queue.Queue[str]" = queue.Queue()
    tokens: list[int] = []
    req = Request(
        token_ids=list(prompt),
        sampling=SamplingParams(temperature=0.0, max_tokens=max_tokens),
        on_token=on_token or tokens.append,
        on_done=done.put,
        id=rid,
    )
    return req, tokens, done


class TestPoolScaleLive:
    def test_scale_up_mid_traffic(self):
        """Growing the pool while a generation streams must not disturb
        it, and new replicas take traffic immediately."""
        pool = _elastic_pool(1)
        pool.start()
        try:
            started = threading.Event()
            runner, _, runner_done = _request(
                [9, 8, 7], "runner", max_tokens=25,
                on_token=lambda t: started.set(),
            )
            assert pool.submit(runner)
            assert started.wait(timeout=60)
            result = pool.scale_to(3)
            assert result["size"] == 3 and len(result["added"]) == 2
            assert pool.pool_size() == 3
            assert pool.desired_replicas == 3
            dones = []
            for i in range(4):
                req, _, done = _request([i + 20, 1], f"post-{i}")
                assert pool.submit(req)
                dones.append(done)
            for done in dones:
                assert done.get(timeout=120) == "length"
            assert runner_done.get(timeout=120) == "length"
            # New replicas actually served: placements spread past idx 0.
            assert pool.stats.snapshot()["pool_size"] == 3
        finally:
            pool.stop()

    def test_scale_down_drains_victim_with_inflight_generation(self):
        """The drain-during-scale-down race: scale_to picks the
        least-loaded replica while it still streams a generation — the
        generation must finish normally and the replica detach only
        afterwards, with its router mirror and TSDB series dropped."""
        from generativeaiexamples_tpu.obs.tsdb import get_tsdb, reset_tsdb

        reset_tsdb()
        pool = _elastic_pool(2, sched_kw=dict(max_batch=1))
        pool.start()
        try:
            # Fill both single-slot replicas with streaming runners.
            events = [threading.Event() for _ in range(2)]
            runner_dones = []
            for i in range(2):
                req, _, done = _request(
                    [i + 1, 5], f"run-{i}", max_tokens=40,
                    on_token=lambda t, e=events[i]: e.set(),
                )
                runner_dones.append(done)
                assert pool.submit(req)
            assert all(e.wait(timeout=60) for e in events)
            # Queue one more; with both single-slot replicas occupied it
            # waits in an admission queue.
            queued, _, queued_done = _request([40, 41, 42], "queued")
            assert pool.submit(queued)
            pool._feed_tsdb()
            names = get_tsdb().names()
            for idx in range(2):
                assert any(
                    n.startswith(f"engine.replica.{idx}.") for n in names
                )
            # Whichever replica scale_to retires, it is mid-generation.
            result = pool.scale_to(1)
            assert len(result["drained"]) == 1
            victim = result["drained"][0]
            assert pool.replicas[victim].state == DRAINING
            assert pool.desired_replicas == 1
            # The victim's in-flight generation completes untouched...
            for done in runner_dones:
                assert done.get(timeout=120) == "length"
            assert queued_done.get(timeout=120) == "length"
            # ...and only then does the health pass detach it.
            pool.check_replicas()
            assert pool.replicas[victim].state == DETACHED
            assert pool.pool_size() == 1
            assert pool.healthy()  # scale-down is not degradation
            # Per-replica series die with the replica.
            assert not any(
                n.startswith(f"engine.replica.{victim}.")
                for n in get_tsdb().names()
            )
        finally:
            pool.stop()
            reset_tsdb()

    def test_scale_down_then_up_reuses_factory(self):
        """A full shrink-then-grow cycle: indices never collide and the
        pool ends healthy at the new size."""
        pool = _elastic_pool(2)
        pool.start()
        try:
            pool.scale_to(1)
            pool.check_replicas()
            assert pool.pool_size() == 1
            result = pool.scale_to(2)
            assert len(result["added"]) == 1
            added = result["added"][0]
            assert added not in {
                r.idx for r in pool.replicas if r.state == DETACHED
            }
            req, _, done = _request([3, 4, 5], "after")
            assert pool.submit(req)
            assert done.get(timeout=120) == "length"
        finally:
            pool.stop()


# -- engine HTTP: Retry-After + /admin/scale --------------------------------


@pytest.fixture
def overloaded_client():
    """Engine app over a pool whose queues reject everything."""
    from generativeaiexamples_tpu.engine.server import create_engine_app

    pool = _elastic_pool(2, sched_kw=dict(max_queue=0))
    app = create_engine_app(pool, ByteTokenizer(), model_name="llama-tiny")
    loop = asyncio.new_event_loop()
    client = TestClient(TestServer(app), loop=loop)
    loop.run_until_complete(client.start_server())
    yield client, loop, pool
    loop.run_until_complete(client.close())
    loop.close()
    pool.stop()


class TestEngineShedHints:
    def test_429_carries_retry_after(self, overloaded_client):
        client, loop, _pool_ = overloaded_client

        async def go(path, payload):
            resp = await client.post(path, json=payload)
            return resp.status, resp.headers, await resp.json()

        status, headers, body = loop.run_until_complete(
            go(
                "/v1/completions",
                {"model": "llama-tiny", "prompt": "x", "max_tokens": 2},
            )
        )
        assert status == 429
        assert body["error"]["type"] == "overloaded_error"
        assert int(headers["Retry-After"]) >= 1
        status, headers, _body = loop.run_until_complete(
            go(
                "/v1/chat/completions",
                {
                    "model": "llama-tiny",
                    "messages": [{"role": "user", "content": "hi"}],
                    "max_tokens": 2,
                },
            )
        )
        assert status == 429
        assert 1 <= int(headers["Retry-After"]) <= 30

    def test_admin_scale_endpoint(self, overloaded_client):
        client, loop, pool = overloaded_client

        async def go(params):
            resp = await client.post("/admin/scale", params=params)
            return resp.status, await resp.json()

        status, body = loop.run_until_complete(go({"replicas": "3"}))
        assert status == 200
        assert body["size"] == 3 and len(body["added"]) == 1
        assert pool.pool_size() == 3
        status, _body = loop.run_until_complete(go({"replicas": "zero"}))
        assert status == 422
        status, _body = loop.run_until_complete(go({}))
        assert status == 422

    def test_admin_scale_on_bare_scheduler_501(self):
        from generativeaiexamples_tpu.engine.server import create_engine_app

        sched = _sched()
        app = create_engine_app(sched, ByteTokenizer(), model_name="t")
        loop = asyncio.new_event_loop()
        client = TestClient(TestServer(app), loop=loop)
        loop.run_until_complete(client.start_server())
        try:

            async def go():
                resp = await client.post(
                    "/admin/scale", params={"replicas": "2"}
                )
                return resp.status

            assert loop.run_until_complete(go()) == 501
        finally:
            loop.run_until_complete(client.close())
            loop.close()
            sched.stop()


# -- chain server: admission end-to-end -------------------------------------


@pytest.fixture
def chain_client(monkeypatch, tmp_path):
    """Chain app with a 1-token batch quota: the second batch request in
    a burst sheds while interactive traffic is untouched."""
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
    # Token bucket: rate ~0 with burst floor of one token.
    monkeypatch.setenv("APP_ADMISSION_RATES", "batch=0.001")
    monkeypatch.setenv("APP_ADMISSION_BURSTS", "1.0")
    reset_config_cache()
    reset_factories()
    from generativeaiexamples_tpu.server.app import create_app

    loop = asyncio.new_event_loop()
    client = TestClient(TestServer(create_app()), loop=loop)
    loop.run_until_complete(client.start_server())
    yield client, loop
    loop.run_until_complete(client.close())
    loop.close()
    reset_config_cache()
    reset_factories()


class TestChainAdmissionE2E:
    def test_batch_quota_sheds_interactive_flows(self, chain_client):
        client, loop = chain_client

        async def go():
            hdr = {"X-Traffic-Class": "batch"}
            first = await client.post(
                "/search", json={"query": "alpha", "top_k": 1}, headers=hdr
            )
            shed = await client.post(
                "/search", json={"query": "alpha", "top_k": 1}, headers=hdr
            )
            shed_body = await shed.json()
            interactive = await client.post(
                "/search", json={"query": "alpha", "top_k": 1}
            )
            metrics = await (await client.get("/metrics")).text()
            health = await client.get("/health")
            return first, shed, shed_body, interactive, metrics, health

        first, shed, shed_body, interactive, metrics, health = (
            loop.run_until_complete(go())
        )
        assert first.status == 200
        assert shed.status == 429
        assert shed.headers["X-Admission-Class"] == "batch"
        assert int(shed.headers["Retry-After"]) >= 1
        assert shed_body["class"] == "batch"
        assert shed_body["reason"] == "quota"
        # Interactive is untouched by the batch quota.
        assert interactive.status == 200
        # Non-API routes bypass admission entirely.
        assert health.status == 200
        assert 'rag_admission_shed_total{class="batch"} 1' in metrics
        assert 'rag_admission_admitted_total{class="batch"} 1' in metrics
        assert 'rag_admission_shed_total{class="interactive"} 0' in metrics

    def test_shed_does_not_burn_error_budget(self, chain_client):
        """Admission 429s are deliberate, not failures: the SLO engine
        must not count them as errors."""
        client, loop = chain_client
        from generativeaiexamples_tpu.obs.tsdb import get_tsdb

        async def go():
            hdr = {"X-Traffic-Class": "batch"}
            for _ in range(3):
                await client.post(
                    "/search", json={"query": "a", "top_k": 1}, headers=hdr
                )

        loop.run_until_complete(go())
        now = time.time()
        db = get_tsdb()
        bad_count, _ = db.window_stats("slo.bad.availability./search", 120.0, now)
        total_count, _ = db.window_stats("slo.total./search", 120.0, now)
        assert bad_count == 0
        assert total_count >= 3
