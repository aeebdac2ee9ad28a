"""Replica pool + prefix-affinity router tests (CPU, tiny config).

Covers the serving-topology layer (`engine.replica` + `engine.router`):
policy placement, prefix-affinity hit-rate vs round-robin, failover of a
killed replica's queued requests, graceful drain, the cancel-beats-
requeue race, pool-level 429 backpressure end-to-end over HTTP, the
real /health signal, and per-replica /metrics.
"""

import asyncio
import queue
import threading
import time

import pytest
from aiohttp.test_utils import TestClient, TestServer

from generativeaiexamples_tpu.engine.replica import (
    DETACHED,
    DRAINING,
    EnginePool,
    Replica,
    UNHEALTHY,
)
from generativeaiexamples_tpu.engine.router import ReplicaView, Router
from generativeaiexamples_tpu.engine.sampler import SamplingParams
from generativeaiexamples_tpu.engine.scheduler import Request, Scheduler
from generativeaiexamples_tpu.engine.tokenizer import ByteTokenizer
from generativeaiexamples_tpu.models import llama

CFG = llama.llama_tiny(dtype="float32", max_seq_len=128)


def _sched(**kw):
    base = dict(max_batch=2, max_len=128, decode_chunk_size=4)
    base.update(kw)
    return Scheduler(CFG, **base)


def _pool(n=2, policy="least_loaded", sched_kw=None, **kw):
    kw.setdefault("health_interval", None)  # tests drive check_replicas()
    return EnginePool(
        [_sched(**(sched_kw or {})) for _ in range(n)], policy=policy, **kw
    )


def _request(prompt, rid, *, max_tokens=3, session_id="", on_token=None):
    done: "queue.Queue[str]" = queue.Queue()
    tokens: list[int] = []
    req = Request(
        token_ids=list(prompt),
        sampling=SamplingParams(temperature=0.0, max_tokens=max_tokens),
        on_token=on_token or tokens.append,
        on_done=done.put,
        id=rid,
        session_id=session_id,
    )
    return req, tokens, done


def _kill(replica):
    """Synthetic replica death: stop the tick loop and wait it out."""
    replica.scheduler.request_stop()
    replica.scheduler._thread.join(timeout=30)
    assert not replica.thread_alive()


class TestRouterPolicies:
    VIEWS = [ReplicaView(0, 0), ReplicaView(1, 0), ReplicaView(2, 0)]

    def test_round_robin_cycles_all(self):
        r = Router("round_robin")
        picks = {r.select([1], "", self.VIEWS) for _ in range(6)}
        assert picks == {0, 1, 2}

    def test_least_loaded_picks_minimum(self):
        r = Router("least_loaded")
        views = [ReplicaView(0, 5), ReplicaView(1, 1), ReplicaView(2, 3)]
        assert r.select([1], "", views) == 1

    def test_least_loaded_spreads_ties(self):
        r = Router("least_loaded")
        assert {r.select([1], "", self.VIEWS) for _ in range(6)} == {0, 1, 2}

    def test_session_sticky_and_remap_on_drop(self):
        r = Router("session")
        first = r.select([1], "conv", self.VIEWS)
        # Sticky even when another replica becomes less loaded.
        views = [ReplicaView(i, 9 if i == first else 0) for i in range(3)]
        assert r.select([2], "conv", views) == first
        r.drop_replica(first)
        survivors = [v for v in self.VIEWS if v.idx != first]
        assert r.select([3], "conv", survivors) != first

    def test_prefix_routes_to_mirrored_replica(self):
        r = Router("prefix")
        history = list(range(2, 50))  # 48 tokens > min_prefix
        r.note_finished(1, history)
        assert r.select(history[:40] + [7, 8], "", self.VIEWS) == 1
        # Below min_prefix or unknown prompt: least-loaded fallback, not
        # a crash and not a forced miss onto replica 1.
        assert r.select(list(range(200, 240)), "", self.VIEWS) in {0, 1, 2}
        short = history[:8] + [9] * 30
        assert r.select(short, "", self.VIEWS) in {0, 1, 2}

    def test_prefix_longest_match_wins(self):
        r = Router("prefix")
        base = list(range(2, 50))
        r.note_finished(0, base)
        r.note_finished(2, base + [60, 61, 62, 63])
        assert r.select(base + [60, 61, 62, 63, 99], "", self.VIEWS) == 2

    def test_unknown_policy_rejected(self):
        with pytest.raises(ValueError):
            Router("fastest")

    def test_mirror_capped(self):
        r = Router("prefix", mirror_max_segments=2)
        for i in range(5):
            r.note_finished(0, [100 + i] * 40)
        assert len(r._mirrors[0]) == 2


class TestReplicaHealthSignals:
    def test_ticking_detects_frozen_counter(self):
        r = Replica(0, _sched())
        now = time.monotonic()
        assert r.ticking(now, 0.1)  # first observation = progress
        r.scheduler.stats.tick_count = 7
        assert r.ticking(now + 1.0, 0.1)  # counter moved
        assert not r.ticking(now + 2.0, 0.1)  # frozen past the timeout

    @pytest.mark.filterwarnings(
        "ignore::pytest.PytestUnhandledThreadExceptionWarning"
    )
    def test_scheduler_healthy_reflects_dead_thread(self):
        s = _sched()
        assert s.healthy()  # never started: not dead
        s.start()
        try:
            assert s.healthy()
            # Kill the tick thread while _running stays True (SystemExit
            # escapes the loop's `except Exception` recovery).
            def boom():
                raise SystemExit

            s._tick = boom
            s._thread.join(timeout=30)
            assert not s._thread.is_alive()
            assert not s.healthy()
        finally:
            s.stop()
        assert s.healthy()  # cleanly stopped is not 'dead'


class TestPrefixAffinity:
    def _run(self, policy, families, reqs):
        """Closed-loop family workload; returns pool-wide shared hits."""
        pool = _pool(
            2,
            policy=policy,
            sched_kw=dict(max_batch=1, prefix_cache="shared"),
        )
        pool.start()
        try:
            for i, fam in enumerate(reqs):
                prompt = families[fam] + [300 + i, 301 + i, 302 + i]
                req, _, done = _request(prompt, f"{policy}-{i}")
                assert pool.submit(req)
                assert done.get(timeout=120) == "length"
            snap = pool.stats.snapshot()
        finally:
            pool.stop()
        return snap["shared_prefix_hits"], snap

    def test_prefix_policy_beats_round_robin(self):
        """Acceptance: with 2 replicas, `prefix` routes repeated-prefix
        requests to the replica whose radix index holds the segment —
        the shared-prefix hit-rate must beat round-robin placement on
        the same workload."""
        families = [
            list(range(2, 50)),  # 48 tokens > MIN_PREFIX=32
            list(range(200, 248)),
        ]
        # Family order phase-shifted against a 2-replica rotation: with
        # round_robin each replica alternates families and its single
        # parked slot never matches; prefix affinity pins each family.
        reqs = [0, 1, 1, 0, 0, 1, 1, 0, 0, 1, 1, 0]
        prefix_hits, prefix_snap = self._run("prefix", families, reqs)
        rr_hits, _ = self._run("round_robin", families, reqs)
        assert prefix_hits > rr_hits
        # After each family's seed request, every placement should hit.
        assert prefix_hits >= len(reqs) - len(families) - 1
        # And the hits split across BOTH replicas (affinity, not
        # single-replica pile-up).
        per_replica = [
            r["shared_prefix_hits"] for r in prefix_snap["replicas"]
        ]
        assert all(h > 0 for h in per_replica)


class TestFailover:
    def test_dead_replica_requeues_queued_requests(self):
        """Acceptance: killing one replica's tick thread requeues its
        queued (zero-token) requests to the survivor and completes them
        — no hang, no dropped request, exactly one on_done each."""
        pool = _pool(2, policy="round_robin")
        pool.start()
        try:
            _kill(pool.replicas[0])
            dones: "queue.Queue[str]" = queue.Queue()
            n = 6
            for i in range(n):
                req, _, _ = _request([i + 1, 2, 3], f"fo-{i}")
                req.on_done = dones.put
                assert pool.submit(req)
            # Round-robin placed half on the dead replica; they sit in
            # its queue until the health pass fails it over.
            time.sleep(0.2)
            pool.check_replicas()
            reasons = [dones.get(timeout=120) for _ in range(n)]
            assert reasons == ["length"] * n
            assert dones.empty()  # exactly one completion per request
            snap = pool.stats.snapshot()
            assert snap["replicas"][0]["state"] == UNHEALTHY
            assert snap["router_failovers_total"] == 1
            assert snap["router_requeued_total"] >= 1
            assert not pool.healthy()
            assert not pool._placements
        finally:
            pool.stop()

    def test_inflight_on_dead_replica_surfaces_error(self):
        """A generation that already streamed tokens cannot be silently
        replayed — the pool must end it with on_done('error')."""
        pool = _pool(2)
        pool.start()
        try:
            started = threading.Event()
            req, _, done = _request(
                [5, 6, 7], "inflight", max_tokens=200,
                on_token=lambda t: started.set(),
            )
            assert pool.submit(req)
            assert started.wait(timeout=60)
            victim = pool.replicas[pool._placements["inflight"].replica]
            _kill(victim)
            pool.check_replicas()
            assert done.get(timeout=60) == "error"
        finally:
            pool.stop()

    def test_no_survivor_fails_requests_not_hangs(self):
        pool = _pool(1, policy="round_robin")
        pool.start()
        try:
            _kill(pool.replicas[0])
            req, _, done = _request([1, 2], "lone")
            assert pool.submit(req)
            pool.check_replicas()
            assert done.get(timeout=60) == "error"
        finally:
            pool.stop()


class TestDrain:
    def test_drain_finishes_inflight_refuses_new_then_detaches(self):
        """Acceptance: drain lets in-flight generations finish, places
        nothing new on the draining replica, and detaches it once idle."""
        pool = _pool(2, policy="least_loaded")
        pool.start()
        try:
            started = threading.Event()
            runner, _, runner_done = _request(
                [9, 8, 7], "runner", max_tokens=25,
                on_token=lambda t: started.set(),
            )
            assert pool.submit(runner)
            assert started.wait(timeout=60)
            victim = pool._placements["runner"].replica
            assert pool.drain(victim) == DRAINING
            # New placements all avoid the draining replica.
            for i in range(4):
                req, _, done = _request([i + 20, 1], f"post-{i}")
                assert pool.submit(req)
                assert pool._placements[f"post-{i}"].replica != victim
                assert done.get(timeout=120) == "length"
            # The in-flight generation finishes normally...
            assert runner_done.get(timeout=120) == "length"
            # ...and the next health pass detaches the empty replica.
            pool.check_replicas()
            assert pool.replicas[victim].state == DETACHED
            assert pool.replicas[victim].scheduler._thread is None
            assert pool.healthy()  # drained != degraded
        finally:
            pool.stop()

    def test_drain_migrates_queued_requests_to_survivor(self):
        """A request queued behind a full draining replica must move to
        the survivor instead of waiting for the drain to finish."""
        pool = _pool(2, policy="round_robin", sched_kw=dict(max_batch=1))
        pool.start()
        try:
            # Fill both single-slot replicas with long runners.
            events = [threading.Event() for _ in range(2)]
            runner_dones = []
            for i in range(2):
                req, _, done = _request(
                    [i + 1, 5], f"run-{i}", max_tokens=60,
                    on_token=lambda t, e=events[i]: e.set(),
                )
                runner_dones.append(done)
                assert pool.submit(req)
            assert all(e.wait(timeout=60) for e in events)
            # Queue a request behind one replica, then drain it.
            queued, _, queued_done = _request([40, 41, 42], "queued")
            assert pool.submit(queued)
            victim = pool._placements["queued"].replica
            pool.drain(victim)
            assert pool._placements["queued"].replica != victim
            # Everyone completes: runners in place, the queued request
            # on the survivor once its runner's slot frees.
            for done in runner_dones:
                assert done.get(timeout=120) == "length"
            assert queued_done.get(timeout=120) == "length"
        finally:
            pool.stop()


class TestCancelRequeueRace:
    def test_cancel_wins_over_failover_requeue(self):
        """Regression (satellite): a request queued at a replica that
        dies must finish as 'cancelled' — never resurrect on the
        survivor — when the client cancelled before the health pass."""
        pool = _pool(2, policy="round_robin")
        pool.start()
        try:
            _kill(pool.replicas[0])
            # Place requests until one lands on the dead replica (its
            # queue still accepts; the thread just never pops).
            target = None
            others = []
            for i in range(2):
                req, _, done = _request([i + 1, 2, 3], f"c-{i}")
                assert pool.submit(req)
                if pool._placements[f"c-{i}"].replica == 0:
                    target = (req, done)
                else:
                    others.append(done)
            assert target is not None
            req, done = target
            for other in others:
                other.get(timeout=120)  # let the live one(s) finish first
            survivor_before = pool.replicas[1].scheduler.stats.snapshot()[
                "requests_total"
            ]
            pool.cancel(req.id)
            pool.check_replicas()
            assert done.get(timeout=60) == "cancelled"
            assert done.empty()
            # Nothing was requeued for the cancelled request.
            snap1 = pool.replicas[1].scheduler.stats.snapshot()
            assert snap1["requests_total"] == survivor_before
            assert req.id not in pool._placements
        finally:
            pool.stop()

    def test_cancel_wins_over_drain_migration(self):
        """Same race on the drain path: the pool must not migrate a
        cancelled-but-still-queued request off a draining replica."""
        pool = _pool(2, policy="round_robin", sched_kw=dict(max_batch=1))
        pool.start()
        try:
            events = [threading.Event() for _ in range(2)]
            runner_dones = []
            for i in range(2):
                req, _, done = _request(
                    [i + 1, 9], f"dr-{i}", max_tokens=30,
                    on_token=lambda t, e=events[i]: e.set(),
                )
                runner_dones.append(done)
                assert pool.submit(req)
            assert all(e.wait(timeout=60) for e in events)
            queued, _, queued_done = _request([50, 51, 52], "dq")
            assert pool.submit(queued)
            victim = pool._placements["dq"].replica
            pool.cancel("dq")
            pool.drain(victim)
            # Not migrated: still recorded against the draining replica
            # (or already gone), and it finishes as cancelled there.
            placement = pool._placements.get("dq")
            assert placement is None or placement.replica == victim
            assert queued_done.get(timeout=120) == "cancelled"
            for done in runner_dones:
                assert done.get(timeout=120) == "length"
        finally:
            pool.stop()


@pytest.fixture
def pool_client():
    """HTTP app over a 2-replica pool whose queues reject everything
    (max_queue=0): the global-backpressure topology."""
    from generativeaiexamples_tpu.engine.server import create_engine_app

    pool = _pool(2, sched_kw=dict(max_queue=0))
    app = create_engine_app(pool, ByteTokenizer(), model_name="llama-tiny")
    loop = asyncio.new_event_loop()
    client = TestClient(TestServer(app), loop=loop)
    loop.run_until_complete(client.start_server())
    yield client, loop, pool
    loop.run_until_complete(client.close())
    loop.close()
    pool.stop()


class TestPoolBackpressureHTTP:
    """Satellite: pool-level 429 end-to-end through the HTTP front."""

    def test_chat_completions_aggregate_429(self, pool_client):
        client, loop, pool = pool_client

        async def go(stream):
            resp = await client.post(
                "/v1/chat/completions",
                json={
                    "model": "llama-tiny",
                    "messages": [{"role": "user", "content": "hi"}],
                    "max_tokens": 2,
                    "stream": stream,
                },
            )
            return resp.status, await resp.json()

        status, body = loop.run_until_complete(go(False))
        assert status == 429
        assert body["error"]["type"] == "overloaded_error"
        # Streaming requests shed BEFORE the SSE stream opens.
        status, body = loop.run_until_complete(go(True))
        assert status == 429
        assert body["error"]["type"] == "overloaded_error"
        assert pool.stats.snapshot()["rejected_total"] == 2

    def test_completions_429_and_metric(self, pool_client):
        client, loop, pool = pool_client

        async def go():
            resp = await client.post(
                "/v1/completions",
                json={"model": "llama-tiny", "prompt": "x", "max_tokens": 2},
            )
            status = resp.status
            metrics = await (await client.get("/metrics")).text()
            return status, metrics

        status, metrics = loop.run_until_complete(go())
        assert status == 429
        assert "engine_rejected_total 1" in metrics


@pytest.fixture
def live_pool_client():
    """HTTP app over a live (started) 2-replica pool."""
    from generativeaiexamples_tpu.engine.server import create_engine_app

    pool = _pool(2, policy="least_loaded")
    pool.start()
    app = create_engine_app(pool, ByteTokenizer(), model_name="llama-tiny")
    loop = asyncio.new_event_loop()
    client = TestClient(TestServer(app), loop=loop)
    loop.run_until_complete(client.start_server())
    yield client, loop, pool
    loop.run_until_complete(client.close())
    loop.close()
    pool.stop()


class TestPoolHTTP:
    def test_completion_routes_through_pool(self, live_pool_client):
        client, loop, pool = live_pool_client

        async def go():
            resp = await client.post(
                "/v1/chat/completions",
                json={
                    "model": "llama-tiny",
                    "messages": [{"role": "user", "content": "hello"}],
                    "max_tokens": 4,
                    "temperature": 0,
                },
            )
            assert resp.status == 200
            return await resp.json()

        body = loop.run_until_complete(go())
        assert body["usage"]["completion_tokens"] == 4
        assert pool.stats.snapshot()["requests_total"] == 1

    def test_metrics_per_replica_series(self, live_pool_client):
        client, loop, _pool_ = live_pool_client

        async def go():
            return await (await client.get("/metrics")).text()

        metrics = loop.run_until_complete(go())
        assert "engine_rejected_total 0" in metrics
        assert 'engine_replica_healthy{replica="0"} 1' in metrics
        assert 'engine_replica_healthy{replica="1"} 1' in metrics
        assert 'engine_replica_queued{replica="0"}' in metrics
        assert 'engine_replica_shared_prefix_hits_total{replica="1"}' in metrics
        assert "engine_router_failovers_total 0" in metrics

    def test_health_degrades_on_dead_replica(self, live_pool_client):
        """Satellite: /health reports degraded + 503 when a replica is
        unhealthy, instead of the old unconditional 200."""
        client, loop, pool = live_pool_client

        async def health():
            resp = await client.get("/health")
            return resp.status, await resp.json()

        status, body = loop.run_until_complete(health())
        assert status == 200 and body["status"] == "ok"
        assert body["message"] == "Service is up."
        _kill(pool.replicas[0])
        pool.check_replicas()
        status, body = loop.run_until_complete(health())
        assert status == 503
        assert body["status"] == "degraded"
        states = {r["replica"]: r["state"] for r in body["replicas"]}
        assert states[0] == UNHEALTHY

    def test_admin_drain_endpoint(self, live_pool_client):
        client, loop, pool = live_pool_client

        async def go():
            bad = await client.post("/admin/drain")
            missing = await client.post("/admin/drain?replica=9")
            ok = await client.post("/admin/drain?replica=0")
            listing = await (await client.get("/admin/replicas")).json()
            return bad.status, missing.status, ok.status, await ok.json(), listing

        bad, missing, ok, body, listing = loop.run_until_complete(go())
        assert bad == 422
        assert missing == 404
        assert ok == 200
        assert body["state"] in (DRAINING, DETACHED)
        assert {r["replica"] for r in listing["replicas"]} == {0, 1}
        assert pool.healthy()  # draining never degrades /health


class TestSingleSchedulerHealth:
    @pytest.mark.filterwarnings(
        "ignore::pytest.PytestUnhandledThreadExceptionWarning"
    )
    def test_health_degrades_when_tick_thread_dies(self):
        """Satellite: the single-scheduler server also reports a dead
        tick thread as degraded."""
        from generativeaiexamples_tpu.engine.server import create_engine_app

        sched = _sched()
        sched.start()
        app = create_engine_app(sched, ByteTokenizer(), model_name="llama-tiny")
        loop = asyncio.new_event_loop()
        client = TestClient(TestServer(app), loop=loop)
        loop.run_until_complete(client.start_server())
        try:

            async def health():
                resp = await client.get("/health")
                return resp.status, await resp.json()

            status, body = loop.run_until_complete(health())
            assert status == 200 and body["status"] == "ok"

            def boom():
                raise SystemExit

            sched._tick = boom
            sched._thread.join(timeout=30)
            status, body = loop.run_until_complete(health())
            assert status == 503
            assert body["status"] == "degraded"

            async def drain_unsupported():
                return (await client.post("/admin/drain?replica=0")).status

            assert loop.run_until_complete(drain_unsupported()) == 501
        finally:
            loop.run_until_complete(client.close())
            loop.close()
            sched.stop()


class TestPoolAggregation:
    def test_snapshot_aggregates_and_breaks_down(self):
        pool = _pool(2, policy="round_robin")
        pool.start()
        try:
            for i in range(4):
                req, _, done = _request([i + 1, 2], f"agg-{i}")
                assert pool.submit(req)
                assert done.get(timeout=120) == "length"
            snap = pool.stats.snapshot()
        finally:
            pool.stop()
        assert snap["requests_total"] == 4
        assert snap["tokens_total"] == 12
        assert len(snap["replicas"]) == 2
        assert sum(r["requests_total"] for r in snap["replicas"]) == 4
        # Round-robin spread the closed-loop requests over both.
        assert all(r["requests_total"] == 2 for r in snap["replicas"])
        assert snap["ttft_avg_ms"] > 0
        assert snap["router_policy"] == "round_robin"


# What a scheduler counts that a pool does not add up: the averages and
# EWMAs it weighs or takes the worst replica's, the rejections it counts
# itself, and the bytes and planes of state, which stay a replica's own.
NOT_SUMMED = {
    "cache_planes", "kv_bytes_per_token",
    "rejected_total", "spec_acceptance_ewma",
    "state_bytes_draft", "state_bytes_full", "state_bytes_window",
    "tick_ms_ewma", "tick_ms_norm_ewma", "ttft_avg_ms",
}


def test_the_pool_sums_what_a_scheduler_counts():
    """A key added to ``Stats.snapshot()`` is summed over the replicas or
    named above, and a key taken away is summed no longer."""
    pool = _pool(2)
    snap = pool.replicas[0].scheduler.stats.snapshot()
    assert all(isinstance(v, (int, float)) for v in snap.values())
    assert set(snap) - set(EnginePool._SUM_KEYS) == NOT_SUMMED
    assert set(EnginePool._SUM_KEYS) <= set(snap)
    agg = pool.snapshot()
    assert set(EnginePool._SUM_KEYS) <= set(agg)
    assert not [k for k in agg if "kv_page" in k or "kv_cow" in k]
