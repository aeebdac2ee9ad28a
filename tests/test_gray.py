"""Gray-failure tolerance tests (CPU, tiny config).

Covers the PR 13 layer (`engine.health` + the EnginePool ejection state
machine + score-weighted routing + hedged requests): brownout scoring
from hand-fed TSDB series, the eject -> probation -> re-admit machine
(including the no-flap probation guarantee and the max-ejected-fraction
guard), the router's score weighting and bounded session map, the
hedge budget/delay controller, first-response-wins hedging over real
replicas, and the `replica:latency=ms,index=i` fault site.
"""

import queue
import threading
import time

import pytest

from generativeaiexamples_tpu.core.configuration import HealthConfig
from generativeaiexamples_tpu.engine.health import (
    HedgeController,
    ReplicaScorer,
    gray_metrics_lines,
)
from generativeaiexamples_tpu.engine.replica import (
    EJECTED,
    HEALTHY,
    PROBATION,
    EnginePool,
)
from generativeaiexamples_tpu.engine.router import ReplicaView, Router
from generativeaiexamples_tpu.engine.sampler import SamplingParams
from generativeaiexamples_tpu.engine.scheduler import Request, Scheduler
from generativeaiexamples_tpu.models import llama
from generativeaiexamples_tpu.obs.tsdb import Tsdb
from generativeaiexamples_tpu.resilience.faults import (
    get_fault_injector,
    inject_replica,
    reset_faults,
)

CFG = llama.llama_tiny(dtype="float32", max_seq_len=128)


def _sched(**kw):
    base = dict(max_batch=2, max_len=128, decode_chunk_size=4)
    base.update(kw)
    return Scheduler(CFG, **base)


def _cfg(**kw):
    base = dict(
        window_s=5.0,
        score_smoothing=1.0,  # no smoothing: tests assert raw scores
        eject_threshold=0.5,
        eject_after_s=0.0,  # first low check transitions (deterministic)
        readmit_score=0.8,
        readmit_after_s=0.0,
        probation_s=5.0,
        max_eject_fraction=0.5,
    )
    base.update(kw)
    return HealthConfig(**base)


def _pool(n=2, policy="least_loaded", **kw):
    kw.setdefault("health_interval", None)
    kw.setdefault("health_cfg", _cfg())
    kw.setdefault("tsdb", Tsdb())
    kw.setdefault("recorder", _Recorder())
    return EnginePool([_sched() for _ in range(n)], policy=policy, **kw)


def _request(prompt, rid, *, max_tokens=3, hedgeable=False):
    done: "queue.Queue[str]" = queue.Queue()
    tokens: list[int] = []
    req = Request(
        token_ids=list(prompt),
        sampling=SamplingParams(temperature=0.0, max_tokens=max_tokens),
        on_token=tokens.append,
        on_done=done.put,
        id=rid,
        hedgeable=hedgeable,
    )
    return req, tokens, done


class _Recorder:
    def __init__(self):
        self.entries = []

    def record(self, entry):
        self.entries.append(entry)


class _FixedScorer:
    """Stub scorer: the state-machine tests set scores directly."""

    def __init__(self, scores=None):
        self.scores = dict(scores or {})

    def score_all(self, indices, now=None):
        return {i: self.scores.get(i, 1.0) for i in indices}

    def drop(self, idx):
        self.scores.pop(idx, None)


# -- scoring ---------------------------------------------------------------


class TestReplicaScorer:
    def _feed(self, db, idx, name, values, t0=1000.0):
        for k, v in enumerate(values):
            db.record(f"engine.replica.{idx}.{name}", v, ts=t0 + k * 0.5)

    def test_no_data_scores_one(self):
        scorer = ReplicaScorer(_cfg(), Tsdb())
        assert scorer.score_all([0, 1, 2]) == {0: 1.0, 1: 1.0, 2: 1.0}

    def test_straggler_scores_low_peers_stay_high(self):
        db = Tsdb()
        for i in (0, 1, 2):
            self._feed(db, i, "tick_ms", [200.0 if i == 0 else 20.0] * 4)
        scorer = ReplicaScorer(_cfg(tick_tolerance=2.0), db)
        scores = scorer.score_all([0, 1, 2], now=1002.0)
        # 200ms vs a 20ms peer median = 10x, tolerance 2 -> 1/5^2.
        assert scores[0] == pytest.approx(0.04, abs=0.01)
        assert scores[1] == 1.0 and scores[2] == 1.0

    def test_correlated_slowness_ejects_nobody(self):
        db = Tsdb()
        for i in (0, 1, 2):
            self._feed(db, i, "tick_ms", [500.0] * 4)
        scorer = ReplicaScorer(_cfg(), db)
        scores = scorer.score_all([0, 1, 2], now=1002.0)
        # Everyone slow together: every ratio is 1.0, every score 1.0.
        assert all(s == 1.0 for s in scores.values())

    def test_queue_imbalance_scores_low(self):
        db = Tsdb()
        for i in (0, 1):
            self._feed(db, i, "queued", [15.0 if i == 0 else 0.0] * 4)
        scorer = ReplicaScorer(_cfg(tick_tolerance=2.0), db)
        scores = scorer.score_all([0, 1], now=1002.0)
        assert scores[0] < 0.5 < scores[1]

    def test_smoothing_slows_transitions(self):
        db = Tsdb()
        self._feed(db, 0, "tick_ms", [400.0] * 4)
        self._feed(db, 1, "tick_ms", [20.0] * 4)
        scorer = ReplicaScorer(_cfg(score_smoothing=0.4), db)
        first = scorer.score_all([0, 1], now=1002.0)[0]
        second = scorer.score_all([0, 1], now=1002.0)[0]
        # EWMA from 1.0 toward the (near-zero) raw score, stepwise.
        assert 0.5 < first < 0.7
        assert second < first

    def test_disabled_scores_constant_one(self):
        db = Tsdb()
        self._feed(db, 0, "tick_ms", [400.0] * 4)
        self._feed(db, 1, "tick_ms", [20.0] * 4)
        scorer = ReplicaScorer(_cfg(enabled=False), db)
        assert scorer.score_all([0, 1], now=1002.0) == {0: 1.0, 1: 1.0}


# -- ejection state machine ------------------------------------------------


class TestEjection:
    def test_sustained_brownout_ejects(self):
        pool = _pool(3)
        pool.scorer = _FixedScorer({0: 0.2})
        pool.check_replicas(now=100.0)
        assert pool.replicas[0].state == EJECTED
        assert pool.ejections_total == 1
        assert pool.ejected_count() == 1
        assert pool.pool_size() == 2
        # The transition is pinned into the flight recorder.
        pins = [e for e in pool._recorder.entries if "gray" in e["attrs"]]
        assert pins and pins[0]["attrs"]["gray"] == "ejected"
        assert pins[0]["degraded"] == ["gray:ejected:0"]

    def test_eject_needs_dwell_time(self):
        pool = _pool(3, health_cfg=_cfg(eject_after_s=3.0))
        pool.scorer = _FixedScorer({0: 0.2})
        pool.check_replicas(now=100.0)
        assert pool.replicas[0].state == HEALTHY  # dwell not elapsed
        pool.check_replicas(now=102.0)
        assert pool.replicas[0].state == HEALTHY
        pool.check_replicas(now=103.5)
        assert pool.replicas[0].state == EJECTED

    def test_score_recovery_resets_dwell(self):
        pool = _pool(2, health_cfg=_cfg(eject_after_s=3.0))
        pool.scorer = _FixedScorer({0: 0.2})
        pool.check_replicas(now=100.0)
        pool.scorer.scores[0] = 1.0  # blip, not a brownout
        pool.check_replicas(now=102.0)
        pool.scorer.scores[0] = 0.2
        pool.check_replicas(now=104.0)  # dwell restarts here
        assert pool.replicas[0].state == HEALTHY
        pool.check_replicas(now=107.5)
        assert pool.replicas[0].state == EJECTED

    def test_max_eject_fraction_guard(self):
        pool = _pool(3, health_cfg=_cfg(max_eject_fraction=0.4))
        pool.scorer = _FixedScorer({0: 0.1, 1: 0.1, 2: 0.1})
        pool.check_replicas(now=100.0)
        # floor(0.4 * 3) = 1: at most one replica may be quarantined,
        # however bad the scores look.
        states = [r.state for r in pool.replicas]
        assert states.count(EJECTED) == 1
        assert pool.pool_size() == 2

    def test_ejected_replica_unroutable_and_unmirrored(self):
        pool = _pool(2, policy="prefix")
        history = list(range(40))
        pool.router.note_finished(0, history)
        pool.scorer = _FixedScorer({0: 0.2})
        pool.check_replicas(now=100.0)
        assert 0 not in pool.router._mirrors
        views = pool._views_locked()
        assert [v.idx for v in views] == [1]

    def test_probation_readmission_no_flap(self):
        """A stalled-then-recovered replica re-admits through probation;
        a relapse during probation re-ejects instantly, and only a full
        clean probation restores HEALTHY."""
        pool = _pool(3, health_cfg=_cfg(probation_s=5.0))
        pool.scorer = _FixedScorer({0: 0.2})
        pool.check_replicas(now=100.0)
        assert pool.replicas[0].state == EJECTED
        # Recovery: score back over readmit_score -> PROBATION, routable.
        pool.scorer.scores[0] = 0.95
        pool.check_replicas(now=103.0)
        assert pool.replicas[0].state == PROBATION
        assert pool.readmissions_total == 1
        assert 0 in [v.idx for v in pool._views_locked()]
        # Still on probation before probation_s elapses: NOT healthy yet.
        pool.check_replicas(now=105.0)
        assert pool.replicas[0].state == PROBATION
        # Relapse during probation: re-ejected with no eject_after_s
        # dwell (this is the anti-flap teeth).
        pool.scorer.scores[0] = 0.3
        pool.check_replicas(now=106.0)
        assert pool.replicas[0].state == EJECTED
        assert pool.ejections_total == 2
        # Second recovery, clean all the way through probation.
        pool.scorer.scores[0] = 0.95
        pool.check_replicas(now=107.0)
        assert pool.replicas[0].state == PROBATION
        pool.check_replicas(now=112.5)
        assert pool.replicas[0].state == HEALTHY
        restored = [
            e
            for e in pool._recorder.entries
            if e["attrs"].get("gray") == "restored"
        ]
        assert restored

    def test_snapshot_and_metrics_surface_gray_state(self):
        pool = _pool(2)
        pool.scorer = _FixedScorer({0: 0.2})
        pool.check_replicas(now=100.0)
        snap = pool.snapshot()
        assert snap["ejected_replicas"] == 1
        assert snap["ejections_total"] == 1
        assert snap["pool_size"] == 1
        by_idx = {r["replica"]: r for r in snap["replicas"]}
        assert by_idx[0]["state"] == EJECTED and by_idx[0]["healthy"] == 0
        assert by_idx[0]["score"] == pytest.approx(0.2)
        text = "\n".join(gray_metrics_lines(pool))
        assert "engine_replica_ejections_total 1" in text
        assert "engine_pool_ejected_replicas 1" in text
        assert 'engine_replica_score{replica="0"} 0.2' in text


# -- score-weighted routing + bounded sessions -----------------------------


class TestScoredRouting:
    def test_least_loaded_prefers_higher_score(self):
        r = Router("least_loaded")
        views = [ReplicaView(0, 0, score=0.2), ReplicaView(1, 0, score=1.0)]
        assert all(r.select([1], "", views) == 1 for _ in range(4))

    def test_prefix_match_discounted_by_score(self):
        r = Router("prefix")
        history = list(range(40))
        r.note_finished(0, history)
        # Healthy mirror holder wins...
        views = [ReplicaView(0, 0, score=1.0), ReplicaView(1, 0, score=1.0)]
        assert r.select(history, "", views) == 0
        # ...but browned out (40 * 0.1 < min_prefix) it loses the match
        # AND the least-loaded fallback.
        views = [ReplicaView(0, 0, score=0.1), ReplicaView(1, 0, score=1.0)]
        assert r.select(history, "", views) == 1

    def test_session_breaks_off_browned_out_replica(self):
        r = Router("session", session_break=0.5)
        views = [ReplicaView(0, 0), ReplicaView(1, 0)]
        first = r.select([1], "conv", views)
        views = [
            ReplicaView(i, 0, score=0.2 if i == first else 1.0)
            for i in range(2)
        ]
        moved = r.select([2], "conv", views)
        assert moved != first
        # And the remap sticks.
        assert r.select([3], "conv", views) == moved

    def test_session_map_lru_bounded(self):
        r = Router("session", max_sessions=2)
        views = [ReplicaView(0, 0), ReplicaView(1, 0)]
        r.select([1], "a", views)
        r.select([1], "b", views)
        r.select([1], "a", views)  # refresh "a": now "b" is LRU
        r.select([1], "c", views)
        assert set(r._sessions) == {"a", "c"}
        assert r.session_evictions_total == 1

    def test_drop_replica_clears_its_sessions(self):
        r = Router("session")
        views = [ReplicaView(0, 0), ReplicaView(1, 0)]
        for sid in ("a", "b", "c", "d"):
            r.select([1], sid, views)
        dropped = {s for s, i in r._sessions.items() if i == 0}
        r.drop_replica(0)
        assert dropped.isdisjoint(r._sessions)


# -- hedging ---------------------------------------------------------------


class TestHedgeController:
    def test_budget_token_bucket(self):
        hc = HedgeController(_cfg(hedge_burst=2.0, hedge_budget_ratio=0.05))
        assert hc.try_spend() and hc.try_spend()
        assert not hc.try_spend()
        assert hc.suppressed_total == 1
        # 20 eligible submits at 5% refill one token.
        for _ in range(20):
            hc.note_submit()
        assert hc.try_spend()
        assert not hc.try_spend()

    def test_delay_tracks_upper_tail_with_floor(self):
        hc = HedgeController(_cfg(hedge_min_delay_ms=30.0))
        assert hc.delay_ms() == 30.0
        for _ in range(20):
            hc.note_latency(500.0)
        assert hc.delay_ms() > 200.0
        for _ in range(1000):
            hc.note_latency(1.0)
        # Slow decay, hard floor.
        assert hc.delay_ms() == 30.0

    def test_warmup_gate(self):
        hc = HedgeController(_cfg())
        assert not hc.ready
        for _ in range(HedgeController.WARMUP_SAMPLES):
            hc.note_latency(50.0)
        assert hc.ready

    def test_disabled_by_config(self):
        assert not HedgeController(_cfg(hedge_enabled=False)).enabled
        assert not HedgeController(_cfg(enabled=False)).enabled
        assert HedgeController(_cfg()).enabled


class TestHedgedRequests:
    def test_hedge_wins_when_primary_stuck(self):
        """Primary replica never ticks (not started); the hedge copy on
        the live sibling answers, claims the placement, and the client
        sees exactly one completion."""
        pool = _pool(2)
        try:
            req, tokens, done = _request(
                [1, 2, 3], "hedge-1", max_tokens=3, hedgeable=True
            )
            assert pool.submit(req)
            primary = pool._placements["hedge-1"].replica
            sibling = 1 - primary
            pool.replicas[sibling].scheduler.start()
            pool._hedge_fire("hedge-1")
            assert pool.hedger.fired_total == 1
            assert done.get(timeout=30) in ("stop", "length")
            assert len(tokens) == 3
            assert done.empty()  # exactly one terminal callback
            assert pool.hedger.wins_total == 1
            assert pool.hedger.cancelled_total == 1
            assert "hedge-1" not in pool._placements
            snap = pool.snapshot()
            assert snap["hedge_wins_total"] == 1
        finally:
            pool.stop()

    def test_primary_win_cancels_hedge(self):
        """Both replicas live: whoever answers first wins and the loser
        is cancelled; the client still sees exactly one stream."""
        pool = _pool(2)
        try:
            pool.replicas[0].scheduler.start()
            pool.replicas[1].scheduler.start()
            req, tokens, done = _request(
                [1, 2, 3], "hedge-2", max_tokens=3, hedgeable=True
            )
            assert pool.submit(req)
            pool._hedge_fire("hedge-2")
            assert done.get(timeout=30) in ("stop", "length")
            assert len(tokens) == 3
            assert done.empty()
            assert pool.hedger.fired_total <= 1
            if pool.hedger.fired_total:
                assert pool.hedger.cancelled_total == 1
        finally:
            pool.stop()

    def test_arm_respects_eligibility(self):
        pool = _pool(2)
        try:
            pool.replicas[0].scheduler.start()
            pool.replicas[1].scheduler.start()
            # Warm the controller so arming is not warmup-gated.
            for _ in range(HedgeController.WARMUP_SAMPLES):
                pool.hedger.note_latency(50.0)
            # Not hedgeable: no timer armed.
            req, _, done = _request([1, 2, 3], "h-a", hedgeable=False)
            assert pool.submit(req)
            assert pool._placements["h-a"].hedge_timer is None
            done.get(timeout=30)
            # Too long a generation: not eligible either.
            req, _, done = _request(
                [1, 2, 3], "h-b", max_tokens=99, hedgeable=True
            )
            assert pool.submit(req)
            assert pool._placements["h-b"].hedge_timer is None
            done.get(timeout=30)
            # Short + hedgeable: timer armed.
            req, _, done = _request(
                [1, 2, 3], "h-c", max_tokens=3, hedgeable=True
            )
            assert pool.submit(req)
            placement = pool._placements.get("h-c")
            assert placement is None or placement.hedge_eligible
            done.get(timeout=30)
        finally:
            pool.stop()

    def test_cancel_reaches_both_copies(self):
        pool = _pool(2)
        try:
            req, _, done = _request(
                [1, 2, 3], "h-x", max_tokens=3, hedgeable=True
            )
            assert pool.submit(req)
            pool._hedge_fire("h-x")  # hedge copy parked on the sibling
            pool.cancel("h-x")
            placement = pool._placements["h-x"]
            assert placement.cancelled
            # Neither copy may deliver tokens now; start the schedulers
            # and confirm the request dies as cancelled.
            pool.replicas[0].scheduler.start()
            pool.replicas[1].scheduler.start()
            assert done.get(timeout=30) == "cancelled"
        finally:
            pool.stop()


# -- replica fault site ----------------------------------------------------


class TestReplicaFaultSite:
    def teardown_method(self):
        reset_faults()

    def test_index_filter(self):
        inj = get_fault_injector()
        inj.configure("replica:latency=5,index=1")
        t0 = time.perf_counter()
        inject_replica(0)
        fast = time.perf_counter() - t0
        inject_replica(1)
        counts = inj.counts()["replica"]
        # Only the indexed replica traverses the armed point.
        assert counts["hits"] == 1
        assert fast < 0.004

    def test_spec_round_trip_and_unknown_key(self):
        inj = get_fault_injector()
        inj.configure("replica:latency=1,index=0")
        point = inj._points["replica"]
        assert point.index == 0 and point.latency_ms == 1.0
        with pytest.raises(ValueError, match="unknown key"):
            inj.configure("replica:bogus=1")

    def test_indexless_spec_hits_all_replicas(self):
        inj = get_fault_injector()
        inj.configure("replica:latency=0")
        inject_replica(0)
        inject_replica(3)
        assert inj.counts()["replica"]["hits"] == 2


# -- scheduler integration -------------------------------------------------


class _ClockedScorer(ReplicaScorer):
    """The real scorer, reading its window at the test's clock."""

    now = 0.0

    def score_all(self, indices, now=None):
        return super().score_all(indices, now=self.now)


class TestBrownoutLoop:
    def test_slow_replica_is_ejected_and_readmitted_and_no_request_lost(self):
        """Series -> scorer -> state machine -> routing, closed over three
        live replicas on a simulated clock: the tick series of replica 0
        read ten times its peers', it is scored down and, after the
        dwell, ejected; requests then complete on the other two; with
        its series back to the peers' it returns through probation to
        healthy.  Every request ends once, with all its tokens.  (That
        an injected latency reaches the series is
        `TestSchedulerTickInjection`; here the series are written by
        hand, two a second, so no wall clock decides who is slow.)"""
        pool = _pool(
            3,
            health_cfg=_cfg(
                window_s=3.0,
                score_smoothing=0.6,
                eject_after_s=1.0,
                readmit_after_s=1.0,
                probation_s=1.0,
            ),
        )
        pool.scorer = _ClockedScorer(pool.health_cfg, pool.tsdb)
        clock = [1000.0]

        def step(slow_ms):
            clock[0] += 0.5
            for i in range(3):
                pool.tsdb.record(
                    f"engine.replica.{i}.tick_ms",
                    slow_ms if i == 0 else 20.0,
                    ts=clock[0],
                )
            pool.scorer.now = clock[0]
            pool.check_replicas(now=clock[0])

        def ask(i):
            req, tokens, done = _request([1 + i % 7, 2, 3, 4], f"brown-{i}")
            assert pool.submit(req)
            assert done.get(timeout=120) == "length"
            assert len(tokens) == 3 and done.empty()

        def states():
            return [r.state for r in pool.replicas]

        pool.start()
        try:
            for i in range(6):
                ask(i)
            for _ in range(4):
                step(20.0)
            assert states() == [HEALTHY] * 3

            step(200.0)  # scored down, but not yet for a second
            assert states() == [HEALTHY] * 3
            for _ in range(5):
                step(200.0)
            assert states() == [EJECTED, HEALTHY, HEALTHY]
            assert pool.replicas[0].score < 0.5
            assert pool.replicas[1].score == pool.replicas[2].score == 1.0
            assert [v.idx for v in pool._views_locked()] == [1, 2]
            served = pool.replicas[0].scheduler.stats.requests_total
            for i in range(6, 12):
                ask(i)
            assert pool.replicas[0].scheduler.stats.requests_total == served

            seen = []
            for _ in range(16):  # 3 s for the window to forget, then dwell
                step(20.0)
                if pool.replicas[0].state not in seen:
                    seen.append(pool.replicas[0].state)
            assert seen == [EJECTED, PROBATION, HEALTHY]
            assert pool.ejections_total == pool.readmissions_total == 1
            ask(12)
            assert not pool._placements
        finally:
            pool.stop()


class TestSchedulerTickInjection:
    def teardown_method(self):
        reset_faults()

    def test_injected_latency_lands_in_tick_ewma(self):
        get_fault_injector().configure("replica:latency=30,index=0")
        pool = _pool(1)
        try:
            pool.replicas[0].scheduler.start()
            deadline = time.monotonic() + 10
            while time.monotonic() < deadline:
                if pool.replicas[0].scheduler.stats.tick_ms_ewma > 10.0:
                    break
                time.sleep(0.05)
            assert pool.replicas[0].scheduler.stats.tick_ms_ewma > 10.0
        finally:
            pool.stop()

    def test_feed_tsdb_emits_score_and_latency_series(self):
        db = Tsdb()
        pool = _pool(2, tsdb=db)
        pool._feed_tsdb()
        names = set(db.names())
        for i in (0, 1):
            assert f"engine.replica.{i}.tick_ms" in names
            assert f"engine.replica.{i}.score" in names
