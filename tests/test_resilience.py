"""Resilience layer: deadlines, retries, breakers, faults, degradation.

Unit coverage for every ``resilience/`` primitive, the Retriever's
degradation ladder, the MicroBatcher's deadline-expiry and crash-guard
contracts, and end-to-end chain-server behavior: a reranker fault must
yield HTTP 200 with ``degraded=["rerank"]``, a hard-down embedder must
yield an LLM-only answer with ``degraded=["retrieval"]``, and an expired
request deadline must yield a fast 504 — never a hang.
"""

import asyncio
import json
import os
import threading
import time

import pytest
from aiohttp.test_utils import TestClient, TestServer

from generativeaiexamples_tpu.core.configuration import reset_config_cache
from generativeaiexamples_tpu.resilience.breaker import (
    CircuitBreaker,
    CircuitOpenError,
    get_breaker,
    reset_breakers,
)
from generativeaiexamples_tpu.resilience.deadline import (
    Deadline,
    DeadlineExceeded,
    current_deadline,
    deadline_scope,
)
from generativeaiexamples_tpu.resilience.degrade import (
    DegradeLog,
    degrade_scope,
    mark_degraded,
)
from generativeaiexamples_tpu.resilience.faults import (
    FaultInjected,
    FaultInjector,
    get_fault_injector,
    inject,
    reset_faults,
)
from generativeaiexamples_tpu.resilience.metrics import (
    reset_resilience,
    resilience_metrics_lines,
    resilience_snapshot,
)
from generativeaiexamples_tpu.resilience.retry import RetryBudget, RetryPolicy


@pytest.fixture(autouse=True)
def _clean_resilience():
    reset_resilience()
    yield
    reset_resilience()


# -- Deadline ----------------------------------------------------------------


def test_deadline_budget_and_expiry():
    dl = Deadline.after_ms(10_000)
    assert not dl.expired()
    assert 9_000 < dl.remaining_ms() <= 10_000
    dl.check("ok")  # no raise

    expired = Deadline(time.monotonic() - 1.0)
    assert expired.expired()
    with pytest.raises(DeadlineExceeded, match="at embed"):
        expired.check("embed")
    assert resilience_snapshot()["deadline_expired_total"] == 1


def test_deadline_nonpositive_means_unlimited():
    for ms in (0, -5):
        dl = Deadline.after_ms(ms)
        assert dl.is_unlimited and not dl.expired()
        dl.check()


def test_deadline_latest_is_loosest_member():
    a = Deadline.after_ms(100)
    b = Deadline.after_ms(10_000)
    joined = Deadline.latest([a, b])
    assert joined.remaining_ms() > 5_000
    # Any unlimited member (or an empty batch) makes the batch unlimited.
    assert Deadline.latest([a, None]) is None
    assert Deadline.latest([a, Deadline.unlimited()]) is None
    assert Deadline.latest([]) is None


def test_deadline_cap_timeout_never_extends():
    dl = Deadline.after_ms(1_000)
    assert dl.cap_timeout(60.0) <= 1.0
    assert dl.cap_timeout(0.2) == 0.2
    assert dl.cap_timeout(None) <= 1.0
    assert Deadline.unlimited().cap_timeout(None) is None


def test_deadline_contextvar_scope():
    assert current_deadline() is None
    dl = Deadline.after_ms(5_000)
    with deadline_scope(dl):
        assert current_deadline() is dl
        seen = {}

        def other_thread():
            seen["dl"] = current_deadline()

        t = threading.Thread(target=other_thread)
        t.start()
        t.join(10)
        # contextvars do NOT cross threads — that's why the micro-batcher
        # carries deadlines per queue entry.
        assert seen["dl"] is None
    assert current_deadline() is None


# -- RetryPolicy -------------------------------------------------------------


def test_retry_succeeds_after_transient_failures():
    calls = []

    def flaky():
        calls.append(1)
        if len(calls) < 3:
            raise ValueError("transient")
        return "ok"

    policy = RetryPolicy(max_attempts=3, base_ms=1, jitter=0.0)
    assert policy.call(flaky) == "ok"
    assert len(calls) == 3
    assert resilience_snapshot()["retries_total"] == 2


def test_retry_exhaustion_raises_last_error():
    policy = RetryPolicy(max_attempts=2, base_ms=1)
    with pytest.raises(ValueError, match="always"):
        policy.call(lambda: (_ for _ in ()).throw(ValueError("always")))


def test_retry_budget_caps_retry_storm():
    budget = RetryBudget(ratio=0.0, cap=1.0)
    budget._tokens = 0.0  # drained: a hard-down dependency
    policy = RetryPolicy(max_attempts=5, base_ms=1, budget=budget)
    calls = []

    def failing():
        calls.append(1)
        raise ValueError("down")

    with pytest.raises(ValueError):
        policy.call(failing)
    assert len(calls) == 1  # failed fast, no budgetless retries


def test_retry_never_sleeps_past_deadline():
    policy = RetryPolicy(max_attempts=5, base_ms=60_000, jitter=0.0)
    calls = []

    def failing():
        calls.append(1)
        raise ValueError("dependency down")

    t0 = time.perf_counter()
    # Backoff (60s) exceeds the remaining budget: the dependency's error
    # surfaces instead of a sleep that manufactures a timeout.
    with pytest.raises(ValueError, match="dependency down"):
        policy.call(failing, deadline=Deadline.after_ms(200))
    assert time.perf_counter() - t0 < 1.0
    assert len(calls) == 1


def test_retry_does_not_retry_deadline_or_breaker_errors():
    policy = RetryPolicy(max_attempts=5, base_ms=1)
    calls = []

    def expired():
        calls.append(1)
        raise DeadlineExceeded("spent")

    with pytest.raises(DeadlineExceeded):
        policy.call(expired)
    assert len(calls) == 1

    breaker = CircuitBreaker("dep", window=4, min_calls=1, failure_threshold=0.5)
    breaker.record_failure()  # trips (1/1 >= 0.5)
    with pytest.raises(CircuitOpenError):
        policy.call(lambda: "unreached", breaker=breaker)


def test_retry_records_outcomes_into_breaker():
    breaker = CircuitBreaker("dep", window=8, min_calls=8)
    policy = RetryPolicy(max_attempts=2, base_ms=1)
    calls = []

    def flaky():
        calls.append(1)
        if len(calls) < 2:
            raise ValueError("once")
        return "ok"

    assert policy.call(flaky, breaker=breaker) == "ok"
    assert list(breaker._window) == [True, False]


# -- CircuitBreaker ----------------------------------------------------------


def _fake_clock():
    state = {"t": 1000.0}

    def clock():
        return state["t"]

    return state, clock


def test_breaker_trips_at_failure_threshold():
    b = CircuitBreaker("dep", window=8, min_calls=4, failure_threshold=0.5)
    for _ in range(2):
        b.record_success()
    b.record_failure()
    assert b.state == "closed"  # 1/3 failures, below min_calls anyway
    b.record_failure()  # 2/4 = 0.5 -> trips
    assert b.state == "open"
    assert b.open_total == 1
    with pytest.raises(CircuitOpenError) as exc_info:
        b.check()
    assert exc_info.value.retry_after_s > 0


def test_breaker_half_open_probe_then_close():
    state, clock = _fake_clock()
    b = CircuitBreaker(
        "dep", window=4, min_calls=2, failure_threshold=0.5,
        reset_timeout_s=30.0, half_open_max=2, clock=clock,
    )
    b.record_failure()
    b.record_failure()
    assert b.state == "open"
    assert not b.allow()  # cool-down not elapsed
    state["t"] += 31.0
    assert b.state == "half_open"
    assert b.allow() and b.allow()  # two probes admitted
    assert not b.allow()  # third refused: half_open_max=2
    b.record_success()
    b.record_success()
    assert b.state == "closed"


def test_breaker_reopens_on_probe_failure():
    state, clock = _fake_clock()
    b = CircuitBreaker(
        "dep", window=4, min_calls=2, failure_threshold=0.5,
        reset_timeout_s=30.0, clock=clock,
    )
    b.record_failure()
    b.record_failure()
    state["t"] += 31.0
    assert b.allow()
    b.record_failure()  # failed probe: fresh cool-down
    assert b.state == "open"
    assert not b.allow()
    assert b.open_total == 2


def test_breaker_registry_shares_instances():
    assert get_breaker("embedder") is get_breaker("embedder")
    assert get_breaker("embedder") is not get_breaker("store")
    reset_breakers()
    from generativeaiexamples_tpu.resilience.breaker import all_breakers

    assert all_breakers() == {}


# -- FaultInjector -----------------------------------------------------------


def test_fault_spec_parsing_and_injection():
    inj = FaultInjector(seed=7)
    inj.configure("embedder:error=1.0;reranker:latency=5")
    with pytest.raises(FaultInjected):
        inj.inject("embedder")
    t0 = time.perf_counter()
    inj.inject("reranker")  # latency only, no error
    assert time.perf_counter() - t0 >= 0.004
    inj.inject("llm")  # unarmed site: no-op
    counts = inj.counts()
    assert counts["embedder"]["errors"] == 1
    assert counts["reranker"]["hits"] == 1


def test_fault_count_budget_disarms():
    inj = FaultInjector()
    inj.install("store", error_rate=1.0, count=2)
    for _ in range(2):
        with pytest.raises(FaultInjected):
            inj.inject("store")
    inj.inject("store")  # budget spent: passes through


def test_fault_bad_specs_rejected():
    inj = FaultInjector()
    for spec in ("noseparator", "x:error=nan2", "x:bogus=1", "x:error=2.0"):
        with pytest.raises(ValueError):
            inj.configure(spec)


def test_module_inject_fast_path_and_reset():
    inject("embedder")  # nothing armed: free no-op
    get_fault_injector().configure("embedder:error=1.0")
    with pytest.raises(FaultInjected):
        inject("embedder")
    reset_faults()
    inject("embedder")  # disarmed again


def test_gaie_faults_env_arms_on_first_use(monkeypatch):
    reset_faults()
    monkeypatch.setenv("GAIE_FAULTS", "llm:error=1.0")
    with pytest.raises(FaultInjected):
        inject("llm")


# -- DegradeLog + metrics ----------------------------------------------------


def test_degrade_log_dedups_and_counts_once():
    with degrade_scope() as log:
        mark_degraded("rerank")
        mark_degraded("rerank")
        mark_degraded("shrink_k")
        assert log.stages() == ["rerank", "shrink_k"]
    snap = resilience_snapshot()
    assert snap["degraded_total"] == {"rerank": 1, "shrink_k": 1}


def test_mark_degraded_without_scope_still_counts():
    mark_degraded("retrieval")
    assert resilience_snapshot()["degraded_total"]["retrieval"] == 1


def test_metrics_lines_export_all_series_from_zero():
    text = "\n".join(resilience_metrics_lines())
    assert "rag_retries_total 0" in text
    assert "rag_deadline_expired_total 0" in text
    for stage in ("rerank", "shrink_k", "index_fallback", "retrieval"):
        assert f'rag_degraded_total{{stage="{stage}"}} 0' in text
    for dep in ("embedder", "store", "reranker", "llm"):
        assert f'rag_breaker_state{{dep="{dep}"}} 0' in text
        assert f'rag_breaker_open_total{{dep="{dep}"}} 0' in text


# -- Retriever degradation ladder --------------------------------------------


class _FakeEmbedder:
    dimensions = 8

    def embed_queries(self, texts):
        return [[1.0] * 8 for _ in texts]

    def embed_query(self, text):
        return [1.0] * 8

    def embed_documents(self, texts):
        return [[1.0] * 8 for _ in texts]


class _FakeStore:
    """search_batch raises on demand; search_fallback always answers."""

    def __init__(self, fail=False):
        self.fail = fail
        self.fallback_calls = 0

    def search_batch(self, embeddings, top_k):
        if self.fail:
            raise RuntimeError("index corrupt")
        return [self._hits(top_k) for _ in embeddings]

    def search_fallback(self, embeddings, top_k):
        self.fallback_calls += 1
        return [self._hits(top_k) for _ in embeddings]

    @staticmethod
    def _hits(top_k):
        from generativeaiexamples_tpu.retrieval.base import Chunk, ScoredChunk

        return [
            ScoredChunk(Chunk(text=f"passage {i}", source="d.txt"), 1.0 - i * 0.1)
            for i in range(top_k)
        ]


class _FailingReranker:
    def score(self, query, texts):
        raise RuntimeError("reranker down")


class _IdentityReranker:
    def score(self, query, texts):
        return [float(len(texts) - i) for i in range(len(texts))]


def _make_retriever(**kwargs):
    from generativeaiexamples_tpu.retrieval.retriever import Retriever

    defaults = dict(
        store=_FakeStore(),
        embedder=_FakeEmbedder(),
        top_k=4,
        score_threshold=-1e30,
        embed_retry=RetryPolicy(max_attempts=2, base_ms=1, name="embed"),
        search_retry=RetryPolicy(max_attempts=2, base_ms=1, name="store-search"),
    )
    defaults.update(kwargs)
    return Retriever(**defaults)


def test_reranker_fault_degrades_to_vector_order():
    retriever = _make_retriever(reranker=_FailingReranker())
    with degrade_scope() as log:
        hits = retriever.retrieve("q")
    assert len(hits) == 4
    assert hits[0].score >= hits[-1].score  # vector-search order preserved
    assert log.stages() == ["rerank"]


def test_reranker_breaker_open_skips_rerank_without_recording():
    retriever = _make_retriever(reranker=_IdentityReranker())
    b = get_breaker("reranker", window=4, min_calls=1, failure_threshold=0.5)
    b.record_failure()
    assert b.state == "open"
    with degrade_scope() as log:
        hits = retriever.retrieve("q")
    assert len(hits) == 4
    assert log.stages() == ["rerank"]


def test_store_fault_serves_exact_fallback():
    store = _FakeStore(fail=True)
    retriever = _make_retriever(store=store)
    with degrade_scope() as log:
        hits = retriever.retrieve("q")
    assert len(hits) == 4
    assert store.fallback_calls == 1
    assert log.stages() == ["index_fallback"]
    # The store breaker recorded the real failures.
    assert get_breaker("store")._window.count(True) >= 1


def test_low_budget_shrinks_k_and_skips_rerank():
    retriever = _make_retriever(
        reranker=_IdentityReranker(),
        min_rerank_budget_ms=10_000.0,
        min_full_k_budget_ms=5_000.0,
    )
    with degrade_scope() as log:
        hits = retriever.retrieve_many(["q"], deadline=Deadline.after_ms(1_000))[0]
    assert len(hits) == 2  # shrunk from 4
    assert set(log.stages()) == {"shrink_k", "rerank"}


def test_embedder_hard_down_raises_for_chain_level_fallback():
    class _DownEmbedder(_FakeEmbedder):
        def embed_queries(self, texts):
            raise ConnectionError("embedder unreachable")

    retriever = _make_retriever(embedder=_DownEmbedder())
    with pytest.raises(ConnectionError):
        retriever.retrieve("q")


def test_batched_degrade_marks_every_members_log():
    retriever = _make_retriever(reranker=_FailingReranker())
    logs = [DegradeLog(), DegradeLog(), None]
    retriever.retrieve_many(["a", "b", "c"], degrade_logs=logs)
    assert logs[0].stages() == ["rerank"]
    assert logs[1].stages() == ["rerank"]
    # The per-request counter bumped once per request, not once per batch.
    assert resilience_snapshot()["degraded_total"]["rerank"] == 3


def test_expired_deadline_rejects_before_any_stage():
    retriever = _make_retriever()
    with pytest.raises(DeadlineExceeded):
        retriever.retrieve_many(["q"], deadline=Deadline(time.monotonic() - 1))


@pytest.mark.parametrize("attempts, lost", [(3, 0), (1, 2)])
def test_concurrent_clients_under_faults_lose_only_what_no_retry_covers(
    attempts, lost
):
    """Eight clients at once, the embedder failing twice and the reranker
    down for good.  With three attempts a stage no request is lost and
    every one is served in vector order, marked ``rerank``; with one
    attempt exactly the two faulted requests fail.  A request that got its
    hits got all of them either way."""
    retriever = _make_retriever(
        reranker=_IdentityReranker(),
        embed_retry=RetryPolicy(max_attempts=attempts, base_ms=1, name="embed"),
    )
    get_fault_injector().configure(
        "embedder:error=1.0,count=2;reranker:error=1.0"
    )
    outcomes: list = []

    def client():
        for _ in range(6):
            try:
                with deadline_scope(Deadline.after_ms(30_000)), \
                        degrade_scope() as log:
                    hits = retriever.retrieve("q")
                outcomes.append((len(hits), log.stages()))
            except FaultInjected:
                outcomes.append(None)

    threads = [threading.Thread(target=client) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    assert len(outcomes) == 48
    assert outcomes.count(None) == lost
    assert all(o == (4, ["rerank"]) for o in outcomes if o is not None)
    assert resilience_snapshot()["retries_total"] == (2 if attempts > 1 else 0)
    reset_faults()
    assert get_fault_injector().active_sites() == []


# -- MicroBatcher: deadline expiry + crash guard -----------------------------


def test_microbatch_expired_entries_fail_before_dispatch():
    from generativeaiexamples_tpu.engine.microbatch import MicroBatcher

    dispatched = []

    def slow_fn(items):
        dispatched.append(list(items))
        return items

    batcher = MicroBatcher(slow_fn, max_batch=8, max_wait_ms=80.0, name="t")
    try:
        # Expires while queued (the 80 ms window outlives the 20 ms budget).
        fut = batcher.submit("x", deadline=Deadline.after_ms(20))
        with pytest.raises(DeadlineExceeded):
            fut.result(timeout=5)
        assert dispatched == [] or "x" not in dispatched[0]
        assert resilience_snapshot()["deadline_expired_total"] >= 1
    finally:
        batcher.close()


def test_microbatch_submit_refuses_already_expired():
    from generativeaiexamples_tpu.engine.microbatch import MicroBatcher

    batcher = MicroBatcher(lambda items: items, name="t")
    try:
        with pytest.raises(DeadlineExceeded):
            batcher.submit("x", deadline=Deadline(time.monotonic() - 1))
    finally:
        batcher.close()


def test_microbatch_call_picks_up_context_deadline():
    from generativeaiexamples_tpu.engine.microbatch import MicroBatcher

    seen = []

    def fn(items):
        seen.append(current_deadline())
        return items

    batcher = MicroBatcher(fn, max_batch=4, max_wait_ms=1.0, name="t")
    try:
        with deadline_scope(Deadline.after_ms(30_000)):
            assert batcher.call("x", timeout=5) == "x"
        # The worker thread ran under the entry's deadline even though
        # contextvars don't cross threads.
        assert seen[0] is not None and not seen[0].is_unlimited
    finally:
        batcher.close()


def test_microbatch_worker_crash_fails_pending_and_restarts(monkeypatch):
    from generativeaiexamples_tpu.engine import microbatch as mb

    batcher = mb.MicroBatcher(
        lambda items: items, max_batch=4, max_wait_ms=5.0, name="t"
    )
    try:
        # Crash the worker OUTSIDE the per-item dispatch path: stats
        # recording happens before fn runs, so per-item isolation can't
        # catch it — exactly the bug class the crash guard exists for.
        original = batcher.stats.record_batch
        calls = {"n": 0}

        def bomb(*args, **kwargs):
            calls["n"] += 1
            if calls["n"] == 1:
                raise RuntimeError("bookkeeping bug")
            return original(*args, **kwargs)

        monkeypatch.setattr(batcher.stats, "record_batch", bomb)
        fut = batcher.submit("poisoned")
        with pytest.raises(RuntimeError, match="worker crashed"):
            fut.result(timeout=5)
        # The restarted worker serves new submissions normally.
        assert batcher.call("fresh", timeout=5) == "fresh"
    finally:
        batcher.close()


# -- End-to-end: chain server ------------------------------------------------


def _reset_server_env(monkeypatch, tmp_path):
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
def server(monkeypatch, tmp_path):
    _reset_server_env(monkeypatch, tmp_path)
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


async def _sse_chunks(resp):
    chunks = []
    async for line in resp.content:
        line = line.decode().strip()
        if line.startswith("data: "):
            chunks.append(json.loads(line[len("data: "):]))
    return chunks


def _upload_doc(server, tmp_path):
    c, loop = server
    doc = tmp_path / "facts.txt"
    doc.write_text(
        "TPU v5e chips have 16 GiB of HBM.\n\n"
        "The systolic array multiplies matrices."
    )

    async def upload():
        with open(doc, "rb") as fh:
            resp = await c.post("/documents", data={"file": fh})
        return resp.status

    assert _run(loop, upload()) == 200


def _generate(c, extra_headers=None, **overrides):
    body = {
        "messages": [{"role": "user", "content": "how much HBM?"}],
        "use_knowledge_base": True,
        "max_tokens": 64,
    }
    body.update(overrides)
    return c.post("/generate", json=body, headers=extra_headers or {})


class _LexicalTestReranker:
    def score(self, query, texts):
        qw = set(query.lower().split())
        return [len(qw & set(t.lower().split())) / max(len(qw), 1) for t in texts]


def test_e2e_reranker_fault_yields_degraded_rerank(server, tmp_path, monkeypatch):
    """A failing reranker must not fail the request: 200, grounded
    answer from vector-search order, degraded=["rerank"] on [DONE]."""
    import functools

    from generativeaiexamples_tpu.chains import factory

    # lru_cache gives the fake the cache_clear() reset_factories expects.
    monkeypatch.setattr(
        factory,
        "get_reranker",
        functools.lru_cache(maxsize=None)(lambda: _LexicalTestReranker()),
    )
    c, loop = server
    _upload_doc(server, tmp_path)
    get_fault_injector().configure("reranker:error=1.0")

    async def go():
        resp = await _generate(c)
        assert resp.status == 200
        return await _sse_chunks(resp)

    chunks = _run(loop, go())
    done = chunks[-1]
    assert done["choices"][0]["finish_reason"] == "[DONE]"
    assert done["degraded"] == ["rerank"]
    text = "".join(ch["choices"][0]["message"]["content"] for ch in chunks[:-1])
    # The echo LLM reports its system-prompt size: retrieved context
    # reached the prompt despite the dead reranker.
    assert "ECHO[how much HBM?]" in text and "ctx:" in text


def test_e2e_embedder_down_serves_llm_only(server, tmp_path):
    """Embedder breaker open -> retrieval is hard-down -> the chain
    answers LLM-only with degraded=["retrieval"] instead of erroring."""
    c, loop = server
    _upload_doc(server, tmp_path)

    async def go():
        resp = await _generate(c)
        assert resp.status == 200
        return await _sse_chunks(resp)

    def ctx_chars(chunks):
        text = "".join(
            ch["choices"][0]["message"]["content"] for ch in chunks[:-1]
        )
        assert "ECHO[how much HBM" in text
        return int(text.rsplit("ctx:", 1)[1].rstrip("ch")) if "ctx:" in text else 0

    grounded = _run(loop, go())
    assert grounded[-1]["degraded"] == []

    b = get_breaker("embedder")
    for _ in range(32):
        b.record_failure()
    assert b.state == "open"

    # The exact query asked before the outage still serves GROUNDED:
    # the exact cache tier needs no embedding at all.
    cached = _run(loop, go())
    assert cached[-1]["degraded"] == []
    assert cached[-1]["cached"] and cached[-1]["cache_tier"] == "exact"

    # A never-seen query is a true miss: retrieval is hard-down and the
    # chain answers LLM-only with degraded=["retrieval"].
    async def fresh():
        resp = await _generate(
            c,
            messages=[
                {"role": "user", "content": "how much HBM exactly today?"}
            ],
        )
        assert resp.status == 200
        return await _sse_chunks(resp)

    llm_only = _run(loop, fresh())
    assert llm_only[-1]["degraded"] == ["retrieval"]
    # The echo LLM reports its system-prompt size: the LLM-only prompt is
    # the bare base prompt, strictly smaller than the grounded one.
    assert ctx_chars(llm_only) < ctx_chars(grounded)


def test_e2e_expired_deadline_is_fast_504(server, tmp_path):
    """An unmeetable deadline must be refused quickly with a typed 504 —
    not computed, not hung, not a 200 with an error chunk."""
    c, loop = server
    _upload_doc(server, tmp_path)

    async def go():
        t0 = time.perf_counter()
        resp = await _generate(
            c, extra_headers={"X-Request-Deadline-Ms": "1"}
        )
        elapsed = time.perf_counter() - t0
        body = await resp.json()
        return resp.status, elapsed, body

    status, elapsed, body = _run(loop, go())
    assert status == 504
    assert elapsed < 2.0
    assert "deadline" in body["detail"].lower()
    # The expiry was counted for /metrics.
    assert resilience_snapshot()["deadline_expired_total"] >= 1


def test_e2e_search_deadline_504_and_degraded_field(server, tmp_path):
    c, loop = server
    _upload_doc(server, tmp_path)

    async def expired():
        resp = await c.post(
            "/search",
            json={"query": "HBM", "top_k": 2},
            headers={"X-Request-Deadline-Ms": "1"},
        )
        return resp.status

    assert _run(loop, expired()) == 504

    async def healthy():
        resp = await c.post("/search", json={"query": "HBM", "top_k": 2})
        return resp.status, await resp.json()

    status, body = _run(loop, healthy())
    assert status == 200
    assert body["degraded"] == []
    assert body["chunks"]


def test_e2e_llm_breaker_open_is_retryable_503(server, tmp_path):
    """An open LLM breaker means no backend can answer: 503 with a
    Retry-After hint, the load-balancer-friendly refusal."""
    c, loop = server
    b = get_breaker("llm")
    for _ in range(32):
        b.record_failure()
    assert b.state == "open"

    async def go():
        resp = await _generate(c, use_knowledge_base=False)
        return resp.status, resp.headers.get("Retry-After")

    status, retry_after = _run(loop, go())
    assert status == 503
    assert retry_after is not None and int(retry_after) >= 1


def test_e2e_health_reports_breaker_states(server):
    c, loop = server
    get_breaker("embedder")  # touch one so the registry is non-empty

    async def go():
        resp = await c.get("/health")
        return await resp.json()

    body = _run(loop, go())
    assert body["breakers"].get("embedder") == "closed"


def test_e2e_metrics_export_resilience_series(server):
    c, loop = server

    async def go():
        resp = await c.get("/metrics")
        return await resp.text()

    text = _run(loop, go())
    assert "rag_retries_total" in text
    assert "rag_deadline_expired_total" in text
    assert 'rag_breaker_state{dep="llm"}' in text
    assert 'rag_degraded_total{stage="rerank"}' in text
