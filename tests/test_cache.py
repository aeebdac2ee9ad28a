"""Multi-tier result cache: exact/semantic tiers, version-keyed
invalidation, serve-stale rung, admission guards, metrics export, and
factory wiring (see ``docs/caching.md``)."""

import numpy as np
import pytest

from generativeaiexamples_tpu.cache.core import (
    CacheEntry,
    RetrievalCache,
    normalize_query,
)
from generativeaiexamples_tpu.cache.log import CacheLog, cache_scope
from generativeaiexamples_tpu.cache.metrics import (
    cache_metrics_lines,
    cache_snapshot,
    record_cache_hit,
    reset_cache_metrics,
)
from generativeaiexamples_tpu.engine.embedder import HashEmbedder
from generativeaiexamples_tpu.resilience.breaker import reset_breakers
from generativeaiexamples_tpu.resilience.deadline import Deadline
from generativeaiexamples_tpu.resilience.degrade import DegradeLog
from generativeaiexamples_tpu.retrieval.base import Chunk
from generativeaiexamples_tpu.retrieval.memory import MemoryVectorStore
from generativeaiexamples_tpu.retrieval.retriever import Retriever

DIM = 32


@pytest.fixture(autouse=True)
def _clean_counters():
    reset_cache_metrics()
    reset_breakers()
    yield
    reset_cache_metrics()
    reset_breakers()


def _vec(seed: int) -> np.ndarray:
    rng = np.random.default_rng(seed)
    v = rng.normal(size=DIM).astype(np.float32)
    return v / np.linalg.norm(v)


def _hit(text: str, score: float = 1.0):
    from generativeaiexamples_tpu.retrieval.base import ScoredChunk

    return ScoredChunk(Chunk(text=text, source="s.txt"), score)


def _admit(cache, query, top_k=2, chain="rag", version=0, emb=None, hits=None):
    hits = hits if hits is not None else [_hit(f"hit for {query}")]
    return cache.admit(query, top_k, chain, version, emb, list(hits), list(hits))


class TestNormalizeQuery:
    def test_collapses_whitespace_and_case(self):
        assert normalize_query("  What   IS\tJAX? ") == "what is jax?"
        assert normalize_query("what is jax?") == "what is jax?"


class TestExactTier:
    def test_roundtrip_and_version_check(self):
        cache = RetrievalCache(DIM, semantic_enabled=False)
        entry = _admit(cache, "What is JAX?", top_k=2, version=7)
        got = cache.lookup_exact("  what IS jax? ", 2, "rag", 7)
        assert got is entry
        # Different top_k or chain is a different key.
        assert cache.lookup_exact("what is jax?", 3, "rag", 7) is None
        assert cache.lookup_exact("what is jax?", 2, "other", 7) is None
        snap = cache_snapshot()
        assert snap["hits"].get("exact") == 1
        assert snap["invalidations"] == 0

    def test_version_mismatch_invalidates_o1(self):
        cache = RetrievalCache(DIM, semantic_enabled=False)
        _admit(cache, "q one", version=1)
        _admit(cache, "q two", version=1)
        assert cache.lookup_exact("q one", 2, "rag", 2) is None
        snap = cache_snapshot()
        assert snap["invalidations"] == 1
        # Lazy per-entry eviction, not a flush: the sibling survives
        # (until its own lookup sees the mismatch).
        assert len(cache) == 1

    def test_lru_eviction_respects_capacity(self):
        cache = RetrievalCache(DIM, max_entries=2, semantic_enabled=False)
        _admit(cache, "a")
        _admit(cache, "b")
        cache.lookup_exact("a", 2, "rag", 0)  # refresh 'a'
        _admit(cache, "c")  # evicts 'b', the least recent
        assert cache.lookup_exact("b", 2, "rag", 0) is None
        assert cache.lookup_exact("a", 2, "rag", 0) is not None
        assert cache.lookup_exact("c", 2, "rag", 0) is not None
        assert len(cache) == 2


class TestSemanticTier:
    def test_similar_embedding_hits_identical_misses_distant(self):
        cache = RetrievalCache(DIM, similarity_threshold=0.9)
        v = _vec(1)
        entry = _admit(cache, "original phrasing", emb=v)
        same, distant = cache.lookup_semantic_many(
            [v, _vec(2)], "rag", 0
        )
        assert same is not None and same[0] is entry
        assert same[1] == pytest.approx(1.0, abs=1e-5)
        assert distant is None  # random 32-d vectors are nowhere near .9

    def test_chain_partitioning(self):
        cache = RetrievalCache(DIM, similarity_threshold=0.9)
        v = _vec(3)
        _admit(cache, "q", chain="rag", emb=v)
        assert cache.lookup_semantic_many([v], "other", 0) == [None]

    def test_version_mismatch_evicts_ring_slot(self):
        cache = RetrievalCache(DIM, similarity_threshold=0.9)
        v = _vec(4)
        _admit(cache, "q", version=1, emb=v)
        assert cache.lookup_semantic_many([v], "rag", 2) == [None]
        assert cache_snapshot()["invalidations"] == 1
        assert cache.stats()["ring_entries"] == 0
        # Fully gone: the exact tier dropped it too.
        assert cache.lookup_exact("q", 2, "rag", 1) is None

    def test_disabled_semantic_returns_misses(self):
        cache = RetrievalCache(DIM, semantic_enabled=False)
        v = _vec(5)
        _admit(cache, "q", emb=v)
        assert cache.lookup_semantic_many([v], "rag", 0) == [None]

    def test_ring_wraps_at_capacity(self):
        cache = RetrievalCache(
            DIM, semantic_entries=2, similarity_threshold=0.9
        )
        vs = [_vec(10 + i) for i in range(3)]
        for i, v in enumerate(vs):
            _admit(cache, f"q{i}", emb=v)
        # Slot of q0 was overwritten by q2; q1/q2 still live.
        out = cache.lookup_semantic_many(vs, "rag", 0)
        assert out[0] is None
        assert out[1] is not None and out[2] is not None
        assert cache.stats()["ring_entries"] == 2


class TestStaleLookup:
    def test_exact_match_any_top_k_deepest_wins(self):
        cache = RetrievalCache(DIM)
        shallow = _admit(cache, "q", top_k=2, version=1)
        deep = _admit(cache, "q", top_k=8, version=1)
        # Version-IGNORING by design: rung only fires when the store is
        # hard-down, where possibly-stale beats failing.
        got = cache.lookup_stale("Q", "rag")
        assert got is deep and got is not shallow

    def test_semantic_fallback_with_embedding(self):
        cache = RetrievalCache(DIM, similarity_threshold=0.9)
        v = _vec(6)
        entry = _admit(cache, "cached phrasing", emb=v)
        assert cache.lookup_stale("different words", "rag") is None
        assert cache.lookup_stale("different words", "rag", embedding=v) is entry


def _corpus(emb, store, n=8):
    texts = [f"passage number {i} about topic {i % 3}" for i in range(n)]
    store.add(
        [Chunk(text=t, source="doc.txt") for t in texts],
        emb.embed_documents(texts),
    )
    return texts


class _SpyEmbedder(HashEmbedder):
    def __init__(self):
        super().__init__(dimensions=DIM)
        self.calls = 0
        self.embedded: list[str] = []

    def embed_queries(self, texts):
        self.calls += 1
        self.embedded.extend(texts)
        return super().embed_queries(texts)


class _SpyStore(MemoryVectorStore):
    def __init__(self, dim):
        super().__init__(dim)
        self.searches = 0
        self.fail = False

    def search_batch(self, embeddings, top_k):
        if self.fail:
            raise RuntimeError("store down")
        self.searches += 1
        return super().search_batch(embeddings, top_k)


def _mk(cache=None, **kw):
    emb = _SpyEmbedder()
    store = _SpyStore(DIM)
    texts = _corpus(emb, store)
    emb.calls = 0  # ignore corpus embedding
    r = Retriever(
        store=store, embedder=emb, top_k=2, score_threshold=-1.0,
        cache=cache, **kw,
    )
    return r, emb, store, texts


class TestRetrieverIntegration:
    def test_exact_hit_is_zero_dispatch(self):
        cache = RetrievalCache(DIM)
        r, emb, store, texts = _mk(cache)
        first = r.retrieve(texts[0])
        assert (emb.calls, store.searches) == (1, 1)
        log = CacheLog()
        second = r.retrieve_many([texts[0]], cache_logs=[log])[0]
        # No embed, no search: tier 0 answered from the LRU alone.
        assert (emb.calls, store.searches) == (1, 1)
        assert [h.chunk.text for h in second] == [h.chunk.text for h in first]
        assert log.tier == "exact" and bool(log)
        snap = cache_snapshot()
        assert snap["hits"] == {"exact": 1} and snap["misses"] == 1

    def test_semantic_hit_skips_search_and_admits_exact_alias(self):
        cache = RetrievalCache(DIM, similarity_threshold=-1.0)
        r, emb, store, texts = _mk(cache)
        r.retrieve(texts[0])
        log = CacheLog()
        got = r.retrieve_many(["completely new words"], cache_logs=[log])[0]
        # Embedded (tier 1 needs the vector) but never searched.
        assert (emb.calls, store.searches) == (2, 1)
        assert log.tier == "semantic"
        assert [h.chunk.text for h in got]
        # The semantic serve aliased (query, k) into tier 0: repeating
        # the paraphrase is now a zero-dispatch exact hit.
        r.retrieve_many(["completely new words"])
        assert (emb.calls, store.searches) == (2, 1)
        snap = cache_snapshot()
        assert snap["hits"] == {"semantic": 1, "exact": 1}

    def test_semantic_hit_smaller_k_reruns_rerank(self):
        class _Rerank:
            def __init__(self):
                self.calls = 0

            def score_pairs(self, pairs):
                self.calls += 1
                return [float(len(p)) for _, p in pairs]

        rr = _Rerank()
        cache = RetrievalCache(DIM, similarity_threshold=-1.0)
        r, emb, store, texts = _mk(cache, reranker=rr)
        r.retrieve(texts[0], top_k=4)
        assert rr.calls == 1
        log = CacheLog()
        got = r.retrieve_many(
            ["paraphrase of it"], top_k=2, cache_logs=[log]
        )[0]
        # Cached ordering is never trusted across top_k with a reranker
        # active: the hit re-ran the rerank over the entry's candidates
        # — but still without a store search.
        assert rr.calls == 2
        assert store.searches == 1
        assert log.tier == "semantic" and len(got) == 2

    def test_semantic_deeper_k_is_a_miss(self):
        cache = RetrievalCache(DIM, similarity_threshold=-1.0)
        r, emb, store, texts = _mk(cache)
        r.retrieve(texts[0], top_k=2)
        r.retrieve_many(["another phrasing"], top_k=4)
        # Cached set is shallower than requested: full compute.
        assert store.searches == 2

    def test_store_mutation_invalidates_cached_result(self):
        cache = RetrievalCache(DIM)
        r, emb, store, texts = _mk(cache)
        query = "brand new doc exact words"
        r.retrieve(query)
        assert store.searches == 1
        # Every mutation path bumps version(): add() here, and the
        # server test covers the bulk-ingest path end to end.
        v0 = store.version()
        store.add(
            [Chunk(text=query, source="new.txt")],
            emb.embed_documents([query]),
        )
        assert store.version() > v0
        got = r.retrieve(query)
        assert store.searches == 2  # recomputed, not served stale
        assert got[0].chunk.text == query
        assert cache_snapshot()["invalidations"] >= 1
        # delete_source bumps too and invalidates the fresh entry.
        v1 = store.version()
        store.delete_source("new.txt")
        assert store.version() > v1
        got = r.retrieve(query)
        assert got and store.searches == 3
        assert all(h.chunk.text != query for h in got)

    def test_degraded_result_never_admitted(self):
        class _BrokenRerank:
            def score_pairs(self, pairs):
                raise RuntimeError("rerank down")

        cache = RetrievalCache(DIM)
        r, emb, store, texts = _mk(cache, reranker=_BrokenRerank())
        log = DegradeLog()
        hits = r.retrieve_many([texts[0]], degrade_logs=[log])[0]
        assert hits  # served in vector order (rerank rung)
        assert "rerank" in log.stages()
        assert len(cache) == 0  # degraded truth is never cached

    def test_expired_deadline_never_admitted(self):
        class _ExpiredLater(Deadline):
            """Plenty of budget at admission, expired by the time the
            result would be cached (a mid-flight expiry)."""

            def __init__(self):
                super().__init__(None)

            @property
            def is_unlimited(self):
                return False

            def remaining_ms(self):
                return 1e9

            def check(self, where=""):
                return None

            def expired(self):
                return True

        cache = RetrievalCache(DIM)
        r, emb, store, texts = _mk(cache)
        hits = r.retrieve_many([texts[0]], deadline=_ExpiredLater())[0]
        assert hits
        assert len(cache) == 0

    def test_fresh_deadline_still_admits(self):
        cache = RetrievalCache(DIM)
        r, emb, store, texts = _mk(cache)
        r.retrieve_many([texts[0]], deadline=Deadline.after_ms(60_000))
        assert len(cache) == 1

    def test_store_down_serves_stale_and_marks_rung(self):
        cache = RetrievalCache(DIM)
        r, emb, store, texts = _mk(cache)
        r.retrieve(texts[0], top_k=2)
        store.fail = True
        log = DegradeLog()
        clog = CacheLog()
        # Same query at a different top_k: exact key misses, the cached
        # set is shallower than requested (semantic miss) — the search
        # raises, MemoryVectorStore has no host fallback, and the
        # version-ignoring stale rung serves the old entry.
        got = r.retrieve_many(
            [texts[0]], top_k=4, degrade_logs=[log], cache_logs=[clog]
        )[0]
        assert [h.chunk.text for h in got]
        assert "cache_stale" in log.stages()
        assert clog.tier == "stale"
        assert cache_snapshot()["hits"].get("stale") == 1

    def test_store_down_no_stale_match_reraises(self):
        cache = RetrievalCache(DIM, similarity_threshold=0.9)
        r, emb, store, texts = _mk(cache)
        store.fail = True
        with pytest.raises(RuntimeError, match="store down"):
            r.retrieve("never seen before")

    def test_serve_stale_disabled_reraises(self):
        cache = RetrievalCache(DIM)
        r, emb, store, texts = _mk(cache, cache_serve_stale=False)
        r.retrieve(texts[0], top_k=2)
        store.fail = True
        with pytest.raises(RuntimeError, match="store down"):
            r.retrieve(texts[0], top_k=4)

    def test_no_cache_behaves_as_before(self):
        r, emb, store, texts = _mk(cache=None)
        r.retrieve(texts[0])
        r.retrieve(texts[0])
        assert (emb.calls, store.searches) == (2, 2)
        snap = cache_snapshot()
        assert snap["hits"] == {} and snap["misses"] == 0


class TestAnswerAttachment:
    def test_attach_and_replay_by_params_key(self):
        cache = RetrievalCache(DIM)
        entry = _admit(cache, "q")
        key = (("max_tokens", 256), ("temperature", 0.2))
        assert entry.get_answer(key) is None
        cache.attach_answer(entry, key, "the answer")
        assert entry.get_answer(key) == "the answer"
        assert entry.get_answer((("temperature", 0.7),)) is None

    def test_cache_log_scope_and_note_entry(self):
        from generativeaiexamples_tpu.cache.log import current_cache_log

        assert current_cache_log() is None
        with cache_scope() as log:
            assert current_cache_log() is log
            entry = CacheEntry("q", 2, "rag", 0, None, [], [])
            log.note_entry(entry)
            assert log.entry is entry and not log  # noted, NOT a hit
            log.mark_hit("exact", entry)
            assert log.tier == "exact" and bool(log)
            log.mark_answer()
            assert log.answer_hit
        assert current_cache_log() is None


class TestMetricsExport:
    @pytest.fixture(autouse=True)
    def _as_at_process_start(self):
        # "From zero" is the state of a new process: drop the cache
        # singleton and counters an earlier test file left in this worker.
        from generativeaiexamples_tpu.chains.factory import reset_factories

        reset_factories()

    def test_all_series_export_from_zero(self):
        text = "\n".join(cache_metrics_lines())
        assert 'rag_cache_hits_total{tier="exact"} 0' in text
        assert 'rag_cache_hits_total{tier="semantic"} 0' in text
        assert "rag_cache_misses_total 0" in text
        assert "rag_cache_entries 0" in text
        assert "rag_cache_invalidations_total 0" in text

    def test_dynamic_tier_appears_when_recorded(self):
        record_cache_hit("stale")
        text = "\n".join(cache_metrics_lines())
        assert 'rag_cache_hits_total{tier="stale"} 1' in text
        reset_cache_metrics()
        assert 'tier="stale"' not in "\n".join(cache_metrics_lines())


class TestFactoryWiring:
    def test_singleton_and_reset(self, monkeypatch):
        from generativeaiexamples_tpu.chains.factory import (
            get_retrieval_cache,
            peek_retrieval_cache,
            reset_factories,
        )
        from generativeaiexamples_tpu.core.configuration import (
            reset_config_cache,
        )

        monkeypatch.setenv("APP_CACHE_MAXENTRIES", "33")
        reset_config_cache()
        reset_factories()
        try:
            assert peek_retrieval_cache() is None
            cache = get_retrieval_cache()
            assert cache is not None and cache.max_entries == 33
            assert get_retrieval_cache() is cache
            assert peek_retrieval_cache() is cache
            reset_factories()
            assert peek_retrieval_cache() is None
        finally:
            monkeypatch.delenv("APP_CACHE_MAXENTRIES", raising=False)
            reset_config_cache()
            reset_factories()

    def test_disabled_by_config(self, monkeypatch):
        from generativeaiexamples_tpu.chains.factory import (
            get_retrieval_cache,
            reset_factories,
        )
        from generativeaiexamples_tpu.core.configuration import (
            reset_config_cache,
        )

        monkeypatch.setenv("APP_CACHE_ENABLED", "false")
        reset_config_cache()
        reset_factories()
        try:
            assert get_retrieval_cache() is None
        finally:
            monkeypatch.delenv("APP_CACHE_ENABLED", raising=False)
            reset_config_cache()
            reset_factories()
