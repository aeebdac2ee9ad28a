"""Retrieval layer tests: all backends against the same contract, plus the
embedder + retriever policy stack."""

import numpy as np
import pytest

from generativeaiexamples_tpu.engine.embedder import HashEmbedder, TPUEmbedder
from generativeaiexamples_tpu.models import bert
from generativeaiexamples_tpu.retrieval.base import Chunk
from generativeaiexamples_tpu.retrieval.memory import MemoryVectorStore
from generativeaiexamples_tpu.retrieval.native import NativeVectorStore
from generativeaiexamples_tpu.retrieval.retriever import Retriever
from generativeaiexamples_tpu.retrieval.tpu import (
    TPUIVFVectorStore,
    TPUVectorStore,
)

DIM = 32


def _mk_store(kind: str):
    if kind == "memory":
        return MemoryVectorStore(DIM)
    if kind == "tpu":
        return TPUVectorStore(DIM, dtype="float32")
    if kind == "tpu-ivf":
        # Tiny corpora sit in the exact-fallback regime; the IVF path has
        # its own dedicated tests below.
        return TPUIVFVectorStore(DIM, dtype="float32")
    if kind == "native":
        return NativeVectorStore(DIM)
    raise ValueError(kind)


def _unit(v):
    v = np.asarray(v, dtype=np.float32)
    return (v / np.linalg.norm(v)).tolist()


def _basis(i: int):
    v = np.zeros(DIM, dtype=np.float32)
    v[i % DIM] = 1.0
    return v.tolist()


STORE_KINDS = ["memory", "tpu", "tpu-ivf", "native"]


@pytest.mark.parametrize("kind", STORE_KINDS)
class TestVectorStoreContract:
    def test_add_search_roundtrip(self, kind):
        store = _mk_store(kind)
        chunks = [Chunk(text=f"chunk {i}", source=f"doc{i % 2}.txt") for i in range(8)]
        store.add(chunks, [_basis(i) for i in range(8)])
        assert len(store) == 8
        hits = store.search(_basis(3), top_k=2)
        assert hits[0].chunk.text == "chunk 3"
        assert hits[0].score == pytest.approx(1.0, abs=1e-2)
        assert hits[1].score < 0.5

    def test_top_k_ordering(self, kind):
        store = _mk_store(kind)
        base = np.random.default_rng(0).standard_normal(DIM)
        vecs = []
        for i in range(6):
            noise = np.random.default_rng(i + 1).standard_normal(DIM)
            vecs.append(_unit(base + noise * (0.1 * i)))
        store.add([Chunk(text=f"c{i}", source="s") for i in range(6)], vecs)
        hits = store.search(_unit(base), top_k=6)
        scores = [h.score for h in hits]
        assert scores == sorted(scores, reverse=True)
        assert hits[0].chunk.text == "c0"

    def test_sources_and_delete(self, kind):
        store = _mk_store(kind)
        chunks = [
            Chunk(text="a", source="a.pdf"),
            Chunk(text="b", source="b.pdf"),
            Chunk(text="b2", source="b.pdf"),
        ]
        store.add(chunks, [_basis(0), _basis(1), _basis(2)])
        assert sorted(store.sources()) == ["a.pdf", "b.pdf"]
        removed = store.delete_source("b.pdf")
        assert removed == 2
        assert len(store) == 1
        assert store.sources() == ["a.pdf"]
        hits = store.search(_basis(1), top_k=3)
        assert all(h.chunk.source != "b.pdf" for h in hits)

    def test_search_empty(self, kind):
        store = _mk_store(kind)
        assert store.search(_basis(0), top_k=4) == []

    def test_add_after_delete(self, kind):
        store = _mk_store(kind)
        store.add([Chunk(text="x", source="x")], [_basis(0)])
        store.delete_source("x")
        store.add([Chunk(text="y", source="y")], [_basis(1)])
        hits = store.search(_basis(1), top_k=2)
        assert [h.chunk.text for h in hits] == ["y"]


@pytest.mark.parametrize("kind", ["tpu", "native"])
def test_backends_match_memory_reference(kind):
    """Exact backends must return identical results to the numpy reference."""
    rng = np.random.default_rng(42)
    vecs = rng.standard_normal((50, DIM)).astype(np.float32)
    vecs /= np.linalg.norm(vecs, axis=1, keepdims=True)
    chunks = [Chunk(text=f"t{i}", source=f"s{i % 5}") for i in range(50)]

    ref = MemoryVectorStore(DIM)
    ref.add(chunks, vecs.tolist())
    other = _mk_store(kind)
    other.add(chunks, vecs.tolist())

    for qi in range(5):
        q = _unit(rng.standard_normal(DIM))
        ref_hits = ref.search(q, 5)
        got_hits = other.search(q, 5)
        assert [h.chunk.text for h in got_hits] == [h.chunk.text for h in ref_hits]
        np.testing.assert_allclose(
            [h.score for h in got_hits],
            [h.score for h in ref_hits],
            rtol=2e-2, atol=1e-3,
        )


def test_native_ivf_recall():
    """IVF with reference defaults (nlist=64, nprobe=16) on clustered data
    must reach high recall@10 vs exact search."""
    rng = np.random.default_rng(7)
    centers = rng.standard_normal((16, DIM)).astype(np.float32) * 3
    vecs = []
    for i in range(3000):
        c = centers[i % 16]
        v = c + rng.standard_normal(DIM).astype(np.float32) * 0.3
        vecs.append((v / np.linalg.norm(v)).tolist())
    chunks = [Chunk(text=f"t{i}", source="s") for i in range(3000)]

    exact = NativeVectorStore(DIM, index_type="exact")
    exact.add(chunks, vecs)
    ivf = NativeVectorStore(DIM, index_type="ivf", nlist=64, nprobe=16,
                            ivf_build_threshold=1000)
    ivf.add(chunks, vecs)

    recalls = []
    for qi in range(20):
        q = vecs[rng.integers(0, 3000)]
        truth = {h.chunk.text for h in exact.search(q, 10)}
        got = {h.chunk.text for h in ivf.search(q, 10)}
        recalls.append(len(truth & got) / 10)
    assert np.mean(recalls) >= 0.9, f"IVF recall too low: {np.mean(recalls)}"


def _clustered(n, n_centers=16, seed=7):
    rng = np.random.default_rng(seed)
    centers = rng.standard_normal((n_centers, DIM)).astype(np.float32) * 3
    vecs = []
    for i in range(n):
        v = centers[i % n_centers] + rng.standard_normal(DIM).astype(
            np.float32
        ) * 0.3
        vecs.append((v / np.linalg.norm(v)).tolist())
    return vecs, rng


def test_tpu_ivf_recall():
    """TPU IVF with the reference defaults (nlist=64, nprobe=16) on
    clustered data must reach high recall@10 vs exact search."""
    vecs, rng = _clustered(3000)
    chunks = [Chunk(text=f"t{i}", source="s") for i in range(3000)]
    exact = TPUVectorStore(DIM, dtype="float32")
    exact.add(chunks, vecs)
    ivf = TPUIVFVectorStore(
        DIM, dtype="float32", nlist=64, nprobe=16, min_train_size=1000
    )
    ivf.add(chunks, vecs)
    recalls = []
    for _ in range(20):
        q = vecs[rng.integers(0, 3000)]
        truth = {h.chunk.text for h in exact.search(q, 10)}
        got = {h.chunk.text for h in ivf.search(q, 10)}
        recalls.append(len(truth & got) / 10)
    assert np.mean(recalls) >= 0.9, f"IVF recall too low: {np.mean(recalls)}"


def test_search_batch_matches_per_query():
    """One-dispatch batched search must return exactly the per-query
    results, for the exact store, the IVF store, and the IVF store's
    exact-fallback (sub-min_train_size) regime."""
    vecs, rng = _clustered(1200)
    chunks = [Chunk(text=f"t{i}", source="s") for i in range(1200)]
    queries = [vecs[rng.integers(0, 1200)] for _ in range(7)]

    exact = TPUVectorStore(DIM, dtype="float32")
    exact.add(chunks, vecs)
    ivf = TPUIVFVectorStore(
        DIM, dtype="float32", nlist=16, nprobe=4, min_train_size=500
    )
    ivf.add(chunks, vecs)
    tiny = TPUIVFVectorStore(DIM, dtype="float32", min_train_size=5000)
    tiny.add(chunks[:100], vecs[:100])

    for store in (exact, ivf, tiny):
        single = [
            [(h.chunk.text, round(h.score, 5)) for h in store.search(q, 10)]
            for q in queries
        ]
        batched = [
            [(h.chunk.text, round(h.score, 5)) for h in hits]
            for hits in store.search_batch(queries, 10)
        ]
        assert batched == single
    assert exact.search_batch([], 10) == []


def test_search_batch_buckets_query_batch_one_compile():
    """Varying batch sizes within one power-of-two bucket must share ONE
    compiled program (ragged sizes each paid a full XLA compile before;
    padded rows are masked host-side by collecting only real rows)."""
    vecs, rng = _clustered(1200)
    chunks = [Chunk(text=f"t{i}", source="s") for i in range(1200)]
    queries = [vecs[rng.integers(0, 1200)] for _ in range(8)]

    exact = TPUVectorStore(DIM, dtype="float32")
    exact.add(chunks, vecs)
    ivf = TPUIVFVectorStore(
        DIM, dtype="float32", nlist=16, nprobe=4, min_train_size=500
    )
    ivf.add(chunks, vecs)
    for store, fn in (
        (exact, lambda: exact._search_batch_fn),
        (ivf, lambda: ivf._ivf_search_batch_fn),
    ):
        per_query = [
            [(h.chunk.text, round(h.score, 5)) for h in store.search(q, 5)]
            for q in queries
        ]
        for n in (5, 6, 7, 8):
            batched = [
                [(h.chunk.text, round(h.score, 5)) for h in hits]
                for hits in store.search_batch(queries[:n], 5)
            ]
            assert batched == per_query[:n], n
        # 5..8 all pad to the 8-row bucket: one executable.
        assert fn()._cache_size() == 1


def test_tpu_ivf_probe_all_lists_is_exact():
    """nprobe == nlist scores every bucket: results must equal the exact
    store's, by construction."""
    vecs, rng = _clustered(600)
    chunks = [Chunk(text=f"t{i}", source="s") for i in range(600)]
    exact = TPUVectorStore(DIM, dtype="float32")
    exact.add(chunks, vecs)
    ivf = TPUIVFVectorStore(
        DIM, dtype="float32", nlist=8, nprobe=8, min_train_size=100
    )
    ivf.add(chunks, vecs)
    for _ in range(5):
        q = _unit(rng.standard_normal(DIM))
        want = [h.chunk.text for h in exact.search(q, 8)]
        got = [h.chunk.text for h in ivf.search(q, 8)]
        assert got == want


def test_tpu_ivf_masked_delete_and_regrow():
    vecs, _ = _clustered(400)
    chunks = [
        Chunk(text=f"t{i}", source="evict" if i % 4 == 0 else "keep")
        for i in range(400)
    ]
    ivf = TPUIVFVectorStore(
        DIM, dtype="float32", nlist=8, nprobe=8, min_train_size=100
    )
    ivf.add(chunks, vecs)
    assert ivf.search(vecs[0], 5)  # build the index
    removed = ivf.delete_source("evict")
    assert removed == 100 and len(ivf) == 300
    hits = ivf.search(vecs[0], 20)
    assert hits and all(h.chunk.source == "keep" for h in hits)
    # Adds after delete re-sync and stay searchable.
    ivf.add([Chunk(text="new", source="keep")], [vecs[0]])
    hits = ivf.search(vecs[0], 3)
    assert any(h.chunk.text == "new" for h in hits)


def test_tpu_ivf_index_rebuilds_from_live_rows_only():
    """After a large delete, the index must cluster the SURVIVING corpus:
    dead rows may not occupy bucket slots (they'd crowd out live
    candidates and waste probe traffic)."""
    vecs, _ = _clustered(600)
    chunks = [
        Chunk(text=f"t{i}", source="dead" if i < 400 else "live")
        for i in range(600)
    ]
    ivf = TPUIVFVectorStore(
        DIM, dtype="float32", nlist=8, nprobe=4, min_train_size=100
    )
    ivf.add(chunks, vecs)
    ivf.delete_source("dead")
    hits = ivf.search(vecs[500], 5)
    assert hits and hits[0].chunk.text == "t500"
    # Every bucket slot holds a live row: total valid slots == live corpus.
    assert int(np.asarray(ivf._bucket_valid).sum()) == 200


def test_tpu_ivf_sharded_over_mesh():
    import jax
    from generativeaiexamples_tpu.parallel.mesh import MeshSpec, make_mesh

    mesh = make_mesh(MeshSpec(data=4), devices=jax.devices()[:4])
    vecs, rng = _clustered(600)
    chunks = [Chunk(text=f"t{i}", source="s") for i in range(600)]
    ivf = TPUIVFVectorStore(
        DIM, dtype="float32", nlist=8, nprobe=8, min_train_size=100,
        mesh=mesh,
    )
    ivf.add(chunks, vecs)
    exact = TPUVectorStore(DIM, dtype="float32")
    exact.add(chunks, vecs)
    for _ in range(3):
        q = _unit(rng.standard_normal(DIM))
        assert [h.chunk.text for h in ivf.search(q, 5)] == [
            h.chunk.text for h in exact.search(q, 5)
        ]


def test_tpu_ivf_skewed_clusters_bounded_memory():
    """A dominant cluster must not inflate the shared bucket capacity:
    total slots stay <= ~4x the corpus (overflow rows spill to their
    next-nearest list and remain retrievable)."""
    rng = np.random.default_rng(3)
    # 90% of rows in ONE tight cluster, the rest spread.
    tight = rng.standard_normal(DIM).astype(np.float32) * 3
    vecs = []
    for i in range(1000):
        base = tight if i < 900 else rng.standard_normal(DIM) * 3
        v = base + rng.standard_normal(DIM).astype(np.float32) * 0.1
        vecs.append((v / np.linalg.norm(v)).tolist())
    chunks = [Chunk(text=f"t{i}", source="s") for i in range(1000)]
    ivf = TPUIVFVectorStore(
        DIM, dtype="float32", nlist=16, nprobe=16, min_train_size=100
    )
    ivf.add(chunks, vecs)
    assert ivf.search(vecs[0], 1)  # build
    nlist, cap, _ = ivf._buckets.shape
    assert nlist * cap <= 8 * 1000  # 4x target, pow2-rounded headroom
    # Overflowed rows are still found (nprobe == nlist scores every list).
    for probe_row in (5, 450, 899, 950):
        hits = ivf.search(vecs[probe_row], 1)
        assert hits[0].chunk.text == f"t{probe_row}"


def test_tpu_store_grows_capacity():
    store = TPUVectorStore(DIM, dtype="float32")
    rng = np.random.default_rng(0)
    n = 1500  # crosses the 1024 capacity bucket
    vecs = rng.standard_normal((n, DIM)).astype(np.float32)
    vecs /= np.linalg.norm(vecs, axis=1, keepdims=True)
    store.add([Chunk(text=f"t{i}", source="s") for i in range(n)], vecs.tolist())
    hits = store.search(vecs[1234].tolist(), 1)
    assert hits[0].chunk.text == "t1234"


class TestEmbedders:
    def test_hash_embedder_deterministic(self):
        e = HashEmbedder(dimensions=64)
        a = e.embed_query("hello")
        b = e.embed_query("hello")
        c = e.embed_query("goodbye")
        assert a == b
        assert np.abs(np.dot(a, c)) < 0.5
        assert np.linalg.norm(a) == pytest.approx(1.0, abs=1e-6)

    def test_tpu_embedder_shapes_and_norm(self):
        cfg = bert.bert_tiny(dtype="float32")
        e = TPUEmbedder(cfg, batch_size=4, max_length=64)
        vecs = e.embed_documents(["short", "a slightly longer document text"])
        assert len(vecs) == 2
        assert len(vecs[0]) == cfg.d_model
        assert np.linalg.norm(vecs[0]) == pytest.approx(1.0, abs=1e-3)

    def test_tpu_embedder_batch_padding_invariance(self):
        """A text's embedding must not depend on its batch neighbors."""
        cfg = bert.bert_tiny(dtype="float32")
        e = TPUEmbedder(cfg, batch_size=4, max_length=64)
        solo = np.asarray(e.embed_documents(["the target text"])[0])
        batched = np.asarray(
            e.embed_documents(
                ["the target text", "other a", "other b", "other c", "overflow e"]
            )[0]
        )
        np.testing.assert_allclose(solo, batched, rtol=1e-4, atol=1e-5)

    def test_batch_bucketing_parity_with_fixed_batch(self):
        """Round-9 satellite: pow2 batch buckets must return the same
        embeddings as the old fixed-batch padding, while small calls use
        small programs (a 1-doc call compiles a floor-sized forward, not
        the full batch)."""
        import jax

        cfg = bert.bert_tiny(dtype="float32")
        params = bert.init_params(cfg, jax.random.PRNGKey(3))
        bucketed = TPUEmbedder(cfg, params, batch_size=8, max_length=64)
        fixed = TPUEmbedder(cfg, params, batch_size=8, max_length=64,
                            bucket_batch=False)
        texts = [f"passage number {i} with words" for i in range(5)]
        np.testing.assert_allclose(
            np.asarray(bucketed.embed_documents(texts)),
            np.asarray(fixed.embed_documents(texts)),
            rtol=1e-4, atol=1e-5,
        )
        # One doc -> the 4-bucket program; 5 docs -> the 8 bucket: two
        # distinct compiles prove small calls stopped paying batch-8.
        bucketed.embed_documents(["solo"])
        assert bucketed._embed._cache_size() == 2
        assert fixed._embed._cache_size() == 1

    def test_query_prefix_applied(self):
        cfg = bert.bert_tiny(dtype="float32")
        e = TPUEmbedder(cfg, batch_size=2, max_length=64)
        q = np.asarray(e.embed_query("hello"))
        d = np.asarray(e.embed_documents(["hello"])[0])
        assert not np.allclose(q, d)  # prefix must change the encoding


class TestRetriever:
    def test_threshold_and_context_budget(self):
        emb = HashEmbedder(dimensions=DIM)
        store = MemoryVectorStore(DIM)
        texts = ["alpha beta", "gamma delta", "epsilon zeta"]
        chunks = [Chunk(text=t, source="doc") for t in texts]
        store.add(chunks, emb.embed_documents(texts))
        r = Retriever(store=store, embedder=emb, top_k=3, score_threshold=0.99,
                      max_context_tokens=2)
        # hash embeddings: only the exact same text scores ~1.0...
        hits = r.retrieve("alpha beta")
        # embed_query on HashEmbedder has no prefix, so exact match scores 1.
        assert [h.chunk.text for h in hits] == ["alpha beta"]
        ctx = r.build_context(hits)
        assert len(ctx) <= 8  # 2 tokens * 4 chars


class TestReranker:
    def test_score_shapes_determinism_and_rerank_order(self):
        from generativeaiexamples_tpu.engine.reranker import TPUReranker
        from generativeaiexamples_tpu.models import bert

        rr = TPUReranker(bert.bert_tiny(), batch_size=4, max_length=64)
        passages = ["alpha beta", "gamma delta", "epsilon zeta", "eta theta"]
        s1 = rr.score("alpha?", passages)
        s2 = rr.score("alpha?", passages)
        assert len(s1) == 4
        assert s1 == s2  # deterministic
        ranked = rr.rerank("alpha?", passages, top_k=2)
        assert len(ranked) == 2
        # best-first and consistent with score()
        assert ranked[0][1] >= ranked[1][1]
        assert ranked[0][1] == max(s1)

    def test_batch_split_invariance(self):
        """Scores must not depend on how passages split into jit batches."""
        from generativeaiexamples_tpu.engine.reranker import TPUReranker
        from generativeaiexamples_tpu.models import bert

        cfg = bert.bert_tiny()
        import jax

        params = bert.init_params(cfg, jax.random.PRNGKey(1))
        head = bert.init_rerank_head(cfg, jax.random.PRNGKey(2))
        wide = TPUReranker(cfg, params, head, batch_size=8, max_length=64)
        narrow = TPUReranker(cfg, params, head, batch_size=2, max_length=64)
        passages = [f"passage number {i}" for i in range(5)]
        a = wide.score("a query", passages)
        b = narrow.score("a query", passages)
        assert all(abs(x - y) < 1e-3 for x, y in zip(a, b))


class TestAutoBackendSelection:
    """``auto`` picks the platform's fastest adaptive store with the
    measured exact-vs-IVF crossover (the reference
    hardwires Milvus GPU_IVF_FLAT, ``common/utils.py:198-203``)."""

    def _auto_store(self, monkeypatch, dim=64, extra_env=()):
        from generativeaiexamples_tpu.core.configuration import (
            reset_config_cache,
        )
        from generativeaiexamples_tpu.retrieval.factory import (
            get_vector_store,
        )

        monkeypatch.setenv("APP_VECTORSTORE_NAME", "auto")
        monkeypatch.setenv("APP_EMBEDDINGS_DIMENSIONS", str(dim))
        monkeypatch.delenv("GAIE_RETRIEVAL_CROSSOVER", raising=False)
        for k, v in extra_env:
            monkeypatch.setenv(k, v)
        reset_config_cache()
        try:
            return get_vector_store()
        finally:
            reset_config_cache()

    def test_cpu_selects_native_adaptive_ivf(self, monkeypatch):
        store = self._auto_store(monkeypatch)
        assert store.__class__.__name__ == "NativeVectorStore"
        assert store.index_type == "ivf"
        # narrow-dim CPU crossover from the measured table.
        assert store.ivf_build_threshold == 6_000

    def test_wide_dim_raises_crossover(self, monkeypatch):
        store = self._auto_store(monkeypatch, dim=1024)
        assert store.ivf_build_threshold == 16_000

    def test_env_override_pins_measured_value(self, monkeypatch):
        store = self._auto_store(
            monkeypatch, extra_env=[("GAIE_RETRIEVAL_CROSSOVER", "123000")]
        )
        assert store.ivf_build_threshold == 123_000

    def test_tpu_platform_selects_tpu_ivf(self, monkeypatch):
        from generativeaiexamples_tpu.retrieval import factory
        from generativeaiexamples_tpu.retrieval.tpu import TPUIVFVectorStore

        monkeypatch.setattr(factory, "_platform", lambda: "tpu")
        store = self._auto_store(monkeypatch, dim=1024)
        assert isinstance(store, TPUIVFVectorStore)
        # Hardware-measured policy: batched exact MXU search is flat
        # ~7 ms/query through 1M rows (recall 1.0), so the adaptive
        # store stays exact until the extrapolated ~4M break-even.
        assert store.min_train_size == 4_000_000

    def test_platform_is_the_live_backend(self):
        """_platform reports JAX's live backend (cpu here) and nothing
        inferred from the environment."""
        from generativeaiexamples_tpu.retrieval import factory

        assert factory._platform() == "cpu"

    def test_auto_store_roundtrip_small_corpus(self, monkeypatch):
        """Below the crossover the adaptive store serves exact search."""
        from generativeaiexamples_tpu.retrieval.base import Chunk

        store = self._auto_store(monkeypatch, dim=8)
        rng = np.random.default_rng(0)
        vecs = rng.standard_normal((32, 8)).astype(np.float32)
        store.add(
            [Chunk(text=f"c{i}", source="s") for i in range(32)], vecs
        )
        hits = store.search(vecs[7], top_k=3)
        assert hits and hits[0].chunk.text == "c7"


class TestBatchedRetrieval:
    """Round-8 micro-batched hot path: retrieve_many / score_pairs /
    bounded query-batch compile cache."""

    def _corpus(self, emb, store, n=12):
        texts = [f"passage number {i} about topic {i % 3}" for i in range(n)]
        chunks = [Chunk(text=t, source=f"doc{i % 2}.txt") for i, t in enumerate(texts)]
        store.add(chunks, emb.embed_documents(texts))
        return texts

    def test_retrieve_many_matches_per_query(self):
        emb = HashEmbedder(dimensions=DIM)
        store = MemoryVectorStore(DIM)
        texts = self._corpus(emb, store)
        r = Retriever(store=store, embedder=emb, top_k=3, score_threshold=-1.0)
        queries = [texts[0], texts[5], "unrelated question"]
        batched = r.retrieve_many(queries)
        single = [r.retrieve(q) for q in queries]
        assert [
            [(h.chunk.text, round(h.score, 6)) for h in hits]
            for hits in batched
        ] == [
            [(h.chunk.text, round(h.score, 6)) for h in hits]
            for hits in single
        ]
        assert r.retrieve_many([]) == []
        assert r.retrieve_many(queries, top_k=0) == [[], [], []]

    def test_retrieve_many_with_reranker_matches_per_query(self):
        from generativeaiexamples_tpu.engine.reranker import TPUReranker
        from generativeaiexamples_tpu.models import bert

        emb = HashEmbedder(dimensions=DIM)
        store = MemoryVectorStore(DIM)
        texts = self._corpus(emb, store)
        rr = TPUReranker(bert.bert_tiny(), batch_size=4, max_length=64)
        r = Retriever(
            store=store, embedder=emb, top_k=2, score_threshold=-1.0,
            reranker=rr, fetch_k_multiplier=3,
        )
        queries = [texts[1], texts[4]]
        batched = r.retrieve_many(queries)
        single = [r.retrieve(q) for q in queries]
        for b_hits, s_hits in zip(batched, single):
            assert [h.chunk.text for h in b_hits] == [
                h.chunk.text for h in s_hits
            ]
            assert all(
                abs(a.score - b.score) < 1e-3
                for a, b in zip(b_hits, s_hits)
            )

    def test_fetch_k_multiplier_configurable(self):
        """The over-fetch multiplier (hardwired 4x before) follows the
        constructor arg; without a reranker no over-fetch happens."""

        class SpyStore(MemoryVectorStore):
            def __init__(self, dim):
                super().__init__(dim)
                self.requested_k: list[int] = []

            def search_batch(self, embeddings, top_k):
                self.requested_k.append(top_k)
                return super().search_batch(embeddings, top_k)

        class FakeReranker:
            def score_pairs(self, pairs):
                return [float(len(p)) for _, p in pairs]

        emb = HashEmbedder(dimensions=DIM)
        store = SpyStore(DIM)
        self._corpus(emb, store)
        r = Retriever(
            store=store, embedder=emb, top_k=2, score_threshold=-1.0,
            reranker=FakeReranker(), fetch_k_multiplier=5,
        )
        r.retrieve("a query")
        assert store.requested_k[-1] == 10  # top_k 2 * multiplier 5
        r_plain = Retriever(
            store=store, embedder=emb, top_k=2, score_threshold=-1.0,
            fetch_k_multiplier=5,
        )
        r_plain.retrieve("a query")
        assert store.requested_k[-1] == 2  # no reranker -> no over-fetch
        # Default stays the historical 4x.
        assert Retriever(store=store, embedder=emb).fetch_k_multiplier == 4

    def test_score_pairs_matches_score_across_queries(self):
        """Cross-request pair scoring must agree with per-query score():
        the batched rerank stage cannot change rankings."""
        from generativeaiexamples_tpu.engine.reranker import TPUReranker
        from generativeaiexamples_tpu.models import bert

        rr = TPUReranker(bert.bert_tiny(), batch_size=4, max_length=64)
        qa, qb = "first question", "second different question"
        pa = [f"passage {i}" for i in range(3)]
        pb = [f"other text {i}" for i in range(2)]
        flat = rr.score_pairs(
            [(qa, p) for p in pa] + [(qb, p) for p in pb]
        )
        ref = rr.score(qa, pa) + rr.score(qb, pb)
        assert len(flat) == 5
        assert all(abs(x - y) < 1e-3 for x, y in zip(flat, ref))
        assert rr.score_pairs([]) == []

    def test_tpu_store_query_batch_cap_bounds_compiles(self):
        """Query batches beyond max_query_batch chunk into the capped
        bucket set: results stay exact and the batched-search program
        cache stays a small fixed set under any burst size."""
        vecs, rng = _clustered(600)
        chunks = [Chunk(text=f"t{i}", source="s") for i in range(600)]
        store = TPUVectorStore(DIM, dtype="float32", max_query_batch=8)
        store.add(chunks, vecs)
        queries = [vecs[rng.integers(0, 600)] for _ in range(21)]
        single = [
            [(h.chunk.text, round(h.score, 5)) for h in store.search(q, 5)]
            for q in queries
        ]
        batched = [
            [(h.chunk.text, round(h.score, 5)) for h in hits]
            for hits in store.search_batch(queries, 5)
        ]
        assert batched == single
        # 21 queries at cap 8 -> chunks of 8/8/5, buckets {8} only; a
        # 64-query burst adds nothing new.
        store.search_batch([vecs[i] for i in range(64)], 5)
        assert store._search_batch_fn._cache_size() <= 2

    def test_tpu_ivf_query_chunk_respects_cap(self):
        vecs, rng = _clustered(1200)
        chunks = [Chunk(text=f"t{i}", source="s") for i in range(1200)]
        ivf = TPUIVFVectorStore(
            DIM, dtype="float32", nlist=16, nprobe=16, min_train_size=500,
            max_query_batch=4,
        )
        ivf.add(chunks, vecs)
        queries = [vecs[rng.integers(0, 1200)] for _ in range(10)]
        single = [
            [(h.chunk.text, round(h.score, 5)) for h in ivf.search(q, 5)]
            for q in queries
        ]
        batched = [
            [(h.chunk.text, round(h.score, 5)) for h in hits]
            for hits in ivf.search_batch(queries, 5)
        ]
        assert batched == single

    def test_retrieve_many_uses_embed_queries_once(self):
        """The batched path embeds the whole query list in one
        embed_queries call (no per-query fallback loop when the batched
        surface exists)."""

        class SpyEmbedder(HashEmbedder):
            def __init__(self):
                super().__init__(dimensions=DIM)
                self.batched_calls = 0
                self.single_calls = 0

            def embed_queries(self, texts):
                self.batched_calls += 1
                return super().embed_queries(texts)

            def embed_query(self, text):
                self.single_calls += 1
                return super().embed_query(text)

        emb = SpyEmbedder()
        store = MemoryVectorStore(DIM)
        self._corpus(emb, store)
        r = Retriever(store=store, embedder=emb, top_k=2, score_threshold=-1.0)
        r.retrieve_many(["q one", "q two", "q three"])
        assert emb.batched_calls == 1
        assert emb.single_calls == 0

    def test_concurrent_clients_through_the_batcher_get_their_own_hits(self):
        """The chain layer's shape: clients call a ``MicroBatcher`` over
        ``retrieve_many`` on the device store.  Every client gets the hits
        a lone ``retrieve`` returns for its query, none comes back empty,
        and the sixteen requests share fewer dispatches than requests."""
        import threading

        from generativeaiexamples_tpu.engine.microbatch import MicroBatcher

        emb = HashEmbedder(dimensions=DIM)
        store = TPUVectorStore(DIM, dtype="float32", max_query_batch=16)
        texts = self._corpus(emb, store, n=48)
        r = Retriever(store=store, embedder=emb, top_k=3, score_threshold=-1.0)
        queries = texts[:16]
        alone = [[h.chunk.text for h in r.retrieve(q)] for q in queries]
        batcher = MicroBatcher(
            lambda qs: r.retrieve_many(qs, top_k=3),
            max_batch=16,
            max_wait_ms=200.0,
        )
        got: dict = {}

        def client(i):
            got[i] = batcher.call(queries[i])

        try:
            threads = [
                threading.Thread(target=client, args=(i,)) for i in range(16)
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=60)
            snap = batcher.stats.snapshot()
        finally:
            batcher.close()
        assert all(alone) and [
            [h.chunk.text for h in got[i]] for i in range(16)
        ] == alone
        assert snap["requests_total"] == 16 and snap["batches_total"] < 16

    def test_tpu_embedder_embed_queries_matches_embed_query(self):
        emb = TPUEmbedder(bert.bert_tiny(), batch_size=4)
        texts = ["alpha", "beta gamma", "delta epsilon zeta", "eta", "theta"]
        batched = np.asarray(emb.embed_queries(texts))
        single = np.asarray([emb.embed_query(t) for t in texts])
        assert batched.shape == single.shape
        np.testing.assert_allclose(batched, single, atol=1e-4)
        assert emb.embed_queries([]) == []


class TestIncrementalSync:
    """Round-9: O(new-rows) device sync — appends land in the tail
    staging buffer (jitted dynamic_update_slice), deletes re-upload only
    the masks, and results stay bit-identical to a full rebuild."""

    def _mk_pair(self):
        inc = TPUVectorStore(DIM, dtype="float32")
        full = TPUVectorStore(DIM, dtype="float32", incremental=False)
        return inc, full

    @staticmethod
    def _results(store, queries, k=10):
        # Single- and batched-query einsums lower differently on CPU XLA
        # (~1e-7 score jitter, same precedent as
        # test_search_batch_matches_per_query): ordering must be exact,
        # scores compare within tolerance.
        single = [
            [(h.chunk.text, h.score) for h in store.search(q, k)]
            for q in queries
        ]
        batched = [
            [(h.chunk.text, h.score) for h in hits]
            for hits in store.search_batch(queries, k)
        ]
        assert [[t for t, _ in hits] for hits in batched] == [
            [t for t, _ in hits] for hits in single
        ]
        np.testing.assert_allclose(
            [s for hits in batched for _, s in hits],
            [s for hits in single for _, s in hits],
            atol=2e-5,
        )
        return single

    def test_incremental_equals_full_rebuild_bitwise(self):
        """After interleaved adds/deletes, incremental-sync results are
        identical (ordering exact, scores to float32 display precision)
        to a from-scratch rebuild."""
        vecs, rng = _clustered(360)
        inc, full = self._mk_pair()
        queries = [vecs[rng.integers(0, 360)] for _ in range(4)]

        def both(fn):
            fn(inc), fn(full)

        def compare():
            a, b = self._results(inc, queries), self._results(full, queries)
            assert [[t for t, _ in hits] for hits in a] == [
                [t for t, _ in hits] for hits in b
            ]
            np.testing.assert_allclose(
                [s for hits in a for _, s in hits],
                [s for hits in b for _, s in hits],
                atol=2e-5,
            )

        both(lambda s: s.add(
            [Chunk(text=f"a{i}", source="a") for i in range(300)],
            vecs[:300],
        ))
        compare()
        # Appends after the first sync ride the tail, not a rebuild.
        both(lambda s: s.add(
            [Chunk(text=f"b{i}", source="b") for i in range(40)],
            vecs[300:340],
        ))
        compare()
        both(lambda s: s.delete_source("a"))
        compare()
        both(lambda s: s.add(
            [Chunk(text=f"c{i}", source="c") for i in range(20)],
            vecs[340:360],
        ))
        compare()
        assert len(inc) == len(full) == 60

    def test_append_and_delete_do_not_rebuild_main_buffer(self):
        """The structural O(new-rows) claim: after the first sync, small
        appends and deletes leave the main device buffer untouched (same
        array object) — only the tail and the masks change."""
        vecs, _ = _clustered(300)
        store = TPUVectorStore(DIM, dtype="float32")
        store.add([Chunk(text=f"t{i}", source="s") for i in range(256)],
                  vecs[:256])
        assert store.search(vecs[0], 1)  # first sync: full build
        buf0 = store._device_buf
        base0 = store._base
        store.add([Chunk(text=f"n{i}", source="new") for i in range(32)],
                  vecs[256:288])
        hits = store.search(vecs[260], 1)
        assert hits[0].chunk.text == "n4"
        assert store._device_buf is buf0 and store._base == base0
        store.delete_source("new")
        assert store.search(vecs[0], 1)[0].chunk.text == "t0"
        assert store._device_buf is buf0  # delete flipped masks only

    @pytest.mark.parametrize("incremental", [True, False])
    def test_rows_appended_after_a_sync_are_found_by_the_next_search(
        self, incremental
    ):
        """Time-to-searchable is one search: the first query after an
        append of N rows to a synced corpus of M >> N finds a new row,
        through the tail sync and through a full rebuild alike."""
        vecs, _ = _clustered(1088)
        store = TPUVectorStore(DIM, dtype="float32", incremental=incremental)
        store.add(
            [Chunk(text=f"r{i}", source="base") for i in range(1024)],
            vecs[:1024],
        )
        assert store.search(vecs[0], 10)  # M sits exactly at capacity
        store.add(
            [Chunk(text=f"n{i}", source="new") for i in range(64)],
            vecs[1024:],
        )
        assert store.search(vecs[1024], 10)[0].chunk.text == "n0"
        assert len(store) == 1088

    def test_tail_overflow_compacts(self, monkeypatch):
        """Appends beyond the tail capacity fold into a rebuilt main
        buffer and stay searchable."""
        from generativeaiexamples_tpu.retrieval import tpu as tpu_mod

        monkeypatch.setattr(tpu_mod, "_MIN_TAIL", 32)
        vecs, _ = _clustered(300)
        store = TPUVectorStore(DIM, dtype="float32")
        store.add([Chunk(text=f"t{i}", source="s") for i in range(100)],
                  vecs[:100])
        assert store.search(vecs[0], 1)
        buf0 = store._device_buf
        assert int(store._tail_buf.shape[0]) == 128  # 1024-cap // 8
        store.add([Chunk(text=f"t{i}", source="s2")
                   for i in range(100, 300)], vecs[100:300])
        hits = store.search(vecs[150], 1)
        assert hits[0].chunk.text == "t150"
        assert store._device_buf is not buf0  # compaction happened
        assert store._base == 300

    def test_add_validates_eagerly(self):
        store = TPUVectorStore(DIM, dtype="float32")
        with pytest.raises(ValueError, match="chunks but"):
            store.add([Chunk(text="x", source="s")], [])
        with pytest.raises(ValueError, match="shape"):
            store.add([Chunk(text="x", source="s")], [[0.0] * (DIM + 1)])
        with pytest.raises(ValueError, match="ragged|shape"):
            store.add(
                [Chunk(text="x", source="s"), Chunk(text="y", source="s")],
                [[0.0] * DIM, [0.0] * 3],
            )
        assert store.add([], []) == []
        assert len(store) == 0  # failed adds left no partial state

    def test_concurrent_add_while_search(self):
        """Regression: concurrent ingest+search share the store lock —
        no torn sync state, every search returns valid results."""
        import threading

        vecs, rng = _clustered(600)
        store = TPUVectorStore(DIM, dtype="float32")
        store.add([Chunk(text=f"seed{i}", source="seed")
                   for i in range(100)], vecs[:100])
        assert store.search(vecs[0], 1)
        errors: list = []

        def writer():
            try:
                for lo in range(100, 600, 50):
                    store.add(
                        [Chunk(text=f"w{i}", source=f"src{lo}")
                         for i in range(lo, lo + 50)],
                        vecs[lo : lo + 50],
                    )
                    if lo == 300:
                        store.delete_source("src100")
            except Exception as e:  # noqa: BLE001
                errors.append(e)

        t = threading.Thread(target=writer)
        t.start()
        try:
            while t.is_alive():
                hits = store.search(vecs[0], 5)
                assert hits and hits[0].chunk.text == "seed0"
        finally:
            t.join(10)
        assert not errors
        assert store.search(vecs[550], 1)[0].chunk.text == "w550"
        assert len(store) == 550  # 600 - 50 deleted


class TestIVFIncremental:
    """Round-9: FAISS-style add-by-assignment — appended rows are exactly
    searchable before any re-train; re-train runs in the background with
    an atomic swap."""

    def test_append_searchable_before_retrain(self):
        vecs, _ = _clustered(700)
        ivf = TPUIVFVectorStore(
            DIM, dtype="float32", nlist=8, nprobe=8, min_train_size=100,
            retrain_growth=10.0,  # never retrains inside this test
        )
        ivf.add([Chunk(text=f"t{i}", source="s") for i in range(500)],
                vecs[:500])
        assert ivf.search(vecs[0], 1)  # inline first build
        buckets0 = ivf._buckets
        base0 = ivf._ivf_base
        ivf.add([Chunk(text=f"new{i}", source="fresh")
                 for i in range(100)], vecs[500:600])
        hits = ivf.search(vecs[550], 1)
        assert hits[0].chunk.text == "new50"
        # The bucket index did NOT rebuild: fresh rows serve from the tail.
        assert ivf._buckets is buckets0 and ivf._ivf_base == base0
        assert ivf.wait_for_maintenance() is None  # nothing scheduled
        assert ivf._buckets is buckets0
        # Deletes of tail rows mask them out without a rebuild.
        ivf.delete_source("fresh")
        hits = ivf.search(vecs[550], 30)
        assert hits and all(h.chunk.source == "s" for h in hits)

    def test_background_retrain_atomic_under_search(self):
        import threading

        vecs, rng = _clustered(900)
        ivf = TPUIVFVectorStore(
            DIM, dtype="float32", nlist=8, nprobe=8, min_train_size=100,
            retrain_growth=1.5,
        )
        ivf.add([Chunk(text=f"t{i}", source="s") for i in range(300)],
                vecs[:300])
        assert ivf.search(vecs[0], 1)
        assert ivf._last_train_live == 300
        stop = threading.Event()
        errors: list = []

        def reader():
            try:
                while not stop.is_set():
                    hits = ivf.search(vecs[5], 3)
                    assert hits and hits[0].chunk.text == "t5"
            except Exception as e:  # noqa: BLE001
                errors.append(e)

        t = threading.Thread(target=reader)
        t.start()
        try:
            # 300 -> 900 live crosses the 1.5x growth threshold.
            ivf.add([Chunk(text=f"g{i}", source="grow")
                     for i in range(600)], vecs[300:900])
            assert ivf.search(vecs[700], 1)[0].chunk.text == "g400"
            ivf.wait_for_maintenance()
            # One more sync pass so any just-finished swap is visible.
            assert ivf.search(vecs[700], 1)[0].chunk.text == "g400"
        finally:
            stop.set()
            t.join(10)
        assert not errors
        # The swap happened: the new index covers the grown corpus.
        assert ivf._ivf_base == 900
        assert ivf._last_train_live == 900

    def test_fold_keeps_frozen_centroids(self, monkeypatch):
        """A tail overflow folds rows into the buckets WITHOUT k-means:
        centroids stay frozen, no row is lost."""
        from generativeaiexamples_tpu.retrieval import tpu as tpu_mod

        monkeypatch.setattr(tpu_mod, "_MIN_TAIL", 32)
        vecs, _ = _clustered(600)
        ivf = TPUIVFVectorStore(
            DIM, dtype="float32", nlist=8, nprobe=8, min_train_size=100,
            retrain_growth=50.0,
        )
        ivf.add([Chunk(text=f"t{i}", source="s") for i in range(400)],
                vecs[:400])
        assert ivf.search(vecs[0], 1)
        c0 = np.asarray(ivf._centroids)
        ivf.add([Chunk(text=f"f{i}", source="fold")
                 for i in range(100)], vecs[400:500])
        assert ivf.search(vecs[450], 1)[0].chunk.text == "f50"
        ivf.wait_for_maintenance()
        assert ivf.search(vecs[450], 1)[0].chunk.text == "f50"
        if ivf._ivf_base > 400:  # the fold swapped in
            np.testing.assert_array_equal(np.asarray(ivf._centroids), c0)
        # Every row remains retrievable (nprobe == nlist => exact).
        for row in (0, 250, 420, 499):
            got = ivf.search(vecs[row], 1)[0].chunk.text
            assert got in (f"t{row}", f"f{row - 400}")


# -- quantized scoring (round-10) -------------------------------------------

QDIM = 64  # pq subspaces need headroom; 64/8 = 8-dim subspaces


def _clustered_q(n, seed=0, n_centers=32):
    """Clustered unit vectors + query set with exact top-10 ground truth
    (PQ codebooks are meaningless on iid noise — real embedding corpora
    cluster, so the recall gates measure the realistic regime)."""
    rng = np.random.default_rng(seed)
    centers = rng.standard_normal((n_centers, QDIM)).astype(np.float32) * 3
    vecs = centers[rng.integers(0, n_centers, n)] + rng.standard_normal(
        (n, QDIM)
    ).astype(np.float32)
    vecs /= np.linalg.norm(vecs, axis=1, keepdims=True)
    queries = centers[rng.integers(0, n_centers, 16)] + (
        0.3 * rng.standard_normal((16, QDIM)).astype(np.float32)
    )
    queries /= np.linalg.norm(queries, axis=1, keepdims=True)
    return vecs, queries


def _recall_at_10(store, queries, truth):
    hits = 0
    for q, want in zip(queries, truth):
        got = {h.chunk.id for h in store.search(q.tolist(), 10)}
        hits += len(got & want)
    return hits / (10 * len(truth))


class TestQuantized:
    """Round-10: int8 + PQ compressed scoring with two-stage rescored
    top-k.  Recall gates vs the exact full-width scan, bit-exact parity
    for quantization='none', tiny-store exact fallback, and
    append/delete/retrain equivalence with quantization on."""

    def _truth(self, vecs, queries):
        exact = TPUVectorStore(QDIM, dtype="float32")
        exact.add(
            [Chunk(id=str(i), text=f"t{i}", source="s")
             for i in range(len(vecs))],
            vecs,
        )
        return exact, [
            {h.chunk.id for h in exact.search(q.tolist(), 10)}
            for q in queries
        ]

    def test_int8_recall_gate(self):
        vecs, queries = _clustered_q(3000)
        _, truth = self._truth(vecs, queries)
        st = TPUVectorStore(QDIM, dtype="float32", quantization="int8")
        st.add(
            [Chunk(id=str(i), text=f"t{i}", source="s")
             for i in range(len(vecs))],
            vecs,
        )
        st.search(queries[0].tolist(), 1)  # sync: compressed buffer built
        assert st._quant_ready(10)  # the compressed path actually engaged
        r = _recall_at_10(st, queries, truth)
        assert r >= 0.95, f"int8 recall@10 {r}"

    def test_pq_recall_gate(self):
        vecs, queries = _clustered_q(3000)
        _, truth = self._truth(vecs, queries)
        st = TPUVectorStore(
            QDIM, dtype="float32", quantization="pq", pq_m=8,
            rescore_multiplier=8,
        )
        st.add(
            [Chunk(id=str(i), text=f"t{i}", source="s")
             for i in range(len(vecs))],
            vecs,
        )
        st.search(queries[0].tolist(), 1)  # sync: codebooks trained
        assert st._quant_ready(10)
        r = _recall_at_10(st, queries, truth)
        assert r >= 0.90, f"pq recall@10 {r}"

    def test_none_mode_bit_exact(self):
        vecs, queries = _clustered_q(600)
        exact, _ = self._truth(vecs, queries)
        st = TPUVectorStore(QDIM, dtype="float32", quantization="none")
        st.add(
            [Chunk(id=str(i), text=f"t{i}", source="s")
             for i in range(len(vecs))],
            vecs,
        )
        for q in queries[:6]:
            want = [(h.chunk.id, h.score) for h in exact.search(q.tolist(), 10)]
            got = [(h.chunk.id, h.score) for h in st.search(q.tolist(), 10)]
            assert got == want

    def test_tiny_store_falls_back_to_exact(self):
        """Stores smaller than top_k * rescore_multiplier skip stage one:
        the oversample would cover the whole corpus anyway, and
        approx_max_k over a handful of rows is pure overhead."""
        vecs, queries = _clustered_q(30)
        exact, _ = self._truth(vecs, queries)
        st = TPUVectorStore(
            QDIM, dtype="float32", quantization="int8",
            rescore_multiplier=4,
        )
        st.add(
            [Chunk(id=str(i), text=f"t{i}", source="s")
             for i in range(len(vecs))],
            vecs,
        )
        assert not st._quant_ready(10)  # 30 <= 10 * 4
        for q in queries[:4]:
            want = [(h.chunk.id, round(h.score, 5))
                    for h in exact.search(q.tolist(), 10)]
            got = [(h.chunk.id, round(h.score, 5))
                   for h in st.search(q.tolist(), 10)]
            assert got == want

    @pytest.mark.parametrize("mode,kw", [
        ("int8", {}),
        ("pq", {"pq_m": 8, "rescore_multiplier": 8}),
    ])
    def test_append_delete_with_quantization(self, mode, kw):
        """Fresh rows serve from the full-width tail (recall 1.0 before
        any rebuild); deletes mask out of the compressed stage."""
        vecs, _ = _clustered_q(2000)
        st = TPUVectorStore(QDIM, dtype="float32", quantization=mode, **kw)
        st.add(
            [Chunk(id=str(i), text=f"t{i}", source="s")
             for i in range(len(vecs))],
            vecs,
        )
        st.search(vecs[0].tolist(), 1)  # sync: compressed buffer built
        rng = np.random.default_rng(99)
        fresh = rng.standard_normal((50, QDIM)).astype(np.float32)
        fresh /= np.linalg.norm(fresh, axis=1, keepdims=True)
        st.add(
            [Chunk(id=f"x{i}", text="fresh", source="fresh")
             for i in range(50)],
            fresh,
        )
        hits = st.search(fresh[7].tolist(), 3)
        assert hits[0].chunk.id == "x7"  # tail rows bypass stage one
        st.delete_source("fresh")
        got = {h.chunk.id for h in st.search(fresh[7].tolist(), 10)}
        assert not any(g.startswith("x") for g in got)
        # Delete INDEXED rows: the stage-one mask must hide them too.
        st.delete_source("s")
        assert len(st) == 0 and st.search(vecs[0].tolist(), 5) == []

    def test_batch_matches_single_quantized(self):
        vecs, queries = _clustered_q(1500)
        for mode, kw in (
            ("int8", {}),
            ("pq", {"pq_m": 8, "rescore_multiplier": 8}),
        ):
            st = TPUVectorStore(
                QDIM, dtype="float32", quantization=mode, **kw
            )
            st.add(
                [Chunk(id=str(i), text=f"t{i}", source="s")
                 for i in range(len(vecs))],
                vecs,
            )
            single = [
                [(h.chunk.id, round(h.score, 5))
                 for h in st.search(q.tolist(), 10)]
                for q in queries[:6]
            ]
            batched = [
                [(h.chunk.id, round(h.score, 5)) for h in hits]
                for hits in st.search_batch(
                    [q.tolist() for q in queries[:6]], 10
                )
            ]
            assert batched == single, mode

    def test_scanned_bytes_ratios(self, monkeypatch):
        """The bandwidth claim itself: compressed stage-one scan cuts
        HBM bytes/query to <= 0.55x (int8) and <= 0.15x (PQ) of the
        full-width scan.  The tail cap is clamped small: production sizes
        (100k-1M rows, bench_quant) amortize the always-exact tail to
        <1% of the scan, but at 4k rows the default cap//8 tail would
        add a flat ~12% full-width floor that swamps the PQ term."""
        from generativeaiexamples_tpu.retrieval import tpu as tpu_mod

        monkeypatch.setattr(tpu_mod, "_MIN_TAIL", 128)
        monkeypatch.setattr(tpu_mod, "_MAX_TAIL", 128)
        vecs, _ = _clustered_q(4096)
        chunks = [
            Chunk(id=str(i), text=f"t{i}", source="s")
            for i in range(len(vecs))
        ]
        base = TPUVectorStore(QDIM, dtype="float32")
        base.add(chunks, vecs)
        full = base.scanned_bytes_per_query(10)
        st8 = TPUVectorStore(QDIM, dtype="float32", quantization="int8")
        st8.add(chunks, vecs)
        stpq = TPUVectorStore(
            QDIM, dtype="float32", quantization="pq", pq_m=8,
            rescore_multiplier=8,
        )
        stpq.add(chunks, vecs)
        r8 = st8.scanned_bytes_per_query(10) / full
        rpq = stpq.scanned_bytes_per_query(10) / full
        assert r8 <= 0.55, f"int8 scanned-bytes ratio {r8:.3f}"
        assert rpq <= 0.15, f"pq scanned-bytes ratio {rpq:.3f}"

    def test_capacity_stats(self):
        vecs, _ = _clustered_q(1000)
        st = TPUVectorStore(QDIM, dtype="float32", quantization="int8")
        st.add(
            [Chunk(id=str(i), text=f"t{i}", source="s")
             for i in range(len(vecs))],
            vecs,
        )
        st.search(vecs[0].tolist(), 1)
        stats = st.capacity_stats()
        assert stats["rows"] == 1000
        # bytes cover the full-width buffer AND the compressed copy.
        cap = int(st._device_buf.shape[0])
        assert stats["bytes"] >= cap * QDIM * 4 + cap * QDIM
        assert stats["tail_rows"] == 0
        # The abstract default keeps external backends metric-safe.
        assert MemoryVectorStore(QDIM).capacity_stats() == {
            "rows": 0, "bytes": 0, "tail_rows": 0,
        }

    def test_constructor_validation(self):
        with pytest.raises(ValueError, match="quantization"):
            TPUVectorStore(QDIM, quantization="int4")
        with pytest.raises(ValueError, match="pq_m"):
            TPUVectorStore(QDIM, quantization="pq", pq_m=7)
        with pytest.raises(ValueError, match="rescore_multiplier"):
            TPUVectorStore(QDIM, quantization="int8", rescore_multiplier=0)

    def test_config_factory_plumbing(self):
        """vectorstore.quantization/pq_m/rescore_multiplier/recall_target
        reach the constructed store for 'tpu' and 'tpu-ivf'."""
        import dataclasses

        from generativeaiexamples_tpu.core.configuration import AppConfig
        from generativeaiexamples_tpu.retrieval.factory import (
            get_vector_store,
        )

        cfg = AppConfig()
        cfg = dataclasses.replace(
            cfg,
            vector_store=dataclasses.replace(
                cfg.vector_store, name="tpu", quantization="pq", pq_m=8,
                rescore_multiplier=6, recall_target=0.9,
            ),
        )
        st = get_vector_store(cfg, dimensions=QDIM)
        assert isinstance(st, TPUVectorStore)
        assert st.quantization == "pq" and st.pq_m == 8
        assert st.rescore_multiplier == 6 and st.recall_target == 0.9
        cfg = dataclasses.replace(
            cfg,
            vector_store=dataclasses.replace(
                cfg.vector_store, name="tpu-ivf", quantization="int8",
            ),
        )
        ivf = get_vector_store(cfg, dimensions=QDIM)
        assert isinstance(ivf, TPUIVFVectorStore)
        assert ivf.quantization == "int8"


class TestIVFQuantized:
    """Quantized IVF: compressed buckets swap atomically with the index,
    survive background fold/re-train, and keep append/delete semantics."""

    def _store(self, mode, **kw):
        return TPUIVFVectorStore(
            QDIM, dtype="float32", nlist=16, nprobe=16,
            min_train_size=256, quantization=mode, **kw,
        )

    @pytest.mark.parametrize("mode,kw", [
        ("int8", {}),
        ("pq", {"pq_m": 8, "rescore_multiplier": 8}),
    ])
    def test_recall_probe_all(self, mode, kw):
        """nprobe == nlist isolates the quantization error: stage one
        scans every bucket, so the only recall loss is compression."""
        vecs, queries = _clustered_q(3000)
        exact = TPUVectorStore(QDIM, dtype="float32")
        exact.add(
            [Chunk(id=str(i), text=f"t{i}", source="s")
             for i in range(len(vecs))],
            vecs,
        )
        truth = [
            {h.chunk.id for h in exact.search(q.tolist(), 10)}
            for q in queries
        ]
        ivf = self._store(mode, **kw)
        ivf.add(
            [Chunk(id=str(i), text=f"t{i}", source="s")
             for i in range(len(vecs))],
            vecs,
        )
        ivf.search(queries[0].tolist(), 1)
        assert ivf._q_buckets is not None  # compressed buckets built
        r = _recall_at_10(ivf, queries, truth)
        floor = 0.95 if mode == "int8" else 0.90
        assert r >= floor, f"ivf {mode} recall@10 {r}"

    def test_background_retrain_keeps_quantization(self):
        """Growth past retrain_growth re-trains k-means AND the PQ
        codebooks in one atomic swap; every row stays retrievable."""
        vecs, _ = _clustered_q(3000)
        ids = [f"t{i}" for i in range(len(vecs))]
        ivf = self._store("pq", pq_m=8, rescore_multiplier=8)
        ivf.retrain_growth = 1.5
        ivf.add(
            [Chunk(id=ids[i], text=ids[i], source="s")
             for i in range(1000)],
            vecs[:1000],
        )
        ivf.search(vecs[0].tolist(), 1)
        assert ivf._q_buckets is not None
        books0 = ivf._pq_codebooks_h
        # 1000 -> 3000 crosses the 1.5x growth threshold.
        ivf.add(
            [Chunk(id=ids[i], text=ids[i], source="grow")
             for i in range(1000, 3000)],
            vecs[1000:3000],
        )
        assert ivf.search(vecs[1500].tolist(), 1)[0].chunk.id == "t1500"
        ivf.wait_for_maintenance()
        ivf.search(vecs[0].tolist(), 1)  # absorb the swap
        assert ivf._q_buckets is not None
        assert ivf._ivf_base == 3000  # the re-train swapped in
        # Clustered corpora hold near-duplicates whose PQ codes collide,
        # so assert top-10 membership, not rank-1 (exact rescore then
        # ranks the true row first whenever stage one surfaces it).
        for row in (0, 999, 1000, 2500, 2999):
            got = {h.chunk.id for h in ivf.search(vecs[row].tolist(), 10)}
            assert f"t{row}" in got, row
        del books0  # codebooks may retrain or persist; both are valid

    def test_delete_masks_compressed_stage(self):
        vecs, _ = _clustered_q(1500)
        ivf = self._store("int8")
        ivf.add(
            [Chunk(id=str(i), text=f"t{i}",
                   source="evict" if i % 3 == 0 else "keep")
             for i in range(len(vecs))],
            vecs,
        )
        ivf.search(vecs[0].tolist(), 1)
        assert ivf._q_buckets is not None
        ivf.delete_source("evict")
        hits = ivf.search(vecs[0].tolist(), 20)
        assert hits and all(h.chunk.source == "keep" for h in hits)
