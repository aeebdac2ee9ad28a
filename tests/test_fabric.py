"""Sharded scatter-gather retrieval fabric (retrieval/fabric/).

Merge correctness first: the fabric's oversampled per-shard fan-out plus
exact stage-2 scoring must make the merged top-k BIT-EQUIVALENT to a
single store scanning the same corpus — for exact children, quantized
(int8/PQ) children, under delete-masking, and against fresh-tail rows
mid-ingest.  Then the tenancy layer (named collections, quotas,
per-collection versions), the host-RAM cold tier, persistence, and the
chain-server plumbing (collection params, 413 on quota, 404 on unknown).
"""

import asyncio
import os
import threading
import time

import numpy as np
import pytest

from generativeaiexamples_tpu.retrieval.base import Chunk
from generativeaiexamples_tpu.retrieval.fabric import (
    DEFAULT_COLLECTION,
    CollectionManager,
    CollectionQuotaExceeded,
    ShardedVectorStore,
    UnknownCollection,
)
from generativeaiexamples_tpu.retrieval.memory import MemoryVectorStore

DIM = 32


def _corpus(n, dim=DIM, seed=0, n_sources=7):
    rng = np.random.default_rng(seed)
    vecs = rng.normal(size=(n, dim)).astype(np.float32)
    vecs /= np.linalg.norm(vecs, axis=1, keepdims=True)
    chunks = [
        Chunk(text=f"t{i}", source=f"s{i % n_sources}") for i in range(n)
    ]
    return chunks, vecs


def _ids(hits):
    return [h.chunk.id for h in hits]


@pytest.fixture
def corpus():
    return _corpus(300)


# -- scatter-gather merge correctness ---------------------------------------


def test_exact_fabric_bit_equivalent_to_single_store(corpus):
    chunks, vecs = _corpus(300, seed=1)
    single = MemoryVectorStore(DIM)
    single.add(chunks, vecs)
    fab = ShardedVectorStore(DIM, num_shards=4)
    fab.add(chunks, vecs)
    try:
        for qi in range(8):
            q = vecs[qi * 17].tolist()
            ref = single.search(q, top_k=10)
            got = fab.search(q, top_k=10)
            assert _ids(got) == _ids(ref)
            for a, b in zip(got, ref):
                assert abs(a.score - b.score) < 1e-6
    finally:
        fab.close()


@pytest.mark.parametrize("quant", ["int8", "pq"])
def test_quantized_fabric_matches_single_exact_store(quant):
    """Quantized children report EXACT scores (two-stage rescore), and at
    test scale the oversample covers every row — so the merged top-k must
    equal the single exact store's, bit for bit."""
    from generativeaiexamples_tpu.retrieval.tpu import TPUVectorStore

    chunks, vecs = _corpus(240, seed=2)
    single = MemoryVectorStore(DIM)
    single.add(chunks, vecs)
    kw = dict(quantization=quant, rescore_multiplier=64)
    if quant == "pq":
        kw["pq_m"] = 8
    fab = ShardedVectorStore(
        DIM,
        num_shards=3,
        shard_factory=lambda i: TPUVectorStore(
            DIM, dtype="float32", **kw
        ),
        rescore_multiplier=8,
    )
    fab.add(chunks, vecs)
    try:
        for qi in range(6):
            q = vecs[qi * 31].tolist()
            ref = single.search(q, top_k=5)
            got = fab.search(q, top_k=5)
            assert _ids(got) == _ids(ref), f"mode {quant} diverged"
            for a, b in zip(got, ref):
                assert abs(a.score - b.score) < 1e-4
    finally:
        fab.close()


def test_delete_masking_matches_single_store(corpus):
    chunks, vecs = corpus
    single = MemoryVectorStore(DIM)
    single.add(chunks, vecs)
    fab = ShardedVectorStore(DIM, num_shards=4)
    fab.add(chunks, vecs)
    try:
        removed_fab = fab.delete_source("s3")
        removed_single = single.delete_source("s3")
        assert removed_fab == removed_single > 0
        assert len(fab) == len(single)
        assert "s3" not in fab.sources()
        q = vecs[5].tolist()
        got = fab.search(q, top_k=10)
        assert _ids(got) == _ids(single.search(q, top_k=10))
        assert all(h.chunk.source != "s3" for h in got)
    finally:
        fab.close()


def test_cold_tier_delete_masking():
    """Deletes must mask rows in DEMOTED (PQ-coded) partitions too."""
    chunks, vecs = _corpus(200, seed=3)
    fab = ShardedVectorStore(DIM, num_shards=2, pq_m=8,
                             rescore_multiplier=8)
    fab.add(chunks, vecs)
    try:
        fab.demote_shard(0)
        fab.demote_shard(1)
        assert fab.cold_shards() == [0, 1]
        before = len(fab)
        removed = fab.delete_source("s1")
        assert removed > 0
        assert len(fab) == before - removed
        got = fab.search(vecs[8].tolist(), top_k=20)
        assert all(h.chunk.source != "s1" for h in got)
    finally:
        fab.close()


def test_fresh_tail_rows_visible_mid_ingest():
    """Rows appended after the first sync must be immediately searchable
    (the TPU children's fresh-tail path, exercised through the fabric)."""
    from generativeaiexamples_tpu.retrieval.tpu import TPUVectorStore

    chunks, vecs = _corpus(120, seed=4)
    fab = ShardedVectorStore(
        DIM,
        num_shards=2,
        shard_factory=lambda i: TPUVectorStore(DIM, dtype="float32"),
    )
    fab.add(chunks[:80], vecs[:80])
    fab.search(vecs[0].tolist(), top_k=3)  # force device sync
    fab.add(chunks[80:], vecs[80:])  # lands in the fresh tails
    try:
        for i in (85, 100, 119):
            got = fab.search(vecs[i].tolist(), top_k=1)
            assert got[0].chunk.id == chunks[i].id
    finally:
        fab.close()


def test_concurrent_search_under_ingest():
    """PR 4 pattern at the fabric level: searches racing bulk adds never
    error and always return valid, correctly-ordered results."""
    chunks, vecs = _corpus(800, seed=5)
    fab = ShardedVectorStore(DIM, num_shards=4)
    fab.add(chunks[:200], vecs[:200])
    errors: list = []
    stop = threading.Event()

    def _ingest():
        i = 200
        try:
            while i < 800 and not stop.is_set():
                fab.add(chunks[i : i + 50], vecs[i : i + 50])
                i += 50
        except Exception as exc:  # noqa: BLE001
            errors.append(exc)

    t = threading.Thread(target=_ingest)
    t.start()
    try:
        for qi in range(30):
            got = fab.search(vecs[qi % 200].tolist(), top_k=5)
            assert len(got) == 5
            scores = [h.score for h in got]
            assert scores == sorted(scores, reverse=True)
    finally:
        stop.set()
        t.join(timeout=30)
        fab.close()
    assert not errors
    assert len(fab) == 800


def test_search_batch_fans_out_and_trims_per_query(corpus):
    chunks, vecs = corpus
    fab = ShardedVectorStore(DIM, num_shards=3)
    fab.add(chunks, vecs)
    single = MemoryVectorStore(DIM)
    single.add(chunks, vecs)
    try:
        queries = [vecs[i * 11].tolist() for i in range(5)]
        many = fab.search_batch(queries, top_k=7)
        assert len(many) == 5
        for q, got in zip(queries, many):
            assert _ids(got) == _ids(single.search(q, top_k=7))
        snap = fab.stats_snapshot()
        assert snap["queries_total"] >= 5
        assert snap["merge_count"] >= 5
    finally:
        fab.close()


def test_shard_k_oversampling_floor():
    fab = ShardedVectorStore(
        DIM, num_shards=8, rescore_multiplier=4, margin=8
    )
    try:
        # ceil(10*4/8)+8 = 13 >= top_k keeps exact merges exact.
        assert fab.shard_k(10) == 13
        # Never below top_k (exact-mode bit-equivalence clamp).
        assert fab.shard_k(40) >= 40
    finally:
        fab.close()


# -- host-RAM cold tier ------------------------------------------------------


def test_cold_tier_search_matches_exact_with_full_rescore():
    chunks, vecs = _corpus(200, seed=6)
    single = MemoryVectorStore(DIM)
    single.add(chunks, vecs)
    fab = ShardedVectorStore(
        DIM, num_shards=2, pq_m=8, rescore_multiplier=8
    )
    fab.add(chunks, vecs)
    try:
        fab.demote_shard(0)
        fab.demote_shard(1)
        # rescore_k = shard_k * rescore_multiplier >= shard rows here, so
        # stage-2 rescans every candidate and the merge is exact.
        q = vecs[3].tolist()
        got = fab.search(q, top_k=5)
        assert _ids(got) == _ids(single.search(q, top_k=5))
    finally:
        fab.close()


def test_cold_tier_byte_split_and_capacity():
    chunks, vecs = _corpus(400, seed=7)
    fab = ShardedVectorStore(DIM, num_shards=4, pq_m=8)
    fab.add(chunks, vecs)
    try:
        all_hot = fab.scanned_bytes_split(10)
        assert all_hot["host"] == 0 and all_hot["hbm"] > 0
        fab.demote_shard(0)
        fab.demote_shard(1)
        split = fab.scanned_bytes_split(10)
        assert split["host"] > 0
        assert split["hbm"] < all_hot["hbm"]
        # PQ codes scan far fewer bytes than the full-width rows they
        # replace (the <=0.15x bench gate, structurally).
        cold_rows = sum(
            p.rows() for p in (fab._shards[0].cold, fab._shards[1].cold)
        )
        assert split["host"] < 0.5 * cold_rows * DIM * 4
        caps = fab.capacity_stats()
        assert caps["rows"] == 400
        assert caps["cold_shards"] == 2 and caps["hot_shards"] == 2
        assert caps["host_bytes"] > 0
        assert fab.scanned_bytes_per_query(10) == (
            split["host"] + split["hbm"]
        )
    finally:
        fab.close()


def _clustered(rows, n_queries, dim=DIM, seed=37):
    """Topics of ~64 rows: structure PQ codebooks can learn (iid rows
    would make the recall figure say nothing)."""
    rng = np.random.default_rng(seed)
    centers = rng.standard_normal((rows // 64, dim)).astype(np.float32) * 3
    vecs = centers[rng.integers(0, len(centers), size=rows)]
    vecs = vecs + rng.standard_normal((rows, dim)).astype(np.float32)
    vecs /= np.linalg.norm(vecs, axis=1, keepdims=True)
    queries = centers[rng.integers(0, len(centers), size=n_queries)]
    queries = queries + 0.3 * rng.standard_normal(
        (n_queries, dim)
    ).astype(np.float32)
    queries /= np.linalg.norm(queries, axis=1, keepdims=True)
    chunks = [Chunk(text=f"r{i}", source="corpus") for i in range(rows)]
    return chunks, vecs, queries


@pytest.mark.parametrize("mode", ["int8", "pq_cold"])
def test_compressed_fabric_recall_where_the_rescore_sees_a_fraction(mode):
    """At 2048 rows a shard the stage-2 rescore sees a few dozen
    candidates, not every row (the tests above make it see all): the
    merged top-10 still holds >= 0.95 of the exact scan's, and a demoted
    shard's host scan reads <= 0.15x the bytes its rows cost full-width."""
    from generativeaiexamples_tpu.retrieval.tpu import TPUVectorStore

    chunks, vecs, queries = _clustered(4096, 16)
    single = MemoryVectorStore(DIM)
    single.add(chunks, vecs)
    if mode == "int8":
        fab = ShardedVectorStore(
            DIM,
            num_shards=2,
            shard_factory=lambda i: TPUVectorStore(
                DIM, dtype="float32", quantization="int8",
                rescore_multiplier=4,
            ),
        )
        fab.add(chunks, vecs)
    else:
        fab = ShardedVectorStore(
            DIM, num_shards=2, hot_shard_budget=1, pq_m=8
        )
        fab.add(chunks, vecs)
        fab.rebalance()
    try:
        found = sum(
            len(
                set(_ids(fab.search(q.tolist(), top_k=10)))
                & set(_ids(single.search(q.tolist(), top_k=10)))
            )
            for q in queries
        )
        assert found / (len(queries) * 10) >= 0.95
        if mode == "pq_cold":
            (cold,) = fab.cold_shards()
            cold_rows = fab._shards[cold].cold.rows()
            assert fab.shard_k(10) * fab.rescore_multiplier < cold_rows
            host = fab.scanned_bytes_split(10)["host"]
            assert host <= 0.15 * cold_rows * DIM * 4
    finally:
        fab.close()


def test_ewma_rebalance_promotes_hot_demotes_cold():
    chunks, vecs = _corpus(300, seed=8)
    fab = ShardedVectorStore(
        DIM, num_shards=3, hot_shard_budget=1, pq_m=8, ewma_alpha=0.5
    )
    fab.add(chunks, vecs)
    fab.rebalance()
    try:
        assert len(fab.hot_shards()) == 1
        assert len(fab.cold_shards()) == 2
        snap = fab.stats_snapshot()
        assert snap["coldtier_demotions_total"] == 2
        # Searches still span every shard (cold ones via host PQ scans).
        got = fab.search(vecs[0].tolist(), top_k=10)
        assert len(got) == 10
    finally:
        fab.close()


def test_explicit_promote_restores_hot_serving():
    chunks, vecs = _corpus(150, seed=9)
    fab = ShardedVectorStore(DIM, num_shards=2, pq_m=8,
                             rescore_multiplier=8)
    fab.add(chunks, vecs)
    try:
        v0 = fab.version()
        fab.demote_shard(1)
        assert fab.version() > v0
        fab.promote_shard(1)
        assert fab.cold_shards() == []
        single = MemoryVectorStore(DIM)
        single.add(chunks, vecs)
        q = vecs[2].tolist()
        assert _ids(fab.search(q, top_k=5)) == _ids(
            single.search(q, top_k=5)
        )
    finally:
        fab.close()


# -- persistence -------------------------------------------------------------


def test_save_load_roundtrip_with_cold_shards(tmp_path):
    chunks, vecs = _corpus(180, seed=10)
    fab = ShardedVectorStore(DIM, num_shards=3, pq_m=8,
                             rescore_multiplier=8)
    fab.add(chunks, vecs)
    fab.demote_shard(2)
    q = vecs[4].tolist()
    want = _ids(fab.search(q, top_k=5))
    version = fab.version()
    fab.save(str(tmp_path / "fab"))
    fab.close()
    loaded = ShardedVectorStore.load(str(tmp_path / "fab"))
    try:
        assert len(loaded) == 180
        assert loaded.cold_shards() == [2]
        assert loaded.version() == version
        assert _ids(loaded.search(q, top_k=5)) == want
    finally:
        loaded.close()


# -- replica hydration -------------------------------------------------------


def test_shards_for_replica_partition_the_fabric():
    fab = ShardedVectorStore(DIM, num_shards=4)
    try:
        owned = [fab.shards_for_replica(r, 2) for r in range(2)]
        assert owned == [[0, 2], [1, 3]]
        # Every shard owned by exactly one replica.
        flat = sorted(s for o in owned for s in o)
        assert flat == [0, 1, 2, 3]
    finally:
        fab.close()


def test_hydrate_replica_warms_only_routed_shards():
    from generativeaiexamples_tpu.retrieval.tpu import TPUVectorStore

    chunks, vecs = _corpus(120, seed=11)
    fab = ShardedVectorStore(
        DIM,
        num_shards=4,
        shard_factory=lambda i: TPUVectorStore(DIM, dtype="float32"),
    )
    fab.add(chunks, vecs)
    try:
        warmed = fab.hydrate_replica(1, 2)
        assert warmed == [1, 3]
        assert fab.stats_snapshot()["replica_hydrations_total"] == 1
    finally:
        fab.close()


# -- named collections / quotas ---------------------------------------------


def test_collection_manager_lifecycle_and_quotas():
    mgr = CollectionManager(
        lambda name, ov: MemoryVectorStore(DIM), max_collections=3
    )
    chunks, vecs = _corpus(30, seed=12)
    mgr.create("a", max_rows=10)
    mgr.create("b", max_bytes=12 * DIM * 4)
    assert sorted(mgr.list()) == ["a", "b"]
    assert mgr.exists("a") and not mgr.exists("zzz")
    # Idempotent re-create returns the same store.
    assert mgr.create("a") is mgr.get("a")
    with pytest.raises(CollectionQuotaExceeded):
        mgr.add("a", chunks[:11], vecs[:11])
    mgr.add("a", chunks[:10], vecs[:10])
    with pytest.raises(CollectionQuotaExceeded):
        mgr.add("a", chunks[10:11], vecs[10:11])
    with pytest.raises(CollectionQuotaExceeded):
        mgr.add("b", chunks[:13], vecs[:13])
    with pytest.raises(UnknownCollection):
        mgr.get("zzz")
    with pytest.raises(ValueError):
        mgr.create("bad name!")
    mgr.create("c")
    with pytest.raises(CollectionQuotaExceeded):
        mgr.create("d")  # count cap
    snap = mgr.stats_snapshot()
    assert snap["created_total"] == 3
    assert snap["quota_rejections_total"] == 3
    assert mgr.drop("c") and not mgr.drop("c")
    with pytest.raises(ValueError):
        mgr.drop(DEFAULT_COLLECTION)
    mgr.close()


def test_collection_versions_are_independent():
    mgr = CollectionManager(lambda name, ov: MemoryVectorStore(DIM))
    chunks, vecs = _corpus(4, seed=13)
    mgr.create("a")
    mgr.create("b")
    va, vb = mgr.version("a"), mgr.version("b")
    mgr.add("a", chunks, vecs)
    assert mgr.version("a") > va
    assert mgr.version("b") == vb  # tenant isolation for cache stamps
    mgr.close()


def test_capacity_by_collection_feeds_labeled_gauges():
    mgr = CollectionManager(lambda name, ov: MemoryVectorStore(DIM))
    chunks, vecs = _corpus(6, seed=14)
    mgr.create("a")
    mgr.add("a", chunks, vecs)
    by = mgr.capacity_by_collection()
    assert by["a"]["rows"] == 6
    assert DEFAULT_COLLECTION not in by  # peek contract


def test_fold_collection_labels_caps_cardinality():
    from generativeaiexamples_tpu.retrieval.fabric.metrics import (
        fold_collection_labels,
    )

    per = {f"c{i:03d}": {"rows": 1, "bytes": 2} for i in range(80)}
    rows = fold_collection_labels(per)
    assert len(rows) == 64
    assert rows[-1][0] == "other"
    assert rows[-1][1]["rows"] == 80 - 63
    assert sum(stats["rows"] for _, stats in rows) == 80


# -- factory wiring ----------------------------------------------------------


def test_factory_builds_fabric_backend(monkeypatch):
    from generativeaiexamples_tpu.core.configuration import (
        reset_config_cache,
    )
    from generativeaiexamples_tpu.retrieval.factory import get_vector_store

    for key in list(os.environ):
        if key.startswith("APP_"):
            monkeypatch.delenv(key, raising=False)
    monkeypatch.setenv("APP_VECTORSTORE_NAME", "fabric")
    monkeypatch.setenv("APP_FABRIC_NUMSHARDS", "3")
    monkeypatch.setenv("APP_FABRIC_CHILDBACKEND", "memory")
    monkeypatch.setenv("APP_EMBEDDINGS_DIMENSIONS", str(DIM))
    reset_config_cache()
    try:
        store = get_vector_store()
        assert isinstance(store, ShardedVectorStore)
        assert store.num_shards == 3
        chunks, vecs = _corpus(20, seed=15)
        store.add(chunks, vecs)
        assert len(store.search(vecs[0].tolist(), top_k=3)) == 3
        store.close()
        # Per-collection overrides flow through.
        quant = get_vector_store(
            overrides={"backend": "memory"}, collection="t"
        )
        assert isinstance(quant, MemoryVectorStore)
        with pytest.raises(ValueError, match="nest"):
            get_vector_store(overrides={"child_backend": "fabric"})
    finally:
        reset_config_cache()


# -- ingest admission --------------------------------------------------------


def test_ingest_pipeline_admit_fn_isolates_offending_file(tmp_path):
    """A quota refusal fails ONLY the file that breached it; batch-mates
    land (the per-file retry path in _flush)."""
    from generativeaiexamples_tpu.ingest.pipeline import IngestPipeline

    landed: list = []

    def _admit(chunks, embs):
        if any(c.source == "big.txt" for c in chunks):
            raise CollectionQuotaExceeded("t", "rows over quota")

    pipeline = IngestPipeline(
        parse_fn=lambda path, name: [
            Chunk(text=f"{name}-{i}", source=name) for i in range(3)
        ],
        embed_fn=lambda texts: [[0.1] * DIM for _ in texts],
        append_fn=lambda chunks, embs: landed.extend(chunks),
        admit_fn=_admit,
        parse_workers=2,
    )
    small = tmp_path / "small.txt"
    big = tmp_path / "big.txt"
    small.write_text("x")
    big.write_text("y")
    job = pipeline.submit([(str(small), "small.txt"), (str(big), "big.txt")])
    deadline = time.time() + 30
    while time.time() < deadline:
        snap = pipeline.status(job)
        if snap and snap["status"] in ("done", "failed", "partial"):
            break
        time.sleep(0.05)
    pipeline.close()
    snap = pipeline.status(job)
    assert snap["files_done"] == 1
    assert snap["files_failed"] == 1
    assert any("quota" in e for e in snap["errors"])
    assert sorted({c.source for c in landed}) == ["small.txt"]


# -- chain server plumbing ---------------------------------------------------


def _reset_server_env(monkeypatch, tmp_path):
    from generativeaiexamples_tpu.chains.factory import reset_factories
    from generativeaiexamples_tpu.core.configuration import (
        reset_config_cache,
    )

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
def server_client(monkeypatch, tmp_path):
    from aiohttp.test_utils import TestClient, TestServer

    from generativeaiexamples_tpu.server.app import create_app

    _reset_server_env(monkeypatch, tmp_path)
    loop = asyncio.new_event_loop()
    client = TestClient(TestServer(create_app()), loop=loop)
    loop.run_until_complete(client.start_server())
    yield client, loop
    loop.run_until_complete(client.close())
    loop.close()
    from generativeaiexamples_tpu.chains.factory import reset_factories
    from generativeaiexamples_tpu.core.configuration import (
        reset_config_cache,
    )

    reset_config_cache()
    reset_factories()


def test_server_collection_upload_search_list_delete(
    server_client, tmp_path
):
    c, loop = server_client

    async def go():
        doc = tmp_path / "tenant_doc.txt"
        doc.write_text("Saturn has rings.\n\nJupiter is large.")
        with open(doc, "rb") as fh:
            resp = await c.post(
                "/documents?collection=tenant-a", data={"file": fh}
            )
            assert resp.status == 200
        # The named collection serves its own search...
        resp = await c.post(
            "/search",
            json={"query": "saturn", "top_k": 2, "collection": "tenant-a"},
        )
        assert resp.status == 200
        hits = (await resp.json())["chunks"]
        assert hits and hits[0]["filename"] == "tenant_doc.txt"
        # ...while the default collection never saw the document.
        resp = await c.get("/documents")
        assert (await resp.json())["documents"] == []
        resp = await c.get("/documents?collection=tenant-a")
        assert (await resp.json())["documents"] == ["tenant_doc.txt"]
        # Unknown collections 404 instead of silently serving nothing.
        resp = await c.post(
            "/search", json={"query": "x", "collection": "nope"}
        )
        assert resp.status == 404
        resp = await c.get("/documents?collection=nope")
        assert resp.status == 404
        resp = await c.delete(
            "/documents?filename=tenant_doc.txt&collection=tenant-a"
        )
        assert resp.status == 200
        resp = await c.get("/documents?collection=tenant-a")
        assert (await resp.json())["documents"] == []

    loop.run_until_complete(go())


def test_server_collection_quota_maps_to_413(
    server_client, tmp_path, monkeypatch
):
    c, loop = server_client
    from generativeaiexamples_tpu.chains.factory import (
        get_collection_manager,
    )

    get_collection_manager().create("tiny", max_rows=1)

    async def go():
        first = tmp_path / "first.txt"
        first.write_text("Alpha fits the quota.")
        with open(first, "rb") as fh:
            resp = await c.post(
                "/documents?collection=tiny", data={"file": fh}
            )
        assert resp.status == 200
        second = tmp_path / "second.txt"
        second.write_text("Beta breaches the row quota.")
        with open(second, "rb") as fh:
            resp = await c.post(
                "/documents?collection=tiny", data={"file": fh}
            )
        assert resp.status == 413
        assert "quota" in (await resp.json())["detail"]

    loop.run_until_complete(go())


def test_server_generate_with_collection(server_client, tmp_path):
    c, loop = server_client

    async def go():
        doc = tmp_path / "facts.txt"
        doc.write_text("The capital of Mars is Olympus.")
        with open(doc, "rb") as fh:
            assert (
                await c.post(
                    "/documents?collection=kb", data={"file": fh}
                )
            ).status == 200
        resp = await c.post(
            "/generate",
            json={
                "messages": [{"role": "user", "content": "capital?"}],
                "use_knowledge_base": True,
                "collection": "kb",
            },
        )
        assert resp.status == 200
        body = (await resp.text()).strip()
        assert "[DONE]" in body
        # Unknown collection is a typed 404 BEFORE streaming.
        resp = await c.post(
            "/generate",
            json={
                "messages": [{"role": "user", "content": "q"}],
                "use_knowledge_base": True,
                "collection": "ghost",
            },
        )
        assert resp.status == 404

    loop.run_until_complete(go())


def test_bulk_upload_into_collection(server_client, tmp_path):
    c, loop = server_client

    async def go():
        import aiohttp

        f1 = tmp_path / "b1.txt"
        f2 = tmp_path / "b2.txt"
        f1.write_text("Comets are icy.")
        f2.write_text("Asteroids are rocky.")
        form = aiohttp.FormData()
        form.add_field("files", f1.read_bytes(), filename="b1.txt")
        form.add_field("files", f2.read_bytes(), filename="b2.txt")
        resp = await c.post(
            "/documents/bulk?collection=bulk-t", data=form
        )
        assert resp.status == 202
        job_id = (await resp.json())["job_id"]
        for _ in range(200):
            resp = await c.get(f"/documents/status?job_id={job_id}")
            snap = await resp.json()
            if snap["status"] in ("done", "failed", "partial"):
                break
            await asyncio.sleep(0.05)
        assert snap["status"] == "done"
        resp = await c.get("/documents?collection=bulk-t")
        docs = (await resp.json())["documents"]
        assert "b1.txt" in docs

    loop.run_until_complete(go())


# -- aggregated gauges -------------------------------------------------------


def test_aggregate_capacity_stats_sums_fabric_and_collections():
    from generativeaiexamples_tpu.retrieval.fabric.metrics import (
        aggregate_capacity_stats,
    )

    assert aggregate_capacity_stats(None, None) is None
    chunks, vecs = _corpus(50, seed=16)
    fab = ShardedVectorStore(DIM, num_shards=2)
    fab.add(chunks, vecs)
    mgr = CollectionManager(lambda name, ov: MemoryVectorStore(DIM))
    mgr.create("a")
    c2, v2 = _corpus(7, seed=17)
    mgr.add("a", c2, v2)
    try:
        agg = aggregate_capacity_stats(fab, mgr)
        assert agg["rows"] == 57
    finally:
        fab.close()
        mgr.close()
