"""Lazy singletons for the heavy components.

The reference's factory/util layer (``common/utils.py``): ``get_llm``,
``get_embedding_model``, ``get_vector_index``, ``get_text_splitter`` — all
``lru_cache``d so pipelines share one engine, one embedder, one store per
process.
"""

from __future__ import annotations

import functools
import threading
from typing import Optional

from generativeaiexamples_tpu.core.configuration import get_config
from generativeaiexamples_tpu.core.logging import get_logger

logger = get_logger(__name__)


@functools.lru_cache(maxsize=1)
def get_chat_llm():
    """Configured chat LLM (reference ``get_llm``, ``utils.py:263-288``)."""
    cfg = get_config()
    engine = cfg.llm.model_engine.lower()
    if engine == "echo":
        from generativeaiexamples_tpu.chains.llm import EchoChatLLM

        return EchoChatLLM()
    if engine == "openai":
        from generativeaiexamples_tpu.chains.llm import OpenAIChatLLM

        # Same normalisation as the embeddings client: the compose
        # files give the engine's root (http://engine:8000).
        base = (cfg.llm.server_url or "http://localhost:8000").rstrip("/")
        if not base.startswith("http"):
            base = f"http://{base}"
        if not base.endswith("/v1"):
            base = f"{base}/v1"
        return OpenAIChatLLM(base_url=base, model=cfg.llm.model_name)
    if engine == "tpu":
        from generativeaiexamples_tpu.chains.llm import TPUChatLLM
        from generativeaiexamples_tpu.engine.weights import resolve_model_preset

        preset = resolve_model_preset(cfg.llm.model_name)
        return TPUChatLLM(model_preset=preset)
    raise ValueError(f"unknown llm.model_engine {cfg.llm.model_engine!r}")


@functools.lru_cache(maxsize=1)
def get_embedder():
    """Configured embedder (reference ``get_embedding_model``,
    ``utils.py:291-318``)."""
    cfg = get_config()
    engine = cfg.embeddings.model_engine.lower()
    if engine == "hash":
        from generativeaiexamples_tpu.engine.embedder import HashEmbedder

        return HashEmbedder(dimensions=cfg.embeddings.dimensions)
    if engine == "huggingface":
        from generativeaiexamples_tpu.engine.embedder import STEmbedder

        return STEmbedder(cfg.embeddings.model_name, cfg.embeddings.dimensions)
    if engine == "openai":
        from generativeaiexamples_tpu.engine.embedder_client import (
            HTTPEmbedder,
        )

        return HTTPEmbedder(
            cfg.embeddings.server_url, cfg.embeddings.model_name,
            cfg.embeddings.dimensions,
        )
    if engine == "tpu":
        from generativeaiexamples_tpu.engine.embedder import TPUEmbedder
        from generativeaiexamples_tpu.engine.tokenizer import get_tokenizer
        from generativeaiexamples_tpu.engine.weights import (
            bert_config_from_hf,
            load_hf_bert,
            weights_dir_for,
        )
        from generativeaiexamples_tpu.models import bert

        ckpt_dir = weights_dir_for(cfg.embeddings.model_name)
        if ckpt_dir:
            # Real arctic-embed-l-class weights + their WordPiece vocab.
            bcfg = bert_config_from_hf(ckpt_dir)
            if bcfg.d_model != cfg.embeddings.dimensions:
                raise ValueError(
                    f"provisioned embedder checkpoint {ckpt_dir} has "
                    f"hidden_size={bcfg.d_model} but embeddings.dimensions="
                    f"{cfg.embeddings.dimensions}; the vector store would be "
                    "sized wrong — fix APP_EMBEDDINGS_DIMENSIONS"
                )
            return TPUEmbedder(
                bcfg,
                load_hf_bert(bcfg, ckpt_dir),
                tokenizer=get_tokenizer(ckpt_dir),
            )
        if cfg.embeddings.dimensions == 1024:
            bcfg = bert.arctic_embed_l()
        else:
            bcfg = bert.bert_tiny(d_model=cfg.embeddings.dimensions)
        return TPUEmbedder(bcfg)
    raise ValueError(f"unknown embeddings.model_engine {cfg.embeddings.model_engine!r}")


@functools.lru_cache(maxsize=1)
def get_store():
    """Configured vector store singleton.

    With ``durability.enabled``, the in-process backend is wrapped in a
    :class:`DurableVectorStore`: construction itself performs crash
    recovery (snapshot restore + WAL tail replay), and every mutation
    from then on is write-ahead logged."""
    from generativeaiexamples_tpu.retrieval.factory import get_vector_store

    cfg = get_config()
    store = get_vector_store(cfg)
    if cfg.durability.enabled:
        store = _wrap_durable(store, cfg)
    return store


def _wrap_durable(store, cfg, subdir: str = "store"):
    import os

    from generativeaiexamples_tpu.retrieval.base import VectorStore

    if type(store).save is VectorStore.save:
        # External backends (milvus/pgvector/elasticsearch) own their
        # durability; wrapping them would snapshot nothing.
        logger.warning(
            "durability.enabled but %s has no save() path; store runs "
            "without a WAL", type(store).__name__,
        )
        return store
    from generativeaiexamples_tpu.durability.store import DurableVectorStore

    return DurableVectorStore(
        store,
        os.path.join(cfg.durability.directory, subdir),
        fsync_every=cfg.durability.fsync_every,
        snapshot_every_records=cfg.durability.snapshot_every_records,
        keep_snapshots=cfg.durability.keep_snapshots,
    )


def peek_store():
    """The store singleton IF one has been created, else None.

    ``/metrics`` scrapes must never instantiate the store: against an
    external backend (milvus/pgvector) construction opens network
    connections, and before first use there is nothing to report anyway
    (same contract as ``peek_ingest_pipeline``)."""
    if get_store.cache_info().currsize:
        return get_store()
    return None


# Not lru_cached: reset_factories must close the dropped collections'
# stores (fabric fan-out workers included) instead of leaking them.
_COLLECTIONS_LOCK = threading.Lock()
_COLLECTIONS_STATE: dict = {"manager": None}


def get_collection_manager():
    """Process-wide :class:`CollectionManager` over the store factory.

    Named collections get independent stores (per-collection backend and
    quantization via create() overrides, per-collection WAL directory
    when durability is on); the ``default`` collection IS the
    :func:`get_store` singleton, so every legacy single-namespace path
    keeps its exact behaviour and nothing is double counted."""
    with _COLLECTIONS_LOCK:
        manager = _COLLECTIONS_STATE["manager"]
        if manager is not None:
            return manager
        from generativeaiexamples_tpu.retrieval.factory import (
            get_vector_store,
        )
        from generativeaiexamples_tpu.retrieval.fabric.collections import (
            CollectionManager,
        )

        cfg = get_config()

        def _store_factory(name: str, overrides: dict):
            store = get_vector_store(
                cfg, collection=name, overrides=overrides
            )
            if cfg.durability.enabled:
                # Per-collection WAL/snapshot directory: tenant A's
                # ingest never rewrites tenant B's recovery artifacts.
                store = _wrap_durable(
                    store, cfg, subdir=f"collections/{name}"
                )
            return store

        manager = CollectionManager(
            _store_factory,
            default_store=get_store,
            max_collections=cfg.collections.max_collections,
            default_max_rows=cfg.collections.max_rows_per_collection,
            default_max_bytes=cfg.collections.max_bytes_per_collection,
        )
        _COLLECTIONS_STATE["manager"] = manager
        return manager


def peek_collection_manager():
    """The live manager if one was ever built, else None — /metrics must
    export the rag_collection_* zeros without building anything."""
    with _COLLECTIONS_LOCK:
        return _COLLECTIONS_STATE["manager"]


@functools.lru_cache(maxsize=1)
def get_memory_store():
    """Separate store for conversation memory (the reference multi-turn
    pipeline keeps a second ``conv_store`` collection,
    ``multi_turn_rag/chains.py:146-148``)."""
    from generativeaiexamples_tpu.retrieval.factory import get_vector_store

    return get_vector_store(get_config(), collection="memory")


@functools.lru_cache(maxsize=1)
def get_splitter():
    from generativeaiexamples_tpu.ingest.splitters import get_text_splitter

    return get_text_splitter(get_config())


@functools.lru_cache(maxsize=1)
def get_retriever():
    """Shared Retriever over the singleton store/embedder/reranker.

    One instance per process (not per pipeline object): cross-request
    micro-batching only coalesces calls that reach the SAME retriever,
    and the chain server builds a fresh pipeline object per request.
    """
    from generativeaiexamples_tpu.resilience.retry import policy_from_config
    from generativeaiexamples_tpu.retrieval.retriever import Retriever

    cfg = get_config()
    return Retriever(
        store=get_store(),
        embedder=get_embedder(),
        top_k=cfg.retriever.top_k,
        score_threshold=cfg.retriever.score_threshold,
        fetch_k_multiplier=cfg.retriever.fetch_k_multiplier,
        reranker=get_reranker(),
        min_rerank_budget_ms=cfg.resilience.min_rerank_budget_ms,
        min_full_k_budget_ms=cfg.resilience.min_full_k_budget_ms,
        embed_retry=policy_from_config("embed"),
        search_retry=policy_from_config("store-search"),
        cache=get_retrieval_cache(),
        cache_serve_stale=cfg.cache.serve_stale,
    )


# Like the batcher below: NOT lru_cached, so reset_factories can drop the
# cache (and its device ring) and a disabled config caches "off" without
# pinning a dead object.
_CACHE_LOCK = threading.Lock()
_CACHE_STATE: dict = {"set": False, "cache": None}


def get_retrieval_cache():
    """Process-wide two-tier result cache, or ``None`` when disabled.

    Shared by the retriever (exact + semantic retrieval tiers), the chain
    (pre-batcher exact check + answer replay), and ``/metrics`` (entry
    gauge via :func:`peek_retrieval_cache`).
    """
    with _CACHE_LOCK:
        if _CACHE_STATE["set"]:
            return _CACHE_STATE["cache"]
        cfg = get_config()
        cache = None
        if cfg.cache.enabled:
            from generativeaiexamples_tpu.cache.core import RetrievalCache

            cache = RetrievalCache(
                cfg.embeddings.dimensions,
                max_entries=cfg.cache.max_entries,
                semantic_entries=cfg.cache.semantic_entries,
                similarity_threshold=cfg.cache.similarity_threshold,
                semantic_enabled=cfg.cache.semantic_enabled,
            )
        _CACHE_STATE.update(set=True, cache=cache)
        return cache


def peek_retrieval_cache():
    """The live cache if one was ever built, else None — the /metrics
    entry gauge must not instantiate anything."""
    with _CACHE_LOCK:
        return _CACHE_STATE["cache"]


# The retrieval micro-batcher is NOT lru_cached: reset_factories must be
# able to close the old worker thread, and a disabled config should cache
# "off" without holding a dead object.
_BATCHER_LOCK = threading.Lock()
_BATCHER_STATE: dict = {"set": False, "batcher": None}


def get_retrieval_batcher():
    """Process-wide micro-batcher over ``get_retriever().retrieve_many``.

    Items are ``(query, top_k, degrade_log, cache_log, trace)`` tuples;
    concurrent server handlers submitting within one ``batch_wait_ms``
    window share a single embed → search → rerank dispatch chain.  Each
    item carries its request's :class:`DegradeLog`, :class:`CacheLog`
    and :class:`RequestTrace` (the batcher worker runs outside the
    request's contextvars scope) so a batch-level degradation — or a
    per-member cache hit, or a shared stage timing — marks that member's
    response; deadlines ride the MicroBatcher queue entries and the
    batch runs under the loosest member's budget.  Returns ``None`` when
    ``retriever.batch_max_size`` <= 1 (batching disabled).
    """
    with _BATCHER_LOCK:
        if _BATCHER_STATE["set"]:
            return _BATCHER_STATE["batcher"]
        cfg = get_config()
        batcher = None
        if cfg.retriever.batch_max_size > 1:
            from generativeaiexamples_tpu.engine.microbatch import MicroBatcher

            def _retrieve_batch(items):
                retriever = get_retriever()
                ks = [k for _, k, _, _, _ in items]
                # One shared search at the widest k; each caller keeps its
                # own prefix (top-k_i of top-k_max == top-k_i).
                many = retriever.retrieve_many(
                    [q for q, _, _, _, _ in items],
                    top_k=max(ks),
                    degrade_logs=[log for _, _, log, _, _ in items],
                    cache_logs=[clog for _, _, _, clog, _ in items],
                    traces=[trace for _, _, _, _, trace in items],
                )
                return [
                    hits[:k] for hits, (_, k, _, _, _) in zip(many, items)
                ]

            batcher = MicroBatcher(
                _retrieve_batch,
                max_batch=cfg.retriever.batch_max_size,
                max_wait_ms=cfg.retriever.batch_wait_ms,
                name="rag-retrieve",
            )
        _BATCHER_STATE.update(set=True, batcher=batcher)
        return batcher


# Like the batcher: not lru_cached, so reset_factories can close the old
# worker threads instead of leaking them.
_INGEST_LOCK = threading.Lock()
_INGEST_STATE: dict = {"pipeline": None}


def get_ingest_pipeline():
    """Process-wide bulk-ingestion pipeline (``ingest/pipeline.py``) over
    the singleton splitter → embedder → store stack.

    The staged path the chain server's ``POST /documents/bulk`` uses:
    parse/split on a CPU pool, chunks coalesced into shared embed
    dispatches, O(new rows) incremental store appends.
    """
    with _INGEST_LOCK:
        if _INGEST_STATE["pipeline"] is not None:
            return _INGEST_STATE["pipeline"]
        from generativeaiexamples_tpu.ingest.loaders import load_document
        from generativeaiexamples_tpu.ingest.pipeline import IngestPipeline
        from generativeaiexamples_tpu.retrieval.base import Chunk

        cfg = get_config()

        def _parse(path: str, filename: str) -> list[Chunk]:
            pieces = get_splitter().split(load_document(path))
            return [Chunk(text=p, source=filename) for p in pieces]

        journal = None
        delete_source_fn = None
        durable_flush_fn = None
        if cfg.durability.enabled:
            import os

            from generativeaiexamples_tpu.durability.journal import IngestJournal
            from generativeaiexamples_tpu.durability.store import DurableVectorStore

            os.makedirs(cfg.durability.directory, exist_ok=True)
            journal = IngestJournal(
                os.path.join(cfg.durability.directory, "ingest-journal.log")
            )
            store = get_store()
            delete_source_fn = store.delete_source
            if isinstance(store, DurableVectorStore):
                durable_flush_fn = store.flush

        from generativeaiexamples_tpu.retrieval.fabric.collections import (
            DEFAULT_COLLECTION,
        )

        def _admit(chunks, embs):
            # Quota gate at ingest admission: refuse BEFORE the store
            # mutates (CollectionQuotaExceeded isolates to the file).
            get_collection_manager().admit(
                DEFAULT_COLLECTION,
                len(chunks),
                sum(len(e) * 4 for e in embs),
            )

        pipeline = IngestPipeline(
            parse_fn=_parse,
            embed_fn=lambda texts: get_embedder().embed_documents(texts),
            append_fn=lambda chunks, embs: get_store().add(chunks, embs),
            admit_fn=_admit,
            parse_workers=cfg.ingest.parse_workers,
            embed_batch_chunks=cfg.ingest.embed_batch_chunks,
            append_batch_chunks=cfg.ingest.append_batch_chunks,
            queue_depth=cfg.ingest.queue_depth,
            delete_files=True,  # bulk uploads stream to unique temp paths
            journal=journal,
            delete_source_fn=delete_source_fn,
            durable_flush_fn=durable_flush_fn,
        )
        _INGEST_STATE["pipeline"] = pipeline
        if journal is not None and cfg.durability.resume_jobs:
            try:
                journal.compact()
                resumed = pipeline.resume()
                if resumed:
                    logger.info("resumed %d interrupted ingest job(s)", len(resumed))
            except Exception:
                logger.exception("ingest job resume failed")
        return pipeline


def peek_ingest_pipeline():
    """The live pipeline if one was ever built, else None — /metrics must
    export ingest_* zeros without instantiating the embedder stack."""
    with _INGEST_LOCK:
        return _INGEST_STATE["pipeline"]


@functools.lru_cache(maxsize=1)
def get_reranker():
    cfg = get_config()
    engine = cfg.ranking.model_engine.lower()
    if engine in ("", "none"):
        return None
    if engine == "tpu":
        from generativeaiexamples_tpu.engine.reranker import TPUReranker
        from generativeaiexamples_tpu.engine.tokenizer import get_tokenizer
        from generativeaiexamples_tpu.engine.weights import (
            bert_config_from_hf,
            load_hf_cross_encoder,
            weights_dir_for,
        )

        ckpt_dir = weights_dir_for(cfg.ranking.model_name)
        if ckpt_dir:
            bcfg = bert_config_from_hf(ckpt_dir)
            params, head = load_hf_cross_encoder(bcfg, ckpt_dir)
            return TPUReranker(
                bcfg, params, head, tokenizer=get_tokenizer(ckpt_dir)
            )
        return TPUReranker()
    raise ValueError(f"unknown ranking.model_engine {cfg.ranking.model_engine!r}")


def shutdown_durability() -> None:
    """Graceful-shutdown hook: drain queued ingest work, close the journal,
    flush the WAL and (per ``durability.final_snapshot_on_shutdown``) cut a
    final snapshot so the next boot replays nothing.

    Safe to call when durability is disabled or nothing was instantiated —
    it only touches singletons that already exist."""
    from generativeaiexamples_tpu.durability.store import DurableVectorStore

    pipeline = peek_ingest_pipeline()
    if pipeline is not None:
        try:
            pipeline.close()
        except Exception:
            logger.exception("ingest pipeline drain failed during shutdown")
        journal = getattr(pipeline, "journal", None)
        if journal is not None:
            try:
                journal.close()
            except Exception:
                logger.exception("ingest journal close failed during shutdown")
    store = peek_store()
    if isinstance(store, DurableVectorStore):
        try:
            store.close(
                final_snapshot=get_config().durability.final_snapshot_on_shutdown
            )
        except Exception:
            logger.exception("durable store close failed during shutdown")


def reset_factories() -> None:
    """Testing hook: drop all singletons (pairs with reset_config_cache)."""
    from generativeaiexamples_tpu.cache.metrics import reset_cache_metrics
    from generativeaiexamples_tpu.durability.metrics import reset_durability_metrics
    from generativeaiexamples_tpu.durability.store import DurableVectorStore
    from generativeaiexamples_tpu.obs import reset_obs
    from generativeaiexamples_tpu.resilience.metrics import reset_resilience

    reset_resilience()
    reset_cache_metrics()
    reset_durability_metrics()
    reset_obs()
    with _CACHE_LOCK:
        _CACHE_STATE.update(set=False, cache=None)
    with _BATCHER_LOCK:
        batcher = _BATCHER_STATE["batcher"]
        _BATCHER_STATE.update(set=False, batcher=None)
    if batcher is not None:
        batcher.close()
    with _INGEST_LOCK:
        pipeline = _INGEST_STATE["pipeline"]
        _INGEST_STATE["pipeline"] = None
    if pipeline is not None:
        pipeline.close()
        journal = getattr(pipeline, "journal", None)
        if journal is not None:
            journal.close()
    with _COLLECTIONS_LOCK:
        manager = _COLLECTIONS_STATE["manager"]
        _COLLECTIONS_STATE["manager"] = None
    if manager is not None:
        manager.close()
    store = peek_store()
    if isinstance(store, DurableVectorStore):
        # No final snapshot on reset: tests exercising recovery rely on
        # the WAL tail staying exactly as the scenario left it.
        store.close(final_snapshot=False)
    # A sharded singleton owns fan-out worker threads; stop them.
    from generativeaiexamples_tpu.retrieval.fabric.sharded import (
        ShardedVectorStore,
    )

    inner = getattr(store, "_inner", store)
    if isinstance(inner, ShardedVectorStore):
        inner.close()
    for fn in (
        get_chat_llm,
        get_embedder,
        get_store,
        get_memory_store,
        get_splitter,
        get_reranker,
        get_retriever,
    ):
        fn.cache_clear()
