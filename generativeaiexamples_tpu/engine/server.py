"""OpenAI-compatible serving front for the TPU engine.

The replacement for the reference's model-serving containers — NIM LLM
(OpenAI ``/v1/chat/completions``, ``docker-compose-nim-ms.yaml:2-22``),
NeMo Retriever embedding (``/v1/embeddings``, ``:24-57``) and reranking
(``/v1/ranking``, ``:59-84``) — as one aiohttp service over the in-process
scheduler, embedder, and reranker.  Existing OpenAI clients (including our
own ``OpenAIChatLLM`` connector and the reference's ChatNVIDIA) work
unchanged against it.

Also serves ``/v1/models``, ``/health`` (real liveness: degraded + 503
when the tick thread dies or a replica is unhealthy), and
Prometheus-style ``/metrics`` (tokens/sec, TTFT, slot occupancy,
rejections — the serving metrics the reference lacks in-repo, SURVEY.md
§5.5; with ``--replicas N`` also a per-replica breakdown).

Scale-out: ``--replicas N --routing-policy prefix`` serves through an
``engine.replica.EnginePool`` — N data-parallel scheduler replicas (each
on its own mesh slice on multi-chip hosts) behind a prefix-affinity
router with health-checked failover and ``/admin/drain``
(``docs/replica-routing.md``).
"""

from __future__ import annotations

import asyncio
import dataclasses
import json
import os
import time
import uuid
from typing import Any, Optional

from aiohttp import web

from generativeaiexamples_tpu.core.logging import get_logger
from generativeaiexamples_tpu.engine.sampler import SamplingParams
from generativeaiexamples_tpu.engine.scheduler import (
    DISPATCH_STAGES,
    STARVED_PHASES,
    TICK_PHASES,
    Request,
    Scheduler,
)

# Profiler endpoints live in ``obs/profiler.py`` so the chain server can
# register the same handlers; these re-exports keep this module's
# long-standing public names.
from generativeaiexamples_tpu.obs.profiler import (
    PROFILER_DIR_ENV,
    PROFILER_ENV,
    handle_profiler_start,
    handle_profiler_stop,
    profiler_enabled,
)

logger = get_logger(__name__)

SCHED_KEY = web.AppKey("scheduler", object)
TOKENIZER_KEY = web.AppKey("tokenizer", object)
EMBEDDER_KEY = web.AppKey("embedder", object)
RERANKER_KEY = web.AppKey("reranker", object)
MODEL_KEY = web.AppKey("model_name", str)


def _now() -> int:
    return int(time.time())


class _TokenBridge:
    """Scheduler-thread callbacks -> asyncio queue."""

    def __init__(self, loop: asyncio.AbstractEventLoop) -> None:
        self.loop = loop
        self.queue: asyncio.Queue = asyncio.Queue()

    def on_token(self, tid: int) -> None:
        self.loop.call_soon_threadsafe(self.queue.put_nowait, ("token", tid))

    def on_done(self, reason: str) -> None:
        self.loop.call_soon_threadsafe(self.queue.put_nowait, ("done", reason))


def _decode_stream(tokenizer):
    """Incremental byte-safe detokenizer closure."""
    import codecs

    decoder = codecs.getincrementaldecoder("utf-8")(errors="replace")
    byte_mode = getattr(tokenizer, "vocab_size", 0) == 259

    def piece(tid: int, final: bool = False) -> str:
        if final:
            return decoder.decode(b"", final=True) if byte_mode else ""
        if byte_mode:
            return decoder.decode(bytes([tid])) if tid < 256 else ""
        return tokenizer.decode([tid])

    return piece


def _add_lifecycle_stages(request: web.Request, req: "Request") -> None:
    """Where a finished generation's time went, from the scheduler's
    stamps on ``req``: ``queue_wait`` (submit to slot claim), ``prefill``
    (claim to first token fetched) and ``decode`` (first token to now) on
    the request's trace — one ``add_stage`` each, at the end, not per
    token.  A request that never reached a stamp ends at the last one."""
    trace = request.get(TRACE_KEY)
    if trace is None:
        return
    marks = [
        ("queue_wait", req.submitted_at),
        ("prefill", req.claimed_at),
        ("decode", req.first_token_at),
    ]
    ends = [t for _, t in marks[1:]] + [time.perf_counter()]
    for (stage, start), end in zip(marks, ends):
        if start is None or end is None:
            break
        trace.add_stage(stage, (end - start) * 1000.0, start=start)


async def _stream_generation(
    request: web.Request,
    scheduler: "Scheduler",
    req: "Request",
    bridge: "_TokenBridge",
    piece,
    stop: list[str],
    make_chunk,
    preamble: Optional[bytes] = None,
) -> web.StreamResponse:
    """Shared SSE loop for both completion surfaces.

    ``make_chunk(text_or_None, finish)`` formats one SSE event; ``None``
    text means a finish-only event.  Handles stop-sequence truncation
    (slot freed early via cancel), the trailing decoder flush, and
    cancel-on-disconnect.
    """
    resp = web.StreamResponse(
        status=200, headers={"Content-Type": "text/event-stream"}
    )
    await resp.prepare(request)
    if preamble is not None:
        await resp.write(preamble)
    emitted = ""
    stopped = False
    completed = False
    try:
        while True:
            kind, value = await bridge.queue.get()
            if kind == "done":
                tail = piece(0, final=True)
                if tail and not stopped:
                    await resp.write(make_chunk(tail, None))
                finish = "stop" if (stopped or value == "cancelled") else value
                await resp.write(make_chunk(None, finish))
                await resp.write(b"data: [DONE]\n\n")
                completed = True
                break
            if stopped:
                continue
            text = piece(value)
            if not text:
                continue
            emitted += text
            cut = _find_stop(emitted, stop)
            if cut is not None:
                overshoot = len(emitted) - cut
                if len(text) > overshoot:
                    await resp.write(
                        make_chunk(text[: len(text) - overshoot], None)
                    )
                stopped = True
                # The request is satisfied; free the slot now instead of
                # decoding to max_tokens.
                scheduler.cancel(req.id)
                continue
            await resp.write(make_chunk(text, None))
    finally:
        # Client disconnects release the slot too.
        if not completed:
            scheduler.cancel(req.id)
        _add_lifecycle_stages(request, req)
    await resp.write_eof()
    return resp


async def _aggregate_generation(
    bridge: "_TokenBridge", piece, stop: list[str], scheduler, request_id: str
) -> tuple[str, int, str]:
    """Non-streaming path: collect the full completion text.

    Mirrors the streaming handler's slot hygiene: a matched stop sequence
    cancels the request immediately (no decoding on to max_tokens), and
    cancellation also runs on the way out if the collection loop dies
    early (client disconnect closing the handler task, callback errors) —
    otherwise the slot would keep decoding with nobody listening.
    """
    parts: list[str] = []
    emitted = ""  # incremental accumulation; re-joining per token is O(n^2)
    n_tokens = 0
    finish = "stop"
    completed = False
    matched_stop = False
    try:
        while True:
            kind, value = await bridge.queue.get()
            if kind == "done":
                finish = value
                tail = piece(0, final=True)
                if tail:
                    parts.append(tail)
                completed = True
                break
            text_piece = piece(value)
            parts.append(text_piece)
            emitted += text_piece
            # Tokens drained after a stop-sequence match are discarded by
            # the cut below; counting them would make usage overstate the
            # returned completion.
            if not matched_stop:
                n_tokens += 1
            if (
                stop
                and not matched_stop
                and _find_stop(emitted, stop) is not None
            ):
                matched_stop = True
                # Satisfied: free the slot now; keep draining the bridge
                # until the cancel lands so the queue does not build up.
                scheduler.cancel(request_id)
    finally:
        if not completed:
            scheduler.cancel(request_id)
    text = "".join(parts)
    cut = _find_stop(text, stop)
    if cut is not None:
        text = text[:cut]
        finish = "stop"
    return text, n_tokens, finish


async def handle_chat_completions(request: web.Request) -> web.StreamResponse:
    try:
        body = await request.json()
        messages = [(m["role"], m["content"]) for m in body["messages"]]
    except (json.JSONDecodeError, KeyError, TypeError) as exc:
        return web.json_response({"error": {"message": str(exc)}}, status=422)

    scheduler: Scheduler = request.app[SCHED_KEY]  # type: ignore[assignment]
    tokenizer = request.app[TOKENIZER_KEY]
    model = request.app[MODEL_KEY]
    stream = bool(body.get("stream", False))
    sampling = SamplingParams(
        temperature=float(body.get("temperature", 0.2)),
        top_p=float(body.get("top_p", 0.7)),
        top_k=int(body.get("top_k", 0)),
        max_tokens=int(body.get("max_tokens", 1024)),
    )
    prompt_ids = tokenizer.apply_chat_template(messages)

    loop = asyncio.get_running_loop()
    bridge = _TokenBridge(loop)
    req = Request(
        token_ids=list(prompt_ids),
        sampling=sampling,
        on_token=bridge.on_token,
        on_done=bridge.on_done,
        eos_id=tokenizer.eos_id,
        id=f"chatcmpl-{uuid.uuid4().hex[:24]}",
        # Conversation key for KV-prefix reuse across turns: the OpenAI
        # "user" field, or an explicit session_id extension.
        session_id=str(body.get("session_id") or body.get("user") or ""),
        # Non-streaming responses tolerate a duplicated copy (the pool
        # dedups by first response); a stream must stay single-sourced.
        hedgeable=not stream,
    )
    if not scheduler.submit(req):
        # Admission queue full: shed load so accepted requests keep
        # bounded TTFT (the NIM/Triton-style backpressure contract).
        return _overloaded_response(scheduler)
    piece = _decode_stream(tokenizer)

    stop = body.get("stop") or []
    if isinstance(stop, str):
        stop = [stop]

    if stream:

        def delta_chunk(delta: dict, finish: Optional[str]) -> bytes:
            payload = {
                "id": req.id,
                "object": "chat.completion.chunk",
                "created": _now(),
                "model": model,
                "choices": [
                    {"index": 0, "delta": delta, "finish_reason": finish}
                ],
            }
            return f"data: {json.dumps(payload)}\n\n".encode()

        def chunk(text: Optional[str], finish: Optional[str]) -> bytes:
            return delta_chunk({} if text is None else {"content": text}, finish)

        return await _stream_generation(
            request,
            scheduler,
            req,
            bridge,
            piece,
            stop,
            chunk,
            preamble=delta_chunk({"role": "assistant"}, None),
        )

    text, n_tokens, finish = await _aggregate_generation(
        bridge, piece, stop, scheduler, req.id
    )
    _add_lifecycle_stages(request, req)
    if finish == "error":
        return _retryable_error_response()
    return web.json_response(
        {
            "id": req.id,
            "object": "chat.completion",
            "created": _now(),
            "model": model,
            "choices": [
                {
                    "index": 0,
                    "message": {"role": "assistant", "content": text},
                    "finish_reason": finish,
                }
            ],
            "usage": {
                "prompt_tokens": len(prompt_ids),
                "completion_tokens": n_tokens,
                "total_tokens": len(prompt_ids) + n_tokens,
            },
        }
    )


def _find_stop(text: str, stop: list[str]) -> Optional[int]:
    cuts = [text.find(s) for s in stop if s and text.find(s) >= 0]
    return min(cuts) if cuts else None


def _overloaded_response(scheduler) -> web.Response:
    """429 for a full admission queue, with a ``Retry-After`` hint sized
    from the actual backlog: queued requests × smoothed tick latency is
    roughly how long the queue needs to drain one slot's worth of work
    (clamped to [1, 30] s; matches the breaker 503's Retry-After idiom)."""
    retry_after = 1.0
    try:
        snap = scheduler.stats.snapshot()
        # Token-normalized tick latency when available: a tick whose
        # drafts were accepted emits up to two tokens a step, so its raw
        # wall time over-estimates drain time by the acceptance multiple.
        tick_ms = float(
            snap.get("tick_ms_norm_ewma", 0.0)
            or snap.get("tick_ms_ewma", 0.0)
        )
        retry_after = 1.0 + float(snap.get("queued", 0)) * tick_ms / 1000.0
    except Exception:
        pass
    return web.json_response(
        {
            "error": {
                "message": "engine overloaded: admission queue full",
                "type": "overloaded_error",
                "code": 429,
            }
        },
        status=429,
        headers={
            "Retry-After": str(max(1, min(30, round(retry_after)))),
        },
    )


def _retryable_error_response() -> web.Response:
    """A non-streamed generation died mid-flight (replica failover, tick
    fault): nothing was delivered, so the client can simply retry — 503
    is the idiomatic 'retry me' signal.  Streaming responses instead end
    with ``finish_reason: "error"`` since bytes already went out."""
    return web.json_response(
        {
            "error": {
                "message": "generation failed mid-flight (replica "
                "failover or engine fault); safe to retry",
                "type": "engine_error",
                "code": 503,
            }
        },
        status=503,
    )


async def handle_completions(request: web.Request) -> web.StreamResponse:
    """OpenAI legacy ``/v1/completions`` (raw prompt, no chat template) —
    NIM exposes both surfaces; some reference tooling uses this one."""
    try:
        body = await request.json()
        prompt = body["prompt"]
    except (json.JSONDecodeError, KeyError, TypeError) as exc:
        return web.json_response({"error": {"message": str(exc)}}, status=422)

    scheduler: Scheduler = request.app[SCHED_KEY]  # type: ignore[assignment]
    tokenizer = request.app[TOKENIZER_KEY]
    model = request.app[MODEL_KEY]

    # OpenAI prompt shapes: a string, a token-id list, a 1-element list of
    # either.  Multi-prompt batches (one choice per prompt) are not
    # supported — reject loudly rather than silently answering the first.
    if isinstance(prompt, list) and len(prompt) == 1:
        prompt = prompt[0]
    if isinstance(prompt, str):
        prompt_ids = tokenizer.encode(prompt, add_bos=True)
    elif isinstance(prompt, list) and prompt and all(
        isinstance(t, int) for t in prompt
    ):
        prompt_ids = list(prompt)
    else:
        return web.json_response(
            {
                "error": {
                    "message": "prompt must be a string or a token-id "
                    "list; multi-prompt batches are not supported"
                }
            },
            status=422,
        )

    stream = bool(body.get("stream", False))
    sampling = SamplingParams(
        temperature=float(body.get("temperature", 0.2)),
        top_p=float(body.get("top_p", 0.7)),
        top_k=int(body.get("top_k", 0)),
        max_tokens=int(body.get("max_tokens", 16)),
    )

    loop = asyncio.get_running_loop()
    bridge = _TokenBridge(loop)
    req = Request(
        token_ids=list(prompt_ids),
        sampling=sampling,
        on_token=bridge.on_token,
        on_done=bridge.on_done,
        eos_id=tokenizer.eos_id,
        id=f"cmpl-{uuid.uuid4().hex[:24]}",
        session_id=str(body.get("session_id") or body.get("user") or ""),
        hedgeable=not stream,
    )
    if not scheduler.submit(req):
        return _overloaded_response(scheduler)
    piece = _decode_stream(tokenizer)
    stop = body.get("stop") or []
    if isinstance(stop, str):
        stop = [stop]

    if stream:

        def chunk(text: Optional[str], finish: Optional[str]) -> bytes:
            payload = {
                "id": req.id,
                "object": "text_completion",
                "created": _now(),
                "model": model,
                "choices": [
                    {"index": 0, "text": text or "", "finish_reason": finish}
                ],
            }
            return f"data: {json.dumps(payload)}\n\n".encode()

        return await _stream_generation(
            request, scheduler, req, bridge, piece, stop, chunk
        )

    text, n_tokens, finish = await _aggregate_generation(
        bridge, piece, stop, scheduler, req.id
    )
    _add_lifecycle_stages(request, req)
    if finish == "error":
        return _retryable_error_response()
    return web.json_response(
        {
            "id": req.id,
            "object": "text_completion",
            "created": _now(),
            "model": model,
            "choices": [{"index": 0, "text": text, "finish_reason": finish}],
            "usage": {
                "prompt_tokens": len(prompt_ids),
                "completion_tokens": n_tokens,
                "total_tokens": len(prompt_ids) + n_tokens,
            },
        }
    )


async def handle_embeddings(request: web.Request) -> web.Response:
    try:
        body = await request.json()
        inputs = body["input"]
        if isinstance(inputs, str):
            inputs = [inputs]
    except (json.JSONDecodeError, KeyError, TypeError) as exc:
        return web.json_response({"error": {"message": str(exc)}}, status=422)
    embedder = request.app[EMBEDDER_KEY]
    if embedder is None:
        return web.json_response(
            {"error": {"message": "no embedder configured"}}, status=501
        )
    input_type = body.get("input_type", "passage")
    loop = asyncio.get_running_loop()
    if input_type == "query":
        # Single-query requests go through embed_query so that, when the
        # server runs with --embed-max-batch (embedder is a
        # BatchedEmbedder), CONCURRENT requests coalesce into one forward.
        # Multi-query requests are already a batch: one embed_queries
        # dispatch, no wait window.
        if len(inputs) == 1:
            vectors = await loop.run_in_executor(
                None, lambda: [embedder.embed_query(inputs[0])]
            )
        elif hasattr(embedder, "embed_queries"):
            vectors = await loop.run_in_executor(
                None, embedder.embed_queries, inputs
            )
        else:
            vectors = await loop.run_in_executor(
                None, lambda: [embedder.embed_query(t) for t in inputs]
            )
    else:
        vectors = await loop.run_in_executor(
            None, embedder.embed_documents, inputs
        )
    return web.json_response(
        {
            "object": "list",
            "model": body.get("model", "arctic-embed-l"),
            "data": [
                {"object": "embedding", "index": i, "embedding": v}
                for i, v in enumerate(vectors)
            ],
            "usage": {"prompt_tokens": 0, "total_tokens": 0},
        }
    )


async def handle_ranking(request: web.Request) -> web.Response:
    """NeMo-Retriever-style reranking: {query:{text}, passages:[{text}]}."""
    try:
        body = await request.json()
        query = body["query"]["text"] if isinstance(body.get("query"), dict) else body["query"]
        passages = [
            p["text"] if isinstance(p, dict) else p for p in body["passages"]
        ]
    except (json.JSONDecodeError, KeyError, TypeError) as exc:
        return web.json_response({"error": {"message": str(exc)}}, status=422)
    reranker = request.app[RERANKER_KEY]
    if reranker is None:
        return web.json_response(
            {"error": {"message": "no reranker configured"}}, status=501
        )
    loop = asyncio.get_running_loop()
    scores = await loop.run_in_executor(None, reranker.score, query, passages)
    order = sorted(range(len(scores)), key=lambda i: -scores[i])
    return web.json_response(
        {"rankings": [{"index": i, "logit": scores[i]} for i in order]}
    )


async def handle_models(request: web.Request) -> web.Response:
    return web.json_response(
        {
            "object": "list",
            "data": [
                {
                    "id": request.app[MODEL_KEY],
                    "object": "model",
                    "created": _now(),
                    "owned_by": "generativeaiexamples-tpu",
                }
            ],
        }
    )


TRACE_KEY = "gaie_engine_request_trace"


@web.middleware
async def engine_telemetry_middleware(
    request: web.Request, handler
) -> web.StreamResponse:
    """Engine-side counterpart of the chain server's telemetry shell.

    Joins the upstream W3C trace when the caller sent ``traceparent`` /
    ``X-Request-Id`` (every engine-bound client injects via
    ``core.tracing.inject_trace_headers``), so the engine's flight
    recorder holds a ``RequestTrace`` with the SAME request id as the
    chain server's — ``/debug/requests`` on either process lines up."""
    from generativeaiexamples_tpu.core.tracing import extract_trace_headers
    from generativeaiexamples_tpu.obs.recorder import get_flight_recorder
    from generativeaiexamples_tpu.obs.trace import RequestTrace, new_request_id
    from generativeaiexamples_tpu.server.app import (
        REQUEST_ID_HEADER,
        _feed_fleet_telemetry,
        _obs_enabled,
    )

    req_id, parent_span = extract_trace_headers(request.headers)
    propagated = bool(req_id)
    req_id = req_id or new_request_id()
    trace: Optional[RequestTrace] = None
    if _obs_enabled():
        trace = RequestTrace(request_id=req_id, route=request.path)
        if parent_span:
            trace.set_attr("parent_span_id", parent_span)
        if propagated:
            trace.set_attr("propagated", True)
        request[TRACE_KEY] = trace

    def finalize(status: Optional[int]) -> None:
        if trace is None:
            return
        snap = trace.finish(status=status)
        get_flight_recorder().record(snap)
        try:
            _feed_fleet_telemetry(snap, prefix="engine")
        except Exception:  # telemetry must never fail a request
            logger.exception("engine fleet telemetry feed failed")

    try:
        resp = await handler(request)
    except web.HTTPException as exc:
        finalize(exc.status)
        exc.headers[REQUEST_ID_HEADER] = req_id
        raise
    except Exception as exc:
        if trace is not None:
            trace.mark_error(exc)
        finalize(500)
        raise
    finalize(resp.status)
    if not resp.prepared:
        resp.headers[REQUEST_ID_HEADER] = req_id
    return resp


async def handle_health(request: web.Request) -> web.Response:
    """Liveness that actually checks the engine: a dead scheduler tick
    thread or an unhealthy pool replica reports ``degraded`` with a 503
    (load balancers and compose healthchecks key off the status code),
    instead of the old unconditional 200.  A firing SLO fast-burn alert
    also reports ``degraded`` — at 200, since the process itself is fine
    and serving a drained replica beats serving none."""
    from generativeaiexamples_tpu.obs.slo import slo_health

    engine = request.app[SCHED_KEY]
    healthy_fn = getattr(engine, "healthy", None)
    ok = bool(healthy_fn()) if callable(healthy_fn) else True
    slo = slo_health()
    degraded = (not ok) or bool(slo.get("degraded"))
    body: dict = {
        "message": "Service is up." if not degraded else "Service is degraded.",
        "status": "ok" if not degraded else "degraded",
        "slo": slo,
    }
    states_fn = getattr(engine, "replica_states", None)
    if callable(states_fn):
        body["replicas"] = states_fn()
    from generativeaiexamples_tpu.utils.jax_runtime import runtime_report

    body["runtime"] = runtime_report()
    return web.json_response(body, status=200 if ok else 503)


async def handle_metrics(request: web.Request) -> web.Response:
    engine = request.app[SCHED_KEY]
    snap = engine.stats.snapshot()
    lines = [
        "# TYPE engine_requests_total counter",
        f"engine_requests_total {snap['requests_total']}",
        "# TYPE engine_tokens_total counter",
        f"engine_tokens_total {snap['tokens_total']}",
        "# TYPE engine_ttft_avg_ms gauge",
        f"engine_ttft_avg_ms {snap['ttft_avg_ms']:.2f}",
        "# TYPE engine_active_slots gauge",
        f"engine_active_slots {snap['active_slots']}",
        "# TYPE engine_queued_requests gauge",
        f"engine_queued_requests {snap['queued']}",
        # Admission-control sheds (the 429 path): for a pool this counts
        # CLIENT-VISIBLE rejections (every replica queue full), not
        # per-replica attempts that a sibling absorbed.
        "# TYPE engine_rejected_total counter",
        f"engine_rejected_total {snap['rejected_total']}",
        "# TYPE engine_prefix_hits_total counter",
        f"engine_prefix_hits_total {snap['prefix_hits']}",
        "# TYPE engine_prefix_tokens_reused_total counter",
        f"engine_prefix_tokens_reused_total {snap['prefix_tokens_reused']}",
        "# TYPE engine_shared_prefix_hits_total counter",
        f"engine_shared_prefix_hits_total {snap['shared_prefix_hits']}",
        "# TYPE engine_prefill_chunks_total counter",
        f"engine_prefill_chunks_total {snap['prefill_chunks']}",
        # Of those, dispatched behind a decode chunk before the host
        # blocked on its tokens.
        "# TYPE engine_prefill_chunks_ahead_total counter",
        f"engine_prefill_chunks_ahead_total {snap.get('prefill_chunks_ahead', 0)}",
        # Programs dispatched for those chunks (one may carry the chunks
        # of several slots): chunks / programs = chunks a weight pass.
        "# TYPE engine_prefill_chunk_programs_total counter",
        f"engine_prefill_chunk_programs_total {snap.get('prefill_chunk_programs', 0)}",
        "# TYPE engine_decode_chunks_ahead_total counter",
        f"engine_decode_chunks_ahead_total {snap.get('decode_chunks_ahead', 0)}",
        "# TYPE engine_decode_tokens_dropped_total counter",
        f"engine_decode_tokens_dropped_total {snap.get('decode_tokens_dropped', 0)}",
        "# TYPE engine_spec_rounds_total counter",
        f"engine_spec_rounds_total {snap['spec_rounds']}",
        "# TYPE engine_spec_tokens_total counter",
        f"engine_spec_tokens_total {snap['spec_tokens']}",
        # From zero whether or not the model drafts, so dashboards need
        # no existence checks: acceptance = accepted/proposed.
        "# TYPE engine_spec_proposed_total counter",
        f"engine_spec_proposed_total {snap.get('spec_proposed', 0)}",
        "# TYPE engine_spec_accepted_total counter",
        f"engine_spec_accepted_total {snap.get('spec_accepted', 0)}",
        "# TYPE engine_spec_acceptance_ewma gauge",
        f"engine_spec_acceptance_ewma {snap.get('spec_acceptance_ewma', 0.0)}",
    ]
    # Where the tick thread's time goes (exclusive phases: the six sum
    # to its wall time), how long it left the device with nothing
    # queued, and the request lifecycle.  ``.get`` keeps engine stubs
    # without these keys exporting zeros.
    lines.append("# TYPE engine_tick_phase_seconds_total counter")
    lines += [
        f'engine_tick_phase_seconds_total{{phase="{p}"}} '
        f"{snap.get(f'tick_phase_{p}_s', 0.0):.6f}"
        for p in TICK_PHASES
    ]
    lines.append("# TYPE engine_device_starved_seconds_total counter")
    lines += [
        f'engine_device_starved_seconds_total{{phase="{p}"}} '
        f"{snap.get(f'device_starved_{p}_s', 0.0):.6f}"
        for p in STARVED_PHASES
    ]
    # Dispatch sites returned from, and the dispatch phase's seconds by
    # stage (its rest: the sites' counters behind their jitted calls).
    lines.append("# TYPE engine_dispatch_sites_total counter")
    lines.append(f"engine_dispatch_sites_total {snap.get('dispatch_sites', 0)}")
    lines.append("# TYPE engine_dispatch_stage_seconds_total counter")
    lines += [
        f'engine_dispatch_stage_seconds_total{{stage="{stage}"}} '
        f"{snap.get(f'dispatch_{stage}_s', 0.0):.6f}"
        for stage in DISPATCH_STAGES
    ]
    # Executables the tick thread asked JAX for, by what the persistent
    # cache said, and their seconds by stage: after warm-up each is a
    # request that waited for a compile (GET /debug/executables names it).
    requested = snap.get("executables_requested", 0)
    hit = snap.get("executables_hit", 0)
    missed = snap.get("executables_missed", 0)
    lines.append("# TYPE engine_executables_total counter")
    lines += [
        f'engine_executables_total{{cache="{cache}"}} {n}'
        for cache, n in (
            ("hit", hit), ("miss", missed), ("off", requested - hit - missed),
        )
    ]
    lines.append("# TYPE engine_executable_seconds_total counter")
    lines += [
        f'engine_executable_seconds_total{{stage="{stage}"}} '
        f"{snap.get(f'executable_{stage}_s', 0.0):.6f}"
        for stage in ("trace", "lower", "backend")
    ]
    for name, key, fmt in (
        ("engine_busy_ticks_total", "busy_ticks", "d"),
        ("engine_queue_wait_seconds_total", "queue_wait_s_sum", ".6f"),
        ("engine_queue_wait_count_total", "queue_wait_count", "d"),
        ("engine_warm_seconds_total", "warm_s_sum", ".6f"),
        ("engine_warm_count_total", "warm_count", "d"),
        ("engine_prompt_tokens_admitted_total", "prompt_tokens_admitted", "d"),
        ("engine_prompts_clipped_total", "prompts_clipped", "d"),
        ("engine_prompt_tokens_clipped_total", "prompt_tokens_clipped", "d"),
        (
            "engine_prefill_tokens_dispatched_total",
            "prefill_tokens_dispatched",
            "d",
        ),
        ("engine_prefill_tokens_padded_total", "prefill_tokens_padded", "d"),
        ("engine_admits_lone_total", "admits_lone", "d"),
        ("engine_admits_batched_total", "admits_batched", "d"),
        ("engine_decode_kv_tokens_read_total", "decode_kv_tokens_read", "d"),
        ("engine_decode_kv_tokens_dense_total", "decode_kv_tokens_dense", "d"),
        ("engine_prefix_tokens_matched_total", "prefix_tokens_matched", "d"),
        ("engine_state_snapshots_saved_total", "state_snapshots_saved", "d"),
        ("engine_state_snapshots_restored_total", "state_snapshots_restored", "d"),
        ("engine_state_snapshots_evicted_total", "state_snapshots_evicted", "d"),
        # What the model's step programs count (an expert model's routing:
        # ops.moe.COUNTERS; the rows its attention layers read, by kind:
        # models.hybrid.ATTN_COUNTERS; a drafting model's verify steps:
        # serving_models.HybridServing.DRAFT_COUNTERS); none for a model
        # that returns none.
        *(
            (f"engine_{key}_total", key, "d")
            for key in sorted(snap)
            if key.startswith(("moe_", "attn_rows_", "draft_", "verify_", "decode_tokens_emitted"))
        ),
    ):
        lines.append(f"# TYPE {name} counter")
        lines.append(f"{name} {format(snap.get(key, 0), fmt)}")
    # Passes of the stack dispatched (a looped stack: ``ut_steps`` a step).
    lines.append("# TYPE engine_stack_passes_total counter")
    lines += [
        f'engine_stack_passes_total{{phase="{phase}"}} '
        f"{snap.get(f'{phase}_stack_passes', 0)}"
        for phase in ("decode", "prefill")
    ]
    for key in (
        "state_snapshot_bytes", "state_bytes_full", "state_bytes_window", "state_bytes_draft",
        "cache_planes", "kv_bytes_per_token",
    ):
        lines.append(f"# TYPE engine_{key} gauge")
        lines.append(f"engine_{key} {snap.get(key, 0)}")
    # Which serving matmul path is live (info-style gauge: every known
    # value exported, the active one carrying 1) — deployments can alert
    # on the fused kernel silently falling back to XLA.  From zero:
    # engines that predate the attribute (or stubs) report 'xla'.
    active_kernel = getattr(engine, "matmul_kernel", None)
    if active_kernel is None:
        for rep in getattr(engine, "replicas", []) or []:
            active_kernel = getattr(rep.scheduler, "matmul_kernel", None)
            if active_kernel is not None:
                break
    active_kernel = active_kernel or "xla"
    lines.append("# TYPE engine_matmul_kernel gauge")
    for kernel in ("xla", "pallas_w8a8"):
        lines.append(
            f'engine_matmul_kernel{{kernel="{kernel}"}} '
            f"{1 if kernel == active_kernel else 0}"
        )
    replicas = snap.get("replicas")
    if replicas is not None:
        lines += [
            "# TYPE engine_router_failovers_total counter",
            f"engine_router_failovers_total {snap['router_failovers_total']}",
            "# TYPE engine_router_requeued_total counter",
            f"engine_router_requeued_total {snap['router_requeued_total']}",
        ]
        lines += [
            "# TYPE engine_router_session_evictions_total counter",
            "engine_router_session_evictions_total "
            f"{snap.get('session_evictions_total', 0)}",
        ]
        per_replica = [
            ("engine_replica_healthy", "gauge", "healthy"),
            ("engine_replica_queued", "gauge", "queued"),
            ("engine_replica_active_slots", "gauge", "active_slots"),
            ("engine_replica_requests_total", "counter", "requests_total"),
            ("engine_replica_rejected_total", "counter", "rejected_total"),
            ("engine_replica_prefix_hits_total", "counter", "prefix_hits"),
            (
                "engine_replica_shared_prefix_hits_total",
                "counter",
                "shared_prefix_hits",
            ),
        ]
        for name, kind, key in per_replica:
            lines.append(f"# TYPE {name} {kind}")
            for rep in replicas:
                lines.append(
                    f'{name}{{replica="{rep["replica"]}"}} {rep[key]}'
                )
    # Embedding micro-batcher series (--embed-max-batch): how many
    # /v1/embeddings query calls shared each device forward.
    embedder = request.app[EMBEDDER_KEY]
    batcher = getattr(embedder, "batcher", None)
    if batcher is not None:
        from generativeaiexamples_tpu.server.app import rag_metrics_lines

        lines += rag_metrics_lines(batcher.stats.snapshot())
    # Vector-store capacity gauges: the engine process hosts the store
    # when serving all-in-one, so capacity planning reads the same
    # rag_store_* series on either /metrics endpoint (zeros before the
    # store singleton exists).
    from generativeaiexamples_tpu.chains.factory import (
        peek_collection_manager,
        peek_store,
    )
    from generativeaiexamples_tpu.retrieval.fabric.metrics import (
        aggregate_capacity_stats,
        fabric_metrics_lines,
    )
    from generativeaiexamples_tpu.server.app import store_metrics_lines

    store = peek_store()
    manager = peek_collection_manager()
    lines += store_metrics_lines(
        aggregate_capacity_stats(store, manager),
        manager.capacity_by_collection() if manager is not None else None,
    )
    # Sharded-fabric + collection families: from-zero on both servers,
    # live when the all-in-one process hosts a fabric store.
    lines += fabric_metrics_lines(store, manager)
    # Pool-size gauges: real sizes for an EnginePool, a pool of one for a
    # bare Scheduler — same family the chain server exports as zeros.
    from generativeaiexamples_tpu.engine.autoscale import pool_metrics_lines

    lines += pool_metrics_lines(engine)
    # Resilience counters + breaker gauges: the engine process runs the
    # same retry/breaker/deadline machinery when serving all-in-one.
    from generativeaiexamples_tpu.resilience.metrics import (
        resilience_metrics_lines,
    )

    lines += resilience_metrics_lines()
    # Per-class admission counters: from-zero on both servers so the
    # shed dashboards scrape one family everywhere.
    from generativeaiexamples_tpu.resilience.admission import (
        admission_metrics_lines,
    )

    lines += admission_metrics_lines()
    # Result-cache counters: same from-zero contract on both servers.
    from generativeaiexamples_tpu.cache.metrics import cache_metrics_lines

    lines += cache_metrics_lines()
    # Stage/request latency histograms: observed wherever the pipeline
    # runs, so the all-in-one process exports them here too.
    from generativeaiexamples_tpu.obs.metrics import (
        engine_tick_metrics_lines,
        obs_metrics_lines,
    )

    lines += obs_metrics_lines()
    # Scheduler tick wall-time histogram (fed by Scheduler._loop).
    lines += engine_tick_metrics_lines()
    # SLO burn-rate gauges: evaluated lazily here (read side), from-zero
    # for every configured route.
    from generativeaiexamples_tpu.obs.slo import slo_metrics_lines

    lines += slo_metrics_lines()
    # WAL / recovery counters: from-zero on both servers, like the rest.
    from generativeaiexamples_tpu.durability.metrics import (
        durability_metrics_lines,
    )

    lines += durability_metrics_lines()
    # Gray-failure layer: hedge counters, ejection transitions, and
    # per-replica brownout scores (from-zero; a bare Scheduler engine
    # exports the zeros).
    from generativeaiexamples_tpu.engine.health import gray_metrics_lines

    lines += gray_metrics_lines(engine)
    return web.Response(text="\n".join(lines) + "\n", content_type="text/plain")


async def handle_admin_replicas(request: web.Request) -> web.Response:
    """Replica-pool introspection: per-replica state + stats."""
    engine = request.app[SCHED_KEY]
    if not hasattr(engine, "replicas"):
        return web.json_response(
            {"error": {"message": "not a replica pool (started with "
                                  "--replicas 1)"}},
            status=501,
        )
    return web.json_response({"replicas": engine.snapshot()["replicas"]})


async def handle_admin_drain(request: web.Request) -> web.Response:
    """``POST /admin/drain?replica=i``: stop placing on replica ``i``,
    migrate its queued requests, let in-flight generations finish, then
    detach it (``engine.replica.EnginePool.drain``)."""
    engine = request.app[SCHED_KEY]
    if not hasattr(engine, "drain"):
        return web.json_response(
            {"error": {"message": "not a replica pool (started with "
                                  "--replicas 1)"}},
            status=501,
        )
    try:
        idx = int(request.query["replica"])
    except (KeyError, ValueError):
        return web.json_response(
            {"error": {"message": "replica=<int> query parameter required"}},
            status=422,
        )
    loop = asyncio.get_running_loop()
    try:
        # drain() may join a detaching replica's tick thread — keep that
        # off the event loop.
        state = await loop.run_in_executor(None, engine.drain, idx)
    except ValueError as exc:
        return web.json_response({"error": {"message": str(exc)}}, status=404)
    return web.json_response({"replica": idx, "state": state})


async def handle_admin_scale(request: web.Request) -> web.Response:
    """``POST /admin/scale?replicas=n``: drive the pool to ``n`` healthy
    replicas by hand (the autoscaler's actuator, exposed for operators
    and the chaos harness).  Scale-down drains the least-loaded replicas;
    scale-up needs the pool's scheduler factory."""
    engine = request.app[SCHED_KEY]
    if not hasattr(engine, "scale_to"):
        return web.json_response(
            {"error": {"message": "not a replica pool (started with "
                                  "--replicas 1 and no --autoscale)"}},
            status=501,
        )
    try:
        n = int(request.query["replicas"])
        if n < 1:
            raise ValueError
    except (KeyError, ValueError):
        return web.json_response(
            {"error": {"message": "replicas=<int >= 1> query parameter "
                                  "required"}},
            status=422,
        )
    loop = asyncio.get_running_loop()
    try:
        # scale_to may compile a new scheduler or join drained replicas'
        # tick threads — keep both off the event loop.
        result = await loop.run_in_executor(None, engine.scale_to, n)
    except RuntimeError as exc:  # no scheduler_factory to grow with
        return web.json_response({"error": {"message": str(exc)}}, status=409)
    return web.json_response(result)


def _query_limit(request: web.Request) -> Optional[int]:
    """``?limit=N`` of a debug endpoint (100 without one); None where it
    is no integer."""
    try:
        return int(request.query.get("limit", "100"))
    except ValueError:
        return None


async def handle_debug_ticks(request: web.Request) -> web.Response:
    """``GET /debug/ticks?limit=N``: the newest N busy ticks of the
    scheduler (``Scheduler.tick_records``), oldest first; for a replica
    pool, of each replica.  What a stall is read from: which phase the
    tick thread was in, and for how long."""
    limit = _query_limit(request)
    if limit is None:
        return web.json_response(
            {"detail": "limit must be an integer"}, status=422
        )
    engine = request.app[SCHED_KEY]
    if hasattr(engine, "replicas"):
        return web.json_response(
            {
                "replicas": [
                    {
                        "replica": rep.idx,
                        "ticks": rep.scheduler.tick_records(limit),
                    }
                    for rep in engine.replicas
                ]
            }
        )
    ticks = engine.tick_records(limit)
    return web.json_response({"ticks": ticks, "count": len(ticks)})


async def handle_debug_executables(request: web.Request) -> web.Response:
    """``GET /debug/executables?limit=N``: the newest N executables this
    process asked JAX for (``utils.jax_runtime.EXECUTABLES``), oldest
    first: which program, found in the cache or built, what each stage
    cost, who asked and, from a tick thread, in which tick and with what
    shapes.  The record is the process's; for a replica pool each
    replica gets the entries its own tick thread asked for."""
    from generativeaiexamples_tpu.utils.jax_runtime import EXECUTABLES

    limit = _query_limit(request)
    if limit is None:
        return web.json_response(
            {"detail": "limit must be an integer"}, status=422
        )
    engine = request.app[SCHED_KEY]
    record = EXECUTABLES.report()
    if hasattr(engine, "replicas"):
        entries = EXECUTABLES.newest()

        def asked_by(idx: int) -> list:
            mine = [e for e in entries if e.get("replica") == idx]
            return mine[-limit:] if limit > 0 else []

        return web.json_response(
            {
                **record,
                "replicas": [
                    {"replica": rep.idx, "entries": asked_by(rep.idx)}
                    for rep in engine.replicas
                ],
            }
        )
    entries = EXECUTABLES.newest(limit)
    return web.json_response(
        {**record, "entries": entries, "count": len(entries)}
    )


def create_engine_app(
    scheduler,
    tokenizer,
    embedder=None,
    reranker=None,
    model_name: str = "llama3-8b",
    enable_profiler: Optional[bool] = None,
) -> web.Application:
    """Build the aiohttp app over one engine object: a single
    ``Scheduler`` or an ``engine.replica.EnginePool`` (``--replicas N``)
    — both expose ``submit``/``cancel``/``stats.snapshot()``/``healthy``,
    so every generation endpoint routes through whichever is given.  The
    pool additionally serves the ``/admin`` replica endpoints."""
    from generativeaiexamples_tpu.server.app import (
        handle_debug_requests,
        handle_debug_timeseries,
    )

    enable_profiler = profiler_enabled(enable_profiler)
    app = web.Application(middlewares=[engine_telemetry_middleware])
    app[SCHED_KEY] = scheduler
    app[TOKENIZER_KEY] = tokenizer
    app[EMBEDDER_KEY] = embedder
    app[RERANKER_KEY] = reranker
    app[MODEL_KEY] = model_name
    app.router.add_post("/v1/chat/completions", handle_chat_completions)
    app.router.add_post("/v1/completions", handle_completions)
    app.router.add_post("/v1/embeddings", handle_embeddings)
    app.router.add_post("/v1/ranking", handle_ranking)
    app.router.add_get("/v1/models", handle_models)
    app.router.add_get("/health", handle_health)
    app.router.add_get("/metrics", handle_metrics)
    app.router.add_get("/admin/replicas", handle_admin_replicas)
    app.router.add_post("/admin/drain", handle_admin_drain)
    app.router.add_post("/admin/scale", handle_admin_scale)
    app.router.add_get("/debug/requests", handle_debug_requests)
    app.router.add_get("/debug/ticks", handle_debug_ticks)
    app.router.add_get("/debug/executables", handle_debug_executables)
    app.router.add_get("/debug/timeseries", handle_debug_timeseries)
    if enable_profiler:
        app.router.add_post("/debug/profiler/start", handle_profiler_start)
        app.router.add_post("/debug/profiler/stop", handle_profiler_stop)
    return app


def drain_engine(engine, timeout: float = 15.0) -> None:
    """Graceful engine retirement for SIGTERM/SIGINT: drain every pool
    replica (queued requests migrate while survivors exist, in-flight
    generations run to completion), wait briefly for detach, then stop
    the tick threads.  A bare ``Scheduler`` just stops."""
    if hasattr(engine, "drain"):
        from generativeaiexamples_tpu.engine.replica import DETACHED, UNHEALTHY

        for i in range(len(engine.replicas)):
            try:
                engine.drain(i)
            except Exception:
                logger.exception("shutdown drain of replica %d failed", i)
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            states = [s["state"] for s in engine.replica_states()]
            if all(s in (DETACHED, UNHEALTHY) for s in states):
                break
            time.sleep(0.05)
    try:
        engine.stop()
    except Exception:
        logger.exception("engine stop failed during shutdown")


def main() -> None:
    """``python -m generativeaiexamples_tpu.engine.server`` entrypoint."""
    import argparse

    from generativeaiexamples_tpu.core.logging import configure_logging
    from generativeaiexamples_tpu.engine.embedder import TPUEmbedder
    from generativeaiexamples_tpu.engine.tokenizer import get_tokenizer
    from generativeaiexamples_tpu.engine.weights import resolve_model_preset
    from generativeaiexamples_tpu.models import bert, llama

    parser = argparse.ArgumentParser(description="TPU model-serving engine")
    parser.add_argument("--host", default="0.0.0.0")
    parser.add_argument("--port", type=int, default=8000)
    parser.add_argument("--model", default="llama-tiny", help="model preset or HF id")
    parser.add_argument("--max-batch", type=int, default=8)
    parser.add_argument("--max-len", type=int, default=2048)
    parser.add_argument("--embedder", default="tiny", choices=["tiny", "arctic", "none"])
    parser.add_argument(
        "--embedder-model",
        default="snowflake/arctic-embed-l",
        help="HF id used to look up converted embedder weights under "
        "$GAIE_WEIGHTS_DIR (the reference's embedding model, "
        "configuration.py:111-125)",
    )
    parser.add_argument(
        "--embed-max-batch",
        type=int,
        default=int(os.environ.get("GAIE_EMBED_MAX_BATCH", "32")),
        help="micro-batch cap for /v1/embeddings query coalescing: up to "
        "this many concurrent single-query requests share one BERT "
        "forward (NIM dynamic-batching parity). 0/1 disables.",
    )
    parser.add_argument(
        "--embed-max-wait-ms",
        type=float,
        default=float(os.environ.get("GAIE_EMBED_MAX_WAIT_MS", "3.0")),
        help="how long a query embedding waits for batch-mates before "
        "its micro-batch dispatches anyway",
    )
    parser.add_argument(
        "--tensor-parallel",
        type=int,
        default=int(os.environ.get("GAIE_TENSOR_PARALLEL", "0")),
        help="chips on the tensor mesh axis (0 = all visible devices; the "
        "INFERENCE_GPU_COUNT equivalent, SURVEY.md §2.9). With --replicas "
        "N the bound applies within each replica's device slice.",
    )
    from generativeaiexamples_tpu.engine.router import POLICIES

    parser.add_argument(
        "--replicas",
        type=int,
        default=int(os.environ.get("GAIE_REPLICAS", "1")),
        help="data-parallel scheduler replicas behind the request router "
        "(engine.replica.EnginePool). On multi-chip hosts each replica "
        "pins to a disjoint mesh slice; on CPU/single-chip they share "
        "the device. 1 = the classic single in-process scheduler.",
    )
    parser.add_argument(
        "--routing-policy",
        default=os.environ.get("GAIE_ROUTING_POLICY", "prefix"),
        choices=list(POLICIES),
        help="replica placement policy: 'prefix' (longest cached-prefix "
        "match via router-side radix mirrors, falling back to "
        "least-loaded — the SGLang-style cache-aware default), "
        "'session' (sticky by conversation id), 'least_loaded', "
        "'round_robin'. Only meaningful with --replicas > 1.",
    )
    parser.add_argument(
        "--autoscale",
        action="store_true",
        default=os.environ.get("GAIE_AUTOSCALE", "") == "1",
        help="run the SLO-driven autoscaler control loop over the replica "
        "pool (engine.autoscale; knobs under the [autoscale] config "
        "section). Implies pool mode even with --replicas 1 so the pool "
        "can grow; autoscaled replicas beyond the initial set share the "
        "visible devices rather than re-partitioning live mesh slices.",
    )
    parser.add_argument(
        "--seed",
        type=int,
        default=0,
        help="seed of the random weights served when no checkpoint is "
        "provisioned, and of the sampler",
    )
    parser.add_argument(
        "--weight-dtype",
        default="bfloat16",
        choices=["bfloat16", "int8"],
        help="projection weights: 'int8' quantizes them per output "
        "channel at load and packs qkv and gate/up (halves decode HBM "
        "traffic; what fits full-depth llama3-8b on one 16 GB chip).",
    )
    parser.add_argument(
        "--kv-dtype",
        default="",
        choices=["", "bfloat16", "int8"],
        help="KV cache storage: 'int8' (per-token-per-head scales) "
        "halves cache HBM and is what the Pallas decode-attention "
        "kernel reads. Empty keeps the model preset's (bfloat16).",
    )
    parser.add_argument(
        "--matmul-kernel",
        default=os.environ.get("GAIE_MATMUL_KERNEL", ""),
        choices=["", "xla", "pallas_w8a8"],
        help="serving matmul path: 'xla' streams weight-only int8 "
        "through XLA's fused convert-dot; 'pallas_w8a8' pre-blocks int8 "
        "projections once at load and decodes through the streaming "
        "W8A8 Pallas kernel (native s8xs8 MXU dot, bit-identical XLA "
        "twin off-TPU). Empty falls back to [llm].matmul_kernel in "
        "config (default xla).",
    )
    parser.add_argument(
        "--prefix-cache",
        default=os.environ.get("GAIE_PREFIX_CACHE", "shared"),
        choices=["shared", "session", "off"],
        help="KV prefix reuse: 'shared' also grafts cached prefixes "
        "across requests/sessions (radix-matched, LRU-evicted — the "
        "RAG shared-system-prompt accelerator); 'session' parks per "
        "conversation only; 'off' disables parking",
    )
    parser.add_argument(
        "--prefill-chunk-tokens",
        type=int,
        default=int(os.environ.get("GAIE_PREFILL_CHUNK_TOKENS", "256")),
        help="split cold prompts longer than this into per-tick prefill "
        "chunks interleaved with decode, bounding running lanes' "
        "inter-token latency during long admissions (0 = monolithic "
        "prefill)",
    )
    from generativeaiexamples_tpu.engine.sampler import exact_sampling_enabled

    parser.add_argument(
        "--exact-sampling",
        action="store_true",
        default=exact_sampling_enabled(),
        help="use exact top-k candidate selection instead of "
        "lax.approx_max_k (~0.95 far-tail recall; see engine.sampler)",
    )
    parser.add_argument("-v", "--verbose", action="count", default=None)
    args = parser.parse_args()
    configure_logging(args.verbose)
    if args.exact_sampling:
        os.environ["GAIE_EXACT_SAMPLING"] = "1"

    preset = resolve_model_preset(args.model)
    from generativeaiexamples_tpu.models import hybrid

    cfg = (hybrid.PRESETS.get(preset) or llama.PRESETS[preset])()
    is_llama = isinstance(cfg, llama.LlamaConfig)
    if is_llama and cfg.n_experts > 1:
        # Serving decodes must match reference (dropless) MoE routing
        # token-for-token; training keeps capacity-factor dropping, so the
        # flag lives here rather than in the shared geometry preset.
        cfg = dataclasses.replace(cfg, moe_dropless=True)
    from generativeaiexamples_tpu.engine.weights import (
        load_hf_causal_lm,
        weights_dir_for,
    )

    params = None
    ckpt_dir = weights_dir_for(args.model)
    if ckpt_dir and not is_llama:
        raise SystemExit(
            f"no checkpoint loader for {preset}: its layer kinds are served "
            "with random weights only"
        )
    if ckpt_dir:
        logger.info("loading weights from %s", ckpt_dir)
        params = load_hf_causal_lm(cfg, ckpt_dir)
    else:
        logger.warning(
            "no checkpoint for %s under $GAIE_WEIGHTS_DIR; serving "
            "random-initialized weights",
            args.model,
        )
    from generativeaiexamples_tpu.utils.jax_runtime import (
        enable_compile_cache,
        require_accelerator,
    )

    device = require_accelerator("engine server")
    n_devices, platform = device["count"], device["platform"]
    logger.info(
        "devices: %s; compile cache: %s", device, enable_compile_cache()
    )
    from generativeaiexamples_tpu.core.configuration import get_config

    # Config-file fallbacks ([llm] section) for deployments that prefer
    # config over flags; explicit flags win.
    llm_cfg = get_config().llm
    matmul_kernel = args.matmul_kernel or str(
        getattr(llm_cfg, "matmul_kernel", "") or "xla"
    )
    if args.kv_dtype:
        cfg = dataclasses.replace(cfg, kv_dtype=args.kv_dtype)
    from generativeaiexamples_tpu.parallel.mesh import (
        MeshSpec,
        make_mesh,
        replica_device_slices,
    )

    def make_scheduler(mesh):
        # The pool's scheduler_factory closes over this too, so replicas
        # the autoscaler grows later are built as the initial set.
        return Scheduler(
            cfg,
            params,
            mesh=mesh,
            max_batch=args.max_batch,
            max_len=args.max_len,
            seed=args.seed,
            prefix_cache=args.prefix_cache,
            prefill_chunk_tokens=args.prefill_chunk_tokens or None,
            quantize=args.weight_dtype == "int8",
            matmul_kernel=matmul_kernel,
        )

    autoscale_on = args.autoscale or get_config().autoscale.enabled
    if args.replicas > 1 or autoscale_on:
        from generativeaiexamples_tpu.engine.replica import EnginePool

        # On accelerator hosts every replica pins to a disjoint device
        # slice (tensor parallelism stays within the slice); on CPU, or
        # when the device count does not split evenly, replicas are
        # plain instances sharing the devices (the tests' topology).
        meshes: list = [None] * args.replicas
        if (
            args.replicas > 1
            and platform != "cpu"
            and n_devices >= args.replicas
            and n_devices % args.replicas == 0
        ):
            slices = replica_device_slices(args.replicas)
            per = len(slices[0])
            tp = min(args.tensor_parallel or per, per)
            if per % tp:
                raise SystemExit(
                    f"--tensor-parallel {tp} does not divide the "
                    f"{per}-device replica slice"
                )
            meshes = [
                make_mesh(MeshSpec(data=per // tp, tensor=tp), devices=sl)
                for sl in slices
            ]
            logger.info(
                "replica meshes: %d x (data=%d tensor=%d)",
                args.replicas, per // tp, tp,
            )
        replica_bootstrap = None
        pool_target = args.replicas
        if (
            get_config().durability.enabled
            or get_config().vector_store.name == "fabric"
        ):
            # Scale-up hydrates the store singleton from the latest
            # snapshot (a no-op once live) so a fresh replica answers
            # retrieval against the existing corpus without re-embedding.
            # Against a sharded fabric the two-arg form kicks in: the
            # grown replica warms ONLY the hot partitions hash-routed to
            # its index instead of device-syncing every shard.
            def replica_bootstrap(scheduler, replica_idx: int = 0) -> None:
                from generativeaiexamples_tpu.chains.factory import get_store

                store = get_store()
                inner = getattr(store, "_inner", store)
                hydrate = getattr(inner, "hydrate_replica", None)
                if callable(hydrate):
                    warmed = hydrate(
                        replica_idx, max(pool_target, replica_idx + 1)
                    )
                    logger.info(
                        "replica %d hydrated fabric shard(s) %s",
                        replica_idx, warmed,
                    )

        engine = EnginePool(
            [make_scheduler(m) for m in meshes],
            policy=args.routing_policy,
            # Autoscaled replicas share the devices (mesh=None): scale-up
            # must not re-partition slices under live replicas.
            scheduler_factory=lambda: make_scheduler(None),
            replica_bootstrap=replica_bootstrap,
        )
    else:
        mesh = None
        tp = args.tensor_parallel or n_devices
        if tp > 1:
            if n_devices % tp:
                raise SystemExit(
                    f"--tensor-parallel {tp} does not divide {n_devices} "
                    "devices"
                )
            mesh = make_mesh(MeshSpec(data=n_devices // tp, tensor=tp))
            logger.info("serving mesh: data=%d tensor=%d", n_devices // tp, tp)
        engine = make_scheduler(mesh)
    engine.start()
    if autoscale_on and hasattr(engine, "scale_to"):
        from generativeaiexamples_tpu.engine.autoscale import Autoscaler

        Autoscaler(engine).start()
    tokenizer = get_tokenizer(args.model)
    embedder = None
    if args.embedder != "none":
        from generativeaiexamples_tpu.engine.weights import (
            bert_config_from_hf,
            load_hf_bert,
        )

        # Only the arctic (full-geometry) mode looks up converted weights;
        # --embedder tiny stays a fast random-init dev server even when a
        # checkpoint is provisioned.
        embed_ckpt = (
            weights_dir_for(args.embedder_model) if args.embedder == "arctic" else None
        )
        if embed_ckpt:
            logger.info("loading embedder weights from %s", embed_ckpt)
            bcfg = bert_config_from_hf(embed_ckpt)
            embedder = TPUEmbedder(
                bcfg,
                load_hf_bert(bcfg, embed_ckpt),
                tokenizer=get_tokenizer(embed_ckpt),
            )
        else:
            bcfg = (
                bert.arctic_embed_l() if args.embedder == "arctic" else bert.bert_tiny()
            )
            embedder = TPUEmbedder(bcfg)
        if args.embed_max_batch > 1:
            from generativeaiexamples_tpu.engine.microbatch import (
                BatchedEmbedder,
            )

            embedder = BatchedEmbedder(
                embedder,
                max_batch=args.embed_max_batch,
                max_wait_ms=args.embed_max_wait_ms,
            )
    app = create_engine_app(engine, tokenizer, embedder, model_name=args.model)

    async def _graceful_shutdown(_app: web.Application) -> None:
        loop = asyncio.get_running_loop()
        await loop.run_in_executor(None, drain_engine, engine)
        if get_config().durability.enabled:
            from generativeaiexamples_tpu.chains.factory import (
                shutdown_durability,
            )

            await loop.run_in_executor(None, shutdown_durability)

    # Registered here (the entrypoint) rather than in create_engine_app:
    # tests build apps over long-lived schedulers they keep using after
    # client teardown.
    app.on_shutdown.append(_graceful_shutdown)
    from generativeaiexamples_tpu.server.__main__ import (
        install_graceful_signal_handlers,
    )

    install_graceful_signal_handlers()
    logger.info(
        "engine server on %s:%d (model %s, replicas %d)",
        args.host, args.port, preset, args.replicas,
    )
    web.run_app(app, host=args.host, port=args.port, print=None)


if __name__ == "__main__":
    main()
