"""Continuous-batching scheduler + engine server tests."""

import asyncio
import json
import queue
import sys
import threading
import time

import pytest
from aiohttp.test_utils import TestClient, TestServer

from generativeaiexamples_tpu.engine.sampler import SamplingParams
from generativeaiexamples_tpu.engine.scheduler import Request, Scheduler
from generativeaiexamples_tpu.engine.tokenizer import ByteTokenizer
from generativeaiexamples_tpu.models import llama

CFG = llama.llama_tiny(dtype="float32", max_seq_len=128)
INT8 = llama.llama_tiny(dtype="float32", max_seq_len=128, kv_dtype="int8")
CFGS = {"bf16": CFG, "int8": INT8}


def _collect(
    scheduler, prompt, max_tokens=6, temperature=0.0, timeout=60, session_id=""
):
    """Submit a request and block until done; returns (tokens, reason)."""
    tokens: list[int] = []
    done = queue.Queue()
    req = Request(
        token_ids=list(prompt),
        sampling=SamplingParams(temperature=temperature, max_tokens=max_tokens),
        on_token=tokens.append,
        on_done=done.put,
        session_id=session_id,
    )
    scheduler.submit(req)
    reason = done.get(timeout=timeout)
    return tokens, reason


def _collect_all(scheduler, prompts, max_tokens=6, sessions=None, start=False):
    """Submit every prompt before waiting for any (``start``: and before
    the scheduler's first tick, so that they wait together); returns
    their (tokens, reason), in the prompts' order."""
    out = [([], queue.Queue()) for _ in prompts]
    for i, (prompt, (tokens, done)) in enumerate(zip(prompts, out)):
        scheduler.submit(
            Request(
                token_ids=list(prompt),
                sampling=SamplingParams(temperature=0.0, max_tokens=max_tokens),
                on_token=tokens.append,
                on_done=done.put,
                session_id=sessions[i] if sessions else "",
            )
        )
    if start:
        scheduler.start()
    return [(tokens, done.get(timeout=120)) for tokens, done in out]


@pytest.fixture(scope="module")
def scheduler():
    s = Scheduler(CFG, max_batch=4, max_len=128, decode_chunk_size=4)
    s.start()
    yield s
    s.stop()


@pytest.fixture(scope="module")
def alone_cold():
    """The oracle of what a prompt streams: a scheduler a KV dtype with
    no prefix cache and no chunked prefill, asked one prompt at a time.
    ``alone_cold(kv, prompt, max_tokens)`` returns the tokens."""
    built = {}

    def oracle(kv, prompt, max_tokens):
        if kv not in built:
            built[kv] = Scheduler(
                CFGS[kv], max_batch=2, max_len=128, decode_chunk_size=4,
                prefix_cache="off", prefill_chunk_tokens=None,
            )
            built[kv].start()
        return _collect(built[kv], prompt, max_tokens=max_tokens)[0]

    yield oracle
    for s in built.values():
        s.stop()


class TestScheduler:
    def test_single_request(self, scheduler):
        tokens, reason = _collect(scheduler, [1, 2, 3], max_tokens=6)
        assert len(tokens) == 6
        assert reason == "length"

    def test_matches_batch_generator(self, scheduler):
        """Greedy continuous-batching output == batch generator output."""
        from generativeaiexamples_tpu.engine.generator import LlamaGenerator

        gen = LlamaGenerator(CFG, max_batch=2, max_len=128)
        expected = gen.generate(
            [[5, 6, 7]], SamplingParams(temperature=0.0, max_tokens=5)
        )[0].token_ids
        tokens, _ = _collect(scheduler, [5, 6, 7], max_tokens=5)
        assert tokens == expected

    def test_concurrent_requests_independent(self, scheduler):
        """Concurrent submissions produce the same greedy outputs as solo."""
        solo_a, _ = _collect(scheduler, [10, 11], max_tokens=5)
        solo_b, _ = _collect(scheduler, [20, 21, 22], max_tokens=5)

        results = {}
        threads = []

        def run(name, prompt):
            results[name] = _collect(scheduler, prompt, max_tokens=5)[0]

        for name, prompt in [("a", [10, 11]), ("b", [20, 21, 22])]:
            t = threading.Thread(target=run, args=(name, prompt))
            t.start()
            threads.append(t)
        for t in threads:
            t.join(timeout=60)
        assert results["a"] == solo_a
        assert results["b"] == solo_b

    def test_more_requests_than_slots(self, scheduler):
        """Oversubscription queues and completes everything."""
        n = 10  # > max_batch=4
        done = queue.Queue()
        for i in range(n):
            scheduler.submit(
                Request(
                    token_ids=[i + 1, i + 2],
                    sampling=SamplingParams(temperature=0.0, max_tokens=3),
                    on_token=lambda t: None,
                    on_done=done.put,
                )
            )
        reasons = [done.get(timeout=120) for _ in range(n)]
        assert all(r == "length" for r in reasons)

    def test_prefix_cache_reuses_parked_session(self, scheduler):
        """Turn 2 of a session whose prompt extends turn 1's history must
        take the suffix-prefill path (prefix_hits increments, reused
        tokens ~= the shared history) and still decode exactly like a
        fresh request with the same full prompt."""
        base = scheduler.stats.snapshot()
        prompt1 = list(range(2, 44))  # 42 tokens > MIN_PREFIX
        out1, reason1 = _collect(
            scheduler, prompt1, max_tokens=4, session_id="conv-a"
        )
        assert reason1 == "length"
        snap1 = scheduler.stats.snapshot()
        assert snap1["prefix_hits"] == base["prefix_hits"]  # turn 1: miss

        prompt2 = prompt1 + out1 + [90, 91, 92]
        out2, reason2 = _collect(
            scheduler, prompt2, max_tokens=4, session_id="conv-a"
        )
        assert reason2 == "length"
        snap2 = scheduler.stats.snapshot()
        assert snap2["prefix_hits"] == base["prefix_hits"] + 1
        # Reused = prompt1 + out1 minus the never-written last token.
        assert (
            snap2["prefix_tokens_reused"] - snap1["prefix_tokens_reused"]
            == len(prompt1) + len(out1) - 1
        )
        # Correctness: identical to a sessionless request on the full
        # prompt (greedy).
        expected, _ = _collect(scheduler, prompt2, max_tokens=4)
        assert out2 == expected

    def test_prefix_cache_mismatched_history_falls_back(self, scheduler):
        """A same-session prompt that does NOT extend the parked history
        must take the normal full-prefill path."""
        prompt1 = list(range(3, 40))
        _collect(scheduler, prompt1, max_tokens=3, session_id="conv-b")
        before = scheduler.stats.snapshot()
        different = list(range(60, 100))
        out, _ = _collect(scheduler, different, max_tokens=3, session_id="conv-b")
        after = scheduler.stats.snapshot()
        assert after["prefix_hits"] == before["prefix_hits"]
        expected, _ = _collect(scheduler, different, max_tokens=3)
        assert out == expected

    def test_parked_prefix_survives_other_decodes(self, scheduler):
        """Regression: while a session is parked, other requests' decode
        chunks run with the parked slot as a masked lane — their garbage
        K/V writes must land on the overwritable last position, not
        position 0, or the cached prefix corrupts silently."""
        prompt1 = list(range(5, 45))
        out1, _ = _collect(scheduler, prompt1, max_tokens=3, session_id="conv-d")
        # Decode chunks run while conv-d is parked.
        _collect(scheduler, [9, 9, 9], max_tokens=8)
        _collect(scheduler, [8, 8, 8], max_tokens=8)
        before = scheduler.stats.snapshot()
        prompt2 = prompt1 + out1 + [70, 71]
        out2, _ = _collect(scheduler, prompt2, max_tokens=4, session_id="conv-d")
        assert scheduler.stats.snapshot()["prefix_hits"] == before["prefix_hits"] + 1
        expected, _ = _collect(scheduler, prompt2, max_tokens=4)
        assert out2 == expected

    def test_prefix_cache_int8_kv(self):
        """The suffix prefill's warm path must also hold for quantized
        caches (attention reads back int8 KV + scales mid-prompt)."""
        cfg = llama.llama_tiny(dtype="float32", max_seq_len=128, kv_dtype="int8")
        s = Scheduler(cfg, max_batch=2, max_len=128, decode_chunk_size=4)
        s.start()
        try:
            prompt1 = list(range(2, 44))
            out1, _ = _collect(s, prompt1, max_tokens=3, session_id="c")
            prompt2 = prompt1 + out1 + [7, 8]
            out2, _ = _collect(s, prompt2, max_tokens=3, session_id="c")
            assert s.stats.snapshot()["prefix_hits"] == 1
            expected, _ = _collect(s, prompt2, max_tokens=3)
            assert out2 == expected
        finally:
            s.stop()

    def test_stats(self, scheduler):
        snap = scheduler.stats.snapshot()
        assert snap["requests_total"] >= 1
        assert snap["tokens_total"] >= 1


@pytest.fixture
def engine_client():
    scheduler = Scheduler(CFG, max_batch=2, max_len=128, decode_chunk_size=4)
    scheduler.start()
    tok = ByteTokenizer()
    from generativeaiexamples_tpu.engine.embedder import HashEmbedder
    from generativeaiexamples_tpu.engine.server import create_engine_app

    app = create_engine_app(
        scheduler,
        tok,
        embedder=HashEmbedder(dimensions=32),
        model_name="llama-tiny",
        enable_profiler=True,
    )
    loop = asyncio.new_event_loop()
    client = TestClient(TestServer(app), loop=loop)
    loop.run_until_complete(client.start_server())
    yield client, loop
    loop.run_until_complete(client.close())
    loop.close()
    scheduler.stop()


class TestAdmissionControl:
    def test_submit_rejects_beyond_max_queue(self):
        s = Scheduler(CFG, max_batch=2, max_len=128, max_queue=2)
        # Not started: submissions stay queued, so the bound is exact.
        results = []
        for i in range(5):
            req = Request(
                token_ids=[1, 2],
                sampling=SamplingParams(max_tokens=2),
                on_token=lambda t: None,
                on_done=lambda r: None,
                id=f"q{i}",
            )
            results.append(s.submit(req))
        assert results == [True, True, False, False, False]
        snap = s.stats.snapshot()
        assert snap["queued"] == 2
        assert snap["rejected_total"] == 3

    def test_admission_token_budget_interleaves_prefill_and_decode(self):
        """A burst of long prompts must not prefill in one monster tick:
        admission splits at ADMIT_TOKEN_BUDGET prompt tokens per tick so
        running requests keep decoding between prefill batches."""
        import queue as _q

        sched = Scheduler(
            CFG, max_batch=8, max_len=128, decode_chunk_size=4,
            admit_token_budget=64, admit_cap=2,
        )
        # Spy on both admission batches and decode chunks so admitted
        # tokens can be aggregated PER TICK (the budget's actual contract
        # — per-batch sums would pass even if a tick over-admitted via a
        # second batch).
        events: list = []
        # Patch the dispatch layer: both the pipelined tick and the
        # synchronous idle path funnel through _admit_cold (which sends
        # these batches of two as chunks of one row each); tick
        # boundaries (the budget's scope) come from patching _tick.
        orig_admit = sched._admit_cold
        orig_tick = sched._tick
        sched._admit_cold = lambda reqs, slots: (
            events.append(sum(len(r.token_ids) for r in reqs)),
            orig_admit(reqs, slots),
        )[1]
        sched._tick = lambda: (events.append("tick"), orig_tick())[1]
        done: "_q.Queue[str]" = _q.Queue()
        # 8 x 30-token prompts: admit_cap=2 makes each batch 60 tokens,
        # leaving a 4-token remainder that must NOT admit another batch
        # in the same tick.
        for i in range(8):
            sched.submit(
                Request(
                    token_ids=[1 + (i % 7)] * 30,
                    sampling=SamplingParams(temperature=0.0, max_tokens=3),
                    on_token=lambda t: None,
                    on_done=done.put,
                    id=f"tb{i}",
                )
            )
        sched.start()
        try:
            for _ in range(8):
                assert done.get(timeout=120) == "length"
        finally:
            sched.stop()
        per_tick = []
        acc = 0
        for ev in events:
            if ev == "tick":
                if acc:
                    per_tick.append(acc)
                acc = 0
            else:
                acc += ev
        if acc:
            per_tick.append(acc)
        assert len(per_tick) >= 3, (per_tick, events)
        assert all(t <= 64 for t in per_tick), (per_tick, events)

    def test_single_over_budget_request_still_admits(self):
        """The over-budget exemption must fire during BUSY ticks: with
        another request actively decoding, the idle path (which bypasses
        the budget) is unreachable, so only the exemption can admit a
        prompt larger than the whole tick budget."""
        import queue as _q

        sched = Scheduler(
            CFG, max_batch=3, max_len=128, decode_chunk_size=4,
            admit_token_budget=8,
        )
        done: "_q.Queue[str]" = _q.Queue()
        runner_done: "_q.Queue[str]" = _q.Queue()
        # Keep a request decoding for many chunks so ticks stay busy.
        sched.submit(
            Request(
                token_ids=[2, 3],
                sampling=SamplingParams(temperature=0.0, max_tokens=80),
                on_token=lambda t: None,
                on_done=runner_done.put,
            )
        )
        sched.start()
        try:
            import time as _time

            _time.sleep(0.5)  # ensure the runner is active before submit
            sched.submit(
                Request(
                    token_ids=[1] * 40,  # alone exceeds the 8-token budget
                    sampling=SamplingParams(temperature=0.0, max_tokens=2),
                    on_token=lambda t: None,
                    on_done=done.put,
                )
            )
            assert done.get(timeout=60) == "length"
            assert runner_done.get(timeout=60) == "length"
        finally:
            sched.stop()

    def test_server_returns_429_when_queue_full(self):
        from generativeaiexamples_tpu.engine.server import create_engine_app

        sched = Scheduler(CFG, max_batch=2, max_len=128, max_queue=0)
        tok = ByteTokenizer()
        app = create_engine_app(sched, tok, model_name="llama-tiny")
        loop = asyncio.new_event_loop()
        client = TestClient(TestServer(app), loop=loop)
        loop.run_until_complete(client.start_server())
        try:

            async def go():
                resp = await client.post(
                    "/v1/chat/completions",
                    json={
                        "model": "llama-tiny",
                        "messages": [{"role": "user", "content": "hi"}],
                        "max_tokens": 2,
                    },
                )
                return resp.status, await resp.json()

            status, body = loop.run_until_complete(go())
            assert status == 429
            assert body["error"]["type"] == "overloaded_error"
        finally:
            loop.run_until_complete(client.close())
            loop.close()
            sched.stop()


class TestEngineServer:
    def test_chat_completion_nonstream(self, engine_client):
        c, loop = engine_client

        async def go():
            resp = await c.post(
                "/v1/chat/completions",
                json={
                    "model": "llama-tiny",
                    "messages": [{"role": "user", "content": "hi"}],
                    "max_tokens": 5,
                    "temperature": 0,
                },
            )
            assert resp.status == 200
            return await resp.json()

        body = loop.run_until_complete(go())
        assert body["object"] == "chat.completion"
        assert body["choices"][0]["finish_reason"] == "length"
        assert body["usage"]["completion_tokens"] == 5

    def test_chat_completion_stream(self, engine_client):
        c, loop = engine_client

        async def go():
            resp = await c.post(
                "/v1/chat/completions",
                json={
                    "model": "llama-tiny",
                    "messages": [{"role": "user", "content": "hi"}],
                    "max_tokens": 5,
                    "temperature": 0,
                    "stream": True,
                },
            )
            assert resp.status == 200
            lines = []
            async for line in resp.content:
                line = line.decode().strip()
                if line.startswith("data: "):
                    lines.append(line[6:])
            return lines

        lines = loop.run_until_complete(go())
        assert lines[-1] == "[DONE]"
        first = json.loads(lines[0])
        assert first["choices"][0]["delta"].get("role") == "assistant"
        finals = [json.loads(l) for l in lines[:-1]]
        assert finals[-1]["choices"][0]["finish_reason"] in ("length", "stop")

    def test_embeddings_endpoint(self, engine_client):
        c, loop = engine_client

        async def go():
            resp = await c.post(
                "/v1/embeddings",
                json={"model": "e", "input": ["a", "b"], "input_type": "passage"},
            )
            assert resp.status == 200
            return await resp.json()

        body = loop.run_until_complete(go())
        assert len(body["data"]) == 2
        assert len(body["data"][0]["embedding"]) == 32
        assert body["data"][0]["index"] == 0

    def test_models_metrics_health(self, engine_client):
        c, loop = engine_client

        async def go():
            models = await (await c.get("/v1/models")).json()
            health = await (await c.get("/health")).json()
            metrics = await (await c.get("/metrics")).text()
            return models, health, metrics

        models, health, metrics = loop.run_until_complete(go())
        assert models["data"][0]["id"] == "llama-tiny"
        assert health["message"] == "Service is up."
        assert "engine_tokens_total" in metrics
        assert "engine_shared_prefix_hits_total" in metrics
        assert "engine_prefill_chunks_total" in metrics

    def test_ranking_without_reranker(self, engine_client):
        c, loop = engine_client

        async def go():
            resp = await c.post(
                "/v1/ranking",
                json={"query": {"text": "q"}, "passages": [{"text": "p"}]},
            )
            return resp.status

        assert loop.run_until_complete(go()) == 501

    def test_validation_error(self, engine_client):
        c, loop = engine_client

        async def go():
            resp = await c.post("/v1/chat/completions", json={"nope": 1})
            return resp.status

        assert loop.run_until_complete(go()) == 422


class TestCompletionsEndpoint:
    def test_completions_nonstream(self, engine_client):
        c, loop = engine_client

        async def go():
            resp = await c.post(
                "/v1/completions",
                json={
                    "model": "llama-tiny",
                    "prompt": "Once upon a time",
                    "max_tokens": 5,
                    "temperature": 0,
                },
            )
            assert resp.status == 200
            return await resp.json()

        body = loop.run_until_complete(go())
        assert body["object"] == "text_completion"
        assert body["choices"][0]["finish_reason"] == "length"
        assert body["usage"]["completion_tokens"] == 5

    def test_completions_stream_done_sentinel(self, engine_client):
        c, loop = engine_client

        async def go():
            resp = await c.post(
                "/v1/completions",
                json={
                    "model": "llama-tiny",
                    "prompt": "hello",
                    "max_tokens": 4,
                    "temperature": 0,
                    "stream": True,
                },
            )
            assert resp.status == 200
            lines = []
            async for line in resp.content:
                line = line.decode().strip()
                if line.startswith("data: "):
                    lines.append(line[6:])
            return lines

        lines = loop.run_until_complete(go())
        assert lines[-1] == "[DONE]"
        import json as _json

        payloads = [_json.loads(l) for l in lines[:-1]]
        assert all(p["object"] == "text_completion" for p in payloads)
        assert payloads[-1]["choices"][0]["finish_reason"] == "length"

    def test_completions_validation_error(self, engine_client):
        c, loop = engine_client

        async def go():
            resp = await c.post("/v1/completions", json={"nope": 1})
            return resp.status

        assert loop.run_until_complete(go()) == 422


class Test70BTensorParallelServing:
    def test_70b_ratio_tp8_server_end_to_end(self, tmp_path):
        """Boot the engine server on a TP-8 mesh with a ratio-scaled
        llama3-70b config (the 64q:8kv GQA layout, one KV head per
        device — reference serves 70B across GPUs,
        ``docs/support-matrix.md:36-46``), loading weights through the
        sharded orbax path (each leaf restores directly with its
        NamedSharding — no host ever holds the unsharded tree), then
        serve one chat completion over HTTP."""
        import jax
        from jax.sharding import NamedSharding

        from generativeaiexamples_tpu.engine.server import create_engine_app
        from generativeaiexamples_tpu.engine.weights import (
            load_orbax_sharded,
            save_orbax,
        )
        from generativeaiexamples_tpu.parallel.mesh import MeshSpec, make_mesh

        assert len(jax.devices()) >= 8
        cfg = llama.llama3_70b(
            dtype="float32",
            d_model=128,
            n_layers=2,
            n_heads=64,
            n_kv_heads=8,
            head_dim=8,
            d_ff=256,
            vocab_size=512,
            max_seq_len=64,
        )
        mesh = make_mesh(
            MeshSpec(data=1, tensor=8, fsdp=1, seq=1, expert=1),
            devices=jax.devices()[:8],
        )
        host_params = llama.init_params(cfg, jax.random.PRNGKey(0))
        save_orbax(host_params, str(tmp_path / "ckpt"))
        params = load_orbax_sharded(cfg, str(tmp_path / "ckpt"), mesh)
        # Restored leaves live on the mesh with their serving specs: the
        # attention projections actually split over the tensor axis.
        wq = params["layers"]["wq"]
        assert isinstance(wq.sharding, NamedSharding)
        assert wq.sharding.mesh.shape["tensor"] == 8
        shard_shape = wq.sharding.shard_shape(wq.shape)
        assert shard_shape[-1] == wq.shape[-1] // 8

        scheduler = Scheduler(
            cfg,
            params=params,
            mesh=mesh,
            max_batch=2,
            max_len=64,
            decode_chunk_size=4,
        )
        scheduler.start()
        tok = ByteTokenizer()
        app = create_engine_app(scheduler, tok, model_name="llama3-70b")
        loop = asyncio.new_event_loop()
        client = TestClient(TestServer(app), loop=loop)
        try:
            loop.run_until_complete(client.start_server())

            async def go():
                resp = await client.post(
                    "/v1/chat/completions",
                    json={
                        "model": "llama3-70b",
                        "messages": [{"role": "user", "content": "hi"}],
                        "max_tokens": 4,
                        "stream": False,
                    },
                )
                assert resp.status == 200, await resp.text()
                body = await resp.json()
                assert body["choices"][0]["message"]["content"] is not None
                assert body["usage"]["completion_tokens"] >= 1

            loop.run_until_complete(go())
        finally:
            loop.run_until_complete(client.close())
            loop.close()
            scheduler.stop()


class TestSchedulerStress:
    def test_many_requests_random_cancels(self):
        self.churn(
            Scheduler(CFG, max_batch=3, max_len=128, decode_chunk_size=4)
        )

    @staticmethod
    def churn(sched):
        """Churn: 24 requests over ``sched``'s 3 slots with mid-flight
        cancels — every request must finish exactly once with a sane
        reason (SURVEY §5.2: stress the batching scheduler in lieu of
        sanitizers).  ``tests/test_own_draft_serving.py`` runs the same
        over a model whose every decode step verifies its own draft."""
        import random
        import threading

        rng = random.Random(0)
        sched.start()
        done: dict[int, list[str]] = {i: [] for i in range(24)}
        tokens: dict[int, int] = {i: 0 for i in range(24)}
        events = [threading.Event() for _ in range(24)]
        lock = threading.Lock()

        def make_cbs(i):
            def on_token(tid):
                with lock:
                    tokens[i] += 1

            def on_done(reason):
                with lock:
                    done[i].append(reason)
                events[i].set()

            return on_token, on_done

        reqs = []
        for i in range(24):
            on_token, on_done = make_cbs(i)
            req = Request(
                token_ids=[1 + (i % 7), 2, 3],
                sampling=SamplingParams(
                    temperature=0.0, max_tokens=rng.choice([3, 6, 10])
                ),
                on_token=on_token,
                on_done=on_done,
                id=f"req-{i}",
            )
            reqs.append(req)
            sched.submit(req)
            if i % 3 == 2:
                # cancel a random earlier request mid-flight
                sched.cancel(f"req-{rng.randrange(i)}")

        for i, ev in enumerate(events):
            assert ev.wait(timeout=180), f"request {i} never finished"
        sched.stop()

        for i in range(24):
            assert len(done[i]) == 1, f"request {i} finished {len(done[i])}x"
            assert done[i][0] in ("length", "stop", "cancelled")
        finished_normally = [i for i in range(24) if done[i][0] == "length"]
        assert finished_normally, "expected some requests to run to length"


class TestProfilerEndpoints:
    def test_start_stop_cycle(self, engine_client, tmp_path, monkeypatch):
        monkeypatch.setenv("GAIE_PROFILER_DIR", str(tmp_path / "trace"))
        c, loop = engine_client

        async def go():
            r1 = await c.post("/debug/profiler/start")
            if r1.status == 501:  # backend without trace support
                return "unsupported"
            assert r1.status == 200
            r_dup = await c.post("/debug/profiler/start")
            assert r_dup.status == 409
            r2 = await c.post("/debug/profiler/stop")
            assert r2.status == 200
            r3 = await c.post("/debug/profiler/stop")
            assert r3.status == 409
            return "ok"

        assert loop.run_until_complete(go()) in ("ok", "unsupported")


class TestSharedPrefixCache:
    """Cross-request shared-prefix KV cache: a content-matched graft +
    suffix prefill must decode exactly like a cold full (monolithic)
    prefill on the greedy path — for suffix lengths 0 (prompt equals the
    cached history), 1, and > the prefill chunk size (the warming path),
    in both bf16-KV and int8 append-buffer modes."""

    # case name -> (which case, extra tokens appended to the cached history)
    SUFFIX_CASES = {
        "suffix0": (0, 0),
        "suffix1": (1, 1),
        "suffix_gt_chunk": (2, 9),  # > prefill_chunk_tokens=4 below
    }

    @pytest.fixture(scope="class", params=["bf16", "int8_append_buffer"])
    def cold_and_warm(self, request):
        """A scheduler that prefills every prompt whole and cold, and one
        with the shared cache and chunks of 4: one pair a KV dtype."""
        kw = dict(max_batch=2, max_len=128, decode_chunk_size=4)
        with pytest.MonkeyPatch.context() as patch:
            cfg = CFG
            if request.param == "int8_append_buffer":
                # Read where a step program is traced: set for the pair's life.
                patch.setenv("GAIE_FORCE_APPEND_BUFFER", "1")
                cfg = INT8
            cold = Scheduler(
                cfg, **kw, prefix_cache="off", prefill_chunk_tokens=None
            )
            warm = Scheduler(
                cfg, **kw, prefix_cache="shared", prefill_chunk_tokens=4
            )
            cold.start()
            warm.start()
            try:
                yield cold, warm
            finally:
                cold.stop()
                warm.stop()

    @pytest.mark.parametrize("name", sorted(SUFFIX_CASES))
    def test_shared_hit_matches_cold(self, cold_and_warm, name):
        cold, warm = cold_and_warm
        case_i, extra = self.SUFFIX_CASES[name]
        # Distinct base prompt per case so segments parked by another
        # case can never match this one.
        base = list(range(2 + 50 * case_i, 42 + 50 * case_i))
        out1, _ = _collect(cold, base, max_tokens=3)
        # Parked history after a length finish drops the last
        # sampled token (its KV was never written).
        history = base + out1[:-1]
        prompt2 = history + [499 - i for i in range(extra)]
        expected, _ = _collect(cold, prompt2, max_tokens=4)

        before = warm.stats.snapshot()
        out1w, _ = _collect(warm, base, max_tokens=3)
        assert out1w == out1  # seed itself decodes cold
        got, _ = _collect(warm, prompt2, max_tokens=4)
        after = warm.stats.snapshot()
        assert after["shared_prefix_hits"] == before["shared_prefix_hits"] + 1
        assert after["prefix_hits"] == before["prefix_hits"]
        # Reuse = the full common prefix (capped at plen-1 when
        # the prompt equals the cached history).
        reused = after["prefix_tokens_reused"] - before["prefix_tokens_reused"]
        assert reused == min(len(history), len(prompt2) - 1)
        assert got == expected

    def test_shared_hit_takeover_when_no_free_slot(self):
        """With a single slot the graft has no destination: the hit must
        consume the source segment in place (destructive takeover) and
        still decode like a cold prefill."""
        cold = Scheduler(
            CFG, max_batch=1, max_len=128, decode_chunk_size=4,
            prefix_cache="off", prefill_chunk_tokens=None,
        )
        warm = Scheduler(
            CFG, max_batch=1, max_len=128, decode_chunk_size=4,
            prefix_cache="shared", prefill_chunk_tokens=None,
        )
        cold.start()
        warm.start()
        try:
            base = list(range(3, 44))
            out1, _ = _collect(cold, base, max_tokens=3)
            prompt2 = base + out1[:-1] + [7]
            expected, _ = _collect(cold, prompt2, max_tokens=3)
            _collect(warm, base, max_tokens=3)
            got, _ = _collect(warm, prompt2, max_tokens=3)
            snap = warm.stats.snapshot()
            assert snap["shared_prefix_hits"] == 1
            assert got == expected
        finally:
            cold.stop()
            warm.stop()


# Long enough to clear Scheduler.MIN_PREFIX (32), so that continuations
# and cross-session hits take the graft paths, not cold admission.
PREFIX = [(i * 13) % 256 + 1 for i in range(48)]


class TestTwoSessionsOnePrefix:
    """Two sessions that leave one parked prefix and append what differs
    do not see each other's rows: each streams what its prompt streams
    alone and cold.  Under ``shared`` both graft the seed's rows, which
    stay parked; under ``session`` each takes over its own last turn."""

    @pytest.mark.parametrize("mode", ["shared", "session"])
    @pytest.mark.parametrize("kv", ["bf16", "int8"])
    def test_each_streams_what_it_streams_alone(self, alone_cold, kv, mode):
        sched = Scheduler(
            CFGS[kv], max_batch=4, max_len=128, decode_chunk_size=4,
            prefill_chunk_tokens=None, prefix_cache=mode,
        )
        sched.start()
        try:
            _collect(sched, PREFIX, session_id="seed")
            turns = [PREFIX + [100], PREFIX + [200]]
            first = _collect_all(sched, turns, sessions=["a", "b"])
            # A second turn a session, past where the first two diverged.
            turns2 = [PREFIX + [100, 101], PREFIX + [200, 201]]
            second = _collect_all(sched, turns2, sessions=["a", "b"])
            snap = sched.stats.snapshot()
        finally:
            sched.stop()
        streams = [tokens for tokens, _ in first + second]
        assert streams == [alone_cold(kv, p, 6) for p in turns + turns2]
        assert streams[0] != streams[1]  # what differs was seen
        # The rows were reused, not prefilled again.
        hits = snap["shared_prefix_hits"] + snap["prefix_hits"]
        assert hits == (4 if mode == "shared" else 2)


class TestSlotPressure:
    @pytest.mark.parametrize("kv", ["bf16", "int8"])
    def test_more_sessions_than_slots_all_end_and_nothing_leaks(
        self, alone_cold, kv
    ):
        """Six sessions on two slots, all waiting at once: every request
        ends with its own stream, the prefixes parked along the way are
        evicted for the next arrival, the index names exactly the slots
        that are still parked, and reclaiming those leaves every slot
        free."""
        sched = Scheduler(
            CFGS[kv], max_batch=2, max_len=128, decode_chunk_size=4,
            prefill_chunk_tokens=32, prefix_cache="shared",
        )
        prompts = [list(range(1 + 40 * i, 41 + 40 * i)) for i in range(6)]
        sched.start()
        try:
            got = _collect_all(
                sched, prompts, max_tokens=3,
                sessions=[f"s{i}" for i in range(6)],
            )
        finally:
            sched.stop()
        assert [reason for _, reason in got] == ["length"] * 6
        assert [t for t, _ in got] == [alone_cold(kv, p, 3) for p in prompts]
        slots = sched._slots
        assert all(s.request is None and s.warm_pos is None for s in slots)
        parked = {i for i, s in enumerate(slots) if s.cached}
        assert parked and set(sched._prefix_index.segments()) == parked
        assert sorted(sched._reclaim_parked(len(slots))) == sorted(parked)
        assert len(sched._prefix_index) == 0
        assert sched._free_slots() == list(range(len(slots)))


class TestAStreamDoesNotDependOnItsAdmission:
    """One prompt down every admission path over int8 KV: what it streams
    alone and cold is what it streams in a cold batch of unequal prompts,
    as a session hit, as a shared hit, as a takeover of the only slot and
    through chunked prefill."""

    PROMPT = PREFIX + list(range(60, 75))

    @pytest.fixture(scope="class")
    def warm(self):
        s = Scheduler(
            INT8, max_batch=4, max_len=128, decode_chunk_size=4,
            prefill_chunk_tokens=16, prefix_cache="shared",
        )
        s.start()
        yield s
        s.stop()

    def _through(self, path, warm):
        """(prompt, its tokens, whether its path's counter moved)"""
        prompt = self.PROMPT
        if path == "cold_batch":
            s = Scheduler(
                INT8, max_batch=4, max_len=128, decode_chunk_size=4,
                prefix_cache="off", prefill_chunk_tokens=None,
            )
            others = [[7, 8, 9], list(range(200, 222))]
            try:
                got = _collect_all(s, [prompt] + others, start=True)
            finally:
                s.stop()
            snap = s.stats.snapshot()
            taken = snap["prefill_rows"] == 3 and snap["prefill_chunks"] == 0
            return prompt, got[0][0], taken
        if path == "takeover":
            s = Scheduler(
                INT8, max_batch=1, max_len=128, decode_chunk_size=4,
                prefix_cache="shared", prefill_chunk_tokens=None,
            )
            s.start()
            try:
                _collect(s, PREFIX, max_tokens=3)
                got, _ = _collect(s, prompt)
            finally:
                s.stop()
            return prompt, got, s.stats.snapshot()["shared_prefix_hits"] == 1
        before = warm.stats.snapshot()
        if path == "chunked":
            # Nothing parked shares its first tokens: four chunks of 16, cold.
            prompt = list(range(130, 193))
            got, _ = _collect(warm, prompt)
            key, by = "prefill_chunks", 4
        elif path == "session_hit":
            _collect(warm, PREFIX, max_tokens=3, session_id="mine")
            before = warm.stats.snapshot()
            got, _ = _collect(warm, prompt, session_id="mine")
            key, by = "prefix_hits", 1
        else:
            _collect(warm, PREFIX, max_tokens=3, session_id="theirs")
            before = warm.stats.snapshot()
            got, _ = _collect(warm, prompt)
            key, by = "shared_prefix_hits", 1
        return prompt, got, warm.stats.snapshot()[key] == before[key] + by

    @pytest.mark.parametrize(
        "path",
        ["cold_batch", "session_hit", "shared_hit", "takeover", "chunked"],
    )
    def test_same_stream(self, alone_cold, warm, path):
        prompt, got, taken = self._through(path, warm)
        assert taken, path
        assert got == alone_cold("int8", prompt, 6)


@pytest.mark.parametrize("flag", ["--kv-layout", "--kv-page-size"])
def test_engine_server_parser_has_no_flag_for_a_second_kv_layout(
    flag, monkeypatch, capsys
):
    from generativeaiexamples_tpu.engine import server

    monkeypatch.setattr(sys, "argv", ["engine-server", flag, "paged"])
    with pytest.raises(SystemExit) as exit_:
        server.main()
    assert exit_.value.code == 2
    assert f"unrecognized arguments: {flag}" in capsys.readouterr().err


class TestChunkedPrefill:
    def test_chunked_matches_monolithic(self):
        """A cold prompt admitted in prefill chunks must decode exactly
        like the monolithic batched prefill (greedy)."""
        prompt = list(range(1, 31))  # 30 tokens -> 4 chunks of 8
        mono = Scheduler(
            CFG, max_batch=2, max_len=128, decode_chunk_size=4,
            prefix_cache="off", prefill_chunk_tokens=None,
        )
        chunked = Scheduler(
            CFG, max_batch=2, max_len=128, decode_chunk_size=4,
            prefix_cache="off", prefill_chunk_tokens=8,
        )
        mono.start()
        chunked.start()
        try:
            expected, _ = _collect(mono, prompt, max_tokens=5)
            got, reason = _collect(chunked, prompt, max_tokens=5)
            assert reason == "length"
            assert got == expected
            assert chunked.stats.snapshot()["prefill_chunks"] == 4
        finally:
            mono.stop()
            chunked.stop()

    def test_chunked_prefill_interleaves_with_decode(self):
        """Latency bound: during a long cold admission, a running lane
        must never wait more than one prefill chunk + one decode chunk
        between emitted tokens — i.e. chunk dispatches for the warming
        slot strictly alternate with decode dispatches."""
        sched = Scheduler(
            CFG, max_batch=2, max_len=128, decode_chunk_size=4,
            prefix_cache="off", prefill_chunk_tokens=8,
        )
        events: list[str] = []
        # The step program, not _advance_warm: a chunk sent a tick ahead
        # is booked by a second call that dispatches nothing.
        orig_chunk = sched._prefill_suffix
        orig_decode = sched._decode_dispatch
        sched._prefill_suffix = lambda *a, **k: (
            events.append("chunk"), orig_chunk(*a, **k)
        )[1]
        sched._decode_dispatch = lambda *a, **k: (
            events.append("decode"), orig_decode(*a, **k)
        )[1]
        runner_done = queue.Queue()
        runner_started = threading.Event()
        sched.submit(
            Request(
                token_ids=[5, 6],
                sampling=SamplingParams(temperature=0.0, max_tokens=120),
                on_token=lambda t: runner_started.set(),
                on_done=runner_done.put,
                id="runner",
            )
        )
        sched.start()
        try:
            assert runner_started.wait(timeout=60)
            long_prompt = list(range(1, 41))  # 40 tokens -> 5 chunks
            got, reason = _collect(sched, long_prompt, max_tokens=3)
            assert reason == "length"
            assert len(got) == 3
        finally:
            sched.cancel("runner")
            runner_done.get(timeout=60)
            sched.stop()
        # The runner's admission, alone, is a chunk too: the first.
        assert sched.stats.snapshot()["prefill_chunks"] == 1 + 5
        chunk_idx = [i for i, e in enumerate(events) if e == "chunk"]
        assert len(chunk_idx) == 1 + 5
        for a, b in zip(chunk_idx[1:], chunk_idx[2:]):
            # The runner decodes between every pair of the long prompt's
            # prefill chunks.
            assert "decode" in events[a + 1 : b], events[a : b + 1]


class TestPipelinedTickBounds:
    def test_long_prompt_admission_stays_clear_of_flush_zone(
        self, monkeypatch
    ):
        """Regression (ADVICE r5, scheduler KV corruption): a prompt
        longer than max_len - decode_chunk_size admitted while another
        lane is decoding lands in a pipelined tick whose decode chunk
        pins the new lane to max_len - 1; the append-buffer flush then
        garbage-writes [max_len - chunk, max_len).  Admissions must be
        bounded below that zone so the prompt decodes exactly as it does
        alone on an idle scheduler."""
        monkeypatch.setenv("GAIE_FORCE_APPEND_BUFFER", "1")
        cfg = llama.llama_tiny(
            dtype="float32", max_seq_len=128, kv_dtype="int8"
        )
        kw = dict(
            max_batch=2, max_len=128, decode_chunk_size=8,
            prefix_cache="off", prefill_chunk_tokens=None,
        )
        long_prompt = list(range(1, 127))  # 126 tokens: inside the zone
        ref = Scheduler(cfg, **kw)
        ref.start()
        try:
            expected, _ = _collect(ref, long_prompt, max_tokens=4)
        finally:
            ref.stop()
        # Truncation bound: strictly below the flush-clip zone.
        assert ref._admit_limit == 128 - 8

        busy = Scheduler(cfg, **kw)
        runner_done = queue.Queue()
        runner_started = threading.Event()
        busy.submit(
            Request(
                token_ids=[9, 8],
                sampling=SamplingParams(temperature=0.0, max_tokens=110),
                on_token=lambda t: runner_started.set(),
                on_done=runner_done.put,
                id="busy-runner",
            )
        )
        busy.start()
        try:
            assert runner_started.wait(timeout=60)
            got, _ = _collect(busy, long_prompt, max_tokens=4)
        finally:
            busy.cancel("busy-runner")
            runner_done.get(timeout=60)
            busy.stop()
        assert got == expected

    def test_pipelined_active_slots_counts_same_tick_admissions(self):
        """stats.active_slots must include lanes admitted THIS tick, as
        the sync tick reports (``/metrics`` and the benchmark's log read it)."""
        sched = Scheduler(
            CFG, max_batch=4, max_len=128, decode_chunk_size=4,
            prefix_cache="off",
        )
        # Drive ticks manually (scheduler thread not started).
        def submit(i):
            sched.submit(
                Request(
                    token_ids=[i + 1, i + 2],
                    sampling=SamplingParams(temperature=0.0, max_tokens=50),
                    on_token=lambda t: None,
                    on_done=lambda r: None,
                    id=f"occ-{i}",
                )
            )

        submit(0)
        sched._tick()  # idle-path admission of the first request
        submit(1)
        sched._tick()  # pipelined: decode snapshot [r0], admit r1
        assert sched.stats.snapshot()["active_slots"] == 2


class TestTickNormalization:
    KW = dict(max_batch=2, max_len=128, decode_chunk_size=4)

    def test_multi_token_ticks_normalize_tick_ms(self):
        """A tick emitting N tokens a lane-step is not N times slower — the
        ``engine.tick_ms`` signal (autoscaler, replica scorer, 429
        Retry-After) must be normalized to per-decode-chunk cost while
        the raw EWMA keeps wall-clock truth."""
        sched = Scheduler(CFG, **self.KW)  # never started
        for _ in range(60):
            # Synthetic tick: 1 decode dispatch, 24 tokens emitted
            # (chunk budget 4) in 60 ms -> normalized cost 10 ms.
            sched._tick_tokens = 24
            sched._tick_decoded = 1
            sched._note_tick(60.0)
        snap = sched.stats.snapshot()
        assert snap["tick_ms_ewma"] == pytest.approx(60.0, rel=0.05)
        assert snap["tick_ms_norm_ewma"] == pytest.approx(10.0, rel=0.05)

    def test_plain_ticks_unchanged(self):
        sched = Scheduler(CFG, **self.KW)
        for _ in range(60):
            sched._tick_tokens = 4  # == decode_chunk_size: no speedup
            sched._tick_decoded = 1
            sched._note_tick(20.0)
        snap = sched.stats.snapshot()
        assert snap["tick_ms_norm_ewma"] == pytest.approx(
            snap["tick_ms_ewma"], rel=0.01
        )


def test_engine_metrics_export_embed_batcher_series():
    """With the embedder wrapped in a BatchedEmbedder (--embed-max-batch),
    /v1/embeddings query calls ride the micro-batcher and /metrics
    exports the rag_* series next to the engine_* ones."""
    from generativeaiexamples_tpu.engine.embedder import HashEmbedder
    from generativeaiexamples_tpu.engine.microbatch import BatchedEmbedder
    from generativeaiexamples_tpu.engine.server import create_engine_app

    scheduler = Scheduler(CFG, max_batch=2, max_len=128, decode_chunk_size=4)
    scheduler.start()
    emb = BatchedEmbedder(
        HashEmbedder(dimensions=32), max_batch=8, max_wait_ms=1.0
    )
    app = create_engine_app(
        scheduler, ByteTokenizer(), embedder=emb, model_name="llama-tiny"
    )
    loop = asyncio.new_event_loop()
    client = TestClient(TestServer(app), loop=loop)
    loop.run_until_complete(client.start_server())
    try:

        async def go():
            r = await client.post(
                "/v1/embeddings",
                json={"model": "e", "input": "a query", "input_type": "query"},
            )
            assert r.status == 200
            body = await r.json()
            assert len(body["data"]) == 1
            # Multi-query requests dispatch as one embed_queries batch.
            r = await client.post(
                "/v1/embeddings",
                json={"model": "e", "input": ["q1", "q2"], "input_type": "query"},
            )
            assert r.status == 200
            return await (await client.get("/metrics")).text()

        metrics = loop.run_until_complete(go())
    finally:
        loop.run_until_complete(client.close())
        loop.close()
        emb.close()
        scheduler.stop()
    assert "engine_tokens_total" in metrics
    # One single-query call went through the batcher; the 2-query call
    # bypassed the queue (already a batch).
    assert "rag_requests_total 1" in metrics
    assert "rag_embed_batch_size_sum 1" in metrics
    assert "rag_embed_batch_size_count 1" in metrics
    assert "rag_queue_wait_ms_sum" in metrics
