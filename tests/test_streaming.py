"""Streaming SDR -> ASR -> RAG: DSP math, accumulator, DB, chains, server.

DSP blocks are validated against scipy.signal references and an analytic
FM tone round-trip (modulate in numpy -> demodulate through the JAX chain
-> recover the tone); the service path runs the real aiohttp app with
scripted LLM + hash embedder.
"""

import asyncio
import json
import time

import numpy as np
import pytest
import scipy.signal

from generativeaiexamples_tpu.streaming import dsp
from generativeaiexamples_tpu.streaming.accumulator import TextAccumulator
from generativeaiexamples_tpu.streaming.timestamps import TimestampDatabase


class TestFIR:
    def test_firwin_matches_scipy(self):
        taps = dsp.firwin_lowpass(101, 16_000, 250_000)
        ref = scipy.signal.firwin(101, 16_000, fs=250_000)
        np.testing.assert_allclose(taps, ref, atol=1e-6)

    def test_streaming_blocks_match_one_shot_lfilter(self):
        rng = np.random.default_rng(0)
        x = rng.normal(size=8192).astype(np.float32)
        taps = dsp.firwin_lowpass(101, 16_000, 250_000)
        want = scipy.signal.lfilter(taps, [1.0], x)

        lp = dsp.LowPassFilter(16_000, 250_000, 101)
        got = np.concatenate([np.asarray(lp(x[i : i + 1024])) for i in range(0, 8192, 1024)])
        np.testing.assert_allclose(got, want, atol=1e-4)

    def test_complex_blocks(self):
        rng = np.random.default_rng(1)
        x = (rng.normal(size=4096) + 1j * rng.normal(size=4096)).astype(np.complex64)
        taps = dsp.firwin_lowpass(51, 50_000, 250_000)
        want = scipy.signal.lfilter(taps, [1.0], x)
        lp = dsp.LowPassFilter(50_000, 250_000, 51)
        got = np.concatenate([np.asarray(lp(x[:2048])), np.asarray(lp(x[2048:]))])
        np.testing.assert_allclose(got, want, atol=1e-4)


class TestFMChain:
    def test_tone_roundtrip(self):
        """1 kHz tone -> FM modulate -> receiver chain -> 1 kHz tone out."""
        from generativeaiexamples_tpu.streaming.replay import fm_modulate

        fs_audio, fs_bb = 16_000, 256_000
        t = np.arange(fs_audio) / fs_audio  # 1 second
        audio = 0.8 * np.sin(2 * np.pi * 1000 * t)
        iq = fm_modulate(audio, fs_audio, fs_bb, deviation_hz=75e3)

        rx = dsp.FMReceiverChain(
            dsp.FMReceiverConfig(fs_baseband=fs_bb, fs_audio=fs_audio)
        )
        out = np.concatenate(
            [rx(iq[i : i + 62_500]) for i in range(0, len(iq), 62_500)]
        ).astype(np.float32) / 32767.0

        # Dominant frequency of the demodulated audio must be 1 kHz.
        spec = np.abs(np.fft.rfft(out[2000:]))  # skip filter warmup
        freqs = np.fft.rfftfreq(len(out) - 2000, 1 / fs_audio)
        assert abs(freqs[spec.argmax()] - 1000) < 20

    def test_pcm16_clipping(self):
        pcm = np.asarray(dsp.to_pcm16(np.asarray([-2.0, -1.0, 0.0, 1.0, 2.0])))
        assert pcm[0] == -32767 and pcm[-1] == 32767 and pcm[2] == 0

    def test_resampler_preserves_tone(self):
        fs_in, fs_out = 250_000, 16_000
        t = np.arange(fs_in) / fs_in
        x = np.sin(2 * np.pi * 2000 * t).astype(np.float32)
        rs = dsp.Resampler(fs_in, fs_out)
        y = np.asarray(rs(x))
        assert len(y) == fs_out
        spec = np.abs(np.fft.rfft(y[1000:]))
        freqs = np.fft.rfftfreq(len(y) - 1000, 1 / fs_out)
        assert abs(freqs[spec.argmax()] - 2000) < 20


class TestAccumulator:
    def test_chunking_with_overlap(self):
        chunks = []
        acc = TextAccumulator(
            lambda text, src, t0, t1: chunks.append((text, src)),
            chunk_chars=100,
            overlap_chars=20,
        )
        for _ in range(10):
            acc.update("word " * 8, source="radio")  # 40 chars per update
        assert chunks
        assert all(len(c) == 100 for c, _ in chunks)
        # Consecutive chunks share the 20-char overlap.
        tail = chunks[0][0][-20:]
        assert chunks[1][0].startswith(tail)

    def test_flush_emits_partial(self):
        chunks = []
        acc = TextAccumulator(lambda *a: chunks.append(a), chunk_chars=1000)
        acc.update("short transcript")
        assert not chunks
        assert acc.flush() == 1
        assert chunks[0][0] == "short transcript"
        assert acc.pending() == ""

    def test_sources_are_independent(self):
        chunks = []
        acc = TextAccumulator(
            lambda text, src, t0, t1: chunks.append(src), chunk_chars=50, overlap_chars=10
        )
        acc.update("a" * 49, source="s1")
        acc.update("b" * 60, source="s2")
        assert chunks == ["s2"]

    def test_concurrent_updates_race_free(self):
        import threading

        chunks = []
        acc = TextAccumulator(
            lambda text, src, t0, t1: chunks.append(text), chunk_chars=64, overlap_chars=8
        )
        threads = [
            threading.Thread(
                target=lambda: [acc.update("x" * 16, source="s") for _ in range(50)]
            )
            for _ in range(8)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(30)
        acc.flush("s")
        total = sum(len(c) for c in chunks)
        # Every character is preserved modulo the per-chunk overlap re-emits.
        overlap_extra = (len(chunks) - 1) * 8
        assert total - overlap_extra >= 8 * 50 * 16 - 64

    def test_concurrent_multi_source_integrity(self):
        """Regression: per-source locking.  Threads hammering distinct
        sources plus one shared source must lose no text and never
        interleave another source's bytes into a chunk."""
        import threading

        chunks: dict[str, list[str]] = {}
        lock = threading.Lock()

        def sink(text, src, t0, t1):
            with lock:
                chunks.setdefault(src, []).append(text)

        acc = TextAccumulator(sink, chunk_chars=64, overlap_chars=8)
        marks = {"s1": "a", "s2": "b", "shared": "c"}

        def pump(source, mark):
            for _ in range(200):
                acc.update(mark * 16, source=source)

        threads = [threading.Thread(target=pump, args=("s1", "a"))]
        threads += [threading.Thread(target=pump, args=("s2", "b"))]
        threads += [
            threading.Thread(target=pump, args=("shared", "c"))
            for _ in range(4)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(30)
        for source in marks:
            acc.flush(source)
        for source, mark in marks.items():
            # Chunks carry only this source's marker (plus separators).
            assert all(
                set(c) <= {mark, " "} for c in chunks[source]
            ), f"foreign bytes leaked into {source}"
        # Character conservation per source, modulo overlap re-emits.
        for source, writers in (("s1", 1), ("s2", 1), ("shared", 4)):
            got = chunks[source]
            total = sum(len(c) for c in got)
            overlap_extra = (len(got) - 1) * 8
            assert total - overlap_extra >= writers * 200 * 16

    def test_slow_sink_on_one_source_does_not_block_others(self):
        """A sink stalled mid-flush for one source must not stop an
        independent source from flushing (the reference repo's
        acknowledged multi-stream race/serialization TODO)."""
        import threading

        stall = threading.Event()
        entered = threading.Event()
        flushed = []

        def sink(text, src, t0, t1):
            if src == "slow":
                entered.set()
                assert stall.wait(5), "test orchestration failed"
            flushed.append(src)

        acc = TextAccumulator(sink, chunk_chars=32, overlap_chars=4)
        blocker = threading.Thread(
            target=lambda: acc.update("s" * 40, source="slow")
        )
        blocker.start()
        assert entered.wait(5)
        # The slow sink holds its source's lock; the fast source must
        # still complete promptly on this thread.
        done = threading.Event()

        def fast():
            acc.update("f" * 40, source="fast")
            done.set()

        t = threading.Thread(target=fast)
        t.start()
        assert done.wait(2), "independent source blocked by slow sink"
        stall.set()
        blocker.join(5)
        t.join(5)
        assert "fast" in flushed and "slow" in flushed


class TestTimestampDatabase:
    def test_recent_and_window(self):
        db = TimestampDatabase()
        now = 1000.0
        db.insert("old", "s", 100, 110)
        db.insert("mid", "s", 500, 510)
        db.insert("new", "s", 990, 995)
        recent = db.recent(30, now)
        assert [r["text"] for r in recent] == ["new"]
        window = db.window(490, 520)
        assert [r["text"] for r in window] == ["mid"]
        assert db.count() == 3
        assert len(db.all_chunks()) == 3
        db.close()


class TestStreamingChains:
    def _mk(self, llm_responses):
        from generativeaiexamples_tpu.chains.llm import ScriptedChatLLM
        from generativeaiexamples_tpu.engine.embedder import HashEmbedder
        from generativeaiexamples_tpu.retrieval.memory import MemoryVectorStore
        from generativeaiexamples_tpu.streaming.chains import StreamingChains

        return StreamingChains(
            ScriptedChatLLM(llm_responses),
            HashEmbedder(dimensions=32),
            MemoryVectorStore(dimensions=32),
            TimestampDatabase(),
        )

    def test_relevance_route(self):
        chains = self._mk(["relevance", "the answer"])
        chains.store_chunk("TPUs use systolic arrays.", "radio", 10, 20)
        out = "".join(chains.answer("what do TPUs use?", now=100))
        assert out == "the answer"

    def test_recent_route_uses_db(self):
        chains = self._mk(["recent", "they talked about weather"])
        chains.store_chunk("weather report sunny", "radio", 90, 95)
        out = "".join(chains.answer("what was just said?", now=100))
        assert "weather" in out

    def test_past_route_parses_window(self):
        chains = self._mk(
            ["past", '{"start": 400, "end": 600}', "mid content answer"]
        )
        chains.store_chunk("mid content", "radio", 500, 510)
        out = "".join(chains.answer("what was said at minute 8?", now=1000))
        assert out == "mid content answer"

    def test_unparseable_intent_defaults_to_relevance(self):
        chains = self._mk(["banana", "fallback answer"])
        assert "".join(chains.answer("q", now=1)) == "fallback answer"


@pytest.fixture
def streaming_client():
    from aiohttp.test_utils import TestClient, TestServer

    from generativeaiexamples_tpu.chains.llm import EchoChatLLM
    from generativeaiexamples_tpu.engine.embedder import HashEmbedder
    from generativeaiexamples_tpu.retrieval.memory import MemoryVectorStore
    from generativeaiexamples_tpu.streaming.chains import StreamingChains
    from generativeaiexamples_tpu.streaming.server import create_streaming_app

    chains = StreamingChains(
        EchoChatLLM(),
        HashEmbedder(dimensions=32),
        MemoryVectorStore(dimensions=32),
        TimestampDatabase(),
    )
    loop = asyncio.new_event_loop()
    client = TestClient(TestServer(create_streaming_app(chains)), loop=loop)
    loop.run_until_complete(client.start_server())
    yield client, loop, chains
    loop.run_until_complete(client.close())
    loop.close()


class TestStreamingServer:
    def test_store_flush_generate(self, streaming_client):
        client, loop, chains = streaming_client

        async def go():
            resp = await client.post(
                "/storeStreamingText", json={"text": "breaking news about tpus"}
            )
            assert resp.status == 200
            resp = await client.post("/flush", json={"source": "stream"})
            assert (await resp.json())["chunks_flushed"] == 1
            assert chains.db.count() == 1

            resp = await client.post(
                "/generate",
                json={
                    "messages": [{"role": "user", "content": "what about tpus?"}],
                    "use_knowledge_base": True,
                    "max_tokens": 16,
                },
            )
            text = await resp.text()
            chunks = [
                json.loads(l[6:]) for l in text.splitlines() if l.startswith("data: ")
            ]
            assert chunks[-1]["choices"][0]["finish_reason"] == "[DONE]"

        loop.run_until_complete(go())

    def test_empty_text_rejected(self, streaming_client):
        client, loop, _ = streaming_client

        async def go():
            resp = await client.post("/storeStreamingText", json={"text": "  "})
            assert resp.status == 400

        loop.run_until_complete(go())


class TestUDPEndToEnd:
    def test_replay_through_pipeline(self):
        """UDP I/Q replay -> operator graph -> FM receiver -> PCM sink."""
        from generativeaiexamples_tpu.streaming.graph import Operator, Pipeline, UDPSource
        from generativeaiexamples_tpu.streaming.replay import fm_modulate, replay_iq

        fs_audio, fs_bb = 16_000, 256_000
        t = np.arange(fs_audio // 2) / fs_audio
        audio = 0.8 * np.sin(2 * np.pi * 800 * t)
        iq = fm_modulate(audio, fs_audio, fs_bb, deviation_hz=75e3)

        rx = dsp.FMReceiverChain(
            dsp.FMReceiverConfig(fs_baseband=fs_bb, fs_audio=fs_audio)
        )
        pcm_out = []
        pipeline = Pipeline(
            [
                Operator("fm-rx", rx),
                Operator("sink", lambda pcm: pcm_out.append(np.asarray(pcm))),
            ]
        )
        pipeline.start()
        src = UDPSource(pipeline, port=0, block_samples=16384)
        src.start()
        try:
            # 30x real time: fast, but paced so the kernel buffer can't
            # overflow while the first DSP block compiles.
            replay_iq(iq, "127.0.0.1", src.port, fs_bb, speed=30)
            deadline = time.time() + 30
            want_blocks = len(iq) // 16384
            while len(pcm_out) < want_blocks and time.time() < deadline:
                time.sleep(0.1)
        finally:
            src.stop()
            pipeline.stop()
        assert pcm_out, "no PCM blocks emerged from the pipeline"
        out = np.concatenate(pcm_out).astype(np.float32) / 32767.0
        skip = min(1000, max(len(out) - 2048, 0))
        spec = np.abs(np.fft.rfft(out[skip:]))
        freqs = np.fft.rfftfreq(len(out) - skip, 1 / fs_audio)
        assert abs(freqs[spec.argmax()] - 800) < 30
