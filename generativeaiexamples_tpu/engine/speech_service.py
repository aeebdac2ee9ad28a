"""Speech service: OpenAI-style ASR/TTS HTTP endpoints on TPU models.

The serving front for ``models.speech`` — replaces Riva's gRPC services
behind the same client utilities (``frontend/speech.py``):

* ``POST /v1/audio/transcriptions`` (multipart WAV) -> ``{"text": ...}``
* ``WS   /v1/audio/transcriptions/stream`` — *streaming* recognition, the
  Riva ``StreamingRecognize`` equivalent (reference
  ``frontend/asr_utils.py:91-155``): the client sends an optional JSON
  config frame ``{"type": "config", "sample_rate": N}`` then binary PCM16
  frames; the server pushes ``{"type": "partial"|"final", "text": ...}``
  as the incremental recognizer produces them, and a closing
  ``{"type": "done", "transcript": ...}`` after ``{"type": "end"}``.
* ``POST /v1/audio/speech`` ``{"input", "voice"}`` -> WAV bytes
* ``POST /v1/audio/speech/stream`` — *streaming* synthesis, the Riva
  ``synthesize_online`` equivalent (reference ``tts_utils.py:104-127``):
  input text is segmented below the 400-char request cap (300-char
  segments) and each segment's PCM16 audio streams back as a
  length-prefixed frame (u32 LE byte count + payload) as soon as it is
  synthesized; sample rate rides the ``X-Sample-Rate`` header.
* ``GET  /v1/audio/voices`` -> voice discovery (reference
  ``tts_utils.py:37-64``)
* ``GET  /health``

Like the LLM engine, it serves random-initialized weights when no
checkpoint is present under ``GAIE_WEIGHTS_DIR`` (architecture/serving
path exercised; quality needs trained weights).
"""

from __future__ import annotations

import asyncio
import io
import json
import wave
from typing import Optional

import numpy as np
from aiohttp import web

from generativeaiexamples_tpu.core.logging import get_logger
from generativeaiexamples_tpu.models import speech

logger = get_logger(__name__)

ASR_KEY = web.AppKey("asr", object)
TTS_KEY = web.AppKey("tts", object)


class SpeechEngine:
    """Holds ASR+TTS params and serializes device work onto one thread.

    ASR backends: the conformer (random-init unless trained in-process)
    or a TRAINED wav2vec2-CTC — either passed directly as
    ``w2v2=(cfg, params)`` or converted from an HF
    ``Wav2Vec2ForCTC`` checkpoint directory (``w2v2_dir`` /
    ``GAIE_W2V2_DIR``, via ``engine.weights.load_hf_wav2vec2``).  When a
    wav2vec2 model is present it serves BOTH the offline endpoint and the
    streaming websocket — trained-model streaming recognition, the Riva
    production-model contract (reference ``frontend/asr_utils.py:91-155``).
    """

    def __init__(
        self,
        asr_cfg: Optional[speech.ASRConfig] = None,
        tts_cfg: Optional[speech.TTSConfig] = None,
        seed: int = 0,
        *,
        w2v2: Optional[tuple] = None,
        w2v2_dir: Optional[str] = None,
        asr_params=None,
        tts_params=None,
    ) -> None:
        import os

        import jax

        self.asr_cfg = asr_cfg or speech.conformer_s()
        self.tts_cfg = tts_cfg or speech.fastspeech_s()
        k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
        self.w2v2_vocab = None  # custom CTC decode table (vocab.json)
        w2v2_dir = w2v2_dir or os.environ.get("GAIE_W2V2_DIR")
        # An explicitly-passed trained conformer wins over the
        # environment: GAIE_W2V2_DIR must not silently hijack an engine
        # constructed around asr_params.
        if w2v2 is None and w2v2_dir and asr_params is None:
            from generativeaiexamples_tpu.engine.weights import (
                load_hf_wav2vec2,
                w2v2_config_from_hf,
            )

            cfg = w2v2_config_from_hf(w2v2_dir)
            w2v2 = (cfg, load_hf_wav2vec2(cfg, w2v2_dir))
            logger.info("ASR backend: wav2vec2-CTC from %s", w2v2_dir)
            vocab_path = os.path.join(w2v2_dir, "vocab.json")
            if os.path.isfile(vocab_path):
                with open(vocab_path, encoding="utf-8") as fh:
                    tok_to_id = json.load(fh)
                self.w2v2_vocab = [""] * cfg.vocab_size
                for tok, i in tok_to_id.items():
                    if 0 <= int(i) < cfg.vocab_size:
                        self.w2v2_vocab[int(i)] = tok
        self.w2v2 = w2v2
        if asr_params is not None:
            self.asr_params = asr_params  # trained conformer
        elif w2v2 is None:
            self.asr_params = speech.asr_init_params(self.asr_cfg, k1)
        else:
            # A wav2vec2 backend serves both endpoints; don't initialize
            # (or hold) an unused conformer tree.
            self.asr_params = None
        self.tts_params = (
            tts_params
            if tts_params is not None
            else speech.tts_init_params(self.tts_cfg, k2)
        )
        self._mel_to_linear = np.linalg.pinv(
            speech.mel_filterbank(
                self.tts_cfg.n_mels, self.tts_cfg.n_fft, self.tts_cfg.fs
            ).T
        ).astype(np.float32)
        self.voices = ["default"]

    @property
    def asr_backend(self) -> str:
        return "wav2vec2-ctc" if self.w2v2 is not None else "conformer-ctc"

    def transcribe(self, pcm: np.ndarray) -> str:
        if self.w2v2 is not None:
            cfg, params = self.w2v2
            # pad=True buckets AFTER the HF-style utterance normalization
            # (stats over the utterance alone — HF-processor parity),
            # while keeping the streaming session's bounded compiled-
            # program count on this endpoint too.
            return speech.w2v2_transcribe(
                params, cfg, pcm, self.w2v2_vocab, pad=True
            )
        return speech.transcribe(self.asr_params, self.asr_cfg, pcm)

    def streaming_transcriber(self, **kwargs) -> "speech.StreamingTranscriber":
        """A fresh incremental-recognition session (one per stream)."""
        if self.w2v2 is not None:
            cfg, params = self.w2v2
            return speech.StreamingTranscriber.wav2vec2(
                params, cfg, vocab=self.w2v2_vocab, **kwargs
            )
        return speech.StreamingTranscriber(self.asr_params, self.asr_cfg, **kwargs)

    def synthesize(self, text: str) -> tuple[int, np.ndarray]:
        wave_f = speech.synthesize(
            self.tts_params, self.tts_cfg, text, mel_to_linear=self._mel_to_linear
        )
        return self.tts_cfg.fs, (wave_f * 32767).astype(np.int16)


def _read_wav(data: bytes) -> np.ndarray:
    with wave.open(io.BytesIO(data), "rb") as w:
        rate = w.getframerate()
        pcm = np.frombuffer(w.readframes(w.getnframes()), np.int16)
        if w.getnchannels() > 1:
            pcm = pcm.reshape(-1, w.getnchannels()).mean(-1).astype(np.int16)
    return _resample_to_16k(pcm.astype(np.float32) / 32768.0, rate)


def _write_wav(rate: int, pcm: np.ndarray) -> bytes:
    out = io.BytesIO()
    with wave.open(out, "wb") as w:
        w.setnchannels(1)
        w.setsampwidth(2)
        w.setframerate(rate)
        w.writeframes(pcm.tobytes())
    return out.getvalue()


async def handle_transcriptions(request: web.Request) -> web.Response:
    engine: SpeechEngine = request.app[ASR_KEY]
    reader = await request.multipart()
    audio_bytes = b""
    field = await reader.next()
    while field is not None:
        if field.name == "file":
            audio_bytes = await field.read()
        field = await reader.next()
    if not audio_bytes:
        return web.json_response({"text": "", "message": "no file"}, status=400)
    try:
        pcm = _read_wav(audio_bytes)
    except Exception:
        return web.json_response(
            {"text": "", "message": "undecodable audio (expect WAV/PCM16)"},
            status=400,
        )
    text = await asyncio.get_running_loop().run_in_executor(
        None, engine.transcribe, pcm
    )
    return web.json_response({"text": text})


def _resample_to_16k(audio: np.ndarray, rate: int) -> np.ndarray:
    if rate == 16_000 or not len(audio):
        return audio
    pos = np.linspace(0, len(audio) - 1, int(len(audio) * 16_000 / rate))
    return np.interp(pos, np.arange(len(audio)), audio).astype(np.float32)


async def handle_stream_transcriptions(request: web.Request) -> web.WebSocketResponse:
    """Streaming recognition over a websocket (see module docstring)."""
    engine: SpeechEngine = request.app[ASR_KEY]
    ws = web.WebSocketResponse()
    await ws.prepare(request)
    session = engine.streaming_transcriber()
    loop = asyncio.get_running_loop()
    rate = 16_000
    graceful = False
    carry = b""  # dangling byte of an odd-split int16 frame
    try:
        async for msg in ws:
            if msg.type == web.WSMsgType.TEXT:
                try:
                    data = json.loads(msg.data)
                except ValueError:
                    continue
                if data.get("type") == "config":
                    rate = int(data.get("sample_rate", 16_000)) or 16_000
                elif data.get("type") == "end":
                    graceful = True
                    break
            elif msg.type == web.WSMsgType.BINARY:
                # Frames may split int16 samples at odd byte boundaries;
                # carry the dangling byte into the next frame so sample
                # alignment survives (dropping it would desync the whole
                # remaining stream into noise).
                data = carry + msg.data
                cut = len(data) & ~1
                raw, carry = data[:cut], data[cut:]
                if not raw:
                    continue
                pcm = (
                    np.frombuffer(raw, dtype=np.int16).astype(np.float32)
                    / 32768.0
                )
                pcm = _resample_to_16k(pcm, rate)
                events = await loop.run_in_executor(None, session.feed, pcm)
                for ev in events:
                    await ws.send_json(
                        {
                            "type": "final" if ev["is_final"] else "partial",
                            "text": ev["text"],
                        }
                    )
            elif msg.type in (web.WSMsgType.CLOSE, web.WSMsgType.ERROR):
                break
        if graceful:
            # Only a client that said "end" is still listening; after an
            # abrupt disconnect these sends would raise on a dead socket.
            for ev in await loop.run_in_executor(None, session.finish):
                await ws.send_json(
                    {
                        "type": "final" if ev["is_final"] else "partial",
                        "text": ev["text"],
                    }
                )
            await ws.send_json(
                {"type": "done", "transcript": session.transcript}
            )
    except ConnectionResetError:
        logger.info("streaming ASR client disconnected mid-stream")
    finally:
        await ws.close()
    return ws


async def handle_speech_stream(request: web.Request) -> web.StreamResponse:
    """Streaming synthesis: length-prefixed PCM16 frames per <=300-char
    segment (see module docstring)."""
    from generativeaiexamples_tpu.frontend.speech import segment_text

    engine: SpeechEngine = request.app[TTS_KEY]
    body = await request.json()
    text = str(body.get("input", ""))
    if not text.strip():
        return web.json_response({"message": "empty input"}, status=400)
    resp = web.StreamResponse(
        headers={
            "Content-Type": "application/octet-stream",
            "X-Sample-Rate": str(engine.tts_cfg.fs),
        }
    )
    await resp.prepare(request)
    loop = asyncio.get_running_loop()
    for segment in segment_text(text):
        _, pcm = await loop.run_in_executor(None, engine.synthesize, segment)
        payload = pcm.tobytes()
        await resp.write(len(payload).to_bytes(4, "little") + payload)
    await resp.write_eof()
    return resp


async def handle_speech(request: web.Request) -> web.Response:
    engine: SpeechEngine = request.app[TTS_KEY]
    body = await request.json()
    text = str(body.get("input", ""))[:400]  # Riva-parity request cap
    if not text.strip():
        return web.json_response({"message": "empty input"}, status=400)
    rate, pcm = await asyncio.get_running_loop().run_in_executor(
        None, engine.synthesize, text
    )
    return web.Response(body=_write_wav(rate, pcm), content_type="audio/wav")


async def handle_voices(request: web.Request) -> web.Response:
    engine: SpeechEngine = request.app[TTS_KEY]
    return web.json_response(
        {"voices": [{"name": v, "language": "en-US"} for v in engine.voices]}
    )


async def handle_health(request: web.Request) -> web.Response:
    engine: SpeechEngine = request.app[ASR_KEY]
    return web.json_response(
        {"message": "Service is up.", "asr_backend": engine.asr_backend}
    )


def create_speech_app(engine: Optional[SpeechEngine] = None) -> web.Application:
    engine = engine or SpeechEngine()
    app = web.Application(client_max_size=1024 * 1024 * 64)
    app[ASR_KEY] = engine
    app[TTS_KEY] = engine
    app.router.add_post("/v1/audio/transcriptions", handle_transcriptions)
    app.router.add_get(
        "/v1/audio/transcriptions/stream", handle_stream_transcriptions
    )
    app.router.add_post("/v1/audio/speech", handle_speech)
    app.router.add_post("/v1/audio/speech/stream", handle_speech_stream)
    app.router.add_get("/v1/audio/voices", handle_voices)
    app.router.add_get("/health", handle_health)
    return app


def main() -> None:
    import argparse
    import os

    from generativeaiexamples_tpu.core.logging import configure_logging

    parser = argparse.ArgumentParser(description="TPU speech service (ASR+TTS)")
    parser.add_argument("--host", default="0.0.0.0")
    parser.add_argument("--port", type=int, default=8020)
    parser.add_argument("--tiny", action="store_true", help="tiny configs (smoke)")
    parser.add_argument(
        "--w2v2-dir",
        default=None,
        help="HF Wav2Vec2ForCTC checkpoint dir: serve trained ASR "
        "(offline + streaming) instead of the random-init conformer",
    )
    parser.add_argument("-v", "--verbose", action="count", default=None)
    args = parser.parse_args()
    configure_logging(args.verbose)
    from generativeaiexamples_tpu.utils.jax_runtime import (
        enable_compile_cache,
        require_accelerator,
    )

    require_accelerator("speech service")
    enable_compile_cache()
    engine = (
        SpeechEngine(speech.asr_tiny(), speech.tts_tiny(), w2v2_dir=args.w2v2_dir)
        if args.tiny
        else SpeechEngine(w2v2_dir=args.w2v2_dir)
    )
    web.run_app(create_speech_app(engine), host=args.host, port=args.port, print=None)


if __name__ == "__main__":
    main()
