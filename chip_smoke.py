#!/usr/bin/env python3
"""Chip smoke: the serving path, end to end, on one TPU chip.

    python chip_smoke.py [--seed N]          # one chip (what the driver runs)
    python chip_smoke.py --four-chips        # the two multi-chip paths only
    python chip_smoke.py --hybrid [--model ling|mellum|exaone_moe|mistral4|zaya|nemotron_h|dots3_note|deepseek_v32|longcat_flash] [--control NAME]   # phase 5 only

Drives the system through the entry points a user calls, at llama3-8b's
published widths with random int8 weights from ``--seed``:

1. ``device``   — what JAX finds; anything but a TPU ends the run.
2. ``serve``    — ``python -m generativeaiexamples_tpu.engine.server
   --model llama3-8b --embedder arctic`` (32 layers, int8 weights, int8
   KV) answers concurrent streaming chat completions with ~1,500-token
   prompts and an embeddings batch; then the chain server
   (``python -m generativeaiexamples_tpu.server``, placed on the CPU by
   an explicit ``JAX_PLATFORMS=cpu`` as its container is) takes a
   document and streams a ``/generate`` with the knowledge base on.
3. ``retrieval`` — the ``tpu`` vector store over 262,144 x 1024 rows
   against a numpy top-k; ``vector_store.name=auto`` picks a TPU store.
4. ``optin``    — ``matmul_kernel=pallas_w8a8`` decodes greedy streams
   through the ``Scheduler`` (8 layers) and matches its XLA twin's
   streams token for token.

5. ``hybrid`` (``--hybrid``; not part of the default run) — a layer-kind
   model (``models/hybrid.py``) at its published widths, in process on
   the step programs' own calls: logits of a chunked prefill and then 64
   positions decoded through the state, against the plain float32
   reference's full forward over the same tokens.  ``--model ling`` (the
   default): ling-3.0-flash-vl-l7e128, KDA beside MLA and a share of the
   experts, a prompt of two chunks.  ``--model mellum``:
   mellum2-12b-a2.5b-l12, window layers beside full ones, a prompt of
   1,300 tokens, so that the rings have wrapped and YaRN has passed a
   chunk.  A ``--control`` has to fail the comparison: ``w8a8_mlp``
   computes the reference's MLP products as W8A8 matmuls, the nearest
   precision below the configuration's; for ``mellum`` also ``no_window``
   (the program's window layers attend to everything) and ``no_yarn``
   (its full layers take the plain frequencies and factor 1).
   ``--model exaone_moe``: k-exaone-236b-a23b-l5e16, whose decode step
   verifies the draft of its own prediction module: a prompt of 800
   tokens in chunks of 256, its last 32 positions through the verify step
   on true and on wrong drafts, and the module's logits, by the
   benchmark's own comparison (``benchmarks/arch/exaone_moe.py``) against
   the configuration's limits; controls ``no_qk_norm``, ``rope_on_full``,
   ``no_window``, ``stale_reject`` (a rejected draft's position counted as
   written) and ``w8a8_mlp``.  ``--model mistral4``:
   mistral-small-4-119b-l6e32, latent attention in every layer: a prompt
   of 8,448 tokens (past ``original_max_position_embeddings``, so both
   values of the query's position scale and both regimes of YaRN's blend
   are read) in chunks of 256 through the chunk program the scheduler
   runs (``prefill_rows``, the slots' 32,768 rows in place), its last 16
   positions through the absorbed decode step, by the benchmark's own
   comparison (``benchmarks/arch/mistral4.py``) against the
   configuration's limits; controls ``no_attn_scale`` (``a(p)`` = 1),
   ``plain_rope`` (YaRN off), ``no_mscale`` (``m`` = 1), ``no_q_norm``
   (the query's latent not normed) and ``w8a8_mlp``.  ``--model zaya``:
   zaya1-8b-l20, compressed convolutional attention in every layer and
   one expert a token through the ZAYA router: a prompt of 800 tokens in
   chunks of 256 through the chunk program (``prefill_rows`` on a state of
   8,192 rows a slot: every chunk after the first takes its convolutions'
   history and its late values from the slot's tails), its last 16
   positions through the decode step, by the benchmark's own comparison
   (``benchmarks/arch/zaya.py``) against the configuration's limits;
   controls, each the reference without one mechanism: ``no_value_shift``,
   ``no_qk_mean``, ``no_conv``, ``no_router_average``, ``renormed_top1``
   (the chosen expert weighted 1, not by its probability) and ``w8a8``
   (the nearest precision below in every projection of a layer: the
   experts' products alone, the other families' ``w8a8_mlp``, read inside
   the sound runs' spread here, since an expert's output enters the stream
   times its probability).  ``--model dots3_note``: a prompt of 4,608
   tokens (past twice ``index_topk``) in chunks of 256 through the chunk
   program, its last 16 positions through the decode step, by the
   benchmark's own comparison (``benchmarks/arch/dots3_note.py``: the
   logit shares and ``index_overlap``, the share of the rows the program's
   indexer keeps that the reference keeps); controls, each the reference
   without one mechanism: ``no_selection`` (every row attended),
   ``last_2048`` (the newest rows for the highest scores),
   ``no_index_relu``, ``no_rescale``, ``no_gate``, ``full_sizes_in_window``
   (a sliding layer given the full layers' theta) and ``w8a8_mlp``.
   ``--model deepseek_v32``: a prompt of 4,608 tokens in chunks of 256
   through the chunk program, its last 32 positions through the VERIFY
   step on true and on wrong drafts (both of a step's positions select
   for themselves), and the prediction module's logits, by the
   benchmark's own comparison (``benchmarks/arch/deepseek_v32.py``: the
   logit shares and the index overlaps of the stack and of the module's
   block); controls: ``no_selection``, ``last_2048``, ``no_index_relu``,
   ``no_groups`` (the 8 best of all 256 router outputs), ``no_mscale``,
   ``draft_shares_set`` (every second position attends the set of the one
   before it), ``stale_reject`` and ``w8a8_mlp``.
   ``--model longcat_flash``: longcat-flash-chat-l4e16, four published
   layers of two latent sublayers, two dense MLPs and one expert layer
   whose sum is added after the second sublayer's dense MLP, under a
   router of 768 outputs of which 256 are identity experts: a prompt of
   4,864 tokens (the cell's reference length) in chunks of 256 through
   the chunk program (``prefill_rows``, the slots' 16,384 rows in place),
   its last 16 positions through the decode step, by the benchmark's own
   comparison (``benchmarks/arch/longcat_flash.py``) against the
   configuration's limits; controls, each the reference with one step
   changed: ``no_latent_rescale`` (the normed latents not scaled),
   ``no_zero_identity`` (identity choices dropped), ``shortcut_early`` (the
   experts' sum added before the second sublayer), ``renormed_weights``
   (the 12 weights renormalised to sum to one before the scaling factor)
   and ``w8a8_mlp``.

The parent imports no JAX: the chip belongs to one process at a time, so
each phase is a child (or the pair engine + chain server) that has
exited before the next starts.  Every phase prints one JSON line; any
failure, or a device that is not the expected one, exits non-zero.  The
last line is ``{"ok": true, "device": {...}}`` and nothing else.
"""

from __future__ import annotations

import argparse
import contextlib
import functools
import dataclasses
import json
import os
import re
import shutil
import signal
import socket
import subprocess
import sys
import tempfile
import threading
import time
import urllib.error
import urllib.request
import uuid

ROOT = os.path.dirname(os.path.abspath(__file__))
PACKAGE = "generativeaiexamples_tpu"


class SmokeFailure(Exception):
    """A phase did not do what it must; the message says which and why."""


@dataclasses.dataclass(frozen=True)
class Sizes:
    """Everything that differs between the chip run and its rehearsal."""

    model: str = "llama3-8b"
    embedder: str = "arctic"
    embed_dim: int = 1024
    max_batch: int = 32
    max_len: int = 2048
    # Byte tokenizer, and the chat template adds 100 tokens: 1,500-token
    # prompts (chunked prefill), and one of 220 that prefills cold in the
    # s=256 bucket — the flash kernel's shape under default chunking.
    prompt_chars: int = 1400
    short_prompt_chars: int = 120
    new_tokens: int = 96
    concurrent: int = 4
    embed_batch: int = 8
    corpus_rows: int = 262_144
    queries: int = 32
    top_k: int = 4
    optin_layers: int = 8
    optin_model: str = "llama3-8b"
    hybrid_model: str = "ling-3.0-flash-vl-l7e128"
    # Two prefill chunks (a whole one and a padded one), then decoding.
    hybrid_chunks: tuple = (256, 128)
    hybrid_decode: int = 64
    # ``--model mellum``: chunks of ``hybrid_chunks[0]`` over a prompt
    # longer than the window (the last one padded), then decoding.
    mellum_model: str = "mellum2-12b-a2.5b-l12"
    mellum_prompt: int = 1300
    # ``--model exaone_moe``: a prompt longer than five windows (128) in
    # chunks of ``exaone_chunk``, its last ``exaone_verify`` positions
    # through the verify step on true and on wrong drafts.
    exaone_model: str = "k-exaone-236b-a23b-l5e16"
    exaone_prompt: int = 800
    exaone_chunk: int = 256
    exaone_verify: int = 32
    # ``--model mistral4``: a prompt past the original context (8,192) in
    # chunks of ``mistral4_chunk``, its last ``mistral4_decode`` positions
    # through the decode step.
    mistral4_model: str = "mistral-small-4-119b-l6e32"
    mistral4_prompt: int = 8448
    mistral4_chunk: int = 256
    mistral4_decode: int = 16
    # ``--model zaya``: a prompt of the cell's reference length in chunks
    # of ``zaya_chunk``, its last ``zaya_decode`` positions through the
    # decode step.
    zaya_model: str = "zaya1-8b-l20"
    zaya_prompt: int = 800
    zaya_chunk: int = 256
    zaya_decode: int = 16
    # ``--model nemotron_h``: likewise (two scan blocks of 128 a chunk).
    nemotron_model: str = "nemotron-3-super-120b-a12b-l11e128"
    nemotron_prompt: int = 800
    nemotron_chunk: int = 256
    nemotron_decode: int = 16
    # ``--model dots3_note``: a prompt past twice ``index_topk`` (the
    # selection keeps under half of the rows at its end).
    dots3_model: str = "dots3-note-prev-l6e32"
    dots3_prompt: int = 4608
    dots3_chunk: int = 256
    dots3_decode: int = 16
    # ``--model deepseek_v32``: a prompt past twice ``index_topk``, its
    # last ``deepseek_verify`` positions through the verify step on true
    # and on wrong drafts.
    deepseek_model: str = "deepseek-v3.2-l5e16"
    deepseek_prompt: int = 4608
    deepseek_chunk: int = 256
    deepseek_verify: int = 32
    # Not 0: program AND reference keep this many rows (16,384: every row a
    # query sees, which tells what of a reading the selection's flips make).
    deepseek_topk: int = 0
    # ``--model longcat_flash``: a prompt of the cell's reference length
    # in chunks of ``longcat_chunk``, its last ``longcat_decode`` positions
    # through the decode step.
    longcat_model: str = "longcat-flash-chat-l4e16"
    longcat_prompt: int = 4864
    longcat_chunk: int = 256
    longcat_decode: int = 16
    kv_heads: int = 8  # the preset's own; the tiny rehearsal needs 4 to split
    start_timeout_s: float = 600.0
    request_timeout_s: float = 600.0


FULL = Sizes()
# The CPU rehearsal of the control flow (tests/test_chip_smoke.py).
TINY = Sizes(
    model="llama-tiny",
    embedder="tiny",
    embed_dim=64,
    max_batch=4,
    max_len=256,
    prompt_chars=150,
    short_prompt_chars=40,
    new_tokens=8,
    concurrent=2,
    embed_batch=4,
    corpus_rows=2048,
    queries=8,
    optin_layers=2,
    optin_model="llama-tiny",
    hybrid_model="ling-tiny",
    hybrid_chunks=(32, 16),
    hybrid_decode=8,
    mellum_model="mellum-tiny",
    mellum_prompt=75,
    exaone_model="exaone_moe-tiny",
    exaone_prompt=60,
    exaone_chunk=16,
    exaone_verify=8,
    mistral4_model="mistral4-tiny",
    mistral4_prompt=75,
    mistral4_chunk=16,
    mistral4_decode=8,
    zaya_model="zaya-tiny",
    zaya_prompt=45,
    zaya_chunk=16,
    zaya_decode=8,
    nemotron_model="nemotron_h-tiny",
    nemotron_prompt=45,
    nemotron_chunk=16,
    nemotron_decode=8,
    dots3_model="dots3_note-tiny",
    dots3_prompt=75,
    dots3_chunk=16,
    dots3_decode=8,
    deepseek_model="deepseek_v32-tiny",
    deepseek_prompt=76,
    deepseek_chunk=16,
    deepseek_verify=12,
    longcat_model="longcat_flash-tiny",
    longcat_prompt=75,
    longcat_chunk=16,
    longcat_decode=8,
    kv_heads=4,
    start_timeout_s=240.0,
    request_timeout_s=240.0,
)


def emit(line: dict) -> None:
    print(json.dumps(line), flush=True)


# --------------------------------------------------------------------------
# Parent-side plumbing: children, HTTP, SSE (stdlib only — no JAX here).
# --------------------------------------------------------------------------


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _child_env(**extra: str) -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = ROOT + os.pathsep + env.get("PYTHONPATH", "")
    env.setdefault("HF_HUB_OFFLINE", "1")
    env.setdefault("TRANSFORMERS_OFFLINE", "1")
    env["LOGLEVEL"] = "INFO"  # the phases read the servers' logs
    env.update(extra)
    return env


class Server:
    """A child server process with its log in a file; always stopped."""

    def __init__(self, name: str, argv: list, env: dict, logdir: str) -> None:
        self.name = name
        self.log_path = os.path.join(logdir, f"{name}.log")
        self._log = open(self.log_path, "wb")
        self.proc = subprocess.Popen(
            [sys.executable, *argv],
            cwd=ROOT,
            env=env,
            stdout=self._log,
            stderr=subprocess.STDOUT,
        )

    def log_tail(self, n: int = 2000) -> str:
        with open(self.log_path, "rb") as f:
            return f.read()[-n:].decode(errors="replace")

    def log_has(self, needle: str) -> bool:
        with open(self.log_path, "rb") as f:
            return needle.encode() in f.read()

    def log_count(self, pattern: str) -> int:
        """The last number ``pattern`` captured in the log, or 0."""
        with open(self.log_path, "rb") as f:
            found = re.findall(pattern, f.read().decode(errors="replace"))
        return int(found[-1]) if found else 0

    def wait_healthy(self, url: str, timeout: float) -> dict:
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if self.proc.poll() is not None:
                raise SmokeFailure(
                    f"{self.name} exited with code {self.proc.returncode} "
                    f"before it served: {self.log_tail()}"
                )
            try:
                return _http_json("GET", url, timeout=5.0)
            except (urllib.error.URLError, OSError, ValueError):
                time.sleep(1.0)
        raise SmokeFailure(
            f"{self.name} not healthy after {timeout:.0f}s: {self.log_tail()}"
        )

    def stop(self) -> None:
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGTERM)
            try:
                self.proc.wait(timeout=60)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait(timeout=30)
        self._log.close()


def _http_json(method: str, url: str, body=None, timeout: float = 60.0):
    data = None if body is None else json.dumps(body).encode()
    req = urllib.request.Request(
        url,
        data=data,
        method=method,
        headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return json.loads(resp.read().decode())


def _http_text(url: str, timeout: float = 30.0) -> str:
    with urllib.request.urlopen(url, timeout=timeout) as resp:
        return resp.read().decode()


def _sse(url: str, body: dict, timeout: float) -> tuple[list, bool]:
    """POST and read a server-sent-event stream: (JSON events, saw [DONE])."""
    req = urllib.request.Request(
        url,
        data=json.dumps(body).encode(),
        method="POST",
        headers={"Content-Type": "application/json"},
    )
    events, done = [], False
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        for raw in resp:
            line = raw.decode(errors="replace").strip()
            if not line.startswith("data:"):
                continue
            payload = line[5:].strip()
            if payload == "[DONE]":
                done = True
                break
            events.append(json.loads(payload))
    return events, done


def _upload(url: str, filename: str, text: str, timeout: float) -> dict:
    boundary = uuid.uuid4().hex
    body = (
        f"--{boundary}\r\nContent-Disposition: form-data; name=\"file\"; "
        f"filename=\"{filename}\"\r\nContent-Type: text/plain\r\n\r\n"
        f"{text}\r\n--{boundary}--\r\n"
    ).encode()
    req = urllib.request.Request(
        url,
        data=body,
        method="POST",
        headers={"Content-Type": f"multipart/form-data; boundary={boundary}"},
    )
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return json.loads(resp.read().decode() or "{}")


def _metric(metrics: str, name: str) -> float:
    for line in metrics.splitlines():
        if line.startswith(name + " "):
            return float(line.split()[1])
    raise SmokeFailure(f"engine /metrics has no {name}")


def _engine_counts(engine_url: str) -> tuple[int, int]:
    """(generation requests, tokens) the engine has served so far."""
    metrics = _http_text(engine_url + "/metrics")
    return (
        int(_metric(metrics, "engine_requests_total")),
        int(_metric(metrics, "engine_tokens_total")),
    )


def _words(seed: int, n_chars: int, salt: int) -> str:
    """Deterministic English-like filler of about ``n_chars`` bytes."""
    import random

    rng = random.Random(seed * 1000 + salt)
    vocab = (
        "the chip serves a model from memory while retrieval finds "
        "passages about tensor cores pages caches queues latency "
        "throughput batches tokens prompts answers documents vectors"
    ).split()
    out, size = [], 0
    while size < n_chars:
        w = rng.choice(vocab)
        out.append(w)
        size += len(w) + 1
    return " ".join(out)[:n_chars]


def _run_child(name: str, seed: int, sizes: Sizes, timeout: float) -> list:
    """Run ``chip_smoke.py --child <name>`` to its end; its JSON lines."""
    proc = subprocess.run(
        [
            sys.executable,
            os.path.abspath(__file__),
            "--child",
            name,
            "--seed",
            str(seed),
            "--sizes",
            json.dumps(dataclasses.asdict(sizes)),
        ],
        cwd=ROOT,
        env=_child_env(),
        capture_output=True,
        text=True,
        timeout=timeout,
    )
    lines = []
    for ln in proc.stdout.splitlines():
        if ln.startswith("{"):
            lines.append(json.loads(ln))
    if proc.returncode != 0:
        for line in lines:  # what it read before it failed
            emit(line)
        raise SmokeFailure(
            f"phase {name} exited with code {proc.returncode}: "
            f"{proc.stderr[-3000:]}"
        )
    return lines


def child_phase(
    name: str,
    seed: int,
    sizes: Sizes,
    expect: str,
    timeout: float,
    count: int | None = None,
) -> dict:
    """A phase that is one child: print its lines, hold each to the
    expected device (and device count); returns the device."""
    device = None
    for line in _run_child(name, seed, sizes, timeout):
        emit(line)
        device = line["device"]
        if device["platform"] != expect or count not in (None, device["count"]):
            raise SmokeFailure(
                f"{line['phase']} ran on {device}, not {count or 1} x {expect}"
            )
    if device is None:
        raise SmokeFailure(f"phase {name} printed nothing")
    return device


# --------------------------------------------------------------------------
# Phase: device.
# --------------------------------------------------------------------------


def phase_device(seed: int, sizes: Sizes, expect: str) -> dict:
    (line,) = _run_child("device", seed, sizes, timeout=300)
    device = line["device"]
    if device["platform"] != expect:
        raise SmokeFailure(
            f"no {expect} device: JAX found platform "
            f"{device['platform']!r} ({device['kind']} x{device['count']})"
        )
    emit(line)
    return device


def child_device(seed: int, sizes: Sizes) -> None:
    from generativeaiexamples_tpu.utils.jax_runtime import device_report

    emit({"phase": "device", "device": device_report()})


# --------------------------------------------------------------------------
# Phase: serve — engine server, then the chain server against it.
# --------------------------------------------------------------------------


def phase_serve(seed: int, sizes: Sizes, expect: str) -> None:
    logdir = tempfile.mkdtemp(prefix="chip-smoke-")
    port = _free_port()
    engine_url = f"http://127.0.0.1:{port}"
    t0 = time.monotonic()
    engine = Server(
        "engine",
        [
            "-m", f"{PACKAGE}.engine.server",
            "--host", "127.0.0.1", "--port", str(port),
            "--model", sizes.model,
            "--embedder", sizes.embedder,
            "--weight-dtype", "int8", "--kv-dtype", "int8",
            "--max-batch", str(sizes.max_batch),
            "--max-len", str(sizes.max_len),
            "--seed", str(seed),
        ],
        _child_env(),
        logdir,
    )
    chain = None
    try:
        health = engine.wait_healthy(
            engine_url + "/health", sizes.start_timeout_s
        )
        start_s = time.monotonic() - t0
        device = health["runtime"]["device"]
        if device["platform"] != expect:
            raise SmokeFailure(f"engine serves from {device}, not {expect}")
        tokens0 = _metric(
            _http_text(engine_url + "/metrics"), "engine_tokens_total"
        )

        # Concurrent streaming chat completions: long prompts (chunked
        # prefill) plus one short one (a cold prefill at s=256).
        prompts = [
            _words(seed, sizes.prompt_chars, i)
            for i in range(sizes.concurrent)
        ] + [_words(seed, sizes.short_prompt_chars, 99)]
        finishes: list = [None] * len(prompts)
        errors: list = []

        def chat(i: int) -> None:
            try:
                events, done = _sse(
                    engine_url + "/v1/chat/completions",
                    {
                        "model": sizes.model,
                        "messages": [{"role": "user", "content": prompts[i]}],
                        "stream": True,
                        "max_tokens": sizes.new_tokens,
                        "temperature": 0.2,
                    },
                    sizes.request_timeout_s,
                )
                reasons = [
                    c["finish_reason"]
                    for e in events
                    for c in e.get("choices", [])
                    if c.get("finish_reason")
                ]
                finishes[i] = (reasons[-1] if reasons else None, done)
            except Exception as e:  # noqa: BLE001 — reported below
                errors.append(f"request {i}: {type(e).__name__}: {e}")

        t_req = time.monotonic()
        threads = [
            threading.Thread(target=chat, args=(i,)) for i in range(len(prompts))
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(sizes.request_timeout_s + 30)
        chat_s = time.monotonic() - t_req
        if errors or any(t.is_alive() for t in threads):
            raise SmokeFailure(f"chat completions failed: {errors or 'hung'}")
        for i, fin in enumerate(finishes):
            if fin is None or fin[0] not in ("length", "stop") or not fin[1]:
                raise SmokeFailure(
                    f"chat completion {i} finished {fin}, not length/stop "
                    "then [DONE]"
                )

        texts = [
            _words(seed, 300, 200 + i) for i in range(sizes.embed_batch)
        ]
        emb = _http_json(
            "POST",
            engine_url + "/v1/embeddings",
            {"model": sizes.embedder, "input": texts},
            timeout=sizes.request_timeout_s,
        )
        vectors = [d["embedding"] for d in emb["data"]]
        if len(vectors) != len(texts) or any(
            len(v) != sizes.embed_dim or not all(x == x for x in v)
            for v in vectors
        ):
            raise SmokeFailure("embeddings: wrong shape or non-finite values")

        metrics = _http_text(engine_url + "/metrics")
        tokens = _metric(metrics, "engine_tokens_total") - tokens0
        if tokens < len(prompts) * sizes.new_tokens * 0.9:
            raise SmokeFailure(
                f"engine returned {tokens:.0f} tokens for "
                f"{len(prompts)} x {sizes.new_tokens} asked"
            )
        runtime = _http_json("GET", engine_url + "/health")["runtime"]
        paths = runtime["kernel_paths"]
        emit(
            {
                "phase": "serve.engine",
                "entry": f"python -m {PACKAGE}.engine.server",
                "model": sizes.model,
                "weights": "int8",
                "kv": "int8",
                "max_batch": sizes.max_batch,
                "max_len": sizes.max_len,
                "device": runtime["device"],
                "start_s": round(start_s, 1),
                "chat_requests": len(prompts),
                "chat_finish": [f[0] for f in finishes],
                "chat_s": round(chat_s, 1),
                "tokens_returned": int(tokens),
                "embeddings": [len(vectors), sizes.embed_dim],
                "compile": runtime["compile"],
                "kernel_paths": paths,
                "peak_bytes_in_use": runtime["peak_bytes_in_use"],
            }
        )

        if expect == "tpu":
            for site in ("decode_attention", "prefill_attention"):
                if "pallas" not in {
                    v for k, v in paths.items() if k.startswith(site)
                }:
                    raise SmokeFailure(
                        f"{site} never took its Pallas kernel: {paths}"
                    )

        # The RAG path: chain server on the CPU, engine on the chip —
        # the layout of deploy/compose/rag-app-base.yaml.
        cport = _free_port()
        chain_url = f"http://127.0.0.1:{cport}"
        chain = Server(
            "chain",
            ["-m", f"{PACKAGE}.server", "--host", "127.0.0.1",
             "--port", str(cport)],
            _child_env(
                JAX_PLATFORMS="cpu",
                APP_LLM_MODELENGINE="openai",
                APP_LLM_SERVERURL=engine_url,
                APP_LLM_MODELNAME=sizes.model,
                APP_EMBEDDINGS_MODELENGINE="openai",
                APP_EMBEDDINGS_SERVERURL=engine_url,
                APP_EMBEDDINGS_DIMENSIONS=str(sizes.embed_dim),
                APP_VECTORSTORE_NAME="tpu",
                # Two ~500-character chunks: with the byte tokenizer the
                # chain's prompt is then ~1,500 tokens and fits max_len
                # with room for the answer (four would be clipped).
                APP_RETRIEVER_TOPK="2",
                APP_RETRIEVER_SCORETHRESHOLD="0.0",
                # Uploads stay under this run's own directory (the
                # server's default is a fixed path outside the checkout).
                GAIE_UPLOAD_DIR=os.path.join(logdir, "uploads"),
            ),
            logdir,
        )
        chain.wait_healthy(chain_url + "/health", sizes.start_timeout_s)
        doc = "\n\n".join(_words(seed, 900, 300 + i) for i in range(4))
        _upload(
            chain_url + "/documents", "smoke.txt", doc,
            sizes.request_timeout_s,
        )
        question = _words(seed, 80, 300)
        found = _http_json(
            "POST",
            chain_url + "/search",
            {"query": question, "top_k": 4},
            timeout=sizes.request_timeout_s,
        )
        n_chunks = len(found.get("chunks", []))
        if n_chunks < 1:
            raise SmokeFailure(f"/search found no chunk: {found}")
        before = _engine_counts(engine_url)
        answer_tokens = min(sizes.new_tokens, 64)
        events, _ = _sse(
            chain_url + "/generate",
            {
                "messages": [{"role": "user", "content": question}],
                "use_knowledge_base": True,
                "max_tokens": answer_tokens,
            },
            sizes.request_timeout_s,
        )
        # The chain's sentinel is a last ChainResponse whose choice has
        # finish_reason "[DONE]"; the error idiom is the same chunk with
        # an empty id and the error as its content.
        degraded = sorted({d for e in events for d in e.get("degraded") or []})
        last = events[-1] if events else {}
        done = any(
            c.get("finish_reason") == "[DONE]" for c in last.get("choices", [])
        )
        if not done or not last.get("id") or degraded:
            raise SmokeFailure(
                f"/generate: [DONE]={done}, degraded={degraded}, last "
                f"chunk {last}: {chain.log_tail()}"
            )
        retrieved = chain.log_count(r"retrieved (\d+) chunks")
        if retrieved < 1:
            raise SmokeFailure("/generate answered without retrieved context")
        # The answer must be the engine's: random weights emit ids far
        # outside the byte tokenizer's 0..255, which detokenize to empty
        # text, so the stream may carry no content chunk — the engine's
        # own counters say whether it generated for the chain's request,
        # and all it was asked for (a prompt that overflows max_len is
        # clipped to its tail and leaves room for nine tokens).
        deadline = time.monotonic() + 10.0  # counters flush per tick
        while True:
            asked, generated = (
                now - was
                for now, was in zip(_engine_counts(engine_url), before)
            )
            if generated >= answer_tokens or time.monotonic() > deadline:
                break
            time.sleep(0.2)
        if asked != 1 or generated != answer_tokens:
            raise SmokeFailure(
                f"/generate reached [DONE] but the engine served {asked} "
                f"chat completion(s) and {generated} of {answer_tokens} "
                "token(s) for it"
            )
        if engine.log_has("scheduler tick failed"):
            raise SmokeFailure(
                f"engine logged a failed scheduler tick: {engine.log_tail(4000)}"
            )
        emit(
            {
                "phase": "serve.chain",
                "entry": f"python -m {PACKAGE}.server",
                "placement": "JAX_PLATFORMS=cpu (explicit); engine on the chip",
                "document_chars": len(doc),
                "search_chunks": n_chunks,
                "generate_context_chunks": retrieved,
                "generate_events": len(events),
                "generate_text_chars": sum(
                    len(c.get("message", {}).get("content") or "")
                    for e in events
                    for c in e.get("choices", [])
                ),
                "engine_requests_for_generate": asked,
                "engine_tokens_for_generate": generated,
                "generate_done": done,
                "degraded": degraded,
            }
        )
    finally:
        if chain is not None:
            chain.stop()
        engine.stop()
        shutil.rmtree(logdir, ignore_errors=True)


# --------------------------------------------------------------------------
# Phase: retrieval on the chip.
# --------------------------------------------------------------------------


def child_retrieval(seed: int, sizes: Sizes) -> None:
    import numpy as np

    from generativeaiexamples_tpu.core.configuration import get_config
    from generativeaiexamples_tpu.retrieval.base import Chunk
    from generativeaiexamples_tpu.retrieval.factory import get_vector_store
    from generativeaiexamples_tpu.utils.jax_runtime import (
        enable_compile_cache,
        runtime_report,
    )

    enable_compile_cache()
    dim, n, k = sizes.embed_dim, sizes.corpus_rows, sizes.top_k
    rng = np.random.default_rng(seed)
    corpus = rng.standard_normal((n, dim), dtype=np.float32)
    corpus /= np.linalg.norm(corpus, axis=1, keepdims=True)
    queries = rng.standard_normal((sizes.queries, dim), dtype=np.float32)
    queries /= np.linalg.norm(queries, axis=1, keepdims=True)

    cfg = get_config()
    store = get_vector_store(cfg, dimensions=dim, overrides={"backend": "tpu"})
    t0 = time.monotonic()
    store.add([Chunk(text=str(i), source="smoke") for i in range(n)], corpus)
    hits = store.search_batch(queries, k)  # first call: load + compile
    load_s = time.monotonic() - t0
    t0 = time.monotonic()
    hits = store.search_batch(queries, k)
    search_s = time.monotonic() - t0
    got = np.array([[int(h.chunk.text) for h in row] for row in hits])
    # The reference scores the rows the store holds: it keeps them (and
    # casts queries) in bfloat16 and accumulates in f32.
    import ml_dtypes

    def held(x):
        return x.astype(ml_dtypes.bfloat16).astype(np.float32)

    want = np.argsort(-(held(queries) @ held(corpus).T), axis=1)[:, :k]
    if got.shape != want.shape or not (got == want).all():
        raise SmokeFailure(
            f"tpu store ids differ from the numpy top-{k}: "
            f"{int((got != want).sum())} of {want.size}"
        )
    auto = get_vector_store(cfg, dimensions=dim, overrides={"backend": "auto"})
    report = runtime_report()
    on_tpu = report["device"]["platform"] == "tpu"
    if on_tpu and not type(auto).__name__.startswith("TPU"):
        raise SmokeFailure(
            f"vector_store.name=auto resolved to {type(auto).__name__} "
            "on a TPU host"
        )
    emit(
        {
            "phase": "retrieval",
            "store": type(store).__name__,
            "corpus": [n, dim],
            "queries": sizes.queries,
            "top_k": k,
            "ids_equal_numpy": True,
            "reference": "numpy f32 top-k over the bfloat16 rows the store holds",
            "auto_resolves_to": type(auto).__name__,
            "load_and_first_search_s": round(load_s, 2),
            "second_search_s": round(search_s, 4),
            "device": report["device"],
            "compile": report["compile"],
            "peak_bytes_in_use": report["peak_bytes_in_use"],
        }
    )


# --------------------------------------------------------------------------
# Phase: the two opt-in paths through the Scheduler.
# --------------------------------------------------------------------------


def _greedy(scheduler, prompts: list, max_tokens: int, timeout: float) -> list:
    """Greedy streams for ``prompts`` through a running scheduler/pool."""
    import queue

    from generativeaiexamples_tpu.engine.sampler import SamplingParams
    from generativeaiexamples_tpu.engine.scheduler import Request

    streams: list = [[] for _ in prompts]
    done: "queue.Queue[tuple]" = queue.Queue()
    for i, prompt in enumerate(prompts):
        ok = scheduler.submit(
            Request(
                token_ids=list(prompt),
                sampling=SamplingParams(temperature=0.0, max_tokens=max_tokens),
                on_token=streams[i].append,
                on_done=lambda reason, i=i: done.put((i, reason)),
            )
        )
        if not ok:
            raise SmokeFailure("scheduler refused a request")
    for _ in prompts:
        i, reason = done.get(timeout=timeout)
        if reason not in ("length", "stop"):
            raise SmokeFailure(f"request {i} finished with reason {reason!r}")
    return streams


def _optin_cfg(sizes: Sizes):
    import dataclasses as dc

    from generativeaiexamples_tpu.models import llama

    return dc.replace(
        llama.PRESETS[sizes.optin_model](),
        n_layers=sizes.optin_layers,
        kv_dtype="int8",
        max_seq_len=256,
    )


def _prompts(seed: int, vocab: int, lengths: list) -> list:
    import numpy as np

    rng = np.random.default_rng(seed + 7)
    return [rng.integers(0, vocab, (n,)).tolist() for n in lengths]


def _taken(prefix: str) -> dict:
    from generativeaiexamples_tpu.ops.dispatch import TAKEN

    return {k: v for k, v in sorted(TAKEN.items()) if k.startswith(prefix)}


def _require_pallas(what: str, paths: dict, on_tpu: bool) -> None:
    if on_tpu and (not paths or set(paths.values()) != {"pallas"}):
        raise SmokeFailure(
            f"{what} was asked for and is not the path that ran: {paths}"
        )


def child_optin(seed: int, sizes: Sizes) -> None:
    import jax

    from generativeaiexamples_tpu.engine.decode import (
        init_random_int8_params,
        prepare_params,
    )
    from generativeaiexamples_tpu.engine.scheduler import Scheduler
    from generativeaiexamples_tpu.ops.dispatch import TAKEN
    from generativeaiexamples_tpu.utils.jax_runtime import (
        enable_compile_cache,
        runtime_report,
    )

    enable_compile_cache()
    cfg = _optin_cfg(sizes)
    on_tpu = jax.default_backend() == "tpu"
    raw = init_random_int8_params(cfg, jax.random.PRNGKey(seed))
    packed = prepare_params(cfg, raw, None, pack=True)
    short = _prompts(seed, cfg.vocab_size, [40, 33, 24, 17])
    new_tokens = 16
    kw = dict(max_batch=16, max_len=256, decode_chunk_size=8, seed=seed)
    note = (
        f"{sizes.optin_model} widths, depth cut to {cfg.n_layers} layers "
        "to save compile time"
    )

    def run(params, sets, **extra) -> list:
        """One scheduler, one greedy pass per prompt set, in order."""
        sched = Scheduler(cfg, params, **kw, **extra)
        sched.start()
        try:
            return [_greedy(sched, ps, new_tokens, 600.0) for ps in sets]
        finally:
            sched.stop()

    # W8A8: the kernel against its XLA twin on the same blocked params
    # (the twin is what GAIE_DISABLE_QMM_KERNEL selects at trace time).
    t0 = time.monotonic()
    os.environ["GAIE_DISABLE_QMM_KERNEL"] = "1"
    try:
        (twin,) = run(packed, (short,), matmul_kernel="pallas_w8a8")
    finally:
        del os.environ["GAIE_DISABLE_QMM_KERNEL"]
    TAKEN.clear()
    (fused,) = run(packed, (short,), matmul_kernel="pallas_w8a8")
    decode_paths = {
        k: v for k, v in _taken("q_matmul").items() if k.endswith("m=32")
    }
    if fused != twin:
        raise SmokeFailure(
            f"matmul_kernel=pallas_w8a8 stream differs from its XLA "
            f"twin: {fused} vs {twin}"
        )
    _require_pallas("the W8A8 kernel", decode_paths, on_tpu)
    report = runtime_report()
    emit(
        {
            "phase": "optin.w8a8",
            "config": note,
            "streams": len(short),
            "tokens_each": new_tokens,
            "matches_xla_twin": True,
            "kernel_paths": _taken("q_matmul"),
            "seconds": round(time.monotonic() - t0, 1),
            "device": report["device"],
            "compile": report["compile"],
            "peak_bytes_in_use": report["peak_bytes_in_use"],
        }
    )


# --------------------------------------------------------------------------
# --four-chips: the tensor-parallel mesh and the replica pool.
# --------------------------------------------------------------------------


def _shard_summary(tree) -> dict:
    """Per leaf: global shape, one device's shard shape, devices."""
    import jax

    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        out[jax.tree_util.keystr(path)] = {
            "shape": list(leaf.shape),
            "shard": list(leaf.addressable_shards[0].data.shape),
            "devices": len(leaf.sharding.device_set),
        }
    return out


def child_four(seed: int, sizes: Sizes) -> None:
    import dataclasses as dc

    import jax
    import jax.numpy as jnp
    import numpy as np

    from generativeaiexamples_tpu.engine.replica import EnginePool
    from generativeaiexamples_tpu.engine.scheduler import Scheduler
    from generativeaiexamples_tpu.models import llama
    from generativeaiexamples_tpu.ops.dispatch import TAKEN
    from generativeaiexamples_tpu.parallel.mesh import (
        MeshSpec,
        make_mesh,
        replica_device_slices,
    )
    from generativeaiexamples_tpu.utils.jax_runtime import (
        enable_compile_cache,
        runtime_report,
    )

    enable_compile_cache()
    devices = jax.devices()
    if len(devices) != 4:
        raise SmokeFailure(f"--four-chips needs 4 devices, JAX has {len(devices)}")
    on_tpu = devices[0].platform == "tpu"
    prompts_len = [40, 33, 24, 17]
    new_tokens = 16
    kw = dict(max_batch=16, max_len=256, decode_chunk_size=8, seed=seed)

    # (a) Tensor parallel: bf16, depth cut so the unsharded copy fits one
    # chip beside its sharded twin.
    cfg = dc.replace(
        llama.PRESETS[sizes.optin_model](),
        n_layers=sizes.optin_layers,
        n_kv_heads=sizes.kv_heads,
        max_seq_len=256,
    )
    prompts = _prompts(seed, cfg.vocab_size, prompts_len)
    params = llama.init_params(cfg, jax.random.PRNGKey(seed))
    t0 = time.monotonic()
    mesh = make_mesh(MeshSpec(data=1, tensor=4))
    single = Scheduler(cfg, params, **kw)
    tp = Scheduler(cfg, params, mesh=mesh, **kw)
    shards = _shard_summary(
        {"params": tp.params["layers"], "kv": tp._cache}
    )
    whole = [k for k, v in shards.items() if v["devices"] != 4]
    split = {
        k: v for k, v in shards.items() if v["shard"] != v["shape"]
    }
    for name in ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down"):
        if not any(name in k for k in split):
            raise SmokeFailure(f"{name} is not split across the mesh: {shards}")
    if whole or not all("kv" not in k or k in split for k in shards):
        raise SmokeFailure(f"leaves not on all four chips or KV whole: {shards}")
    streams = {}
    for label, sched in (("single", single), ("tp4", tp)):
        sched.start()
        try:
            streams[label] = _greedy(sched, prompts, new_tokens, 900.0)
        finally:
            sched.stop()

    # Logits of one forward pass, sharded against whole: the stated
    # tolerance for bf16 sums taken in a different order.
    toks = jnp.asarray(np.array([prompts[0][:16]], np.int32))
    pos = jnp.arange(16, dtype=jnp.int32)[None]

    def logits(p, m):
        hidden, _ = llama.forward(p, cfg, toks, pos, mesh=m)
        return llama.logits(p, hidden).astype(jnp.float32)

    lg_one = np.asarray(jax.jit(lambda p: logits(p, None))(single.params))
    lg_tp = np.asarray(jax.jit(lambda p: logits(p, mesh))(tp.params))
    err = float(np.abs(lg_one - lg_tp).max())
    scale = float(np.abs(lg_one).max())
    tol = 0.05
    same_tokens = streams["single"] == streams["tp4"]
    if not same_tokens and err > tol * scale:
        raise SmokeFailure(
            f"tensor=4 differs from one device: tokens differ and max "
            f"|dlogit| {err:.4f} > {tol} x {scale:.4f}"
        )
    report = runtime_report()
    emit(
        {
            "phase": "four.tensor_parallel",
            "config": f"{sizes.optin_model} widths, bf16, "
            f"{cfg.n_layers} layers, MeshSpec(tensor=4)",
            "same_greedy_tokens": same_tokens,
            "first_tokens_equal": [
                a[0] == b[0]
                for a, b in zip(streams["single"], streams["tp4"])
            ],
            "logits_max_abs_diff": round(err, 5),
            "logits_max_abs": round(scale, 4),
            "tolerance": f"{tol} x max|logit|",
            "sharded_leaves": {
                k: f"{v['shape']} -> {v['shard']} x{v['devices']}"
                for k, v in split.items()
            },
            "seconds": round(time.monotonic() - t0, 1),
            "device": report["device"],
        }
    )
    del single, tp, params

    # (b) Four one-device replicas behind the router, int8 + both opt-in
    # kernels' host path (W8A8) and the default decode kernel.
    t0 = time.monotonic()
    cfg8 = dc.replace(cfg, kv_dtype="int8")
    rkw = dict(kw, quantize=True, matmul_kernel="pallas_w8a8")
    # Each replica first answers ``probe`` alone (so the paths it takes
    # can be read off), then the router spreads ``prompts``: no replica
    # sees a prompt twice, which would be served from its prefix cache
    # (int8 KV read back, not the cold path's numerics).
    (probe,) = _prompts(seed + 1, cfg.vocab_size, [29])
    reference_sched = Scheduler(cfg8, None, **rkw)
    reference_sched.start()
    try:
        reference = _greedy(reference_sched, prompts, new_tokens, 900.0)
        (probe_ref,) = _greedy(reference_sched, [probe], new_tokens, 900.0)
    finally:
        reference_sched.stop()
    del reference_sched
    meshes = [
        make_mesh(MeshSpec(data=1, tensor=1), devices=sl)
        for sl in replica_device_slices(4)
    ]
    replicas = [Scheduler(cfg8, None, mesh=m, **rkw) for m in meshes]
    placement = []
    for i, (rep, m) in enumerate(zip(replicas, meshes)):
        want = set(m.devices.flat)
        leaves = jax.tree.leaves((rep.params, rep._cache))
        if any(leaf.devices() != want for leaf in leaves):
            raise SmokeFailure(
                f"replica {i}: params or cache not on its own device {want}"
            )
        placement.append(str(next(iter(want))))
    pool = EnginePool(replicas, policy="round_robin")
    pool.start()
    try:
        per_replica = []
        for i, rep in enumerate(replicas):
            TAKEN.clear()
            (got,) = _greedy(rep, [probe], new_tokens, 900.0)
            paths = _taken("")
            if got != probe_ref:
                raise SmokeFailure(
                    f"replica {i} stream differs from the single "
                    f"scheduler: {got} vs {probe_ref}"
                )
            decode = {
                k: v for k, v in paths.items()
                if k.startswith("decode_attention")
                or (k.startswith("q_matmul") and k.endswith("m=32"))
            }
            _require_pallas(f"replica {i}'s kernels", decode, on_tpu)
            per_replica.append(decode)
        routed = _greedy(pool, prompts, new_tokens, 900.0)
        if routed != reference:
            raise SmokeFailure(
                f"routed streams differ from the single scheduler: "
                f"{routed} vs {reference}"
            )
        served = [
            int(s.get("requests_total", 0))
            for s in (r.stats.snapshot() for r in replicas)
        ]
    finally:
        pool.stop()
    report = runtime_report()
    emit(
        {
            "phase": "four.replicas",
            "config": f"{sizes.optin_model} widths, int8 weights + int8 KV, "
            f"{cfg.n_layers} layers, 4 one-device replicas, round_robin",
            "replica_devices": placement,
            "replica_requests": served,
            "same_greedy_tokens_as_single": True,
            "kernel_paths_per_replica": per_replica,
            "seconds": round(time.monotonic() - t0, 1),
            "device": report["device"],
        }
    )


# --------------------------------------------------------------------------
# Entry.
# --------------------------------------------------------------------------

# Limits of the hybrid phase's comparison: quantiles over positions of
# each position's error as a share of its reference logits' root mean
# square.  PERF.md section 6 (PR 27 for ling, PR 31 for mellum) has the
# readings they lie between: the served precision (bf16 weights and
# activations, float32 recurrent state) below them, the controls above
# at least one.  The ninth tenth reads expert flips in sound runs and in
# the precision control alike; its limit is there for a fault in some of
# the positions, such as state lost between chunks or steps.
HYBRID_QUANTILE_LIMITS = {
    "ling": {"p10": 0.025, "p50": 0.1, "p90": 0.4},
    "mellum": {"p10": 0.0175, "p50": 0.06, "p90": 0.15},
}
HYBRID_CONTROLS = {
    "ling": ("w8a8_mlp",),
    "mellum": ("w8a8_mlp", "no_window", "no_yarn"),
    "exaone_moe": ("w8a8_mlp", "no_qk_norm", "rope_on_full", "no_window", "stale_reject"),
    "mistral4": ("w8a8_mlp", "no_attn_scale", "plain_rope", "no_mscale", "no_q_norm"),
    "zaya": (
        "w8a8", "no_value_shift", "no_qk_mean", "no_conv", "no_router_average",
        "renormed_top1",
    ),
    "nemotron_h": (
        "w8a8_mlp", "state_bf16", "no_conv", "no_d_skip", "norm_whole", "gate_after_norm",
        "relu_not_relu2", "no_routed_scale", "rope_on",
    ),
    # ``window_512`` (one row of the window layers' 513 fewer) is no control
    # on the chip: at seeded weights it reads 0.0092 / 0.0097 / 0.0106 /
    # 0.0095 beside a sound 0.0090 / 0.0094 / 0.0102 / 0.0092 (my chip call
    # 2, PR 47), so tests/test_dots3_note_model.py holds it in float32.
    "dots3_note": (
        "w8a8_mlp", "no_selection", "last_2048", "no_index_relu", "no_rescale", "no_gate",
        "full_sizes_in_window",
    ),
    "deepseek_v32": (
        "w8a8_mlp", "no_selection", "last_2048", "no_index_relu", "stale_reject", "no_groups",
        "no_mscale", "draft_shares_set",
    ),
    "longcat_flash": (
        "w8a8_mlp", "no_latent_rescale", "no_zero_identity", "shortcut_early", "renormed_weights",
    ),
}
# ``--model exaone_moe`` is held to the limits of its benchmark
# configuration (``reference.logit_share_limits``; PERF.md section 6,
# PR 33, has the readings they lie between), by the comparison that
# decides its cell's ``correct`` (``benchmarks/arch/exaone_moe.py``).
EXAONE_CONFIG = "benchmarks/configs/k-exaone-236b-a23b-l5e16.json"
# ``--model mistral4`` likewise (``benchmarks/arch/mistral4.py``; PERF.md
# section 6, PR 38).
MISTRAL4_CONFIG = "benchmarks/configs/mistral-small-4-119b-l6e32.json"
# ``--model zaya`` likewise (``benchmarks/arch/zaya.py``; PERF.md section
# 6, PR 40).
ZAYA_CONFIG = "benchmarks/configs/zaya1-8b-l20.json"
# ``--model nemotron_h`` likewise (``benchmarks/arch/nemotron_h.py``;
# PERF.md section 6, PR 44).
NEMOTRON_CONFIG = "benchmarks/configs/nemotron-3-super-120b-a12b-l11e128.json"
# ``--model dots3_note`` likewise (``benchmarks/arch/dots3_note.py``;
# PERF.md section 6, PR 47).
DOTS3_CONFIG = "benchmarks/configs/dots3-note-prev-l6e32.json"
# ``--model deepseek_v32`` likewise (``benchmarks/arch/deepseek_v32.py``;
# PERF.md section 6, PR 53).
DEEPSEEK_CONFIG = "benchmarks/configs/deepseek-v3.2-l5e16.json"
# ``--model longcat_flash`` likewise (``benchmarks/arch/longcat_flash.py``;
# PERF.md section 6, PR 57).
LONGCAT_CONFIG = "benchmarks/configs/longcat-flash-chat-l4e16.json"


def hybrid_limits(model: str) -> dict:
    return {
        f"{part}_{q}_share": limit
        for part in ("prefill", "decode") for q, limit in HYBRID_QUANTILE_LIMITS[model].items()
    }


def _int8(a, axis):
    """``a`` rounded to int8's 255 levels, one scale along ``axis``."""
    import jax.numpy as jnp

    scale = jnp.max(jnp.abs(a), axis=axis, keepdims=True) / 127.0 + 1e-30
    return jnp.round(a / scale) * scale


def _w8a8_project(h, w):
    """A projection as a W8A8 matmul would compute it: int8 weights, one
    scale an output channel, and int8 activations, one scale a token."""
    import jax.numpy as jnp

    return _int8(h, -1) @ _int8(w.astype(jnp.float32), 0)


def _w8a8_swiglu():
    """The nearest precision below the configurations': every MLP product
    (dense, shared and routed experts) as a W8A8 matmul would compute it:
    int8 weights, one scale an output channel, and int8 activations, one
    scale a token (what ``benchmarks/run.py --control`` serves the
    llama-shaped models through)."""
    import jax

    def w8a8_swiglu(h, w_gu, w_down):
        gu = _w8a8_project(h, w_gu)
        half = gu.shape[-1] // 2
        return _w8a8_project(jax.nn.silu(gu[:, :half]) * gu[:, half:], w_down)

    return w8a8_swiglu


def _bench_arch(name: str):
    """``benchmarks/arch/<name>.py`` as the harness loads it, with
    ``benchmarks/`` on the path while it imports its reference."""
    import importlib.util

    bench = os.path.join(ROOT, "benchmarks")
    sys.path.insert(0, bench)
    try:
        spec = importlib.util.spec_from_file_location(
            f"arch_{name}", os.path.join(bench, "arch", f"{name}.py"))
        arch = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(arch)
    finally:
        sys.path.remove(bench)
    return arch


def child_exaone(seed: int, sizes: Sizes, control: str = "") -> None:
    """``--hybrid --model exaone_moe``: the serving model's chunked
    prefill and its verify step (on true drafts, on wrong drafts, and the
    prediction module's logits) against the float32 reference, by the
    benchmark's own comparison.  A control changes what the program
    computes (``no_qk_norm``, ``rope_on_full``: the full layers rotated
    like the window ones, ``no_window``), how the check steps after a
    rejected draft (``stale_reject``: its position counted as written) or
    the reference's precision (``w8a8_mlp``); each has to leave a limit."""
    import jax
    import numpy as np

    from generativeaiexamples_tpu.engine.serving_models import serving_model
    from generativeaiexamples_tpu.models import hybrid
    from generativeaiexamples_tpu.utils.jax_runtime import (
        device_report,
        enable_compile_cache,
        runtime_report,
    )

    enable_compile_cache()
    t0 = time.monotonic()
    arch = _bench_arch("exaone_moe")
    with open(os.path.join(ROOT, EXAONE_CONFIG)) as f:
        limits = json.load(f)["reference"]["logit_share_limits"]
    arch._CHECK.update(limits=limits, verify=sizes.exaone_verify, chunk=sizes.exaone_chunk)
    cfg = hybrid.PRESETS[sizes.exaone_model]()  # what the reference computes
    served = {
        "no_qk_norm": dataclasses.replace(cfg, qk_norm=False),
        "rope_on_full": dataclasses.replace(cfg, rope_full=cfg.rope_window),
        "no_window": dataclasses.replace(cfg, sliding_window=sizes.max_len),
    }.get(control, cfg)
    pad_to = sizes.exaone_prompt
    params = serving_model(cfg, None, pad_to).prepare_params(
        None, quantize=False, matmul_kernel="xla", seed=seed)
    tokens = np.random.RandomState(seed).randint(1, cfg.vocab_size, size=pad_to).astype(np.int32)
    plain = arch.exaone_moe_reference._swiglu
    if control == "w8a8_mlp":
        arch.exaone_moe_reference._swiglu = _w8a8_swiglu()
        jax.clear_caches()  # a layer traced before this would keep the plain one
    try:
        shares, _ = arch.logit_shares(
            params, cfg, tokens, pad_to, served=served, stale_reject=control == "stale_reject")
    finally:
        if control == "w8a8_mlp":  # a caller in this process gets the plain one back
            arch.exaone_moe_reference._swiglu = plain
            jax.clear_caches()
    readings = arch.share_quantiles(shares)
    failed = {k: v for k, v in readings.items() if not v <= limits[k]}
    report = runtime_report()
    emit(
        {
            "phase": "hybrid", "model": sizes.exaone_model, "control": control or None,
            "positions": {k: int(len(v)) for k, v in shares.items()},
            **readings, "limits": limits, "within_limits": not failed,
            "seconds": time.monotonic() - t0,
            "compile": report["compile"], "peak_bytes_in_use": report["peak_bytes_in_use"],
            "kernel_paths": {**_taken("moe_experts"), **_taken("attn_"), **_taken("mtp_")},
            "device": device_report(),
        }
    )
    if control and not failed:
        raise SmokeFailure(f"the control {control!r} stayed inside every limit: {readings}")
    if not control and failed:
        raise SmokeFailure(f"logits left the reference: {failed} (limits {limits})")


def child_mistral4(seed: int, sizes: Sizes, control: str = "") -> None:
    """``--hybrid --model mistral4``: the serving model's chunk program
    (``prefill_rows`` over the whole slot: attention in blocks over the
    latent rows, read and written in place in a state of 32,768 rows a
    slot) and its decode step over that state (the absorbed form, a
    row's blocks up to its length) against the float32 reference, by the
    benchmark's own comparison.  A control changes what the program computes
    (``no_attn_scale``: the query's position scale left out,
    ``plain_rope``: the plain frequencies for YaRN's, ``no_mscale``: the
    softmax scale without ``m^2``) or what the reference computes
    (``no_q_norm``: the query's latent not normed, ``w8a8_mlp``: its MLP
    products in the nearest precision below); each has to leave a limit."""
    import jax
    import numpy as np

    from generativeaiexamples_tpu.engine.serving_models import serving_model
    from generativeaiexamples_tpu.models import hybrid
    from generativeaiexamples_tpu.ops.rope import RopeSpec
    from generativeaiexamples_tpu.utils.jax_runtime import (
        device_report,
        enable_compile_cache,
        runtime_report,
    )

    enable_compile_cache()
    t0 = time.monotonic()
    arch = _bench_arch("mistral4")
    with open(os.path.join(ROOT, MISTRAL4_CONFIG)) as f:
        limits = json.load(f)["reference"]["logit_share_limits"]
    arch._CHECK.update(limits=limits, decode=sizes.mistral4_decode, chunk=sizes.mistral4_chunk)
    cfg = hybrid.PRESETS[sizes.mistral4_model]()  # what the reference computes
    spec = cfg.rope_latent
    served = {
        "no_attn_scale": dataclasses.replace(cfg, attn_scale_beta=0.0),
        "plain_rope": dataclasses.replace(
            cfg, rope_latent=RopeSpec(theta=spec.theta, original_max=spec.original_max)),
        "no_mscale": dataclasses.replace(cfg, softmax_mscale=1.0),
    }.get(control, cfg)
    pad_to = sizes.mistral4_prompt
    params = serving_model(cfg, None, pad_to).prepare_params(
        None, quantize=False, matmul_kernel="xla", seed=seed)
    tokens = np.random.RandomState(seed).randint(1, cfg.vocab_size, size=pad_to).astype(np.int32)
    reference = arch.mistral4_reference
    patched = {
        "w8a8_mlp": ("_swiglu", _w8a8_swiglu()),
        "no_q_norm": ("_q_norm", lambda x, gain, eps: x * gain.astype(x.dtype)),
    }.get(control)
    if patched:
        plain = getattr(reference, patched[0])
        setattr(reference, *patched)
        jax.clear_caches()  # a layer traced before this would keep the plain one
    try:
        share, _ = arch.logit_shares(params, cfg, tokens, pad_to, served=served)
    finally:
        if patched:  # a caller in this process gets the plain one back
            setattr(reference, patched[0], plain)
            jax.clear_caches()
    readings = arch.share_quantiles(share, sizes.mistral4_decode)
    failed = {k: v for k, v in readings.items() if not v <= limits[k]}
    report = runtime_report()
    emit(
        {
            "phase": "hybrid", "model": sizes.mistral4_model, "control": control or None,
            "positions": {"prefill": int(len(share)) - sizes.mistral4_decode,
                          "decode": sizes.mistral4_decode},
            **readings, "limits": limits, "within_limits": not failed,
            "seconds": time.monotonic() - t0,
            "compile": report["compile"], "peak_bytes_in_use": report["peak_bytes_in_use"],
            "kernel_paths": {**_taken("moe_experts"), **_taken("attn_latent")},
            "device": device_report(),
        }
    )
    if control and not failed:
        raise SmokeFailure(f"the control {control!r} stayed inside every limit: {readings}")
    if not control and failed:
        raise SmokeFailure(f"logits left the reference: {failed} (limits {limits})")


@contextlib.contextmanager
def _reference_patched(reference, patched: dict):
    """``reference`` with the functions ``patched`` names replaced (a
    control of a comparison); a caller in this process gets the plain ones
    back.  A layer traced before or under the patch would keep what it was
    traced with: the caches are cleared on both sides."""
    import jax

    plain = {name: getattr(reference, name) for name in patched}
    for name, stand_in in patched.items():
        setattr(reference, name, stand_in)
    if patched:
        jax.clear_caches()
    try:
        yield
    finally:
        for name, fn in plain.items():
            setattr(reference, name, fn)
        if patched:
            jax.clear_caches()


def _child_by_benchmark(
    seed: int, control: str, *, family: str, config: str, preset: str, prompt: int,
    chunk: int, decode: int, patches: dict, sites: tuple,
) -> None:
    """A family whose comparison is its benchmark's own
    (``benchmarks/arch/<family>.py::logit_shares``): the serving model's
    chunk program (``prefill_rows``, the chunk beside a pad row on a state
    of ``max_len`` rows a slot) and its decode step over that state against
    the float32 reference, held to the limits of ``config``.  A control
    changes what the reference computes (``patches``: the reference module
    -> control -> the functions of that module it replaces); each has to
    leave a limit."""
    import numpy as np

    from generativeaiexamples_tpu.engine.serving_models import serving_model
    from generativeaiexamples_tpu.models import hybrid
    from generativeaiexamples_tpu.utils.jax_runtime import (
        device_report,
        enable_compile_cache,
        runtime_report,
    )

    enable_compile_cache()
    t0 = time.monotonic()
    arch = _bench_arch(family)
    with open(os.path.join(ROOT, config)) as f:
        reference_block = json.load(f)["reference"]
    limits = reference_block["logit_share_limits"]
    arch._CHECK.update(limits=limits, decode=decode, chunk=chunk)
    cfg = hybrid.PRESETS[preset]()
    params = serving_model(cfg, None, prompt).prepare_params(
        None, quantize=False, matmul_kernel="xla", seed=seed)
    tokens = np.random.RandomState(seed).randint(1, cfg.vocab_size, size=prompt).astype(np.int32)
    reference = getattr(arch, f"{family}_reference")
    with _reference_patched(reference, patches(reference).get(control, {})):
        share, _, *more = arch.logit_shares(params, cfg, tokens, prompt)
    readings = arch.share_quantiles(share, decode)
    failed = {k: v for k, v in readings.items() if not v <= limits[k]}
    if more:  # a family whose comparison reads its indexer's selected sets too
        readings["index_overlap"] = more[0]
        limits = {**limits, "index_overlap_floor": reference_block["index_overlap_floor"]}
        if not more[0] >= limits["index_overlap_floor"]:
            failed["index_overlap"] = more[0]
    report = runtime_report()
    emit(
        {
            "phase": "hybrid", "model": preset, "control": control or None,
            "positions": {"prefill": int(len(share)) - decode, "decode": decode},
            **readings, "limits": limits, "within_limits": not failed,
            "seconds": time.monotonic() - t0,
            "compile": report["compile"], "peak_bytes_in_use": report["peak_bytes_in_use"],
            "kernel_paths": {k: v for site in sites for k, v in _taken(site).items()},
            "device": device_report(),
        }
    )
    if control and not failed:
        raise SmokeFailure(f"the control {control!r} stayed inside every limit: {readings}")
    if not control and failed:
        raise SmokeFailure(f"logits left the reference: {failed} (limits {limits})")


def child_zaya(seed: int, sizes: Sizes, control: str = "") -> None:
    """``--hybrid --model zaya``: a control changes what the reference
    computes, one mechanism at a time: the values not shifted, no q-k mean,
    no convolution (``z = u``), the router without the previous layer's
    state, the chosen expert weighted 1, every projection of a layer in the
    nearest precision below."""
    import jax.numpy as jnp

    patches = lambda ref: {
        # The nearest precision below in EVERY projection of a layer (the
        # mixer's two and the expert's three); the experts' alone reads
        # inside the sound runs' spread here (``w8a8_mlp``: kept for the
        # reading, no control of the comparison).
        "w8a8": {"_swiglu": _w8a8_swiglu(), "_project": _w8a8_project},
        "w8a8_mlp": {"_swiglu": _w8a8_swiglu()},
        "no_value_shift": {"_shift_values": lambda now, late: jnp.concatenate([now, late], axis=-1)},
        "no_qk_mean": {"_qk_mean": lambda qp, kp: (jnp.zeros_like(qp), jnp.zeros_like(kp))},
        "no_conv": {"_conv": lambda u, lp, dims: u},
        "no_router_average": {"_router_average": lambda rho, prev, gamma: rho},
        "renormed_top1": {"_top1_weight": lambda p, chosen: chosen.astype(p.dtype)},
    }
    _child_by_benchmark(
        seed, control, family="zaya", config=ZAYA_CONFIG, preset=sizes.zaya_model,
        prompt=sizes.zaya_prompt, chunk=sizes.zaya_chunk, decode=sizes.zaya_decode,
        patches=patches, sites=("moe_experts", "attn_cca"),
    )


def child_longcat_flash(seed: int, sizes: Sizes, control: str = "") -> None:
    """``--hybrid --model longcat_flash``: a control changes what the
    reference computes, one step at a time: its MLP products (dense and
    experts) in the nearest precision below, the normed latents not
    rescaled, the identity experts' term left out, the experts' sum added
    where a plain expert layer adds it (before the second sublayer), the
    chosen outputs' weights renormalised to sum to one."""
    import jax.numpy as jnp

    def early(ref):
        def layer(x, first, second, dims_t):
            eps = dict(dims_t)["eps"]
            x = ref._attend(x, first, dims_t)
            x = ref._dense(x, first, eps) + ref._experts(x, first, dims_t)
            return ref._dense(ref._attend(x, second, dims_t), second, eps)

        return layer

    def renormed(g, chosen, dims):
        w = jnp.where(chosen, g, 0.0)
        return w / w.sum(-1, keepdims=True) * dims["scale"]

    patches = lambda ref: {
        "w8a8_mlp": {"_swiglu": _w8a8_swiglu()},
        "no_latent_rescale": {"_rescale": lambda c, d_model, rank: c},
        "no_zero_identity": {"_identity": lambda u, w_zero: jnp.zeros_like(u)},
        "shortcut_early": {"layer": early(ref)},
        "renormed_weights": {"_weights": renormed},
    }
    _child_by_benchmark(
        seed, control, family="longcat_flash", config=LONGCAT_CONFIG, preset=sizes.longcat_model,
        prompt=sizes.longcat_prompt, chunk=sizes.longcat_chunk, decode=sizes.longcat_decode,
        patches=patches, sites=("moe_experts", "attn_latent"),
    )


def child_nemotron_h(seed: int, sizes: Sizes, control: str = "") -> None:
    """``--hybrid --model nemotron_h``: a control changes what the
    reference computes: the experts' products in the nearest precision
    below (``w8a8_mlp``), the state-space state kept in bfloat16, no
    convolution, no ``D x``, the gated norm over all channels at once or
    with the gate after it, ``relu`` for ``relu2``, the routed weights not
    scaled, the attention layers rotated with the config's unused
    ``rope_theta``."""
    import jax
    import jax.numpy as jnp

    def gate_after_norm(y, z, gain, groups, eps):
        g = y.reshape(y.shape[0], groups, -1)
        g = g * jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True) + eps)
        return g.reshape(y.shape) * gain.astype(jnp.float32) * jax.nn.silu(z)

    def rope_on(q, k, theta=10000.0):
        d = q.shape[-1]
        inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
        ang = jnp.arange(q.shape[0], dtype=jnp.float32)[:, None, None] * inv
        cos, sin = jnp.cos(ang), jnp.sin(ang)
        turn = lambda x: jnp.concatenate(
            [x[..., : d // 2] * cos - x[..., d // 2 :] * sin,
             x[..., d // 2 :] * cos + x[..., : d // 2] * sin], -1)
        return turn(q), turn(k)

    def patches(ref):
        plain_norm = ref._gated_norm
        return {
            "w8a8_mlp": {"_mlp": lambda h, w_up, w_down: _w8a8_project(
                ref._act(_w8a8_project(h, w_up)), w_down)},
            # bfloat16's 8 bits of exponent and 7 of mantissa; a pair of casts
            # is folded away on the chip (XLA allows excess precision).
            "state_bf16": {"_keep": lambda state: jax.lax.reduce_precision(
                state, exponent_bits=8, mantissa_bits=7)},
            "no_conv": {"_conv": lambda u, lp: u},
            "no_d_skip": {"_skip": lambda y, d, xs: y},
            "norm_whole": {"_gated_norm": lambda y, z, gain, groups, eps: plain_norm(y, z, gain, 1, eps)},
            "gate_after_norm": {"_gated_norm": gate_after_norm},
            "relu_not_relu2": {"_act": jax.nn.relu},
            "no_routed_scale": {"_routed_weights": lambda s, scale: s / (s.sum(-1, keepdims=True) + 1e-20)},
            "rope_on": {"_rotate": rope_on},
        }

    _child_by_benchmark(
        seed, control, family="nemotron_h", config=NEMOTRON_CONFIG, preset=sizes.nemotron_model,
        prompt=sizes.nemotron_prompt, chunk=sizes.nemotron_chunk, decode=sizes.nemotron_decode,
        patches=patches, sites=("moe_experts", "attn_full", "ssm_"),
    )


def child_dots3_note(seed: int, sizes: Sizes, control: str = "") -> None:
    """``--hybrid --model dots3_note``: a control changes what the
    reference computes: every row a query sees attended (``no_selection``),
    the newest ``index_topk`` rows for the highest scores (``last_2048``),
    the index scores without their ``relu``, the normed latents not
    rescaled, no gate on the heads' outputs, a sliding layer rotated with
    the full layers' theta, the MLP products in the nearest precision
    below."""
    patches = lambda ref: {
        "w8a8_mlp": {"_swiglu": _w8a8_swiglu()},
        "no_selection": {"_select": lambda scores, seen, topk: seen},
        "last_2048": {"_select": _newest_rows},
        "no_index_relu": {"_index_act": lambda dots: dots},
        "no_rescale": {"_rescale": lambda c, d_model, rank: c},
        "no_gate": {"_gate": lambda o, h, w_gate: o},
        "full_sizes_in_window": {"_window_theta": lambda dims: dims["theta"]},
    }
    _child_by_benchmark(
        seed, control, family="dots3_note", config=DOTS3_CONFIG, preset=sizes.dots3_model,
        prompt=sizes.dots3_prompt, chunk=sizes.dots3_chunk, decode=sizes.dots3_decode,
        patches=patches, sites=("moe_experts", "index_scores", "attn_latent"),
    )


def _newest_rows(scores, seen, topk):
    """``last_2048``: the newest ``topk`` rows a query sees, for the highest scores."""
    import jax.numpy as jnp

    return seen & (jnp.cumsum(seen[:, ::-1], axis=-1)[:, ::-1] <= topk)


def deepseek_v32_patches(ref) -> dict:
    """The controls of ``--model deepseek_v32`` that change what the
    REFERENCE computes (control -> the functions of ``ref`` it replaces):
    every row a query sees attended, the newest ``index_topk`` rows for the
    highest scores, the index scores without their ``relu``, the 8 best of
    all 256 router outputs (no groups), the softmax scale without YaRN's
    ``m^2``, every second position attending the set of the position
    before it (the shortcut a verify step's shared gather must not take),
    the MLP products in the nearest precision below.  ``stale_reject`` is
    the check's own: the step after a rejection reads the stale row."""
    import jax.numpy as jnp

    return {
        "w8a8_mlp": {"_swiglu": _w8a8_swiglu()},
        "no_selection": {"_select": lambda scores, seen, topk: seen},
        "last_2048": {"_select": _newest_rows},
        "no_index_relu": {"_index_act": lambda dots: dots},
        "no_groups": {"_group_limit": lambda ranked, n_group, topk_group: jnp.ones(ranked.shape, bool)},
        "no_mscale": {"_softmax_scale": lambda nope, rope, mscale: jnp.float32((nope + rope) ** -0.5)},
        "draft_shares_set": {"_own_set": lambda kept: kept.at[1::2].set(kept[:-1:2])},
    }


def child_deepseek_v32(seed: int, sizes: Sizes, control: str = "") -> None:
    """``--hybrid --model deepseek_v32``: the serving model's chunk program
    (``prefill_rows`` in place on a state of ``max_len`` rows a slot) and
    its verify step (on true drafts, on wrong drafts, and the prediction
    module's logits) against the float32 reference, with the sets the
    stack's and the module's indexers keep, by the benchmark's own
    comparison (``benchmarks/arch/deepseek_v32.py``).  A control changes
    what the reference computes (``deepseek_v32_patches``) or how the check
    steps after a rejected draft (``stale_reject``); each has to leave a
    limit."""
    import numpy as np

    from generativeaiexamples_tpu.engine.serving_models import serving_model
    from generativeaiexamples_tpu.models import hybrid
    from generativeaiexamples_tpu.utils.jax_runtime import (
        device_report,
        enable_compile_cache,
        runtime_report,
    )

    enable_compile_cache()
    t0 = time.monotonic()
    arch = _bench_arch("deepseek_v32")
    with open(os.path.join(ROOT, DEEPSEEK_CONFIG)) as f:
        block = json.load(f)["reference"]
    limits, floors = block["logit_share_limits"], block["index_overlap_floors"]
    arch._CHECK.update(
        limits=limits, floors=floors, verify=sizes.deepseek_verify, chunk=sizes.deepseek_chunk)
    cfg = hybrid.PRESETS[sizes.deepseek_model]()
    if sizes.deepseek_topk:
        cfg = dataclasses.replace(cfg, index_topk=sizes.deepseek_topk)
    prompt = sizes.deepseek_prompt
    params = serving_model(cfg, None, prompt).prepare_params(
        None, quantize=False, matmul_kernel="xla", seed=seed)
    tokens = np.random.RandomState(seed).randint(1, cfg.vocab_size, size=prompt).astype(np.int32)
    reference = arch.deepseek_v32_reference
    with _reference_patched(reference, deepseek_v32_patches(reference).get(control, {})):
        shares, _, overlaps = arch.logit_shares(
            params, cfg, tokens, prompt, stale_reject=control == "stale_reject")
    readings = arch.share_quantiles(shares)
    failed = arch.outside_limits(readings, overlaps)
    report = runtime_report()
    emit(
        {
            "phase": "hybrid", "model": sizes.deepseek_model, "control": control or None,
            "positions": {k: int(len(v)) for k, v in shares.items()},
            **readings, **{f"index_overlap_{k}": v for k, v in overlaps.items()},
            "limits": {**limits, "index_overlap_floors": floors}, "within_limits": not failed,
            "outside": failed, "seconds": time.monotonic() - t0,
            "compile": report["compile"], "peak_bytes_in_use": report["peak_bytes_in_use"],
            "kernel_paths": {
                k: v for site in ("moe_experts", "index_scores", "attn_latent", "mtp_")
                for k, v in _taken(site).items()
            },
            "device": device_report(),
        }
    )
    if control and not failed:
        raise SmokeFailure(f"the control {control!r} stayed inside every limit: {readings} {overlaps}")
    if not control and failed:
        raise SmokeFailure(f"the program left the reference: {failed} of {readings} {overlaps}")


def child_hybrid(seed: int, sizes: Sizes, control: str = "", model: str = "ling") -> None:
    if model == "deepseek_v32":
        return child_deepseek_v32(seed, sizes, control)
    if model == "longcat_flash":
        return child_longcat_flash(seed, sizes, control)
    if model == "dots3_note":
        return child_dots3_note(seed, sizes, control)
    if model == "zaya":
        return child_zaya(seed, sizes, control)
    if model == "nemotron_h":
        return child_nemotron_h(seed, sizes, control)
    if model == "exaone_moe":
        return child_exaone(seed, sizes, control)
    if model == "mistral4":
        return child_mistral4(seed, sizes, control)
    import importlib

    import jax
    import jax.numpy as jnp
    import numpy as np

    from generativeaiexamples_tpu.engine.serving_models import serving_model
    from generativeaiexamples_tpu.models import hybrid
    from generativeaiexamples_tpu.utils.jax_runtime import (
        device_report,
        enable_compile_cache,
        runtime_report,
    )

    enable_compile_cache()
    t0 = time.monotonic()
    first, second = sizes.hybrid_chunks
    if model == "ling":
        preset, n_prompt = sizes.hybrid_model, first + second - 7
        reference = importlib.import_module(f"{PACKAGE}.models.hybrid_reference")
    else:
        preset, n_prompt = sizes.mellum_model, sizes.mellum_prompt
        reference = importlib.import_module(f"{PACKAGE}.models.mellum_reference")
    n_all = n_prompt + sizes.hybrid_decode
    cfg = hybrid.PRESETS[preset]()  # what the reference computes
    served = cfg  # what the program computes: a control may differ
    if control == "no_window":
        served = dataclasses.replace(cfg, sliding_window=sizes.max_len)
    elif control == "no_yarn":
        served = dataclasses.replace(cfg, rope_full=cfg.rope_window)
    limits = hybrid_limits(model)
    server = serving_model(served, None, sizes.max_len)
    params = server.prepare_params(None, quantize=False, matmul_kernel="xla", seed=seed)
    state = server.init_state(2, sizes.max_len)
    tokens = np.random.RandomState(seed).randint(1, cfg.vocab_size, size=n_all).astype(np.int32)

    @functools.partial(jax.jit, donate_argnums=(1,), static_argnums=(5,))
    def prefill(params, cache, toks, start, n, kv_bucket):
        cache, hidden, _ = server.prefill_row(params, cache, toks, start, n, jnp.int32(1), kv_bucket)
        return cache, server.logits(params, hidden)[0]

    @functools.partial(jax.jit, donate_argnums=(1,))
    def step(params, cache, tok, pos):
        """One teacher-forced decode step of slot 1 (slot 0 does not
        decode), returning its logits: decode_chunk's own body."""
        hidden, cache, _ = hybrid.forward(
            params, served, jnp.stack([tok, tok])[:, None], jnp.stack([pos, pos]),
            jnp.asarray([0, 1], jnp.int32), cache, window=sizes.max_len,
        )
        return cache, server.logits(params, hidden)[1, 0]

    got = []
    for start in range(0, n_prompt, first):
        n = min(first, n_prompt - start)
        toks = np.zeros((1, first), np.int32)
        toks[0, :n] = tokens[start : start + n]
        state, lg = prefill(params, state, jnp.asarray(toks), jnp.int32(start), jnp.int32(n), sizes.max_len)
        got.append(np.asarray(lg[:n], np.float32))
    for pos in range(n_prompt, n_all):
        state, lg = step(params, state, jnp.int32(tokens[pos]), jnp.int32(pos))
        got.append(np.asarray(lg, np.float32)[None])
    got = np.concatenate(got)
    served_s = time.monotonic() - t0
    # The reference's full forward, in blocks of positions through the
    # head so that a float32 (positions, vocabulary) block fits.
    if control == "w8a8_mlp":
        # The control: the reference computed in the nearest precision
        # below the configuration's (``_w8a8_swiglu``).  It has to leave
        # the limits.
        reference._swiglu = _w8a8_swiglu()  # this process runs nothing else
        jax.clear_caches()  # a layer traced before this would keep the plain one
    x = reference.hidden_states(params, cfg, tokens)
    want = np.concatenate([
        np.asarray(reference._head(
            x[i : i + 128], params["final_norm"], params["lm_head"], float(cfg.norm_eps)))
        for i in range(0, n_all, 128)
    ])

    # Each position's error as a share of its own logits' root mean
    # square.  A token whose eighth and ninth expert scores lie within
    # rounding takes another expert, and that moves its logits, and
    # through the state those of later positions, by far more than the
    # precision does: the lowest tenth over positions reads the
    # arithmetic, the median and the ninth tenth read those flips too.
    share = np.sqrt(((got - want) ** 2).mean(-1)) / np.sqrt((want**2).mean(-1))

    def quantiles(lo, hi):
        return [float(np.quantile(share[lo:hi], q)) for q in (0.1, 0.5, 0.9)]

    readings = {
        f"{part}_{q}_share": value
        for part, span in (("prefill", (0, n_prompt)), ("decode", (n_prompt, n_all)))
        for q, value in zip(HYBRID_QUANTILE_LIMITS[model], quantiles(*span))
    }
    worst = float(np.abs(got - want).max() / np.abs(want).max())
    agree = int((got.argmax(-1) == want.argmax(-1)).sum())
    failed = {k: v for k, v in readings.items() if not v <= limits[k]}
    report = runtime_report()
    emit(
        {
            "phase": "hybrid", "model": preset, "control": control or None,
            "positions": {"prefill": n_prompt, "decode": sizes.hybrid_decode},
            **readings, "limits": limits, "within_limits": not failed,
            "first_chunk_p50": quantiles(0, first)[1],
            "worst_abs_gap_share": worst, "argmax_agree": agree, "of": n_all,
            "served_s": served_s, "reference_s": time.monotonic() - t0 - served_s,
            "compile": report["compile"], "peak_bytes_in_use": report["peak_bytes_in_use"],
            "kernel_paths": {**_taken("moe_experts"), **_taken("attn_")},
            "device": device_report(),
        }
    )
    if control and not failed:
        raise SmokeFailure(f"the control {control!r} stayed inside every limit: {readings}")
    if not control and failed:
        raise SmokeFailure(f"logits left the reference: {failed} (limits {limits})")


CHILDREN = {
    **{
        f"hybrid_{model}" + (f"_{control}" if control else ""):
            functools.partial(child_hybrid, control=control, model=model)
        for model, controls in HYBRID_CONTROLS.items() for control in ("", *controls)
    },
    "device": child_device,
    "retrieval": child_retrieval,
    "optin": child_optin,
    "four": child_four,
}


def run(
    seed: int,
    sizes: Sizes = FULL,
    expect: str = "tpu",
    four_chips: bool = False,
    hybrid: tuple | None = None,
) -> dict:
    """Run the phases; returns the device for the last line.  ``sizes``
    and ``expect`` exist for the CPU rehearsal in the tests — the command
    line always runs FULL on a TPU."""
    if not os.path.isdir(os.path.join(ROOT, PACKAGE)):
        raise SmokeFailure(
            f"{ROOT} holds no {PACKAGE}/: chip_smoke.py runs from the "
            "root of a checkout"
        )
    if four_chips:
        return child_phase("four", seed, sizes, expect, 3000, count=4)
    if hybrid is not None:
        model, control = hybrid
        if control and control not in HYBRID_CONTROLS[model]:
            raise SmokeFailure(f"--model {model} has no control {control!r}: {HYBRID_CONTROLS[model]}")
        name = f"hybrid_{model}" + (f"_{control}" if control else "")
        return child_phase(name, seed, sizes, expect, 3000)
    device = phase_device(seed, sizes, expect)
    phase_serve(seed, sizes, expect)
    child_phase("retrieval", seed, sizes, expect, 900)
    child_phase("optin", seed, sizes, expect, 1200)
    return device


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument(
        "--four-chips",
        action="store_true",
        help="run only the tensor-parallel mesh and the replica pool, "
        "in one process that holds four chips",
    )
    parser.add_argument(
        "--hybrid",
        action="store_true",
        help="run only the layer-kind model's logits against its reference",
    )
    parser.add_argument(
        "--model", choices=sorted(HYBRID_CONTROLS), default="ling",
        help="with --hybrid: which layer-kind model",
    )
    parser.add_argument(
        "--control", choices=sorted({c for cs in HYBRID_CONTROLS.values() for c in cs}), default="",
        help="with --hybrid: run one of the comparison's controls, which has to fail it",
    )
    parser.add_argument("--child", choices=sorted(CHILDREN), help=argparse.SUPPRESS)
    parser.add_argument("--sizes", help=argparse.SUPPRESS)
    args = parser.parse_args(argv)
    if args.child:
        sizes = Sizes(**json.loads(args.sizes)) if args.sizes else FULL
        try:
            CHILDREN[args.child](args.seed, sizes)
        except SmokeFailure as e:
            print(f"chip_smoke {args.child}: {e}", file=sys.stderr)
            return 1
        return 0
    try:
        device = run(
            args.seed, four_chips=args.four_chips,
            hybrid=(args.model, args.control) if args.hybrid else None,
        )
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    emit({"ok": True, "device": device})
    return 0


if __name__ == "__main__":
    sys.exit(main())
