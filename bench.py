"""Serving benchmark: llama3-8b decode throughput + TTFT on the local TPU chip.

Prints ONE COMPACT JSON line (<= 1 KB) as the last stdout line:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N, ...headline}
and writes the FULL result dict to ``perf/bench_full.json``
(``GAIE_BENCH_RESULT_PATH`` overrides; the compact line carries the path
as ``full_results``).  The split exists because the driver's tail capture
parses the last stdout line — a single giant result line does not
survive it, so the headline must stay small and the detail goes to a
file.  A run that finds no TPU, or in which a phase fails, exits
non-zero; every result carries the device it ran on.

Method
------
Measures KV-cached decode throughput (tokens/sec/chip) and prefill TTFT of
llama3-8b at FULL 32-layer depth with weight-only int8 quantization (the
serving configuration: int8 weights ~8 GB fit one v5e chip's 16 GB HBM,
where bf16's 16 GB of weights cannot).  QKV and gate/up projections are
packed (``llama.pack_for_serving``) and decode runs in 128-step device-side
scan chunks so host round-trips are amortized over the chunk.

Baseline
--------
The reference publishes no performance numbers (BASELINE.md); the
comparison denominator is NVIDIA's public TRT-LLM llama3-8b A100 offline
throughput, ~2500 output tok/s/GPU at moderate batch.  vs_baseline =
measured full-depth tokens/sec/chip / 2500.
"""

from __future__ import annotations

import json
import os
import threading
import time
from typing import Optional, Sequence

import numpy as np

A100_TRTLLM_LLAMA3_8B_TOKS = 2500.0  # public TRT-LLM A100 figure (see docstring)
# Long-context RAG profile denominator: no single public A100 TRT-LLM
# number exists for ISL 1500 / OSL 512; NVIDIA's published TRT-LLM perf
# tables show ~20-30% output-throughput degradation from short-ISL to
# 1.5-2k-ISL workloads, so 0.8 x 2500 = 2000 is used as the estimated
# A100 denominator for this profile (recorded as an estimate).
A100_TRTLLM_LONG_TOKS = 2000.0

# Realistic RAG serving shapes (reference: 1500-token context budget,
# `common/utils.py:97-122`; up-to-1024-token answers, `common/server.py:85`).
LONG_BATCH = 48
LONG_MAX_LEN = 2048
LONG_PROMPT = 1500  # buckets to 1536 (dense 3*2^k sequence buckets)
LONG_DECODE = 512  # 1500 + 512 fits max_len 2048
BATCH = 320
MAX_LEN = 256  # 128-token prompts + 128 decode steps exactly fill it
PROMPT_LEN = 128
DECODE_STEPS = 128
PREFILL_CHUNK = 160  # rows per prefill sub-batch (caps MLP transients)
KV_DTYPE = "int8"  # per-(token, head) scales; halves cache HBM + read traffic
SERVING_SLOTS = 320  # scheduler slots for the serving-path phase
# Decode steps per chunk: the serving tick (admission prefill + one
# chunk) bounds TTFT, since a request's first token lands ~RTT+prefill
# into the tick after the one it arrives in (pipelined tick).  Measured
# frontier on the v5e chip, 2026-07-31 (perf/exp_serving.py, budget 4096):
# chunk 8 -> capacity 3304 tok/s but p50 671 ms at 0.8x; chunk 4 ->
# capacity 2731 tok/s and p50 378 ms at 0.8x.  The <400 ms p50 north
# star (BASELINE.md) prices ~17% of saturated throughput.
SERVING_CHUNK = 4
SERVING_SECONDS = 60.0  # measured steady-state window
# Admission-queue bound: under sustained overload a FIFO queue (and its
# TTFT) grows without bound; shedding beyond a few seconds of queue keeps
# accepted requests' latency bounded — the NIM/Triton backpressure
# contract.  64 ~= 3s of accepted arrivals at measured capacity.
SERVING_MAX_QUEUE = 64
# Per-tick admission prefill budget: the scheduler default (32k tokens)
# lets one admission tick prefill ~3s of work before the next decode
# chunk, which is exactly the 4.5s TTFT p50 the 2026-07-30 run measured near
# capacity.  4k tokens = 32 rows of 128: admission throughput stays above
# any sub-capacity arrival rate (so the queue drains every tick) while
# one tick's prefill stays ~O(200 ms).  2048 measured p50 427 ms vs
# 4096's 378 ms at the same 0.8x load: bigger batches amortize the
# per-forward fixed cost without lengthening the queue.
SERVING_ADMIT_BUDGET = 4096


def bench_serving(cfg, params, offline_tps: float) -> dict:
    """Serving-path benchmark: the continuous-batching scheduler under
    Poisson arrivals of streaming requests.

    This measures what TRT-LLM's in-flight-batching numbers mean
    (reference `docs/architecture.md:57-66`): sustained output tokens/sec
    with requests arriving concurrently, p50/p95 TTFT *under load*, and
    slot occupancy — not the offline full-batch decode above.  Three
    phases: deep saturation FIRST (measures serving capacity = sustained
    tok/s including prefill and scheduling costs; doubles as the
    overload row), then 0.8x and 1.0x of that MEASURED capacity — the
    0.8x point is the <400 ms TTFT north star (BASELINE.md).  Offered
    load is calibrated to measured serving capacity, not offline decode
    throughput: offline tok/s ignores prefill entirely, so phases sized
    from it sit beyond true capacity and only measure the admission
    controller under overload.  List-valued keys stay ordered [near,
    capacity, overload].
    """
    import random
    import threading

    from generativeaiexamples_tpu.engine.scheduler import Request, Scheduler

    sched = Scheduler(
        cfg,
        params=params,
        max_batch=SERVING_SLOTS,
        max_len=MAX_LEN,
        decode_chunk_size=SERVING_CHUNK,
        seed=1,
        max_queue=SERVING_MAX_QUEUE,
        admit_token_budget=SERVING_ADMIT_BUDGET,
    )
    sched.start()
    rng = np.random.default_rng(1)
    rnd = random.Random(7)
    lock = threading.Lock()
    token_times: list[float] = []
    ttfts: list[float] = []
    occupancy: list[int] = []

    def make_request(i: int, max_tokens: int = DECODE_STEPS):
        from generativeaiexamples_tpu.engine.sampler import SamplingParams

        prompt = rng.integers(0, cfg.vocab_size, (PROMPT_LEN,)).tolist()
        state = {"first": None, "submitted": None}

        def on_token(tid: int, state=state) -> None:
            now = time.perf_counter()
            with lock:
                token_times.append(now)
                if state["first"] is None:
                    state["first"] = now
                    ttfts.append(now - state["submitted"])

        return Request(
            token_ids=prompt,
            sampling=SamplingParams(
                temperature=0.7, top_p=0.9, max_tokens=max_tokens
            ),
            on_token=on_token,
            on_done=lambda reason: None,
            id=f"bench-{i}",
        ), state

    # Warm the compile buckets (prefill pb up to the admission budget's
    # row cap at s=128, decode chunk at kv buckets 128/256) before the
    # timed window: the largest reachable admission batch is
    # budget/PROMPT_LEN rows, and its first compile must not land
    # mid-measurement.
    max_rows = max(SERVING_ADMIT_BUDGET // PROMPT_LEN, 1)
    bursts = [b for b in (1, 4, 8, 16, 32, 64) if b <= max_rows]
    for burst in bursts:
        reqs = []
        for i in range(burst):
            req, state = make_request(10_000 + burst * 100 + i, max_tokens=4)
            state["submitted"] = time.perf_counter()
            reqs.append(req)
            sched.submit(req)
        deadline = time.perf_counter() + 120
        while time.perf_counter() < deadline:
            snap = sched.stats.snapshot()
            if not snap["active_slots"] and not snap["queued"]:
                break
            time.sleep(0.2)
        time.sleep(0.5)

    def poisson_phase(rate: float, warm_s: float, measure_s: float):
        """Open-loop Poisson arrivals at ``rate`` req/s; returns
        (sustained tok/s, p50 ms, p95 ms, mean occupancy, rejected
        fraction) over the measurement window (arrivals start at t0,
        stats from t0+warm)."""
        with lock:
            token_times.clear()
            ttfts.clear()
        occupancy.clear()
        rej0 = sched.stats.snapshot()["rejected_total"]
        t0 = time.perf_counter()
        t_end = t0 + warm_s + measure_s
        nxt = t0
        i = 0
        offered = 0
        while (now := time.perf_counter()) < t_end:
            if now >= nxt:
                req, state = make_request(i)
                state["submitted"] = time.perf_counter()
                sched.submit(req)
                i += 1
                offered += 1
                nxt += rnd.expovariate(rate)
            occupancy.append(sched.stats.snapshot()["active_slots"])
            time.sleep(min(max(nxt - time.perf_counter(), 0.0), 0.05))
        with lock:
            window = [t for t in token_times if t >= t0 + warm_s]
            # Steady-state rate from the second half of the window: at
            # request lifetimes comparable to the window (slow-tick
            # transients, deep saturation) the first half is ramp, and a
            # ramp-diluted "capacity" would mis-calibrate every phase
            # derived from it.
            half = [t for t in window if t >= t0 + warm_s + measure_s / 2]
            tt = sorted(ttfts)
        rejected = sched.stats.snapshot()["rejected_total"] - rej0
        # Drain so the next phase starts from an empty queue.
        deadline = time.perf_counter() + 90
        while time.perf_counter() < deadline:
            snap = sched.stats.snapshot()
            if not snap["active_slots"] and not snap["queued"]:
                break
            time.sleep(0.25)
        sustained = max(
            len(window) / measure_s, len(half) / (measure_s / 2)
        )
        p50 = tt[len(tt) // 2] * 1000 if tt else 0.0
        p95 = tt[int(len(tt) * 0.95)] * 1000 if tt else 0.0
        occ = float(np.mean(occupancy)) if occupancy else 0.0
        rej_frac = rejected / max(offered, 1)
        return sustained, p50, p95, occ, rej_frac

    # Phase 0 — deep saturation: measures SERVING capacity (sustained
    # tok/s with prefill, admission, and scheduling costs included) and
    # doubles as the overload row.  The long warm segment also compiles
    # every full-occupancy decode shape before any measured window.
    # Offered load for the remaining phases is calibrated against THIS
    # number, not offline decode throughput: offline tok/s ignores
    # prefill, so "0.8x offline" is beyond true serving capacity and
    # only ever measured the admission controller under overload.
    sat_rate = 2.0 * offline_tps / DECODE_STEPS
    sat_tps, sat_p50, sat_p95, sat_occ, sat_rej = poisson_phase(
        sat_rate, 25.0, SERVING_SECONDS
    )
    if sat_tps < 0.35 * offline_tps:
        # Implausibly low capacity (expected ~0.6-0.7x offline at these
        # shapes): a transient — backend slow patch, one-off compile —
        # polluted the window, and every later phase is calibrated off
        # this number.  One retry; keep the better run.
        tps2, p50_2, p95_2, occ2, rej2 = poisson_phase(
            sat_rate, 25.0, SERVING_SECONDS
        )
        if tps2 > sat_tps:
            sat_tps, sat_p50, sat_p95, sat_occ, sat_rej = (
                tps2, p50_2, p95_2, occ2, rej2
            )
    capacity_tps = sat_tps
    # Phase 1 — 0.8x measured capacity: the TTFT north-star operating
    # point (BASELINE.md: p50 < 400 ms at ~80% load).
    near_rate = 0.8 * capacity_tps / DECODE_STEPS
    near_tps, p50, p95, near_occ, near_rej = poisson_phase(
        near_rate, 10.0, SERVING_SECONDS
    )
    # Phase 2 — 1.0x measured capacity: TTFT at offered == capacity.
    cap_rate = 1.0 * capacity_tps / DECODE_STEPS
    cap_tps, cap_p50, cap_p95, cap_occ, cap_rej = poisson_phase(
        cap_rate, 10.0, SERVING_SECONDS
    )
    sched.stop()
    return {
        "serving_tokens_per_sec": round(sat_tps, 1),
        "serving_vs_baseline": round(sat_tps / A100_TRTLLM_LLAMA3_8B_TOKS, 3),
        "serving_measured_capacity_tokens_per_sec": round(capacity_tps, 1),
        # The overload phase's rate was fixed at 2x OFFLINE decode
        # throughput (it runs first, before capacity is known); express
        # it in the same capacity-relative units as the other two.
        "serving_phase_load_fracs_of_capacity": [
            0.8,
            1.0,
            round(sat_rate * DECODE_STEPS / max(capacity_tps, 1e-9), 2),
        ],
        "serving_near_capacity_tokens_per_sec": round(near_tps, 1),
        "serving_ttft_p50_ms": round(p50, 1),
        "serving_ttft_p95_ms": round(p95, 1),
        "serving_capacity_tokens_per_sec": round(cap_tps, 1),
        "serving_capacity_ttft_p50_ms": round(cap_p50, 1),
        "serving_capacity_ttft_p95_ms": round(cap_p95, 1),
        "serving_overload_ttft_p50_ms": round(sat_p50, 1),
        "serving_overload_ttft_p95_ms": round(sat_p95, 1),
        "serving_rejected_frac": [
            round(near_rej, 3), round(cap_rej, 3), round(sat_rej, 3)
        ],
        "serving_max_queue": SERVING_MAX_QUEUE,
        "serving_admit_token_budget": SERVING_ADMIT_BUDGET,
        "serving_offered_req_per_sec": [
            round(near_rate, 2), round(cap_rate, 2), round(sat_rate, 2)
        ],
        "serving_mean_active_slots": [
            round(near_occ, 1), round(cap_occ, 1), round(sat_occ, 1)
        ],
        "serving_slots": SERVING_SLOTS,
        "serving_decode_chunk": SERVING_CHUNK,
    }


# Speculative phase: moderate batch keeps the draft model + second
# scheduler cache within HBM next to the offline generator's buffers.
# (The verify pass uses the append-buffer protocol on TPU — same
# memory/layout profile as the plain decode path — so batch here is a
# memory-budget choice, not a layout constraint.)
SPEC_BATCH = 64
SPEC_GAMMA = 4


def bench_speculative(cfg, params) -> dict:
    """Speculative decoding through the scheduler: tok/s with and without
    a draft at the same batch/geometry, for BOTH the greedy (prefix
    agreement) and sampled (rejection sampling, temp 0.7 / top_p 0.9)
    acceptance paths, plus the measured acceptance rates.

    Draft selection (``GAIE_SPEC_DRAFT``):
      * ``1b`` (default) — llama3.2-1b geometry with random weights.
      * ``self:K`` — early-exit self-speculation: the target's own first
        K layers (weight-sharing, ``spec_decode.self_draft``); draft cost
        is K/32 of a target pass, so breakeven acceptance is far lower.

    With random weights either draft's agreement with the target — and
    therefore the measured speedup — is a floor, not what a trained pair
    achieves (acceptance >0.5 for a trained pair is demonstrated
    hermetically in tests/test_speculative.py::TestTrainedPairAcceptance).
    The numbers to read together: spec_accept_rate / spec_sampled_accept_
    rate (how often drafts were right), spec_tokens_per_sec vs
    spec_baseline_tokens_per_sec (net machinery effect at that
    acceptance).
    """
    import queue as _q

    from generativeaiexamples_tpu.engine.sampler import SamplingParams
    from generativeaiexamples_tpu.engine.scheduler import Request, Scheduler
    from generativeaiexamples_tpu.models import llama

    rng = np.random.default_rng(11)
    prompts = [
        rng.integers(0, cfg.vocab_size, (PROMPT_LEN,)).tolist()
        for _ in range(SPEC_BATCH)
    ]

    def measure(sched, temperature: float, top_p: float) -> float:
        """Submit the full batch twice (warm, then timed)."""
        best = 0.0
        for timed in (False, True):
            done: "_q.Queue[str]" = _q.Queue()
            counts = [0] * SPEC_BATCH

            def on_token(i):
                def _cb(tid, i=i):
                    counts[i] += 1

                return _cb

            t0 = time.perf_counter()
            for i, p in enumerate(prompts):
                sched.submit(
                    Request(
                        token_ids=list(p),
                        sampling=SamplingParams(
                            temperature=temperature,
                            top_p=top_p,
                            max_tokens=DECODE_STEPS,
                        ),
                        on_token=on_token(i),
                        on_done=done.put,
                        id=f"spec-{timed}-{temperature}-{i}",
                    )
                )
            for _ in range(SPEC_BATCH):
                done.get(timeout=600)
            elapsed = time.perf_counter() - t0
            if timed:
                best = sum(counts) / elapsed
        return best

    # Default draft: early-exit self-speculation.  Unlike an independent
    # random 1b draft (acceptance ~0 by construction), the target's own
    # first K layers correlate with its full forward even at random
    # init (measured ~0.37 sampled acceptance at tiny scale), and the
    # draft costs K/32 of a target pass — so the bench measures the
    # machinery at a real, non-floor acceptance without external
    # weights.  GAIE_SPEC_DRAFT=1b restores the independent-draft floor
    # measurement.
    draft_mode = os.environ.get("GAIE_SPEC_DRAFT", "self:8")
    spec_kw: dict = {}
    if draft_mode == "ngram":
        # Prompt-lookup: zero draft cost; acceptance is whatever the
        # workload's self-repetition gives (random greedy decodes often
        # fall into loops, RAG answers quote their context).
        draft_cfg = None
        draft_desc = f"prompt-lookup (ngram), gamma {SPEC_GAMMA}"
        spec_kw = {"spec_mode": "ngram"}
        draft_kw = {}
    elif draft_mode.startswith("self:"):
        from generativeaiexamples_tpu.engine.spec_decode import self_draft

        k = int(draft_mode.split(":", 1)[1])
        draft_cfg, draft_params = self_draft(cfg, params, k)
        draft_desc = f"self-speculation, first {k}/{cfg.n_layers} layers"
        draft_kw = {"draft_params": draft_params, "draft_quantize": False}
    else:
        draft_cfg = llama.llama32_1b(max_seq_len=MAX_LEN)
        draft_desc = "llama3.2-1b geometry, random int8 weights"
        draft_kw = {"draft_quantize": True}
    spec_sched = Scheduler(
        cfg,
        params=params,
        max_batch=SPEC_BATCH,
        max_len=MAX_LEN,
        decode_chunk_size=SERVING_CHUNK,
        seed=3,
        draft_cfg=draft_cfg,
        gamma=SPEC_GAMMA,
        **draft_kw,
        **spec_kw,
    )
    spec_sched.start()

    def accept_delta(sched, before: dict) -> float:
        """Acceptance rate derived from the spec counters accumulated
        since ``before`` (requires the loop thread paused/joined)."""
        snap = sched.stats.snapshot()
        rounds = snap["spec_rounds"] - before["spec_rounds"]
        tokens = snap["spec_tokens"] - before["spec_tokens"]
        if not rounds:
            return 0.0
        return max(0.0, (tokens / rounds - 1.0) / SPEC_GAMMA)

    base_snap = spec_sched.stats.snapshot()
    spec_tps = measure(spec_sched, 0.0, 0.9)
    # Counter reads race the loop thread by up to one chunk; the error on
    # 64x128 tokens is <1%, acceptable for a rate.
    greedy_snap = spec_sched.stats.snapshot()
    greedy_accept = accept_delta(spec_sched, base_snap)
    spec_sampled_tps = measure(spec_sched, 0.7, 0.9)
    spec_sched.stop()
    sampled_accept = accept_delta(spec_sched, greedy_snap)
    del spec_sched

    plain_sched = Scheduler(
        cfg,
        params=params,
        max_batch=SPEC_BATCH,
        max_len=MAX_LEN,
        decode_chunk_size=SERVING_CHUNK,
        seed=3,
    )
    plain_sched.start()
    plain_tps = measure(plain_sched, 0.0, 0.9)
    plain_sampled_tps = measure(plain_sched, 0.7, 0.9)
    plain_sched.stop()
    del plain_sched
    return {
        "spec_tokens_per_sec": round(spec_tps, 1),
        "spec_baseline_tokens_per_sec": round(plain_tps, 1),
        "spec_speedup": round(spec_tps / max(plain_tps, 1e-9), 3),
        "spec_accept_rate": round(greedy_accept, 4),
        "spec_sampled_tokens_per_sec": round(spec_sampled_tps, 1),
        "spec_sampled_baseline_tokens_per_sec": round(plain_sampled_tps, 1),
        "spec_sampled_speedup": round(
            spec_sampled_tps / max(plain_sampled_tps, 1e-9), 3
        ),
        "spec_sampled_accept_rate": round(sampled_accept, 4),
        "spec_gamma": SPEC_GAMMA,
        "spec_batch": SPEC_BATCH,
        "spec_draft": draft_desc,
        "spec_note": (
            "early-exit self-draft: acceptance is real (first-K layers "
            "correlate with the full forward even at random init) at K/32 "
            "draft cost"
            if draft_mode.startswith("self:")
            else "prompt-lookup: zero draft cost; acceptance = the "
            "workload's self-repetition"
            if draft_mode == "ngram"
            else "independent random draft => acceptance floor"
        )
        + "; trained-pair acceptance (>0.5) demonstrated in "
        "tests/test_speculative.py",
    }


# Cyclic-corpus geometry shared by the trained-pair spec phases: both
# models learn the period-7 sequence to near-certainty, the hermetic
# stand-in for a production 8B/1B draft pair.
SPEC_PAIR_PERIOD = 7
SPEC_PAIR_BASE = 10  # token ids [10, 10 + period)


def _train_spec_pair() -> tuple:
    """Train the hermetic target/one-layer-draft pair from
    ``tests/test_speculative.py`` on the cyclic corpus; returns
    ``(tcfg, dcfg, tparams, dparams, losses, base, period)``.  Shared by
    ``bench_spec_trained`` (offline acceptance) and
    ``bench_spec_serving`` (online scheduler at high concurrency)."""
    import jax
    import jax.numpy as jnp
    import optax

    from generativeaiexamples_tpu.engine import training
    from generativeaiexamples_tpu.models import llama

    tcfg = llama.llama_tiny(dtype="float32", max_seq_len=128)
    dcfg = llama.llama_tiny(dtype="float32", max_seq_len=128, n_layers=1)
    rng = np.random.default_rng(0)
    period = SPEC_PAIR_PERIOD
    base = np.arange(SPEC_PAIR_BASE, SPEC_PAIR_BASE + period)

    def batch(bsz=32, seq=33):
        phase = rng.integers(0, period, bsz)
        rows = np.stack([np.tile(base, 6)[p : p + seq] for p in phase])
        return {
            "tokens": jnp.asarray(rows[:, :-1]),
            "targets": jnp.asarray(rows[:, 1:]),
            "mask": jnp.ones((bsz, seq - 1), jnp.float32),
        }

    losses = []
    pair = []
    for cfg_i, seed in ((tcfg, 0), (dcfg, 1)):
        opt = optax.adam(3e-3)
        state = training.init_train_state(cfg_i, opt, jax.random.PRNGKey(seed))
        step = jax.jit(training.make_train_step(cfg_i, opt))
        for _ in range(120):
            state, metrics = step(state, batch())
        losses.append(float(metrics["loss"]))
        pair.append(state.params)
    return tcfg, dcfg, pair[0], pair[1], losses, base, period


def bench_spec_trained() -> dict:
    """Trained-pair speculative decoding: hardware-measured acceptance
    and net speedup at a NON-floor acceptance rate.

    The flagship spec phase above necessarily runs random weights (no
    production checkpoints are reachable here), which measures the
    machinery at the acceptance FLOOR only.  This phase trains the
    hermetic target/one-layer-draft pair from
    ``tests/test_speculative.py`` (cyclic corpus both models learn to
    near-certainty, the stand-in for a production 8B/1B pair) and
    measures acceptance + spec-on/off throughput through the scheduler
    on hardware.  Caveat, stated in the artifact: at tiny scale
    wall-clock is per-dispatch-latency-bound, so the ACCEPTANCE rates are the transferable quantity;
    the tok/s ratio under-reports what the same acceptance yields at 8B
    compute intensity."""
    from generativeaiexamples_tpu.engine.sampler import SamplingParams
    from generativeaiexamples_tpu.engine.scheduler import Request, Scheduler

    tcfg, dcfg, tparams, dparams, losses, base, period = _train_spec_pair()
    rng = np.random.default_rng(0)
    gamma = 3
    n_req, max_tokens = 16, 48

    def run(sched, temperature) -> float:
        import queue as _q

        done: "_q.Queue[str]" = _q.Queue()
        t0 = time.perf_counter()
        for i in range(n_req):
            p = int(rng.integers(0, period))
            prompt = np.tile(base, 3)[p : p + 10].tolist()
            sched.submit(
                Request(
                    token_ids=prompt,
                    sampling=SamplingParams(
                        temperature=temperature, max_tokens=max_tokens
                    ),
                    on_token=lambda t: None,
                    on_done=done.put,
                    id=f"st-{temperature}-{i}",
                )
            )
        for _ in range(n_req):
            done.get(timeout=300)
        return n_req * max_tokens / (time.perf_counter() - t0)

    spec = Scheduler(
        tcfg, tparams, max_batch=n_req, max_len=128, decode_chunk_size=4,
        draft_cfg=dcfg, draft_params=dparams, gamma=gamma, seed=5,
    )
    spec.start()
    try:
        run(spec, 0.0)  # compile both modes' shapes outside the
        run(spec, 0.7)  # timed windows
        base_snap = spec.stats.snapshot()
        spec_tps = run(spec, 0.0)
        mid_snap = spec.stats.snapshot()
        spec_sampled_tps = run(spec, 0.7)
        end_snap = spec.stats.snapshot()
    finally:
        spec.stop()

    def accept(a, b) -> float:
        rounds = b["spec_rounds"] - a["spec_rounds"]
        tokens = b["spec_tokens"] - a["spec_tokens"]
        return max(0.0, (tokens / max(rounds, 1) - 1.0) / gamma)

    plain = Scheduler(
        tcfg, tparams, max_batch=n_req, max_len=128, decode_chunk_size=4,
        seed=5,
    )
    plain.start()
    try:
        run(plain, 0.0)
        run(plain, 0.7)
        plain_tps = run(plain, 0.0)
        plain_sampled_tps = run(plain, 0.7)
    finally:
        plain.stop()
    return {
        "spec_trained_accept_rate": round(accept(base_snap, mid_snap), 4),
        "spec_trained_sampled_accept_rate": round(
            accept(mid_snap, end_snap), 4
        ),
        "spec_trained_speedup": round(spec_tps / max(plain_tps, 1e-9), 3),
        "spec_trained_sampled_speedup": round(
            spec_sampled_tps / max(plain_sampled_tps, 1e-9), 3
        ),
        "spec_trained_tokens_per_sec": round(spec_tps, 1),
        "spec_trained_baseline_tokens_per_sec": round(plain_tps, 1),
        "spec_trained_gamma": gamma,
        "spec_trained_final_loss": [round(x, 4) for x in losses],
        "spec_trained_note": (
            "tiny target + 1-layer draft trained in-bench (cyclic corpus) "
            "— acceptance is the transferable quantity; tiny-scale tok/s "
            "is dispatch-latency-bound and under-reports the speedup the "
            "same acceptance yields at 8B compute intensity"
        ),
    }


def bench_spec_serving() -> dict:
    """Speculative decoding through the ONLINE serving scheduler (PR 14).

    ``bench_spec_trained`` above measures the offline machinery; this
    phase measures the tentpole integration — per-slot draft state,
    batched verify, acceptance-adaptive gamma — under serving load:
    GAIE_BENCH_SPEC_C concurrent requests (default 128, oversubscribing
    the slot pool so admission/queueing runs hot) on the trained pair,
    spec-on vs spec-off.  Reports decode tok/s ratio, TTFT p95 ratio
    (draft prefill rides the admission batch — TTFT must not pay for
    speculation), windowed acceptance, greedy bit-identity, and the
    adaptive-gamma drill: a RANDOM draft (acceptance floor) must cost
    <= ~10% vs spec-off because the EWMA walks gamma down to 1."""
    import queue as _q

    import jax

    from generativeaiexamples_tpu.engine.sampler import SamplingParams
    from generativeaiexamples_tpu.engine.scheduler import Request, Scheduler
    from generativeaiexamples_tpu.models import llama

    tcfg, dcfg, tparams, dparams, losses, base, period = _train_spec_pair()
    c = int(os.environ.get("GAIE_BENCH_SPEC_C", "128"))
    slots = min(c, 32)
    gamma = 3
    max_tokens = 32
    rng = np.random.default_rng(3)
    prompts = [
        np.tile(base, 3)[p : p + 10].tolist()
        for p in rng.integers(0, period, c)
    ]

    def run_load(sched) -> tuple[float, float]:
        """Submit all c requests at once; returns (tok/s, TTFT p95 ms)."""
        done: "_q.Queue[str]" = _q.Queue()
        ttfts: list[float] = []
        n_tok = [0]

        def submit(i, prompt):
            state = {"sub": time.perf_counter(), "first": None}

            def on_token(tid):
                n_tok[0] += 1
                if state["first"] is None:
                    state["first"] = time.perf_counter() - state["sub"]

            def on_done(reason):
                ttfts.append(state["first"] or 0.0)
                done.put(reason)

            sched.submit(
                Request(
                    token_ids=list(prompt),
                    sampling=SamplingParams(
                        temperature=0.0, max_tokens=max_tokens
                    ),
                    on_token=on_token,
                    on_done=on_done,
                    id=f"ss-{i}",
                )
            )

        t0 = time.perf_counter()
        for i, p in enumerate(prompts):
            submit(i, p)
        for _ in range(c):
            done.get(timeout=600)
        elapsed = time.perf_counter() - t0
        return n_tok[0] / elapsed, float(np.percentile(ttfts, 95) * 1000)

    def collect_one(sched, prompt) -> list[int]:
        toks: list[int] = []
        done: "_q.Queue[str]" = _q.Queue()
        sched.submit(
            Request(
                token_ids=list(prompt),
                sampling=SamplingParams(temperature=0.0, max_tokens=16),
                on_token=toks.append,
                on_done=done.put,
            )
        )
        done.get(timeout=300)
        return toks

    # Two warm loads per scheduler: the first compiles the cold-admission
    # shapes, the SECOND compiles the shared-prefix graft path (segments
    # parked by load N are grafted by load N+1 — the graft executables
    # don't exist until a reload, and paying their compile inside the
    # timed window swamps the measurement at tiny scale).
    kw = dict(max_batch=slots, max_len=128, decode_chunk_size=4, seed=5)
    plain = Scheduler(tcfg, tparams, **kw)
    plain.start()
    try:
        run_load(plain)
        run_load(plain)
        plain_tps, plain_ttft = run_load(plain)
        plain_bits = collect_one(plain, prompts[0])
    finally:
        plain.stop()

    spec = Scheduler(
        tcfg, tparams, **kw,
        draft_cfg=dcfg, draft_params=dparams, gamma=gamma,
    )
    spec.start()
    try:
        run_load(spec)
        run_load(spec)
        before = spec.stats.snapshot()
        spec_tps, spec_ttft = run_load(spec)
        after = spec.stats.snapshot()
        spec_bits = collect_one(spec, prompts[0])
    finally:
        spec.stop()
    proposed = after["spec_proposed"] - before["spec_proposed"]
    accepted = after["spec_accepted"] - before["spec_accepted"]

    # Adaptive-gamma drill: random draft = acceptance floor.  The per-slot
    # EWMA must shrink gamma so the net cost vs spec-off stays bounded.
    rand = Scheduler(
        tcfg, tparams, **kw,
        draft_cfg=dcfg,
        draft_params=llama.init_params(dcfg, jax.random.PRNGKey(123)),
        gamma=gamma,
    )
    rand.start()
    try:
        run_load(rand)
        run_load(rand)
        rand_tps, _ = run_load(rand)
        rand_snap = rand.stats.snapshot()
    finally:
        rand.stop()

    return {
        "spec_serving_concurrency": c,
        "spec_serving_slots": slots,
        "spec_serving_tokens_per_sec": round(spec_tps, 1),
        "spec_serving_baseline_tokens_per_sec": round(plain_tps, 1),
        "spec_serving_speedup": round(spec_tps / max(plain_tps, 1e-9), 3),
        "spec_serving_ttft_p95_ms": round(spec_ttft, 1),
        "spec_serving_ttft_ratio": round(
            spec_ttft / max(plain_ttft, 1e-9), 3
        ),
        "spec_serving_accept_rate": round(accepted / max(proposed, 1), 4),
        "spec_serving_bit_identical": spec_bits == plain_bits,
        "spec_serving_adaptive_random_ratio": round(
            rand_tps / max(plain_tps, 1e-9), 3
        ),
        "spec_serving_random_gamma": rand_snap["spec_gamma"],
        "spec_serving_gamma": gamma,
        "spec_serving_final_loss": [round(x, 4) for x in losses],
    }


# Shared-prefix serving phase: the canonical RAG fan-out — many users, one
# system prompt + overlapping retrieved context.  A 1200-token shared
# prefix + 64-token unique question approximates the reference's 1500-token
# context budget with a per-user tail; decode kept short because the phase
# measures PREFILL reuse (TTFT), not decode throughput.
SHARED_PREFIX_LEN = 1200
SHARED_SUFFIX_LEN = 64
SHARED_REQS = 12
SHARED_MAX_LEN = 2048
SHARED_SLOTS = 8
SHARED_DECODE = 16
SHARED_PREFILL_CHUNK = 256


def bench_shared_prefix(params, cfg=None) -> dict:
    """Cross-request shared-prefix KV cache + chunked prefill phase.

    Two sub-measurements:

    1. **Prefix-cache TTFT**: the same shared-prefix workload runs twice —
       once with the prefix cache off (every request cold-prefills the
       full prompt) and once with the shared cache on (a seed request
       populates the radix-indexed segment; every later request grafts
       the 1200-token prefix and prefills only its 64-token suffix).
       Requests run closed-loop so each TTFT is pure prefill path, no
       queueing.
    2. **Chunked-prefill decode gap**: with one lane decoding steadily, a
       long cold prompt is admitted; the running lane's maximum
       inter-token gap is the latency cost of an admission — bounded by
       one prefill chunk + one decode chunk when chunking is on, vs the
       whole monolithic prefill when off.
    """
    import queue as _q
    import threading

    from generativeaiexamples_tpu.engine.sampler import SamplingParams
    from generativeaiexamples_tpu.engine.scheduler import Request, Scheduler
    from generativeaiexamples_tpu.models import llama

    if cfg is None:
        cfg = llama.llama3_8b(max_seq_len=SHARED_MAX_LEN, kv_dtype=KV_DTYPE)

    def run_phase(mode: str) -> tuple[list[float], dict]:
        sched = Scheduler(
            cfg,
            params=params,
            max_batch=SHARED_SLOTS,
            max_len=SHARED_MAX_LEN,
            decode_chunk_size=SERVING_CHUNK,
            seed=2,
            prefix_cache=mode,
            prefill_chunk_tokens=SHARED_PREFILL_CHUNK,
        )
        sched.start()
        rng = np.random.default_rng(13)
        prefix = rng.integers(0, cfg.vocab_size, (SHARED_PREFIX_LEN,)).tolist()
        ttfts: list[float] = []
        try:
            for i in range(SHARED_REQS + 1):
                suffix = rng.integers(
                    0, cfg.vocab_size, (SHARED_SUFFIX_LEN,)
                ).tolist()
                done: "_q.Queue[str]" = _q.Queue()
                state = {"first": None}

                def on_token(tid, state=state):
                    if state["first"] is None:
                        state["first"] = time.perf_counter()

                t0 = time.perf_counter()
                sched.submit(
                    Request(
                        token_ids=prefix + suffix,
                        sampling=SamplingParams(
                            temperature=0.0, max_tokens=SHARED_DECODE
                        ),
                        on_token=on_token,
                        on_done=done.put,
                        id=f"shared-{mode}-{i}",
                    )
                )
                done.get(timeout=600)
                if i > 0 and state["first"] is not None:
                    # Request 0 seeds the cache (and warms compile
                    # buckets for the cold phase) — excluded from both.
                    ttfts.append(state["first"] - t0)
            snap = sched.stats.snapshot()
        finally:
            sched.stop()
        return ttfts, snap

    cold_ttfts, cold_snap = run_phase("off")
    hit_ttfts, hit_snap = run_phase("shared")

    # Chunked-prefill probe: max inter-token gap of a running lane while a
    # long cold prompt admits in chunks.
    sched = Scheduler(
        cfg,
        params=params,
        max_batch=2,
        max_len=SHARED_MAX_LEN,
        decode_chunk_size=SERVING_CHUNK,
        seed=3,
        prefix_cache="off",
        prefill_chunk_tokens=SHARED_PREFILL_CHUNK,
    )
    sched.start()
    rng = np.random.default_rng(17)
    gap_ms = 0.0
    admit_ttft_ms = 0.0
    try:
        times: list[float] = []
        runner_done: "_q.Queue[str]" = _q.Queue()
        running = threading.Event()

        def on_runner_token(tid):
            times.append(time.perf_counter())
            running.set()

        sched.submit(
            Request(
                token_ids=rng.integers(0, cfg.vocab_size, (64,)).tolist(),
                sampling=SamplingParams(temperature=0.7, max_tokens=512),
                on_token=on_runner_token,
                on_done=runner_done.put,
                id="gap-runner",
            )
        )
        running.wait(timeout=600)
        long_done: "_q.Queue[str]" = _q.Queue()
        state = {"first": None}

        def on_long_token(tid, state=state):
            if state["first"] is None:
                state["first"] = time.perf_counter()

        t0 = time.perf_counter()
        sched.submit(
            Request(
                token_ids=rng.integers(
                    0, cfg.vocab_size, (LONG_PROMPT,)
                ).tolist(),
                sampling=SamplingParams(temperature=0.0, max_tokens=4),
                on_token=on_long_token,
                on_done=long_done.put,
                id="gap-long",
            )
        )
        long_done.get(timeout=600)
        t_first = state["first"] or time.perf_counter()
        admit_ttft_ms = (t_first - t0) * 1000
        window = [t for t in times if t0 <= t <= t_first]
        if len(window) >= 2:
            gap_ms = max(
                (b - a) * 1000 for a, b in zip(window, window[1:])
            )
        sched.cancel("gap-runner")
        runner_done.get(timeout=600)
    finally:
        sched.stop()

    def p50(xs: list[float]) -> float:
        return float(np.median(xs) * 1000) if xs else 0.0

    cold_p50 = p50(cold_ttfts)
    hit_p50 = p50(hit_ttfts)
    return {
        "shared_prefix_ttft_p50_ms": round(hit_p50, 1),
        "shared_prefix_cold_ttft_p50_ms": round(cold_p50, 1),
        "shared_prefix_speedup": round(cold_p50 / max(hit_p50, 1e-9), 2),
        "shared_prefix_hits": hit_snap["shared_prefix_hits"],
        "shared_prefix_tokens_reused": hit_snap["prefix_tokens_reused"],
        "shared_prefix_len": SHARED_PREFIX_LEN,
        "shared_prefix_suffix_len": SHARED_SUFFIX_LEN,
        "shared_prefix_reqs": SHARED_REQS,
        "prefill_chunk_tokens": SHARED_PREFILL_CHUNK,
        "prefill_chunks": hit_snap["prefill_chunks"]
        + cold_snap["prefill_chunks"],
        "chunked_prefill_admit_ttft_ms": round(admit_ttft_ms, 1),
        "chunked_prefill_max_decode_gap_ms": round(gap_ms, 1),
    }


# Replica-router phase: routing behavior is model-size-independent (it is
# host-side placement + the replica's own prefix cache), so the phase runs
# tiny-config replica pools like bench_spec_trained — measuring the POLICY
# delta (prefix-affinity hit-rate vs round-robin) and the failover-requeue
# latency, not raw token throughput.
ROUTER_REPLICAS = 2
ROUTER_PREFIX_LEN = 48
ROUTER_FAMILIES = 2
ROUTER_REQS = 14
ROUTER_DECODE = 3
ROUTER_MAX_LEN = 128
ROUTER_FAILOVER_REQS = 8


def bench_router(cfg=None) -> dict:
    """Replica pool + prefix-affinity router phase.

    Two sub-measurements over 2-replica pools:

    1. **Prefix-affinity hit-rate**: the same repeated-prefix workload
       (ROUTER_FAMILIES prompt families, submission order phase-shifted
       against a 2-replica rotation) runs under ``prefix`` and
       ``round_robin`` placement; the pool-wide shared-prefix hit counts
       quantify what cache-aware routing buys over blind spreading.
    2. **Failover requeue latency**: one replica's tick thread is killed
       with requests queued on it; the time from the health pass that
       detects the death to the last requeued request completing on the
       survivor is the client-visible failover cost.
    """
    import queue as _q

    from generativeaiexamples_tpu.engine.replica import EnginePool
    from generativeaiexamples_tpu.engine.sampler import SamplingParams
    from generativeaiexamples_tpu.engine.scheduler import Request, Scheduler
    from generativeaiexamples_tpu.models import llama

    if cfg is None:
        cfg = llama.llama_tiny(dtype="float32", max_seq_len=ROUTER_MAX_LEN)

    rng = np.random.default_rng(29)
    families = [
        rng.integers(0, cfg.vocab_size, (ROUTER_PREFIX_LEN,)).tolist()
        for _ in range(ROUTER_FAMILIES)
    ]
    # Phase-shifted family order (pairs swapped every two requests): a
    # 2-replica rotation alternates replicas per request, so round-robin
    # keeps landing each family on the replica parked with the OTHER one.
    order = [(i // 2 + i) % ROUTER_FAMILIES for i in range(ROUTER_REQS)]

    def run_policy(policy: str) -> tuple[int, list[float]]:
        pool = EnginePool(
            [
                Scheduler(
                    cfg,
                    max_batch=1,
                    max_len=ROUTER_MAX_LEN,
                    decode_chunk_size=4,
                    seed=5,
                    prefix_cache="shared",
                )
                for _ in range(ROUTER_REPLICAS)
            ],
            policy=policy,
            health_interval=None,
        )
        pool.start()
        ttfts: list[float] = []
        try:
            for i, fam in enumerate(order):
                prompt = families[fam] + [300 + i, 301 + i, 302 + i]
                done: "_q.Queue[str]" = _q.Queue()
                state = {"first": None}

                def on_token(tid, state=state):
                    if state["first"] is None:
                        state["first"] = time.perf_counter()

                t0 = time.perf_counter()
                pool.submit(
                    Request(
                        token_ids=prompt,
                        sampling=SamplingParams(
                            temperature=0.0, max_tokens=ROUTER_DECODE
                        ),
                        on_token=on_token,
                        on_done=done.put,
                        id=f"rt-{policy}-{i}",
                    )
                )
                done.get(timeout=300)
                if i >= ROUTER_FAMILIES and state["first"] is not None:
                    # Seed requests (one per family) warm caches and
                    # compile buckets — excluded from both policies.
                    ttfts.append(state["first"] - t0)
            hits = pool.stats.snapshot()["shared_prefix_hits"]
        finally:
            pool.stop()
        return hits, ttfts

    prefix_hits, prefix_ttfts = run_policy("prefix")
    rr_hits, rr_ttfts = run_policy("round_robin")

    # Failover: kill replica 0, queue requests onto it via round-robin
    # placement, then time the health pass + requeue + completion.
    pool = EnginePool(
        [
            Scheduler(
                cfg,
                max_batch=2,
                max_len=ROUTER_MAX_LEN,
                decode_chunk_size=4,
                seed=7,
                prefix_cache="off",
            )
            for _ in range(ROUTER_REPLICAS)
        ],
        policy="round_robin",
        health_interval=None,
    )
    pool.start()
    try:
        victim = pool.replicas[0]
        victim.scheduler.request_stop()
        victim.scheduler._thread.join(timeout=60)
        dones: "_q.Queue[str]" = _q.Queue()
        for i in range(ROUTER_FAILOVER_REQS):
            pool.submit(
                Request(
                    token_ids=[1 + (i % 7), 2, 3],
                    sampling=SamplingParams(temperature=0.0, max_tokens=3),
                    on_token=lambda t: None,
                    on_done=dones.put,
                    id=f"rt-fail-{i}",
                )
            )
        t0 = time.perf_counter()
        pool.check_replicas()
        reasons = [dones.get(timeout=300) for _ in range(ROUTER_FAILOVER_REQS)]
        failover_ms = (time.perf_counter() - t0) * 1000
        snap = pool.stats.snapshot()
        requeued = snap["router_requeued_total"]
        dropped = sum(1 for r in reasons if r not in ("length", "stop"))
    finally:
        pool.stop()

    def p50(xs: list[float]) -> float:
        return float(np.median(xs) * 1000) if xs else 0.0

    post_seed = ROUTER_REQS - ROUTER_FAMILIES
    return {
        "router_replicas": ROUTER_REPLICAS,
        "router_prefix_hits": prefix_hits,
        "router_round_robin_hits": rr_hits,
        "router_prefix_hit_rate": round(prefix_hits / post_seed, 3),
        "router_round_robin_hit_rate": round(rr_hits / post_seed, 3),
        "router_prefix_ttft_p50_ms": round(p50(prefix_ttfts), 1),
        "router_round_robin_ttft_p50_ms": round(p50(rr_ttfts), 1),
        "router_failover_requeue_ms": round(failover_ms, 1),
        "router_failover_requeued": requeued,
        "router_failover_dropped": dropped,  # contract: 0
        "router_note": (
            "tiny-config pools — the hit-rate delta and requeue latency "
            "are the transferable quantities; at 8B scale each hit saves "
            "a ~full-prompt prefill (see bench_shared_prefix)"
        ),
    }


def bench_long_context(params) -> dict:
    """Realistic-RAG offline profile: 1500-token prompts, 512 decode.

    Exercises what the 128/128 profile cannot: prefill at real context
    length (dense 1536 bucket) and decode attention over 1.5-2k KV
    windows, where the Pallas decode kernel's read-once streaming matters
    most.  Shares the already-quantized weights with the short profile.
    """
    import jax

    from generativeaiexamples_tpu.engine.generator import LlamaGenerator
    from generativeaiexamples_tpu.engine.sampler import SamplingParams
    from generativeaiexamples_tpu.models import llama

    cfg = llama.llama3_8b(max_seq_len=LONG_MAX_LEN, kv_dtype=KV_DTYPE)
    gen = LlamaGenerator(
        cfg,
        params=params,
        max_batch=LONG_BATCH,
        max_len=LONG_MAX_LEN,
        decode_chunk_size=64,
        seed=0,
        quantize=False,  # params arrive already int8 + packed
        pack=False,
        prefill_chunk=8,
    )
    rng = np.random.default_rng(7)
    prompts = [
        rng.integers(0, cfg.vocab_size, (LONG_PROMPT,)).tolist()
        for _ in range(LONG_BATCH)
    ]
    sp = SamplingParams(temperature=0.7, top_p=0.9, max_tokens=LONG_DECODE)
    gen.generate(prompts, sp)  # warm/compile all buckets
    best = 0.0
    for _ in range(2):
        t0 = time.perf_counter()
        results = gen.generate(prompts, sp)
        elapsed = time.perf_counter() - t0
        tokens = sum(len(r.token_ids) for r in results)
        best = max(best, tokens / elapsed)
    # Long-prompt TTFT: single 1500-token prefill to first token.
    ttfts = []
    for _ in range(3):
        t0 = time.perf_counter()
        gen.generate(
            [prompts[0]], SamplingParams(temperature=0.0, max_tokens=1)
        )
        ttfts.append(time.perf_counter() - t0)
    del gen
    return {
        "long_tokens_per_sec": round(best, 1),
        "long_vs_baseline": round(best / A100_TRTLLM_LONG_TOKS, 3),
        "long_baseline_tokens_per_sec": A100_TRTLLM_LONG_TOKS,
        "long_baseline_note": "estimated A100 TRT-LLM at ISL1500/OSL512 "
        "(0.8x the 128/128 figure; no public number for this profile)",
        "long_batch": LONG_BATCH,
        "long_prompt_len": LONG_PROMPT,
        "long_decode_steps": LONG_DECODE,
        "long_max_len": LONG_MAX_LEN,
        "long_ttft_p50_ms": round(float(np.median(ttfts) * 1000), 1),
    }


def _embed_fixture():
    """WordPiece tokenizer fixture + ~128-token docs.

    Approximates arctic-embed-l serving (bert-base-uncased WordPiece,
    ``engine/tokenizer.py``): most corpus words are whole-vocab tokens,
    ~10% split into ## continuation pieces, so chars/token and the
    longest-match host cost are realistic.
    """
    import random as _random

    from generativeaiexamples_tpu.engine.tokenizer import WordPieceTokenizer

    words = (
        "the of and to in a is that for it as was with be by on not he "
        "this are or his from at which but have an they you were her she "
        "all would there been one so can more if no man out other what "
        "time up go about than into could state only new year some take "
        "come these know see use get like then first any work now may "
        "such give over think most even find day also after way many must "
        "look before great back through long where much should well people "
        "down own just because good each those feel seem how high too "
        "place little world very still nation hand old life tell write "
        "become here show house both between need mean call develop under "
        "last right move thing general school never same another begin "
        "while number part turn real leave might want point form off child "
        "few small since against ask late home interest large person end "
        "open public follow during present without again hold govern "
        "retrieval augmented generation embedding vector search pipeline "
        "index document query context tokens model attention transformer"
    ).split()
    specials = ["[PAD]", "[CLS]", "[SEP]", "[UNK]", "[MASK]"]
    chars = [chr(c) for c in range(ord("a"), ord("z") + 1)] + list("0123456789")
    vocab_tokens = (
        specials
        + chars
        + ["##" + c for c in chars]
        + ["##ing", "##ed", "##tion", "##s", "##er", "##ly", "##ment"]
        # ~90% of corpus words are whole tokens; the rest exercise the
        # longest-match subword loop.
        + [w for i, w in enumerate(words) if i % 10 != 0]
    )
    vocab = {t: i for i, t in enumerate(dict.fromkeys(vocab_tokens))}
    tok = WordPieceTokenizer(vocab)
    rng = _random.Random(3)
    docs = [
        " ".join(rng.choice(words) for _ in range(105)) + f" doc {i}"
        for i in range(256)
    ]
    return tok, docs


# End-to-end RAG retrieval phase (embed -> search [-> rerank]) —
# cross-request micro-batching vs the per-request path.  Corpus vectors are
# synthesized directly (ingest is not the measured path); queries run the
# real TPUEmbedder forward + one corpus matmul per dispatch.  Concurrency
# levels follow the serving north star: 1 (idle-latency floor), 32
# (moderate fan-in), 128 (the replica pool's aggregate request pressure).
RAG_CORPUS_DOCS = 8192
RAG_TOP_K = 4
RAG_CONCURRENCY = (1, 32, 128)
RAG_REQS_PER_CLIENT = 8  # closed-loop requests per worker thread
RAG_MAX_BATCH = 128
RAG_MAX_WAIT_MS = 3.0


def bench_rag(embedder=None, store=None) -> dict:
    """Retrieval QPS + p50/p95 latency at concurrency {1, 32, 128},
    micro-batched vs unbatched.

    The unbatched mode is the pre-round-8 hot path: every request pays
    its own batch-1 embed forward and batch-1 corpus matmul.  The batched
    mode funnels the same closed-loop clients through a ``MicroBatcher``
    over ``Retriever.retrieve_many``, so concurrent requests share
    bucketed device dispatches; the dispatch counts land in the artifact
    (``rag_batched_dispatches``) next to the request counts, making the
    O(N) -> O(batches) claim checkable from the numbers alone.
    """
    import threading

    from generativeaiexamples_tpu.engine.microbatch import MicroBatcher
    from generativeaiexamples_tpu.retrieval.base import Chunk
    from generativeaiexamples_tpu.retrieval.retriever import Retriever

    if embedder is None:
        from generativeaiexamples_tpu.engine.embedder import TPUEmbedder

        wp_tok, _ = _embed_fixture()
        # Embed batch sized to the micro-batcher cap: a full coalesced
        # batch is then ONE BERT forward (one dispatch), and a lone query
        # pads to the same fixed program — batch-dim padding is ~free on
        # the MXU, which is the embedder's fixed-batch discipline anyway.
        embedder = TPUEmbedder(
            batch_size=RAG_MAX_BATCH, tokenizer=wp_tok
        )
    if store is None:
        from generativeaiexamples_tpu.retrieval.tpu import TPUVectorStore

        store = TPUVectorStore(
            embedder.dimensions, max_query_batch=RAG_MAX_BATCH
        )
    if len(store) == 0:
        rng = np.random.default_rng(23)
        vecs = rng.standard_normal(
            (RAG_CORPUS_DOCS, embedder.dimensions)
        ).astype(np.float32)
        vecs /= np.linalg.norm(vecs, axis=1, keepdims=True)
        store.add(
            [
                Chunk(text=f"corpus passage {i}", source=f"doc{i % 64}.txt")
                for i in range(RAG_CORPUS_DOCS)
            ],
            vecs.tolist(),
        )
    retriever = Retriever(
        store=store, embedder=embedder, top_k=RAG_TOP_K,
        score_threshold=-1e30,
    )
    query_words = (
        "retrieval augmented generation embedding vector search pipeline "
        "index document query context tokens model attention transformer "
        "serving latency throughput batch"
    ).split()
    import random as _random

    qrng = _random.Random(11)
    queries = [
        " ".join(qrng.choice(query_words) for _ in range(12))
        for _ in range(256)
    ]

    def run_level(conc: int, batched: bool):
        batcher = (
            MicroBatcher(
                lambda qs: retriever.retrieve_many(qs, top_k=RAG_TOP_K),
                max_batch=RAG_MAX_BATCH,
                max_wait_ms=RAG_MAX_WAIT_MS,
                name="bench-rag",
            )
            if batched
            else None
        )
        lock = threading.Lock()
        lats: list[float] = []
        start_gate = threading.Barrier(conc + 1)

        def worker(wid: int) -> None:
            start_gate.wait()
            for j in range(RAG_REQS_PER_CLIENT):
                q = queries[(wid * RAG_REQS_PER_CLIENT + j) % len(queries)]
                t0 = time.perf_counter()
                if batcher is not None:
                    hits = batcher.call(q)
                else:
                    hits = retriever.retrieve(q, top_k=RAG_TOP_K)
                dt = time.perf_counter() - t0
                with lock:
                    lats.append(dt)
                if not hits:
                    raise AssertionError("empty retrieval result")

        threads = [
            threading.Thread(target=worker, args=(w,), daemon=True)
            for w in range(conc)
        ]
        for t in threads:
            t.start()
        start_gate.wait()
        t_start = time.perf_counter()
        for t in threads:
            t.join(timeout=600)
        elapsed = time.perf_counter() - t_start
        n = conc * RAG_REQS_PER_CLIENT
        dispatches = (
            batcher.stats.snapshot()["batches_total"]
            if batcher is not None
            else n
        )
        if batcher is not None:
            batcher.close()
        lats.sort()
        return {
            "qps": n / max(elapsed, 1e-9),
            "p50_ms": lats[len(lats) // 2] * 1000 if lats else 0.0,
            "p95_ms": lats[int(len(lats) * 0.95)] * 1000 if lats else 0.0,
            "dispatches": dispatches,
            "requests": n,
        }

    # Warm every compile bucket both modes can hit (embed length buckets,
    # search query-batch buckets) outside the timed windows.
    retriever.retrieve_many(queries[:RAG_MAX_BATCH], top_k=RAG_TOP_K)
    retriever.retrieve(queries[0], top_k=RAG_TOP_K)

    out: dict = {
        "rag_corpus_docs": len(store),
        "rag_top_k": RAG_TOP_K,
        "rag_concurrency": list(RAG_CONCURRENCY),
        "rag_max_batch": RAG_MAX_BATCH,
        "rag_max_wait_ms": RAG_MAX_WAIT_MS,
    }
    for key in (
        "rag_qps_batched", "rag_qps_unbatched",
        "rag_p50_ms_batched", "rag_p95_ms_batched",
        "rag_p50_ms_unbatched", "rag_p95_ms_unbatched",
        "rag_batched_dispatches", "rag_requests",
    ):
        out[key] = []
    for conc in RAG_CONCURRENCY:
        unb = run_level(conc, batched=False)
        bat = run_level(conc, batched=True)
        out["rag_qps_unbatched"].append(round(unb["qps"], 1))
        out["rag_qps_batched"].append(round(bat["qps"], 1))
        out["rag_p50_ms_unbatched"].append(round(unb["p50_ms"], 1))
        out["rag_p95_ms_unbatched"].append(round(unb["p95_ms"], 1))
        out["rag_p50_ms_batched"].append(round(bat["p50_ms"], 1))
        out["rag_p95_ms_batched"].append(round(bat["p95_ms"], 1))
        out["rag_batched_dispatches"].append(bat["dispatches"])
        out["rag_requests"].append(bat["requests"])
    # Headline scalars: the acceptance quantities at the top concurrency.
    out["rag_qps_batched_cmax"] = out["rag_qps_batched"][-1]
    out["rag_qps_unbatched_cmax"] = out["rag_qps_unbatched"][-1]
    out["rag_batch_speedup_cmax"] = round(
        out["rag_qps_batched"][-1] / max(out["rag_qps_unbatched"][-1], 1e-9),
        2,
    )
    # p95 at max concurrency vs the concurrency-1 p50 (both batched): the
    # "batching must not melt tail latency" acceptance ratio.
    out["rag_p95_cmax_vs_c1_p50"] = round(
        out["rag_p95_ms_batched"][-1]
        / max(out["rag_p50_ms_batched"][0], 1e-9),
        2,
    )
    return out


# Bulk-ingestion phase (round-9 lever): staged parse→embed→append pipeline
# vs the serial per-doc loop, incremental O(new-rows) store sync vs
# rebuild-per-insert, and search availability during a concurrent bulk
# ingest.  The phase measures PIPELINE mechanics, not raw BERT throughput
# (the embed phase above owns that), so it runs a small-geometry encoder
# on every platform and CPU-friendly store dtype.
INGEST_DOCS = 128  # files for the bulk-vs-serial comparison
INGEST_WORDS = 400  # ~7-9 chunks per doc at the 400-char splitter
INGEST_PARSE_WORKERS = 4
INGEST_EMBED_BATCH = 64  # chunks per coalesced embed dispatch
INGEST_TTS_CORPUS = (16384, 65536)  # corpus sizes M for time-to-searchable
INGEST_TTS_APPEND = 256  # rows N appended (N << M)
INGEST_CONCURRENT_SECONDS = 2.0  # search window during concurrent ingest


def bench_ingest(embedder=None) -> dict:
    """Bulk ingestion + incremental index sync phase.

    Three measurements, old path vs new:
      (a) docs/sec — the staged pipeline (parse pool overlapped with one
          embed dispatcher feeding coalesced pow2-bucketed forwards,
          chunked appends) vs the serial per-upload loop (load → split →
          per-doc embed → add), same splitter/embedder/store.
      (b) time-to-searchable — first search latency after appending N
          rows to a corpus of M >> N, incremental tail sync vs full
          rebuild, across corpus sizes (the O(new rows) vs O(corpus)
          claim: the incremental column must stay ~flat in M).
      (c) search p95 during a concurrent bulk ingest — incremental sync
          vs rebuild-per-insert (availability: no full-rebuild stall).
    """
    import tempfile
    import threading

    from generativeaiexamples_tpu.ingest.loaders import load_document
    from generativeaiexamples_tpu.ingest.pipeline import IngestPipeline
    from generativeaiexamples_tpu.ingest.splitters import (
        RecursiveCharacterSplitter,
    )
    from generativeaiexamples_tpu.retrieval.base import Chunk
    from generativeaiexamples_tpu.retrieval.tpu import TPUVectorStore

    import logging as _logging

    import jax

    # Loader INFO lines cost ~10 ms each through a piped stdout — real
    # measurement noise at one line per document.
    _logging.getLogger(
        "generativeaiexamples_tpu.ingest.loaders"
    ).setLevel(_logging.WARNING)

    platform = jax.devices()[0].platform
    store_dtype = "float32" if platform == "cpu" else "bfloat16"
    fixed_embedder = None
    if embedder is None:
        from generativeaiexamples_tpu.engine.embedder import TPUEmbedder
        from generativeaiexamples_tpu.models import bert

        wp_tok, _ = _embed_fixture()
        bcfg = bert.bert_tiny(d_model=256)
        embedder = TPUEmbedder(
            bcfg, batch_size=INGEST_EMBED_BATCH, tokenizer=wp_tok,
        )
        # The TRUE pre-round-9 serial path: fixed-batch padding (every
        # per-doc call pays a full batch_size forward).  Shares params so
        # only the padding policy differs.
        fixed_embedder = TPUEmbedder(
            bcfg, embedder.params, batch_size=INGEST_EMBED_BATCH,
            tokenizer=wp_tok, bucket_batch=False,
        )
    dim = embedder.dimensions
    splitter = RecursiveCharacterSplitter(chunk_size=400, chunk_overlap=0)

    import random as _random

    rng = _random.Random(17)
    words = (
        "retrieval augmented generation embedding vector search pipeline "
        "index document query context tokens model attention transformer "
        "serving latency throughput batch ingest corpus chunk split"
    ).split()

    out: dict = {
        "ingest_docs": INGEST_DOCS,
        "ingest_embed_batch": INGEST_EMBED_BATCH,
        "ingest_parse_workers": INGEST_PARSE_WORKERS,
    }

    with tempfile.TemporaryDirectory() as tmp:
        files = []
        for i in range(INGEST_DOCS):
            path = os.path.join(tmp, f"doc{i}.txt")
            with open(path, "w") as f:
                f.write(
                    " ".join(rng.choice(words) for _ in range(INGEST_WORDS))
                    + f" marker doc {i}"
                )
            files.append((path, f"doc{i}.txt"))

        def parse(path, name):
            return [
                Chunk(text=t, source=name)
                for t in splitter.split(load_document(path))
            ]

        # Warm EVERY embed batch bucket both paths can hit, outside the
        # timed windows (a cold batch-64 compile inside the bulk window
        # would swamp the measurement).
        warm_text = " ".join(rng.choice(words) for _ in range(12))
        b = 4
        while b <= INGEST_EMBED_BATCH:
            embedder.embed_documents([warm_text] * b)
            b *= 2
        embedder.embed_documents([warm_text])
        if fixed_embedder is not None:
            fixed_embedder.embed_documents([warm_text])

        # (a) serial per-doc loop with the round-9 bucketed embedder
        # (conservative baseline: the bucketing satellite already sped
        # the serial path up).
        serial_store = TPUVectorStore(dim, dtype=store_dtype)
        t0 = time.perf_counter()
        for path, name in files:
            chunks = parse(path, name)
            embs = embedder.embed_documents([c.text for c in chunks])
            serial_store.add(chunks, embs)
        serial_store.search([0.0] * dim, 1)  # searchable = synced
        serial_s = time.perf_counter() - t0

        # (a) serial loop exactly as shipped before round 9: per-doc
        # fixed-batch forwards.
        fixed_s = None
        if fixed_embedder is not None:
            fixed_store = TPUVectorStore(dim, dtype=store_dtype)
            t0 = time.perf_counter()
            for path, name in files:
                chunks = parse(path, name)
                embs = fixed_embedder.embed_documents(
                    [c.text for c in chunks]
                )
                fixed_store.add(chunks, embs)
            fixed_store.search([0.0] * dim, 1)
            fixed_s = time.perf_counter() - t0

        # (a) staged bulk pipeline, same components.
        bulk_store = TPUVectorStore(dim, dtype=store_dtype)
        pipe = IngestPipeline(
            parse_fn=parse,
            embed_fn=embedder.embed_documents,
            append_fn=bulk_store.add,
            parse_workers=INGEST_PARSE_WORKERS,
            embed_batch_chunks=INGEST_EMBED_BATCH,
        )
        t0 = time.perf_counter()
        job = pipe.submit(files)
        snap = pipe.wait(job, timeout=600)
        bulk_store.search([0.0] * dim, 1)
        bulk_s = time.perf_counter() - t0
        pipe.close()
        if snap["files_failed"] or len(bulk_store) != len(serial_store):
            raise AssertionError(f"bulk ingest diverged: {snap}")
    out.update(
        {
            "ingest_serial_docs_per_sec": round(INGEST_DOCS / serial_s, 1),
            "ingest_bulk_docs_per_sec": round(INGEST_DOCS / bulk_s, 1),
            "ingest_chunks": len(bulk_store),
        }
    )
    if fixed_s is not None:
        # Headline speedup: bulk pipeline vs the ACTUAL pre-round-9
        # serial path (fixed-batch per-doc embeds).
        out["ingest_serial_fixed_docs_per_sec"] = round(
            INGEST_DOCS / fixed_s, 1
        )
        out["ingest_bulk_speedup"] = round(fixed_s / bulk_s, 2)
        out["ingest_bulk_speedup_vs_bucketed_serial"] = round(
            serial_s / bulk_s, 2
        )
    else:
        out["ingest_bulk_speedup"] = round(serial_s / bulk_s, 2)

    # (b) time-to-searchable after appending N rows to M >> N.
    nrng = np.random.default_rng(29)
    qvec = nrng.standard_normal(dim).astype(np.float32)

    def synth(n, seed):
        v = np.random.default_rng(seed).standard_normal((n, dim)).astype(
            np.float32
        )
        v /= np.linalg.norm(v, axis=1, keepdims=True)
        return v

    def tts(M, incremental):
        store = TPUVectorStore(dim, dtype=store_dtype,
                               incremental=incremental)
        store.add(
            [Chunk(text=f"r{i}", source="base") for i in range(M)],
            synth(M, 5),
        )
        store.search(qvec, 10)  # initial sync + compile
        # Two warm append cycles outside the timed window: the first may
        # trigger a capacity-doubling rebuild (M is a power of two, so
        # the corpus sits exactly at capacity), the second compiles the
        # append-slice program against the settled buffers.
        for warm_i in (61, 62):
            store.add(
                [Chunk(text=f"w{warm_i}_{i}", source="warm")
                 for i in range(INGEST_TTS_APPEND)],
                synth(INGEST_TTS_APPEND, warm_i),
            )
            store.search(qvec, 10)
        steady = []
        for _ in range(3):
            t0 = time.perf_counter()
            store.search(qvec, 10)
            steady.append(time.perf_counter() - t0)
        new = synth(INGEST_TTS_APPEND, 7)
        store.add(
            [Chunk(text=f"n{i}", source="new")
             for i in range(INGEST_TTS_APPEND)],
            new,
        )
        t0 = time.perf_counter()
        hits = store.search(new[0].tolist(), 10)
        dt = time.perf_counter() - t0
        assert hits and hits[0].chunk.text == "n0"
        return dt * 1000, float(np.median(steady) * 1000)

    out["ingest_tts_corpus"] = list(INGEST_TTS_CORPUS)
    out["ingest_tts_append_rows"] = INGEST_TTS_APPEND
    for mode, incremental in (
        ("incremental", True),
        ("rebuild", False),
    ):
        col, steady_col, sync_col = [], [], []
        for M in INGEST_TTS_CORPUS:
            dt, steady = tts(M, incremental)
            col.append(round(dt, 2))
            steady_col.append(round(steady, 2))
            # The sync cost proper: first-search-after-append minus the
            # steady search (the matmul itself scales with M either way).
            sync_col.append(round(max(dt - steady, 0.0), 2))
        out[f"ingest_tts_ms_{mode}"] = col
        out[f"ingest_steady_search_ms_{mode}"] = steady_col
        out[f"ingest_sync_ms_{mode}"] = sync_col
        # Scaling across the corpus sweep: ~1.0 = flat in M (the O(new
        # rows) claim); the rebuild column scales with the corpus.
        out[f"ingest_sync_scaling_{mode}"] = round(
            sync_col[-1] / max(sync_col[0], 1e-9), 2
        )

    # (c) search availability during a concurrent bulk ingest.
    def p95_during_ingest(incremental):
        M = INGEST_TTS_CORPUS[0]
        store = TPUVectorStore(dim, dtype=store_dtype,
                               incremental=incremental)
        store.add(
            [Chunk(text=f"r{i}", source="base") for i in range(M)],
            synth(M, 11),
        )
        store.search(qvec, 10)
        stop = threading.Event()
        appended = [0]

        def writer():
            seed = 100
            while not stop.is_set():
                store.add(
                    [Chunk(text=f"w{seed}_{i}", source=f"s{seed}")
                     for i in range(256)],
                    synth(256, seed),
                )
                appended[0] += 256
                seed += 1
                time.sleep(0.005)

        t = threading.Thread(target=writer, daemon=True)
        lats = []
        t.start()
        t_end = time.monotonic() + INGEST_CONCURRENT_SECONDS
        try:
            while time.monotonic() < t_end:
                t0 = time.perf_counter()
                store.search(qvec, 10)
                lats.append(time.perf_counter() - t0)
        finally:
            stop.set()
            t.join(10)
        lats.sort()
        return (
            lats[int(len(lats) * 0.95)] * 1000,
            lats[len(lats) // 2] * 1000,
            appended[0],
        )

    p95_inc, p50_inc, rows_inc = p95_during_ingest(True)
    p95_reb, p50_reb, rows_reb = p95_during_ingest(False)
    out.update(
        {
            "ingest_search_p95_ms_during_bulk": round(p95_inc, 2),
            "ingest_search_p50_ms_during_bulk": round(p50_inc, 2),
            "ingest_search_p95_ms_during_bulk_rebuild": round(p95_reb, 2),
            "ingest_rows_during_window": rows_inc,
            "ingest_rows_during_window_rebuild": rows_reb,
        }
    )
    return out


# Quantized-search phase (round-10 lever): full-width scan vs int8 vs PQ
# two-stage rescored top-k on the exact TPU store.  Measures search
# p50/p95, analytic scanned bytes/query, the effective scan bandwidth
# those two imply, and recall@10 against the full-width results.  The
# corpus is CLUSTERED (k-means-friendly, like real embeddings) — on iid
# Gaussian data PQ codebooks have nothing to learn and the recall number
# would be meaninglessly pessimistic.
QUANT_ROWS = tuple(
    int(x)
    for x in os.environ.get("GAIE_QUANT_ROWS", "100000,1000000").split(",")
)
QUANT_DIM = int(os.environ.get("GAIE_QUANT_DIM", "384"))
QUANT_QUERIES = int(os.environ.get("GAIE_QUANT_QUERIES", "32"))
QUANT_TOPK = 10
QUANT_PQ_M = 16  # 384/16 = 24-dim subspaces
# Cluster SIZE (~64 rows) is held fixed as the corpus grows, not cluster
# count: a fixed count makes clusters into blobs of near-duplicate rows
# whose PQ codes all collide, and stage-1 recall degenerates to
# k2/cluster_size -- an artifact of the synthetic corpus, not the
# quantizer (real 1M-row corpora have far more than 1k topics).
QUANT_CLUSTER_ROWS = 64


def bench_quant(
    rows: Sequence[int] = QUANT_ROWS,
    dim: int = QUANT_DIM,
    n_queries: int = QUANT_QUERIES,
) -> dict:
    """Search latency + scanned-bytes comparison across quantization
    modes at each corpus size.  Tiny-arg invocations (tests) exercise the
    same code path in seconds."""
    import gc

    import jax

    from generativeaiexamples_tpu.retrieval.base import Chunk
    from generativeaiexamples_tpu.retrieval.tpu import TPUVectorStore

    platform = jax.devices()[0].platform
    store_dtype = "float32" if platform == "cpu" else "bfloat16"
    out: dict = {
        "quant_rows": list(rows),
        "quant_dim": dim,
        "quant_topk": QUANT_TOPK,
        "quant_pq_m": QUANT_PQ_M,
        "quant_platform": platform,
    }
    rng = np.random.default_rng(23)
    modes = (
        ("bf16", dict(quantization="none")),
        ("int8", dict(quantization="int8", rescore_multiplier=4)),
        (
            "pq",
            dict(
                quantization="pq",
                pq_m=QUANT_PQ_M,
                rescore_multiplier=8,
            ),
        ),
    )
    cols: dict = {
        f"quant_{k}_{m}": []
        for m, _ in modes
        for k in ("p50_ms", "p95_ms", "scanned_mb", "gbps", "recall10")
    }
    for n in rows:
        nc = max(n // QUANT_CLUSTER_ROWS, 1)
        centers = rng.standard_normal((nc, dim)).astype(np.float32) * 3.0
        assign = rng.integers(0, nc, size=n)
        vecs = centers[assign] + rng.standard_normal((n, dim)).astype(
            np.float32
        )
        vecs /= np.linalg.norm(vecs, axis=1, keepdims=True)
        chunks = [Chunk(text=f"r{i}", source="corpus") for i in range(n)]
        qidx = rng.integers(0, nc, size=n_queries)
        queries = centers[qidx] + 0.3 * rng.standard_normal(
            (n_queries, dim)
        ).astype(np.float32)
        queries /= np.linalg.norm(queries, axis=1, keepdims=True)
        truth: list[set] = []
        for mode, kw in modes:
            store = TPUVectorStore(dim, dtype=store_dtype, **kw)
            store.add(chunks, vecs)
            store.search(queries[0].tolist(), QUANT_TOPK)  # sync+compile
            lats, hits = [], 0
            for q in queries:
                t0 = time.perf_counter()
                got = store.search(q.tolist(), QUANT_TOPK)
                lats.append(time.perf_counter() - t0)
                ids = {s.chunk.id for s in got}
                if mode == "bf16":
                    truth.append(ids)
                else:
                    hits += len(ids & truth[len(lats) - 1])
            lats.sort()
            p50 = lats[len(lats) // 2]
            p95 = lats[int(len(lats) * 0.95)]
            scanned = store.scanned_bytes_per_query(QUANT_TOPK)
            cols[f"quant_p50_ms_{mode}"].append(round(p50 * 1000, 3))
            cols[f"quant_p95_ms_{mode}"].append(round(p95 * 1000, 3))
            cols[f"quant_scanned_mb_{mode}"].append(
                round(scanned / 1e6, 3)
            )
            cols[f"quant_gbps_{mode}"].append(round(scanned / p50 / 1e9, 2))
            cols[f"quant_recall10_{mode}"].append(
                1.0
                if mode == "bf16"
                else round(hits / (n_queries * QUANT_TOPK), 4)
            )
            del store
            gc.collect()
        del vecs, chunks
        gc.collect()
    out.update(cols)
    # Headline scalars at the LARGEST corpus: the acceptance ratios
    # (compressed scan bytes vs full-width) and the latency win.
    b = out["quant_scanned_mb_bf16"][-1]
    out["quant_int8_bytes_ratio"] = round(
        out["quant_scanned_mb_int8"][-1] / b, 4
    )
    out["quant_pq_bytes_ratio"] = round(
        out["quant_scanned_mb_pq"][-1] / b, 4
    )
    out["quant_int8_speedup"] = round(
        out["quant_p50_ms_bf16"][-1]
        / max(out["quant_p50_ms_int8"][-1], 1e-9),
        2,
    )
    out["quant_pq_speedup"] = round(
        out["quant_p50_ms_bf16"][-1]
        / max(out["quant_p50_ms_pq"][-1], 1e-9),
        2,
    )
    out["quant_recall10_int8_final"] = out["quant_recall10_int8"][-1]
    out["quant_recall10_pq_final"] = out["quant_recall10_pq"][-1]
    return out


# Sharded-fabric phase (round-20 lever): the scatter-gather retrieval
# fabric vs a single exact store on the SAME clustered corpus.  Gates:
# exact-mode merge BIT-IDENTICAL to the unsharded scan, recall@10 >= 0.95
# for int8 and PQ-cold-tier collections at bench scale, host cold-tier
# scan bytes <= 0.15x what those rows would cost as full-width HBM scans,
# and search p95 under concurrent bulk ingest into a SIBLING collection
# <= 2x the clean p95 (tenant isolation, not just correctness).
SHARD_ROWS = int(os.environ.get("GAIE_SHARD_ROWS", "1000000"))
SHARD_DIM = int(os.environ.get("GAIE_SHARD_DIM", "96"))
SHARD_QUERIES = int(os.environ.get("GAIE_SHARD_QUERIES", "32"))
SHARD_TOPK = 10
SHARD_NUM = int(os.environ.get("GAIE_SHARD_NUM", "4"))
SHARD_PQ_M = 16  # 96/16 = 6-dim subspaces
SHARD_INGEST_BATCH = 2048  # sibling-collection ingest batch while serving


def bench_shard(
    rows: int = None,
    dim: int = None,
    n_queries: int = None,
    num_shards: int = None,
) -> dict:
    """Sharded scatter-gather fabric: merge exactness, quantized recall,
    cold-tier byte split, and p95 isolation under sibling-collection
    ingest.  Tiny-arg invocations (tests) exercise the same code path in
    seconds."""
    import gc
    import threading

    import jax

    from generativeaiexamples_tpu.retrieval.base import Chunk
    from generativeaiexamples_tpu.retrieval.fabric import (
        CollectionManager,
        ShardedVectorStore,
    )
    from generativeaiexamples_tpu.retrieval.memory import MemoryVectorStore
    from generativeaiexamples_tpu.retrieval.tpu import TPUVectorStore

    rows = rows or SHARD_ROWS
    dim = dim or SHARD_DIM
    n_queries = n_queries or SHARD_QUERIES
    num_shards = num_shards or SHARD_NUM
    top_k = SHARD_TOPK
    platform = jax.devices()[0].platform
    store_dtype = "float32" if platform == "cpu" else "bfloat16"
    out: dict = {
        "shard_rows": rows,
        "shard_dim": dim,
        "shard_num": num_shards,
        "shard_topk": top_k,
        "shard_pq_m": SHARD_PQ_M,
        "shard_platform": platform,
    }
    rng = np.random.default_rng(37)
    # Clustered corpus, same construction as bench_quant (PQ codebooks
    # need structure to learn; iid Gaussian rows would be meaninglessly
    # pessimistic).
    nc = max(rows // QUANT_CLUSTER_ROWS, 1)
    centers = rng.standard_normal((nc, dim)).astype(np.float32) * 3.0
    assign = rng.integers(0, nc, size=rows)
    vecs = centers[assign] + rng.standard_normal((rows, dim)).astype(
        np.float32
    )
    vecs /= np.linalg.norm(vecs, axis=1, keepdims=True)
    chunks = [Chunk(text=f"r{i}", source="corpus") for i in range(rows)]
    qidx = rng.integers(0, nc, size=n_queries)
    queries = centers[qidx] + 0.3 * rng.standard_normal(
        (n_queries, dim)
    ).astype(np.float32)
    queries /= np.linalg.norm(queries, axis=1, keepdims=True)

    def _measure(store) -> tuple[list[list], float, float]:
        results, lats = [], []
        store.search(queries[0].tolist(), top_k)  # warm/compile
        for q in queries:
            t0 = time.perf_counter()
            got = store.search(q.tolist(), top_k)
            lats.append(time.perf_counter() - t0)
            results.append(got)
        lats.sort()
        p50 = lats[len(lats) // 2] * 1000
        p95 = lats[int(len(lats) * 0.95)] * 1000
        return results, round(p50, 3), round(p95, 3)

    # 1) Unsharded exact baseline: ground truth AND the latency bar the
    # fan-out merge is compared against.
    base = MemoryVectorStore(dim)
    base.add(chunks, vecs)
    base_res, base_p50, base_p95 = _measure(base)
    truth = [{s.chunk.id for s in got} for got in base_res]
    out["shard_base_p50_ms"] = base_p50
    out["shard_base_p95_ms"] = base_p95

    # 2) Exact fabric: the merged top-k must be BIT-IDENTICAL to the
    # single-store scan (ids and scores), not merely high-recall.
    fab = ShardedVectorStore(dim, num_shards=num_shards)
    fab.add(chunks, vecs)
    fab_res, p50, p95 = _measure(fab)
    identical = all(
        [s.chunk.id for s in got] == [s.chunk.id for s in ref]
        and all(
            abs(a.score - b.score) < 1e-6 for a, b in zip(got, ref)
        )
        for got, ref in zip(fab_res, base_res)
    )
    out["shard_exact_p50_ms"] = p50
    out["shard_exact_p95_ms"] = p95
    out["shard_exact_bit_identical"] = bool(identical)

    # 3) p95 isolation: keep serving the exact fabric while a sibling
    # collection takes bulk ingest on another thread.  The fabric's
    # fan-out workers and the sibling's appends contend for the host;
    # the gate is p95(under ingest) <= 2x p95(clean).
    manager = CollectionManager(
        lambda name, ov: MemoryVectorStore(dim), max_collections=8
    )
    manager.create("sibling")
    stop = threading.Event()
    ingested = [0]

    def _ingest_loop() -> None:
        b = 0
        while not stop.is_set():
            lo = (b * SHARD_INGEST_BATCH) % rows
            hi = min(lo + SHARD_INGEST_BATCH, rows)
            manager.add(
                "sibling",
                [
                    Chunk(text=f"s{b}_{i}", source=f"bulk{b}")
                    for i in range(hi - lo)
                ],
                vecs[lo:hi],
            )
            ingested[0] += hi - lo
            b += 1

    t = threading.Thread(target=_ingest_loop, daemon=True)
    t.start()
    try:
        _, _, p95_under = _measure(fab)
    finally:
        stop.set()
        t.join(timeout=30)
    out["shard_ingest_rows_during_window"] = ingested[0]
    out["shard_p95_under_ingest_ms"] = p95_under
    out["shard_p95_under_ingest_ratio"] = round(
        p95_under / max(p95, 1e-9), 3
    )
    manager.close()
    fab.close()
    del fab, fab_res, base, base_res
    gc.collect()

    # 4) int8 fabric collection: per-shard quantized stores, fabric-level
    # oversampled merge; recall@10 against the exact truth.
    fab8 = ShardedVectorStore(
        dim,
        num_shards=num_shards,
        shard_factory=lambda i: TPUVectorStore(
            dim, dtype=store_dtype, quantization="int8",
            rescore_multiplier=4,
        ),
    )
    fab8.add(chunks, vecs)
    res8, p50, p95 = _measure(fab8)
    hits = sum(
        len({s.chunk.id for s in got} & t) for got, t in zip(res8, truth)
    )
    out["shard_int8_p50_ms"] = p50
    out["shard_int8_p95_ms"] = p95
    out["shard_recall10_int8"] = round(hits / (n_queries * top_k), 4)
    fab8.close()
    del fab8, res8
    gc.collect()

    # 5) PQ cold tier: all but one shard demoted to host-RAM PQ codes;
    # stage-1 ADC scans run against host memory, only the stage-2 rescore
    # candidates move to the device.  Gate: the cold rows' host scan
    # bytes <= 0.15x what the same rows would cost as full-width scans.
    fabpq = ShardedVectorStore(
        dim,
        num_shards=num_shards,
        hot_shard_budget=1,
        pq_m=SHARD_PQ_M,
    )
    fabpq.add(chunks, vecs)
    fabpq.rebalance()
    respq, p50, p95 = _measure(fabpq)
    hits = sum(
        len({s.chunk.id for s in got} & t) for got, t in zip(respq, truth)
    )
    out["shard_pq_p50_ms"] = p50
    out["shard_pq_p95_ms"] = p95
    out["shard_recall10_pq"] = round(hits / (n_queries * top_k), 4)
    out["shard_cold_shards"] = len(fabpq.cold_shards())
    split = fabpq.scanned_bytes_split(top_k)
    out["shard_scan_host_mb"] = round(split["host"] / 1e6, 3)
    out["shard_scan_hbm_mb"] = round(split["hbm"] / 1e6, 3)
    caps = fabpq.capacity_stats()
    cold_rows = rows * len(fabpq.cold_shards()) // num_shards
    fullwidth = max(cold_rows * dim * 4, 1)
    out["shard_cold_host_ratio"] = round(split["host"] / fullwidth, 4)
    out["shard_host_bytes_mb"] = round(
        caps.get("host_bytes", 0) / 1e6, 3
    )
    fabpq.close()
    del fabpq, respq
    gc.collect()

    # Gate verdicts (informational here; the capture review reads them).
    out["shard_pass_bit_identical"] = out["shard_exact_bit_identical"]
    out["shard_pass_recall_int8"] = out["shard_recall10_int8"] >= 0.95
    out["shard_pass_recall_pq"] = out["shard_recall10_pq"] >= 0.95
    out["shard_pass_cold_bytes"] = out["shard_cold_host_ratio"] <= 0.15
    out["shard_pass_p95_under_ingest"] = (
        out["shard_p95_under_ingest_ratio"] <= 2.0
    )
    return out


# Chaos/resilience phase (round-11 lever): the SAME closed-loop retrieval
# workload run five ways — bare call sequence (no resilience machinery, the
# pre-round-11 path), clean resilient path (machinery overhead), faulted
# with retries disabled (what an unprotected stack does under the fault
# spec), faulted with the full ladder (retries + breakers + deadlines +
# degradation), and a hard-down reranker (the graceful-degradation rung
# visible at 100%).  In-process HashEmbedder + exact MemoryVectorStore +
# a lexical reranker keep the phase CPU-cheap and deterministic: the
# measured quantity is the RESILIENCE machinery, not embed/search
# throughput (bench_rag owns that), so it runs identically on any
# platform.  The batcher is deliberately absent: its per-item error
# isolation would mask the protected-vs-unprotected contrast this phase
# exists to measure.
CHAOS_CORPUS_DOCS = 65536
CHAOS_DIM = 256  # with the corpus above the scan is ~64 MB/query (a few
# ms — the cost bracket of a real embed forward + corpus scan), so the
# machinery-overhead ratio prices the machinery (a fixed ~tens of
# µs/request) against realistic per-request work, not timer noise
CHAOS_TOP_K = 4
CHAOS_CONCURRENCY = 16
CHAOS_REQS_PER_CLIENT = 16
CHAOS_DEADLINE_MS = 750.0
# Acceptance fault spec: 10% embedder failures + 200 ms reranker latency.
CHAOS_FAULTS = "embedder:error=0.1;reranker:latency=200"
# Hard-down variant: reranker always fails — the ladder must serve
# vector-search order on every request, not error.
CHAOS_FAULTS_RERANK_DOWN = "embedder:error=0.1;reranker:error=1.0"
CHAOS_OVERHEAD_ITERS = 192  # paired raw/resilient overhead samples


def bench_chaos() -> dict:
    """Success rate + p50/p99 under injected faults, protected vs not,
    plus the clean-path overhead of the resilience machinery itself."""
    import random as _random
    import threading

    from generativeaiexamples_tpu.engine.embedder import HashEmbedder
    from generativeaiexamples_tpu.resilience.deadline import (
        Deadline,
        deadline_scope,
    )
    from generativeaiexamples_tpu.resilience.degrade import degrade_scope
    from generativeaiexamples_tpu.resilience.faults import get_fault_injector
    from generativeaiexamples_tpu.resilience.metrics import (
        reset_resilience,
        resilience_snapshot,
    )
    from generativeaiexamples_tpu.resilience.retry import RetryPolicy
    from generativeaiexamples_tpu.retrieval.base import Chunk
    from generativeaiexamples_tpu.retrieval.memory import MemoryVectorStore
    from generativeaiexamples_tpu.retrieval.retriever import Retriever

    dims = CHAOS_DIM
    embedder = HashEmbedder(dimensions=dims)

    class _LexicalReranker:
        """Word-overlap cross-encoder stand-in: cheap, deterministic, and
        traverses the real ``reranker`` fault point + breaker path."""

        @staticmethod
        def score(query: str, texts: Sequence[str]) -> list[float]:
            qw = set(query.split())
            return [
                len(qw & set(t.split())) / max(len(qw), 1) for t in texts
            ]

    word_pool = (
        "retrieval augmented generation embedding vector search pipeline "
        "index document query context tokens model attention transformer "
        "serving latency throughput batch deadline retry breaker fault"
    ).split()
    qrng = _random.Random(17)
    store = MemoryVectorStore(dims)
    texts = [
        " ".join(qrng.choice(word_pool) for _ in range(24))
        for _ in range(CHAOS_CORPUS_DOCS)
    ]
    store.add(
        [
            Chunk(text=t, source=f"doc{i % 64}.txt")
            for i, t in enumerate(texts)
        ],
        embedder.embed_documents(texts),
    )
    queries = [
        " ".join(qrng.choice(word_pool) for _ in range(8)) for _ in range(256)
    ]
    reranker = _LexicalReranker()
    fetch_k = CHAOS_TOP_K * 4

    def _raw_retrieve(query: str) -> list:
        """The pre-resilience call sequence: embed → search → rerank with
        no deadline/retry/breaker/inject machinery (overhead baseline)."""
        qs = embedder.embed_queries([query])
        hits = store.search_batch(qs, fetch_k)[0]
        scores = reranker.score(query, [h.chunk.text for h in hits])
        order = sorted(range(len(hits)), key=lambda i: -scores[i])
        return [hits[i] for i in order[:CHAOS_TOP_K]]

    def _make_retriever(protected: bool) -> Retriever:
        return Retriever(
            store=store,
            embedder=embedder,
            top_k=CHAOS_TOP_K,
            score_threshold=-1e30,
            reranker=reranker,
            embed_retry=RetryPolicy(
                max_attempts=3 if protected else 1, name="embed"
            ),
            search_retry=RetryPolicy(
                max_attempts=3 if protected else 1, name="store-search"
            ),
        )

    def run_level(name: str, *, protected: bool, faults: str, raw: bool):
        reset_resilience()
        retriever = _make_retriever(protected)
        # Warm the path before arming faults so the first request's
        # import/lock costs stay out of the timed window.
        (_raw_retrieve if raw else retriever.retrieve)(queries[0])
        if faults:
            get_fault_injector().configure(faults)
        lock = threading.Lock()
        lats: list[float] = []
        failures = [0]
        degraded_reqs = [0]
        start_gate = threading.Barrier(CHAOS_CONCURRENCY + 1)

        def worker(wid: int) -> None:
            start_gate.wait()
            for j in range(CHAOS_REQS_PER_CLIENT):
                q = queries[
                    (wid * CHAOS_REQS_PER_CLIENT + j) % len(queries)
                ]
                t0 = time.perf_counter()
                ok = True
                was_degraded = False
                try:
                    if raw:
                        hits = _raw_retrieve(q)
                    else:
                        with deadline_scope(
                            Deadline.after_ms(CHAOS_DEADLINE_MS)
                        ), degrade_scope() as log:
                            hits = retriever.retrieve(q)
                        was_degraded = bool(log)
                    ok = bool(hits)
                except Exception:
                    ok = False
                dt = time.perf_counter() - t0
                with lock:
                    lats.append(dt)
                    if not ok:
                        failures[0] += 1
                    if was_degraded:
                        degraded_reqs[0] += 1

        threads = [
            threading.Thread(target=worker, args=(w,), daemon=True)
            for w in range(CHAOS_CONCURRENCY)
        ]
        for t in threads:
            t.start()
        start_gate.wait()
        for t in threads:
            t.join(timeout=600)
        snap = resilience_snapshot()
        get_fault_injector().clear()
        lats.sort()
        n = len(lats)
        return {
            "success": round(1.0 - failures[0] / max(n, 1), 4),
            "p50_ms": round(lats[n // 2] * 1000, 2) if lats else 0.0,
            "p99_ms": round(lats[min(int(n * 0.99), n - 1)] * 1000, 2)
            if lats
            else 0.0,
            "degraded_requests": degraded_reqs[0],
            "retries": snap["retries_total"],
            "deadline_expired": snap["deadline_expired_total"],
            "degraded_total": snap["degraded_total"],
        }

    out: dict = {
        "chaos_corpus_docs": CHAOS_CORPUS_DOCS,
        "chaos_top_k": CHAOS_TOP_K,
        "chaos_concurrency": CHAOS_CONCURRENCY,
        "chaos_requests": CHAOS_CONCURRENCY * CHAOS_REQS_PER_CLIENT,
        "chaos_deadline_ms": CHAOS_DEADLINE_MS,
        "chaos_faults": CHAOS_FAULTS,
    }
    runs = (
        ("raw", dict(protected=False, faults="", raw=True)),
        ("clean", dict(protected=True, faults="", raw=False)),
        ("unprotected", dict(protected=False, faults=CHAOS_FAULTS, raw=False)),
        ("protected", dict(protected=True, faults=CHAOS_FAULTS, raw=False)),
        (
            "rerank_down",
            dict(protected=True, faults=CHAOS_FAULTS_RERANK_DOWN, raw=False),
        ),
    )
    for name, kwargs in runs:
        res = run_level(name, **kwargs)
        out[f"chaos_{name}_success"] = res["success"]
        out[f"chaos_{name}_p50_ms"] = res["p50_ms"]
        out[f"chaos_{name}_p99_ms"] = res["p99_ms"]
        out[f"chaos_{name}_degraded_requests"] = res["degraded_requests"]
        out[f"chaos_{name}_retries"] = res["retries"]
        out[f"chaos_{name}_deadline_expired"] = res["deadline_expired"]
        out[f"chaos_{name}_degraded_total"] = res["degraded_total"]
    # -- machinery overhead: paired single-threaded measurement ------------
    # The concurrency runs above are GIL/memory-bandwidth contention-noisy
    # at sub-ms deltas; alternating raw/resilient calls on one thread
    # cancels system drift, so the median delta is the machinery itself
    # (deadline + contextvar scopes, retry wrappers, breaker bookkeeping,
    # disarmed fault points) — the ≤3% clean-path-regression claim.
    reset_resilience()
    clean_retriever = _make_retriever(protected=True)
    clean_retriever.retrieve(queries[0])
    _raw_retrieve(queries[0])
    raw_l: list[float] = []
    deltas: list[float] = []
    for i in range(CHAOS_OVERHEAD_ITERS):
        q = queries[i % len(queries)]
        t0 = time.perf_counter()
        _raw_retrieve(q)
        t1 = time.perf_counter()
        with deadline_scope(
            Deadline.after_ms(CHAOS_DEADLINE_MS)
        ), degrade_scope():
            clean_retriever.retrieve(q)
        t2 = time.perf_counter()
        raw_l.append(t1 - t0)
        # Same query, back-to-back on one thread: the per-pair delta is
        # the machinery; its median is robust where a difference of two
        # independent medians is not.
        deltas.append((t2 - t1) - (t1 - t0))
    raw_l.sort()
    deltas.sort()
    raw_p50 = raw_l[len(raw_l) // 2] * 1000.0
    overhead_ms = deltas[len(deltas) // 2] * 1000.0
    out["chaos_overhead_raw_p50_ms"] = round(raw_p50, 3)

    reset_resilience()  # never leak armed faults into later phases
    # Headline scalars: the acceptance quantities.  p99 must stay under
    # the deadline; protected success must hold ≥0.99 where the
    # unprotected stack loses ~1 request in 10.
    out["chaos_success_protected"] = out["chaos_protected_success"]
    out["chaos_success_unprotected"] = out["chaos_unprotected_success"]
    out["chaos_p99_protected_ms"] = out["chaos_protected_p99_ms"]
    out["chaos_clean_overhead_ms"] = round(overhead_ms, 3)
    out["chaos_clean_overhead_pct"] = round(
        overhead_ms / max(raw_p50, 1e-9) * 100.0, 2
    )
    out["chaos_degraded_frac_rerank_down"] = round(
        out["chaos_rerank_down_degraded_requests"]
        / max(out["chaos_requests"], 1),
        4,
    )
    return out


# Semantic-cache phase (round-12 lever): the retrieval hot path under a
# zipf-repeated query workload, cache-off vs cache-on.  Same CPU-cheap
# deterministic stack as bench_chaos (hash-derived embedder + exact
# MemoryVectorStore + lexical reranker) — the measured quantity is the
# CACHE (dict probe + one small ring matmul vs the full
# embed→search→rerank chain), not raw device throughput.  Requests route
# through the real chain-layer shape: a pre-batcher exact check, then the
# micro-batcher into ``retrieve_many`` — so the batcher's own
# requests_total counter proves the exact-hit path dispatches NOTHING.
CACHE_CORPUS_DOCS = 32768
CACHE_DIM = 256
CACHE_TOP_K = 4
CACHE_CONCURRENCY = 32
CACHE_REQS_PER_CLIENT = 32
CACHE_UNIQUE_QUERIES = 192
CACHE_ZIPF_S = 1.1  # zipf exponent of the repeated-query popularity curve
CACHE_SIM_THRESHOLDS = (0.90, 0.95, 0.98)
CACHE_PARAPHRASES_PER_CLASS = 64


def bench_cache() -> dict:
    """Cache-off vs cache-on QPS + latency on a zipf(1.1) repeated-query
    workload at c=32, plus the semantic-threshold paraphrase sweep."""
    import random as _random
    import threading

    from generativeaiexamples_tpu.cache.core import RetrievalCache
    from generativeaiexamples_tpu.cache.metrics import (
        cache_snapshot,
        reset_cache_metrics,
    )
    from generativeaiexamples_tpu.engine.embedder import HashEmbedder
    from generativeaiexamples_tpu.engine.microbatch import MicroBatcher
    from generativeaiexamples_tpu.retrieval.base import Chunk
    from generativeaiexamples_tpu.retrieval.memory import MemoryVectorStore
    from generativeaiexamples_tpu.retrieval.retriever import Retriever

    dims = CACHE_DIM

    class _BowEmbedder:
        """Bag-of-words embedder: a text's vector is the normalized sum
        of per-word hash vectors.  Unlike the whole-text HashEmbedder
        (any two distinct strings are near-orthogonal), word-sharing
        texts land NEAR each other — which is what the semantic tier's
        similarity threshold needs to be exercised against."""

        def __init__(self, d: int) -> None:
            self._hash = HashEmbedder(dimensions=d)
            self._words: dict = {}
            self._lock = threading.Lock()

        def _word_vec(self, word: str):
            with self._lock:
                v = self._words.get(word)
                if v is None:
                    v = np.asarray(
                        self._hash.embed_documents([word])[0],
                        dtype=np.float32,
                    )
                    self._words[word] = v
                return v

        def _text_vec(self, text: str) -> list:
            words = text.split() or [""]
            v = np.sum([self._word_vec(w) for w in words], axis=0)
            return (v / max(float(np.linalg.norm(v)), 1e-12)).tolist()

        def embed_query(self, text: str) -> list:
            return self._text_vec(text)

        def embed_queries(self, texts: Sequence[str]) -> list:
            return [self._text_vec(t) for t in texts]

        def embed_documents(self, texts: Sequence[str]) -> list:
            return [self._text_vec(t) for t in texts]

    class _LexicalReranker:
        @staticmethod
        def score(query: str, texts: Sequence[str]) -> list[float]:
            qw = set(query.split())
            return [
                len(qw & set(t.split())) / max(len(qw), 1) for t in texts
            ]

    embedder = _BowEmbedder(dims)
    word_pool = (
        "retrieval augmented generation embedding vector search pipeline "
        "index document query context tokens model attention transformer "
        "serving latency throughput batch cache tier semantic exact zipf"
    ).split()
    rng = _random.Random(23)
    store = MemoryVectorStore(dims)
    texts = [
        " ".join(rng.choice(word_pool) for _ in range(24))
        for _ in range(CACHE_CORPUS_DOCS)
    ]
    store.add(
        [Chunk(text=t, source=f"doc{i % 64}.txt") for i, t in enumerate(texts)],
        embedder.embed_documents(texts),
    )
    uniques = [
        " ".join(rng.choice(word_pool) for _ in range(8))
        for _ in range(CACHE_UNIQUE_QUERIES)
    ]
    # Zipf(s) popularity: rank r drawn with weight 1/r^s — the classic
    # production-query shape where a head of repeats dominates.
    weights = [1.0 / (r + 1) ** CACHE_ZIPF_S for r in range(len(uniques))]
    total_requests = CACHE_CONCURRENCY * CACHE_REQS_PER_CLIENT
    workload = rng.choices(uniques, weights=weights, k=total_requests)
    reranker = _LexicalReranker()

    def run_level(cache: Optional[RetrievalCache]) -> dict:
        reset_cache_metrics()
        retriever = Retriever(
            store=store,
            embedder=embedder,
            top_k=CACHE_TOP_K,
            score_threshold=-1e30,
            reranker=reranker,
            cache=cache,
        )

        def _batch(items):
            many = retriever.retrieve_many(
                [q for q, _, _, _ in items],
                top_k=max(k for _, k, _, _ in items),
                degrade_logs=[log for _, _, log, _ in items],
                cache_logs=[clog for _, _, _, clog in items],
            )
            return [hits[:k] for hits, (_, k, _, _) in zip(many, items)]

        batcher = MicroBatcher(
            _batch, max_batch=CACHE_CONCURRENCY, max_wait_ms=1.0,
            name="bench-cache",
        )

        def _request(q: str) -> list:
            # The chain layer's shape: exact tier BEFORE the batcher (a
            # hit is one dict probe — no queue, no dispatch), misses ride
            # the shared pipeline.
            if cache is not None:
                entry = cache.lookup_exact(
                    q, CACHE_TOP_K, "rag", store.version()
                )
                if entry is not None:
                    return list(entry.hits[:CACHE_TOP_K])
            return batcher.call((q, CACHE_TOP_K, None, None))

        # Warm: JIT/compile + (cache-on) fill — steady-state is the
        # quantity of interest; the fill cost is the miss path, priced
        # by the cache-off run.
        for q in uniques:
            _request(q)
        warm_pipeline = batcher.stats.snapshot()["requests_total"]
        warm_snap = cache_snapshot()

        lock = threading.Lock()
        lats: list[float] = []
        start_gate = threading.Barrier(CACHE_CONCURRENCY + 1)

        def worker(wid: int) -> None:
            start_gate.wait()
            for j in range(CACHE_REQS_PER_CLIENT):
                q = workload[wid * CACHE_REQS_PER_CLIENT + j]
                t0 = time.perf_counter()
                _request(q)
                dt = time.perf_counter() - t0
                with lock:
                    lats.append(dt)

        threads = [
            threading.Thread(target=worker, args=(w,), daemon=True)
            for w in range(CACHE_CONCURRENCY)
        ]
        for t in threads:
            t.start()
        start_gate.wait()
        t_start = time.perf_counter()
        for t in threads:
            t.join(timeout=600)
        wall = time.perf_counter() - t_start
        pipeline_requests = (
            batcher.stats.snapshot()["requests_total"] - warm_pipeline
        )
        snap = cache_snapshot()
        batcher.close()
        lats.sort()
        n = len(lats)
        hits = sum(snap["hits"].values()) - sum(warm_snap["hits"].values())
        return {
            "qps": round(n / max(wall, 1e-9), 1),
            "p50_ms": round(lats[n // 2] * 1000, 3) if lats else 0.0,
            "p95_ms": round(lats[min(int(n * 0.95), n - 1)] * 1000, 3)
            if lats
            else 0.0,
            "hit_rate": round(hits / max(n, 1), 4),
            "pipeline_requests": pipeline_requests,
        }

    out: dict = {
        "cache_corpus_docs": CACHE_CORPUS_DOCS,
        "cache_concurrency": CACHE_CONCURRENCY,
        "cache_requests": total_requests,
        "cache_unique_queries": CACHE_UNIQUE_QUERIES,
        "cache_zipf_s": CACHE_ZIPF_S,
    }
    off = run_level(None)
    on = run_level(
        RetrievalCache(
            dims, max_entries=4096, semantic_entries=512,
            similarity_threshold=0.98,
        )
    )
    out["cache_off_qps"] = off["qps"]
    out["cache_off_p50_ms"] = off["p50_ms"]
    out["cache_off_p95_ms"] = off["p95_ms"]
    out["cache_off_pipeline_requests"] = off["pipeline_requests"]
    out["cache_on_qps"] = on["qps"]
    out["cache_on_p50_ms"] = on["p50_ms"]
    out["cache_on_p95_ms"] = on["p95_ms"]
    out["cache_on_pipeline_requests"] = on["pipeline_requests"]
    out["cache_hit_rate"] = on["hit_rate"]
    out["cache_speedup_p50"] = round(
        off["p50_ms"] / max(on["p50_ms"], 1e-9), 2
    )
    out["cache_speedup_qps"] = round(on["qps"] / max(off["qps"], 1e-9), 2)
    # The zero-dispatch acceptance: every timed request either hit a
    # cache tier or is accounted one-for-one by a batcher submission —
    # exact hits never reach the pipeline at all.
    out["cache_exact_zero_dispatch"] = int(
        on["pipeline_requests"] <= total_requests * (1.0 - on["hit_rate"]) + 1
    )

    # -- semantic-threshold paraphrase sweep ----------------------------
    # Three paraphrase classes against admitted base queries: word
    # reorder (identical bag → sim 1.0), one filler word (~sqrt(8/9) ≈
    # .94), two fillers (~sqrt(8/10) ≈ .89).  The sweep shows what each
    # threshold setting buys (and stops matching) — docs/caching.md's
    # tuning table comes from here.
    fillers = ("please", "kindly", "now")
    classes = {"reorder": 0, "one_filler": 1, "two_fillers": 2}
    for thresh in CACHE_SIM_THRESHOLDS:
        cache = RetrievalCache(
            dims, max_entries=1024, semantic_entries=512,
            similarity_threshold=thresh,
        )
        retr = Retriever(
            store=store, embedder=embedder, top_k=CACHE_TOP_K,
            score_threshold=-1e30, cache=cache,
        )
        base = uniques[: CACHE_PARAPHRASES_PER_CLASS]
        retr.retrieve_many(base)  # admit
        for cls, n_fill in classes.items():
            reset_cache_metrics()
            para = []
            for q in base:
                words = q.split()
                prng = _random.Random(hash((q, cls)) & 0xFFFF)
                prng.shuffle(words)
                para.append(" ".join(words + list(fillers[:n_fill])))
            retr.retrieve_many(para)
            snap = cache_snapshot()
            rate = snap["hits"].get("semantic", 0) / len(para)
            key = f"cache_semantic_hitrate_t{int(thresh * 100)}_{cls}"
            out[key] = round(rate, 4)
    reset_cache_metrics()
    return out


# Observability phase (round-13 lever): the cost of the telemetry layer
# itself.  Same CPU-cheap deterministic stack as bench_chaos (hash
# embedder + exact MemoryVectorStore + lexical reranker); the measured
# quantity is the TRACE MACHINERY (contextvar bind, perf_counter stamps,
# histogram observes, recorder append) laid over an otherwise identical
# retrieval, not the retrieval itself.  The ≤3% gate is the acceptance
# claim in docs/observability.md.
OBS_CORPUS_DOCS = 65536  # bench_chaos parity: the same corpus the
# resilience clean-overhead gate is measured against, so the two ≤3%
# claims share a denominator
OBS_DIM = 256
OBS_TOP_K = 4
OBS_OVERHEAD_ITERS = 192  # paired raw/traced overhead samples
OBS_GATE_PCT = 3.0


def bench_obs() -> dict:
    """Paired single-threaded overhead of per-request tracing: raw
    embed→search→rerank vs the same calls inside a bound RequestTrace
    with stage spans, histogram observes, finish() and flight-recorder
    append — the full per-request telemetry cost."""
    import random as _random

    from generativeaiexamples_tpu.engine.embedder import HashEmbedder
    from generativeaiexamples_tpu.obs.metrics import (
        obs_snapshot,
        reset_obs_metrics,
    )
    from generativeaiexamples_tpu.obs.recorder import FlightRecorder
    from generativeaiexamples_tpu.obs.trace import RequestTrace, trace_scope
    from generativeaiexamples_tpu.retrieval.base import Chunk
    from generativeaiexamples_tpu.retrieval.memory import MemoryVectorStore

    dims = OBS_DIM
    embedder = HashEmbedder(dimensions=dims)

    word_pool = (
        "retrieval augmented generation embedding vector search pipeline "
        "index document query context tokens model attention transformer "
        "serving latency throughput batch deadline retry breaker fault"
    ).split()
    qrng = _random.Random(23)
    store = MemoryVectorStore(dims)
    texts = [
        " ".join(qrng.choice(word_pool) for _ in range(24))
        for _ in range(OBS_CORPUS_DOCS)
    ]
    store.add(
        [
            Chunk(text=t, source=f"doc{i % 64}.txt")
            for i, t in enumerate(texts)
        ],
        embedder.embed_documents(texts),
    )
    queries = [
        " ".join(qrng.choice(word_pool) for _ in range(8)) for _ in range(256)
    ]
    fetch_k = OBS_TOP_K * 4

    def _rerank(query: str, hits: list) -> list:
        qw = set(query.split())
        scores = [
            len(qw & set(h.chunk.text.split())) / max(len(qw), 1)
            for h in hits
        ]
        order = sorted(range(len(hits)), key=lambda i: -scores[i])
        return [hits[i] for i in order[:OBS_TOP_K]]

    def _raw(query: str) -> list:
        qs = embedder.embed_queries([query])
        hits = store.search_batch(qs, fetch_k)[0]
        return _rerank(query, hits)

    recorder = FlightRecorder(capacity=256)

    def _traced(query: str) -> list:
        # The full per-request telemetry path of server.app: bind a
        # trace, record each stage the way the retriever does
        # (perf-counter stamps + add_stage), finalize into histograms +
        # recorder.
        trace = RequestTrace(route="/search")
        with trace_scope(trace):
            t0 = time.perf_counter()
            qs = embedder.embed_queries([query])
            t1 = time.perf_counter()
            trace.add_stage("embed", (t1 - t0) * 1000.0, start=t0)
            hits = store.search_batch(qs, fetch_k)[0]
            t2 = time.perf_counter()
            trace.add_stage(
                "search", (t2 - t1) * 1000.0, start=t1, fetch_k=fetch_k
            )
            top = _rerank(query, hits)
            trace.add_stage(
                "rerank", (time.perf_counter() - t2) * 1000.0, start=t2
            )
        recorder.record(trace.finish(200))
        return top

    reset_obs_metrics()
    _raw(queries[0])  # warm both paths before timing
    _traced(queries[0])
    raw_l: list[float] = []
    deltas: list[float] = []
    for i in range(OBS_OVERHEAD_ITERS):
        q = queries[i % len(queries)]
        t0 = time.perf_counter()
        _raw(q)
        t1 = time.perf_counter()
        _traced(q)
        t2 = time.perf_counter()
        raw_l.append(t1 - t0)
        # Same query back-to-back on one thread: the per-pair delta is
        # the telemetry machinery; its median is robust where a
        # difference of two independent medians is not (the bench_chaos
        # methodology).
        deltas.append((t2 - t1) - (t1 - t0))
    raw_l.sort()
    deltas.sort()
    raw_p50 = raw_l[len(raw_l) // 2] * 1000.0
    overhead_ms = deltas[len(deltas) // 2] * 1000.0
    overhead_pct = overhead_ms / max(raw_p50, 1e-9) * 100.0
    snap = obs_snapshot()
    stage_samples = sum(v["count"] for v in snap["stage"].values())
    out = {
        "obs_corpus_docs": OBS_CORPUS_DOCS,
        "obs_overhead_iters": OBS_OVERHEAD_ITERS,
        "obs_raw_p50_ms": round(raw_p50, 3),
        "obs_traced_p50_ms": round(raw_p50 + overhead_ms, 3),
        "obs_overhead_ms": round(overhead_ms, 4),
        "obs_overhead_pct": round(overhead_pct, 2),
        "obs_gate_pct": OBS_GATE_PCT,
        "obs_overhead_ok": int(overhead_pct <= OBS_GATE_PCT),
        "obs_stage_samples": stage_samples,
        "obs_recorder_entries": len(recorder),
    }
    reset_obs_metrics()  # never leak bench samples into later phases
    return out


# SLO phase (round-14 lever): the per-request fleet-telemetry feed (TSDB
# pending appends + SLO counters) measured the same paired-delta way as
# bench_obs, sharing its corpus constants so the two ≤3% clean-overhead
# claims keep one denominator — plus a deterministic alert drill: a PR 6
# embedder fault burst must flip the fast-burn rule within ONE evaluation,
# a clean run must not, and post-recovery traffic must clear it.
SLO_OVERHEAD_ITERS = 192
SLO_GATE_PCT = 3.0
SLO_DRILL_REQUESTS = 64  # per drill phase (clean / burst / recovery)


def bench_slo() -> dict:
    """Paired single-threaded overhead of the SLO/TSDB request feed, plus
    the burn-rate alert drill.  Everything is phase-local (own Tsdb,
    SloEngine, FlightRecorder) so no state leaks into other phases; the
    drill drives synthetic timestamps, so it needs no wall-clock sleeps."""
    import random as _random

    from generativeaiexamples_tpu.engine.embedder import HashEmbedder
    from generativeaiexamples_tpu.obs.recorder import FlightRecorder
    from generativeaiexamples_tpu.obs.slo import SloEngine
    from generativeaiexamples_tpu.obs.tsdb import Tsdb
    from generativeaiexamples_tpu.resilience.faults import (
        FaultInjected,
        get_fault_injector,
        inject,
        reset_faults,
    )
    from generativeaiexamples_tpu.retrieval.base import Chunk
    from generativeaiexamples_tpu.retrieval.memory import MemoryVectorStore

    class _SloCfg:
        # Real production thresholds; the drill controls time via
        # explicit timestamps instead of shrinking the windows.
        enabled = True
        availability_target = 0.999
        latency_p95_ms = "/search=500"
        fast_window_s = 300.0
        slow_window_s = 1800.0
        fast_burn_threshold = 14.4
        slow_burn_threshold = 6.0
        evaluation_period_s = 0.0

    dims = OBS_DIM
    embedder = HashEmbedder(dimensions=dims)
    word_pool = (
        "retrieval augmented generation embedding vector search pipeline "
        "index document query context tokens model attention transformer "
        "serving latency throughput batch deadline retry breaker fault"
    ).split()
    qrng = _random.Random(29)
    store = MemoryVectorStore(dims)
    texts = [
        " ".join(qrng.choice(word_pool) for _ in range(24))
        for _ in range(OBS_CORPUS_DOCS)
    ]
    store.add(
        [Chunk(text=t, source=f"doc{i % 64}.txt") for i, t in enumerate(texts)],
        embedder.embed_documents(texts),
    )
    queries = [
        " ".join(qrng.choice(word_pool) for _ in range(8)) for _ in range(256)
    ]
    fetch_k = OBS_TOP_K * 4

    def _raw(query: str) -> list:
        qs = embedder.embed_queries([query])
        hits = store.search_batch(qs, fetch_k)[0]
        qw = set(query.split())
        scores = [
            len(qw & set(h.chunk.text.split())) / max(len(qw), 1) for h in hits
        ]
        order = sorted(range(len(hits)), key=lambda i: -scores[i])
        return [hits[i] for i in order[:OBS_TOP_K]]

    tsdb = Tsdb()
    recorder = FlightRecorder(capacity=256)
    eng = SloEngine(_SloCfg(), tsdb=tsdb, recorder=recorder)

    def _fed(query: str) -> list:
        # The server's _feed_fleet_telemetry cost on top of an identical
        # request: per-request counters + latency series + SLO counters —
        # all pending-list appends, folded at read time.
        t0 = time.perf_counter()
        top = _raw(query)
        dt_ms = (time.perf_counter() - t0) * 1000.0
        tsdb.record("chain.requests./search", 1.0, kind="counter")
        tsdb.record("chain.request_ms./search", dt_ms)
        tsdb.record("chain.stage_ms.search", dt_ms)
        eng.note_request("/search", dt_ms)
        return top

    _raw(queries[0])  # warm both paths before timing
    _fed(queries[0])
    raw_l: list[float] = []
    deltas: list[float] = []
    for i in range(SLO_OVERHEAD_ITERS):
        q = queries[i % len(queries)]
        t0 = time.perf_counter()
        _raw(q)
        t1 = time.perf_counter()
        _fed(q)
        t2 = time.perf_counter()
        raw_l.append(t1 - t0)
        deltas.append((t2 - t1) - (t1 - t0))  # bench_obs paired-delta
    raw_l.sort()
    deltas.sort()
    raw_p50 = raw_l[len(raw_l) // 2] * 1000.0
    overhead_ms = deltas[len(deltas) // 2] * 1000.0
    overhead_pct = overhead_ms / max(raw_p50, 1e-9) * 100.0

    # -- alert drill, on a fresh engine so the overhead loop's requests
    # don't sit in the drill's windows.
    tsdb = Tsdb()
    recorder = FlightRecorder(capacity=256)
    eng = SloEngine(_SloCfg(), tsdb=tsdb, recorder=recorder)
    base = time.time()

    def _drill(t0: float, *, faulted: bool) -> None:
        for i in range(SLO_DRILL_REQUESTS):
            err = False
            if faulted:
                try:
                    inject("embedder")  # the PR 6 chaos fault point
                except FaultInjected:
                    err = True
            eng.note_request("/search", 5.0, error=err, ts=t0 + i * 0.01)

    # Clean baseline must NOT fire.
    _drill(base, faulted=False)
    clean_ok = not eng.evaluate(now=base + 1, force=True)["fast_burn_firing"]

    # Fault burst must flip the fast-burn rule within one evaluation.
    get_fault_injector().configure("embedder:error=1.0")
    t_burst = base + 10
    try:
        _drill(t_burst, faulted=True)
    finally:
        reset_faults()
    verdict = eng.evaluate(now=t_burst + 1, force=True)
    alert_fired = bool(verdict["fast_burn_firing"])
    burn_fast = (
        verdict["routes"]
        .get("/search", {})
        .get("availability", {})
        .get("windows", {})
        .get("fast", {})
        .get("burn_rate", 0.0)
    )

    # Recovery: clean traffic once the fast rule's windows have drained.
    t_rec = t_burst + _SloCfg.fast_window_s * (12 + 1)
    _drill(t_rec, faulted=False)
    alert_clear_ok = not eng.evaluate(now=t_rec + 1, force=True)[
        "fast_burn_firing"
    ]
    transitions = sum(
        1
        for e in recorder.snapshot()
        if (e.get("attrs") or {}).get("slo_alert")
    )

    return {
        "slo_corpus_docs": OBS_CORPUS_DOCS,
        "slo_overhead_iters": SLO_OVERHEAD_ITERS,
        "slo_raw_p50_ms": round(raw_p50, 3),
        "slo_fed_p50_ms": round(raw_p50 + overhead_ms, 3),
        "slo_overhead_ms": round(overhead_ms, 4),
        "slo_overhead_pct": round(overhead_pct, 2),
        "slo_gate_pct": SLO_GATE_PCT,
        "slo_overhead_ok": int(overhead_pct <= SLO_GATE_PCT),
        "slo_clean_ok": int(clean_ok),
        "slo_alert_fired": int(alert_fired),
        "slo_burn_rate_fast": round(burn_fast, 1),
        "slo_alert_clear_ok": int(alert_clear_ok),
        "slo_transitions": transitions,
    }


# Elastic phase (round-15 lever): the closed loop — a 4x load step must
# page (fast burn), the autoscaler must grow the pool, the system must
# recover without breaching the latency SLO, and every shed request must
# be batch/ingest (interactive success >= 0.99).  A discrete-event
# simulation over synthetic timestamps (the bench_slo pattern: phase-local
# Tsdb/SloEngine/Autoscaler/AdmissionController, no wall-clock sleeps)
# drives the REAL controllers; only the replica pool is a stub whose
# capacity is requests-served-per-second.
ELASTIC_BASE_RPS = 8           # baseline offered load
ELASTIC_STEP_FACTOR = 4        # the load step under test
ELASTIC_MU = 10                # per-replica service capacity, req/s
ELASTIC_WARMUP_S = 600         # clean baseline (fills burn-rate windows)
ELASTIC_STEP_S = 300           # overload duration
ELASTIC_RECOVERY_S = 600       # post-step baseline (alert must clear)
ELASTIC_SERVICE_MS = 100.0     # zero-wait service latency
ELASTIC_LATENCY_SLO_MS = 2500.0
ELASTIC_CLASS_MIX = (          # deterministic per-second arrival split
    ("interactive", 0.60),
    ("batch", 0.25),
    ("ingest", 0.15),
)
ELASTIC_OVERHEAD_ITERS = 192
ELASTIC_GATE_PCT = 3.0


def bench_elastic() -> dict:
    """Closed-loop elasticity acceptance: 4x load step -> fast-burn page
    -> autoscale -> recovery within the latency SLO, with admission
    control shedding only batch/ingest; plus the admission gate's paired
    clean-path overhead (bench_obs methodology)."""
    import random as _random

    from generativeaiexamples_tpu.engine.autoscale import Autoscaler
    from generativeaiexamples_tpu.engine.embedder import HashEmbedder
    from generativeaiexamples_tpu.obs.recorder import FlightRecorder
    from generativeaiexamples_tpu.obs.slo import SloEngine
    from generativeaiexamples_tpu.obs.tsdb import Tsdb
    from generativeaiexamples_tpu.resilience.admission import (
        AdmissionController,
    )
    from generativeaiexamples_tpu.retrieval.base import Chunk
    from generativeaiexamples_tpu.retrieval.memory import MemoryVectorStore

    class _SloCfg:
        enabled = True
        availability_target = 0.999
        latency_p95_ms = f"/generate={ELASTIC_LATENCY_SLO_MS:.0f}"
        fast_window_s = 300.0
        slow_window_s = 1800.0
        fast_burn_threshold = 14.4
        slow_burn_threshold = 6.0
        evaluation_period_s = 0.0

    class _AsCfg:
        # Production-shaped knobs except the scale-down cooldown, shrunk
        # so the 10-minute recovery window also exercises scale-down.
        enabled = True
        min_replicas = 1
        max_replicas = 4
        interval_s = 1.0
        window_s = 30.0
        queue_high = 4.0
        queue_low = 0.5
        tick_high_ms = 0.0
        scale_on_fast_burn = True
        down_checks = 3
        up_cooldown_s = 10.0
        down_cooldown_s = 60.0

    class _AdmCfg:
        # Quota-based shedding: batch/ingest rates sized ~1.5x their
        # baseline share, so the clean baseline passes untouched and the
        # 4x step sheds exclusively from the low classes.
        enabled = True
        default_class = "interactive"
        header = "X-Traffic-Class"
        weights = "interactive=70,batch=20,ingest=10"
        rates = "batch=3,ingest=2"
        burst_s = 2.0
        max_inflight = 0
        parallel_hint = 8
        retry_after_max_s = 30.0

    tsdb = Tsdb()
    recorder = FlightRecorder(capacity=512)
    slo = SloEngine(_SloCfg(), tsdb=tsdb, recorder=recorder)
    admission = AdmissionController(_AdmCfg(), recorder=recorder, tsdb=tsdb)

    class _SimPool:
        """Duck-typed EnginePool: capacity is replicas x MU req/s.
        Attach/drain are instant (the real pool compiles on attach; the
        control-loop dynamics under test don't depend on that delay)."""

        def __init__(self) -> None:
            self.n = 1
            self.desired_replicas = 1

        def pool_size(self) -> int:
            return self.n

        def scale_to(self, n: int) -> dict:
            self.n = max(1, int(n))
            self.desired_replicas = self.n
            return {"size": self.n}

    pool = _SimPool()
    scaler = Autoscaler(
        pool, _AsCfg(), tsdb=tsdb, slo=slo, recorder=recorder
    )

    base = 1_000_000.0  # fixed epoch: rings only care about deltas
    t_step = base + ELASTIC_WARMUP_S
    t_recover = t_step + ELASTIC_STEP_S
    t_end = t_recover + ELASTIC_RECOVERY_S

    queue: list = []  # FIFO of (class, enqueue_ts)
    acc = {cls: 0.0 for cls, _ in ELASTIC_CLASS_MIX}
    arrivals = {cls: 0 for cls, _ in ELASTIC_CLASS_MIX}
    served = {cls: 0 for cls, _ in ELASTIC_CLASS_MIX}
    first_fire_ts = 0.0
    max_size = 1
    scale_events: list = []
    post_latencies: list = []
    peak_queue = 0

    t = base
    while t < t_end:
        rps = ELASTIC_BASE_RPS * (
            ELASTIC_STEP_FACTOR if t_step <= t < t_recover else 1
        )
        # Deterministic arrivals: fractional accumulator per class.
        for cls, share in ELASTIC_CLASS_MIX:
            acc[cls] += rps * share
            n_arr = int(acc[cls])
            acc[cls] -= n_arr
            for _ in range(n_arr):
                arrivals[cls] += 1
                d = admission.try_admit(cls, now=t, route="/generate")
                if d.admitted:
                    queue.append((cls, t))
                else:
                    # The middleware's 429: traced, fed to the SLO engine
                    # as a fast non-error (shedding is deliberate).
                    slo.note_request("/generate", 1.0, ts=t)
        # Serve FIFO up to this second's pool capacity.
        for _ in range(pool.n * ELASTIC_MU):
            if not queue:
                break
            cls, t_enq = queue.pop(0)
            lat_ms = (t - t_enq) * 1000.0 + ELASTIC_SERVICE_MS
            slo.note_request("/generate", lat_ms, ts=t)
            admission.release(cls, duration_ms=lat_ms)
            served[cls] += 1
            if t >= t_end - 300:
                post_latencies.append(lat_ms)
        peak_queue = max(peak_queue, len(queue))
        tsdb.record("engine.queued", float(len(queue)), ts=t)
        tsdb.record("engine.tick_ms", ELASTIC_SERVICE_MS / 10.0, ts=t)
        if not first_fire_ts and t >= t_step:
            if slo.evaluate(now=t, force=True)["fast_burn_firing"]:
                first_fire_ts = t
        event = scaler.tick(now=t)
        if event is not None:
            scale_events.append(event)
        max_size = max(max_size, pool.n)
        t += 1.0

    resolved = not slo.evaluate(now=t_end, force=True)["fast_burn_firing"]
    post_latencies.sort()
    post_p95 = (
        post_latencies[int(len(post_latencies) * 0.95)]
        if post_latencies
        else 0.0
    )
    snap = admission.snapshot()
    shed = snap["shed_total"]
    shed_classes = sorted(c for c, n in shed.items() if n > 0)
    interactive_success = served["interactive"] / max(
        arrivals["interactive"], 1
    )
    ups = sum(1 for e in scale_events if e["direction"] == "up")
    downs = sum(1 for e in scale_events if e["direction"] == "down")
    pinned_scale = sum(
        1
        for e in recorder.snapshot()
        if (e.get("attrs") or {}).get("autoscale")
    )

    # -- admission clean-path overhead: paired per-call deltas of the
    # REAL gate (classify + try_admit + release) around an identical
    # retrieval call, median-of-deltas like bench_obs/bench_chaos.
    dims = OBS_DIM
    embedder = HashEmbedder(dimensions=dims)
    word_pool = (
        "retrieval augmented generation embedding vector search pipeline "
        "index document query context tokens model attention transformer "
        "serving latency throughput batch deadline retry breaker fault"
    ).split()
    qrng = _random.Random(31)
    store = MemoryVectorStore(dims)
    texts = [
        " ".join(qrng.choice(word_pool) for _ in range(24))
        for _ in range(OBS_CORPUS_DOCS)
    ]
    store.add(
        [Chunk(text=t, source=f"doc{i % 64}.txt") for i, t in enumerate(texts)],
        embedder.embed_documents(texts),
    )
    queries = [
        " ".join(qrng.choice(word_pool) for _ in range(8)) for _ in range(256)
    ]
    fetch_k = OBS_TOP_K * 4

    def _raw(query: str) -> list:
        qs = embedder.embed_queries([query])
        hits = store.search_batch(qs, fetch_k)[0]
        qw = set(query.split())
        scores = [
            len(qw & set(h.chunk.text.split())) / max(len(qw), 1) for h in hits
        ]
        order = sorted(range(len(hits)), key=lambda i: -scores[i])
        return [hits[i] for i in order[:OBS_TOP_K]]

    class _OpenCfg(_AdmCfg):
        rates = ""  # clean path: classification + counting only

    gate = AdmissionController(
        _OpenCfg(), recorder=FlightRecorder(capacity=8), tsdb=Tsdb()
    )
    headers = {"X-Traffic-Class": "interactive"}

    def _gated(query: str) -> list:
        cls = gate.classify(headers)
        d = gate.try_admit(cls, route="/generate")
        t0 = time.perf_counter()
        try:
            return _raw(query)
        finally:
            gate.release(d.cls, (time.perf_counter() - t0) * 1000.0)

    _raw(queries[0])  # warm both paths before timing
    _gated(queries[0])
    raw_l: list[float] = []
    deltas: list[float] = []
    for i in range(ELASTIC_OVERHEAD_ITERS):
        q = queries[i % len(queries)]
        t0 = time.perf_counter()
        _raw(q)
        t1 = time.perf_counter()
        _gated(q)
        t2 = time.perf_counter()
        raw_l.append(t1 - t0)
        deltas.append((t2 - t1) - (t1 - t0))
    raw_l.sort()
    deltas.sort()
    raw_p50 = raw_l[len(raw_l) // 2] * 1000.0
    overhead_ms = deltas[len(deltas) // 2] * 1000.0
    overhead_pct = overhead_ms / max(raw_p50, 1e-9) * 100.0

    return {
        "elastic_base_rps": ELASTIC_BASE_RPS,
        "elastic_step_factor": ELASTIC_STEP_FACTOR,
        "elastic_fast_burn_fired": int(first_fire_ts > 0),
        "elastic_fire_latency_s": round(
            (first_fire_ts - t_step) if first_fire_ts else -1.0, 1
        ),
        "elastic_scaled_to": max_size,
        "elastic_scale_ups": ups,
        "elastic_scale_downs": downs,
        "elastic_pinned_scale_events": pinned_scale,
        "elastic_peak_queue": peak_queue,
        "elastic_alert_resolved": int(resolved),
        "elastic_post_p95_ms": round(post_p95, 1),
        "elastic_latency_slo_ms": ELASTIC_LATENCY_SLO_MS,
        "elastic_slo_ok": int(0 < post_p95 <= ELASTIC_LATENCY_SLO_MS),
        "elastic_interactive_success": round(interactive_success, 4),
        "elastic_shed_batch": shed.get("batch", 0),
        "elastic_shed_ingest": shed.get("ingest", 0),
        "elastic_shed_interactive": shed.get("interactive", 0),
        "elastic_shed_only_low": int(
            bool(shed_classes) and "interactive" not in shed_classes
        ),
        "elastic_admission_overhead_iters": ELASTIC_OVERHEAD_ITERS,
        "elastic_admission_raw_p50_ms": round(raw_p50, 3),
        "elastic_admission_overhead_ms": round(overhead_ms, 4),
        "elastic_admission_overhead_pct": round(overhead_pct, 2),
        "elastic_admission_gate_pct": ELASTIC_GATE_PCT,
        "elastic_admission_overhead_ok": int(overhead_pct <= ELASTIC_GATE_PCT),
    }


# Durability phase (round-16 lever): the WAL's clean-path cost and the
# crash-recovery drill.  Overhead is the bench_chaos paired-delta method —
# alternating raw/WAL-wrapped store appends on one thread, median per-pair
# delta over the raw p50 — because the quantity claimed (≤3%) is the WAL
# machinery itself, not fs noise.  The drill is a REAL kill: a child
# process bulk-ingests through the journaled pipeline, the parent SIGKILLs
# it mid-job (after the journal shows progress but before completion),
# restarts it, and asserts the resumed corpus is search-equivalent to an
# uninterrupted control run — no duplicated chunks, none lost.
DUR_DIM = 384
DUR_PREFILL_ROWS = 16384  # denominator carries a production-scale corpus
# (bench_cache runs 32768 docs; overhead must be judged against a store
# whose O(rows) append copy dominates, as it does in steady state).
DUR_BATCH = 32  # chunks per append (a bulk-ingest flush shape)
DUR_OVERHEAD_ITERS = 160  # paired raw/durable append samples
DUR_GATE_PCT = 3.0  # clean-path WAL overhead acceptance gate
DUR_CHILD_FILES = 16
DUR_CHILD_LINES = 4  # chunks per staged file
DUR_CHILD_PARSE_SLEEP_S = 0.08  # slows the child so the kill lands mid-job
DUR_KILL_AFTER_FILES = 4  # SIGKILL once the journal shows this many done
DUR_DRILL_TIMEOUT_S = 120.0


def _dur_child_corpus(staging: str) -> list[tuple[str, str]]:
    """Deterministic staged corpus: DUR_CHILD_FILES files of
    DUR_CHILD_LINES one-chunk lines each, identical in every run so the
    crashed+resumed corpus can be compared to the control's."""
    os.makedirs(staging, exist_ok=True)
    files = []
    for i in range(DUR_CHILD_FILES):
        name = f"doc{i:02d}.txt"
        path = os.path.join(staging, name)
        with open(path, "w", encoding="utf-8") as fh:
            for j in range(DUR_CHILD_LINES):
                fh.write(f"file {i} chunk {j} " + f"topic-{i}-{j} " * 8 + "\n")
        files.append((path, name))
    return files


def _durability_child(workdir: str) -> None:
    """Drill child: journaled bulk ingest into a WAL-wrapped store.

    Same command for both phases — if the journal holds an unfinished
    job (previous incarnation was SIGKILLed) it resumes it, otherwise it
    stages the corpus and submits fresh.  On completion it atomically
    writes ``child_result.json`` (rows, per-source counts, search
    results, recovery stats); a killed child never writes it."""
    from generativeaiexamples_tpu.durability.journal import IngestJournal
    from generativeaiexamples_tpu.durability.store import DurableVectorStore
    from generativeaiexamples_tpu.engine.embedder import HashEmbedder
    from generativeaiexamples_tpu.ingest.pipeline import IngestPipeline
    from generativeaiexamples_tpu.retrieval.base import Chunk
    from generativeaiexamples_tpu.retrieval.memory import MemoryVectorStore

    embedder = HashEmbedder(dimensions=DUR_DIM)
    store = DurableVectorStore(
        MemoryVectorStore(DUR_DIM),
        os.path.join(workdir, "store"),
        # Strictest cadence: the drill must not depend on losing few
        # enough records to land inside one group-commit window.
        fsync_every=1,
        snapshot_every_records=0,
    )
    journal = IngestJournal(os.path.join(workdir, "journal.log"))

    def parse(path: str, name: str) -> list[Chunk]:
        time.sleep(DUR_CHILD_PARSE_SLEEP_S)
        with open(path, encoding="utf-8") as fh:
            return [
                Chunk(text=line.strip(), source=name)
                for line in fh
                if line.strip()
            ]

    pipe = IngestPipeline(
        parse_fn=parse,
        embed_fn=embedder.embed_documents,
        append_fn=store.add,
        parse_workers=2,
        delete_files=True,
        journal=journal,
        delete_source_fn=store.delete_source,
        durable_flush_fn=store.flush,
    )
    resumed = bool(journal.unfinished_jobs())
    if resumed:
        job_ids = pipe.resume()
    else:
        job_ids = [pipe.submit(_dur_child_corpus(os.path.join(workdir, "staging")))]
    deadline = time.monotonic() + DUR_DRILL_TIMEOUT_S
    while time.monotonic() < deadline:
        if all(
            (pipe.status(j) or {}).get("status") != "running" for j in job_ids
        ):
            break
        time.sleep(0.02)
    pipe.close()
    counts: dict[str, int] = {}
    for c in store.inner._chunks:  # exact per-source census, bench-only
        counts[c.source] = counts.get(c.source, 0) + 1
    queries = [f"file {i} chunk {i % DUR_CHILD_LINES}" for i in range(8)]
    search = [
        [
            [h.chunk.source, h.chunk.text, round(h.score, 4)]
            for h in store.search(embedder.embed_documents([q])[0], 5)
        ]
        for q in queries
    ]
    result = {
        "resumed": resumed,
        "rows": len(store),
        "counts": counts,
        "search": search,
        "jobs": [pipe.status(j) for j in job_ids],
        "recovery": store.last_recovery,
    }
    store.close()
    journal.close()
    tmp_path = os.path.join(workdir, "child_result.json.tmp")
    with open(tmp_path, "w", encoding="utf-8") as fh:
        json.dump(result, fh)
        fh.flush()
        os.fsync(fh.fileno())
    os.replace(tmp_path, os.path.join(workdir, "child_result.json"))


def _dur_journal_done_count(path: str) -> tuple[int, bool]:
    """(file_done lines, job finished?) in a journal — parent-side poll."""
    done = 0
    finished = False
    try:
        with open(path, encoding="utf-8") as fh:
            for line in fh:
                if '"ev":"file_done"' in line:
                    done += 1
                elif '"ev":"job_done"' in line:
                    finished = True
    except OSError:
        pass
    return done, finished


def _durability_drill(out: dict) -> None:
    """SIGKILL mid-ingest, restart, compare against an uninterrupted run."""
    import shutil
    import signal
    import subprocess
    import sys
    import tempfile

    bench = os.path.abspath(__file__)
    control_dir = tempfile.mkdtemp(prefix="bench-dur-control-")
    crash_dir = tempfile.mkdtemp(prefix="bench-dur-crash-")
    try:
        cmd = [sys.executable, bench, "--durability-child"]
        # The drill's children are host-only and start while this
        # process may hold the chip: place them on the CPU explicitly.
        child_env = {**os.environ, "JAX_PLATFORMS": "cpu"}
        proc = subprocess.run(
            cmd + [control_dir],
            capture_output=True,
            text=True,
            timeout=DUR_DRILL_TIMEOUT_S,
            env=child_env,
        )
        control_path = os.path.join(control_dir, "child_result.json")
        if proc.returncode != 0 or not os.path.exists(control_path):
            raise RuntimeError(
                f"control run failed rc={proc.returncode}: "
                f"{proc.stderr[-300:]}"
            )
        with open(control_path, encoding="utf-8") as fh:
            control = json.load(fh)

        child = subprocess.Popen(
            cmd + [crash_dir],
            stdout=subprocess.DEVNULL,
            stderr=subprocess.DEVNULL,
            env=child_env,
        )
        journal_path = os.path.join(crash_dir, "journal.log")
        killed_after = -1
        deadline = time.monotonic() + DUR_DRILL_TIMEOUT_S
        while time.monotonic() < deadline:
            done, finished = _dur_journal_done_count(journal_path)
            if finished:
                break  # too fast to kill — the drill result records it
            if done >= DUR_KILL_AFTER_FILES:
                os.kill(child.pid, signal.SIGKILL)
                killed_after = done
                break
            time.sleep(0.005)
        child.wait(timeout=30)
        out["durability_drill_killed_after_files"] = killed_after
        if killed_after < 0:
            raise RuntimeError("drill child finished before the kill window")
        if os.path.exists(os.path.join(crash_dir, "child_result.json")):
            raise RuntimeError("killed child still wrote its result marker")

        t0 = time.perf_counter()
        proc = subprocess.run(
            cmd + [crash_dir],
            capture_output=True,
            text=True,
            timeout=DUR_DRILL_TIMEOUT_S,
            env=child_env,
        )
        restart_ms = (time.perf_counter() - t0) * 1000.0
        crash_path = os.path.join(crash_dir, "child_result.json")
        if proc.returncode != 0 or not os.path.exists(crash_path):
            raise RuntimeError(
                f"resume run failed rc={proc.returncode}: "
                f"{proc.stderr[-300:]}"
            )
        with open(crash_path, encoding="utf-8") as fh:
            crash = json.load(fh)

        recovery = crash.get("recovery") or {}
        no_dup_no_loss = crash["counts"] == control["counts"]
        search_equiv = crash["search"] == control["search"]
        jobs = crash.get("jobs") or []
        job_complete = bool(jobs) and all(
            j and j.get("status") == "done" and j.get("files_done") == DUR_CHILD_FILES
            for j in jobs
        )
        out.update(
            {
                "durability_drill_resumed": int(bool(crash.get("resumed"))),
                "durability_drill_rows": crash["rows"],
                "durability_drill_control_rows": control["rows"],
                "durability_drill_no_dup_no_loss": int(no_dup_no_loss),
                "durability_drill_search_equivalent": int(search_equiv),
                "durability_drill_job_complete": int(job_complete),
                "durability_drill_replayed_records": recovery.get(
                    "replayed_records", 0
                ),
                "durability_drill_torn_tail": int(
                    bool(recovery.get("torn_tail"))
                ),
                "durability_recovery_ms": round(
                    float(recovery.get("duration_ms", 0.0)), 3
                ),
                "durability_restart_to_complete_ms": round(restart_ms, 1),
                "durability_drill_ok": int(
                    bool(crash.get("resumed"))
                    and no_dup_no_loss
                    and search_equiv
                    and job_complete
                ),
            }
        )
    finally:
        shutil.rmtree(control_dir, ignore_errors=True)
        shutil.rmtree(crash_dir, ignore_errors=True)


def bench_durability() -> dict:
    """WAL clean-path overhead + snapshot/bootstrap cost + the
    kill-restart drill (`--durability` standalone; CPU-only, ~1 min)."""
    import shutil
    import tempfile

    from generativeaiexamples_tpu.durability import metrics as dur_metrics
    from generativeaiexamples_tpu.durability.store import (
        DurableVectorStore,
        hydrate_store,
    )
    from generativeaiexamples_tpu.retrieval.base import Chunk
    from generativeaiexamples_tpu.retrieval.memory import MemoryVectorStore

    dur_metrics.reset_durability_metrics()
    out: dict = {
        "durability_overhead_iters": DUR_OVERHEAD_ITERS,
        "durability_gate_pct": DUR_GATE_PCT,
    }
    rng = np.random.default_rng(7)
    tmp = tempfile.mkdtemp(prefix="bench-dur-")

    def make_batch(tag: str, n: int) -> tuple[list, np.ndarray]:
        chunks = [
            Chunk(text=f"{tag} passage {i} " * 6, source=f"{tag}.txt")
            for i in range(n)
        ]
        embs = rng.standard_normal((n, DUR_DIM)).astype(np.float32)
        return chunks, embs

    try:
        raw = MemoryVectorStore(DUR_DIM)
        durable = DurableVectorStore(
            MemoryVectorStore(DUR_DIM),
            os.path.join(tmp, "store"),
            fsync_every=16,  # the default production cadence
            snapshot_every_records=0,  # snapshot cost measured separately
        )
        # Identical pre-fill on both sides: MemoryVectorStore.add copies
        # the whole matrix, so an empty-store denominator would overstate
        # the WAL's relative cost ~100x.
        for j in range(DUR_PREFILL_ROWS // 256):
            chunks, embs = make_batch(f"seed{j}", 256)
            raw.add(chunks, embs)
            durable.add(
                [Chunk(text=c.text, source=c.source) for c in chunks], embs
            )
        raw_l: list[float] = []
        deltas: list[float] = []
        for i in range(DUR_OVERHEAD_ITERS):
            chunks, embs = make_batch(f"it{i}", DUR_BATCH)
            mirror = [Chunk(text=c.text, source=c.source) for c in chunks]
            t0 = time.perf_counter()
            raw.add(chunks, embs)
            t1 = time.perf_counter()
            durable.add(mirror, embs)
            t2 = time.perf_counter()
            raw_l.append(t1 - t0)
            # Same payload back-to-back on one thread (bench_chaos
            # method): the per-pair delta is the WAL encode+write+fsync
            # machinery; its median cancels allocator/page-cache drift.
            deltas.append((t2 - t1) - (t1 - t0))
        raw_l.sort()
        deltas.sort()
        raw_p50 = raw_l[len(raw_l) // 2] * 1000.0
        overhead_ms = deltas[len(deltas) // 2] * 1000.0
        overhead_pct = overhead_ms / max(raw_p50, 1e-9) * 100.0
        out.update(
            {
                "durability_overhead_raw_p50_ms": round(raw_p50, 3),
                "durability_overhead_ms": round(overhead_ms, 4),
                "durability_overhead_pct": round(overhead_pct, 2),
                "durability_overhead_ok": int(overhead_pct <= DUR_GATE_PCT),
                "durability_wal_rows": len(durable),
            }
        )

        # Snapshot cost + the replica-bootstrap path over the same corpus.
        t0 = time.perf_counter()
        durable.snapshot()
        out["durability_snapshot_ms"] = round(
            (time.perf_counter() - t0) * 1000.0, 1
        )
        t0 = time.perf_counter()
        boot, boot_stats = hydrate_store(
            os.path.join(tmp, "store"), MemoryVectorStore(DUR_DIM)
        )
        out["durability_bootstrap_ms"] = round(
            (time.perf_counter() - t0) * 1000.0, 1
        )
        out["durability_bootstrap_rows"] = len(boot)
        out["durability_bootstrap_ok"] = int(
            len(boot) == len(durable)
            and bool(boot_stats.get("snapshot_restored"))
        )
        durable.close()
    finally:
        shutil.rmtree(tmp, ignore_errors=True)

    _durability_drill(out)
    dur = dur_metrics.durability_snapshot()
    out["durability_metrics_wal_appends"] = sum(
        dur.get("wal_records", {}).values()
    )
    dur_metrics.reset_durability_metrics()  # never leak into later phases
    return out


# Gray-failure phase (round-17 lever): one replica of a 3-replica pool is
# slowed (not killed) with the `replica:latency` fault; the drill accepts
# only if the continuous layer — brownout scoring, scored routing, hedged
# requests, straggler ejection — holds tail latency without firing the
# SLO fast-burn page, and re-admits the replica once it recovers.  The
# clean-path cost of the layer is the bench_chaos paired-delta method:
# the same pool serves alternating non-hedgeable/hedgeable requests
# (hedge delay floored far above any real latency, so the timer arms and
# cancels but never fires — the machinery cost without the hedges).
GRAY_REPLICAS = 3
GRAY_MAX_LEN = 64
GRAY_DECODE = 8  # <= hedge_max_tokens: every request is hedge-eligible
GRAY_WARM_REQS = 6  # compile + prefix warmup, untimed
# Enough samples that nearest-rank p99 is not the single worst sample:
# at ~5 ms per request on host, one OS-jitter outlier must not decide
# the ratio gate.
GRAY_CLEAN_REQS = 120
GRAY_BRIDGE_REQS = 12  # traffic during the brownout, pre-ejection
GRAY_MEASURED_REQS = 120
GRAY_FAULT_MS = 200  # per-tick straggler latency (vs ~ms healthy ticks)
GRAY_LATENCY_SLO_MS = 1500.0  # an unmitigated straggler request breaches
GRAY_P99_RATIO_GATE = 1.5
GRAY_HEDGE_LOAD_GATE_PCT = 5.0
GRAY_EJECT_TIMEOUT_S = 45.0
GRAY_RECOVER_TIMEOUT_S = 90.0
GRAY_OVERHEAD_ITERS = 60
GRAY_GATE_PCT = 3.0  # clean-path overhead acceptance gate


def bench_gray() -> dict:
    """Gray-failure tolerance acceptance: brownout -> score -> eject ->
    recover -> re-admit, with hedged requests bridging the detection gap
    and the SLO page staying quiet throughout."""
    import queue as _q

    from generativeaiexamples_tpu.core.configuration import HealthConfig
    from generativeaiexamples_tpu.engine.replica import EnginePool
    from generativeaiexamples_tpu.engine.sampler import SamplingParams
    from generativeaiexamples_tpu.engine.scheduler import Request, Scheduler
    from generativeaiexamples_tpu.models import llama
    from generativeaiexamples_tpu.obs.recorder import FlightRecorder
    from generativeaiexamples_tpu.obs.slo import SloEngine
    from generativeaiexamples_tpu.obs.tsdb import Tsdb
    from generativeaiexamples_tpu.resilience.faults import (
        get_fault_injector,
        reset_faults,
    )

    cfg = llama.llama_tiny(dtype="float32", max_seq_len=GRAY_MAX_LEN)
    rng = np.random.default_rng(41)

    class _SloCfg:
        enabled = True
        availability_target = 0.999
        latency_p95_ms = f"/generate={GRAY_LATENCY_SLO_MS:.0f}"
        fast_window_s = 300.0
        slow_window_s = 1800.0
        fast_burn_threshold = 14.4
        slow_burn_threshold = 6.0
        evaluation_period_s = 0.0

    def _health(**kw) -> HealthConfig:
        # Drill-paced dwell times; production defaults are in
        # core/configuration.py (same machine, longer clocks).
        base = dict(
            enabled=True,
            window_s=3.0,
            tick_tolerance=2.5,
            score_smoothing=0.6,
            eject_threshold=0.5,
            eject_after_s=1.0,
            readmit_score=0.8,
            readmit_after_s=1.0,
            probation_s=1.0,
            max_eject_fraction=0.5,
            hedge_enabled=True,
            hedge_budget_ratio=0.05,
            hedge_burst=2.0,
            hedge_min_delay_ms=30.0,
            hedge_max_tokens=32,
        )
        base.update(kw)
        return HealthConfig(**base)

    def _schedulers(n: int) -> list:
        return [
            Scheduler(
                cfg,
                max_batch=2,
                max_len=GRAY_MAX_LEN,
                decode_chunk_size=4,
                seed=11,
                prefix_cache="off",
            )
            for _ in range(n)
        ]

    def _ask(pool, rid: str, hedgeable: bool = True, prompt=None) -> float:
        done: "_q.Queue[str]" = _q.Queue()
        if prompt is None:
            prompt = rng.integers(1, cfg.vocab_size, (12,)).tolist()
        t0 = time.perf_counter()
        pool.submit(
            Request(
                token_ids=prompt,
                sampling=SamplingParams(
                    temperature=0.0, max_tokens=GRAY_DECODE
                ),
                on_token=lambda t: None,
                on_done=done.put,
                id=rid,
                hedgeable=hedgeable,
            )
        )
        done.get(timeout=300)
        return (time.perf_counter() - t0) * 1000.0

    def _pump(pool, until, timeout_s: float) -> float:
        """Run the monitor loop by hand until ``until()`` (returns the
        elapsed seconds, or -1.0 on timeout)."""
        t0 = time.monotonic()
        while time.monotonic() - t0 < timeout_s:
            pool._feed_tsdb()
            pool.check_replicas()
            if until():
                return time.monotonic() - t0
            time.sleep(0.25)
        return -1.0

    def _p99(xs: list) -> float:
        import math

        ordered = sorted(xs)
        # Nearest-rank: ceil(0.99 n)-th order statistic, so with >=100
        # samples the worst sample alone does not define the p99.
        return ordered[max(0, math.ceil(len(ordered) * 0.99) - 1)]

    tsdb = Tsdb()
    recorder = FlightRecorder(capacity=512)
    slo = SloEngine(_SloCfg(), tsdb=tsdb, recorder=recorder)
    pool = EnginePool(
        _schedulers(GRAY_REPLICAS),
        policy="least_loaded",
        health_interval=None,  # the drill drives the monitor pass itself
        health_cfg=_health(),
        tsdb=tsdb,
        recorder=recorder,
    )
    pool.start()
    out: dict = {
        "gray_replicas": GRAY_REPLICAS,
        "gray_fault_ms": GRAY_FAULT_MS,
        "gray_latency_slo_ms": GRAY_LATENCY_SLO_MS,
    }
    try:
        # Warmup is non-hedgeable: compile-time latencies must not feed
        # the hedge-delay estimator (a p95 learned from JIT compiles
        # would postpone every hedge past the straggler itself).
        for i in range(GRAY_WARM_REQS):
            _ask(pool, f"gray-warm-{i}", hedgeable=False)

        # -- clean wave: baseline tail + organic hedger warmup ----------
        clean: list[float] = []
        for i in range(GRAY_CLEAN_REQS):
            ms = _ask(pool, f"gray-clean-{i}")
            clean.append(ms)
            slo.note_request("/generate", ms)
        # Let the scorer see a healthy fleet before the brownout.
        _pump(pool, lambda: True, 5.0)
        clean_p99 = _p99(clean)

        # -- brownout: replica 0 ticks gain GRAY_FAULT_MS each ----------
        get_fault_injector().configure(
            f"replica:latency={GRAY_FAULT_MS},index=0"
        )
        t_fault = time.monotonic()
        # Bridge traffic lands before any scoring pass has seen the
        # straggler.  A concurrent burst (prompts pre-drawn: the rng is
        # not thread-safe) spreads placements across all replicas —
        # whatever lands on the straggler sits token-less behind its
        # injected sleep, which is exactly what the hedge timer rescues.
        bridge: list[float] = []
        bridge_lock = threading.Lock()
        prompts = [
            rng.integers(1, cfg.vocab_size, (12,)).tolist()
            for _ in range(GRAY_BRIDGE_REQS)
        ]

        def _bridge_one(i: int) -> None:
            ms = _ask(pool, f"gray-bridge-{i}", prompt=prompts[i])
            with bridge_lock:
                bridge.append(ms)
            slo.note_request("/generate", ms)

        workers = [
            threading.Thread(target=_bridge_one, args=(i,))
            for i in range(GRAY_BRIDGE_REQS)
        ]
        for w in workers:
            w.start()
        for w in workers:
            w.join(timeout=300)
        eject_s = _pump(
            pool, lambda: pool.ejected_count() >= 1, GRAY_EJECT_TIMEOUT_S
        )
        if eject_s >= 0:
            # Report from fault injection, not from pump start: the
            # bridge wave above is part of the detection window.
            eject_s = time.monotonic() - t_fault
        out["gray_ejected"] = int(pool.ejected_count() >= 1)
        out["gray_eject_latency_s"] = round(max(eject_s, -1.0), 2)

        # -- measured wave: the straggler is quarantined ----------------
        faulted: list[float] = []
        for i in range(GRAY_MEASURED_REQS):
            ms = _ask(pool, f"gray-meas-{i}")
            faulted.append(ms)
            slo.note_request("/generate", ms)
        faulted_p99 = _p99(faulted)

        # -- recovery: clear the fault, wait for probation -> healthy ---
        reset_faults()
        t_clear = time.monotonic()
        recover_s = _pump(
            pool,
            lambda: (
                pool.readmissions_total >= 1
                and pool.replicas[0].state == "healthy"
            ),
            GRAY_RECOVER_TIMEOUT_S,
        )
        out["gray_readmitted"] = int(pool.readmissions_total >= 1)
        out["gray_recovered"] = int(pool.replicas[0].state == "healthy")
        out["gray_recovery_s"] = round(
            (time.monotonic() - t_clear) if recover_s >= 0 else -1.0, 2
        )

        hsnap = pool.hedger.snapshot()
        eligible = max(int(hsnap["hedge_eligible_total"]), 1)
        extra_pct = hsnap["hedge_fired_total"] / eligible * 100.0
        burn = slo.evaluate(force=True)
        pins = sum(
            1
            for e in recorder.snapshot()
            if any(
                str(d).startswith("gray:") for d in (e.get("degraded") or [])
            )
        )
        ratio = faulted_p99 / max(clean_p99, 1e-9)
        out.update(
            {
                "gray_clean_p99_ms": round(clean_p99, 1),
                "gray_bridge_p99_ms": round(_p99(bridge), 1),
                "gray_faulted_p99_ms": round(faulted_p99, 1),
                "gray_p99_ratio": round(ratio, 3),
                "gray_p99_gate": GRAY_P99_RATIO_GATE,
                "gray_p99_ok": int(ratio <= GRAY_P99_RATIO_GATE),
                "gray_fast_burn_fired": int(burn["fast_burn_firing"]),
                "gray_hedge_eligible": int(hsnap["hedge_eligible_total"]),
                "gray_hedge_fired": int(hsnap["hedge_fired_total"]),
                "gray_hedge_wins": int(hsnap["hedge_wins_total"]),
                "gray_hedge_suppressed": int(hsnap["hedge_suppressed_total"]),
                "gray_hedge_extra_load_pct": round(extra_pct, 2),
                "gray_hedge_load_gate_pct": GRAY_HEDGE_LOAD_GATE_PCT,
                "gray_hedge_load_ok": int(
                    extra_pct <= GRAY_HEDGE_LOAD_GATE_PCT
                ),
                "gray_pinned_transitions": pins,
            }
        )
    finally:
        reset_faults()
        pool.stop()

    # -- clean-path overhead: paired non-hedgeable/hedgeable requests on
    # one scored pool whose hedge delay can never elapse — the delta is
    # the per-request cost of the gray layer (eligibility check, budget
    # deposit, timer arm/cancel) on top of identical serving work.
    opool = EnginePool(
        _schedulers(2),
        policy="least_loaded",
        health_interval=None,
        health_cfg=_health(hedge_min_delay_ms=5000.0),
        tsdb=Tsdb(),
        recorder=FlightRecorder(capacity=8),
    )
    opool.start()
    try:
        # Warm compiles AND the hedger past WARMUP_SAMPLES so the gated
        # path actually arms (and cancels) a timer per request.
        for i in range(12):
            _ask(opool, f"gray-ovr-warm-{i}", hedgeable=True)
        raw_l: list[float] = []
        deltas: list[float] = []
        for i in range(GRAY_OVERHEAD_ITERS):
            raw = _ask(opool, f"gray-ovr-raw-{i}", hedgeable=False)
            gated = _ask(opool, f"gray-ovr-hdg-{i}", hedgeable=True)
            raw_l.append(raw)
            deltas.append(gated - raw)
    finally:
        opool.stop()
    raw_l.sort()
    deltas.sort()
    raw_p50 = raw_l[len(raw_l) // 2]
    overhead_ms = deltas[len(deltas) // 2]
    overhead_pct = overhead_ms / max(raw_p50, 1e-9) * 100.0
    out.update(
        {
            "gray_overhead_iters": GRAY_OVERHEAD_ITERS,
            "gray_raw_p50_ms": round(raw_p50, 3),
            "gray_overhead_ms": round(overhead_ms, 4),
            "gray_overhead_pct": round(overhead_pct, 2),
            "gray_overhead_gate_pct": GRAY_GATE_PCT,
            "gray_overhead_ok": int(overhead_pct <= GRAY_GATE_PCT),
            "gray_note": (
                "tiny-config pools on host — the transferable quantities "
                "are the ratios and the control-loop behaviour (eject/"
                "re-admit latency, hedge budget adherence), not absolute "
                "latencies"
            ),
        }
    )
    return out


def bench_fused() -> dict:
    """Fused W8A8 decode phase (round-19 lever): ops/qmm.py end to end.

    Three measurements, two gates:

    * **Kernel microbench** on the PERF_NOTES probe tile
      ((128x4096)@(4096x14336), the shape the 0.306 ms winning probe
      measured): effective GB/s over the int8 weight bytes, streaming
      Pallas kernel vs the XLA twin.
    * **Offline 128/128 decode** tok/s, fused (pallas_w8a8) vs the
      weight-only int8 XLA serving path — the 2.3x projection's
      numerator and denominator.
    * **Spec on/off**: the same fused params through the speculative
      scheduler (early-exit self-draft — zero extra weights) vs plain
      decode, since PR 14's verify forwards multiply the value of every
      per-step millisecond.

    Gates (the CPU capture's job): greedy bit-identity kernel-vs-twin on
    the SAME blocked params, and tile-once loading (BLOCK_EVENTS flat
    across all decode).  GAIE_FUSED_TINY=1 shrinks to tiny geometry so
    the glue runs hermetically on CPU in ~a minute (interpret-mode
    kernel); no chip number is on record yet.
    """
    import jax
    import jax.numpy as jnp

    from generativeaiexamples_tpu.engine.decode import (
        init_random_int8_params,
        prepare_params,
    )
    from generativeaiexamples_tpu.engine.generator import LlamaGenerator
    from generativeaiexamples_tpu.engine.sampler import SamplingParams
    from generativeaiexamples_tpu.models import llama
    from generativeaiexamples_tpu.ops import qmm
    from generativeaiexamples_tpu.ops.quant import quantize_matrix

    tiny = bool(os.environ.get("GAIE_FUSED_TINY"))
    platform = jax.devices()[0].platform
    on_tpu = platform == "tpu"
    if tiny:
        cfg = llama.llama_tiny(dtype="float32", max_seq_len=64)
        mb_m, mb_k, mb_n = 8, 256, 512
        batch, prompt_len, steps, chunk = 2, 8, 8, 4
        reps = 3
    else:
        cfg = llama.llama3_8b(max_seq_len=MAX_LEN, kv_dtype=KV_DTYPE)
        mb_m, mb_k, mb_n = 128, 4096, 14336  # the round-18 probe tile
        batch, prompt_len, steps, chunk = 64, PROMPT_LEN, DECODE_STEPS, 64
        reps = 20

    out: dict = {
        "fused_platform": platform,
        "fused_tile_mkn": [mb_m, mb_k, mb_n],
        "fused_tiny": tiny,
    }

    # --- Kernel microbench: GB/s over the int8 weight bytes ------------
    rng = np.random.default_rng(0)
    w = jnp.asarray(rng.standard_normal((mb_k, mb_n)), jnp.float32)
    bw = qmm.block_matrix(quantize_matrix(w))
    x = jnp.asarray(
        rng.standard_normal((mb_m, mb_k)), jnp.float32
    ).astype(cfg.compute_dtype)
    int8_bytes = mb_k * mb_n  # the stream the kernel exists to halve

    def time_matmul(env: dict) -> float:
        for k, v in env.items():
            os.environ[k] = v
        try:
            fn = jax.jit(lambda a: qmm.q_matmul(a, bw))
            fn(x).block_until_ready()  # compile
            t0 = time.perf_counter()
            for _ in range(reps):
                r = fn(x)
            r.block_until_ready()
            return (time.perf_counter() - t0) / reps
        finally:
            for k in env:
                os.environ.pop(k, None)

    xla_s = time_matmul({"GAIE_DISABLE_QMM_KERNEL": "1"})
    # On TPU the kernel dispatches natively; off-TPU it only engages in
    # interpret mode, whose timings are meaningless — reuse the twin's
    # so the capture stays structurally identical across platforms.
    kernel_s = time_matmul({}) if on_tpu else xla_s
    out.update(
        {
            "fused_kernel_engaged": bool(on_tpu),
            "fused_kernel_ms": round(kernel_s * 1e3, 4),
            "fused_xla_ms": round(xla_s * 1e3, 4),
            "fused_kernel_gbps": round(int8_bytes / kernel_s / 1e9, 1),
            "fused_xla_gbps": round(int8_bytes / xla_s / 1e9, 1),
        }
    )

    # Bit-identity gate #1, kernel vs twin on the microbench tile: the
    # real kernel on TPU, interpret mode (tiny tile to bound runtime)
    # elsewhere.
    if on_tpu:
        ident_env = {}
        bx, bbw = x, bw
    else:
        ident_env = {"GAIE_QMM_INTERPRET": "1"}
        bx = x[: min(mb_m, 8), :256] if not tiny else x
        bbw = (
            qmm.block_matrix(quantize_matrix(w[:256, :512])) if not tiny else bw
        )
    for k, v in ident_env.items():
        os.environ[k] = v
    try:
        kernel_out = np.asarray(qmm.q_matmul(bx, bbw))
    finally:
        for k in ident_env:
            os.environ.pop(k, None)
    os.environ["GAIE_DISABLE_QMM_KERNEL"] = "1"
    try:
        twin_out = np.asarray(qmm.q_matmul(bx, bbw))
    finally:
        os.environ.pop("GAIE_DISABLE_QMM_KERNEL", None)
    out["fused_tile_bit_identical"] = bool((kernel_out == twin_out).all())

    if os.environ.get("GAIE_FUSED_SMOKE"):
        # Glue-smoke profile (meant with GAIE_FUSED_TINY): gate the
        # load-time blocking contract without paying for the generator/
        # scheduler compiles — the full phase runs in tests/test_qmm.py
        # (chip_smoke.py decodes through the kernel on the chip).
        raw = init_random_int8_params(cfg, jax.random.PRNGKey(0))
        packed = prepare_params(cfg, raw, None, pack=True)
        ev0 = qmm.BLOCK_EVENTS["count"]
        blocked = prepare_params(
            cfg, packed, None, matmul_kernel="pallas_w8a8"
        )
        ev_load = qmm.BLOCK_EVENTS["count"]
        prepare_params(cfg, blocked, None, matmul_kernel="pallas_w8a8")
        out.update(
            {
                "fused_smoke": True,
                "fused_block_events_per_load": ev_load - ev0,
                # Re-preparing already-blocked params must tile nothing.
                "fused_block_events_flat": (
                    ev_load - ev0 == 4
                    and qmm.BLOCK_EVENTS["count"] - ev0 == 4
                ),
                "fused_note": (
                    "smoke profile: microbench + tile bit-identity + "
                    "load-time blocking only"
                ),
            }
        )
        return out

    # --- Offline decode: fused vs the weight-only int8 XLA path --------
    raw = init_random_int8_params(cfg, jax.random.PRNGKey(0))
    packed = prepare_params(cfg, raw, None, pack=True)
    prompts = [
        rng.integers(0, cfg.vocab_size, (prompt_len,)).tolist()
        for _ in range(batch)
    ]
    sp = SamplingParams(temperature=0.0, max_tokens=steps)

    def decode_tps(matmul_kernel, env: dict) -> tuple[float, list]:
        for k, v in env.items():
            os.environ[k] = v
        try:
            gen = LlamaGenerator(
                cfg,
                params=packed,
                max_batch=batch,
                max_len=prompt_len + steps,
                decode_chunk_size=chunk,
                quantize=False,
                pack=False,  # already packed; blocking rides the kwarg
                matmul_kernel=matmul_kernel,
            )
            gen.generate(prompts, sp)  # warm/compile
            best = 0.0
            for _ in range(2 if tiny else 3):
                t0 = time.perf_counter()
                results = gen.generate(prompts, sp)
                dt = time.perf_counter() - t0
                best = max(best, sum(len(r.token_ids) for r in results) / dt)
            bits = [r.token_ids for r in results]
            del gen
            return best, bits
        finally:
            for k in env:
                os.environ.pop(k, None)

    ev0 = qmm.BLOCK_EVENTS["count"]
    fused_env = {} if on_tpu else {"GAIE_QMM_INTERPRET": "1"}
    if tiny or on_tpu:
        fused_tps, fused_bits = decode_tps("pallas_w8a8", fused_env)
    else:
        # Full-size interpret-mode decode is infeasible; measure the
        # twin (same blocked arithmetic, XLA execution).
        fused_tps, fused_bits = decode_tps("pallas_w8a8", {})
    ev_load = qmm.BLOCK_EVENTS["count"]
    twin_tps, twin_bits = decode_tps(
        "pallas_w8a8", {"GAIE_DISABLE_QMM_KERNEL": "1"}
    )
    xla_tps, _ = decode_tps(None, {})
    out.update(
        {
            "fused_decode_tokens_per_sec": round(fused_tps, 1),
            "fused_twin_tokens_per_sec": round(twin_tps, 1),
            "fused_baseline_tokens_per_sec": round(xla_tps, 1),
            "fused_vs_xla_speedup": round(fused_tps / max(xla_tps, 1e-9), 3),
            # Gate #2: greedy decode bit-identity, kernel vs twin, through
            # the full generator (prefill + chunked decode + sampling).
            "fused_greedy_bit_identical": fused_bits == twin_bits,
            # Gate #3: blocking happened at load only — 4 projections per
            # fused-generator construction (the twin generator blocks its
            # own copy, the xla-path one blocks nothing), never per step.
            "fused_block_events_per_load": (ev_load - ev0),
            "fused_block_events_flat": (
                ev_load - ev0 == 4
                and qmm.BLOCK_EVENTS["count"] - ev0 == 8
            ),
        }
    )

    # --- Spec on/off on the fused params --------------------------------
    try:
        import queue as _q

        from generativeaiexamples_tpu.engine.scheduler import (
            Request,
            Scheduler,
        )
        from generativeaiexamples_tpu.engine.spec_decode import self_draft

        blocked = prepare_params(
            cfg, packed, None, matmul_kernel="pallas_w8a8"
        )
        dcfg, dparams = self_draft(
            cfg, blocked, 1 if tiny else cfg.n_layers // 4
        )
        spec_batch = min(batch, 16)

        def sched_tps(spec: bool, env: dict) -> float:
            for k, v in env.items():
                os.environ[k] = v
            try:
                kw = dict(
                    max_batch=spec_batch,
                    max_len=prompt_len + steps + 8,
                    decode_chunk_size=min(chunk, 8),
                    seed=3,
                    matmul_kernel="pallas_w8a8",
                )
                if spec:
                    kw.update(
                        draft_cfg=dcfg,
                        draft_params=dparams,
                        draft_quantize=False,
                        gamma=2 if tiny else 4,
                    )
                sched = Scheduler(cfg, blocked, **kw)
                sched.start()
                try:
                    best = 0.0
                    for timed in (False, True):
                        done: "_q.Queue[str]" = _q.Queue()
                        n_tok = [0]
                        t0 = time.perf_counter()
                        for i in range(spec_batch):
                            sched.submit(
                                Request(
                                    token_ids=list(prompts[i]),
                                    sampling=sp,
                                    on_token=lambda t: n_tok.__setitem__(
                                        0, n_tok[0] + 1
                                    ),
                                    on_done=done.put,
                                    id=f"fused-{spec}-{timed}-{i}",
                                )
                            )
                        for _ in range(spec_batch):
                            done.get(timeout=900)
                        if timed:
                            best = n_tok[0] / (time.perf_counter() - t0)
                    return best
                finally:
                    sched.stop()
            finally:
                for k in env:
                    os.environ.pop(k, None)

        spec_env = fused_env if (tiny or on_tpu) else {}
        spec_off = sched_tps(False, spec_env)
        spec_on = sched_tps(True, spec_env)
        out.update(
            {
                "fused_spec_off_tokens_per_sec": round(spec_off, 1),
                "fused_spec_on_tokens_per_sec": round(spec_on, 1),
                "fused_spec_speedup": round(
                    spec_on / max(spec_off, 1e-9), 3
                ),
            }
        )
    except Exception as e:  # noqa: BLE001 — optional sub-phase
        import traceback

        traceback.print_exc()
        out["fused_spec_error"] = f"{type(e).__name__}: {e}"[:500]

    out["fused_note"] = (
        "kernel GB/s over int8 weight bytes; decode fused (pallas_w8a8) vs weight-only int8 XLA; "
        "bit-identity + tile-once gates mechanism on any platform"
    )
    return out


def bench_paged() -> dict:
    """Paged KV cache phase (round-21 lever): block page tables, CoW
    shared-prefix pages, and the paged decode path end to end.

    Four acceptance gates:

    1. **paged_pass_parity** — greedy decode through the FULL scheduler
       is bit-identical paged vs contiguous on cold, grafted, and
       speculative admission paths.  Always tiny geometry: parity is a
       correctness property, not a throughput one, and every CPU
       dispatch reads through the XLA twins.
    2. **paged_pass_throughput** — the per-lane page-window advantage
       at the largest benched batch.  On TPU this is wall clock: decode
       tok/s on a skewed-length ragged batch >= 1.3x contiguous (the
       kernel walks ``ceil(len_i/page_tokens)`` pages per lane while
       every contiguous lane pays the batch-max pow2 bucket) and
       >= 1.0x on a uniform batch.  On CPU both layouts read through
       XLA twins that fetch the *identical* logical window — that
       symmetry is what makes gate 1's bit-parity possible — so the
       per-lane walk is a kernel property CPU wall clock cannot
       express; the CPU gate instead checks the attention-traffic
       ratio that bounds TPU decode time (decode attention is
       HBM-bound, PERF_NOTES round 2): skewed >= 1.3x, uniform
       >= 1.0x, plus wall-clock non-regression of the gather twin
       (paged >= 0.8x contiguous on both workloads).
    3. **paged_pass_shared_bytes** — a 64-way shared-prefix workload
       holds <= 0.5x the contiguous KV bytes, measured from the pool's
       page gauges (``pages_total - pages_free``, the same numbers the
       ``engine_kv_pages_*`` exposition exports), not analytically.
    4. **paged_pass_leaks** — after every workload drains (parked
       segments dropped, slots reset) each pool is all-free with only
       the pinned garbage page referenced: zero page leaks.

    GAIE_PAGED_TINY=1 shrinks to tiny geometry for the hermetic CPU
    capture (perf/captures/bench_paged_cpu_r21.json); no chip number is
    on record yet.  GAIE_PAGED_SMOKE=1 further
    shrinks to key/contract coverage for tests/test_bench_glue.py
    (one batch, one rep, no speculative parity pair).
    """
    import dataclasses
    import queue as _queue

    import jax
    import jax.numpy as jnp

    from generativeaiexamples_tpu.engine.decode import (
        init_random_int8_params,
        make_decode_chunk_fn,
        make_paged_decode_chunk_fn,
        prepare_cache,
        prepare_paged_pool,
        prepare_params,
    )
    from generativeaiexamples_tpu.engine.paged_kv import PAGE_EVENTS
    from generativeaiexamples_tpu.engine.sampler import SamplingParams
    from generativeaiexamples_tpu.engine.scheduler import Request, Scheduler
    from generativeaiexamples_tpu.models import llama

    tiny = bool(os.environ.get("GAIE_PAGED_TINY"))
    smoke = bool(os.environ.get("GAIE_PAGED_SMOKE"))
    platform = jax.devices()[0].platform
    tcfg = llama.llama_tiny(dtype="float32", max_seq_len=128, kv_dtype="int8")
    if tiny or smoke:
        cfg = tcfg
        batches, max_len, pt, steps, reps = [4, 8], 128, 16, 4, 3
        if smoke:
            batches, reps = [4], 1
    else:
        cfg = llama.llama3_8b(max_seq_len=MAX_LEN, kv_dtype=KV_DTYPE)
        # kv_page_size=64 is the serving default and the smallest
        # kernel-eligible page; bench what deployments run.
        batches, max_len, pt, steps, reps = [64, 192], MAX_LEN, 64, 16, 5

    # Per-token KV row: int8 k + int8 v + bf16 k/v scales, all layers.
    kv_heads = cfg.n_kv_heads or cfg.n_heads
    row_bytes = cfg.n_layers * kv_heads * (2 * cfg.head_dim + 4)
    rng = np.random.default_rng(21)
    raw = init_random_int8_params(cfg, jax.random.PRNGKey(0))
    params = prepare_params(cfg, raw, None, pack=True)
    if cfg is tcfg:
        tparams = params
    else:
        tparams = prepare_params(
            tcfg, init_random_int8_params(tcfg, jax.random.PRNGKey(0)),
            None, pack=True,
        )

    out: dict = {
        "paged_platform": platform,
        "paged_tiny": tiny,
        "paged_smoke": smoke,
        "paged_page_tokens": pt,
        "paged_batches": batches,
        "paged_max_len": max_len,
    }
    leaks: list = []

    # --- Gate 1: full-scheduler greedy parity (tiny geometry) ----------
    def _collect(sched, prompt, session_id=""):
        toks: list = []
        done: "_queue.Queue[str]" = _queue.Queue()
        sched.submit(
            Request(
                token_ids=list(prompt),
                sampling=SamplingParams(temperature=0.0, max_tokens=4),
                on_token=toks.append,
                on_done=done.put,
                session_id=session_id,
            )
        )
        reason = done.get(timeout=300)
        return toks, reason

    # 48 tokens clears Scheduler.MIN_PREFIX (32): continuations and
    # cross-session hits actually take the graft paths.
    prefix = [(i * 13) % 256 + 1 for i in range(48)]

    def run_paths(kw, spec):
        kw = dict(kw)
        if spec:
            kw.update(
                draft_cfg=dataclasses.replace(tcfg, n_layers=1),
                draft_quantize=True,
                gamma=2,
                seed=3,
            )
        sched = Scheduler(
            tcfg,
            tparams,
            max_batch=4,
            max_len=128,
            decode_chunk_size=2,
            prefill_chunk_tokens=8,
            prefix_cache="shared",
            **kw,
        )
        res = {}
        sched.start()
        try:
            res["cold"] = _collect(sched, [1, 2, 3, 4])
            res["park"] = _collect(sched, prefix)
            res["graft"] = _collect(sched, prefix + [77], session_id="s1")
            if not smoke:
                res["regraft"] = _collect(
                    sched, prefix + [99], session_id="s2"
                )
        finally:
            sched.stop()
        if "kv_layout" in kw:
            # Gate 4 contribution: drop every parked segment and check
            # the pool returns to all-free (garbage page only).
            pool = sched._pool
            for seg in list(sched._prefix_index.segments()):
                sched._drop_segment(seg)
            leaks.append(
                pool.pages_free == pool.total_pages - 1
                and int(pool._refcount.sum()) == 1
            )
        return res

    paged_kw = dict(kv_layout="paged", kv_page_size=16)
    parity: dict = {}
    ref = run_paths({}, spec=False)
    got = run_paths(paged_kw, spec=False)
    for p in ref:
        parity[p] = got[p] == ref[p]
    if not smoke:
        ref_s = run_paths({}, spec=True)
        got_s = run_paths(paged_kw, spec=True)
        for p in ref_s:
            parity[f"spec_{p}"] = got_s[p] == ref_s[p]
    out["paged_parity_paths"] = parity
    out["paged_pass_parity"] = bool(parity) and all(parity.values())

    # --- Gate 2: skewed vs uniform decode throughput -------------------
    def _bucket(n: int, cap: int) -> int:
        w = 1
        while w < n:
            w *= 2
        return min(w, cap)

    ratios: dict = {"skewed": {}, "uniform": {}}
    traffic: dict = {"skewed": {}, "uniform": {}}
    worst_ratio = 0.0
    for b in batches:
        for wl in ("skewed", "uniform"):
            if wl == "skewed":
                # Spread from short to near-full: the batch-max pow2
                # bucket punishes every short lane on the contiguous
                # side; paged lanes read their own page windows.
                lengths_np = (
                    8 + (np.arange(b) * 7919) % (max_len - steps - 16)
                )
                lengths_np = np.sort(lengths_np).astype(np.int32)
            else:
                # Uniform, deliberately off the pow2 boundary: paged
                # still reads ceil(len/pt) pages < the rounded-up
                # bucket, so it must at least break even.
                lengths_np = np.full(
                    b, (max_len * 9) // 16 + 3, np.int32
                )
            lengths = jnp.asarray(lengths_np)
            bucket = _bucket(int(lengths_np.max()) + steps, max_len)
            tok = jnp.asarray(
                rng.integers(0, cfg.vocab_size, (b,)), jnp.int32
            )
            key = jax.random.PRNGKey(1)
            temp = jnp.zeros((b,), jnp.float32)
            top_p = jnp.ones((b,), jnp.float32)
            top_k = jnp.zeros((b,), jnp.int32)

            def time_chunks(fn, state_fn, paged: bool) -> float:
                best = 0.0
                for _ in range(reps):
                    state = state_fn()
                    if paged:
                        leaves, table = state
                        args = lambda lv: (params, lv, table, tok, lengths)
                        lv = leaves
                    else:
                        lv = state
                        args = lambda lv: (params, lv, tok, lengths)
                    # compile
                    lv, _ = fn(
                        *args(lv), key, temp, top_p, top_k, steps, bucket
                    )
                    t0 = time.perf_counter()
                    lv, toks2 = fn(
                        *args(lv), key, temp, top_p, top_k, steps, bucket
                    )
                    jax.block_until_ready(toks2)
                    dt = time.perf_counter() - t0
                    best = max(best, b * steps / dt)
                return best

            def contiguous_state():
                return prepare_cache(cfg, b, max_len, None)

            def paged_state():
                pool = prepare_paged_pool(cfg, b, max_len, pt)
                for i in range(b):
                    pool.make_writable(
                        i, 0, int(lengths_np[i]) + steps + 1
                    )
                return pool.leaves, pool.device_table()

            cont_fn = make_decode_chunk_fn(cfg, None, max_len)
            paged_fn = make_paged_decode_chunk_fn(cfg, None, max_len, pt)
            cont_tps = time_chunks(cont_fn, contiguous_state, paged=False)
            paged_tps = time_chunks(paged_fn, paged_state, paged=True)
            ratio = paged_tps / cont_tps if cont_tps else 0.0
            ratios[wl][b] = ratio
            out.update(
                {
                    f"paged_decode_tokens_per_sec_{wl}_b{b}": round(
                        paged_tps, 1
                    ),
                    f"contiguous_decode_tokens_per_sec_{wl}_b{b}": round(
                        cont_tps, 1
                    ),
                    f"paged_decode_ratio_{wl}_b{b}": round(ratio, 3),
                }
            )
            # Attention-traffic companion: exact end-of-chunk pages per
            # lane vs the pow2 window every contiguous lane reads.  On
            # TPU this ratio is what the kernel's per-lane walk converts
            # into wall clock; on CPU it is the gated quantity (the XLA
            # twins read the same window by construction).
            cont_bytes = b * bucket * row_bytes
            paged_bytes = int(
                sum(-(-(int(n) + steps) // pt) * pt for n in lengths_np)
                * row_bytes
            )
            traffic[wl][b] = cont_bytes / paged_bytes
            if wl == "skewed":
                worst_ratio = max(worst_ratio, paged_bytes / cont_bytes)
                out[f"paged_kv_bytes_per_step_b{b}"] = paged_bytes
                out[f"contiguous_kv_bytes_per_step_b{b}"] = cont_bytes
    bmax = batches[-1]
    out["paged_kv_bytes_ratio_max"] = round(worst_ratio, 4)
    out["paged_decode_ratio_skewed"] = round(ratios["skewed"][bmax], 3)
    out["paged_decode_ratio_uniform"] = round(ratios["uniform"][bmax], 3)
    out["paged_attn_traffic_ratio_skewed"] = round(traffic["skewed"][bmax], 3)
    out["paged_attn_traffic_ratio_uniform"] = round(
        traffic["uniform"][bmax], 3
    )
    if platform == "tpu":
        out["paged_pass_throughput"] = bool(
            ratios["skewed"][bmax] >= 1.3 and ratios["uniform"][bmax] >= 1.0
        )
    else:
        # CPU: per-lane windows live in the Pallas kernel; the twins
        # fetch identical windows, so gate the traffic ratio plus
        # wall-clock non-regression of the gather path.
        out["paged_wallclock_nonregression"] = bool(
            ratios["skewed"][bmax] >= 0.8 and ratios["uniform"][bmax] >= 0.8
        )
        out["paged_pass_throughput"] = bool(
            traffic["skewed"][bmax] >= 1.3
            and traffic["uniform"][bmax] >= 1.0
            and out["paged_wallclock_nonregression"]
        )

    # --- Gate 3: 64-way shared prefix, measured from page gauges -------
    n_way, spt = 64, 16
    trow = tcfg.n_layers * (tcfg.n_kv_heads or tcfg.n_heads) * (
        2 * tcfg.head_dim + 4
    )
    pool64 = prepare_paged_pool(tcfg, n_way, 128, spt)
    plen, app = 90, 8  # prefix straddles a page boundary: CoW per lane
    pool64.make_writable(0, 0, plen)
    seg_pages = pool64.detach(0)
    before = dict(PAGE_EVENTS)
    breaks0 = pool64.cow_breaks
    for i in range(n_way):
        pool64.share_pages(seg_pages, i, plen)
        pool64.make_writable(i, plen, plen + app)  # private decode tail
    used = pool64.total_pages - pool64.pages_free  # the page gauges
    shared_bytes = used * spt * trow
    cont_equiv = n_way * _bucket(plen + app, 128) * trow
    shared_ratio = shared_bytes / cont_equiv
    out.update(
        {
            "paged_shared_ways": n_way,
            "paged_shared_kv_bytes": shared_bytes,
            "paged_shared_contiguous_bytes": cont_equiv,
            "paged_shared_bytes_ratio": round(shared_ratio, 4),
            "paged_pass_shared_bytes": bool(shared_ratio <= 0.5),
            "paged_shared_cow_breaks": pool64.cow_breaks - breaks0,
            "paged_graft_zero_dispatch": bool(
                PAGE_EVENTS["device_graft_dispatch"]
                == before["device_graft_dispatch"]
                and PAGE_EVENTS["host_grafts"]
                == before["host_grafts"] + n_way
            ),
        }
    )
    pool64.release(seg_pages)
    for i in range(n_way):
        pool64.reset_slot(i)
    leaks.append(
        pool64.pages_free == pool64.total_pages - 1
        and int(pool64._refcount.sum()) == 1
    )

    # --- Graft latency: host table copy vs device gather/scatter -------
    b = batches[0]
    plen = max_len // 2
    pool = prepare_paged_pool(cfg, b, max_len, pt)
    pool.make_writable(0, 0, plen)
    cache = prepare_cache(cfg, b, max_len, None)

    @jax.jit
    def copy_graft(cache):
        return tuple(
            leaf.at[:, :, 1, :plen].set(leaf[:, :, 0, :plen])
            for leaf in cache
        )

    cache = copy_graft(cache)  # compile
    jax.block_until_ready(cache)
    t0 = time.perf_counter()
    for _ in range(reps):
        cache = copy_graft(cache)
    jax.block_until_ready(cache)
    copy_ms = (time.perf_counter() - t0) / reps * 1e3

    t0 = time.perf_counter()
    for i in range(1, min(b, reps + 1)):
        pool.share(0, i, plen)
        pool.device_table()
    host_ms = (time.perf_counter() - t0) / max(1, min(b, reps + 1) - 1) * 1e3
    for i in range(min(b, reps + 1)):
        pool.reset_slot(i)
    leaks.append(
        pool.pages_free == pool.total_pages - 1
        and int(pool._refcount.sum()) == 1
    )
    out.update(
        {
            "paged_graft_host_ms": round(host_ms, 4),
            "paged_graft_copy_ms": round(copy_ms, 4),
            "paged_graft_speedup": round(host_ms and copy_ms / host_ms, 1),
        }
    )

    # --- Gate 4 verdict + summary --------------------------------------
    out["paged_pass_leaks"] = bool(leaks) and all(leaks)
    out["paged_gates_ok"] = bool(
        out["paged_pass_parity"]
        and out["paged_pass_throughput"]
        and out["paged_pass_shared_bytes"]
        and out["paged_pass_leaks"]
    )
    out["paged_note"] = (
        "gate 1: greedy bit-parity through the full scheduler "
        "(cold/graft/spec, tiny geometry); gate 2: per-lane page "
        "windows at the largest batch — wall-clock tok/s >= 1.3x "
        "skewed / >= 1.0x uniform on TPU, attention-traffic ratio at "
        "the same bars plus gather-twin wall-clock non-regression on "
        "CPU (the XLA twins read identical windows; the per-lane walk "
        "is the kernel's); gate 3: 64-way shared prefix <= 0.5x "
        "contiguous KV bytes from the page gauges; gate 4: pools "
        "all-free after drain"
    )
    return out


# Full run incl. compiles is ~20-30 min; leave headroom below the driver's
# outer timeout so the parent's structured error line beats a SIGKILL.
CHILD_TIMEOUT_S = float(os.environ.get("GAIE_BENCH_TIMEOUT_S", 2700))


def _base_result() -> dict:
    return {
        "metric": "llama3-8b decode tokens/sec/chip (full depth, int8)",
        "value": 0.0,
        "unit": "tokens/s",
        "vs_baseline": 0.0,
        "baseline_tokens_per_sec": A100_TRTLLM_LLAMA3_8B_TOKS,
    }


def _error_result(stage: str, err: str, partial: Optional[dict] = None) -> dict:
    """Structured failure result preserving already-measured fields.

    ``partial`` carries any metrics measured before the failure — a
    late-stage crash (e.g. long-context OOM) must not erase an
    already-measured headline number.  Nothing from another run is
    ever substituted.
    """
    out = _base_result()
    if partial:
        out.update(partial)
    out["error"] = f"{stage}: {err}"[:2000]
    return out


def _emit_error(stage: str, err: str, partial: Optional[dict] = None) -> None:
    """CHILD-side failure line: one full JSON object the parent can parse
    from the child's captured stdout (never driver-visible directly)."""
    print(json.dumps(_error_result(stage, err, partial)))


# Headline keys, most important first — the compact line drops from the
# tail until it fits the 1 KB driver-capture budget.
_HEADLINE_KEYS = (
    "metric",
    "value",
    "unit",
    "vs_baseline",
    "error",
    "platform",
    "device_kind",
    "device_count",
    "ttft_p50_ms",
    "serving_tokens_per_sec",
    "serving_vs_baseline",
    "serving_ttft_p50_ms",
    "serving_ttft_p95_ms",
    "long_tokens_per_sec",
    "long_vs_baseline",
    "long_ttft_p50_ms",
    "shared_prefix_ttft_p50_ms",
    "shared_prefix_cold_ttft_p50_ms",
    "shared_prefix_speedup",
    "chunked_prefill_max_decode_gap_ms",
    "spec_speedup",
    "embed_docs_per_sec",
    "rag_qps_batched_cmax",
    "rag_qps_unbatched_cmax",
    "rag_batch_speedup_cmax",
    "rag_p95_cmax_vs_c1_p50",
    "ingest_bulk_speedup",
    "ingest_bulk_docs_per_sec",
    "ingest_sync_scaling_incremental",
    "ingest_sync_scaling_rebuild",
    "ingest_search_p95_ms_during_bulk",
    "quant_int8_bytes_ratio",
    "quant_pq_bytes_ratio",
    "quant_int8_speedup",
    "quant_pq_speedup",
    "quant_recall10_int8_final",
    "quant_recall10_pq_final",
    "chaos_success_protected",
    "chaos_success_unprotected",
    "chaos_p99_protected_ms",
    "chaos_clean_overhead_pct",
    "cache_speedup_p50",
    "cache_speedup_qps",
    "cache_hit_rate",
    "cache_on_p50_ms",
    "cache_off_p50_ms",
    "cache_exact_zero_dispatch",
    "obs_overhead_pct",
    "obs_overhead_ms",
    "obs_overhead_ok",
    "obs_raw_p50_ms",
    "slo_overhead_pct",
    "slo_overhead_ok",
    "slo_alert_fired",
    "slo_clean_ok",
    "slo_alert_clear_ok",
    "elastic_fast_burn_fired",
    "elastic_scaled_to",
    "elastic_alert_resolved",
    "elastic_post_p95_ms",
    "elastic_slo_ok",
    "elastic_interactive_success",
    "elastic_shed_only_low",
    "elastic_admission_overhead_pct",
    "elastic_admission_overhead_ok",
    "durability_overhead_pct",
    "durability_overhead_ok",
    "durability_drill_ok",
    "durability_recovery_ms",
    "durability_bootstrap_ms",
    "gray_p99_ratio",
    "gray_p99_ok",
    "gray_ejected",
    "gray_readmitted",
    "gray_fast_burn_fired",
    "gray_hedge_extra_load_pct",
    "gray_overhead_pct",
    "gray_overhead_ok",
)


def _compact_headline(result: dict, full_path: Optional[str]) -> str:
    """GUARANTEED <= 1 KB single-line JSON headline for the driver's tail
    capture (round 5's giant single-line result came back ``parsed:
    null``; a headline that can exceed the capture budget on any input is
    the same failure waiting to recur).  Shrink order: drop non-essential
    keys from the tail, then truncate the protected strings — the floor
    is ``{"metric":...,"value":...,"unit":...}`` plus a clipped error,
    which cannot reach 1 KB.  Everything dropped here is still in the
    ``full_results`` file."""
    out: dict = {}
    for k in _HEADLINE_KEYS:
        if k in result:
            v = result[k]
            if isinstance(v, str) and len(v) > 160:
                v = v[:160]
            out[k] = v
    if full_path:
        out["full_results"] = full_path
    line = json.dumps(out, separators=(",", ":"))
    while len(line.encode()) > 1024:
        for k in reversed(list(out)):
            if k not in ("metric", "value", "unit", "error"):
                del out[k]
                break
        else:
            # Only protected keys remain: clip their strings hard.
            if len(str(out.get("error", ""))) > 60:
                out["error"] = str(out["error"])[:60]
            elif len(str(out.get("metric", ""))) > 24:
                out["metric"] = str(out["metric"])[:24]
            else:
                break  # unreachable: the floor dict is ~150 bytes
        line = json.dumps(out, separators=(",", ":"))
    return line


def _publish(result: dict) -> None:
    """PARENT-side output contract: full result to a file, compact
    machine-parseable headline as the last stdout line."""
    path = os.environ.get(
        "GAIE_BENCH_RESULT_PATH",
        os.path.join(
            os.path.dirname(os.path.abspath(__file__)),
            "perf",
            "bench_full.json",
        ),
    )
    try:
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        with open(path, "w") as f:
            json.dump(result, f, indent=1, sort_keys=True)
            f.write("\n")
    except OSError:
        path = None
    print(_compact_headline(result, path))


def _last_json_line(text: str) -> Optional[dict]:
    """The last stdout line that parses as a JSON object, or None.

    Validated with ``json.loads`` (not just a ``{`` prefix): a child killed
    mid-write can leave a truncated line, and forwarding that to the driver
    would be exactly the malformed output the watchdog exists to prevent.
    """
    for ln in reversed(text.strip().splitlines()):
        ln = ln.strip()
        if ln.startswith("{"):
            try:
                d = json.loads(ln)
            except ValueError:
                continue
            if isinstance(d, dict):
                return d
    return None


def main() -> None:
    """Run the real bench in a child under a hard timeout.

    Nothing in the parent touches JAX (the chip belongs to the child),
    so the parent can always publish a structured result.  A child that
    found no TPU, died, timed out or reported a failed phase makes the
    run exit non-zero: whatever it measured before is published with
    the error, and nothing from another run stands in for it.
    """
    import subprocess
    import sys

    try:
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--run"],
            capture_output=True,
            text=True,
            timeout=CHILD_TIMEOUT_S,
        )
    except subprocess.TimeoutExpired as e:
        # TimeoutExpired carries bytes even with text=True.
        out = e.stdout.decode(errors="replace") if e.stdout else ""
        err = (e.stderr.decode(errors="replace") if e.stderr else "")[-500:]
        _publish(
            _error_result(
                "bench-timeout",
                f"child exceeded {CHILD_TIMEOUT_S:.0f}s; stderr tail: {err}",
                partial=_last_json_line(out),
            )
        )
        sys.exit(1)
    sys.stderr.write(proc.stderr[-8000:])
    # The child's contract: last stdout line is the JSON result (it
    # emits a partial-result+error line itself on in-run failures).
    result = _last_json_line(proc.stdout)
    if result is None:
        tail = proc.stderr.strip().splitlines()[-1:] or ["no output"]
        result = _error_result(
            "bench-child", f"child rc={proc.returncode}: {tail[-1]}"
        )
    elif proc.returncode != 0 and "error" not in result:
        result["error"] = f"bench-child: rc={proc.returncode}"
    _publish(result)
    if "error" in result:
        sys.exit(1)


def _run(result: dict) -> None:
    """The real benchmark (child process).  Fills ``result`` progressively
    so the caller can emit already-measured stages if a later one dies."""
    import jax

    from generativeaiexamples_tpu.engine.generator import LlamaGenerator
    from generativeaiexamples_tpu.engine.sampler import SamplingParams
    from generativeaiexamples_tpu.models import llama

    from generativeaiexamples_tpu.utils.jax_runtime import (
        enable_compile_cache,
    )

    result.update(_device_fields())
    if result["platform"] != "tpu":
        raise RuntimeError(
            f"no TPU: JAX's backend is {result['platform']!r} "
            f"({result['device_kind']}); this benchmark measures the "
            "chip and has no CPU mode"
        )
    enable_compile_cache()
    cfg = llama.llama3_8b(max_seq_len=MAX_LEN, kv_dtype=KV_DTYPE)
    gen = LlamaGenerator(
        cfg,
        max_batch=BATCH,
        max_len=MAX_LEN,
        # 64, not 128: the decode chunk's KV append buffer (Pallas kernel
        # path) is (L, KH, B, chunk, HD) x2 — 128 would add 2.7 GB and
        # OOM next to the weights + slot cache; the extra host syncs are
        # sub-ms on this backend.
        decode_chunk_size=64,
        seed=0,
        quantize=True,
        pack=True,
        prefill_chunk=PREFILL_CHUNK,
    )

    rng = np.random.default_rng(0)
    prompts = [
        rng.integers(0, cfg.vocab_size, (PROMPT_LEN,)).tolist()
        for _ in range(BATCH)
    ]
    sp = SamplingParams(temperature=0.7, top_p=0.9, max_tokens=DECODE_STEPS)

    # Warmup: compile prefill + the decode-chunk buckets the timed run hits.
    gen.generate([p[:PROMPT_LEN] for p in prompts], SamplingParams(
        temperature=0.7, top_p=0.9, max_tokens=DECODE_STEPS))

    # TTFT: single prompt prefill-to-first-token, median of 5.
    ttfts = []
    for _ in range(5):
        t0 = time.perf_counter()
        gen.generate([prompts[0]], SamplingParams(temperature=0.0, max_tokens=1))
        ttfts.append(time.perf_counter() - t0)
    ttft_p50_ms = float(np.median(ttfts) * 1000)

    # Decode throughput: full batch, fixed steps, best of 3 (first run can
    # still hit a cold compile bucket).
    best = None
    for _ in range(3):
        t0 = time.perf_counter()
        results = gen.generate(prompts, sp)
        elapsed = time.perf_counter() - t0
        tokens = sum(len(r.token_ids) for r in results)
        tps = tokens / elapsed
        if best is None or tps > best:
            best = tps
    measured_tps = best
    result.update(
        {
            "value": round(measured_tps, 1),
            "vs_baseline": round(measured_tps / A100_TRTLLM_LLAMA3_8B_TOKS, 3),
            "batch": BATCH,
            "prompt_len": PROMPT_LEN,
            "decode_steps": DECODE_STEPS,
            "ttft_p50_ms": round(ttft_p50_ms, 1),
        }
    )

    # Embedding ingest throughput (BASELINE.md third target): arctic-embed-l
    # geometry serving its REAL tokenizer class — a WordPiece vocab fixture
    # (offline image: no HF vocab download) with ~128-token English-like
    # docs, so host tokenization cost and tokens/doc match the production
    # configuration instead of the 1-token-per-char byte fallback.
    from generativeaiexamples_tpu.engine.embedder import TPUEmbedder

    wp_tok, docs = _embed_fixture()
    embedder = TPUEmbedder(batch_size=32, tokenizer=wp_tok)
    # Token throughput under the tokenizer actually in use makes the
    # number comparable across tokenizers.
    embed_tokens = sum(len(embedder.tokenizer.encode(d)) for d in docs)
    embed_tokenizer = type(embedder.tokenizer).__name__
    embedder.embed_documents(docs[:32])  # warm the length bucket
    t0 = time.perf_counter()
    embedder.embed_documents(docs)
    embed_elapsed = time.perf_counter() - t0
    embed_docs_per_sec = len(docs) / embed_elapsed
    embed_tokens_per_sec = embed_tokens / embed_elapsed
    del embedder
    result.update(
        {
            "embed_docs_per_sec": round(embed_docs_per_sec, 1),
            "embed_tokens_per_sec": round(embed_tokens_per_sec, 1),
            "embed_tokenizer": embed_tokenizer,
        }
    )

    # Serving path: continuous batching under Poisson load (shares the
    # already-initialized quantized params with the offline generator).
    result.update(bench_serving(cfg, gen.params, measured_tps))

    # Speculative decoding: worst-case (random-draft) machinery overhead
    # + acceptance.
    result.update(bench_speculative(cfg, gen.params))

    # Trained-pair speculative decoding: acceptance above the random
    # floor, measured on hardware with an in-bench-trained tiny pair.
    result.update(bench_spec_trained())

    # Spec-in-the-scheduler serving phase (round-18 lever): trained-pair
    # draft through the ONLINE scheduler at high concurrency — speedup,
    # TTFT ratio, acceptance, bit-identity, adaptive-gamma drill.
    result.update(bench_spec_serving())

    # Realistic-context profile (1500-token prompts).  The short-profile
    # generator's 320-slot cache must be released first: the long cache
    # (64 x 2048) plus weights would not fit beside it.
    params = gen.params
    del gen
    result.update(bench_long_context(params))

    # Shared-prefix + chunked-prefill serving phase (the round-6 TTFT
    # lever): runs after the long phase so its 8 x 2048 scheduler cache
    # replaces the long generator's in HBM.
    result.update(bench_shared_prefix(params))

    # Replica-router phase (tiny-config pools; negligible HBM beside the
    # phases above): prefix-affinity vs round-robin hit-rate + failover
    # requeue latency.
    result.update(bench_router())

    # End-to-end RAG retrieval phase (round-8 lever): micro-batched vs
    # per-request embed->search at concurrency {1,32,128}.
    result.update(bench_rag())

    # Bulk-ingestion phase (round-9 lever): staged pipeline vs serial
    # per-doc loop, incremental O(new-rows) sync vs rebuild-per-insert,
    # search p95 during concurrent ingest.
    result.update(bench_ingest())

    # Quantized-search phase (round-10 lever): full-width vs int8 vs PQ
    # two-stage search latency + scanned bytes + recall.
    result.update(bench_quant())

    # Chaos/resilience phase (round-11 lever): success rate + tail latency
    # under injected faults with and without the resilience stack, plus
    # the machinery's clean-path overhead.
    result.update(bench_chaos())

    # Semantic-cache phase (round-12 lever): cache-off vs cache-on QPS +
    # latency on a zipf repeated-query workload, plus the paraphrase
    # threshold sweep.
    result.update(bench_cache())

    # Observability phase (round-13 lever): per-request telemetry
    # machinery overhead on the clean retrieval path.
    result.update(bench_obs())

    # SLO phase (round-14 lever): fleet-telemetry feed overhead + the
    # burn-rate alert drill.
    result.update(bench_slo())

    # Elastic phase (round-15 lever): the closed autoscale/admission loop
    # under a 4x load step.
    result.update(bench_elastic())

    # Durability phase (round-16 lever): WAL clean-path overhead + the
    # SIGKILL/restart recovery drill.
    result.update(bench_durability())

    # Gray-failure phase (round-17 lever): straggler scoring/ejection +
    # hedged requests under a slow-replica fault.
    result.update(bench_gray())


def _device_fields() -> dict:
    """The device this process's JAX runs on, as result fields."""
    from generativeaiexamples_tpu.utils.jax_runtime import device_report

    device = device_report()
    return {
        "platform": device["platform"],
        "device_kind": device["kind"],
        "device_count": device["count"],
    }


def _print_phase(phase) -> None:
    """Standalone phase entry: its result, with the device it ran on."""
    print(json.dumps({**phase(), **_device_fields()}))


def _child_main() -> None:
    """Child entry: run, then print ONE JSON line (measured results, plus
    an error field if a stage died mid-run — in which case the exit
    code says so too)."""
    import sys

    result = _base_result()
    result.update(
        {
            "weights": "int8 (weight-only, per-channel)",
            "kv_cache": KV_DTYPE,
            "layers": 32,
        }
    )
    try:
        _run(result)
    except Exception as e:  # noqa: BLE001 — contract: always one JSON line
        import traceback

        traceback.print_exc()
        _emit_error("bench-run", f"{type(e).__name__}: {e}", partial=result)
        sys.exit(1)
    print(json.dumps(result))


if __name__ == "__main__":
    import sys

    if "--spec-serving" in sys.argv:
        # Standalone spec-serving phase: trains the tiny pair and runs
        # the online-scheduler drill; CPU-friendly at reduced
        # concurrency (GAIE_BENCH_SPEC_C).
        _print_phase(bench_spec_serving)
    elif "--quant" in sys.argv:
        # Standalone quantized-search phase: no generator weights, runs on
        # CPU in minutes (committed CPU captures).
        _print_phase(bench_quant)
    elif "--shard" in sys.argv:
        # Standalone sharded-fabric phase: scatter-gather merge vs the
        # unsharded exact scan, int8/PQ collection recall, cold-tier
        # byte split, and p95 under sibling-collection ingest.  Runs on
        # CPU in minutes (committed CPU captures).
        _print_phase(bench_shard)
    elif "--chaos" in sys.argv:
        # Standalone chaos/resilience phase: pure-host workload (hash
        # embedder + exact store), runs anywhere in ~1 min.
        _print_phase(bench_chaos)
    elif "--cache" in sys.argv:
        # Standalone semantic-cache phase: pure-host workload, runs
        # anywhere in ~1-2 min.
        _print_phase(bench_cache)
    elif "--obs" in sys.argv:
        # Standalone observability-overhead phase: pure-host workload,
        # runs anywhere in under a minute.
        _print_phase(bench_obs)
    elif "--slo" in sys.argv:
        # Standalone SLO phase: fleet-telemetry feed overhead + the
        # burn-rate alert drill; pure-host, runs anywhere in ~1 min.
        _print_phase(bench_slo)
    elif "--elastic" in sys.argv:
        # Standalone elasticity phase: the simulated 4x load step through
        # the real autoscaler + admission controller + SLO engine, plus
        # the admission clean-path overhead; pure-host, ~1 min.
        _print_phase(bench_elastic)
    elif "--durability" in sys.argv:
        # Standalone durability phase: WAL overhead + the kill-restart
        # drill; pure-host, runs anywhere in ~1 min.
        _print_phase(bench_durability)
    elif "--fused" in sys.argv:
        # Standalone fused-W8A8 phase: kernel GB/s microbench + fused vs
        # XLA decode + spec on/off, with bit-identity and tile-once
        # gates.  GAIE_FUSED_TINY=1 runs hermetically on CPU in ~a
        # minute (committed CPU captures).
        _print_phase(bench_fused)
    elif "--paged" in sys.argv:
        # Standalone paged-KV phase: paged vs contiguous decode over a
        # mixed ragged batch, analytic KV bytes/step gate (<= 0.7x the
        # pow2-window baseline), and the zero-dispatch graft gate.
        # GAIE_PAGED_TINY=1 runs hermetically on CPU in ~a minute
        # (committed CPU captures).
        _print_phase(bench_paged)
    elif "--gray" in sys.argv:
        # Standalone gray-failure phase: slow-replica drill through the
        # real pool (tiny config, CPU-friendly) + the hedge-arm clean-
        # path overhead; runs anywhere in a few minutes.
        _print_phase(bench_gray)
    elif "--durability-child" in sys.argv:
        # Drill child (spawned by _durability_drill, or by hand with a
        # workdir): ingest or resume, then write child_result.json.
        _durability_child(sys.argv[sys.argv.index("--durability-child") + 1])
    elif "--run" in sys.argv:
        _child_main()
    else:
        main()
