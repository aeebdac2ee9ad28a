"""One-phase serving experiment for TTFT/throughput tuning.

Runs a single Poisson phase against the continuous-batching scheduler
with every knob on the command line, and prints one JSON line that
includes the tick-phase breakdown (the scheduler's exclusive phase sums)
so tuning decisions are driven by where the tick time actually goes.

    python perf/exp_serving.py --slots 320 --chunk 12 --max-queue 32 \
        --budget 2048 --rate 27.3 --measure 30

Unlike bench.py's serving phase this does not aim to be a reportable
benchmark — it is the lab bench for finding the config bench.py reports.
The request factory / burst warm-up / Poisson driver deliberately mirror
``bench.bench_serving`` rather than share code with it: the experiment
must be able to diverge (extra knobs, tick-breakdown output) without any
risk of destabilizing the reported benchmark.  When changing the bench
driver's warm-up or windowing, mirror the change here.
"""

import argparse
import json
import os
import random
import sys
import threading
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import bench
from generativeaiexamples_tpu.engine.decode import prepare_params
from generativeaiexamples_tpu.engine.sampler import SamplingParams
from generativeaiexamples_tpu.engine.scheduler import Request, Scheduler
from generativeaiexamples_tpu.models import llama


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--slots", type=int, default=320)
    ap.add_argument("--chunk", type=int, default=12)
    ap.add_argument("--max-queue", type=int, default=32)
    ap.add_argument("--budget", type=int, default=2048)
    ap.add_argument("--rate", type=float, default=27.3, help="req/s offered")
    ap.add_argument("--warm", type=float, default=10.0)
    ap.add_argument("--prewarm", type=float, default=0.0)
    ap.add_argument("--measure", type=float, default=30.0)
    ap.add_argument("--prompt-len", type=int, default=bench.PROMPT_LEN)
    ap.add_argument("--decode-steps", type=int, default=bench.DECODE_STEPS)
    args = ap.parse_args()

    cfg = llama.llama3_8b(max_seq_len=bench.MAX_LEN, kv_dtype=bench.KV_DTYPE)
    params = prepare_params(cfg, None, None, quantize=True, pack=True)
    sched = Scheduler(
        cfg,
        params=params,
        max_batch=args.slots,
        max_len=bench.MAX_LEN,
        decode_chunk_size=args.chunk,
        seed=1,
        max_queue=args.max_queue,
        admit_token_budget=args.budget,
    )
    sched.start()

    rng = np.random.default_rng(1)
    rnd = random.Random(7)
    lock = threading.Lock()
    token_times: list[float] = []
    ttfts: list[float] = []

    def make_request(i: int, max_tokens: int):
        prompt = rng.integers(0, cfg.vocab_size, (args.prompt_len,)).tolist()
        state = {"first": None, "submitted": None}

        def on_token(tid: int, state=state) -> None:
            now = time.perf_counter()
            with lock:
                token_times.append(now)
                if state["first"] is None:
                    state["first"] = now
                    ttfts.append(now - state["submitted"])

        return (
            Request(
                token_ids=prompt,
                sampling=SamplingParams(
                    temperature=0.7, top_p=0.9, max_tokens=max_tokens
                ),
                on_token=on_token,
                on_done=lambda reason: None,
                id=f"exp-{i}",
            ),
            state,
        )

    # Warm compile buckets exactly like bench.bench_serving.
    max_rows = max(args.budget // args.prompt_len, 1)
    for burst in [b for b in (1, 4, 8, 16, 32, 64) if b <= max_rows]:
        for i in range(burst):
            req, state = make_request(10_000 + burst * 100 + i, 4)
            state["submitted"] = time.perf_counter()
            sched.submit(req)
        deadline = time.perf_counter() + 120
        while time.perf_counter() < deadline:
            snap = sched.stats.snapshot()
            if not snap["active_slots"] and not snap["queued"]:
                break
            time.sleep(0.2)

    # Loaded pre-warm at the measured rate: short-decode bursts never
    # reach steady-state occupancy, so the decode chunk's full-occupancy
    # shapes would otherwise compile inside the measured window.
    if args.prewarm > 0:
        t0 = time.perf_counter()
        t_stop = t0 + args.prewarm
        nxt = t0
        i = 50_000
        while (now := time.perf_counter()) < t_stop:
            if now >= nxt:
                req, state = make_request(i, args.decode_steps)
                state["submitted"] = time.perf_counter()
                sched.submit(req)
                i += 1
                nxt += rnd.expovariate(args.rate)
            time.sleep(min(max(nxt - time.perf_counter(), 0.0), 0.05))
        with lock:
            token_times.clear()
            ttfts.clear()

    snap0 = sched.stats.snapshot()
    t0 = time.perf_counter()
    t_end = t0 + args.warm + args.measure
    nxt = t0
    i = 0
    offered = 0
    occupancy: list[int] = []
    while (now := time.perf_counter()) < t_end:
        if now >= nxt:
            req, state = make_request(i, args.decode_steps)
            state["submitted"] = time.perf_counter()
            sched.submit(req)
            i += 1
            offered += 1
            nxt += rnd.expovariate(args.rate)
        occupancy.append(sched.stats.snapshot()["active_slots"])
        time.sleep(min(max(nxt - time.perf_counter(), 0.0), 0.05))
    wall = time.perf_counter() - t0
    snap1 = sched.stats.snapshot()
    with lock:
        window = [t for t in token_times if t >= t0 + args.warm]
        tt = sorted(ttfts)
    sched.stop()

    ticks = snap1["tick_count"] - snap0["tick_count"]
    phase_ms = {
        k[len("tick_phase_"):-2]: (snap1[k] - snap0[k]) * 1000
        for k in snap1
        if k.startswith("tick_phase_")
    }
    out = {
        "slots": args.slots,
        "chunk": args.chunk,
        "max_queue": args.max_queue,
        "budget": args.budget,
        "rate": args.rate,
        "offered": offered,
        "rejected": snap1["rejected_total"] - snap0["rejected_total"],
        "tokens_per_sec": round(len(window) / args.measure, 1),
        "ttft_p50_ms": round(tt[len(tt) // 2] * 1000, 1) if tt else 0.0,
        "ttft_p95_ms": round(tt[int(len(tt) * 0.95)] * 1000, 1) if tt else 0.0,
        "mean_active_slots": round(float(np.mean(occupancy)), 1),
        "ticks": ticks,
        "tick_ms": round(wall / max(ticks, 1) * 1000, 1),
        **{
            f"{phase}_ms_per_tick": round(ms / max(ticks, 1), 1)
            for phase, ms in phase_ms.items()
        },
        "prefill_rows": snap1["prefill_rows"] - snap0["prefill_rows"],
        "decode_chunks": snap1["decode_chunks"] - snap0["decode_chunks"],
    }
    print(json.dumps(out))


if __name__ == "__main__":
    main()
