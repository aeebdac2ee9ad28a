"""Decode-attention scaling at long KV windows (0.5k → 3.5k prompt).

    python perf/bench_long4k.py

This measures the Pallas decode kernel's scaling story: per-step decode
throughput of full-depth int8 llama3-8b at increasing KV window sizes in
ONE 4096-token cache geometry, so the only variable is how much cache the
kernel streams per step.  Prints one JSON line:

  {"windows": [{"prompt_len": N, "decode_tps": T,
                "prefill_batch_ms": T}, ...],
   "batch": B, "max_len": 4096, "decode_steps": 128}

Decode tok/s is isolated from prefill by timing max_tokens=128 generation
and subtracting the measured single-step (max_tokens=1) time for the same
prompt bucket.
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

TINY = os.environ.get("GAIE_LONG4K_TINY", "") == "1"
BATCH = int(os.environ.get("BENCH_B", "2" if TINY else "16"))
MAX_LEN = 256 if TINY else 4096
# 3584 + 128 decode < 4096; prompts bucket to 512/1536/4096 prefill.
# (TINY mode shrinks everything so the glue is CI-exercised on CPU —
# the one hardware shot must not die on a Python-level bug.  It keeps
# 96 decode steps and a third repetition so that the decode time it
# isolates by subtraction, ~50x the prefill at that scale, stays
# positive on a loaded host.)
DECODE_STEPS = 96 if TINY else 128
REPS = 3 if TINY else 2
PROMPT_LENS = (32, 64, 128) if TINY else (512, 1536, 3584)


def main() -> None:
    from generativeaiexamples_tpu.engine.generator import LlamaGenerator
    from generativeaiexamples_tpu.engine.sampler import SamplingParams
    from generativeaiexamples_tpu.models import llama

    if TINY:
        cfg = llama.llama_tiny(dtype="float32", max_seq_len=MAX_LEN)
        gen = LlamaGenerator(
            cfg, max_batch=BATCH, max_len=MAX_LEN, decode_chunk_size=4,
            seed=0,
        )
    else:
        cfg = llama.llama3_8b(max_seq_len=MAX_LEN, kv_dtype="int8")
        gen = LlamaGenerator(
            cfg,
            max_batch=BATCH,
            max_len=MAX_LEN,
            decode_chunk_size=64,
            seed=0,
            quantize=True,
            pack=True,
            prefill_chunk=8,
        )
    rng = np.random.default_rng(5)
    out = {"batch": BATCH, "max_len": MAX_LEN, "decode_steps": DECODE_STEPS,
           "windows": []}
    for plen in PROMPT_LENS:
        prompts = [
            rng.integers(0, cfg.vocab_size, (plen,)).tolist()
            for _ in range(BATCH)
        ]
        long_sp = SamplingParams(temperature=0.0, max_tokens=DECODE_STEPS)
        one_sp = SamplingParams(temperature=0.0, max_tokens=1)
        gen.generate(prompts, long_sp)  # compile both bucket sets
        gen.generate(prompts, one_sp)
        t_one = []
        t_full = []
        for _ in range(REPS):
            t0 = time.perf_counter()
            gen.generate(prompts, one_sp)
            t_one.append(time.perf_counter() - t0)
            t0 = time.perf_counter()
            results = gen.generate(prompts, long_sp)
            t_full.append(time.perf_counter() - t0)
        tokens = sum(len(r.token_ids) for r in results) - BATCH
        decode_s = min(t_full) - min(t_one)
        out["windows"].append(
            {
                "prompt_len": plen,
                "decode_tps": round(tokens / decode_s, 1),
                "prefill_batch_ms": round(min(t_one) * 1000, 1),
            }
        )
        print(f"# window {plen}: {out['windows'][-1]}", file=sys.stderr)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
