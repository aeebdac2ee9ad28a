"""Probe: does lax.scan over stacked layer weights cost extra HBM traffic?

Builds a transformer-shaped per-layer matmul chain (qkv/wo/gate-up/down at
llama3-8b geometry, int8 weights + per-col scales, batch 192) and times a
16-step decode-like outer scan with the 32 layers either:

  * scanned  — weights stacked (L, ...) consumed as lax.scan xs (the
    current models/llama.py structure), or
  * unrolled — a python loop over 32 per-layer arg trees.

Run each mode in its own process (7 GB of weights each):
    python perf/probe_scan_vs_unroll.py scanned
    python perf/probe_scan_vs_unroll.py unrolled
"""

from __future__ import annotations

import sys
import time

import jax
import jax.numpy as jnp

B, D, DQKV, DFF = int(__import__("os").environ.get("PROBE_B", "192")), 4096, 6144, 14336
L = int(__import__("os").environ.get("PROBE_L", "32"))
T = 16  # outer decode-like steps (serialized via data dependency)

LAYER_BYTES = D * DQKV + D * D + D * 2 * DFF + DFF * D  # int8


def make_layer(key):
    ks = jax.random.split(key, 4)
    r = lambda k, shape: jax.random.randint(k, shape, -127, 128, jnp.int8)
    s = lambda k, n: jnp.abs(jax.random.normal(k, (n,), jnp.float32)) * 1e-2
    return {
        "wqkv": (r(ks[0], (D, DQKV)), s(ks[0], DQKV)),
        "wo": (r(ks[1], (D, D)), s(ks[1], D)),
        "w_gu": (r(ks[2], (D, 2 * DFF)), s(ks[2], 2 * DFF)),
        "w_down": (r(ks[3], (DFF, D)), s(ks[3], D)),
    }


def qdot(x, w):
    q, s = w
    out = jnp.einsum(
        "bk,kn->bn", x, q.astype(x.dtype), preferred_element_type=jnp.float32
    )
    return (out * s).astype(x.dtype)


def layer_fn(h, lp):
    qkv = qdot(h, lp["wqkv"])
    attn = qkv[:, :D]  # stand-in for attention output (same weight traffic)
    h = h + qdot(attn, lp["wo"])
    gu = qdot(h, lp["w_gu"])
    gated = jax.nn.silu(gu[:, :DFF]) * gu[:, DFF:]
    h = h + qdot(gated, lp["w_down"])
    return h * 0.5


def main():
    mode = sys.argv[1] if len(sys.argv) > 1 else "scanned"
    key = jax.random.PRNGKey(0)
    x0 = jax.random.normal(key, (B, D), jnp.bfloat16)

    if mode == "scanned":
        # Build the stacked tree directly (a per-layer list + stack would
        # briefly hold 2x7 GB and OOM the 16 GB chip).
        r = lambda k, shape: jax.random.randint(k, shape, -127, 128, jnp.int8)
        s = lambda k, shape: jnp.abs(jax.random.normal(k, shape, jnp.float32)) * 1e-2
        ks = jax.random.split(key, 4)
        stacked = {
            "wqkv": (r(ks[0], (L, D, DQKV)), s(ks[0], (L, DQKV))),
            "wo": (r(ks[1], (L, D, D)), s(ks[1], (L, D))),
            "w_gu": (r(ks[2], (L, D, 2 * DFF)), s(ks[2], (L, 2 * DFF))),
            "w_down": (r(ks[3], (L, DFF, D)), s(ks[3], (L, D))),
        }

        @jax.jit
        def run(x, stacked):
            def step(h, _):
                def body(h, lp):
                    return layer_fn(h, lp), None

                h, _ = jax.lax.scan(body, h, stacked)
                return h, None

            h, _ = jax.lax.scan(step, x, None, length=T)
            return h

        args = (x0, stacked)
    else:
        layers = [make_layer(jax.random.fold_in(key, i)) for i in range(L)]

        @jax.jit
        def run(x, *layers):
            def step(h, _):
                for lp in layers:
                    h = layer_fn(h, lp)
                return h, None

            h, _ = jax.lax.scan(step, x, None, length=T)
            return h

        args = (x0, *layers)

    # A device->host item() transfer is the sync used throughout.
    o = run(*args)
    _ = float(o[0, 0])
    best = 1e9
    for _i in range(3):
        t0 = time.perf_counter()
        o = run(*args)
        _ = float(o[0, 0])
        best = min(best, time.perf_counter() - t0)
    per_step = best / T
    total = L * LAYER_BYTES
    print(
        f"{mode:9s}: {per_step*1e3:8.2f} ms/step  "
        f"{total/per_step/1e9:6.1f} GB/s eff-int8 (ideal {total/910e9*1e3:.1f} ms)"
    )


if __name__ == "__main__":
    main()
