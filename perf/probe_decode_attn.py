"""Probe: the decode step's KV-cache attention path — scanned vs unrolled.

Round-2 profiling (PERF_NOTES.md) showed the two int8 KV-window
dynamic-slice materializations cost 4.3 ms of the 26.6 ms decode step at
b=192, window 256.  The hypothesis: with the layer loop UNROLLED the layer
index (and the window limit) become static slices that XLA fuses into the
attention einsums instead of materializing.

Isolates the per-layer decode attention work at serving geometry:
  * int8 KV cache leaf (L, B, T, KH, HD) + bf16 scales
  * scatter of the new k/v row at position `pos`
  * window slice -> gqa score/weight einsums with folded scales

Run each mode in its own process:
    python perf/probe_decode_attn.py scanned
    python perf/probe_decode_attn.py unrolled
"""

from __future__ import annotations

import os
import sys
import time

import jax
import jax.numpy as jnp

B = int(os.environ.get("PROBE_B", "320"))
T = int(os.environ.get("PROBE_T", "384"))
WINDOW = int(os.environ.get("PROBE_W", "256"))
L = int(os.environ.get("PROBE_L", "32"))
KH, HD, QH = 8, 128, 32
STEPS = 16

_NEG_INF = -1e30


def attn_one_layer(q, k8, v8, ks, vs, positions, lengths):
    """gqa_attention specialized to s=1 decode (same math as ops.attention)."""
    b = q.shape[0]
    group = QH // KH
    qg = q.reshape(b, 1, KH, group, HD)
    scores = jnp.einsum(
        "bsngh,btnh->bngst", qg, k8.astype(q.dtype),
        preferred_element_type=jnp.float32,
    ) * (HD ** -0.5)
    scores = scores * jnp.transpose(ks, (0, 2, 1))[:, :, None, None, :]
    t_idx = jnp.arange(k8.shape[1], dtype=jnp.int32)
    causal = t_idx[None, None, :] <= positions[..., None]
    valid = t_idx[None, :] < lengths[:, None]
    mask = (causal & valid[:, None, :])[:, None, None, :, :]
    scores = jnp.where(mask, scores, _NEG_INF)
    w = jnp.exp(scores - scores.max(axis=-1, keepdims=True)) * mask
    w = w / jnp.maximum(w.sum(axis=-1, keepdims=True), 1e-30)
    w = w * jnp.transpose(vs, (0, 2, 1))[:, :, None, None, :]
    out = jnp.einsum(
        "bngst,btnh->bsngh", w.astype(q.dtype), v8.astype(q.dtype),
        preferred_element_type=jnp.float32,
    )
    return out.reshape(b, 1, QH, HD).astype(q.dtype)


def main():
    mode = sys.argv[1] if len(sys.argv) > 1 else "scanned"
    key = jax.random.PRNGKey(0)
    shape = (L, B, T, KH, HD)
    # random.bits avoids randint's int32 intermediate (4x the cache size).
    rand8 = jax.jit(
        lambda k: jax.lax.bitcast_convert_type(
            jax.random.bits(k, shape, jnp.uint8), jnp.int8
        )
    )
    cache = (
        rand8(key),
        rand8(jax.random.fold_in(key, 1)),
        jnp.ones(shape[:-1], jnp.bfloat16) * 0.05,
        jnp.ones(shape[:-1], jnp.bfloat16) * 0.05,
    )
    q0 = jax.random.normal(key, (B, 1, QH, HD), jnp.bfloat16)
    newk = jax.random.normal(key, (B, 1, KH, HD), jnp.bfloat16)
    lengths = jnp.full((B,), WINDOW - STEPS - 1, jnp.int32)

    def quant(x):
        amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1)
        s = jnp.maximum(amax, 1e-8) / 127.0
        qv = jnp.clip(jnp.round(x.astype(jnp.float32) / s[..., None]), -127, 127)
        return qv.astype(jnp.int8), s.astype(jnp.bfloat16)

    import functools

    if mode == "scanned":

        @functools.partial(jax.jit, donate_argnums=(0,))
        def run(cache, q, newk, lengths):
            def step(carry, _):
                cache, lengths = carry
                positions = lengths[:, None]
                bidx = jnp.arange(B, dtype=jnp.int32)[:, None]

                def body(inner, _):
                    cache, li, acc = inner
                    k8n, ksn = quant(newk)
                    v8n, vsn = quant(newk)
                    cache = (
                        cache[0].at[li, bidx, positions].set(k8n),
                        cache[1].at[li, bidx, positions].set(v8n),
                        cache[2].at[li, bidx, positions].set(ksn),
                        cache[3].at[li, bidx, positions].set(vsn),
                    )

                    def sl(buf):
                        return jax.lax.dynamic_slice(
                            buf, (li,) + (0,) * (buf.ndim - 1),
                            (1, B, WINDOW) + buf.shape[3:],
                        )[0]

                    out = attn_one_layer(
                        q, sl(cache[0]), sl(cache[1]), sl(cache[2]),
                        sl(cache[3]), positions, lengths + 1,
                    )
                    return (cache, li + 1, acc + out.mean()), None

                (cache, _, acc), _ = jax.lax.scan(
                    body, (cache, jnp.int32(0), jnp.float32(0)), None, length=L
                )
                return (cache, lengths + 1), acc

            (cache, lengths), accs = jax.lax.scan(
                step, (cache, lengths), None, length=STEPS
            )
            return cache, accs.sum()

    elif mode == "preattn":
        # Attention over the PRE-scatter window + an explicit self term for
        # the fresh token; the scatter then has no consumer this step, so
        # XLA is free to fuse the window slice into the score einsum and
        # overlap the scatter with attention compute.

        @functools.partial(jax.jit, donate_argnums=(0,))
        def run(cache, q, newk, lengths):
            def step(carry, _):
                cache, lengths = carry
                positions = lengths[:, None]
                bidx = jnp.arange(B, dtype=jnp.int32)[:, None]
                group = QH // KH

                def body(inner, _):
                    cache, li, acc = inner
                    k8n, ksn = quant(newk)
                    v8n, vsn = quant(newk)

                    def sl(buf):
                        return jax.lax.dynamic_slice(
                            buf, (li,) + (0,) * (buf.ndim - 1),
                            (1, B, WINDOW) + buf.shape[3:],
                        )[0]

                    # Window scores over the old cache (strictly t < len).
                    qg = q.reshape(B, 1, KH, group, HD)
                    scores = jnp.einsum(
                        "bsngh,btnh->bngst", qg, sl(cache[0]).astype(q.dtype),
                        preferred_element_type=jnp.float32,
                    ) * (HD ** -0.5)
                    scores = scores * jnp.transpose(
                        sl(cache[2]), (0, 2, 1)
                    )[:, :, None, None, :]
                    t_idx = jnp.arange(WINDOW, dtype=jnp.int32)
                    mask = (t_idx[None, :] < lengths[:, None])[
                        :, None, None, None, :
                    ]
                    scores = jnp.where(mask, scores, _NEG_INF)
                    # Self term from the fresh quantized k (bit-matching
                    # what the cache would hold).
                    kq = k8n[:, 0].astype(jnp.float32) * ksn[
                        :, 0, :, None
                    ].astype(jnp.float32)
                    s_self = jnp.einsum(
                        "bngh,bnh->bng",
                        qg[:, 0].astype(jnp.float32)
                        .reshape(B, KH, group, HD),
                        kq,
                    )[..., None, None] * (HD ** -0.5)  # (b, n, g, 1, 1)
                    s_self = jnp.transpose(s_self, (0, 1, 2, 4, 3))
                    m = jnp.maximum(
                        scores.max(axis=-1, keepdims=True), s_self
                    )
                    w = jnp.exp(scores - m) * mask
                    w_self = jnp.exp(s_self - m)
                    denom = jnp.maximum(
                        w.sum(axis=-1, keepdims=True) + w_self, 1e-30
                    )
                    w = (w / denom) * jnp.transpose(
                        sl(cache[3]), (0, 2, 1)
                    )[:, :, None, None, :]
                    out = jnp.einsum(
                        "bngst,btnh->bsngh",
                        w.astype(q.dtype),
                        sl(cache[1]).astype(q.dtype),
                        preferred_element_type=jnp.float32,
                    )
                    vq = (
                        v8n[:, 0].astype(jnp.float32)
                        * vsn[:, 0, :, None].astype(jnp.float32)
                    ).astype(q.dtype)  # (b, n, h)
                    wf = (w_self / denom)[:, :, :, 0, 0]  # (b, n, g)
                    out = out + jnp.einsum(
                        "bng,bnh->bngh", wf.astype(q.dtype), vq
                    )[:, None].reshape(B, 1, KH, group, HD)
                    out = out.reshape(B, 1, QH, HD)
                    cache = (
                        cache[0].at[li, bidx, positions].set(k8n),
                        cache[1].at[li, bidx, positions].set(v8n),
                        cache[2].at[li, bidx, positions].set(ksn),
                        cache[3].at[li, bidx, positions].set(vsn),
                    )
                    del vq
                    return (cache, li + 1, acc + out.mean()), None

                (cache, _, acc), _ = jax.lax.scan(
                    body, (cache, jnp.int32(0), jnp.float32(0)), None, length=L
                )
                return (cache, lengths + 1), acc

            (cache, lengths), accs = jax.lax.scan(
                step, (cache, lengths), None, length=STEPS
            )
            return cache, accs.sum()

    else:

        @functools.partial(jax.jit, donate_argnums=(0,))
        def run(cache, q, newk, lengths):
            def step(carry, _):
                cache, lengths = carry
                positions = lengths[:, None]
                bidx = jnp.arange(B, dtype=jnp.int32)[:, None]
                acc = jnp.float32(0)
                for li in range(L):
                    k8n, ksn = quant(newk)
                    v8n, vsn = quant(newk)
                    cache = (
                        cache[0].at[li, bidx, positions].set(k8n),
                        cache[1].at[li, bidx, positions].set(v8n),
                        cache[2].at[li, bidx, positions].set(ksn),
                        cache[3].at[li, bidx, positions].set(vsn),
                    )
                    out = attn_one_layer(
                        q,
                        cache[0][li, :, :WINDOW],
                        cache[1][li, :, :WINDOW],
                        cache[2][li, :, :WINDOW],
                        cache[3][li, :, :WINDOW],
                        positions,
                        lengths + 1,
                    )
                    acc = acc + out.mean()
                return (cache, lengths + 1), acc

            (cache, lengths), accs = jax.lax.scan(
                step, (cache, lengths), None, length=STEPS
            )
            return cache, accs.sum()

    cache, o = run(cache, q0, newk, lengths)
    _ = float(o)  # device->host sync
    best = 1e9
    for _i in range(3):
        t0 = time.perf_counter()
        cache, o = run(cache, q0, newk, lengths)
        _ = float(o)
        best = min(best, time.perf_counter() - t0)
    per_step = best / STEPS
    kv_bytes = 2 * B * WINDOW * KH * HD * L  # int8 K+V read once, ideal
    print(
        f"{mode:9s}: {per_step*1e3:8.2f} ms/step  "
        f"(KV window read-once ideal {kv_bytes/910e9*1e3:.1f} ms)"
    )


if __name__ == "__main__":
    main()
