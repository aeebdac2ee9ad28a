"""The plain reference: a llama-shaped decoder in float32 ``jax.numpy``.

No kernels, no cache, no batching, no scan: one prompt, one layer at a
time (one jitted layer program, called once per layer).  Dense SwiGLU
MLP, or Mixtral's top-2 of 8 experts (softmax over all router logits,
top-k, renormalised; HF MixtralSparseMoeBlock).
Rotary embedding in the split-half (HF) convention, RMSNorm, grouped-
query attention, untied head.

Departures from the published models: none in the mathematics.  The
weights are the scheduler's own, dequantized here one layer at a time
(int8 x per-channel scale), so that the comparison covers what the
serving path computes with them — bf16 activations, int8 KV, kernels,
chunking — and not the quantization both sides share.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _dense(w, one=lambda a: a) -> jnp.ndarray:
    """A weight leaf as float32: a plain array, int8 x per-channel scale,
    or the W8A8 path's blocked int8 tiles (``ops/qmm.py``: column blocks
    ``(NB, K_pad, BN)``, zero-padded), which the control run serves.
    ``one`` picks the part of each array that is wanted."""
    if hasattr(w, "tiles"):
        t = one(w.tiles).astype(F32) * one(w.scale).astype(F32)
        return jnp.moveaxis(t, -3, -2).reshape(*t.shape[:-3], t.shape[-2], -1)[
            ..., : w.k, : w.n
        ]
    if hasattr(w, "q") and hasattr(w, "scale"):
        return one(w.q).astype(F32) * one(w.scale).astype(F32)
    return one(w).astype(F32)


def _pick(layers, name, layer) -> jnp.ndarray:
    """Layer ``layer`` of a stacked weight leaf, as float32.  Sliced
    inside the jitted layer, so that no second copy of a whole leaf is
    ever made."""
    return _dense(
        layers[name],
        lambda a: jax.lax.dynamic_index_in_dim(a, layer, 0, keepdims=False),
    )


def _rms_norm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gain


def _rope(x, theta):
    """x: (s, heads, hd); positions 0..s-1; split-half rotation."""
    s, _, hd = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd))
    ang = jnp.arange(s, dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2 :]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _attention(x, layers, layer, dims):
    s = x.shape[0]
    H, KV, HD = dims["H"], dims["KV"], dims["HD"]
    if "wqkv" in layers:
        qkv = x @ _pick(layers, "wqkv", layer)
        q, k, v = jnp.split(qkv, [H * HD, (H + KV) * HD], axis=-1)
    else:
        q, k, v = (x @ _pick(layers, n, layer) for n in ("wq", "wk", "wv"))
    q = _rope(q.reshape(s, H, HD), dims["theta"])
    k = _rope(k.reshape(s, KV, HD), dims["theta"])
    v = v.reshape(s, KV, HD)
    k = jnp.repeat(k, H // KV, axis=1)
    v = jnp.repeat(v, H // KV, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(F32(HD))
    mask = jnp.tril(jnp.ones((s, s), dtype=bool))
    probs = jax.nn.softmax(jnp.where(mask[None], scores, -jnp.inf), axis=-1)
    out = jnp.einsum("hqk,khd->qhd", probs, v).reshape(s, H * HD)
    return out @ _pick(layers, "wo", layer)


def _dense_mlp(h, layers, layer, dims):
    if "w_gu" in layers:
        gate, up = jnp.split(h @ _pick(layers, "w_gu", layer), 2, axis=-1)
    else:
        gate = h @ _pick(layers, "w_gate", layer)
        up = h @ _pick(layers, "w_up", layer)
    return (jax.nn.silu(gate) * up) @ _pick(layers, "w_down", layer)


def _moe_mlp(h, layers, layer, dims):
    """Top-k experts, one expert at a time: its float32 output for every
    position, weighted by the position's routing weight for that expert
    (zero where it was not among the top k).  A whole layer of experts in
    float32 (5.6 GB at Mixtral's widths) does not fit beside the served
    model; one expert (0.7 GB) does."""
    probs = jax.nn.softmax(h @ _pick(layers, "router", layer), axis=-1)  # (s, E)
    top_w, top_i = jax.lax.top_k(probs, dims["K"])
    if dims["norm_topk"]:
        top_w = top_w / top_w.sum(axis=-1, keepdims=True)

    def weights(name, e):
        w = layers[name]  # (layers, experts, rows, cols): leading axes only
        return jax.lax.dynamic_slice(w, (layer, e, 0, 0), (1, 1) + w.shape[2:])[
            0, 0
        ].astype(F32)

    def expert(e, out):
        gate = h @ weights("w_gate_e", e)
        up = h @ weights("w_up_e", e)
        y = (jax.nn.silu(gate) * up) @ weights("w_down_e", e)
        weight = jnp.where(top_i == e, top_w, 0.0).sum(axis=-1)  # (s,)
        return out + y * weight[:, None]

    return jax.lax.fori_loop(0, dims["E"], expert, jnp.zeros_like(h))


@functools.partial(jax.jit, static_argnames=("dims_t",))
def _layer(x, layers, layer, dims_t):
    dims = dict(dims_t)
    h = _rms_norm(x, _pick(layers, "attn_norm", layer), dims["eps"])
    x = x + _attention(h, layers, layer, dims)
    h = _rms_norm(x, _pick(layers, "mlp_norm", layer), dims["eps"])
    mlp = _moe_mlp if dims["E"] > 1 else _dense_mlp
    return x + mlp(h, layers, layer, dims)


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x_last, final_norm, lm_head, eps):
    return _rms_norm(x_last, final_norm.astype(F32), eps) @ _dense(lm_head)


def last_logits(
    params, cfg, tokens, pad_to: int = 0, norm_topk: bool = True
) -> jnp.ndarray:
    """Float32 logits at the last position of one prompt.

    ``params`` is the serving pytree (``models.llama`` layout, layers
    stacked on axis 0, packed or not, int8 or not); ``cfg`` anything with
    the ``LlamaConfig`` field names.  ``pad_to`` pads the prompt on the
    right to that length, so that one compiled program serves prompts of
    every length up to it: attention is causal and everything else acts
    on one position, so no position before the pad sees it.
    ``norm_topk`` False leaves the top-k routing weights as the softmax
    over all experts gave them (families whose ``norm_topk_prob`` is
    false); an architecture module of such a family passes it.
    """
    dims_t = tuple(
        sorted(
            {
                "H": cfg.n_heads, "KV": cfg.n_kv_heads, "HD": cfg.head_dim,
                "E": cfg.n_experts, "K": cfg.n_experts_per_tok,
                "norm_topk": bool(norm_topk),
                "theta": float(cfg.rope_theta), "eps": float(cfg.norm_eps),
            }.items()
        )
    )
    last = len(tokens) - 1
    tokens = jnp.asarray(
        list(tokens) + [0] * max(0, pad_to - len(tokens)), dtype=jnp.int32
    )
    with jax.default_matmul_precision("highest"):
        table = params["embed"]
        if hasattr(table, "q"):
            x = table.q[tokens].astype(F32) * table.scale[tokens].astype(F32)
        else:
            x = table[tokens].astype(F32)
        for layer in range(cfg.n_layers):
            x = _layer(x, params["layers"], jnp.int32(layer), dims_t)
        return _head(x[last], params["final_norm"], params["lm_head"], float(cfg.norm_eps))
