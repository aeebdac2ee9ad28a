"""The plain reference of the looped llama-shaped decoder (Ouro-2.6B,
``model_type: ouro``) as ``models/llama.py`` serves it: float32
``jax.numpy`` at the highest matmul precision, written from the equations
of the LoopLM paper (arXiv:2510.25741) and the model repository's
``modeling_ouro.py``.

No kernel, no cache, no scan, no batching, none of the program's
functions: one prompt, the whole sequence at once, two Python loops (the
passes, and the layers inside a pass), one jitted layer program called
once for every (pass, layer).

With ``x`` the residual stream (s, D), ``L`` layers, ``T`` passes:

    x <- Emb[token]                                  (no scaling)
    for u in 0..T-1:
      for l in 0..L-1:
        h = RMSNorm(x; g1_l)
        q, k, v = h Wq_l, h Wk_l, h Wv_l             (no bias, no QK-norm)
        q, k = rot(q, pos), rot(k, pos)              (whole head, halves paired)
        a = softmax(q k^T / sqrt(d), causal) v       (this pass's keys only:
                                                      they are computed here,
                                                      from this pass's stream)
        x = x + RMSNorm(a Wo_l; g2_l)                (the norm on the way OUT)
        h = RMSNorm(x; g3_l)
        m = (silu(h Wg_l) * (h Wu_l)) Wd_l
        x = x + RMSNorm(m; g4_l)
      x = RMSNorm(x; g_final) -> h_u                 (after EVERY pass; h_u
                                                      feeds pass u + 1)
      lam_u = sigmoid(h_u . w_gate + b_gate)
    p_u = lam_u prod_{j<u} (1 - lam_j) for u < T-1;  p_{T-1} = prod_{j<T-1} (1 - lam_j)
    exit step = first u with sum_{j<=u} p_j >= threshold, else T-1
    logits = h_{exit step} W_head

A stack without sandwich norms (``cfg.sandwich_norm`` false) leaves g2 and
g4 out, and one pass with them out is the plain llama decoder of
``benchmarks/reference.py``.

Departures from the published model: none in the mathematics.  What the
public ``config.json`` has no key for (the four norms' order, the norm
after every pass, a cache a (pass, layer), the gate and the exit rule, no
bias, the rotation's pairing) stands under ``assumed`` in
``benchmarks/configs/ouro-2.6b.json``, each with where it comes from.

The parameters are the serving pytree (``models.llama.init_params``'s
layout, layers stacked on axis 0, packed ``wqkv`` / ``w_gu`` or not, int8
or not: a quantized leaf is dequantized here one layer at a time, int8 x
its per-channel scale, so that both sides of a comparison hold the same
numbers).  ``cfg`` is read for its sizes only.
``benchmarks/ouro_reference.py`` is the benchmark's copy of this file
(``tests/test_ouro_model.py`` holds the two equal byte for byte).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _dense(w, one=lambda a: a):
    """A weight leaf as float32: a plain array, int8 x per-channel scale,
    or the W8A8 path's blocked int8 tiles (``ops/qmm.py``: column blocks
    ``(NB, K_pad, BN)``, zero-padded), which the benchmark's control run
    serves.  ``one`` picks the part of each array that is wanted."""
    if hasattr(w, "tiles"):
        t = one(w.tiles).astype(F32) * one(w.scale).astype(F32)
        return jnp.moveaxis(t, -3, -2).reshape(*t.shape[:-3], t.shape[-2], -1)[
            ..., : w.k, : w.n
        ]
    if hasattr(w, "q") and hasattr(w, "scale"):
        return one(w.q).astype(F32) * one(w.scale).astype(F32)
    return one(w).astype(F32)


def _pick(layers, name, layer):
    """Layer ``layer`` of a stacked leaf, as float32 (sliced inside the
    jitted layer, so that no second copy of a whole leaf is made)."""
    return _dense(
        layers[name],
        lambda a: jax.lax.dynamic_index_in_dim(a, layer, 0, keepdims=False),
    )


def rms_norm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gain.astype(F32)


def _rope(x, theta):
    """x (s, heads, d) at positions 0..s-1: the whole head rotated, value
    ``j`` paired with value ``j + d / 2``, frequencies theta^(-2j / d)."""
    s, _, d = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    ang = jnp.arange(s, dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2 :]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def attention(h, layers, layer, dims):
    s = h.shape[0]
    H, KV, d = dims["H"], dims["KV"], dims["d"]
    if "wqkv" in layers:
        q, k, v = jnp.split(h @ _pick(layers, "wqkv", layer), [H * d, (H + KV) * d], axis=-1)
    else:
        q, k, v = (h @ _pick(layers, n, layer) for n in ("wq", "wk", "wv"))
    q = _rope(q.reshape(s, H, d), dims["theta"])
    k = jnp.repeat(_rope(k.reshape(s, KV, d), dims["theta"]), H // KV, axis=1)
    v = jnp.repeat(v.reshape(s, KV, d), H // KV, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(F32(d))
    causal = jnp.tril(jnp.ones((s, s), dtype=bool))
    probs = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), axis=-1)
    return jnp.einsum("hqk,khd->qhd", probs, v).reshape(s, H * d) @ _pick(layers, "wo", layer)


def mlp(h, layers, layer):
    if "w_gu" in layers:
        gate, up = jnp.split(h @ _pick(layers, "w_gu", layer), 2, axis=-1)
    else:
        gate, up = h @ _pick(layers, "w_gate", layer), h @ _pick(layers, "w_up", layer)
    return (jax.nn.silu(gate) * up) @ _pick(layers, "w_down", layer)


@functools.partial(jax.jit, static_argnames=("dims_t",))
def _layer(x, layers, layer, dims_t):
    dims = dict(dims_t)
    eps = dims["eps"]
    out = attention(rms_norm(x, _pick(layers, "attn_norm", layer), eps), layers, layer, dims)
    if dims["sandwich"]:
        out = rms_norm(out, _pick(layers, "attn_post_norm", layer), eps)
    x = x + out
    out = mlp(rms_norm(x, _pick(layers, "mlp_norm", layer), eps), layers, layer)
    if dims["sandwich"]:
        out = rms_norm(out, _pick(layers, "mlp_post_norm", layer), eps)
    return x + out


def _dims_t(cfg) -> tuple:
    return tuple(sorted({
        "H": cfg.n_heads, "KV": cfg.n_kv_heads, "d": cfg.head_dim,
        "theta": float(cfg.rope_theta), "eps": float(cfg.norm_eps),
        "sandwich": bool(cfg.sandwich_norm),
    }.items()))


def embed(params, tokens):
    """(s, D) float32 rows of the embedding, dequantized where it is int8."""
    tokens = jnp.asarray(tokens, jnp.int32)
    table = params["embed"]
    if hasattr(table, "q"):
        return table.q[tokens].astype(F32) * table.scale[tokens].astype(F32)
    return table[tokens].astype(F32)


def layer(params, cfg, x, l: int):
    """One application of layer ``l`` to the stream ``x`` (s, D)."""
    return _layer(x, params["layers"], jnp.int32(l), _dims_t(cfg))


@functools.partial(jax.jit, static_argnames=("eps",))
def _final_norm(x, gain, eps):
    return rms_norm(x, gain, eps)


def final_norm(params, cfg, x):
    return _final_norm(x, params["final_norm"], float(cfg.norm_eps))


@jax.jit
def _head(h, lm_head):
    return h @ _dense(lm_head)


def head(params, h):
    """Logits (..., V) of normed hidden states (the head is untied)."""
    return _head(h, params["lm_head"])


def hidden_passes(params, cfg, tokens):
    """``h_u`` for every pass: (T, s, D), each the final norm's output."""
    with jax.default_matmul_precision("highest"):
        x = embed(params, tokens)
        passes = []
        for _ in range(cfg.ut_steps):
            for l in range(cfg.n_layers):
                x = layer(params, cfg, x, l)
            x = final_norm(params, cfg, x)
            passes.append(x)
        return jnp.stack(passes)


def _exit_pdf(params, passes):
    """(T, s): the exit distribution of every position over the passes."""
    gate = params["exit_gate"]
    lam = jax.nn.sigmoid(passes @ gate["w"].astype(F32) + gate["b"].astype(F32))
    stay = jnp.cumprod(1.0 - lam[:-1], axis=0)  # prod_{j<=u} (1 - lam_j), u < T-1
    before = jnp.concatenate([jnp.ones_like(lam[:1]), stay[:-1]], axis=0)
    return jnp.concatenate([lam[:-1] * before, stay[-1:]], axis=0)


def exit_pdf(params, cfg, tokens):
    """(T, s) float32: ``p_u`` of every position; it sums to 1 over u."""
    with jax.default_matmul_precision("highest"):
        return _exit_pdf(params, hidden_passes(params, cfg, tokens))


def exit_steps(pdf, threshold: float):
    """(s,) the pass each position leaves at: the first whose cumulative
    mass reaches ``threshold``, else the last."""
    reached = jnp.cumsum(pdf, axis=0) >= threshold
    reached = reached.at[-1].set(True)
    return jnp.argmax(reached, axis=0)


def all_logits(params, cfg, tokens, threshold=None):
    """Float32 logits (s, V) at every position of one prompt, each from
    the pass its exit rule names (``threshold`` None: the
    configuration's)."""
    threshold = cfg.early_exit_threshold if threshold is None else threshold
    with jax.default_matmul_precision("highest"):
        passes = hidden_passes(params, cfg, tokens)
        if cfg.ut_steps == 1:
            return head(params, passes[0])
        steps = exit_steps(_exit_pdf(params, passes), float(threshold))
        chosen = jnp.take_along_axis(passes, steps[None, :, None], axis=0)[0]
        return head(params, chosen)
