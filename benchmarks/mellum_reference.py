"""The plain reference of the ``mellum`` family (Mellum2-12B-A2.5B) as
``models/hybrid.py`` serves it: float32 ``jax.numpy`` at the highest
matmul precision, written from the layer equations.

No kernel, no cache, no chunking, no batching, none of the program's
functions: one prompt, the whole sequence at once, one layer at a time.
Attention is the full softmax under an explicit (i, j) mask: position
``i`` sees ``j <= i`` in a ``full`` layer and ``i - sliding_window < j
<= i`` in a ``window`` layer.  Rotary embedding is half-split
(``rotate_half``) over the whole head; a window layer takes the plain
frequencies ``theta^(-2i/d)``, a full layer YaRN's (``transformers``'
``_compute_yarn_parameters``) with cos and sin multiplied by the
attention factor.  Every expert is computed whole for every position and
weighted by the position's routing weight for it (zero where it was not
chosen): softmax over all the router's outputs, the ``k`` largest (a tie
to the lower index), renormalised to sum to one.

Assumed where the public config has no key (each is also under
``assumed`` in ``benchmarks/configs/mellum2-12b-a2.5b-l12.json``): no
QK-norm; YaRN's ``truncate`` true; the head is untied; no MTP head.

The parameters are the serving pytree (``hybrid.init_params``'s layout:
``w_qkv`` holds the query heads, then the key heads, then the value
heads; ``w_gu_e`` an expert's gate and up side by side).
``benchmarks/mellum_reference.py`` is the benchmark's copy of this file
(``benchmarks/tests/test_arch_mellum.py`` holds the two equal).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


def _rms(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gain.astype(F32)


def inv_freq(rope: tuple, head_dim: int) -> np.ndarray:
    """(head_dim // 2,) float64.  ``rope`` = (rope_type, theta, factor,
    original_max, beta_fast, beta_slow, attention_factor, truncate)."""
    kind, theta, factor, original, beta_fast, beta_slow, _, truncate = rope
    plain = theta ** (-np.arange(0, head_dim, 2, dtype=np.float64) / head_dim)
    if kind == "default":
        return plain

    def dim_of(rotations):
        return head_dim * math.log(original / (rotations * 2 * math.pi)) / (2 * math.log(theta))

    low, high = dim_of(beta_fast), dim_of(beta_slow)
    if truncate:
        low, high = math.floor(low), math.ceil(high)
    low, high = max(low, 0), min(high, head_dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(head_dim // 2) - low) / (high - low), 0.0, 1.0)
    return (1.0 - ramp) * plain + ramp * plain / factor


def _rope(x, rope: tuple):
    """x: (s, heads, d) at positions 0..s-1; halves (x1, x2) ->
    (x1 cos - x2 sin, x2 cos + x1 sin), cos and sin times the factor."""
    s, _, d = x.shape
    ang = jnp.arange(s, dtype=F32)[:, None] * jnp.asarray(inv_freq(rope, d), F32)[None, :]
    cos, sin = (f(ang)[:, None, :] * F32(rope[6]) for f in (jnp.cos, jnp.sin))
    x1, x2 = x[..., : d // 2], x[..., d // 2 :]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def attention(h, lp, dims, mixer):
    """h: (s, D).  ``mixer``: ``full`` or ``window``."""
    s = h.shape[0]
    H, KH, hd = dims["H"], dims["KH"], dims["hd"]
    qkv = (h @ lp["w_qkv"].astype(F32)).reshape(s, H + 2 * KH, hd)
    rope = dims["rope_full"] if mixer == "full" else dims["rope_window"]
    q = _rope(qkv[:, :H], rope)
    k = _rope(qkv[:, H : H + KH], rope)
    v = qkv[:, H + KH :]
    # Query head h reads key head h // (H / KH).
    k, v = (jnp.repeat(x, H // KH, axis=1) for x in (k, v))
    scores = jnp.einsum("ihd,jhd->hij", q, k) / jnp.sqrt(F32(hd))
    i, j = jnp.arange(s)[:, None], jnp.arange(s)[None, :]
    mask = j <= i
    if mixer == "window":
        mask = mask & (j > i - dims["window"])
    probs = jax.nn.softmax(jnp.where(mask[None], scores, -jnp.inf), axis=-1)
    o = jnp.einsum("hij,jhd->ihd", probs, v)
    return o.reshape(s, H * hd) @ lp["w_o"].astype(F32)


def routing(h, lp, dims):
    """(s, E) routing weights: zero where an expert was not chosen.  The
    experts are ranked by a stable descending sort, so a tie goes to the
    lower index."""
    p = jax.nn.softmax(h @ lp["router"].astype(F32), axis=-1)
    rank = jnp.argsort(jnp.argsort(-p, axis=-1, stable=True), axis=-1)
    w = jnp.where(rank < dims["k"], p, 0.0)
    if dims["norm_topk"]:
        w = w / w.sum(-1, keepdims=True)
    return w


def _swiglu(h, w_gu, w_down):
    gu = h @ w_gu.astype(F32)
    half = gu.shape[-1] // 2
    return (jax.nn.silu(gu[:, :half]) * gu[:, half:]) @ w_down.astype(F32)


def expert_layer(h, lp, dims):
    """h: (s, D): every expert for every position, weighted."""
    w = routing(h, lp, dims)

    def expert(e, out):
        pick = lambda name: jax.lax.dynamic_index_in_dim(lp[name], e, 0, keepdims=False)
        y = _swiglu(h, pick("w_gu_e"), pick("w_down_e"))
        return out + y * jax.lax.dynamic_index_in_dim(w, e, 1)

    return jax.lax.fori_loop(0, dims["E"], expert, jnp.zeros_like(h))


def _rope_tuple(spec) -> tuple:
    return (
        str(spec.rope_type), float(spec.theta), float(spec.factor), int(spec.original_max),
        float(spec.beta_fast), float(spec.beta_slow), float(spec.attention_factor),
        bool(spec.truncate),
    )


def _dims(cfg) -> dict:
    return {
        "H": cfg.n_heads, "KH": cfg.n_kv_heads, "hd": cfg.attn_head_dim,
        "window": int(cfg.sliding_window), "eps": float(cfg.norm_eps),
        "rope_full": _rope_tuple(cfg.rope_full), "rope_window": _rope_tuple(cfg.rope_window),
        "E": cfg.n_experts, "k": cfg.n_experts_per_tok, "norm_topk": bool(cfg.norm_topk),
    }


@functools.partial(jax.jit, static_argnames=("mixer", "dims_t"))
def _layer(x, lp, mixer, dims_t):
    dims = dict(dims_t)
    x = x + attention(_rms(x, lp["attn_norm"], dims["eps"]), lp, dims, mixer)
    return x + expert_layer(_rms(x, lp["mlp_norm"], dims["eps"]), lp, dims)


def hidden_states(params, cfg, tokens):
    """(s, D) float32 before the final norm, for one prompt."""
    dims_t = tuple(sorted(_dims(cfg).items()))
    with jax.default_matmul_precision("highest"):
        x = params["embed"][jnp.asarray(tokens, jnp.int32)].astype(F32)
        for (mixer, mlp), lp in zip(cfg.layer_kinds, params["layers"]):
            if mixer not in ("full", "window") or mlp != "experts":
                raise ValueError(f"not a layer of this family: ({mixer}, {mlp})")
            x = _layer(x, lp, mixer, dims_t)
    return x


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, final_norm, lm_head, eps):
    return _rms(x, final_norm, eps) @ lm_head.astype(F32)


def head(params, cfg, x):
    """Final norm and the untied head: (..., D) -> (..., V) float32."""
    with jax.default_matmul_precision("highest"):
        return _head(x, params["final_norm"], params["lm_head"], float(cfg.norm_eps))


def all_logits(params, cfg, tokens):
    """(s, V) float32 logits at every position of one prompt."""
    return head(params, cfg, hidden_states(params, cfg, tokens))
