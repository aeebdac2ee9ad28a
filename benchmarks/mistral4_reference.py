"""The plain reference of the ``mistral4`` family
(Mistral-Small-4-119B-2603's language model) as ``models/hybrid.py``
serves it: float32 ``jax.numpy`` at the highest matmul precision, written
from the layer equations.

No kernel, no cache, no chunked prefill, no batching, none of the
program's functions (nothing of ``ops/``): one prompt, the whole sequence
at once, one layer at a time, keys and values expanded for every
position.

* Layer ``l``: ``x = x + Attn(RMSNorm(x))``, ``x = x + MLP(RMSNorm(x))``
  (pre-norm), every layer alike.
* Attention (latent, low-rank query): ``c_q = RMSNorm_q(h W_qa)``,
  ``q = c_q W_qb`` -> ``H`` heads of ``[q_nope ; q_rope]``;
  ``[c_kv ; k_r] = h W_kva``, ``c = RMSNorm_kv(c_kv)``, ``k_r`` one key
  for all heads; ``[k_nope_h ; v_h] = c W_kvb`` a head.  ``q_rope`` and
  ``k_r`` are rotated over adjacent pairs (x0, x1), (x2, x3), ... with
  YaRN's frequencies (:func:`yarn_frequencies`).  ``score = s a(p_q)
  (q_nope . k_nope + q_rope . k_r)`` under an explicit (i, j) mask
  ``j <= i``, the full softmax over every key, ``o_h = sum softmax v_h``,
  output ``[o_h] W_o``.  ``a(p) = 1 + beta ln(1 + floor(p / original))``
  multiplies the rotated query; ``s = (nope + rope)^-1/2 m^2`` with
  ``m = 0.1 mscale_all_dim ln(factor) + 1``, which the configuration
  carries as ``softmax_mscale``.
* MLP: ``g = softmax(h W_r)`` over all ``E`` router outputs, the ``k``
  largest (a tie to the lower index), their weights renormalised to sum
  to one and scaled, the chosen experts' SwiGLUs weighted, plus the shared
  expert unscaled.  Of the ``E`` experts only ``held`` from ``offset`` on
  are computed (one chip's share: what the absent ones would add is left
  out); ``E`` and 0 give the uncut layer.
* Final RMSNorm and the untied head (:func:`head`, which the caller
  gives a block of positions at a time).

The queries are taken ``QUERY_BLOCK`` at a time against every key only so
that a 9k-token prompt's float32 scores fit beside a serving engine
(32 heads x 8,960 x 8,960 would be 10 GB): each query's softmax is still
the whole row under the mask, nothing is carried from block to block.

What the public config does not settle (softmax routing without a
selection bias, ``m^2`` in the softmax scale, ``a(p)`` on the rotated
query, YaRN's truncated ramp) is listed under ``assumed`` in
``benchmarks/configs/mistral-small-4-119b-l6e32.json``.

The parameters are the serving pytree (``hybrid.init_params``'s layout:
``w_qb`` and ``w_kvb`` hold a head's parts side by side, ``w_gu`` gate and
up side by side).  ``cfg`` is read for its sizes only.
``benchmarks/mistral4_reference.py`` is the benchmark's copy of this file
(``benchmarks/tests/test_arch_mistral4.py`` holds the two equal).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
QUERY_BLOCK = 256


def _rms(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gain.astype(F32)


def _q_norm(x, gain, eps):
    """The RMSNorm of the query's latent (a function of its own, so that a
    control can leave it out)."""
    return _rms(x, gain, eps)


def yarn_frequencies(d: int, theta: float, factor: float, original: int,
                     beta_fast: float, beta_slow: float) -> np.ndarray:
    """(d / 2,) inverse frequencies: pair ``i`` keeps ``theta^(-2i/d)``
    below the dimension that turns ``beta_fast`` times over ``original``
    positions (rounded down), takes it divided by ``factor`` above the one
    that turns ``beta_slow`` times (rounded up), and a linear ramp of the
    two between them."""
    plain = theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)

    def dim_of(turns: float) -> float:
        return d * math.log(original / (turns * 2.0 * math.pi)) / (2.0 * math.log(theta))

    low = max(math.floor(dim_of(beta_fast)), 0)
    high = min(math.ceil(dim_of(beta_slow)), d - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(d // 2, dtype=np.float64) - low) / (high - low), 0.0, 1.0)
    return ((1.0 - ramp) * plain + ramp * plain / factor).astype(np.float32)


def _rope_pairs(x, inv):
    """x: (s, ..., d) at positions 0..s-1; adjacent pairs (x0, x1) ->
    (x0 cos - x1 sin, x1 cos + x0 sin)."""
    s = x.shape[0]
    ang = jnp.arange(s, dtype=F32)[:, None] * jnp.asarray(inv)[None, :]
    ang = ang.reshape((s,) + (1,) * (x.ndim - 2) + (ang.shape[-1],))
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x0, x1 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x0 * cos - x1 * sin, x1 * cos + x0 * sin], axis=-1).reshape(x.shape)


def attention(h, lp, dims):
    """h: (s, D)."""
    s = h.shape[0]
    H, rank, nope, rope, vd = (dims[k] for k in ("H", "rank", "nope", "rope", "vd"))
    inv = yarn_frequencies(rope, dims["theta"], dims["factor"], dims["original"],
                           dims["beta_fast"], dims["beta_slow"])
    c_q = _q_norm(h @ lp["w_qa"].astype(F32), lp["q_norm"], dims["eps"])
    q = (c_q @ lp["w_qb"].astype(F32)).reshape(s, H, nope + rope)
    ckr = h @ lp["w_kva"].astype(F32)
    c = _rms(ckr[:, :rank], lp["kv_norm"], dims["eps"])
    k_r = _rope_pairs(ckr[:, rank:], inv)  # (s, rope): one key for all heads
    kv = (c @ lp["w_kvb"].astype(F32)).reshape(s, H, nope + vd)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    pos = jnp.arange(s)
    a = 1.0 + dims["beta"] * jnp.log1p(jnp.floor(pos.astype(F32) / dims["original"]))
    q_nope = q[..., :nope] * a[:, None, None]
    q_rope = _rope_pairs(q[..., nope:], inv) * a[:, None, None]
    scale = F32((nope + rope) ** -0.5 * dims["mscale"] ** 2)

    def block(args):
        i, qn, qr = args  # (B,), (B, H, nope), (B, H, rope)
        scores = (
            jnp.einsum("ihd,jhd->hij", qn, k_nope) + jnp.einsum("ihd,jd->hij", qr, k_r)
        ) * scale
        mask = pos[None, :] <= i[:, None]
        probs = jax.nn.softmax(jnp.where(mask[None], scores, -jnp.inf), axis=-1)
        return jnp.einsum("hij,jhd->ihd", probs, v)

    # Whole blocks of queries: the last is filled up with copies of the
    # last query, which are dropped again.
    B = min(QUERY_BLOCK, s)
    n = -(-s // B)
    blocks = lambda x: jnp.concatenate(
        [x, jnp.broadcast_to(x[-1:], (n * B - s,) + x.shape[1:])]
    ).reshape((n, B) + x.shape[1:])
    o = jax.lax.map(block, (blocks(pos), blocks(q_nope), blocks(q_rope)))
    o = o.reshape((n * B, H * vd))[:s]
    return o @ lp["w_o"].astype(F32)


def routing(h, lp, dims):
    """(s, E) routing weights: zero where an expert was not chosen.  The
    experts are ranked on the softmax scores by a stable descending sort,
    so a tie goes to the lower index."""
    g = jax.nn.softmax(h @ lp["router"].astype(F32), axis=-1)
    rank = jnp.argsort(jnp.argsort(-g, axis=-1, stable=True), axis=-1)
    w = jnp.where(rank < dims["k"], g, 0.0)
    if dims["norm_topk"]:
        w = w / w.sum(-1, keepdims=True)
    return w * F32(dims["scale"])


def _swiglu(h, w_gu, w_down):
    gu = h @ w_gu.astype(F32)
    half = gu.shape[-1] // 2
    return (jax.nn.silu(gu[:, :half]) * gu[:, half:]) @ w_down.astype(F32)


def routed_experts(h, lp, dims):
    """h: (s, D): every expert held for every position, weighted.  Expert
    ``e`` of ``lp`` is the router's output ``offset + e``."""
    w = routing(h, lp, dims)

    def expert(e, out):
        pick = lambda name: jax.lax.dynamic_index_in_dim(lp[name], e, 0, keepdims=False)
        y = _swiglu(h, pick("w_gu_e"), pick("w_down_e"))
        return out + y * jax.lax.dynamic_index_in_dim(w, dims["offset"] + e, 1)

    return jax.lax.fori_loop(0, dims["held"], expert, jnp.zeros_like(h))


def mlp(h, lp, dims):
    return routed_experts(h, lp, dims) + _swiglu(h, lp["w_gu_s"], lp["w_down_s"])


def _dims(cfg, held, offset) -> dict:
    spec = cfg.rope_latent
    if cfg.score_function != "softmax" or cfg.n_group != 1 or spec is None:
        raise ValueError("this reference routes by softmax scores over one group, under YaRN")
    held = cfg.experts_held if held is None else int(held)
    return {
        "H": cfg.n_heads, "rank": cfg.kv_lora_rank, "nope": cfg.qk_nope_head_dim,
        "rope": cfg.qk_rope_head_dim, "vd": cfg.v_head_dim, "eps": float(cfg.norm_eps),
        "theta": float(spec.theta), "factor": float(spec.factor),
        "original": int(spec.original_max), "beta_fast": float(spec.beta_fast),
        "beta_slow": float(spec.beta_slow), "beta": float(cfg.attn_scale_beta),
        "mscale": float(cfg.softmax_mscale),
        "k": cfg.n_experts_per_tok, "norm_topk": bool(cfg.norm_topk),
        "scale": float(cfg.routed_scaling), "held": held,
        "offset": cfg.expert_offset if offset is None else int(offset),
    }


@functools.partial(jax.jit, static_argnames=("dims_t",))
def _layer(x, lp, dims_t):
    dims = dict(dims_t)
    x = x + attention(_rms(x, lp["attn_norm"], dims["eps"]), lp, dims)
    return x + mlp(_rms(x, lp["mlp_norm"], dims["eps"]), lp, dims)


def hidden_states(params, cfg, tokens, held=None, offset=None):
    """(s, D) float32 before the final norm, for one prompt; ``held``
    experts from ``offset`` (absent: the configuration's share)."""
    dims_t = tuple(sorted(_dims(cfg, held, offset).items()))
    with jax.default_matmul_precision("highest"):
        x = params["embed"][jnp.asarray(tokens, jnp.int32)].astype(F32)
        for kind, lp in zip(cfg.layer_kinds, params["layers"]):
            if kind != ("mla", "experts"):
                raise ValueError(f"not a layer of this family: {kind}")
            x = _layer(x, lp, dims_t)
    return x


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, final_norm, lm_head, eps):
    return _rms(x, final_norm, eps) @ lm_head.astype(F32)


def head(params, cfg, x):
    """Final norm and the untied head: (..., D) -> (..., V) float32."""
    with jax.default_matmul_precision("highest"):
        return _head(x, params["final_norm"], params["lm_head"], float(cfg.norm_eps))


def all_logits(params, cfg, tokens, held=None, offset=None):
    """(s, V) float32 logits at every position of one prompt."""
    return head(params, cfg, hidden_states(params, cfg, tokens, held, offset))
