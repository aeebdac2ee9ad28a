"""The plain reference of the ``longcat_flash`` family
(LongCat-Flash-Chat) as ``models/hybrid.py`` serves it: float32
``jax.numpy`` at the highest matmul precision, written from the layer
equations.

No kernel, no cache, no chunked prefill, no batching, none of the
program's functions (nothing of ``ops/``): one prompt, the whole sequence
at once, one published layer at a time, keys and values expanded for every
position, routing by a plain top-k and a loop over the experts.

* A published layer (``N`` RMSNorm, four of them a layer; ``A0, A1`` its
  two attention sublayers, ``F0, F1`` its two dense SwiGLUs, ``M`` its one
  expert layer)::

      h1 = h  + A0(N(h))
      u  = N(h1);   m = M(u);   h2 = h1 + F0(u)
      h3 = h2 + A1(N(h2))
      h4 = h3 + F1(N(h3))
      out = h4 + m

  The expert branch reads the first sublayer's normed post-attention
  stream and is added after the second sublayer's dense MLP
  (:func:`_join`, a function of its own so that a control can add it
  early).
* ``A`` (latent attention): ``c_q = a_q RMSNorm(x W_qa)``, ``a_q = (D /
  q_lora_rank)^1/2``; ``q = c_q W_qb`` -> ``H`` heads of ``[q_nope ;
  q_rope]``; ``[c' ; k'] = x W_kva``, ``c = a_kv RMSNorm(c')``, ``a_kv =
  (D / kv_lora_rank)^1/2``, ``k_r = rot(k')`` one key for all heads,
  neither normed nor scaled; ``[k_nope_h ; v_h] = c W_kvb`` a head.
  ``q_rope`` and ``k_r`` are rotated over adjacent pairs (x0, x1), (x2,
  x3), ... with the plain frequencies ``theta^(-2i/rope)``.  ``score =
  (nope + rope)^-1/2 (q_nope . k_nope + q_rope . k_r)`` under an explicit
  (i, j) mask ``j <= i``, the full softmax over every key, ``o_h = sum
  softmax v_h``, output ``[o_h] W_o``; no gate, no bias.
* ``M``: ``s = softmax(u W_r)`` over ALL the router's outputs, the
  ``real`` published experts and then ``zero`` identity experts; the ``k``
  largest of ``s + b`` (a tie to the lower index); weights ``s_i`` times
  the scaling factor, not renormalised (:func:`_weights`); ``m = sum_{i <
  real} w_i E_i(u) + sum_{i >= real} w_i u`` (:func:`_identity`).  Of the
  real experts only ``held`` from ``offset`` on are computed (one chip's
  share: what the absent ones would add is left out); ``real`` and 0 give
  the uncut layer.  ``identity=False`` leaves the identity term out: a
  share that is not the token's own chip's (the share test counts that
  term once).
* Final RMSNorm and the untied head (:func:`head`, which the caller gives
  a block of positions at a time).

The queries are taken ``QUERY_BLOCK`` at a time against every key, and the
MLPs' positions ``ROW_BLOCK`` at a time, only so that a 4.9k-token
prompt's float32 scores and a dense MLP's float32 activations fit beside a
serving engine that fills the chip (64 heads x 4,864 x 4,864 would be 6
GB): each query's softmax is still the whole row under the mask, and an
MLP is a function of one position.  For the same reason a published layer
is not one compiled function but five (its sublayers' attention, its dense
MLPs, its experts): a layer's share in float32 is 5 GB, the largest single
matrix 0.6 GB.

Every step a control of the comparison changes is a function of its own
(``_rescale``, ``_identity``, ``_weights``, ``_join``, ``_swiglu``).  What
the catalog row does not settle is listed under ``assumed`` in
``benchmarks/configs/longcat-flash-chat-l4e16.json``.

The parameters are the serving pytree (``hybrid.init_params``'s layout: a
published layer is two entries of ``params["layers"]``, the first with the
router and the experts; ``w_qb`` and ``w_kvb`` hold a head's parts side by
side, ``w_gu`` gate and up side by side).  ``cfg`` is read for its sizes
only.  ``benchmarks/longcat_flash_reference.py`` is the benchmark's copy of
this file (``benchmarks/tests/test_arch_longcat_flash.py`` holds the two
equal).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

F32 = jnp.float32
QUERY_BLOCK = 128
ROW_BLOCK = 1024


def _rms(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gain.astype(F32)


def _rescale(c, d_model: int, rank: int):
    """``mla_scale_q_lora`` / ``mla_scale_kv_lora``: a normed latent times
    (D / rank)^1/2."""
    return c * F32((d_model / rank) ** 0.5)


def _rope_pairs(x, theta: float):
    """x: (s, ..., d) at positions 0..s-1; adjacent pairs (x0, x1) ->
    (x0 cos - x1 sin, x1 cos + x0 sin) at ``theta^(-2i/d)``."""
    s, d = x.shape[0], x.shape[-1]
    inv = F32(theta) ** (-jnp.arange(0, d, 2, dtype=F32) / d)
    ang = jnp.arange(s, dtype=F32)[:, None] * inv[None, :]
    ang = ang.reshape((s,) + (1,) * (x.ndim - 2) + (ang.shape[-1],))
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x0, x1 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x0 * cos - x1 * sin, x1 * cos + x0 * sin], axis=-1).reshape(x.shape)


def _blocks(x, n: int, B: int):
    """Whole blocks of rows: the last is filled up with copies of the last
    row, which are dropped again."""
    s = x.shape[0]
    filled = jnp.concatenate([x, jnp.broadcast_to(x[-1:], (n * B - s,) + x.shape[1:])])
    return filled.reshape((n, B) + x.shape[1:])


def _by_rows(fn, x):
    """``fn`` (a function of each position alone) over ``x`` (s, D),
    ``ROW_BLOCK`` positions at a time."""
    s = x.shape[0]
    B = min(ROW_BLOCK, s)
    n = -(-s // B)
    return jax.lax.map(fn, _blocks(x, n, B)).reshape((n * B,) + x.shape[1:])[:s]


def attention(h, lp, dims):
    """h: (s, D), normed -> (s, D)."""
    s, D = h.shape
    H, r_q, rank, nope, rope, vd = (dims[k] for k in ("H", "r_q", "rank", "nope", "rope", "vd"))
    c_q = _rescale(_rms(h @ lp["w_qa"].astype(F32), lp["q_norm"], dims["eps"]), D, r_q)
    q = (c_q @ lp["w_qb"].astype(F32)).reshape(s, H, nope + rope)
    ckr = h @ lp["w_kva"].astype(F32)
    c = _rescale(_rms(ckr[:, :rank], lp["kv_norm"], dims["eps"]), D, rank)
    k_r = _rope_pairs(ckr[:, rank:], dims["theta"])  # (s, rope): one key for all heads
    kv = (c @ lp["w_kvb"].astype(F32)).reshape(s, H, nope + vd)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    q_nope, q_rope = q[..., :nope], _rope_pairs(q[..., nope:], dims["theta"])
    scale = F32((nope + rope) ** -0.5)
    pos = jnp.arange(s)

    def block(args):
        i, qn, qr = args  # (B,), (B, H, nope), (B, H, rope)
        scores = (
            jnp.einsum("ihd,jhd->hij", qn, k_nope) + jnp.einsum("ihd,jd->hij", qr, k_r)
        ) * scale
        mask = pos[None, :] <= i[:, None]
        probs = jax.nn.softmax(jnp.where(mask[None], scores, -jnp.inf), axis=-1)
        return jnp.einsum("hij,jhd->ihd", probs, v)

    B = min(QUERY_BLOCK, s)
    n = -(-s // B)
    o = jax.lax.map(block, tuple(_blocks(x, n, B) for x in (pos, q_nope, q_rope)))
    return o.reshape(n * B, H * vd)[:s] @ lp["w_o"].astype(F32)


def _weights(g, chosen, dims):
    """The chosen outputs' weights: their softmax scores as they are,
    times the scaling factor (not renormalised)."""
    return jnp.where(chosen, g, 0.0) * F32(dims["scale"])


def routing(u, lp, dims):
    """(s, real + zero) routing weights: zero where an output was not
    chosen.  The outputs are ranked on ``softmax score + bias`` by a
    stable descending sort, so a tie goes to the lower index."""
    g = jax.nn.softmax(u @ lp["router"].astype(F32), axis=-1)
    ranked = g + lp["router_bias"].astype(F32)
    rank = jnp.argsort(jnp.argsort(-ranked, axis=-1, stable=True), axis=-1)
    return _weights(g, rank < dims["k"], dims)


def _swiglu(h, w_gu, w_down):
    gu = h @ w_gu.astype(F32)
    half = gu.shape[-1] // 2
    return (jax.nn.silu(gu[:, :half]) * gu[:, half:]) @ w_down.astype(F32)


def _identity(u, w_zero):
    """The identity experts' term: each chosen one adds its weight times
    the token; ``w_zero`` (s,) the sum of a position's such weights."""
    return u * w_zero[:, None]


def experts(u, lp, dims):
    """u: (s, D), normed -> the expert branch ``m`` (s, D): every real
    expert held for every position, weighted (expert ``e`` of ``lp`` is
    the router's output ``offset + e``), plus the identity term."""
    def part(u):
        w = routing(u, lp, dims)

        def expert(e, out):
            pick = lambda name: jax.lax.dynamic_index_in_dim(lp[name], e, 0, keepdims=False)
            y = _swiglu(u, pick("w_gu_e"), pick("w_down_e"))
            return out + y * jax.lax.dynamic_index_in_dim(w, dims["offset"] + e, 1)

        m = jax.lax.fori_loop(0, dims["held"], expert, jnp.zeros_like(u))
        if dims["identity"]:
            m = m + _identity(u, w[:, dims["real"]:].sum(-1))
        return m

    return _by_rows(part, u)


def dense(h, lp):
    return _by_rows(lambda x: _swiglu(x, lp["w_gu"], lp["w_down"]), h)


def _join(h4, m):
    """Where the expert branch comes back: after the second sublayer's
    dense MLP."""
    return h4 + m


@functools.partial(jax.jit, static_argnames=("dims_t",))
def _attend(x, lp, dims_t):
    dims = dict(dims_t)
    return x + attention(_rms(x, lp["attn_norm"], dims["eps"]), lp, dims)


@functools.partial(jax.jit, static_argnames=("dims_t",))
def _experts(x, lp, dims_t):
    dims = dict(dims_t)
    return experts(_rms(x, lp["mlp_norm"], dims["eps"]), lp, dims)


@functools.partial(jax.jit, static_argnames=("eps",))
def _dense(x, lp, eps):
    return x + dense(_rms(x, lp["mlp_norm"], eps), lp)


def layer(x, first, second, dims_t):
    """One published layer: ``first`` and ``second`` are its two entries
    of ``params["layers"]``."""
    eps = dict(dims_t)["eps"]
    x = _attend(x, first, dims_t)
    # The matrices a compiled part reads, and no others: a part's float32
    # copies are of what it is handed.
    m = _experts(x, {k: first[k] for k in ("mlp_norm", "router", "router_bias", "w_gu_e", "w_down_e")}, dims_t)
    x = _dense(x, {k: first[k] for k in ("mlp_norm", "w_gu", "w_down")}, eps)
    x = _attend(x, second, dims_t)
    x = _dense(x, {k: second[k] for k in ("mlp_norm", "w_gu", "w_down")}, eps)
    return _join(x, m)


def _dims(cfg, held, offset, identity) -> dict:
    if cfg.score_function != "softmax" or cfg.n_group != 1 or cfg.norm_topk or not cfg.latent_rescale:
        raise ValueError(
            "this reference routes by softmax scores over one group, not "
            "renormalised, under rescaled latents"
        )
    return {
        "H": cfg.n_heads, "r_q": cfg.q_lora_rank, "rank": cfg.kv_lora_rank,
        "nope": cfg.qk_nope_head_dim, "rope": cfg.qk_rope_head_dim, "vd": cfg.v_head_dim,
        "theta": float(cfg.rope_theta), "eps": float(cfg.norm_eps),
        "k": cfg.n_experts_per_tok, "scale": float(cfg.routed_scaling),
        "real": cfg.n_experts, "identity": bool(identity),
        "held": cfg.experts_held if held is None else int(held),
        "offset": cfg.expert_offset if offset is None else int(offset),
    }


def hidden_states(params, cfg, tokens, held=None, offset=None, identity=True):
    """(s, D) float32 before the final norm, for one prompt; ``held`` real
    experts from ``offset`` (absent: the configuration's share), with the
    identity experts' term or without."""
    dims_t = tuple(sorted(_dims(cfg, held, offset, identity).items()))
    kinds, layers = cfg.layer_kinds, params["layers"]
    if len(kinds) % 2 or set(zip(kinds[0::2], kinds[1::2])) != {(("mla", "shortcut"), ("mla", "dense_add"))}:
        raise ValueError(f"not the layers of this family: {kinds}")
    with jax.default_matmul_precision("highest"):
        x = params["embed"][jnp.asarray(tokens, jnp.int32)].astype(F32)
        for first, second in zip(layers[0::2], layers[1::2]):
            x = layer(x, first, second, dims_t)
    return x


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, final_norm, lm_head, eps):
    return _rms(x, final_norm, eps) @ lm_head.astype(F32)


def head(params, cfg, x):
    """Final norm and the untied head: (..., D) -> (..., V) float32."""
    with jax.default_matmul_precision("highest"):
        return _head(x, params["final_norm"], params["lm_head"], float(cfg.norm_eps))


def all_logits(params, cfg, tokens, held=None, offset=None, identity=True):
    """(s, V) float32 logits at every position of one prompt."""
    return head(params, cfg, hidden_states(params, cfg, tokens, held, offset, identity))
