"""The plain reference of the ``dots3_note`` family (dots3-note-prev's
language model) as ``models/hybrid.py`` serves it: float32 ``jax.numpy``
at the highest matmul precision, written from the layer equations.

No kernel, no cache, no ring, no chunked prefill, no batching, none of
the program's functions (nothing of ``ops/``): one prompt, the whole
sequence at once, one layer at a time, keys and values expanded for every
position, every position scored by the indexer against every earlier one,
the kept positions found by a full sort.

* Layer ``l``: ``x = x + Attn(RMSNorm(x))``, ``x = x + MLP(RMSNorm(x))``
  (pre-norm); ``h`` is the normed input.
* A latent layer of sizes ``(H, r_q, r, nope, rope, v, theta)``:
  ``c_q = a_q RMSNorm(h W_qa)``, ``a_q = (D / r_q)^1/2``; ``q = c_q W_qb``
  -> ``H`` heads of ``[q_nope ; q_rope]``; ``[c' ; k'] = h W_kva``,
  ``c = a_kv RMSNorm(c')``, ``a_kv = (D / r)^1/2``, ``k_r = rot(k')`` one
  key for all heads; ``[k_nope_h ; v_h] = c W_kvb`` a head.  ``q_rope`` and
  ``k_r`` are rotated over adjacent pairs (x0, x1), (x2, x3), ... with the
  plain frequencies ``theta^(-2i/rope)``.  ``score = (nope + rope)^-1/2
  (q_nope . k_nope + q_rope . k_r)`` under an explicit (i, j) mask, the
  full softmax over every key, ``o_h = sum softmax v_h``; ``g = sigmoid(h
  W_g)``, one value a head; output ``[g_h o_h] W_o``.
* A ``full`` layer (the ``mla`` kind) masks ``j in S_i``: the indexer's
  ``q_I = c_q W_qI`` (``H_I`` heads of ``d_I``), ``k_I = LayerNorm(h
  W_kI)`` (weight and bias), the first ``rope`` values of each rotated as
  above, ``w = h W_w H_I^-1/2 d_I^-1/2``, ``I[i, j] = sum_n w[i, n]
  relu(q_I[i, n] . k_I[j])`` for ``j <= i``; ``S_i`` the positions of the
  ``min(i + 1, topk)`` largest ``I[i, .]``, by a stable descending sort (a
  tie to the lower position).
* A ``sliding`` layer (the ``mla_window`` kind) has its own sizes and
  masks ``i - window < j <= i``; no indexer.
* MLP: layer 0 a SwiGLU; the others ``s = sigmoid(h W_r)``, the ``k``
  largest of ``s + b`` over all ``E`` outputs (one group, a tie to the
  lower index), weights ``s_e / sum s_e`` times the scaling factor, the
  chosen experts' SwiGLUs weighted, plus the shared expert unscaled.  Of
  the ``E`` experts only ``held`` from ``offset`` on are computed (one
  chip's share: what the absent ones would add is left out); ``E`` and 0
  give the uncut layer.
* Final RMSNorm and the untied head (:func:`head`, which the caller gives
  a block of positions at a time).

The queries are taken ``QUERY_BLOCK`` at a time against every key only so
that a 4.8k-token prompt's float32 scores fit beside a serving engine
(128 heads x 4,864 x 4,864 would be 12 GB): each query's softmax, and its
sort, is still over the whole row, nothing is carried from block to block.

Every step a control of the comparison leaves out is a function of its
own (``_rescale``, ``_index_act``, ``_select``, ``_gate``, ``_window_mask``,
``_window_theta``, ``_swiglu``).  What the public config does not settle
is listed under ``assumed`` in
``benchmarks/configs/dots3-note-prev-l6e32.json``.

The parameters are the serving pytree (``hybrid.init_params``'s layout).
``cfg`` is read for its sizes only.
``benchmarks/dots3_note_reference.py`` is the benchmark's copy of this
file (``benchmarks/tests/test_arch_dots3_note.py`` holds the two equal).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

F32 = jnp.float32
QUERY_BLOCK = 128
INDEX_NORM_EPS = 1e-6


def _rms(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gain.astype(F32)


def _rescale(c, d_model: int, rank: int):
    """``apply_mla_qkv_lora_rescale``: a normed latent times (D / rank)^1/2."""
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


def _index_act(dots):
    return jax.nn.relu(dots)


def _select(scores, seen, topk: int):
    """scores (B, s) of a block of queries against every position, seen
    (B, s) what each may see: the ``topk`` largest of what it sees, ranked
    by a stable descending sort."""
    ranked = jnp.where(seen, scores, -jnp.inf)
    first = jnp.argsort(-ranked, axis=-1, stable=True)[:, :topk]
    kept = jnp.zeros(seen.shape, bool).at[jnp.arange(seen.shape[0])[:, None], first].set(True)
    return seen & kept


def _gate(o, h, w_gate):
    """o: (s, H, v): each head's output times its sigmoid gate."""
    return o * jax.nn.sigmoid(h @ w_gate.astype(F32))[:, :, None]


def _window_mask(i, j, window: int):
    """A sliding layer: ``i - window < j <= i``."""
    return (j <= i) & (j > i - window)


def _window_theta(dims) -> float:
    return dims["w_theta"]


def _blocks(x, n: int, B: int):
    """Whole blocks of queries: the last is filled up with copies of the
    last query, which are dropped again."""
    s = x.shape[0]
    filled = jnp.concatenate([x, jnp.broadcast_to(x[-1:], (n * B - s,) + x.shape[1:])])
    return filled.reshape((n, B) + x.shape[1:])


def latent_attention(h, lp, dims, kind: str):
    """h: (s, D) -> ((s, D), the (s, s) mask of the pairs attended)."""
    s, D = h.shape
    full = kind == "mla"
    H, r_q, rank, nope, rope, vd = (
        dims[("" if full else "w_") + k] for k in ("H", "r_q", "rank", "nope", "rope", "vd")
    )
    theta = dims["theta"] if full else _window_theta(dims)
    c_q = _rescale(_rms(h @ lp["w_qa"].astype(F32), lp["q_norm"], dims["eps"]), D, r_q)
    q = (c_q @ lp["w_qb"].astype(F32)).reshape(s, H, nope + rope)
    ckr = h @ lp["w_kva"].astype(F32)
    c = _rescale(_rms(ckr[:, :rank], lp["kv_norm"], dims["eps"]), D, rank)
    k_r = _rope_pairs(ckr[:, rank:], theta)  # (s, rope): one key for all heads
    kv = (c @ lp["w_kvb"].astype(F32)).reshape(s, H, nope + vd)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    q_nope, q_rope = q[..., :nope], _rope_pairs(q[..., nope:], theta)
    scale = F32((nope + rope) ** -0.5)
    pos = jnp.arange(s)
    if full:
        HI, dI, ir = dims["HI"], dims["dI"], dims["rope"]
        turn = lambda x: jnp.concatenate([_rope_pairs(x[..., :ir], theta), x[..., ir:]], axis=-1)
        q_i = turn((c_q @ lp["w_qi"].astype(F32)).reshape(s, HI, dI))
        k_i = h @ lp["w_ki"].astype(F32)
        k_i = k_i - jnp.mean(k_i, axis=-1, keepdims=True)
        k_i = k_i * jax.lax.rsqrt(jnp.mean(k_i * k_i, axis=-1, keepdims=True) + INDEX_NORM_EPS)
        k_i = turn(k_i * lp["ki_norm"].astype(F32) + lp["ki_norm_b"].astype(F32))
        w_i = (h @ lp["w_wi"].astype(F32)) * F32(HI**-0.5 * dI**-0.5)
    else:
        q_i = w_i = jnp.zeros((s, 0), F32)
        k_i = None

    def block(args):
        i, qn, qr, qi, wi = args  # (B,), (B, H, nope), (B, H, rope), (B, HI, dI), (B, HI)
        if full:
            index = jnp.einsum("inj,in->ij", _index_act(jnp.einsum("ind,jd->inj", qi, k_i)), wi)
            mask = _select(index, pos[None, :] <= i[:, None], dims["topk"])
        else:
            mask = _window_mask(i[:, None], pos[None, :], dims["window"])
        scores = (
            jnp.einsum("ihd,jhd->hij", qn, k_nope) + jnp.einsum("ihd,jd->hij", qr, k_r)
        ) * scale
        probs = jax.nn.softmax(jnp.where(mask[None], scores, -jnp.inf), axis=-1)
        return jnp.einsum("hij,jhd->ihd", probs, v), mask

    B = min(QUERY_BLOCK, s)
    n = -(-s // B)
    o, mask = jax.lax.map(
        block, tuple(_blocks(x, n, B) for x in (pos, q_nope, q_rope, q_i, w_i))
    )
    o = _gate(o.reshape(n * B, H, vd)[:s], h, lp["w_gate"])
    return o.reshape(s, H * vd) @ lp["w_o"].astype(F32), mask.reshape(n * B, s)[:s]


def routing(h, lp, dims):
    """(s, E) routing weights: zero where an expert was not chosen.  The
    experts are ranked on ``sigmoid score + bias`` by a stable descending
    sort, so a tie goes to the lower index; the weights are the scores
    themselves, renormalised and scaled."""
    g = jax.nn.sigmoid(h @ lp["router"].astype(F32))
    ranked = g + lp["router_bias"].astype(F32)
    rank = jnp.argsort(jnp.argsort(-ranked, axis=-1, stable=True), axis=-1)
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


def mlp(h, lp, dims, kind: str):
    if kind == "dense":
        return _swiglu(h, lp["w_gu"], lp["w_down"])
    return routed_experts(h, lp, dims) + _swiglu(h, lp["w_gu_s"], lp["w_down_s"])


def _dims(cfg, held, offset) -> dict:
    if cfg.score_function != "sigmoid" or cfg.n_group != 1 or not cfg.index_topk:
        raise ValueError("this reference routes by sigmoid scores over one group, under an indexer")
    win = cfg.window_latent
    return {
        "H": cfg.n_heads, "r_q": cfg.q_lora_rank, "rank": cfg.kv_lora_rank,
        "nope": cfg.qk_nope_head_dim, "rope": cfg.qk_rope_head_dim, "vd": cfg.v_head_dim,
        "theta": float(cfg.rope_theta),
        "w_H": win.n_heads, "w_r_q": win.q_lora_rank, "w_rank": win.kv_lora_rank,
        "w_nope": win.qk_nope_head_dim, "w_rope": win.qk_rope_head_dim,
        "w_vd": win.v_head_dim, "w_theta": float(win.rope_theta),
        "window": cfg.sliding_window, "HI": cfg.index_n_heads, "dI": cfg.index_head_dim,
        "topk": cfg.index_topk, "eps": float(cfg.norm_eps),
        "k": cfg.n_experts_per_tok, "norm_topk": bool(cfg.norm_topk),
        "scale": float(cfg.routed_scaling),
        "held": cfg.experts_held if held is None else int(held),
        "offset": cfg.expert_offset if offset is None else int(offset),
    }


@functools.partial(jax.jit, static_argnames=("dims_t", "kind"))
def _layer(x, lp, dims_t, kind):
    dims = dict(dims_t)
    y, mask = latent_attention(_rms(x, lp["attn_norm"], dims["eps"]), lp, dims, kind[0])
    x = x + y
    return x + mlp(_rms(x, lp["mlp_norm"], dims["eps"]), lp, dims, kind[1]), mask


def layers(params, cfg, tokens, held=None, offset=None):
    """One prompt through the stack: yields, a layer at a time, (the
    layer's kind, its output (s, D) float32, the (s, s) mask of the pairs
    it attended)."""
    dims_t = tuple(sorted(_dims(cfg, held, offset).items()))
    with jax.default_matmul_precision("highest"):
        x = params["embed"][jnp.asarray(tokens, jnp.int32)].astype(F32)
        for kind, lp in zip(cfg.layer_kinds, params["layers"]):
            if kind[0] not in ("mla", "mla_window") or kind[1] not in ("dense", "experts"):
                raise ValueError(f"not a layer of this family: {kind}")
            x, mask = _layer(x, lp, dims_t, kind)
            yield kind, x, mask


def hidden_states(params, cfg, tokens, held=None, offset=None):
    """(s, D) float32 before the final norm, for one prompt; ``held``
    experts from ``offset`` (absent: the configuration's share)."""
    for _, x, _ in layers(params, cfg, tokens, held, offset):
        pass
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
