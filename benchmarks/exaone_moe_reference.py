"""The plain reference of the ``exaone_moe`` family (K-EXAONE-236B-A23B)
as ``models/hybrid.py`` serves it: float32 ``jax.numpy`` at the highest
matmul precision, written from the layer equations.

No kernel, no cache, no chunking, no batching, none of the program's
functions: one prompt, the whole sequence at once, one layer at a time.

* Layer ``l``: ``x = x + Attn(RMSNorm(x))``, ``x = x + MLP(RMSNorm(x))``
  (pre-norm).
* Attention: ``q``, ``k``, ``v`` without bias; an RMSNorm over the
  ``head_dim`` values of every query head and every key head, one gain
  vector each a layer (QK-norm), before any rotation.  A ``window`` layer
  rotates q and k half-split over the whole head with
  ``theta^(-2i/d)`` and lets position ``i`` see ``i - window < j <= i``;
  a ``full`` layer rotates nothing and sees every ``j <= i``.  The full
  softmax under an explicit (i, j) mask; query head ``h`` reads KV head
  ``h // (H / KH)``.
* MLP: the first layer a dense SwiGLU; the others ``s = sigmoid(h Wr)``
  over all ``E`` router outputs, the ``k`` largest of ``s + b`` (a tie
  to the lower index), ``w = scale * s_top / sum(s_top)``, the chosen
  experts' SwiGLUs weighted, plus the shared expert unscaled.  Of the
  ``E`` experts only ``held`` from ``offset`` on are computed (one
  chip's share: what the absent ones would add is left out); ``E`` and 0
  give the uncut layer.
* The prediction module at position ``t`` with the next token:
  ``u_t = W_eh [RMSNorm_e(Emb(x_{t+1})) ; RMSNorm_h(hbar_t)]``, ``hbar``
  the stack's output after its final norm; one block as a ``full`` layer
  with the expert MLP over ``u``; the module's own final RMSNorm; the
  stack's head.  Its logits at ``t`` predict ``x_{t+2}``.

What the public config does not settle (pre-norm, QK-norm, no rotation on
the full layers, the selection bias, the order of the module's halves
and the side of the final norm, its MLP kind, ties) is listed under
``assumed`` in ``benchmarks/configs/k-exaone-236b-a23b-l5e16.json``.

The parameters are the serving pytree (``hybrid.init_params``'s layout:
``w_qkv`` holds the query heads, then the key heads, then the value
heads; ``w_gu`` gate and up side by side; ``params["mtp"]`` the module).
``cfg`` is read for its sizes only.  ``benchmarks/exaone_moe_reference.py``
is the benchmark's copy of this file
(``benchmarks/tests/test_arch_exaone_moe.py`` holds the two equal).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _rms(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gain.astype(F32)


def _rope(x, theta: float):
    """x: (s, heads, d) at positions 0..s-1; halves (x1, x2) ->
    (x1 cos - x2 sin, x2 cos + x1 sin), frequencies theta^(-2i/d)."""
    s, _, d = x.shape
    inv = theta ** (-jnp.arange(0, d, 2, dtype=F32) / d)
    ang = jnp.arange(s, dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2 :]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def attention(h, lp, dims, mixer):
    """h: (s, D).  ``mixer``: ``full`` or ``window``."""
    s = h.shape[0]
    H, KH, hd = dims["H"], dims["KH"], dims["hd"]
    qkv = (h @ lp["w_qkv"].astype(F32)).reshape(s, H + 2 * KH, hd)
    q = _rms(qkv[:, :H], lp["q_norm"], dims["eps"])
    k = _rms(qkv[:, H : H + KH], lp["k_norm"], dims["eps"])
    v = qkv[:, H + KH :]
    if mixer == "window":
        q, k = _rope(q, dims["theta"]), _rope(k, dims["theta"])
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
    experts are ranked on ``s + b`` by a stable descending sort, so a tie
    goes to the lower index; the weights come from ``s`` alone."""
    s = jax.nn.sigmoid(h @ lp["router"].astype(F32))
    rank = jnp.argsort(jnp.argsort(-(s + lp["router_bias"].astype(F32)), axis=-1, stable=True), axis=-1)
    w = jnp.where(rank < dims["k"], s, 0.0)
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


def mlp(h, lp, dims, kind):
    if kind == "dense":
        return _swiglu(h, lp["w_gu"], lp["w_down"])
    return routed_experts(h, lp, dims) + _swiglu(h, lp["w_gu_s"], lp["w_down_s"])


def _dims(cfg, held, offset) -> dict:
    if cfg.n_group != 1 or cfg.score_function != "sigmoid":
        raise ValueError("this reference routes by sigmoid scores over one group")
    held = cfg.experts_held if held is None else int(held)
    return {
        "H": cfg.n_heads, "KH": cfg.n_kv_heads, "hd": cfg.attn_head_dim,
        "window": int(cfg.sliding_window), "eps": float(cfg.norm_eps),
        "theta": float(cfg.rope_window.theta),
        "k": cfg.n_experts_per_tok, "norm_topk": bool(cfg.norm_topk),
        "scale": float(cfg.routed_scaling), "held": held,
        "offset": cfg.expert_offset if offset is None else int(offset),
    }


@functools.partial(jax.jit, static_argnames=("mixer", "kind", "dims_t"))
def _layer(x, lp, mixer, kind, dims_t):
    dims = dict(dims_t)
    x = x + attention(_rms(x, lp["attn_norm"], dims["eps"]), lp, dims, mixer)
    return x + mlp(_rms(x, lp["mlp_norm"], dims["eps"]), lp, dims, kind)


def hidden_states(params, cfg, tokens, held=None, offset=None):
    """(s, D) float32 before the final norm, for one prompt; ``held``
    experts from ``offset`` (absent: the configuration's share)."""
    dims_t = tuple(sorted(_dims(cfg, held, offset).items()))
    with jax.default_matmul_precision("highest"):
        x = params["embed"][jnp.asarray(tokens, jnp.int32)].astype(F32)
        for (mixer, kind), lp in zip(cfg.layer_kinds, params["layers"]):
            if mixer not in ("full", "window"):
                raise ValueError(f"not a layer of this family: ({mixer}, {kind})")
            x = _layer(x, lp, mixer, kind, dims_t)
    return x


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, final_norm, lm_head, eps):
    return _rms(x, final_norm, eps) @ lm_head.astype(F32)


def head(params, cfg, x):
    """Final norm and the untied head: (..., D) -> (..., V) float32."""
    with jax.default_matmul_precision("highest"):
        return _head(x, params["final_norm"], params["lm_head"], float(cfg.norm_eps))


@functools.partial(jax.jit, static_argnames=("eps",))
def _mtp_input(x, e, final_norm, mp, eps):
    both = jnp.concatenate(
        [_rms(e, mp["enorm"], eps), _rms(_rms(x, final_norm, eps), mp["hnorm"], eps)], axis=-1
    )
    return both @ mp["eh_proj"].astype(F32)


def mtp_hidden_states(params, cfg, x, tokens, held=None, offset=None):
    """The prediction module over positions ``0..s-2`` of one prompt (the
    last has no next token): ``x`` (s, D) the stack's ``hidden_states``.
    Returns (s - 1, D) float32 before the module's final norm."""
    dims_t = tuple(sorted(_dims(cfg, held, offset).items()))
    mp = params["mtp"]
    with jax.default_matmul_precision("highest"):
        e = params["embed"][jnp.asarray(tokens, jnp.int32)[1:]].astype(F32)
        u = _mtp_input(x[:-1], e, params["final_norm"], mp, float(cfg.norm_eps))
        return _layer(u, mp["layer"], "full", "experts", dims_t)


def mtp_head(params, cfg, xm):
    """The module's final norm and the stack's head (shared)."""
    with jax.default_matmul_precision("highest"):
        return _head(xm, params["mtp"]["final_norm"], params["lm_head"], float(cfg.norm_eps))


def all_logits(params, cfg, tokens, held=None, offset=None):
    """((s, V) the stack's logits at every position of one prompt,
    (s - 1, V) the prediction module's: at ``t`` they predict token
    ``t + 2``), float32."""
    x = hidden_states(params, cfg, tokens, held, offset)
    xm = mtp_hidden_states(params, cfg, x, tokens, held, offset)
    return head(params, cfg, x), mtp_head(params, cfg, xm)
