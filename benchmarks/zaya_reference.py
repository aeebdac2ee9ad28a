"""The plain reference of the ``zaya`` family (ZAYA1-8B) as
``models/hybrid.py`` serves it: float32 ``jax.numpy`` at the highest
matmul precision, written from the layer equations (CCA:
arXiv:2510.04476; the ZAYA1 report: arXiv:2511.17127).

No kernel, no cache, no chunks, no batching, none of the program's
functions (nothing of ``ops/``): one prompt, the whole sequence at once,
one layer at a time, the convolutions written as sums over shifted
copies of the sequence.

With ``x`` the residual stream, ``t`` a position, ``H`` query heads on
``G`` key-value heads of ``d`` and ``g(i) = i // (H / G)``:

* Layer ``l``: ``x = x + Attn(RMSNorm(x))``, ``x = x + MLP(RMSNorm(x))``
  (pre-norm), every layer alike.
* Attention.  ``qp_t = h_t W_q``, ``kp_t = h_t W_k``, ``u_t = [qp_t ; kp_t]``
  (``H + G`` heads).  Step one, a filter a channel over ``cca_time0``
  positions: ``a_t[c] = sum_j w0[j, c] u_{t - (W0 - 1 - j)}[c] + b0[c]``.
  Step two, a head's channels mixed over ``cca_time1`` positions:
  ``z_t[h] = sum_j a_{t - (W1 - 1 - j)}[h] W1[j, h] + b1[h]``.  Before the
  first token ``u`` and ``a`` are zero (step two sees zeros there, not
  ``b0``).  The q-k mean ``m_t[i] = (qp_t[i] + kp_t[g(i)]) / 2``;
  ``q_t[i] = z_t[i] + m_t[i]``; ``k_t[g] = z_t[H + g] +`` the mean of
  ``m_t[i]`` over the query heads of group ``g``.  Length norm a head:
  ``q <- sqrt(d) q / |q|``, ``k <- tau_g sqrt(d) k / |k|``.  Rotation of
  the first ``rotary_dim`` values of each head, half-split pairs
  ``(x_j, x_{j + rotary_dim / 2})``, frequencies ``theta^(-2j /
  rotary_dim)``.  Values ``v_t = [h_t W_v1 ; h_{t-1} W_v2]``: the first
  half of the key-value heads is the current token's, the second the
  previous token's (zero before the first).  ``o_i = sum_s softmax_s(q_t[i]
  . k_s[g(i)] / sqrt(d)) v_s[g(i)]`` under an explicit mask ``s <= t``;
  output ``[o_i] W_o``.
* MLP.  ``rho_l = h W_down + b_down``; ``rho_l <- rho_l + gamma_l
  rho_{l-1}`` (the layer before's, after its own average; the first layer
  has none); ``p = softmax(W_3 gelu(W_2 gelu(W_1 RMSNorm(rho_l) + b_1) +
  b_2) + b_3)`` with the exact (erf) GELU; ``e = argmax(p + beta)`` (a tie
  to the lower index); ``y = p[e] SwiGLU_e(h)``.  Of the ``E`` experts
  only ``held`` from ``offset`` on are computed (a share: what the absent
  ones would add is left out); ``E`` and 0 give the uncut layer.
* Final RMSNorm; logits ``h E^T`` with ``E`` the embedding (:func:`head`,
  a block of the vocabulary at a time so that 262k rows of float32 are
  never whole in memory), or the untied matrix where the model has one.

Departures from the papers, each also under ``assumed`` (or as not
served) in ``benchmarks/configs/zaya1-8b-l20.json``: biases on both
convolutions and the zero history; the norm's target ``sqrt(d)`` with the
softmax scale ``1 / sqrt(d)``; which value heads are shifted; the router's
depth, its GELU and the RMSNorm before it; ``gamma`` a scalar a layer.
Residual scaling and "MoD" have no key and no equation here: not served.

The parameters are the serving pytree (``hybrid.init_params``'s layout:
``w_qkv`` holds ``W_q``, ``W_k``, ``W_v1``, ``W_v2`` side by side,
``conv1_w`` is (W1, heads, d, d), ``w_gu_e`` an expert's gate and up side
by side).  ``cfg`` is read for its sizes only.
``benchmarks/zaya_reference.py`` is the benchmark's copy of this file
(``benchmarks/tests/test_arch_zaya.py`` holds the two equal).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
# Rows of the embedding the tied head multiplies at a time.
VOCAB_BLOCK = 32768


def _rms(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gain.astype(F32)


def _project(h, w):
    """A projection of the mixer (a function of its own, so that a control
    can compute it in a lower precision)."""
    return h @ w.astype(F32)


def _back(x, n: int):
    """x (s, ...) as of ``n`` positions before: zeros, then ``x[:-n]``."""
    if n == 0:
        return x
    return jnp.concatenate([jnp.zeros_like(x[:n]), x[:-n]])


def _conv(u, lp, dims):
    """The two causal convolutions over ``u`` (s, J, d) -> z (s, J, d)."""
    s, J, d = u.shape
    w0, w1 = lp["conv0_w"].astype(F32), lp["conv1_w"].astype(F32)
    a = sum(w0[j].reshape(J, d) * _back(u, len(w0) - 1 - j) for j in range(len(w0)))
    a = a + lp["conv0_b"].astype(F32).reshape(J, d)
    z = sum(
        jnp.einsum("sjd,jde->sje", _back(a, len(w1) - 1 - j), w1[j]) for j in range(len(w1))
    )
    return z + lp["conv1_b"].astype(F32).reshape(J, d)


def _qk_mean(qp, kp):
    """qp (s, H, d), kp (s, G, d) -> what is added to q (s, H, d) and to
    k (s, G, d)."""
    s, H, d = qp.shape
    G = kp.shape[1]
    m = (qp.reshape(s, G, H // G, d) + kp[:, :, None]) / 2.0
    return m.reshape(s, H, d), m.mean(axis=2)


def _length_norm(x):
    """Each head to length ``sqrt(d)``."""
    d = x.shape[-1]
    return x * math.sqrt(d) / jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def _rope(x, theta: float, rot: int):
    """x (s, heads, d) at positions 0..s-1: the first ``rot`` values of a
    head in half-split pairs, the rest as they are."""
    s = x.shape[0]
    inv = theta ** (-np.arange(0, rot, 2, dtype=np.float64) / rot)
    ang = jnp.arange(s, dtype=F32)[:, None, None] * jnp.asarray(inv, F32)[None, None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., : rot // 2], x[..., rot // 2 : rot]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, x[..., rot:]], axis=-1)


def _shift_values(v_now, v_late):
    """The value heads of a position: the current token's half, then the
    previous token's."""
    return jnp.concatenate([v_now, _back(v_late, 1)], axis=-1)


def attention(h, lp, dims):
    """h: (s, D)."""
    s = h.shape[0]
    H, G, d = dims["H"], dims["G"], dims["d"]
    qkv = _project(h, lp["w_qkv"])
    u = qkv[:, : (H + G) * d].reshape(s, H + G, d)
    values = qkv[:, (H + G) * d :]
    z = _conv(u, lp, dims)
    qp, kp = u[:, :H], u[:, H:]
    to_q, to_k = _qk_mean(qp, kp)
    q = _length_norm(z[:, :H] + to_q)
    k = _length_norm(z[:, H:] + to_k) * lp["k_temp"].astype(F32)[None, :, None]
    q, k = _rope(q, dims["theta"], dims["rot"]), _rope(k, dims["theta"], dims["rot"])
    half = G // 2 * d
    v = _shift_values(values[:, :half], values[:, half:]).reshape(s, G, d)
    qg = q.reshape(s, G, H // G, d)
    scores = jnp.einsum("igqd,jgd->gqij", qg, k) / math.sqrt(d)
    mask = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    probs = jax.nn.softmax(jnp.where(mask[None, None], scores, -jnp.inf), axis=-1)
    o = jnp.einsum("gqij,jgd->igqd", probs, v).reshape(s, H * d)
    return _project(o, lp["w_o"])


def _router_average(rho, prev, gamma):
    return rho + gamma * prev


def router(h, lp, prev, dims):
    """((s, E) probabilities, (s, R) this layer's router state)."""
    w = lambda name: lp[name].astype(F32)
    rho = h @ w("router_down") + w("router_down_b")
    if prev is not None:
        rho = _router_average(rho, prev, w("router_gamma"))
    y = _rms(rho, lp["router_norm"], dims["eps"])
    y = jax.nn.gelu(y @ w("router_w1") + w("router_b1"), approximate=False)
    y = jax.nn.gelu(y @ w("router_w2") + w("router_b2"), approximate=False)
    return jax.nn.softmax(y @ w("router_w3") + w("router_b3"), axis=-1), rho


def _top1_weight(p, chosen):
    """The chosen expert's weight: its probability as it is."""
    return jnp.where(chosen, p, 0.0)


def _swiglu(h, w_gu, w_down):
    gu = h @ w_gu.astype(F32)
    half = gu.shape[-1] // 2
    return (jax.nn.silu(gu[:, :half]) * gu[:, half:]) @ w_down.astype(F32)


def mlp(h, lp, prev, dims):
    """h: (s, D): every expert held for every position, weighted (zero
    where it was not chosen).  Expert ``e`` of ``lp`` is the router's
    output ``offset + e``.  Returns (y, the router state)."""
    p, rho = router(h, lp, prev, dims)
    sel = p + lp["router_bias"].astype(F32)
    # The first of the largest: a tie goes to the lower index.
    chosen = jnp.arange(p.shape[1])[None, :] == jnp.argmax(sel, axis=-1)[:, None]
    w = _top1_weight(p, chosen)

    def expert(e, out):
        pick = lambda name: jax.lax.dynamic_index_in_dim(lp[name], e, 0, keepdims=False)
        y = _swiglu(h, pick("w_gu_e"), pick("w_down_e"))
        return out + y * jax.lax.dynamic_index_in_dim(w, dims["offset"] + e, 1)

    return jax.lax.fori_loop(0, dims["held"], expert, jnp.zeros_like(h)), rho


def _dims(cfg, held, offset) -> dict:
    if not cfg.router_hidden or cfg.n_experts_per_tok != 1:
        raise ValueError("this reference routes one expert a token through the ZAYA router")
    return {
        "H": cfg.n_heads, "G": cfg.n_kv_heads, "d": cfg.attn_head_dim,
        "theta": float(cfg.rope_full.theta), "rot": int(cfg.rotary_dim),
        "eps": float(cfg.norm_eps),
        "held": cfg.experts_held if held is None else int(held),
        "offset": cfg.expert_offset if offset is None else int(offset),
    }


@functools.partial(jax.jit, static_argnames=("dims_t",))
def _layer(x, prev, lp, dims_t):
    dims = dict(dims_t)
    x = x + attention(_rms(x, lp["attn_norm"], dims["eps"]), lp, dims)
    y, rho = mlp(_rms(x, lp["mlp_norm"], dims["eps"]), lp, prev, dims)
    return x + y, rho


def hidden_states(params, cfg, tokens, held=None, offset=None):
    """(s, D) float32 before the final norm, for one prompt; ``held``
    experts from ``offset`` (absent: the configuration's share)."""
    dims_t = tuple(sorted(_dims(cfg, held, offset).items()))
    with jax.default_matmul_precision("highest"):
        x = params["embed"][jnp.asarray(tokens, jnp.int32)].astype(F32)
        rho = None
        for kind, lp in zip(cfg.layer_kinds, params["layers"]):
            if kind != ("cca", "experts"):
                raise ValueError(f"not a layer of this family: {kind}")
            x, rho = _layer(x, rho, lp, dims_t)
    return x


@functools.partial(jax.jit, static_argnames=("eps", "tied"))
def _head(x, final_norm, matrix, eps, tied):
    h = _rms(x, final_norm, eps)
    if not tied:
        return h @ matrix.astype(F32)
    blocks = [
        h @ matrix[at : at + VOCAB_BLOCK].astype(F32).T
        for at in range(0, matrix.shape[0], VOCAB_BLOCK)
    ]
    return jnp.concatenate(blocks, axis=-1)


def head(params, cfg, x):
    """Final norm and the head: (..., D) -> (..., V) float32."""
    tied = bool(cfg.tie_embeddings)
    with jax.default_matmul_precision("highest"):
        return _head(
            x, params["final_norm"], params["embed" if tied else "lm_head"],
            float(cfg.norm_eps), tied,
        )


def all_logits(params, cfg, tokens, held=None, offset=None):
    """(s, V) float32 logits at every position of one prompt."""
    return head(params, cfg, hidden_states(params, cfg, tokens, held, offset))
