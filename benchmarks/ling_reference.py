"""The benchmark's copy of ``generativeaiexamples_tpu/models/hybrid_reference.py``
(``tests/test_arch_ling.py`` holds the two to the same logits): the plain reference of ``models/hybrid.py``, the ``bailing_hybrid``
language model in float32 ``jax.numpy`` at the highest matmul precision.

No kernel, no cache, no chunking, no batching, none of the program's
functions: one prompt, one layer at a time.  KDA runs a token at a time
(``lax.scan`` over positions), MLA is the full softmax in the expanded
form, every expert held is computed whole for every position and
weighted by the position's routing weight for it (zero where it was not
chosen).  Given a share (offset, count) of the experts it leaves out the
absent ones exactly as the program does: their part of the sum is
dropped and the partial result goes on.

Departures from the published description (each is also under
``assumed`` in the configuration's file): the layer kinds follow
``(i + 1) % layer_group_size == 0 -> MLA``; the gate's bias sits inside
the sigmoid's argument; ``linear_silu`` is the SiLU after the
convolution; ``use_qk_norm`` is the L2 norm of q and k in KDA layers;
the KDA output norm is over one head (``group_norm_size`` 1) and its
gate element-wise, the MLA gate one a head; rotary pairs are
interleaved; the head is untied.  No vision tower, no MTP head, no
SwiGLU clamp (zero in every layer before 34).

The parameters are the serving pytree (``hybrid.init_params``'s layout).
This copy decides the cells' ``correct``: a later PR does not edit it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _rms(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gain.astype(F32)


def _l2(x):
    return x / jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def _rope_pairs(x, theta):
    """x: (s, ..., d); positions 0..s-1; pairs (x0, x1), (x2, x3), ..."""
    s, d = x.shape[0], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    ang = jnp.arange(s, dtype=F32)[:, None] * inv[None, :]
    ang = ang.reshape((s,) + (1,) * (x.ndim - 2) + (d // 2,))
    x0, x1 = x[..., 0::2], x[..., 1::2]
    return jnp.stack(
        [x0 * jnp.cos(ang) - x1 * jnp.sin(ang), x1 * jnp.cos(ang) + x0 * jnp.sin(ang)],
        axis=-1,
    ).reshape(x.shape)


def kda_mixer(h, lp, dims):
    """h: (s, D).  A token at a time."""
    s = h.shape[0]
    H, K, W = dims["H"], dims["K"], dims["W"]
    qkv = h @ lp["w_qkv"].astype(F32)  # (s, 3 H K)
    # Depthwise causal convolution: out_t = sum_j w[j] x_(t - (W-1) + j).
    padded = jnp.concatenate([jnp.zeros((W - 1, qkv.shape[1]), F32), qkv])
    conv_w = lp["conv_w"].astype(F32)
    conv = sum(padded[j : j + s] * conv_w[j] for j in range(W))
    q, k, v = jnp.split(jax.nn.silu(conv).reshape(s, 3 * H, K), 3, axis=1)
    q = _l2(q) * K**-0.5
    k = _l2(k)
    f = (h @ lp["w_f"].astype(F32)).reshape(s, H, K)
    g = dims["floor"] * jax.nn.sigmoid(
        jnp.exp(lp["a_log"].astype(F32))[:, None] * (f + lp["dt_bias"].astype(F32))
    )
    beta = jax.nn.sigmoid(h @ lp["w_b"].astype(F32))  # (s, H)

    def token(S, x):
        q_t, k_t, v_t, g_t, b_t = x
        S = S * jnp.exp(g_t)[:, :, None]
        S = S + b_t[:, None, None] * k_t[:, :, None] * (
            v_t - jnp.einsum("hkv,hk->hv", S, k_t)
        )[:, None, :]
        return S, jnp.einsum("hkv,hk->hv", S, q_t)

    _, o = jax.lax.scan(token, jnp.zeros((H, K, K), F32), (q, k, v, g, beta))
    o = _rms(o, lp["o_norm"], dims["eps"])
    o = o * jax.nn.sigmoid(h @ lp["w_g"].astype(F32)).reshape(s, H, K)
    return o.reshape(s, H * K) @ lp["w_o"].astype(F32)


def mla_mixer(h, lp, dims):
    s = h.shape[0]
    H, rank, nope, rope, vd = (dims[n] for n in ("H", "rank", "nope", "rope", "vd"))
    q = (h @ lp["w_q"].astype(F32)).reshape(s, H, nope + rope)
    q_nope, q_rope = q[..., :nope], _rope_pairs(q[..., nope:], dims["theta"])
    ckr = h @ lp["w_kva"].astype(F32)
    c = _rms(ckr[:, :rank], lp["kv_norm"], dims["eps"])
    k_rope = _rope_pairs(ckr[:, rank:], dims["theta"])  # shared by the heads
    kv = (c @ lp["w_kvb"].astype(F32)).reshape(s, H, nope + vd)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    scores = (
        jnp.einsum("qhd,khd->hqk", q_nope, k_nope)
        + jnp.einsum("qhd,kd->hqk", q_rope, k_rope)
    ) / jnp.sqrt(F32(nope + rope))
    mask = jnp.tril(jnp.ones((s, s), dtype=bool))
    probs = jax.nn.softmax(jnp.where(mask[None], scores, -jnp.inf), axis=-1)
    o = jnp.einsum("hqk,khd->qhd", probs, v)
    o = o * jax.nn.sigmoid(h @ lp["w_gate"].astype(F32))[:, :, None]
    return o.reshape(s, H * vd) @ lp["w_o"].astype(F32)


def _swiglu(h, w_gu, w_down):
    gu = h @ w_gu.astype(F32)
    half = gu.shape[-1] // 2
    return (jax.nn.silu(gu[:, :half]) * gu[:, half:]) @ w_down.astype(F32)


def routing(h, lp, dims):
    """(s, E) routing weights: zero where an expert was not chosen.
    Brute force: the groups and then the experts are ranked by a stable
    descending sort, so ties go to the lower index."""
    E, G = dims["E"], dims["G"]
    s = jax.nn.sigmoid(h @ lp["router"].astype(F32))  # (n, E)
    sel = s + lp["router_bias"].astype(F32)
    per_group = sel.reshape(-1, G, E // G)
    top2 = -jnp.sort(-per_group, axis=-1)[..., :2]
    group_rank = jnp.argsort(jnp.argsort(-top2.sum(-1), axis=-1, stable=True), axis=-1)
    allowed = jnp.repeat(group_rank < dims["topk_group"], E // G, axis=-1)
    expert_rank = jnp.argsort(
        jnp.argsort(-jnp.where(allowed, sel, -jnp.inf), axis=-1, stable=True), axis=-1
    )
    chosen = expert_rank < dims["k"]
    w = jnp.where(chosen, s, 0.0)
    if dims["norm_topk"]:
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    return w * dims["scale"]


def expert_layer(h, lp, dims, share=None, with_shared=True):
    """h: (s, D).  ``share`` = (offset, count): the experts whose weights
    ``lp`` holds, rows 0..count-1 of ``w_gu_e``; None: all of them."""
    offset, count = share if share is not None else (0, dims["E"])
    w = routing(h, lp, dims)  # over all E

    def expert(e, out):
        pick = lambda name: jax.lax.dynamic_index_in_dim(lp[name], e, 0, keepdims=False)
        y = _swiglu(h, pick("w_gu_e"), pick("w_down_e"))
        return out + y * jax.lax.dynamic_index_in_dim(w, offset + e, 1)

    out = jax.lax.fori_loop(0, count, expert, jnp.zeros_like(h))
    if with_shared:
        out = out + _swiglu(h, lp["w_gu_s"], lp["w_down_s"])
    return out


def _dims(cfg) -> dict:
    return {
        "H": cfg.n_heads, "K": cfg.kda_head_dim, "W": cfg.conv_kernel,
        "floor": float(cfg.kda_gate_floor), "eps": float(cfg.norm_eps),
        "rank": cfg.kv_lora_rank, "nope": cfg.qk_nope_head_dim,
        "rope": cfg.qk_rope_head_dim, "vd": cfg.v_head_dim,
        "theta": float(cfg.rope_theta), "E": cfg.n_experts, "G": cfg.n_group,
        "topk_group": cfg.topk_group, "k": cfg.n_experts_per_tok,
        "norm_topk": bool(cfg.norm_topk), "scale": float(cfg.routed_scaling),
    }


@functools.partial(jax.jit, static_argnames=("kind", "dims_t", "share"))
def _layer(x, lp, kind, dims_t, share):
    dims = dict(dims_t)
    mixer, mlp = kind
    h = _rms(x, lp["attn_norm"], dims["eps"])
    x = x + (kda_mixer if mixer == "kda" else mla_mixer)(h, lp, dims)
    h = _rms(x, lp["mlp_norm"], dims["eps"])
    if mlp == "dense":
        return x + _swiglu(h, lp["w_gu"], lp["w_down"])
    return x + expert_layer(h, lp, dims, share)


def hidden_states(params, cfg, tokens):
    """(s, D) float32 before the final norm, for one prompt."""
    dims_t = tuple(sorted(_dims(cfg).items()))
    share = (int(cfg.expert_offset), int(cfg.experts_held))
    with jax.default_matmul_precision("highest"):
        x = params["embed"][jnp.asarray(tokens, jnp.int32)].astype(F32)
        for kind, lp in zip(cfg.layer_kinds, params["layers"]):
            x = _layer(x, lp, tuple(kind), dims_t, share)
    return x


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, final_norm, lm_head, eps):
    return _rms(x, final_norm, eps) @ lm_head.astype(F32)


def all_logits(params, cfg, tokens):
    """(s, V) float32 logits at every position of one prompt."""
    x = hidden_states(params, cfg, tokens)
    with jax.default_matmul_precision("highest"):
        return _head(x, params["final_norm"], params["lm_head"], float(cfg.norm_eps))
