"""The plain reference of the ``deepseek_v32`` family (DeepSeek-V3.2) as
``models/hybrid.py`` serves it: float32 ``jax.numpy`` at the highest
matmul precision, written from the layer equations.

No kernel, no cache, no chunked prefill, no verify step, no batching, none
of the program's functions (nothing of ``ops/``): one prompt, the whole
sequence at once, one layer at a time, keys and values expanded for every
position, every position scored by the indexer against every earlier one,
each query's kept positions found by a full sort.

* Layer ``l``: ``x = x + Attn(RMSNorm(x))``, ``x = x + MLP(RMSNorm(x))``
  (pre-norm, eps 1e-6); ``h`` is the normed input.  No bias anywhere but
  the index key's LayerNorm.
* Attention: ``c_q = RMSNorm(h W_qa)``; ``q = c_q W_qb`` -> ``H`` heads of
  ``[q_nope ; q_rope]``; ``[c' ; k'] = h W_kva``, ``c = RMSNorm(c')``,
  ``k_r = rot(k')`` one key for all heads; ``[k_nope_h ; v_h] = c W_kvb`` a
  head.  ``rot`` turns adjacent pairs (x0, x1), (x2, x3), ... by YaRN's
  frequencies (:func:`yarn_frequencies`: factor 40 over an original
  context of 4,096, beta 32 / 1), cos and sin unscaled (``mscale`` =
  ``mscale_all_dim``).  ``score = (nope + rope)^-1/2 m^2 (q_nope . k_nope
  + q_rope . k_r)``, ``m = 0.1 ln(factor) + 1`` (:func:`_softmax_scale`),
  under an explicit (i, j) mask ``j in S_i``; the full softmax over every
  key; ``o_h = sum softmax v_h``; output ``[o_h] W_o`` (no gate).
* The indexer, in EVERY layer: ``q_I = c_q W_qI`` (``H_I`` heads of
  ``d_I``), ``k_I = LayerNorm(h W_kI)`` (weight and bias, eps 1e-6), the
  first ``rope`` values of each rotated as above, ``w = h W_w H_I^-1/2
  d_I^-1/2``, ``I[i, j] = sum_n w[i, n] relu(q_I[i, n] . k_I[j])`` for
  ``j <= i``; ``S_i`` the positions of the ``min(i + 1, topk)`` largest
  ``I[i, .]``, by a stable descending sort (a tie to the lower position).
  Every query attends its OWN set (:func:`_own_set`).
* MLP: the first ``first_k_dense_replace`` layers a SwiGLU; the others
  ``s = sigmoid(h W_r)`` over all ``E`` outputs, ranked on ``s + b``: the
  ``E / n_group`` consecutive outputs of a group score the sum of their
  two largest, the ``topk_group`` best groups are kept
  (:func:`_group_limit`), the ``k`` largest inside them chosen (ties to
  the lower index), weights ``s_e / sum s_chosen`` times the scaling
  factor; the chosen experts' SwiGLUs weighted, plus the shared expert
  unscaled.  Of the ``E`` experts only ``held`` from ``offset`` on are
  computed (one chip's share, which may be a part of a group: what the
  absent ones would add is left out); ``E`` and 0 give the uncut layer.
* Final RMSNorm and the untied head (:func:`head`, a block of positions at
  a time).
* The prediction module at position ``t`` with the next token: ``u_t =
  W_eh [RMSNorm_e(Emb(x_{t+1})) ; RMSNorm_h(hbar_t)]``, ``hbar`` the
  stack's output after its final norm; one block of the same kind as a
  layer of experts (latent attention under its own indexer over ``u``);
  the module's own final RMSNorm; the stack's head.  Its logits at ``t``
  predict ``x_{t+2}``.

The queries are taken ``QUERY_BLOCK`` at a time against every key only so
that a 4.8k-token prompt's float32 scores fit beside a serving engine
(128 heads x 4,864 x 4,864 would be 12 GB; a block of 64 is 0.16 GB), and
a SwiGLU wider than ``MLP_BLOCK`` is summed over blocks of its inner width
(the dense layer's 18,432: its float32 matrices would be 1.6 GB at once):
each query's softmax, and its sort, is still over the whole row.

Every step a control of the comparison leaves out or changes is a function
of its own (``_index_act``, ``_select``, ``_own_set``, ``_group_limit``,
``_softmax_scale``, ``_swiglu``).  What the public config does not settle
is listed under ``assumed`` in
``benchmarks/configs/deepseek-v3.2-l5e16.json``.

The parameters are the serving pytree (``hybrid.init_params``'s layout;
``params["mtp"]`` the module).  ``cfg`` is read for its sizes only.
``benchmarks/deepseek_v32_reference.py`` is the benchmark's copy of this
file (``benchmarks/tests/test_arch_deepseek_v32.py`` holds the two equal).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
QUERY_BLOCK = 64
MLP_BLOCK = 4096
INDEX_NORM_EPS = 1e-6


def _rms(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gain.astype(F32)


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
    (x0 cos - x1 sin, x1 cos + x0 sin) at the frequencies ``inv`` (d / 2,)."""
    s = x.shape[0]
    ang = jnp.arange(s, dtype=F32)[:, None] * jnp.asarray(inv, F32)[None, :]
    ang = ang.reshape((s,) + (1,) * (x.ndim - 2) + (ang.shape[-1],))
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x0, x1 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x0 * cos - x1 * sin, x1 * cos + x0 * sin], axis=-1).reshape(x.shape)


def _softmax_scale(nope: int, rope: int, mscale: float):
    """``(nope + rope)^-1/2 m^2``: YaRN's magnitude term, squared."""
    return F32((nope + rope) ** -0.5 * mscale**2)


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


def _own_set(kept):
    """kept (B, s): every query attends the set it selected itself."""
    return kept


def latent_attention(h, lp, dims):
    """h: (s, D) -> ((s, D), the (s, s) mask of the pairs attended)."""
    s = h.shape[0]
    H, rank, nope, rope, vd = (dims[k] for k in ("H", "rank", "nope", "rope", "vd"))
    inv = yarn_frequencies(rope, *dims["yarn"])
    c_q = _rms(h @ lp["w_qa"].astype(F32), lp["q_norm"], dims["eps"])
    q = (c_q @ lp["w_qb"].astype(F32)).reshape(s, H, nope + rope)
    ckr = h @ lp["w_kva"].astype(F32)
    c = _rms(ckr[:, :rank], lp["kv_norm"], dims["eps"])
    k_r = _rope_pairs(ckr[:, rank:], inv)  # (s, rope): one key for all heads
    kv = (c @ lp["w_kvb"].astype(F32)).reshape(s, H, nope + vd)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    q_nope, q_rope = q[..., :nope], _rope_pairs(q[..., nope:], inv)
    scale = _softmax_scale(nope, rope, dims["mscale"])
    pos = jnp.arange(s)
    HI, dI = dims["HI"], dims["dI"]
    turn = lambda x: jnp.concatenate([_rope_pairs(x[..., :rope], inv), x[..., rope:]], axis=-1)
    q_i = turn((c_q @ lp["w_qi"].astype(F32)).reshape(s, HI, dI))
    k_i = h @ lp["w_ki"].astype(F32)
    k_i = k_i - jnp.mean(k_i, axis=-1, keepdims=True)
    k_i = k_i * jax.lax.rsqrt(jnp.mean(k_i * k_i, axis=-1, keepdims=True) + INDEX_NORM_EPS)
    k_i = turn(k_i * lp["ki_norm"].astype(F32) + lp["ki_norm_b"].astype(F32))
    w_i = (h @ lp["w_wi"].astype(F32)) * F32(HI**-0.5 * dI**-0.5)

    def block(args):
        i, qn, qr, qi, wi = args  # (B,), (B, H, nope), (B, H, rope), (B, HI, dI), (B, HI)
        index = jnp.einsum("inj,in->ij", _index_act(jnp.einsum("ind,jd->inj", qi, k_i)), wi)
        mask = _own_set(_select(index, pos[None, :] <= i[:, None], dims["topk"]))
        scores = (
            jnp.einsum("ihd,jhd->hij", qn, k_nope) + jnp.einsum("ihd,jd->hij", qr, k_r)
        ) * scale
        probs = jax.nn.softmax(jnp.where(mask[None], scores, -jnp.inf), axis=-1)
        return jnp.einsum("hij,jhd->ihd", probs, v), mask

    B = min(QUERY_BLOCK, s)
    n = -(-s // B)

    def blocks(x):
        """Whole blocks of queries: the last is filled up with copies of
        the last query, which are dropped again."""
        filled = jnp.concatenate([x, jnp.broadcast_to(x[-1:], (n * B - s,) + x.shape[1:])])
        return filled.reshape((n, B) + x.shape[1:])

    o, mask = jax.lax.map(block, tuple(blocks(x) for x in (pos, q_nope, q_rope, q_i, w_i)))
    o = o.reshape(n * B, H * vd)[:s]
    return o @ lp["w_o"].astype(F32), mask.reshape(n * B, s)[:s]


def _group_limit(ranked, n_group: int, topk_group: int):
    """ranked (s, E) -> (s, E) bool, the outputs inside the ``topk_group``
    best of ``n_group`` groups of consecutive outputs: a group scores the
    sum of its two largest, a tie goes to the lower group."""
    n, E = ranked.shape
    two = jnp.sort(ranked.reshape(n, n_group, E // n_group), axis=-1)[..., -2:].sum(-1)
    order = jnp.argsort(jnp.argsort(-two, axis=-1, stable=True), axis=-1)
    return jnp.repeat(order < topk_group, E // n_group, axis=-1)


def routing(h, lp, dims):
    """(s, E) routing weights: zero where an expert was not chosen.  The
    experts inside the kept groups are ranked on ``sigmoid score + bias``
    by a stable descending sort, so a tie goes to the lower index; the
    weights are the scores themselves, renormalised and scaled."""
    g = jax.nn.sigmoid(h @ lp["router"].astype(F32))
    ranked = g + lp["router_bias"].astype(F32)
    inside = _group_limit(ranked, dims["n_group"], dims["topk_group"])
    ranked = jnp.where(inside, ranked, -jnp.inf)
    rank = jnp.argsort(jnp.argsort(-ranked, axis=-1, stable=True), axis=-1)
    w = jnp.where(rank < dims["k"], g, 0.0)
    if dims["norm_topk"]:
        w = w / w.sum(-1, keepdims=True)
    return w * F32(dims["scale"])


def _swiglu(h, w_gu, w_down):
    """``(silu(h W_g) * (h W_u)) W_down``, gate and up side by side in
    ``w_gu``; an inner width over ``MLP_BLOCK`` a block at a time."""
    half = w_gu.shape[-1] // 2
    out = jnp.zeros((h.shape[0], w_down.shape[-1]), F32)
    for lo in range(0, half, MLP_BLOCK):
        hi = min(lo + MLP_BLOCK, half)
        gate = h @ w_gu[:, lo:hi].astype(F32)
        up = h @ w_gu[:, half + lo : half + hi].astype(F32)
        out = out + (jax.nn.silu(gate) * up) @ w_down[lo:hi].astype(F32)
    return out


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
    spec = cfg.rope_latent
    if cfg.score_function != "sigmoid" or not cfg.index_topk or spec is None:
        raise ValueError("this reference routes by sigmoid scores, under an indexer and YaRN")
    return {
        "H": cfg.n_heads, "rank": cfg.kv_lora_rank, "nope": cfg.qk_nope_head_dim,
        "rope": cfg.qk_rope_head_dim, "vd": cfg.v_head_dim,
        "yarn": (float(spec.theta), float(spec.factor), int(spec.original_max),
                 float(spec.beta_fast), float(spec.beta_slow)),
        "mscale": float(cfg.softmax_mscale),
        "HI": cfg.index_n_heads, "dI": cfg.index_head_dim, "topk": cfg.index_topk,
        "eps": float(cfg.norm_eps), "k": cfg.n_experts_per_tok,
        "n_group": cfg.n_group, "topk_group": cfg.topk_group,
        "norm_topk": bool(cfg.norm_topk), "scale": float(cfg.routed_scaling),
        "held": cfg.experts_held if held is None else int(held),
        "offset": cfg.expert_offset if offset is None else int(offset),
    }


@functools.partial(jax.jit, static_argnames=("dims_t", "kind"))
def _layer(x, lp, dims_t, kind):
    dims = dict(dims_t)
    y, mask = latent_attention(_rms(x, lp["attn_norm"], dims["eps"]), lp, dims)
    x = x + y
    return x + mlp(_rms(x, lp["mlp_norm"], dims["eps"]), lp, dims, kind), mask


def layers(params, cfg, tokens, held=None, offset=None):
    """One prompt through the stack: yields, a layer at a time, (the
    layer's kind, its output (s, D) float32, the (s, s) mask of the pairs
    it attended)."""
    dims_t = tuple(sorted(_dims(cfg, held, offset).items()))
    with jax.default_matmul_precision("highest"):
        x = params["embed"][jnp.asarray(tokens, jnp.int32)].astype(F32)
        for kind, lp in zip(cfg.layer_kinds, params["layers"]):
            if kind[0] != "mla" or kind[1] not in ("dense", "experts"):
                raise ValueError(f"not a layer of this family: {kind}")
            x, mask = _layer(x, lp, dims_t, kind[1])
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


@functools.partial(jax.jit, static_argnames=("eps",))
def _mtp_input(x, e, final_norm, mp, eps):
    both = jnp.concatenate(
        [_rms(e, mp["enorm"], eps), _rms(_rms(x, final_norm, eps), mp["hnorm"], eps)], axis=-1
    )
    return both @ mp["eh_proj"].astype(F32)


def mtp_hidden_states(params, cfg, x, tokens, held=None, offset=None):
    """The prediction module over positions ``0..s-2`` of one prompt (the
    last has no next token): ``x`` (s, D) the stack's ``hidden_states``.
    Returns ((s - 1, D) float32 before the module's final norm, the
    (s - 1, s - 1) mask of the pairs its block attended)."""
    dims_t = tuple(sorted(_dims(cfg, held, offset).items()))
    mp = params["mtp"]
    with jax.default_matmul_precision("highest"):
        e = params["embed"][jnp.asarray(tokens, jnp.int32)[1:]].astype(F32)
        u = _mtp_input(x[:-1], e, params["final_norm"], mp, float(cfg.norm_eps))
        return _layer(u, mp["layer"], dims_t, "experts")


def mtp_head(params, cfg, xm):
    """The module's final norm and the stack's head (shared)."""
    with jax.default_matmul_precision("highest"):
        return _head(xm, params["mtp"]["final_norm"], params["lm_head"], float(cfg.norm_eps))


def all_logits(params, cfg, tokens, held=None, offset=None):
    """((s, V) the stack's logits at every position of one prompt,
    (s - 1, V) the prediction module's: at ``t`` they predict token
    ``t + 2``), float32."""
    x = hidden_states(params, cfg, tokens, held, offset)
    xm, _ = mtp_hidden_states(params, cfg, x, tokens, held, offset)
    return head(params, cfg, x), mtp_head(params, cfg, xm)
