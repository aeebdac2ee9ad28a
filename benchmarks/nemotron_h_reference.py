"""The plain reference of the ``nemotron_h`` family
(NVIDIA-Nemotron-3-Super-120B-A12B) as ``models/hybrid.py`` serves it:
float32 ``jax.numpy`` at the highest matmul precision, written from the
layer equations (Mamba-2 / SSD: arXiv:2405.21060; Nemotron-H:
arXiv:2504.03624; "LatentMoE: experts in 1024-d latent" from the catalog
row's ``described_as``).

No kernel, no cache, no chunks, no batching, none of the program's
functions (nothing of ``ops/``): one prompt, the whole sequence at once,
a loop over the letters of the layer pattern, the state-space layer as
its recurrence a token at a time.

Every published layer is ``x <- x + f(RMSNorm(x))`` (eps 1e-5, a gain)
with ONE ``f``, named by a letter; ``h`` is that normed input, ``t`` a
position:

* ``M`` (Mamba-2; ``H`` heads of ``P``, ``G`` groups, state ``N``; the
  group of head ``i`` is ``g(i) = i // (H / G)``).  ``[z_t ; u_t ; d_t] =
  h_t W_in`` (``H P`` ; ``H P + 2 G N`` ; ``H``).  A depthwise causal
  convolution of ``conv_kernel`` taps with a bias a channel, ``u`` zero
  before the first token: ``c_t[j] = b[j] + sum_k w[k, j] u_{t-(K-1-k)}[j]``;
  ``[xs_t ; B_t ; C_t] = silu(c_t)``.  ``dt_t[i] = softplus(d_t[i] +
  dt_bias[i])`` (not clamped above), ``A[i] = -exp(A_log[i])``.  The
  state, (P, N) a head, float32, zero before the first token:
  ``S_t[i] = exp(dt_t[i] A[i]) S_{t-1}[i] + dt_t[i] xs_t[i] (x) B_t[g(i)]``;
  ``y_t[i] = S_t[i] C_t[g(i)] + D[i] xs_t[i]``.  The gated norm gates
  first, ``v_t = y_t silu(z_t)``, then norms each of the ``G`` groups of
  ``H P / G`` channels on its own (RMSNorm, a gain of ``H P``); output
  ``v_t W_out``.
* ``*`` (attention): ``q_t = h_t W_q`` (``Hq`` heads of ``d``), ``k_t``,
  ``v_t`` (``Gk`` heads); NO rotation, no norm on q or k; ``o_i = sum_{s<=t}
  softmax_s(q_t[i] . k_s[i // (Hq / Gk)] / sqrt(d)) v_s[i // (Hq / Gk)]``
  under an explicit mask; output ``[o_i] W_o``.
* ``E`` (LatentMoE): ``s = sigmoid(h W_r)`` over all ``E`` router outputs;
  the ``k`` largest of ``s + beta`` (``n_group`` 1: no group limit; a tie
  to the lower index); ``w_e = scale s_e / sum_chosen s``; ``u = h W_dn``
  (the latent); ``r = sum_{e chosen} w_e W2_e relu(W1_e u)^2`` (no gate);
  ``f(h) = r W_up + Ws2 relu(Ws1 h)^2``, the shared expert on ``h``
  itself.  **The share's rule**: of the ``E`` experts only ``held`` from
  ``offset`` on are summed (what the absent ones would add to ``r`` is
  left out, and ``r_here W_up`` goes on: ``W_up`` is linear, so the
  shares' ``r`` add up); the shared expert is every share's alike.  ``E``
  and 0 give the uncut layer.
* Final RMSNorm and the untied head.

Departures from the papers, each also under ``assumed`` in
``benchmarks/configs/nemotron-3-super-120b-a12b-l11e128.json``: no rotary
embedding in the ``*`` layers (the config's ``rope_theta`` is carried
unused); the order of ``W_in``'s outputs and of the convolution's; ``dt``
not clamped; the gate before the norm and the norm a group at a time; the
router on the full hidden state, the latent pair with neither bias nor
norm.  The prediction module is not served.

The parameters are the serving pytree (``hybrid.init_params``'s layout: a
(mixer, MLP) pair an entry, ``w_qkv`` holding ``W_q``, ``W_k``, ``W_v``
side by side); ``cfg`` is read for its sizes only.
``benchmarks/nemotron_h_reference.py`` is the benchmark's copy of this
file (``benchmarks/tests/test_arch_nemotron_h.py`` holds the two equal).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _rms(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gain.astype(F32)


def _back(x, n: int):
    """x (s, ...) as of ``n`` positions before: zeros, then ``x[:-n]``."""
    if n == 0:
        return x
    return jnp.concatenate([jnp.zeros_like(x[:n]), x[:-n]])


# Each mechanism below is a function of its own, so that a control can
# compute the reference without it (``chip_smoke.py --hybrid --model
# nemotron_h --control NAME``, ``tests/test_nemotron_h_model.py``).


def _conv(u, lp):
    """The depthwise causal convolution with its bias: (s, C) -> (s, C)."""
    w = lp["conv_w"].astype(F32)
    taps = len(w)
    return sum(w[k] * _back(u, taps - 1 - k) for k in range(taps)) + lp["conv_b"].astype(F32)


def _keep(state):
    """The state a token leaves for the next: float32, as it is."""
    return state


def _skip(y, d, xs):
    """``y + D x``: (s, H, P), (H,), (s, H, P)."""
    return y + d.astype(F32)[:, None] * xs


def _gated_norm(y, z, gain, groups: int, eps: float):
    """The gate, then an RMSNorm over each group of channels on its own."""
    v = y * jax.nn.silu(z)
    grouped = v.reshape(v.shape[0], groups, -1)
    grouped = grouped * jax.lax.rsqrt(jnp.mean(grouped * grouped, axis=-1, keepdims=True) + eps)
    return grouped.reshape(v.shape) * gain.astype(F32)


def _act(x):
    """``relu2``."""
    return jnp.square(jax.nn.relu(x))


def _mlp(h, w_up, w_down):
    """An MLP without a gate: ``W2 act(W1 h)``."""
    return _act(h @ w_up.astype(F32)) @ w_down.astype(F32)


def _routed_weights(s_chosen, scale: float):
    """The chosen experts' weights: renormalised, times the routed scale."""
    return scale * s_chosen / (s_chosen.sum(-1, keepdims=True) + 1e-20)


def _rotate(q, k):
    """What turns q and k (s, heads, d) by position: nothing."""
    return q, k


def mamba(h, lp, dims):
    """h: (s, D) -> (s, D)."""
    s = h.shape[0]
    H, P, G, N = dims["H"], dims["P"], dims["G"], dims["N"]
    inner = H * P
    zud = h @ lp["w_in"].astype(F32)
    z, u, d = zud[:, :inner], zud[:, inner:-H], zud[:, -H:]
    c = jax.nn.silu(_conv(u, lp))
    xs = c[:, :inner].reshape(s, H, P)
    b_in = c[:, inner : inner + G * N].reshape(s, G, N)
    c_in = c[:, inner + G * N :].reshape(s, G, N)
    dt = jax.nn.softplus(d + lp["ssm_dt_bias"].astype(F32))  # (s, H)
    a = -jnp.exp(lp["ssm_a_log"].astype(F32))
    of_head = jnp.arange(H) // (H // G)

    def token(state, now):
        x_t, dt_t, b_t, c_t = now  # (H, P), (H,), (G, N), (G, N)
        decay = jnp.exp(dt_t * a)[:, None, None]
        state = decay * state + (dt_t[:, None] * x_t)[:, :, None] * b_t[of_head][:, None, :]
        state = _keep(state)
        return state, jnp.einsum("hpn,hn->hp", state, c_t[of_head])

    _, y = jax.lax.scan(token, jnp.zeros((H, P, N), F32), (xs, dt, b_in, c_in))
    y = _skip(y, lp["ssm_d"], xs)
    v = _gated_norm(y.reshape(s, inner), z, lp["ssm_norm"], G, dims["eps"])
    return v @ lp["w_out"].astype(F32)


def attention(h, lp, dims):
    """h: (s, D) -> (s, D)."""
    s = h.shape[0]
    Hq, Gk, d = dims["Hq"], dims["Gk"], dims["d"]
    qkv = (h @ lp["w_qkv"].astype(F32)).reshape(s, Hq + 2 * Gk, d)
    q, k, v = qkv[:, :Hq], qkv[:, Hq : Hq + Gk], qkv[:, Hq + Gk :]
    q, k = _rotate(q, k)
    qg = q.reshape(s, Gk, Hq // Gk, d)
    scores = jnp.einsum("igqd,jgd->gqij", qg, k) / math.sqrt(d)
    mask = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    probs = jax.nn.softmax(jnp.where(mask[None, None], scores, -jnp.inf), axis=-1)
    o = jnp.einsum("gqij,jgd->igqd", probs, v).reshape(s, Hq * d)
    return o @ lp["w_o"].astype(F32)


def routed(h, lp, dims):
    """``r_here W_up``: the routed half of an ``E`` layer for the experts
    held, h: (s, D) -> (s, D).  Expert ``e`` of ``lp`` is the router's
    output ``offset + e``."""
    scores = jax.nn.sigmoid(h @ lp["router"].astype(F32))  # (s, E)
    sel = scores + lp["router_bias"].astype(F32)
    _, idx = jax.lax.top_k(sel, dims["k"])  # ties to the lower index
    chosen = jnp.take_along_axis(scores, idx, axis=-1)
    w = _routed_weights(chosen, dims["scale"])
    # (s, E): a token's weight for every router output, zero where not chosen.
    weight = jnp.zeros_like(scores).at[jnp.arange(h.shape[0])[:, None], idx].set(w)
    u = h @ lp["w_lat_down"].astype(F32)

    def expert(e, out):
        # The share's rule: only the experts held are summed.
        pick = lambda name: jax.lax.dynamic_index_in_dim(lp[name], e, 0, keepdims=False)
        y = _mlp(u, pick("w_up_e"), pick("w_down_e"))
        return out + y * jax.lax.dynamic_index_in_dim(weight, dims["offset"] + e, 1)

    r = jax.lax.fori_loop(0, dims["held"], expert, jnp.zeros_like(u))
    return r @ lp["w_lat_up"].astype(F32)


def experts(h, lp, dims):
    """An ``E`` layer: the routed half of this share and the shared expert."""
    return routed(h, lp, dims) + _mlp(h, lp["w_up_s"], lp["w_down_s"])


def letters(cfg) -> str:
    """The pattern the configuration's pairs were read from."""
    mixer = {"mamba": "M", "full": "*"}
    mlp = {"experts": "E", "none": ""}
    try:
        return "".join(mixer[a] + mlp[b] for a, b in cfg.layer_kinds)
    except KeyError:
        raise ValueError(f"not a stack of this family: {cfg.layer_kinds}") from None


def _dims(cfg, held, offset) -> dict:
    if not cfg.moe_latent or cfg.expert_act != "relu2" or cfg.n_group != 1:
        raise ValueError("this reference has relu2 experts in a latent, one routing group")
    return {
        "H": cfg.mamba_heads, "P": cfg.mamba_head_dim, "G": cfg.mamba_groups,
        "N": cfg.ssm_state, "Hq": cfg.n_heads, "Gk": cfg.n_kv_heads,
        "d": cfg.attn_head_dim, "eps": float(cfg.norm_eps),
        "k": cfg.n_experts_per_tok, "scale": float(cfg.routed_scaling),
        "held": cfg.experts_held if held is None else int(held),
        "offset": cfg.expert_offset if offset is None else int(offset),
    }


@functools.partial(jax.jit, static_argnames=("letter", "dims_t"))
def _layer(x, lp, letter, dims_t):
    dims = dict(dims_t)
    f = {"M": mamba, "*": attention, "E": experts}[letter]
    norm = lp["mlp_norm"] if letter == "E" else lp["attn_norm"]
    return x + f(_rms(x, norm, dims["eps"]), lp, dims)


def hidden_states(params, cfg, tokens, held=None, offset=None):
    """(s, D) float32 before the final norm, for one prompt; ``held``
    experts from ``offset`` (absent: the configuration's share)."""
    dims_t = tuple(sorted(_dims(cfg, held, offset).items()))
    pairs = iter(params["layers"])
    with jax.default_matmul_precision("highest"):
        x = params["embed"][jnp.asarray(tokens, jnp.int32)].astype(F32)
        lp = None
        for letter in letters(cfg):
            if letter != "E":  # a mixer opens a pair; its ``E`` is in the same entry
                lp = next(pairs)
            x = _layer(x, lp, letter, dims_t)
    return x


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, final_norm, matrix, eps):
    return _rms(x, final_norm, eps) @ matrix.astype(F32)


def head(params, cfg, x):
    """Final norm and the head: (..., D) -> (..., V) float32."""
    with jax.default_matmul_precision("highest"):
        return _head(x, params["final_norm"], params["lm_head"], float(cfg.norm_eps))


def all_logits(params, cfg, tokens, held=None, offset=None):
    """(s, V) float32 logits at every position of one prompt."""
    return head(params, cfg, hidden_states(params, cfg, tokens, held, offset))
