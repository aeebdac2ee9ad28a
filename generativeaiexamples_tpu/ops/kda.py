"""Kimi Delta Attention (arXiv:2510.26692): the serving-path forms.

A KDA head keeps a state ``S`` of shape (d_k, d_v).  Per token, with a
per-channel log-gate ``g`` in (floor, 0), ``alpha = exp(g)``, a write
strength ``beta`` in (0, 1), and L2-normalised ``q`` (scaled) and ``k``:

    S' = Diag(alpha_t) S_(t-1)
    S_t = S' + beta_t k_t (v_t - S'^T k_t)^T
    o_t = S_t^T q_t

Two forms, both plain XLA (no Pallas kernel yet: PERF.md section 7):

* :func:`kda_step` — one token a row, the decode step;
* :func:`kda_chunked` — a ``lax.scan`` over sub-chunks of ``SUB`` tokens,
  each solved in closed form (the WY form of the delta rule).  Inside a
  sub-chunk keys are scaled by ``exp(-G)`` and queries by ``exp(+G)``,
  ``G`` the gate cumulated from the sub-chunk's start; the gate's floor
  of -5 a step bounds ``|G|`` by ``5 * SUB = 80``, inside float32's
  range (e^80 = 5.5e34), which is what fixes ``SUB`` at 16.

A token with ``beta = 0`` and ``g = 0`` leaves the state exactly as it
was: padded positions, rows that do not decode and a chunk's padding
are given those.

The state and every product that touches it are float32 at the
highest matmul precision: they are a small share of a layer's
operations (4 * d_k * d_v a head and token, against 2 * 63M for the
projections) and the state is read again by every later token.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

SUB = 16
F32 = jnp.float32
HI = jax.lax.Precision.HIGHEST


def kda_gate(f, a_log, dt_bias, floor: float):
    """The safe gate: ``floor * sigmoid(exp(A_h) * (f + dt_bias))`` in
    (floor, 0) a channel (``floor`` is ``kda_lower_bound``, -5).

    f: (..., H, K) float; a_log: (H,); dt_bias: (H, K)."""
    return floor * jax.nn.sigmoid(
        jnp.exp(a_log.astype(F32))[:, None] * (f.astype(F32) + dt_bias.astype(F32))
    )


def l2_normalize(x, eps: float = 1e-6):
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


def causal_conv(x, tail, weight):
    """Depthwise causal convolution over the sequence, one filter a
    channel, continued from ``tail``.

    x: (b, s, C) the new inputs; tail: (b, W-1, C) the inputs before
    them; weight: (W, C), ``weight[-1]`` multiplying the current input.
    Returns (y (b, s, C) float32, xin (b, s+W-1, C)) — ``xin`` is what
    the caller cuts the next tail from."""
    w = weight.shape[0]
    xin = jnp.concatenate([tail.astype(x.dtype), x], axis=1)
    s = x.shape[1]
    y = sum(
        xin[:, j : j + s].astype(F32) * weight[j].astype(F32) for j in range(w)
    )
    return y, xin


def next_tail(xin, n_valid, width: int):
    """The last ``width - 1`` inputs of each row once ``n_valid`` (b,) new
    ones count: rows ``n_valid + [0, width-1)`` of ``xin``."""
    idx = n_valid[:, None] + jnp.arange(width - 1, dtype=jnp.int32)[None, :]
    return jnp.take_along_axis(xin, idx[:, :, None], axis=1)


@jax.named_scope("layer/kda/scan")
def kda_step(q, k, v, g, beta, state):
    """One token a row.  q, k, g: (b, H, K); v: (b, H, V); beta: (b, H);
    state: (b, H, K, V) float32.  Returns (o (b, H, V) f32, state)."""
    q, k, v, g, beta = (a.astype(F32) for a in (q, k, v, g, beta))
    decayed = state * jnp.exp(g)[..., None]
    read = jnp.einsum("bhkv,bhk->bhv", decayed, k, precision=HI)
    u = beta[..., None] * (v - read)
    state = decayed + k[..., None] * u[..., None, :]
    o = jnp.einsum("bhkv,bhk->bhv", state, q, precision=HI)
    return o, state


def _sub_chunk(state, q, k, v, g, beta):
    """One sub-chunk in closed form.  q, k, g: (b, H, C, K); v: (b, H, C,
    V); beta: (b, H, C); state (b, H, K, V)."""
    c = q.shape[2]
    G = jnp.cumsum(g, axis=2)  # (b, H, C, K), in [-5 C, 0]
    k_in = k * jnp.exp(G)  # key t as the state at t sees it
    k_out = k * jnp.exp(-G)
    q_in = q * jnp.exp(G)
    # M[t, s] = k_s^T Diag(exp(G_t - G_s)) k_t, used for s < t.
    M = jnp.einsum("bhtk,bhsk->bhts", k_in, k_out, precision=HI)
    strict = jnp.tril(jnp.ones((c, c), dtype=bool), -1)
    A = jnp.where(strict, M, 0.0) * beta[..., None]
    rhs = beta[..., None] * (
        v - jnp.einsum("bhtk,bhkv->bhtv", k_in, state, precision=HI)
    )
    # (I + A) U = rhs, A strictly lower triangular.
    U = jax.scipy.linalg.solve_triangular(
        A + jnp.eye(c, dtype=F32), rhs, lower=True, unit_diagonal=True
    )
    P = jnp.einsum("bhtk,bhsk->bhts", q_in, k_out, precision=HI)
    causal = jnp.tril(jnp.ones((c, c), dtype=bool))
    o = jnp.einsum("bhtk,bhkv->bhtv", q_in, state, precision=HI) + jnp.einsum(
        "bhts,bhsv->bhtv", jnp.where(causal, P, 0.0), U, precision=HI
    )
    last = G[:, :, -1]  # (b, H, K)
    state = state * jnp.exp(last)[..., None] + jnp.einsum(
        "bhsk,bhsv->bhkv", k_out * jnp.exp(last)[:, :, None, :], U, precision=HI
    )
    return state, o


@jax.named_scope("layer/kda/scan")
def kda_chunked(q, k, v, g, beta, state, sub: int = SUB):
    """A run of tokens a row.  q, k, g: (b, s, H, K); v: (b, s, H, V);
    beta: (b, s, H); state: (b, H, K, V) float32.  ``s`` is padded to a
    multiple of ``sub`` with tokens that leave the state alone.
    Returns (o (b, s, H, V) f32, state)."""
    b, s, H, K = q.shape
    pad = (-s) % sub
    q, k, v, g, beta = (a.astype(F32) for a in (q, k, v, g, beta))
    if pad:
        q, k, v, g = (
            jnp.pad(a, ((0, 0), (0, pad), (0, 0), (0, 0))) for a in (q, k, v, g)
        )
        beta = jnp.pad(beta, ((0, 0), (0, pad), (0, 0)))
    n = (s + pad) // sub

    def split(a):  # (b, n*sub, H, X) -> (n, b, H, sub, X)
        return a.reshape(b, n, sub, H, -1).transpose(1, 0, 3, 2, 4)

    xs = (split(q), split(k), split(v), split(g), split(beta[..., None])[..., 0])

    def body(st, x):
        return _sub_chunk(st, *x)

    state, o = jax.lax.scan(body, state, xs)
    o = o.transpose(1, 0, 3, 2, 4).reshape(b, n * sub, H, -1)
    return o[:, :s], state
