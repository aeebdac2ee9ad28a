"""Mamba-2's state-space mixer (SSD, arXiv:2405.21060): the serving-path
forms.

A head ``i`` of ``P`` channels keeps a state ``S`` of shape (P, N).  Per
token, with a step ``dt > 0``, a decay rate ``A < 0`` a head, and ``B``,
``C`` of ``N`` values that the ``H / G`` heads of a group share:

    S_t = exp(dt_t A) S_(t-1) + dt_t x_t (x) B_t
    y_t = S_t C_t + D x_t

Two forms, both plain XLA:

* :func:`ssm_step` — one token a row, the decode step: an elementwise
  update of every slot's state and one reduction over it;
* :func:`ssm_scan` — a ``lax.scan`` over blocks of ``block`` tokens, each
  as matrix products (the quadratic form inside a block, the state handed
  from block to block).  With ``a_s = dt_s A`` and ``cs_t`` its running
  sum inside the block (all <= 0):

      y_t = exp(cs_t) S_in C_t
            + sum_{s<=t} exp(cs_t - cs_s) (C_t . B_s) dt_s x_s + D x_t
      S_out = exp(cs_L) S_in + sum_s exp(cs_L - cs_s) dt_s x_s (x) B_s

  so every exponent is of a number <= 0.

A token with ``dt = 0`` decays by exactly 1 and adds exactly 0: padded
positions, rows that do not decode and a block's padding are given that,
and the state is left bit for bit.

The state is float32 and the two products that touch it run at the
highest matmul precision: it is read again by every later token (a head's
decay a token is up to 0.999), and they are 4 P N a head and token beside
2 x 110 M for the layer's projections.  :func:`gated_group_norm` is the
mixer's output norm.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32
HI = jax.lax.Precision.HIGHEST


@jax.named_scope("layer/mamba/step")
def ssm_step(x, dt, a, b_in, c_in, d_skip, state):
    """One token a row.  x: (b, H, P); dt: (b, H) float32 (0: the row
    does not count); a: (H,) float32 < 0; b_in, c_in: (b, G, N); d_skip:
    (H,); state: (b, H, P, N) float32.  Returns (y (b, H, P) f32, state)."""
    b, H, P = x.shape
    G, N = b_in.shape[1:]
    x, b_in, c_in = (t.astype(F32) for t in (x, b_in, c_in))
    S = state.reshape(b, G, H // G, P, N)
    decay = jnp.exp(dt * a).reshape(b, G, H // G, 1, 1)
    dx = (dt[..., None] * x).reshape(b, G, H // G, P, 1)
    S = S * decay + dx * b_in[:, :, None, None, :]
    y = jnp.sum(S * c_in[:, :, None, None, :], axis=-1).reshape(b, H, P)
    return y + d_skip.astype(F32)[:, None] * x, S.reshape(b, H, P, N)


def blocks_of(s: int, block: int) -> tuple[int, int]:
    """(tokens a block, blocks) that :func:`ssm_scan` cuts ``s`` tokens
    into: whole blocks of ``block``, the last padded; a call shorter than
    a block is one block of its own length."""
    length = min(block, s)
    return length, -(-s // length)


@jax.named_scope("layer/mamba/scan")
def ssm_scan(x, dt, a, b_in, c_in, d_skip, state, *, block: int):
    """``s`` tokens a row from a carried state.  x: (b, s, H, P); dt:
    (b, s, H) float32 (0 at a token that does not count); a: (H,); b_in,
    c_in: (b, s, G, N); d_skip: (H,); state: (b, H, P, N) float32.
    Returns (y (b, s, H, P) f32, state)."""
    b, s, H, P = x.shape
    G, N = b_in.shape[2:]
    L, n_blocks = blocks_of(s, block)
    pad = n_blocks * L - s

    def cut(t):
        """(b, s, ...) -> (blocks, b, L, ...), the last block padded."""
        t = jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
        return jnp.moveaxis(t.reshape(b, n_blocks, L, *t.shape[2:]), 1, 0)

    seen = jnp.tril(jnp.ones((L, L), bool))  # [t, s]: s <= t

    def one(S, blk):
        xb, dtb, bb, cb = blk  # (b, L, H, P), (b, L, H), (b, L, G, N) twice
        xg = xb.astype(F32).reshape(b, L, G, H // G, P)
        cs = jnp.cumsum(dtb * a, axis=1)  # (b, L, H), <= 0
        # Inside the block: W[t, s] = exp(cs_t - cs_s) (C_t . B_s) dt_s.
        cb_ts = jnp.einsum("btgn,bsgn->bgts", cb, bb, preferred_element_type=F32)
        gap = cs[:, :, None, :] - cs[:, None, :, :]  # (b, t, s, H)
        w = jnp.exp(jnp.where(seen[None, :, :, None], gap, -jnp.inf)) * dtb[:, None, :, :]
        w = jnp.moveaxis(w, 3, 1).reshape(b, G, H // G, L, L) * cb_ts[:, :, None]
        y = jnp.einsum("bgits,bsgip->btgip", w, xg)
        # What the state brought in, as of each token.
        Sg = S.reshape(b, G, H // G, P, N)
        from_state = jnp.einsum(
            "bgipn,btgn->btgip", Sg, cb.astype(F32), precision=HI
        )
        y = y + jnp.exp(cs).reshape(b, L, G, H // G, 1) * from_state
        # The state handed on.
        to_end = jnp.exp(cs[:, -1:, :] - cs) * dtb  # (b, L, H)
        dx = to_end.reshape(b, L, G, H // G, 1) * xg
        Sg = jnp.exp(cs[:, -1]).reshape(b, G, H // G, 1, 1) * Sg + jnp.einsum(
            "bsgip,bsgn->bgipn", dx, bb.astype(F32), precision=HI
        )
        return Sg.reshape(b, H, P, N), y.reshape(b, L, H, P)

    state, y = jax.lax.scan(one, state, (cut(x), cut(dt), cut(b_in), cut(c_in)))
    y = jnp.moveaxis(y, 0, 1).reshape(b, n_blocks * L, H, P)[:, :s]
    return y + d_skip.astype(F32)[:, None] * x.astype(F32), state


def gated_group_norm(y, z, gain, groups: int, eps: float):
    """Mamba-2's gated RMSNorm, ``norm_before_gate`` false: the gate
    first, ``v = y silu(z)``, then an RMSNorm over each of ``groups``
    groups of channels on its own, times ``gain``.  y, z: (..., C)."""
    v = y.astype(F32) * jax.nn.silu(z.astype(F32))
    grouped = v.reshape(*v.shape[:-1], groups, v.shape[-1] // groups)
    grouped = grouped * jax.lax.rsqrt(jnp.mean(grouped * grouped, axis=-1, keepdims=True) + eps)
    return grouped.reshape(v.shape) * gain.astype(F32)
