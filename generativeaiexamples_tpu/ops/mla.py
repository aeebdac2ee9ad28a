"""Multi-head latent attention (DeepSeek-V2's form, ``q_lora_rank`` null)
over a latent cache: ``kv_lora_rank`` normalised latent values and the
rotated shared rope key a token, 576 values at the published widths.

* :func:`attend_expanded` — prefill: keys and values are expanded from
  the cached latents through ``W_kvb`` and attention is the usual
  softmax over heads of (nope + rope) keys;
* :func:`attend_absorbed` — decode: ``W_kvb``'s key half is folded into
  the query and its value half applied after the weighted sum, so each
  step reads the latent rows once and expands nothing.

Both are plain XLA (one layer in six is of this kind) and give the same
numbers up to rounding: ``tests/test_hybrid_ops.py`` holds one against
the other.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32


def rope_interleaved(x, positions, theta: float):
    """Rotary embedding over adjacent pairs (x0, x1), (x2, x3), ...

    x: (b, s, ..., d) with d even; positions: (b, s)."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    ang = positions.astype(F32)[..., None] * inv  # (b, s, d/2)
    extra = x.ndim - 3
    ang = ang.reshape(ang.shape[:2] + (1,) * extra + ang.shape[-1:])
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    xf = x.astype(F32)
    x0, x1 = xf[..., 0::2], xf[..., 1::2]
    out = jnp.stack([x0 * cos - x1 * sin, x1 * cos + x0 * sin], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


def _mask(q_pos, window: int):
    """(b, 1, s, window): key position <= query position."""
    return (jnp.arange(window, dtype=jnp.int32)[None, None, None, :]
            <= q_pos[:, None, :, None])


@jax.named_scope("layer/mla/attn")
def attend_expanded(q_nope, q_rope, latent, w_kvb, q_pos, *, rank, nope, v_dim):
    """q_nope: (b, s, H, nope); q_rope: (b, s, H, rope) rotated; latent:
    (b, T, rank + rope) the cache window (normalised latent, rotated
    rope key); w_kvb: (rank, H * (nope + v_dim)); q_pos: (b, s).
    Returns (b, s, H, v_dim)."""
    b, T, _ = latent.shape
    H = q_nope.shape[2]
    c, k_rope = latent[..., :rank], latent[..., rank:]
    kv = jnp.dot(c, w_kvb).reshape(b, T, H, nope + v_dim)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    scale = (nope + q_rope.shape[-1]) ** -0.5
    scores = (
        jnp.einsum("bshd,bthd->bhst", q_nope, k_nope, preferred_element_type=F32)
        + jnp.einsum("bshd,btd->bhst", q_rope, k_rope, preferred_element_type=F32)
    ) * scale
    scores = jnp.where(_mask(q_pos, T), scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    return jnp.einsum("bhst,bthd->bshd", probs, v)


@jax.named_scope("layer/mla/attn")
def attend_absorbed(q_nope, q_rope, latent, w_kvb, q_pos, *, rank, nope, v_dim):
    """The same attention with ``W_kvb`` absorbed: arguments and result
    as :func:`attend_expanded`."""
    H = q_nope.shape[2]
    w = w_kvb.reshape(rank, H, nope + v_dim)
    w_k, w_v = w[..., :nope], w[..., nope:]
    c, k_rope = latent[..., :rank], latent[..., rank:]
    q_lat = jnp.einsum("bshd,rhd->bshr", q_nope, w_k)
    scale = (nope + q_rope.shape[-1]) ** -0.5
    scores = (
        jnp.einsum("bshr,btr->bhst", q_lat, c, preferred_element_type=F32)
        + jnp.einsum("bshd,btd->bhst", q_rope, k_rope, preferred_element_type=F32)
    ) * scale
    scores = jnp.where(_mask(q_pos, latent.shape[1]), scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(c.dtype)
    o_lat = jnp.einsum("bhst,btr->bshr", probs, c)
    return jnp.einsum("bshr,rhd->bshd", o_lat, w_v)
