"""Multi-head latent attention (DeepSeek-V2's form) over a latent cache:
``kv_lora_rank`` normalised latent values and the rotated shared rope key
a token (576 values at Ling's published widths, 320 at Mistral-Small-4's).

* :func:`attend_expanded` — prefill: keys and values are expanded from
  the cached latents through ``W_kvb`` and attention is the usual
  softmax over heads of (nope + rope) keys, the whole window at once;
* :func:`attend_blocks` — prefill over a long window: the same
  attention a block of latent rows at a time with an online softmax, so
  that only a block's expansion and scores are alive, and only the whole
  blocks up to the rows' lengths are read;
* :func:`attend_absorbed` — decode: ``W_kvb``'s key half is folded into
  the query and its value half applied after the weighted sum, so each
  step reads the latent rows once and expands nothing;
* :func:`attend_absorbed_blocks` — the absorbed form a block of rows at
  a time up to a row's length, with the rows never cut in two (the folded
  query and the rope query side by side against the whole row, the
  weighted sum over the whole row too, its rope columns dropped
  afterwards): a decode step that reads what a slot holds.

All are plain XLA and give the same numbers up to rounding:
``tests/test_hybrid_ops.py`` holds one against the other.

The rotation is over adjacent pairs (:func:`rope_interleaved`), with the
plain frequencies of a ``theta`` or those of a ``RopeSpec``
(``ops/rope.py``: YaRN).  :func:`position_scale` is the query scale that
grows with the position (``llama_4_scaling_beta``).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from generativeaiexamples_tpu.ops.rope import RopeSpec, spec_frequencies

F32 = jnp.float32


def rope_interleaved(x, positions, theta: float, spec: RopeSpec | None = None):
    """Rotary embedding over adjacent pairs (x0, x1), (x2, x3), ...

    x: (b, s, ..., d) with d even; positions: (b, s).  The frequencies are
    ``theta^(-2i/d)``, or with a ``spec`` that spec's (YaRN's blend), cos
    and sin then multiplied by its ``attention_factor``."""
    d = x.shape[-1]
    if spec is None:
        inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    else:
        inv = jnp.asarray(spec_frequencies(spec, d))
    ang = positions.astype(F32)[..., None] * inv  # (b, s, d/2)
    extra = x.ndim - 3
    ang = ang.reshape(ang.shape[:2] + (1,) * extra + ang.shape[-1:])
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if spec is not None and spec.attention_factor != 1.0:
        cos, sin = cos * spec.attention_factor, sin * spec.attention_factor
    xf = x.astype(F32)
    x0, x1 = xf[..., 0::2], xf[..., 1::2]
    out = jnp.stack([x0 * cos - x1 * sin, x1 * cos + x0 * sin], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


def position_scale(positions, beta: float, original_max: int):
    """``1 + beta ln(1 + floor(p / original_max))``, float32: 1 below the
    original context, and a step up at each multiple of it."""
    steps = jnp.floor(positions.astype(F32) / float(original_max))
    return 1.0 + beta * jnp.log1p(steps)


def _mask(q_pos, window: int):
    """(b, 1, s, window): key position <= query position."""
    return (jnp.arange(window, dtype=jnp.int32)[None, None, None, :]
            <= q_pos[:, None, :, None])


@jax.named_scope("layer/mla/attn")
def attend_expanded(q_nope, q_rope, latent, w_kvb, q_pos, *, rank, nope, v_dim, scale=None):
    """q_nope: (b, s, H, nope); q_rope: (b, s, H, rope) rotated; latent:
    (b, T, rank + rope) the cache window (normalised latent, rotated
    rope key); w_kvb: (rank, H * (nope + v_dim)); q_pos: (b, s); ``scale``
    of the scores (absent: (nope + rope)^-1/2).  Returns (b, s, H, v_dim)."""
    b, T, _ = latent.shape
    H = q_nope.shape[2]
    c, k_rope = latent[..., :rank], latent[..., rank:]
    kv = jnp.dot(c, w_kvb).reshape(b, T, H, nope + v_dim)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    if scale is None:
        scale = (nope + q_rope.shape[-1]) ** -0.5
    scores = (
        jnp.einsum("bshd,bthd->bhst", q_nope, k_nope, preferred_element_type=F32)
        + jnp.einsum("bshd,btd->bhst", q_rope, k_rope, preferred_element_type=F32)
    ) * scale
    scores = jnp.where(_mask(q_pos, T), scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    return jnp.einsum("bhst,bthd->bshd", probs, v)


@jax.named_scope("layer/mla/attn")
def attend_absorbed(q_nope, q_rope, latent, w_kvb, q_pos, *, rank, nope, v_dim, scale=None):
    """The same attention with ``W_kvb`` absorbed: arguments and result
    as :func:`attend_expanded`."""
    H = q_nope.shape[2]
    w = w_kvb.reshape(rank, H, nope + v_dim)
    w_k, w_v = w[..., :nope], w[..., nope:]
    c, k_rope = latent[..., :rank], latent[..., rank:]
    q_lat = jnp.einsum("bshd,rhd->bshr", q_nope, w_k)
    if scale is None:
        scale = (nope + q_rope.shape[-1]) ** -0.5
    scores = (
        jnp.einsum("bshr,btr->bhst", q_lat, c, preferred_element_type=F32)
        + jnp.einsum("bshd,btd->bhst", q_rope, k_rope, preferred_element_type=F32)
    ) * scale
    scores = jnp.where(_mask(q_pos, latent.shape[1]), scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(c.dtype)
    o_lat = jnp.einsum("bhst,btr->bshr", probs, c)
    return jnp.einsum("bshr,rhd->bshd", o_lat, w_v)


def rows_in_blocks(lengths, window: int, block: int):
    """Rows of each row's first ``window`` that lie in the whole blocks
    of ``block`` (as :func:`attend_blocks` cuts them) up to its length:
    (b,) int32."""
    block = math.gcd(window, block)
    n = (lengths.astype(jnp.int32) + block - 1) // block
    return jnp.minimum(n, window // block) * block


def _walk_blocks(score_and_weigh, latent, q_pos, lengths, *, heads, width, block, slot, window):
    """An online softmax over the whole blocks of ``block`` latent rows
    up to the rows' ``lengths``.  ``score_and_weigh(rows)`` gives a
    block's scaled scores (b, H, s, block) float32 and the function that
    weighs its values by probabilities of that shape -> (b, H, s, width)
    float32.  With ``slot`` (1,) the one row of the batch is row ``slot``
    of ``latent`` and its blocks are read from there; ``window`` (absent:
    all of them) bounds the rows seen.  Returns (b, H, s, width) float32:
    zeros, not 0 / 0, for a row that holds nothing and reads no block."""
    b, s = q_pos.shape
    T = min(window or latent.shape[1], latent.shape[1])
    if slot is not None and b != 1:
        raise ValueError("a slot names the state row of a batch of one")
    first = jnp.zeros((), jnp.int32) if slot is None else slot[0].astype(jnp.int32)
    block = math.gcd(T, block)
    n_blocks = jnp.max(rows_in_blocks(lengths, T, block)) // block

    def fold(j, carry):
        m, l, acc = carry
        rows = jax.lax.dynamic_slice(
            latent, (first, j * block, 0), (b, block, latent.shape[2])
        )
        scores, weigh = score_and_weigh(rows)
        key_pos = j * block + jnp.arange(block, dtype=jnp.int32)
        seen = key_pos[None, None, None, :] <= q_pos[:, None, :, None]
        scores = jnp.where(seen, scores, -1e30)
        m_new = jnp.maximum(m, scores.max(-1))
        p = jnp.exp(scores - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        return m_new, l * alpha + p.sum(-1), acc * alpha[..., None] + weigh(p)

    init = (
        jnp.full((b, heads, s), -1e30, F32),
        jnp.zeros((b, heads, s), F32),
        jnp.zeros((b, heads, s, width), F32),
    )
    _, l, acc = jax.lax.fori_loop(0, n_blocks, fold, init)
    return acc / jnp.where(l > 0, l, 1.0)[..., None]


@jax.named_scope("layer/mla/attn")
def attend_blocks(
    q_nope, q_rope, latent, w_kvb, q_pos, lengths, *, rank, nope, v_dim, block, scale=None,
    slot=None, window=None,
):
    """:func:`attend_expanded` a block of ``block`` latent rows at a time
    (the window's length where it divides by no more), with an online
    softmax: a block is expanded through ``W_kvb``, scored against every
    query and folded into the running maximum, sum and weighted values,
    so nothing of the window's size but the latent rows themselves is
    alive.  ``lengths`` (b,) is how many rows each row of the batch holds
    once this call's tokens are written; the blocks wholly past the
    longest are not read.  With ``slot`` (1,) the one row of the batch is
    row ``slot`` of ``latent``, a state of many slots, and its blocks are
    read from there: no copy of the row's window is made.  ``window``
    (absent: all of them) bounds the rows a call may see.  Arguments and
    result otherwise as :func:`attend_expanded`."""
    b, H, rope = q_nope.shape[0], q_nope.shape[2], q_rope.shape[-1]
    if scale is None:
        scale = (nope + rope) ** -0.5
    # One contraction over nope + rope: a head's key is its own nope part
    # beside the one rope key of all heads.
    q = jnp.concatenate([q_nope, q_rope], axis=-1)

    def score_and_weigh(rows):
        n = rows.shape[1]
        kv = jnp.dot(rows[..., :rank], w_kvb).reshape(b, n, H, nope + v_dim)
        k_rope = jnp.broadcast_to(rows[:, :, None, rank : rank + rope], (b, n, H, rope))
        k = jnp.concatenate([kv[..., :nope], k_rope.astype(kv.dtype)], axis=-1)
        scores = jnp.einsum("bshd,bthd->bhst", q, k, preferred_element_type=F32) * scale
        v = kv[..., nope:]
        return scores, lambda p: jnp.einsum(
            "bhst,bthd->bhsd", p.astype(v.dtype), v, preferred_element_type=F32
        )

    out = _walk_blocks(
        score_and_weigh, latent, q_pos, lengths, heads=H, width=v_dim, block=block,
        slot=slot, window=window,
    )
    return jnp.transpose(out.astype(q_nope.dtype), (0, 2, 1, 3))


@jax.named_scope("layer/mla/attn")
def attend_absorbed_blocks(
    q_nope, q_rope, latent, w_kvb, q_pos, lengths, *, rank, nope, v_dim, block, scale=None,
    slot=None, window=None,
):
    """:func:`attend_absorbed` a block of ``block`` latent rows at a time
    with an online softmax, over the whole blocks up to the rows'
    ``lengths`` and no further: a decode step that reads what a slot
    holds, not what it could hold.  A row is never cut into its latent
    and its rope key (a slice of the minor axis is a copy of the block):
    the folded query and the rope query stand side by side against the
    whole row, which may hold zero columns after the rope key, and the
    weighted sum runs over the whole row too.  ``slot``, ``window`` and
    the other arguments as :func:`attend_blocks`."""
    H, width = q_nope.shape[2], latent.shape[2]
    if scale is None:
        scale = (nope + q_rope.shape[-1]) ** -0.5
    w = w_kvb.reshape(rank, H, nope + v_dim)
    q_lat = jnp.einsum("bshd,rhd->bshr", q_nope, w[..., :nope])
    spare = jnp.zeros(q_lat.shape[:-1] + (width - rank - q_rope.shape[-1],), q_lat.dtype)
    q = jnp.concatenate([q_lat, q_rope.astype(q_lat.dtype), spare], axis=-1)

    def score_and_weigh(rows):
        scores = jnp.einsum("bshr,btr->bhst", q, rows, preferred_element_type=F32) * scale
        return scores, lambda p: jnp.einsum(
            "bhst,btr->bhsr", p.astype(rows.dtype), rows, preferred_element_type=F32
        )

    o_row = _walk_blocks(
        score_and_weigh, latent, q_pos, lengths, heads=H, width=width, block=block,
        slot=slot, window=window,
    ).astype(q_nope.dtype)
    return jnp.einsum("bhsr,rhd->bshd", o_row[..., :rank], w[..., nope:])
