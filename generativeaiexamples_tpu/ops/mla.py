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

* :func:`index_scores`, :func:`index_scores_blocks`, :func:`select_mask`,
  :func:`select_rows` — DeepSeek-V3.2's lightning indexer: every earlier
  position scored by a few small heads against one cached index key a
  token, and the ``k`` highest kept (a tie to the lower position);
  :func:`attend_blocks` then takes the kept (query, row) pairs as
  ``allowed`` (a prefill chunk: every block up to the row's length is
  still expanded and scored, the softmax keeps the selected pairs), and
  :func:`attend_selected` gathers the kept rows and attends over them
  alone in the absorbed form (a decode step, and a verify step's two
  positions, each over the rows it selects for itself);
* :func:`attend_latent_ring` — a window layer of latent rows: the ring
  as it was beside the call's own rows in one softmax
  (``ops/gqa.py::attend_ring``'s rule), expanded for a chunk, absorbed
  for a decode step.

All are plain XLA and give the same numbers up to rounding:
``tests/test_hybrid_ops.py`` holds one against the other.  A prefill
chunk's walk (:func:`attend_blocks` as ``models/hybrid.py``'s ``mla``
mixer calls it, with ``allowed`` or without) runs in
``ops/mla_chunk.py``'s Pallas kernel where its gate admits the call (bf16
rows of whole lane tiles on one TPU device); :func:`attend_blocks` stays
its XLA twin, the tests' oracle (``tests/test_latent_chunk.py``) and what
serves everything the gate refuses: float32 state (the rehearsals, the
reference check's own forms), the CPU, several devices, a decode step and
its draft (two queries a row).  A decode step's walk
(:func:`attend_absorbed_blocks`) runs in ``ops/mla_decode.py``'s kernel
under the same conditions, every row of the step in one call, and stays
that kernel's twin and oracle (``tests/test_latent_decode.py``).

The rotation is over adjacent pairs (:func:`rope_interleaved`), with the
plain frequencies of a ``theta`` or those of a ``RopeSpec``
(``ops/rope.py``: YaRN).  :func:`position_scale` is the query scale that
grows with the position (``llama_4_scaling_beta``).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from generativeaiexamples_tpu.ops.gqa import _STEP_QUERIES, ring_held
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


def _walk_blocks(
    score_and_weigh, latent, q_pos, lengths, *, heads, width, block, slot, window, allowed=None,
):
    """An online softmax over the whole blocks of ``block`` latent rows
    up to the rows' ``lengths``.  ``score_and_weigh(rows)`` gives a
    block's scaled scores (b, H, s, block) float32 and the function that
    weighs its values by probabilities of that shape -> (b, H, s, width)
    float32.  With ``slot`` (1,) the one row of the batch is row ``slot``
    of ``latent`` and its blocks are read from there; ``window`` (absent:
    all of them) bounds the rows seen; ``allowed`` (b, s, T) bool, where
    given, the (query, row) pairs the softmax keeps of those the causal
    mask lets through.  Returns (b, H, s, width) float32:
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
        if allowed is not None:
            kept = jax.lax.dynamic_slice(allowed, (0, 0, j * block), (b, s, block))
            seen = seen & kept[:, None]
        scores = jnp.where(seen, scores, -1e30)
        m_new = jnp.maximum(m, scores.max(-1))
        p = jnp.exp(scores - m_new[..., None])
        if allowed is not None:
            # A block of which a query keeps nothing, before any it keeps.
            p = jnp.where(seen, p, 0.0)
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
    slot=None, window=None, allowed=None,
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
    (absent: all of them) bounds the rows a call may see; ``allowed``
    (b, s, T) bool the pairs an indexer kept (:func:`select_mask`).
    Arguments and result otherwise as :func:`attend_expanded`."""
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
        slot=slot, window=window, allowed=allowed,
    )
    return jnp.transpose(out.astype(q_nope.dtype), (0, 2, 1, 3))


def whole_row_queries(q_nope, q_rope, w, nope: int, width: int):
    """The absorbed form's queries against stored rows read as they lie:
    ``W_kvb``'s key part (w (rank, H, nope + v)) folded into q_nope
    (b, s, H, nope), the rope query beside it where a row keeps its rope
    key, zeros against the row's zero columns: (b, s, H, width)."""
    q_lat = jnp.einsum("bshd,rhd->bshr", q_nope, w[..., :nope])
    spare = jnp.zeros(q_lat.shape[:-1] + (width - w.shape[0] - q_rope.shape[-1],), q_lat.dtype)
    return jnp.concatenate([q_lat, q_rope.astype(q_lat.dtype), spare], axis=-1)


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
    q = whole_row_queries(q_nope, q_rope, w, nope, width)

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


# -- the indexer: which rows a query attends --------------------------------------


def index_scores(q_i, w, keys):
    """``I[t, j] = sum_i w[t, i] relu(q_I[t, i] . k_I[j])``: q_i (b, s, HI,
    d) the index queries, w (b, s, HI) float32 their weights, keys (b, t,
    d) index keys.  Returns (b, s, t) float32, unmasked."""
    dots = jnp.einsum("bshd,btd->bsht", q_i, keys, preferred_element_type=F32)
    return jnp.einsum("bsht,bsh->bst", jax.nn.relu(dots), w.astype(F32))


@jax.named_scope("layer/mla/index")
def index_scores_blocks(q_i, w, index_k, q_pos, lengths, *, block, slot=None, window=None):
    """:func:`index_scores` against the index keys a slot holds, a block
    of ``block`` rows at a time up to the rows' ``lengths`` (the blocks
    past the longest are not read), ``-inf`` at every position a query
    does not see (a later one, or one in a block not read).  ``slot`` and
    ``window`` as :func:`attend_blocks`.  Returns (b, s, T) float32."""
    b, s = q_pos.shape
    T = min(window or index_k.shape[1], index_k.shape[1])
    if slot is not None and b != 1:
        raise ValueError("a slot names the state row of a batch of one")
    first = jnp.zeros((), jnp.int32) if slot is None else slot[0].astype(jnp.int32)
    block = math.gcd(T, block)
    n_blocks = jnp.max(rows_in_blocks(lengths, T, block)) // block

    def fold(j, out):
        keys = jax.lax.dynamic_slice(index_k, (first, j * block, 0), (b, block, index_k.shape[2]))
        return jax.lax.dynamic_update_slice(out, index_scores(q_i, w, keys), (0, 0, j * block))

    scores = jax.lax.fori_loop(0, n_blocks, fold, jnp.full((b, s, T), -jnp.inf, F32))
    seen = jnp.arange(T, dtype=jnp.int32)[None, None, :] <= q_pos[:, :, None]
    return jnp.where(seen, scores, -jnp.inf)


def _ordered_bits(x):
    """float32 -> uint32 whose unsigned order is the floats' (``-inf``
    lowest; no NaN is expected)."""
    bits = jax.lax.bitcast_convert_type(x.astype(F32), jnp.uint32)
    negative = (bits >> 31).astype(bool)
    return jnp.where(negative, ~bits, bits | jnp.uint32(0x80000000))


@jax.named_scope("layer/mla/select")
def select_mask(scores, k: int):
    """The ``k`` largest of each row of ``scores`` (..., T) float32, as a
    mask: among equal scores the lower positions first.  A position that
    is not seen carries ``-inf`` and is never kept, so a query that sees
    ``k`` positions or fewer keeps them all.  No sort: the ``k``-th
    largest value is built bit by bit (32 counts over the row), which a
    v5e does in 0.46 ms for (256, 16384) scores where ``lax.top_k`` and a
    threshold at its last value take 2.6 ms and ``top_k`` and a scatter
    4.8 (a chunk program of 8 rows: 245, 315 and 377 ms).  A decode step
    needs the positions, for its gather, and has one query a slot:
    :func:`select_rows`, the same rule."""
    T = scores.shape[-1]
    if k >= T:
        return scores > -jnp.inf
    key = _ordered_bits(scores)

    def bit(i, kth):
        cand = kth | (jnp.uint32(1) << (jnp.uint32(31) - i.astype(jnp.uint32)))
        enough = jnp.sum(key >= cand[..., None], axis=-1) >= k
        return jnp.where(enough, cand, kth)

    kth = jax.lax.fori_loop(0, 32, bit, jnp.zeros(scores.shape[:-1], jnp.uint32))[..., None]
    above, equal = key > kth, key == kth
    room = k - jnp.sum(above, axis=-1, keepdims=True)
    ties = equal & (jnp.cumsum(equal, axis=-1) <= room)
    return (above | ties) & (scores > -jnp.inf)


@jax.named_scope("layer/mla/select")
def select_rows(scores, k: int):
    """The positions of the ``k`` largest of each row of ``scores`` (n, T)
    (a tie to the lower position: ``lax.top_k``'s rule) and which of them
    are positions the query sees (not ``-inf``): ((n, k) int32, (n, k)
    bool).  ``k`` is cut to ``T``.  A decode step hands it one row a slot,
    a verify step one row a slot and position (slot-major): each position
    selects for itself."""
    vals, idx = jax.lax.top_k(scores, min(k, scores.shape[-1]))
    return idx.astype(jnp.int32), vals > -jnp.inf


@jax.named_scope("layer/mla/select")
def rows_needed(scores, idx, keep, counts):
    """How many rows the queries of each slot need between them: the size
    of the union of the sets :func:`select_rows` kept (``idx``, ``keep``
    (b, s, k) of ``scores`` (b, s, T)) for the slot's queries that count
    (``counts`` (b, s) bool).  Each set is rebuilt as a mask from its LAST
    entry (``lax.top_k`` orders by score, equal scores by position, so the
    last position kept holds the lowest kept score and, of the scores
    equal to it, the highest position kept): what lies above that score,
    and of the scores equal to it the positions up to that one.  One score
    a query is looked up; nothing is scattered, cumulated or gathered by
    the set (a gather of 65,536 scores a block took 0.81 ms of a verify
    step on a v5e: PERF.md, PR 53).  Returns (b,) int32."""
    n_kept = jnp.sum(keep, axis=-1, keepdims=True)
    last = jnp.take_along_axis(idx, jnp.maximum(n_kept - 1, 0), axis=-1)  # (b, s, 1)
    key = _ordered_bits(scores)  # ``top_k``'s order: -0.0 under 0.0
    lowest = jnp.take_along_axis(key, last, axis=-1)
    at = jnp.arange(scores.shape[-1], dtype=jnp.int32)
    kept = (n_kept > 0) & ((key > lowest) | ((key == lowest) & (at <= last)))
    return jnp.sum(jnp.any(kept & counts[..., None], axis=1), axis=-1).astype(jnp.int32)


def _attend_whole_rows(q_nope, q_rope, rows, w, mask, nope: int, scale):
    """The absorbed form over stored rows read as they lie (normed latent,
    rope key, zero columns): one product of the queries, ``W_kvb``'s key
    part folded in, with the whole rows; rows (b, t, width), w (rank, H,
    nope + v), mask broadcast to (b, H, s, t).  Returns (b, s, H, v)."""
    rank = w.shape[0]
    q = whole_row_queries(q_nope, q_rope, w, nope, rows.shape[2])
    scores = jnp.einsum("bshr,btr->bhst", q, rows, preferred_element_type=F32) * scale
    probs = jax.nn.softmax(jnp.where(mask, scores, -1e30), axis=-1).astype(rows.dtype)
    o_row = jnp.einsum("bhst,btr->bhsr", probs, rows)
    return jnp.einsum("bhsr,rhd->bshd", o_row[..., :rank], w[..., nope:])


@jax.named_scope("layer/mla/attn")
def attend_selected(q_nope, q_rope, latent, w_kvb, idx, keep, *, rank, nope, v_dim, scale=None):
    """:func:`attend_absorbed` over the rows ``idx`` (b, s, k) of each
    slot's ``latent`` (b, T, width) alone, of which ``keep`` (b, s, k)
    count: every query has its OWN rows (a verify step's second position
    keeps another set than its first), gathered whole (zero columns after
    the rope key and all) and read once for all heads.  q_nope, q_rope:
    (b, s, H, .), a decode step's one query a slot or a verify step's two.
    Returns (b, s, H, v_dim)."""
    if scale is None:
        scale = (nope + q_rope.shape[-1]) ** -0.5
    b, s, H = q_nope.shape[:3]
    k = idx.shape[-1]
    w = w_kvb.reshape(rank, H, nope + v_dim)
    rows = jnp.take_along_axis(latent[:, None], idx[..., None], axis=2)  # (b, s, k, width)
    # A query a row of the batch: each against the rows gathered for it.
    alone = lambda x: x.reshape((b * s, 1) + x.shape[2:])
    o = _attend_whole_rows(
        alone(q_nope), alone(q_rope), rows.reshape(b * s, k, -1), w,
        keep.reshape(b * s, 1, 1, k), nope, scale,
    )
    return o.reshape(b, s, H, v_dim)


def attend_latent_ring(
    q_nope, q_rope, new_rows, ring, w_kvb, q_pos, *, rank, nope, v_dim, window: int, scale=None,
):
    """A window layer of latent rows: q_nope, q_rope (b, s, H, .) at
    consecutive positions ``q_pos`` (b, s); new_rows (b, s, width) this
    call's rows as stored (normed latent, rotated rope key, zero columns);
    ring (b, R, width) the ring BEFORE the call, position ``p`` in row
    ``p % R``.  Position ``i`` sees ``j`` with ``i - window < j <= i``: of
    the ring what ``ops/gqa.py::ring_held`` says it holds, and the call's
    own rows, in one softmax.  A chunk expands keys and values through
    ``W_kvb``; a decode step (one or two queries a row) folds ``W_kvb``
    into the query and reads the rows as they lie.  Returns
    (b, s, H, v_dim)."""
    b, s, H, rope = q_rope.shape
    R = ring.shape[1]
    if scale is None:
        scale = (nope + rope) ** -0.5
    held = ring_held(q_pos[:, 0], R)[:, None, :]  # (b, 1, R)
    old = (held >= 0) & (held > q_pos[:, :, None] - window)
    steps = jnp.arange(s, dtype=jnp.int32)
    back = steps[:, None] - steps[None, :]
    new = jnp.broadcast_to((back >= 0) & (back < window), (b, s, s))
    mask = jnp.concatenate([old, new], axis=-1)[:, None]  # (b, 1, s, R + s)
    rows = jnp.concatenate([ring, new_rows.astype(ring.dtype)], axis=1)
    w = w_kvb.reshape(rank, H, nope + v_dim)
    if s <= _STEP_QUERIES:
        return _attend_whole_rows(q_nope, q_rope, rows, w, mask, nope, scale)
    n = rows.shape[1]
    kv = jnp.dot(rows[..., :rank], w_kvb).reshape(b, n, H, nope + v_dim)
    k_rope = jnp.broadcast_to(rows[:, :, None, rank : rank + rope], (b, n, H, rope))
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate([kv[..., :nope], k_rope.astype(kv.dtype)], axis=-1)
    scores = jnp.einsum("bshd,bthd->bhst", q, k, preferred_element_type=F32) * scale
    probs = jax.nn.softmax(jnp.where(mask, scores, -1e30), axis=-1)
    v = kv[..., nope:]
    return jnp.einsum("bhst,bthd->bshd", probs.astype(v.dtype), v)
