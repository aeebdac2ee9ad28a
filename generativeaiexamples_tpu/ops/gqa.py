"""Grouped-query attention over a slot's own K/V state, in two kinds
(``models/hybrid.py``'s ``full`` and ``window`` mixers).  Plain XLA
here; on the chip a full layer's decode step is ``ops/gqa_decode.py``'s
row walk, its prefill chunk that file's chunk kernel and a window
layer's prefill chunk its ring kernel; :func:`attend_rows` and
:func:`attend_ring` are their twins: what the gates refuse (float32
state, several devices, the CPU) and the tests' oracle.  A window layer's
decode step is :func:`attend_ring` on every platform.

* :func:`attend_rows` — a global layer: the state holds one row a
  position, written before it is read; a query at position ``i`` sees
  rows ``j <= i`` of the first ``T`` given.
* :func:`attend_ring` — a window layer: the state is a ring of ``R``
  rows (position ``p`` lives in row ``p % R``), whatever the length.  A
  call attends over the ring *as it was before the call* and over the
  call's own new rows, in one softmax, and writes afterwards
  (:func:`ring_slots`): so no step copies the ring, a call may be longer
  than the ring (a whole prompt at once), and ``R`` is the window with
  no slack.  Which position a ring row holds is worked out from the
  row's start alone: ``held(r) = e - ((e - r) mod R)`` for ``e`` the last
  position written, and a row that holds none of this sequence's
  positions (``held < 0``: another occupant's leftovers) is masked.

Keys are rotated before they are stored, so attention does not care in
what order the ring holds them.

A state row is ``(KH * D,)``: the KV heads of one position side by side,
so that a row is whole lane tiles and a token's write is one row of a
``(b, T, KH * D)`` buffer (the latent rows' layout).  Head-major
``(b, KH, T, D)`` buffers made the decode step's scatter ask for another
layout than its attention: the v5e compiler then copied every full
layer's K and V whole, once a step (its compile of 32 slots x 8,192:
12 copies of 268 MB in the loop's body).  Attention reads a KV head as a
lane-aligned slice of the rows.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32
_NEG = -1e30
# Queries a row up to which a call is a decode step (one token, or a
# token and its draft) and reads the state rows as they lie (``_qk``).
_STEP_QUERIES = 2


def _grouped(q, n_kv: int):
    b, s, h, d = q.shape
    return q.reshape(b, s, n_kv, h // n_kv, d)


def _head(rows, n: int, d: int):
    """KV head ``n`` of state rows (b, T, KH * D): (b, T, D)."""
    return rows[:, :, n * d : (n + 1) * d]


def _qk(q, keys, n_kv: int):
    """(b, H, s, t) float32 scores of q (b, s, H, D) against state rows
    ``keys`` (b, t, KH * D).

    Two forms.  A run of queries (a prefill chunk, one slot) takes each
    KV head's lanes of the rows and multiplies its own query heads with
    them.  A decode step (s == 1, every slot) would have each of those
    slices copied out of the rows first (the v5e compiler materialises
    them: 24 copies of 67 MB a step at 32 slots x 8,192; a step that
    verifies a draft has two queries a row and is the same case), so it
    widens the
    queries instead: a query head's values sit in its KV head's lanes of a
    ``KH * D`` vector, zeros elsewhere, and one product reads the rows
    once as they lie.  KH times the multiplications, which a decode step
    does not notice; the same numbers."""
    b, s, h, d = q.shape
    qg = _grouped(q, n_kv)
    if s <= _STEP_QUERIES:
        eye = jnp.eye(n_kv, dtype=q.dtype)
        wide = (qg[:, :, :, :, None, :] * eye[None, None, :, None, :, None]).reshape(
            b, s, h, n_kv * d
        )
        return jnp.einsum("bshc,btc->bhst", wide, keys, preferred_element_type=F32) * d**-0.5
    return jnp.concatenate(
        [jnp.einsum("bsgd,btd->bgst", qg[:, :, n], _head(keys, n, d), preferred_element_type=F32)
         for n in range(n_kv)], axis=1,
    ) * d**-0.5


def _pv(probs, values, n_kv: int):
    """probs (b, H, s, t) weights over state rows ``values`` (b, t,
    KH * D) -> (b, s, H, D); the two forms of :func:`_qk`."""
    b, h, s, t = probs.shape
    d, g = values.shape[-1] // n_kv, h // n_kv
    probs = probs.astype(values.dtype)
    if s <= _STEP_QUERIES:
        wide = jnp.einsum("bhst,btc->bshc", probs, values).reshape(b, s, n_kv, g, n_kv, d)
        own = jnp.eye(n_kv, dtype=wide.dtype)  # a head keeps its KV head's lanes
        return jnp.einsum("bsngmd,nm->bsngd", wide, own).reshape(b, s, h, d)
    heads = probs.reshape(b, n_kv, g, s, t)
    return jnp.stack(
        [jnp.einsum("bgst,btd->bsgd", heads[:, n], _head(values, n, d)) for n in range(n_kv)],
        axis=2,
    ).reshape(b, s, h, d)


def attend_rows(q, k_rows, v_rows, q_pos, *, n_kv: int):
    """q: (b, s, H, D) rotated; k_rows, v_rows: (b, T, KH * D), row ``t``
    the keys of position ``t``; q_pos: (b, s).  Returns (b, s, H, D)."""
    mask = jnp.arange(k_rows.shape[1], dtype=jnp.int32)[None, None, :] <= q_pos[:, :, None]
    scores = jnp.where(mask[:, None], _qk(q, k_rows, n_kv), _NEG)
    return _pv(jax.nn.softmax(scores, axis=-1), v_rows, n_kv)


def ring_held(start, ring: int):
    """(b, R): the position each ring row holds when the last position
    written is ``start - 1`` (``start`` (b,)); negative where it holds none."""
    last = start.astype(jnp.int32)[:, None] - 1
    rows = jnp.arange(ring, dtype=jnp.int32)[None, :]
    return last - jnp.mod(last - rows, ring)


def ring_slots(pos, valid, n_valid, ring: int):
    """(b, s) ring row each new position is written to: ``pos % R`` for a
    token that counts and is among the call's last ``R`` that do, else
    ``R`` (out of range: dropped by the scatter)."""
    last = (pos[:, 0] + n_valid)[:, None] - 1
    keep = valid & (pos > last - ring)
    return jnp.where(keep, jnp.mod(pos, ring), ring)


def attend_ring(q, k_new, v_new, ring_k, ring_v, q_pos, *, n_kv: int, window: int):
    """q: (b, s, H, D) rotated, at consecutive positions ``q_pos`` (b, s);
    k_new, v_new: (b, s, KH * D) this call's rows; ring_k, ring_v:
    (b, R, KH * D) the ring before the call.  Position ``i`` sees ``j``
    with ``i - window < j <= i``.  Returns (b, s, H, D).

    Every decode step (``s <= _STEP_QUERIES``) and the prefill calls that
    ``gqa_decode.use_ring_chunk`` refuses; a chunk it admits is
    ``gqa_decode.attend_ring_chunk``, the same numbers with no scores in
    HBM."""
    s, ring = q.shape[1], ring_k.shape[1]
    held = ring_held(q_pos[:, 0], ring)[:, None, :]  # (b, 1, R)
    old_mask = (held >= 0) & (held > q_pos[:, :, None] - window)
    steps = jnp.arange(s, dtype=jnp.int32)
    back = steps[:, None] - steps[None, :]  # query index - key index
    new_mask = (back >= 0) & (back < window)
    scores = jnp.concatenate(
        [jnp.where(old_mask[:, None], _qk(q, ring_k, n_kv), _NEG),
         jnp.where(new_mask[None, None], _qk(q, k_new, n_kv), _NEG)], axis=-1,
    )
    probs = jax.nn.softmax(scores, axis=-1)
    return _pv(probs[..., :ring], ring_v, n_kv) + _pv(probs[..., ring:], v_new, n_kv)
