"""Compressed Convolutional Attention (arXiv:2510.04476), grouped-query
form: what lies between a layer's projections and its attention
(``models/hybrid.py``'s ``cca`` mixer).

The layer projects to a query latent of ``H`` heads and a key latent of
``KH`` heads (``u``: the two side by side, ``H + KH`` heads of ``d``),
mixes ``u`` over the sequence by two causal convolutions, adds the mean
of the un-mixed q and k back to both, norms each head to a fixed length
and attends inside the latent.  Half of its value heads are the previous
token's.

* step one of the convolution is depthwise, one filter a channel:
  ``ops.kda.causal_conv``, with ``ops.kda.next_tail`` for its tail (the
  same operation as KDA's q/k/v convolution);
* :func:`head_conv` is step two: it mixes the ``d`` channels of a head,
  a head at a time, over the last ``W`` positions;
* :func:`add_qk_mean` is the mean of the pre-convolution q and k;
* the length norm is ``ops.kda.l2_normalize`` times ``sqrt(d)``;
* :func:`shift` gives a sequence as of the token before, continued from
  a tail of one (the value shift).

Everything here is continued from a tail, so a chunk of a prompt and a
decode step take their history from the slot's state; the tails move
only past tokens that count (``next_tail``).
"""

from __future__ import annotations

import jax.numpy as jnp

F32 = jnp.float32


def head_conv(x, tail, weight, bias):
    """Causal convolution over the sequence that mixes the channels of
    each head, continued from ``tail``.

    x: (b, s, J * d) the new inputs, ``J`` heads of ``d`` side by side;
    tail: (b, W-1, J * d) the inputs before them; weight: (W, J, d, d),
    ``weight[-1]`` multiplying the current input; bias: (J * d,).
    Returns (y (b, s, J, d) float32, xin (b, s+W-1, J * d)): ``xin`` is
    what the caller cuts the next tail from."""
    w, heads, d, _ = weight.shape
    b, s, _ = x.shape
    xin = jnp.concatenate([tail.astype(x.dtype), x], axis=1)
    by_head = xin.reshape(b, s + w - 1, heads, d)
    y = sum(
        jnp.einsum(
            "btjd,jde->btje", by_head[:, j : j + s], weight[j], preferred_element_type=F32
        )
        for j in range(w)
    )
    return y + bias.astype(F32).reshape(heads, d), xin


def add_qk_mean(z, u, n_q: int):
    """The mean of the pre-convolution q and k added back to both.

    z: (b, s, J, d) float32 the convolution's output, ``n_q`` query heads
    and then the key heads; u: (b, s, J * d) its input.  Query head ``i``
    belongs to key head ``i // (n_q / n_kv)``: ``m[i] = (qp[i] + kp[g(i)])
    / 2`` goes to ``q[i]``, and the mean of its group's ``m`` to ``k[g]``.
    Returns (q (b, s, n_q, d), k (b, s, n_kv, d)) float32."""
    b, s, heads, d = z.shape
    n_kv = heads - n_q
    group = n_q // n_kv
    pre = u.reshape(b, s, heads, d).astype(F32)
    qp = pre[:, :, :n_q].reshape(b, s, n_kv, group, d)
    m = (qp + pre[:, :, n_q:, None]) / 2.0
    return z[:, :, :n_q] + m.reshape(b, s, n_q, d), z[:, :, n_q:] + m.mean(axis=3)


def shift(x, tail):
    """x: (b, s, C) as of the token before: ``tail`` (b, 1, C), then
    ``x[:, :-1]``.  Returns (shifted (b, s, C), xin (b, s+1, C)) — as the
    convolutions, ``xin`` is what the next tail is cut from."""
    xin = jnp.concatenate([tail.astype(x.dtype), x], axis=1)
    return xin[:, :-1], xin
