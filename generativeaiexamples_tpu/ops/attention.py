"""Grouped-query attention with explicit validity masking.

TPU-native replacement for the paged-KV attention inside TensorRT-LLM
(reference consumes it via the NIM container, SURVEY.md §2.8).  This module
is the reference XLA implementation; the Pallas flash-attention kernel with
identical semantics lives in ``ops.flash_attention`` and is selected by
:func:`attention` when profitable (TPU backend, prefill-sized query blocks,
MXU-aligned head dim).

Masking convention: key slot ``t`` is visible to the query at absolute
position ``p`` iff ``t <= p`` (causality over identity-mapped cache slots)
and ``t < kv_length[b]`` (slots beyond the valid prefix — padding garbage —
are never attended).
"""

from __future__ import annotations

from typing import Optional

import jax.numpy as jnp

from generativeaiexamples_tpu.ops.dispatch import record

_NEG_INF = -1e30


def gqa_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    q_positions: jnp.ndarray,
    kv_lengths: Optional[jnp.ndarray] = None,
    *,
    k_scale: Optional[jnp.ndarray] = None,
    v_scale: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """Grouped-query attention over an identity-positioned key/value buffer.

    Args:
      q: (b, s, n_q_heads, head_dim)
      k: (b, t, n_kv_heads, head_dim) — slot i holds the key for position i.
      v: (b, t, n_kv_heads, head_dim)
      q_positions: (b, s) absolute position of each query token.
      kv_lengths: (b,) number of valid kv slots; None = all t slots valid.
      k_scale, v_scale: (b, t, n_kv) dequantization scales for int8 k/v
        (``LlamaConfig.kv_dtype="int8"``).  The int8 tensors convert to
        q's dtype inside the dot (HBM streams int8 bytes only) and scales
        fold into scores / softmax weights, never into a dequantized copy
        of the cache.

    Returns:
      (b, s, n_q_heads, head_dim), dtype of q.
    """
    b, s, n_q, head_dim = q.shape
    t = k.shape[1]
    n_kv = k.shape[2]
    group = n_q // n_kv
    scale = head_dim ** -0.5

    qg = q.reshape(b, s, n_kv, group, head_dim)
    # (b, n_kv, group, s, t).  Operands stay in storage dtype (bf16/int8)
    # with f32 MXU accumulation — an explicit astype would materialize a
    # wider copy of the whole KV cache in HBM every layer, multiplying
    # decode-step memory traffic.
    scores = jnp.einsum(
        "bsngh,btnh->bngst",
        qg,
        k.astype(q.dtype) if k.dtype == jnp.int8 else k,
        preferred_element_type=jnp.float32,
    ) * scale
    if k_scale is not None:
        # (b, t, n_kv) -> (b, n_kv, 1, 1, t)
        scores = scores * jnp.transpose(k_scale, (0, 2, 1))[:, :, None, None, :]

    t_idx = jnp.arange(t, dtype=jnp.int32)
    causal = t_idx[None, None, :] <= q_positions[..., None]  # (b, s, t)
    if kv_lengths is not None:
        valid = t_idx[None, :] < kv_lengths[:, None]  # (b, t)
        causal = causal & valid[:, None, :]
    mask = causal[:, None, None, :, :]  # (b, 1, 1, s, t)

    scores = jnp.where(mask, scores, _NEG_INF)
    weights = jnp.exp(scores - scores.max(axis=-1, keepdims=True))
    weights = weights * mask
    denom = weights.sum(axis=-1, keepdims=True)
    weights = weights / jnp.maximum(denom, 1e-30)

    if v_scale is not None:
        # Fold v's dequant scale into the (tiny) softmax weights instead of
        # dequantizing the (huge) v buffer.
        weights = weights * jnp.transpose(v_scale, (0, 2, 1))[:, :, None, None, :]
    out_dtype = q.dtype
    out = jnp.einsum(
        "bngst,btnh->bsngh",
        weights.astype(out_dtype),
        v.astype(out_dtype) if v.dtype == jnp.int8 else v,
        preferred_element_type=jnp.float32,
    )
    return out.reshape(b, s, n_q, head_dim).astype(q.dtype)


def attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    q_positions: jnp.ndarray,
    kv_lengths: Optional[jnp.ndarray] = None,
    *,
    mesh=None,
    k_scale: Optional[jnp.ndarray] = None,
    v_scale: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """Backend-dispatching attention with the gqa_attention contract."""
    from generativeaiexamples_tpu.ops import flash_attention as fa

    if k_scale is not None or v_scale is not None:
        # int8 KV (decode, s == 1): the XLA path folds scales into scores
        # and weights; the flash/ring kernels are prefill-shaped and never
        # see quantized caches.
        return gqa_attention(
            q, k, v, q_positions, kv_lengths, k_scale=k_scale, v_scale=v_scale
        )

    # Long-context path: a mesh with a populated ``seq`` axis shards
    # self-attention (cacheless, q-len == kv-len) across devices via ring
    # attention — context length then scales with the seq axis (SURVEY.md
    # §5.7: the reference has no equivalent; TRT-LLM caps at one GPU's KV).
    if (
        mesh is not None
        and getattr(mesh, "shape", {}).get("seq", 1) > 1
        and q.shape[1] == k.shape[1]
        and q.shape[1] % mesh.shape["seq"] == 0
        and q.shape[1] > 1
    ):
        from generativeaiexamples_tpu.parallel.ring_attention import (
            sequence_parallel_attention,
        )

        return sequence_parallel_attention(
            q, k, v, q_positions, kv_lengths, mesh=mesh, strategy="ring"
        )
    if record(
        f"prefill_attention b={q.shape[0]} s={q.shape[1]} t={k.shape[1]}",
        fa.use_flash(q.shape[1], q.shape[3], mesh=mesh),
    ):
        return fa.flash_gqa_attention(q, k, v, q_positions, kv_lengths)
    return gqa_attention(q, k, v, q_positions, kv_lengths)
