"""Pallas TPU flash attention for grouped-query decoding/prefill.

TPU-native replacement for the fused attention kernels inside TensorRT-LLM
(consumed by the reference via the NIM container,
``deploy/compose/docker-compose-nim-ms.yaml:2-22``; SURVEY.md §2.8).

Semantics are identical to :func:`ops.attention.gqa_attention`: key slot
``t`` is visible to the query at absolute position ``p`` iff ``t <= p`` and
``t < kv_length[b]``; rows with no visible keys produce zeros.

Kernel design (online-softmax flash attention):

* Grid ``(batch, q_heads, q_blocks, kv_blocks)`` — the kv axis is innermost
  so the running max/sum/accumulator live in VMEM scratch across kv steps
  and the output block is written once on the last kv step.
* GQA is expressed in the ``k``/``v`` index maps (``head // group``), so no
  materialised head-broadcast of the cache ever leaves HBM.
* Scores/accumulation in f32 on the MXU (``preferred_element_type``);
  inputs stay in their storage dtype (bf16) until the dot.
* Causal + validity masking is applied as a multiplicative mask on the
  exp-weights (not just additive -inf), which keeps fully-masked rows
  exactly zero — matching the XLA reference implementation bit-for-bit in
  its handling of padded rows.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from generativeaiexamples_tpu.ops.dispatch import one_device, platform_of

_NEG_INF = -1e30


def _flash_kernel(
    # scalar-prefetch free inputs (regular refs)
    q_pos_ref,  # (1, 1, block_q) int32
    kv_len_ref,  # (1, 1, 1) int32
    q_ref,  # (1, 1, block_q, head_dim)
    k_ref,  # (1, 1, block_k, head_dim)
    v_ref,  # (1, 1, block_k, head_dim)
    out_ref,  # (1, 1, block_q, head_dim)
    # scratch
    m_ref,  # (block_q, 128) f32 running max
    l_ref,  # (block_q, 128) f32 running sum
    acc_ref,  # (block_q, head_dim) f32 accumulator
    *,
    block_q: int,
    block_k: int,
    scale: float,
):
    kv_i = pl.program_id(3)
    n_kv = pl.num_programs(3)

    @pl.when(kv_i == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    q_pos = jnp.transpose(q_pos_ref[0])  # (1, block_q) -> (block_q, 1)
    kv_len = kv_len_ref[0, 0, 0]

    # Causal block skipping: a kv block whose first slot is beyond both the
    # largest query position in this q block and the valid kv prefix
    # contributes nothing — skip its MXU work entirely (~2x flops saved on
    # identity-position prefill, where half the blocks are fully future).
    block_max_pos = jnp.max(q_pos)
    kv_start = kv_i * block_k
    active = (kv_start <= block_max_pos) & (kv_start < kv_len)

    @pl.when(active)
    def _update():
        q = q_ref[0, 0]  # (block_q, head_dim)
        k = k_ref[0, 0]  # (block_k, head_dim)
        v = v_ref[0, 0]

        # (block_q, block_k) scores on the MXU, f32 accumulation.
        s = jax.lax.dot_general(
            q,
            k,
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        s = s * scale

        t_idx = (
            jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
            + kv_start
        )
        mask = (t_idx <= q_pos) & (t_idx < kv_len)
        s = jnp.where(mask, s, _NEG_INF)

        m_prev = m_ref[:, :1]  # (block_q, 1)
        l_prev = l_ref[:, :1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)

        p = jnp.exp(s - m_new) * mask  # multiplicative mask: masked rows -> 0
        l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)

        acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
            p.astype(v.dtype),
            v,
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(kv_i == n_kv - 1)
    def _finalize():
        denom = jnp.maximum(l_ref[:, :1], 1e-30)
        out_ref[0, 0] = (acc_ref[:] / denom).astype(out_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("block_q", "block_k", "interpret")
)
def flash_gqa_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    q_positions: jnp.ndarray,
    kv_lengths: Optional[jnp.ndarray] = None,
    *,
    block_q: int = 128,
    block_k: int = 256,
    interpret: bool = False,
) -> jnp.ndarray:
    """Flash-attention with the gqa_attention contract.

    Args:
      q: (b, s, n_q_heads, head_dim)
      k: (b, t, n_kv_heads, head_dim) — slot i holds position i's key.
      v: (b, t, n_kv_heads, head_dim)
      q_positions: (b, s) absolute position per query token.
      kv_lengths: (b,) valid kv prefix length; None = all t slots valid.

    Returns:
      (b, s, n_q_heads, head_dim) in q's dtype.
    """
    b, s, n_q, head_dim = q.shape
    t = k.shape[1]
    n_kv = k.shape[2]
    group = n_q // n_kv
    scale = head_dim**-0.5

    if kv_lengths is None:
        kv_lengths = jnp.full((b,), t, dtype=jnp.int32)

    # Head-major layout so each grid step reads one contiguous (s, d) tile.
    qh = jnp.transpose(q, (0, 2, 1, 3))  # (b, n_q, s, d)
    kh = jnp.transpose(k, (0, 2, 1, 3))  # (b, n_kv, t, d)
    vh = jnp.transpose(v, (0, 2, 1, 3))

    s_pad = pl.cdiv(s, block_q) * block_q
    t_pad = pl.cdiv(t, block_k) * block_k
    if s_pad != s:
        qh = jnp.pad(qh, ((0, 0), (0, 0), (0, s_pad - s), (0, 0)))
        # Padded query rows get position -1: no key satisfies t <= -1, so
        # they come out exactly zero.
        q_positions = jnp.pad(
            q_positions, ((0, 0), (0, s_pad - s)), constant_values=-1
        )
    if t_pad != t:
        kh = jnp.pad(kh, ((0, 0), (0, 0), (0, t_pad - t), (0, 0)))
        vh = jnp.pad(vh, ((0, 0), (0, 0), (0, t_pad - t), (0, 0)))
        # kv_lengths <= t already masks the padded tail.

    grid = (b, n_q, s_pad // block_q, t_pad // block_k)

    out = pl.pallas_call(
        functools.partial(
            _flash_kernel, block_q=block_q, block_k=block_k, scale=scale
        ),
        grid=grid,
        in_specs=[
            # (b, 1, s_pad) layout: the trailing two block dims (1, block_q)
            # satisfy the Mosaic tiling rule (second-to-last equals the
            # array dim; last is a multiple of 128).
            pl.BlockSpec(
                (1, 1, block_q),
                lambda bi, hi, qi, ki: (bi, 0, qi),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(
                (1, 1, 1),
                lambda bi, hi, qi, ki: (bi, 0, 0),
                memory_space=pltpu.SMEM,
            ),
            pl.BlockSpec(
                (1, 1, block_q, head_dim),
                lambda bi, hi, qi, ki: (bi, hi, qi, 0),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(
                (1, 1, block_k, head_dim),
                lambda bi, hi, qi, ki, g=group: (bi, hi // g, ki, 0),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(
                (1, 1, block_k, head_dim),
                lambda bi, hi, qi, ki, g=group: (bi, hi // g, ki, 0),
                memory_space=pltpu.VMEM,
            ),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, block_q, head_dim),
            lambda bi, hi, qi, ki: (bi, hi, qi, 0),
            memory_space=pltpu.VMEM,
        ),
        out_shape=jax.ShapeDtypeStruct((b, n_q, s_pad, head_dim), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, head_dim), jnp.float32),
        ],
        cost_estimate=pl.CostEstimate(
            # Causal skipping drops ~half the score/accumulate work.
            flops=2 * b * n_q * s_pad * t_pad * head_dim,
            bytes_accessed=(
                qh.size + kh.size * group + vh.size * group
                + b * n_q * s_pad * head_dim
            )
            * q.dtype.itemsize,
            transcendentals=b * n_q * s_pad * t_pad // 2,
        ),
        interpret=interpret,
        name="flash_gqa_attention",
    )(
        q_positions.astype(jnp.int32).reshape(b, 1, s_pad),
        kv_lengths.astype(jnp.int32).reshape(b, 1, 1),
        qh,
        kh,
        vh,
    )

    out = jnp.transpose(out, (0, 2, 1, 3))  # (b, s_pad, n_q, d)
    return out[:, :s]


def use_flash(
    s: int,
    head_dim: int,
    backend: Optional[str] = None,
    mesh=None,
) -> bool:
    """Dispatch predicate.

    Flash pays off for prefill-sized query blocks on TPU with MXU-aligned
    head dims; decode (s==1) and tiny test geometries stay on the XLA path.
    Multi-device meshes also stay on XLA for now: the pallas_call has no
    GSPMD partitioning rule, so inside a sharded jit it would force a
    gather/replicate of the KV cache (a shard_map wrapping is the planned
    path to sharded flash).
    """
    backend = backend or platform_of(mesh)
    if not one_device(mesh):
        return False
    # s >= 256: at s == 128 the (batch, heads, 1, 1) grid degenerates to
    # thousands of tiny programs and per-program dispatch overhead dominates
    # (profiled at 7.5 ms/layer for b=192 s=128 vs ~2.5 ms on the XLA path,
    # where the materialized score tensor is still cheap at this size).
    return backend == "tpu" and s >= 256 and head_dim % 128 == 0
