"""W8A8 Pallas quantized matmul: int8 weight streaming on the MXU.

The decode step's weight matmuls are most of a serving step.  This
kernel was written on the theory that XLA's s8-operand emitter read the
int8 weights at about half the HBM stream; the ledger has since put
XLA's stream at 97 % of the v5e's 819 GB/s (PERF.md section 5), the
kernel has never been timed in a cell, and ROADMAP.md Design 2 decides
whether it stays.  A manual-DMA Pallas kernel with a native int8×int8
MXU dot:

* **Per-token dynamic activation quantization** (symmetric int8,
  ``quantize_activations``) happens in plain jnp OUTSIDE the kernel so
  the Pallas path and its XLA reference twin consume bit-identical
  operands.
* **Pre-blocked weights**: ``block_matrix`` re-tiles a
  :class:`~generativeaiexamples_tpu.ops.quant.QuantizedMatrix` ONCE at
  load into contiguous ``(NB, K, BN)`` int8 tiles (plus ``(NB, 1, BN)``
  f32 scales), so the kernel's double-buffered ``make_async_copy``
  streams each tile with a single dense DMA — no strided descriptor
  per column block, no per-step re-tiling (``BLOCK_EVENTS`` counts
  blocking events so tests can assert tile-once loading).
* **Native s8×s8 MXU dot** accumulating int32
  (``preferred_element_type=int32``) — the weights are never converted
  to bf16 (the in-kernel s8→bf16 convert probe ran at 116–148 GB/s,
  a measured dead end), and the int32 accumulator is exact.
* **Scale folding into the narrow output**: per-output-channel weight
  scales × per-token activation scales multiply the (M, BN) int32
  block accumulator — never a wide dequantized weight buffer.

Bit-exactness contract: :func:`q_matmul` computes the SAME arithmetic
through the Pallas kernel and through the XLA twin (`_qmm_xla`): both
consume the same quantized activations and blocked tiles, accumulate
exactly in int32, and fold scales with the same elementwise f32
expression ``(acc.astype(f32) * a_scale) * w_scale``.  Greedy decode
through the serving scheduler is therefore bit-identical with the
kernel on or off — the property tests/test_qmm.py gates.

Dispatch mirrors ``ops.decode_attention``: the kernel runs on a TPU
device (or anywhere under ``GAIE_QMM_INTERPRET=1`` for hermetic CPU
tests), subject to a VMEM budget; everything else — prefill-sized row
counts, CPU — takes the XLA twin, which is also the reference
implementation.  ``GAIE_DISABLE_QMM_KERNEL=1``
forces the twin everywhere (the tests' A/B switch).
"""

from __future__ import annotations

import dataclasses
import functools
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from generativeaiexamples_tpu.ops.dispatch import record

# Column-block width of a weight tile.  256 keeps the double buffer at
# 2*K*BN = 2 MB for K=4096 while each DMA stays a single dense ~1 MB
# transfer (wide enough to hit stream bandwidth).  Must be a multiple
# of 128 (MXU lane width).
DEFAULT_BLOCK_N = 256

# VMEM ceiling for kernel dispatch, handed to Mosaic as the kernel's
# scoped limit: what :func:`_kernel_vmem_bytes` counts must fit or the
# compile fails with a "scoped vmem" overflow.  A v5e core has 128 MiB
# of VMEM and the compiler's default scoped limit is 16 MiB; 32 MiB
# admits all four llama3-8b projections up to M = 320 rows
# (tests/test_chip_compile.py asks the v5e compiler).
_VMEM_BUDGET_BYTES = 32 * 1024 * 1024

# Host-side blocking-event counter: every ``block_matrix`` call (one
# per projection per model load) increments it, and NOTHING on the
# per-step path does — tests assert the count is flat across decode
# chunks (no per-step re-tiling).
BLOCK_EVENTS = {"count": 0}


def _interpret_mode() -> bool:
    """Test hook: run the kernel in Pallas interpret mode on CPU so the
    fused W8A8 path is exercised hermetically (tests/conftest.py's
    virtual-device platform)."""
    return bool(os.environ.get("GAIE_QMM_INTERPRET"))


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


@dataclasses.dataclass
class BlockedQuantizedMatrix:
    """A QuantizedMatrix re-tiled for the streaming W8A8 kernel.

    ``tiles``: int8 ``(..., NB, K_pad, BN)`` — column block ``i`` of the
    (zero-padded) weight as one contiguous array slice, so the kernel's
    per-block DMA is a single dense copy.
    ``scale``: f32 ``(..., NB, 1, BN)`` — per-output-channel scales in
    the same blocked order (padding columns carry scale 0).
    ``k`` / ``n``: the ORIGINAL (unpadded) contraction / output widths;
    the leading ``...`` axes (stacked layers) ride through ``lax.scan``
    like any other pytree leaf.
    """

    tiles: jnp.ndarray
    scale: jnp.ndarray
    k: int
    n: int

    @property
    def shape(self):
        # The logical (pre-blocking) shape, so shape-based callers
        # (partition specs, validation) see the matmul geometry.
        return self.tiles.shape[:-3] + (self.k, self.n)

    @property
    def ndim(self):
        return self.tiles.ndim - 1


jax.tree_util.register_dataclass(
    BlockedQuantizedMatrix,
    data_fields=["tiles", "scale"],
    meta_fields=["k", "n"],
)


def block_matrix(qm, block_n: int | None = None) -> BlockedQuantizedMatrix:
    """Pre-block a QuantizedMatrix into ``(NB, K_pad, BN)`` int8 tiles.

    Called ONCE per projection at weight load (engine/weights.py /
    engine/decode.py): K pads to a multiple of 128 with zero rows (zero
    int8 rows contribute exact zeros to the integer dot) and N pads to
    a multiple of ``block_n`` with zero columns (scale 0, sliced off by
    :func:`q_matmul`).  Works on stacked ``(L, K, N)`` layer weights —
    the layer axis stays leading so ``lax.scan`` slices per layer.
    """
    from generativeaiexamples_tpu.ops.quant import QuantizedMatrix

    if isinstance(qm, BlockedQuantizedMatrix):  # idempotent
        return qm
    if not isinstance(qm, QuantizedMatrix):
        raise TypeError(
            f"block_matrix expects a QuantizedMatrix, got {type(qm)!r}"
        )
    bn = block_n or int(
        os.environ.get("GAIE_QMM_BN", "0")
    ) or DEFAULT_BLOCK_N
    if bn % 128:
        raise ValueError(f"block_n must be a multiple of 128, got {bn}")
    *lead, k, n = qm.q.shape
    k_pad = _round_up(k, 128)
    n_pad = _round_up(n, bn)
    nb = n_pad // bn
    pad = [(0, 0)] * len(lead) + [(0, k_pad - k), (0, n_pad - n)]
    q = jnp.pad(qm.q, pad)
    # scale is (..., 1, n): pad output channels with zeros.
    spad = [(0, 0)] * len(lead) + [(0, 0), (0, n_pad - n)]
    scale = jnp.pad(qm.scale.astype(jnp.float32), spad)
    # (..., K_pad, NB, BN) -> (..., NB, K_pad, BN): each column block
    # becomes one contiguous tile for the kernel's dense per-block DMA.
    q = q.reshape(*lead, k_pad, nb, bn)
    axes = tuple(range(len(lead))) + (
        len(lead) + 1, len(lead), len(lead) + 2,
    )
    tiles = jnp.transpose(q, axes)
    scale = jnp.transpose(
        scale.reshape(*lead, 1, nb, bn), axes
    )  # (..., NB, 1, BN)
    BLOCK_EVENTS["count"] += 1
    return BlockedQuantizedMatrix(
        tiles=tiles, scale=scale, k=int(k), n=int(n)
    )


def quantize_activations(x: jnp.ndarray):
    """Per-token (row) symmetric int8 quantization of ``(M, K)``.

    Shared verbatim by the kernel path and the XLA twin: both consume
    the int8 values + f32 scales this returns, so activation rounding
    can never diverge between them.
    """
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1, keepdims=True)
    a_scale = jnp.maximum(amax, 1e-8) / 127.0
    xq = jnp.clip(jnp.round(xf / a_scale), -127, 127).astype(jnp.int8)
    return xq, a_scale


def _fold(acc, a_scale, w_scale, out_dtype):
    """The ONE scale-folding expression both paths share.

    ``acc`` int32 → f32 (exact below 2^24, deterministically rounded
    above), × per-token activation scale, × per-channel weight scale —
    elementwise, so kernel (per (M, BN) block) and twin (full (M, NB,
    BN)) produce bit-identical values.
    """
    return ((acc.astype(jnp.float32) * a_scale) * w_scale).astype(out_dtype)


def _qmm_xla(xq, a_scale, tiles, w_scale, out_dtype):
    """XLA reference twin over the SAME blocked operands.

    A batched s8×s8→s32 contraction per column block (weights stream
    once, int8, no transpose copy), then the shared scale fold.  This
    is both the non-TPU fallback for the fused config and the oracle
    the kernel is gated bit-exact against.
    """
    nb, _, bn = tiles.shape
    acc = jax.lax.dot_general(
        xq,
        tiles,
        # Contract xq's K with tiles' K; NB stays a free (batch-like)
        # dim of the rhs: (M, K) x (NB, K, BN) -> (M, NB, BN).
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.int32,
    )
    out = _fold(acc, a_scale[:, :, None], w_scale[None, :, 0, :], out_dtype)
    return out.reshape(xq.shape[0], nb * bn)


def _qmm_kernel(xq_ref, ws_ref, as_ref, w_hbm, out_ref, wbuf, sem):
    """Double-buffered weight-streaming W8A8 matmul.

    Weights stay in HBM (``pl.ANY``); a ``fori_loop`` walks the NB
    column blocks, ``make_async_copy`` prefetching tile i+1 into the
    ping-pong VMEM scratch while the MXU consumes tile i with a native
    s8×s8 dot (int32 accumulate).  Scales fold into the (M, BN) block
    output — the wide weight is never dequantized.
    """
    nb, _, bn = w_hbm.shape

    def tile_dma(slot, i):
        return pltpu.make_async_copy(
            w_hbm.at[i], wbuf.at[slot], sem.at[slot]
        )

    tile_dma(0, 0).start()

    def body(i, _):
        slot = i % 2

        @pl.when(i + 1 < nb)
        def _prefetch():
            tile_dma((i + 1) % 2, i + 1).start()

        tile_dma(slot, i).wait()
        acc = jax.lax.dot_general(
            xq_ref[:],
            wbuf[slot],
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32,
        )
        col = pl.multiple_of(i * bn, bn)
        out_ref[:, pl.ds(col, bn)] = _fold(
            acc, as_ref[:], ws_ref[i], out_ref.dtype
        )
        return 0

    jax.lax.fori_loop(0, nb, body, 0)


def _qmm_pallas(xq, a_scale, tiles, w_scale, out_dtype, interpret):
    nb, k_pad, bn = tiles.shape
    m = xq.shape[0]
    return pl.pallas_call(
        _qmm_kernel,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.VMEM),  # xq (M, K_pad)
            pl.BlockSpec(memory_space=pltpu.VMEM),  # w_scale (NB, 1, BN)
            pl.BlockSpec(memory_space=pltpu.VMEM),  # a_scale (M, 1)
            pl.BlockSpec(memory_space=pl.ANY),  # tiles stay in HBM
        ],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((m, nb * bn), out_dtype),
        scratch_shapes=[
            pltpu.VMEM((2, k_pad, bn), jnp.int8),
            pltpu.SemaphoreType.DMA((2,)),
        ],
        compiler_params=pltpu.CompilerParams(
            # Operand VMEM + the double buffer, with headroom for
            # Mosaic's own temporaries.
            vmem_limit_bytes=_VMEM_BUDGET_BYTES,
        ),
        interpret=interpret,
        name="qmm_w8a8",
    )(xq, w_scale, a_scale, tiles)


def _kernel_vmem_bytes(m_pad, k_pad, n_pad, bn, out_itemsize) -> int:
    """Scoped VMEM Mosaic allocates for :func:`_qmm_pallas`, fitted to
    the least ``vmem_limit_bytes`` the v5e compiler accepts (bisected at
    llama3-8b widths, M = 32..512; the estimate stays within 0.3 MiB
    above it)."""
    return (
        2 * k_pad * bn  # double-buffered weight tile (int8)
        # int8 activations, twice: the operand block plus the copy the
        # loop-invariant ``xq_ref[:]`` load is hoisted into.
        + 2 * m_pad * k_pad
        + m_pad * n_pad * out_itemsize  # narrow output
        + n_pad * 4  # blocked weight scales
        + m_pad * 128 * 4  # per-token activation scales, lane-padded
        + 256 * 1024  # accumulator / fold temporaries
    )


def use_qmm_kernel(
    *, m_pad: int, k_pad: int, n_pad: int, bn: int, out_itemsize: int
) -> bool:
    """Dispatch predicate for the W8A8 streaming kernel.

    TPU within the VMEM budget; interpret mode forces the kernel on CPU
    for tests.  Blocked weights only exist on one device
    (``engine.decode.prepare_params`` blocks for no other placement),
    so only the platform is asked here.  Everything else —
    prefill-sized M, CPU — takes the XLA twin, which is bit-identical
    by construction.
    """
    if os.environ.get("GAIE_DISABLE_QMM_KERNEL"):
        return False
    if (
        _kernel_vmem_bytes(m_pad, k_pad, n_pad, bn, out_itemsize)
        > _VMEM_BUDGET_BYTES
    ):
        return False
    if _interpret_mode():
        return True
    return jax.default_backend() == "tpu"


def q_matmul(
    x: jnp.ndarray, w: BlockedQuantizedMatrix, name: str | None = None
) -> jnp.ndarray:
    """``x @ w`` in W8A8: quantize activations per token, int8 dot,
    fold scales into the narrow output.

    Accepts ``(..., K)`` activations; leading axes flatten to rows
    (tokens).  Chooses the Pallas kernel or its XLA twin per
    :func:`use_qmm_kernel` — the two are bit-identical, so dispatch is
    purely a bandwidth decision.
    """
    nb, k_pad, bn = w.tiles.shape[-3:]
    *lead, k = x.shape
    x2 = x.reshape(-1, k)
    m = x2.shape[0]
    xq, a_scale = quantize_activations(x2)
    if k_pad != k:
        xq = jnp.pad(xq, ((0, 0), (0, k_pad - k)))
    # Row padding to the int8 sublane quantum; padded rows carry scale
    # 1 and are sliced off below.
    m_pad = _round_up(max(m, 1), 32)
    if m_pad != m:
        xq = jnp.pad(xq, ((0, m_pad - m), (0, 0)))
        a_scale = jnp.pad(
            a_scale, ((0, m_pad - m), (0, 0)), constant_values=1.0
        )
    if record(
        f"q_matmul {name or f'{k}x{w.n}'} m={m_pad}",
        use_qmm_kernel(
            m_pad=m_pad,
            k_pad=k_pad,
            n_pad=nb * bn,
            bn=bn,
            out_itemsize=jnp.dtype(x.dtype).itemsize,
        ),
    ):
        out = _qmm_pallas(
            xq, a_scale, w.tiles, w.scale, x.dtype, _interpret_mode()
        )
    else:
        out = _qmm_xla(xq, a_scale, w.tiles, w.scale, x.dtype)
    return out[:m, : w.n].reshape(*lead, w.n)
