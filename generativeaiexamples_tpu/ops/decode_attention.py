"""Pallas TPU decode attention over the stacked int8 KV cache.

TPU-native replacement for the paged-KV decode attention inside
TensorRT-LLM (consumed by the reference via the NIM container,
``deploy/compose/docker-compose-nim-ms.yaml:2-22``; SURVEY.md §2.8).

Why a kernel: the XLA decode path must ``dynamic_slice`` each layer's KV
window out of the stacked cache before the attention einsums, and XLA
materializes that slice in HBM — measured at 4.3 ms of the 26.6 ms decode
step (b=192, window 256; PERF_NOTES.md).  This kernel DMAs (block_b,
block_t) KV tiles straight out of the full ``(L, KH, B, T, HD)`` cache —
the layer index rides in as a scalar-prefetch operand used by the
BlockSpec index maps — so the window streams once at HBM bandwidth with no
intermediate copy.  Probe (b=320, window 256): 11.2 ms vs 16.5 ms for the
slice+einsum XLA path per 32-layer step.

Semantics match :func:`ops.attention.gqa_attention` specialized to s == 1:
key slot ``t`` is visible iff ``t < kv_length[b]`` (the decode caller's
``kv_length = position + 1`` makes this the causal mask), int8 k/v convert
to the query dtype inside the dot (HBM streams int8 bytes only), and the
per-(token, head) dequant scales fold into scores / softmax weights.
Rows with ``kv_length == 0`` produce exact zeros.

Cache layout contract (``models.llama.init_kv_cache``): values
``(L, KH, B, T, HD)``, scales ``(L, KH, B, T)`` — head-major so the
kernel's KV blocks tile the minor-most ``(T, HD)`` dims legally.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# The paged kernel shares the W8A8 streaming kernel's VMEM ceiling: both
# manually double-buffer HBM-resident operands, so one budget constant
# keeps the accounting honest across kernels.
from generativeaiexamples_tpu.ops.dispatch import one_device, platform_of
from generativeaiexamples_tpu.ops.qmm import _VMEM_BUDGET_BYTES

_NEG_INF = -1e30


def flush_clip_start(max_len: int, chunk: int) -> int:
    """First cache position the chunk-end append-buffer flush can
    garbage-write for a lane that cannot advance.

    ``_flush_append_buffer`` (engine/decode.py) clips each row's flush
    start to ``max_len - chunk``, so lanes pinned at ``max_len - 1``
    (parked prefix caches, slots admitted after the pipelined tick's
    decode snapshot, warming chunked-prefill lanes) take ``chunk`` slots
    of garbage in ``[max_len - chunk, max_len)``.  Every producer of
    KV that must SURVIVE such a flush — parked histories, same-tick
    admission prefills, grafted shared prefixes — has to stay strictly
    below this position; the scheduler derives both its parking margin
    and its admission length bound from it so the contract lives in one
    place next to the attention kernel that reads the cache.
    """
    return max_len - chunk


def _interpret_mode() -> bool:
    """Test hook: run the kernel in Pallas interpret mode on CPU so the
    full append-buffer decode path is exercised hermetically
    (tests/conftest.py's virtual-device platform)."""
    return bool(os.environ.get("GAIE_DECODE_KERNEL_INTERPRET"))

def _pick_block_b(batch: int) -> int:
    """Batch rows per program.

    64 measured fastest inside the serving decode scan at b=320 (the
    layer scan already pipelines across kernel calls, so fewer/bigger
    programs win); smaller powers keep small batches legal.  Must be a
    multiple of 16 — it is the second-to-minor dim of the bf16 scale
    blocks.
    """
    env = os.environ.get("GAIE_DECODE_KERNEL_BB")
    if env:
        bb = int(env)
        if bb % 16 != 0 or batch % bb != 0:
            # A non-dividing override would silently drop trailing batch
            # rows (grid = batch // bb) and return wrong attention for
            # them — refuse instead.
            raise ValueError(
                f"GAIE_DECODE_KERNEL_BB={bb} must be a multiple of 16 "
                f"that divides batch {batch}"
            )
        return bb
    for bb in (64, 32, 16):
        if batch % bb == 0:
            return bb
    return 16


# KV slots per program; multiple of 128 (minor dim of the scale blocks).
BLOCK_T = 256


def _online_update(
    q, k, v, kscale, vscale, mask, m_ref, l_ref, acc_ref, scale
):
    """One online-softmax accumulation step over a (BB, BT', HD) KV tile.

    ``mask`` is (BB, G, BT') validity; int8 k/v convert to q's dtype at the
    dot (only int8 bytes ever streamed from HBM), dequant scales fold into
    scores and softmax weights.
    """
    bb, g = q.shape[0], q.shape[1]
    bt = k.shape[1]
    s = jax.lax.dot_general(
        q,
        k.astype(q.dtype),
        dimension_numbers=(((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.float32,
    )
    s = s * scale
    s = s * kscale[:, None, :]
    s = jnp.where(mask, s, _NEG_INF)

    s2 = s.reshape(bb * g, bt)
    mask2 = mask.reshape(bb * g, bt)
    m_prev = m_ref[:, :1]
    l_prev = l_ref[:, :1]
    m_new = jnp.maximum(m_prev, jnp.max(s2, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    # Multiplicative mask keeps fully-masked rows exactly zero (matches
    # gqa_attention's padded-row handling bit-for-bit).
    p = jnp.exp(s2 - m_new) * mask2
    l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)

    pv = (p.reshape(bb, g, bt) * vscale[:, None, :]).astype(q.dtype)
    acc = jax.lax.dot_general(
        pv,
        v.astype(q.dtype),
        dimension_numbers=(((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32,
    )  # (BB, G, HD)
    acc_ref[:] = acc_ref[:] * alpha + acc.reshape(bb * g, -1)
    m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
    l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)


def _decode_kernel(
    li_ref,  # scalar prefetch: (1,) int32 layer index
    abn_ref,  # scalar prefetch: (1,) int32 valid append-buffer slots
    len_ref,  # (BB, 1) int32 valid kv prefix per row
    q_ref,  # (BB, 1, G, HD)
    k_ref,  # (1, 1, BB, BT, HD) int8
    v_ref,  # (1, 1, BB, BT, HD) int8
    ks_ref,  # (1, 1, BB, BT) bf16
    vs_ref,  # (1, 1, BB, BT) bf16
    # with has_ab: kab, vab (1, 1, BB, C, HD) int8; ksab, vsab
    # (1, 1, BB, C) bf16 — the decode chunk's append buffer.
    *rest,
    block_t: int,
    scale: float,
    has_ab: bool,
):
    if has_ab:
        kab_ref, vab_ref, ksab_ref, vsab_ref = rest[:4]
        o_ref, m_ref, l_ref, acc_ref = rest[4:]
    else:
        o_ref, m_ref, l_ref, acc_ref = rest
    ti = pl.program_id(2)
    n_t = pl.num_programs(2)
    bb, g = q_ref.shape[0], q_ref.shape[2]

    @pl.when(ti == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    q = q_ref[:, 0]  # (BB, G, HD)
    lens = len_ref[:, 0]  # (BB,)

    t_idx = (
        jax.lax.broadcasted_iota(jnp.int32, (bb, g, block_t), 2)
        + ti * block_t
    )
    mask = t_idx < lens[:, None, None]
    _online_update(
        q,
        k_ref[0, 0],
        v_ref[0, 0],
        ks_ref[0, 0].astype(jnp.float32),
        vs_ref[0, 0].astype(jnp.float32),
        mask,
        m_ref,
        l_ref,
        acc_ref,
        scale,
    )

    # The append buffer folds into the LAST cache grid step (an extra
    # grid step would double the program count — measured +50% kernel
    # time; its blocks have constant index maps, so they are DMA'd once).
    if has_ab:

        @pl.when(ti == n_t - 1)
        def _ab_tile():
            c = kab_ref.shape[3]
            j_idx = jax.lax.broadcasted_iota(jnp.int32, (bb, g, c), 2)
            ab_mask = j_idx < abn_ref[0]
            _online_update(
                q,
                kab_ref[0, 0],
                vab_ref[0, 0],
                ksab_ref[0, 0].astype(jnp.float32),
                vsab_ref[0, 0].astype(jnp.float32),
                ab_mask,
                m_ref,
                l_ref,
                acc_ref,
                scale,
            )

    @pl.when(ti == n_t - 1)
    def _finalize():
        denom = jnp.maximum(l_ref[:, :1], 1e-30)
        o_ref[:, 0] = (
            (acc_ref[:] / denom).reshape(bb, g, -1).astype(o_ref.dtype)
        )


def use_decode_kernel(
    *,
    s: int,
    kv_int8: bool,
    batch: int,
    window: int,
    n_q: int,
    n_kv: int,
    head_dim: int,
    mesh=None,
    backend=None,
) -> bool:
    """Dispatch predicate for the decode kernel.

    Single-token decode on a single TPU chip with an int8 cache and
    MXU/tile-aligned shapes; everything else falls back to the XLA path
    (which is also the reference implementation for tests).
    """
    if os.environ.get("GAIE_DISABLE_DECODE_KERNEL"):
        return False
    if s != 1 or not kv_int8:
        return False
    if not _interpret_mode():
        if (backend or platform_of(mesh)) != "tpu" or not one_device(mesh):
            return False
    return (
        batch % 16 == 0
        # Exact-tiling gate, mirroring the wrapper's tile pick: a window
        # at or under one tile runs as a single tile (the wrapper widens
        # the small pow2 buckets 32 and 64 — reachable from any
        # short-context decode — to a whole 128-lane tile or the cache's
        # length), and larger windows must split into whole 256- or
        # 128-deep tiles (the dense 3*2^k buckets 384, 768, ... tile at
        # 128).  tests/test_paged_kv.py pins the gate against the wrapper
        # for every reachable bucket; tests/test_chip_compile.py asks the
        # v5e compiler about a 64-slot window of a 256-slot cache.
        and (
            (window <= BLOCK_T and window % 32 == 0)
            or window % 128 == 0
        )
        and head_dim % 128 == 0
        and n_q % n_kv == 0
        and n_q // n_kv <= 16
    )


def use_append_buffer(
    *,
    s: int,
    kv_int8: bool,
    batch: int,
    window: int,
    n_q: int,
    n_kv: int,
    head_dim: int,
    mesh=None,
    backend=None,
) -> bool:
    """Dispatch predicate for the append-buffer decode protocol
    (kernel OR the XLA fallback below).

    On a single TPU chip, int8 single-token decode ALWAYS uses the
    append protocol: the alternative — per-token scatters into the big
    head-major cache — prefers a KH-minor layout that conflicts with
    every other executable touching the cache, and the resulting entry
    copies OOM at serving batch (PERF_NOTES.md round-3 caveat).  When
    :func:`use_decode_kernel` also holds, attention runs in the Pallas
    kernel; otherwise :func:`decode_gqa_attention_xla` computes the same
    contract with einsums — slower (it materializes the per-layer KV
    window) but correct at full batch.  Off-TPU the scatter path stays
    the default test oracle; ``GAIE_FORCE_APPEND_BUFFER=1`` opts in.
    """
    if s < 1 or not kv_int8:
        return False
    if s == 1 and use_decode_kernel(
        s=s, kv_int8=kv_int8, batch=batch, window=window,
        n_q=n_q, n_kv=n_kv, head_dim=head_dim, mesh=mesh, backend=backend,
    ):
        return True
    # s > 1 is the speculative-verify block (verify_gqa_attention_xla):
    # same protocol, no kernel yet, same platform gating.
    if n_q % n_kv != 0:
        return False
    if os.environ.get("GAIE_FORCE_APPEND_BUFFER"):
        return True
    return (backend or platform_of(mesh)) == "tpu" and one_device(mesh)


def _slice_layer_window(buf, li, w):
    """Layer ``li``'s first ``w`` slots of a (L, KH, B, T, ...) buffer:
    (KH, B, w, ...)."""
    return jax.lax.dynamic_slice(
        buf,
        (li,) + (0,) * (buf.ndim - 1),
        (1,) + buf.shape[1:3] + (w,) + buf.shape[4:],
    )[0]


def _window_buffer_attention_core(
    q, k_w, v_w, ks_w, vs_w, kv_lengths, append_w, buf_base
):
    """Shared XLA math for the append-buffer attention family, over
    PRE-SLICED per-layer windows.

    ``q`` is (B, S, n_q, HD) fresh-token queries; ``k_w``/``v_w`` are
    (KH, B, W, HD) int8 window values with (KH, B, W) scales — how the
    window was MATERIALIZED (a contiguous ``dynamic_slice`` or a paged
    page-table gather) is the caller's business.  Window slot ``t``
    contributes iff ``t < kv_lengths[b]`` and the (optional, pre-sliced)
    append buffer contributes slot ``j`` to query ``i`` iff
    ``j <= buf_base + i`` — decode passes ``buf_base = count - 1`` with
    S=1 (all written slots visible), verify passes ``buf_base = 0``
    (causal within the block).  Masked window slots contribute EXACT
    zeros (``where`` before the max + multiplicative mask), so two
    callers whose windows agree on the unmasked slots produce
    bit-identical outputs regardless of what garbage fills the rest —
    the property the paged-vs-contiguous parity gates rely on.  One
    implementation keeps the numerics (mask constants, softmax clamp,
    dequant-scale folding) of all four twins identical.
    """
    b, s, n_q, hd = q.shape
    n_kv = k_w.shape[0]
    g = n_q // n_kv
    scale = hd**-0.5
    window = k_w.shape[2]

    qg = q.reshape(b, s, n_kv, g, hd)

    def scores_part(kpart, kspart):
        # (b, n_kv, g, s, t); int8 keys convert at the dot, scales fold
        # into scores — never into a dequantized cache copy.
        sc = (
            jnp.einsum(
                "bsngh,nbth->bngst",
                qg,
                kpart.astype(q.dtype),
                preferred_element_type=jnp.float32,
            )
            * scale
        )
        return sc * jnp.transpose(kspart, (1, 0, 2)).astype(jnp.float32)[
            :, :, None, None, :
        ]

    t_idx = jnp.arange(window, dtype=jnp.int32)
    mask_w = (t_idx[None, :] < kv_lengths[:, None])[:, None, None, None, :]
    sc_w = jnp.where(mask_w, scores_part(k_w, ks_w), -1e30)
    parts = [(sc_w, jnp.broadcast_to(mask_w, sc_w.shape))]
    vals = [(v_w, vs_w)]
    if append_w is not None:
        k_ab, v_ab, ks_ab, vs_ab = append_w
        c = k_ab.shape[2]
        j_idx = jnp.arange(c, dtype=jnp.int32)
        visible = (
            j_idx[None, :]
            <= buf_base + jnp.arange(s, dtype=jnp.int32)[:, None]
        )[None, None, None, :, :]
        sc_b = jnp.where(
            visible, scores_part(k_ab, ks_ab), -1e30
        )
        parts.append((sc_b, jnp.broadcast_to(visible, sc_b.shape)))
        vals.append((v_ab, vs_ab))

    scores = jnp.concatenate([p[0] for p in parts], axis=-1)
    masks = jnp.concatenate([p[1] for p in parts], axis=-1)
    m = scores.max(axis=-1, keepdims=True)
    weights = jnp.exp(scores - m) * masks
    weights = weights / jnp.maximum(
        weights.sum(axis=-1, keepdims=True), 1e-30
    )
    out = jnp.zeros((b, n_kv, g, s, hd), jnp.float32)
    off = 0
    for vpart, vspart in vals:
        t = vpart.shape[2]
        w = weights[..., off : off + t] * jnp.transpose(
            vspart, (1, 0, 2)
        ).astype(jnp.float32)[:, :, None, None, :]
        out = out + jnp.einsum(
            "bngst,nbth->bngsh",
            w.astype(q.dtype),
            vpart.astype(q.dtype),
            preferred_element_type=jnp.float32,
        )
        off += t
    # (b, n_kv, g, s, hd) -> (b, s, n_q, hd)
    return (
        jnp.transpose(out, (0, 3, 1, 2, 4))
        .reshape(b, s, n_q, hd)
        .astype(q.dtype)
    )


def _cache_buffer_attention_xla(
    q, k8, v8, ks, vs, layer, kv_lengths, append, buf_base, *, window
):
    """Contiguous-cache front half of the append-buffer family: slice
    layer ``li``'s first ``window`` slots out of the stacked
    (L, KH, B, T, ...) cache, then run the shared window core."""
    li = jnp.asarray(layer, jnp.int32)
    append_w = None
    if append is not None:
        k_ab, v_ab, ks_ab, vs_ab = append
        c = k_ab.shape[3]
        append_w = (
            _slice_layer_window(k_ab, li, c),
            _slice_layer_window(v_ab, li, c),
            _slice_layer_window(ks_ab, li, c),
            _slice_layer_window(vs_ab, li, c),
        )
    return _window_buffer_attention_core(
        q,
        _slice_layer_window(k8, li, window),
        _slice_layer_window(v8, li, window),
        _slice_layer_window(ks, li, window),
        _slice_layer_window(vs, li, window),
        kv_lengths,
        append_w,
        buf_base,
    )


def _paged_window_index(page_table, window, page_tokens):
    """Flat pool indices of logical window slots [0, window): (B, W).

    Logical token ``t`` of row ``b`` lives at pool slot
    ``page_table[b, t // page_tokens] * page_tokens + t % page_tokens``.
    Unowned table entries are 0 (the pinned garbage page), so
    out-of-range slots gather page-0 garbage — masked to exact zeros by
    the window core.
    """
    w = jnp.arange(window, dtype=jnp.int32)
    return (
        page_table[:, w // page_tokens] * page_tokens + w % page_tokens
    )


def _gather_pool_layer(buf, li, flat):
    """Gather layer ``li``'s window from a flat (L, KH, P, ...) pool
    leaf via precomputed flat indices (B, W) -> (KH, B, W, ...)."""
    lsl = jax.lax.dynamic_slice(
        buf, (li,) + (0,) * (buf.ndim - 1), (1,) + buf.shape[1:]
    )[0]  # (KH, P, ...)
    return lsl[:, flat]


def _paged_buffer_attention_xla(
    q,
    k8,
    v8,
    ks,
    vs,
    layer,
    kv_lengths,
    page_table,
    append,
    buf_base,
    *,
    window,
    page_tokens,
):
    """Paged-pool front half: gather the logical window [0, window) out
    of the flat (L, KH, P, ...) pool leaves through the page table, then
    run the SAME window core as the contiguous twins.

    Because the core zeroes masked slots exactly, this is bit-identical
    to the contiguous ``_cache_buffer_attention_xla`` whenever the
    page-mapped content of slots ``[0, kv_lengths[b])`` matches the
    contiguous cache — the paged-parity gate in tests/test_paged_kv.py.
    """
    li = jnp.asarray(layer, jnp.int32)
    flat = _paged_window_index(page_table, window, page_tokens)
    append_w = None
    if append is not None:
        k_ab, v_ab, ks_ab, vs_ab = append
        c = k_ab.shape[3]
        append_w = (
            _slice_layer_window(k_ab, li, c),
            _slice_layer_window(v_ab, li, c),
            _slice_layer_window(ks_ab, li, c),
            _slice_layer_window(vs_ab, li, c),
        )
    return _window_buffer_attention_core(
        q,
        _gather_pool_layer(k8, li, flat),
        _gather_pool_layer(v8, li, flat),
        _gather_pool_layer(ks, li, flat),
        _gather_pool_layer(vs, li, flat),
        kv_lengths,
        append_w,
        buf_base,
    )


@functools.partial(jax.jit, static_argnames=("window", "page_tokens"))
def paged_decode_gqa_attention_xla(
    q: jnp.ndarray,
    k8: jnp.ndarray,
    v8: jnp.ndarray,
    ks: jnp.ndarray,
    vs: jnp.ndarray,
    layer: jnp.ndarray,
    kv_lengths: jnp.ndarray,
    page_table: jnp.ndarray,
    append=None,
    *,
    window: int,
    page_tokens: int,
) -> jnp.ndarray:
    """Paged twin of :func:`decode_gqa_attention_xla`.

    Same contract with the cache read through a page table: ``k8``/``v8``
    are flat (L, KH, P, HD) int8 pool values (P = total_pages *
    page_tokens) with (L, KH, P) scales, and ``page_table`` (B,
    n_slot_pages) int32 maps each row's logical pages to pool pages.
    The reference/fallback for :func:`paged_decode_gqa_attention`:
    bit-identical to the contiguous twin on matching content, and equal
    to the kernel to float tolerance (the kernel normalizes its online
    softmax in page order, this twin once over the gathered window).
    """
    if append is not None:
        k_ab, v_ab, ks_ab, vs_ab, count = append
        buf = (k_ab, v_ab, ks_ab, vs_ab)
        buf_base = jnp.asarray(count, jnp.int32) - 1
    else:
        buf, buf_base = None, jnp.int32(0)
    return _paged_buffer_attention_xla(
        q[:, None],
        k8,
        v8,
        ks,
        vs,
        layer,
        kv_lengths,
        page_table,
        buf,
        buf_base,
        window=window,
        page_tokens=page_tokens,
    )[:, 0]


@functools.partial(jax.jit, static_argnames=("window", "page_tokens"))
def paged_verify_gqa_attention_xla(
    q: jnp.ndarray,
    k8: jnp.ndarray,
    v8: jnp.ndarray,
    ks: jnp.ndarray,
    vs: jnp.ndarray,
    layer: jnp.ndarray,
    kv_lengths: jnp.ndarray,
    page_table: jnp.ndarray,
    append,
    *,
    window: int,
    page_tokens: int,
) -> jnp.ndarray:
    """Paged twin of :func:`verify_gqa_attention_xla` (speculative
    verify over [paged prefix ; fresh append block])."""
    return _paged_buffer_attention_xla(
        q,
        k8,
        v8,
        ks,
        vs,
        layer,
        kv_lengths,
        page_table,
        append,
        jnp.int32(0),
        window=window,
        page_tokens=page_tokens,
    )


@functools.partial(jax.jit, static_argnames=("window",))
def decode_gqa_attention_xla(
    q: jnp.ndarray,
    k8: jnp.ndarray,
    v8: jnp.ndarray,
    ks: jnp.ndarray,
    vs: jnp.ndarray,
    layer: jnp.ndarray,
    kv_lengths: jnp.ndarray,
    append=None,
    *,
    window: int,
) -> jnp.ndarray:
    """XLA twin of :func:`decode_gqa_attention` — identical contract,
    einsum math, no shape-alignment requirements.

    The full-batch fallback when the Pallas kernel is off
    (``GAIE_DISABLE_DECODE_KERNEL``), unsupported (odd shapes), or
    regressed: it keeps the append-buffer protocol — the big cache is
    only ever SLICED here, never scattered into — so the decode
    executable shares the kernel path's memory/layout profile instead of
    the scatter path's (which OOMs at serving batch).  Cost vs the
    kernel: the per-layer KV window materializes as an XLA slice (the
    round-2 4.3 ms/step item the kernel exists to kill).
    """
    if append is not None:
        k_ab, v_ab, ks_ab, vs_ab, count = append
        buf = (k_ab, v_ab, ks_ab, vs_ab)
        buf_base = jnp.asarray(count, jnp.int32) - 1
    else:
        buf, buf_base = None, jnp.int32(0)
    return _cache_buffer_attention_xla(
        q[:, None], k8, v8, ks, vs, layer, kv_lengths, buf, buf_base,
        window=window,
    )[:, 0]


@functools.partial(jax.jit, static_argnames=("window",))
def verify_gqa_attention_xla(
    q: jnp.ndarray,
    k8: jnp.ndarray,
    v8: jnp.ndarray,
    ks: jnp.ndarray,
    vs: jnp.ndarray,
    layer: jnp.ndarray,
    kv_lengths: jnp.ndarray,
    append,
    *,
    window: int,
) -> jnp.ndarray:
    """Multi-token verify attention over [big-cache prefix ; fresh block].

    The speculative-decode verify pass's append-buffer attention
    (``engine/spec_decode.py``): ``q`` is (B, S, n_q, HD) — row r's S
    fresh tokens sit at absolute positions ``kv_lengths[r] + i`` — the
    big cache contributes slots ``t < kv_lengths[r]`` (every fresh query
    sees the whole valid prefix), and the append buffer (all S slots
    fresh this call) contributes causally: slot j visible to query i iff
    ``j <= i``.  The big cache is only SLICED — no scatter shares this
    executable, so the layout-copy failure mode of warm multi-token
    scatters at serving batch cannot occur.
    """
    return _cache_buffer_attention_xla(
        q, k8, v8, ks, vs, layer, kv_lengths, append, jnp.int32(0),
        window=window,
    )


@functools.partial(
    jax.jit, static_argnames=("window", "interpret")
)
def decode_gqa_attention(
    q: jnp.ndarray,
    k8: jnp.ndarray,
    v8: jnp.ndarray,
    ks: jnp.ndarray,
    vs: jnp.ndarray,
    layer: jnp.ndarray,
    kv_lengths: jnp.ndarray,
    append=None,
    *,
    window: int,
    interpret=None,
) -> jnp.ndarray:
    """Decode attention for one layer of the stacked cache.

    Args:
      q: (B, n_q_heads, HD) — the single decode token's queries, rope
        already applied.
      k8, v8: (L, KH, B, T, HD) int8 stacked cache values.
      ks, vs: (L, KH, B, T) bf16 dequant scales.
      layer: int32 scalar — which layer's cache to read.
      kv_lengths: (B,) int32 — cache slots [0, kv_lengths[b]) are
        attended.
      append: optional ``(k_ab, v_ab, ks_ab, vs_ab, count)`` — the decode
        chunk's append buffer holding this chunk's fresh KV: values
        (L, KH, B, C, HD) int8, scales (L, KH, B, C) bf16, ``count`` an
        int32 scalar of valid slots (slot j holds the token at absolute
        position kv_lengths[b] + j; all rows share the count).  Processed
        as one extra grid step whose blocks are fetched once per program
        (their index map is constant, so Pallas skips the re-DMA).
      window: static; attention reads cache slots [0, window).  Caller
        guarantees every valid slot (kv_lengths max) is <= window.

    Returns:
      (B, n_q_heads, HD) in q's dtype.
    """
    if interpret is None:
        interpret = _interpret_mode()
    b, n_q, hd = q.shape
    n_kv = k8.shape[1]
    g = n_q // n_kv
    # A KV block's slot dim must be whole 128-lane tiles or the cache's
    # whole length (the bf16 scale blocks carry it minor-most), so a
    # narrower window widens to the next tile: the extra slots lie
    # beyond every row's kv_length and mask to exact zeros.
    cache_len = k8.shape[3]
    if window % 128 and window < cache_len:
        window = min(-(-window // 128) * 128, cache_len)
    if window <= BLOCK_T:
        bt = window
    elif window % BLOCK_T == 0:
        bt = BLOCK_T
    else:
        bt = 128  # dense 3*2^k windows (384, 768, ...) tile at 128
    n_cache = window // bt
    has_ab = append is not None
    bb = _pick_block_b(b)
    grid = (b // bb, n_kv, n_cache)

    def cache_val_map(bi, hi, ti, li, abn):
        return (li[0], hi, bi, ti, 0)

    def cache_scale_map(bi, hi, ti, li, abn):
        return (li[0], hi, bi, ti)

    in_specs = [
        pl.BlockSpec((bb, 1), lambda bi, hi, ti, li, abn: (bi, 0)),
        pl.BlockSpec(
            (bb, 1, g, hd),
            lambda bi, hi, ti, li, abn: (bi, hi, 0, 0),
        ),
        pl.BlockSpec((1, 1, bb, bt, hd), cache_val_map),
        pl.BlockSpec((1, 1, bb, bt, hd), cache_val_map),
        pl.BlockSpec((1, 1, bb, bt), cache_scale_map),
        pl.BlockSpec((1, 1, bb, bt), cache_scale_map),
    ]
    operands = [
        kv_lengths.astype(jnp.int32).reshape(b, 1),
        q.reshape(b, n_kv, g, hd),
        k8,
        v8,
        ks,
        vs,
    ]
    if has_ab:
        k_ab, v_ab, ks_ab, vs_ab, count = append
        c = k_ab.shape[3]
        in_specs += [
            pl.BlockSpec(
                (1, 1, bb, c, hd),
                lambda bi, hi, ti, li, abn: (li[0], hi, bi, 0, 0),
            ),
            pl.BlockSpec(
                (1, 1, bb, c, hd),
                lambda bi, hi, ti, li, abn: (li[0], hi, bi, 0, 0),
            ),
            pl.BlockSpec(
                (1, 1, bb, c),
                lambda bi, hi, ti, li, abn: (li[0], hi, bi, 0),
            ),
            pl.BlockSpec(
                (1, 1, bb, c),
                lambda bi, hi, ti, li, abn: (li[0], hi, bi, 0),
            ),
        ]
        operands += [k_ab, v_ab, ks_ab, vs_ab]
        abn = jnp.asarray(count, jnp.int32).reshape(1)
    else:
        abn = jnp.zeros((1,), jnp.int32)

    out = pl.pallas_call(
        functools.partial(
            _decode_kernel,
            block_t=bt,
            scale=hd**-0.5,
            has_ab=has_ab,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=in_specs,
            out_specs=pl.BlockSpec(
                (bb, 1, g, hd),
                lambda bi, hi, ti, li, abn: (bi, hi, 0, 0),
            ),
            scratch_shapes=[
                pltpu.VMEM((bb * g, 128), jnp.float32),
                pltpu.VMEM((bb * g, 128), jnp.float32),
                pltpu.VMEM((bb * g, hd), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, n_kv, g, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="decode_gqa_attention",
    )(jnp.asarray(layer, jnp.int32).reshape(1), abn, *operands)
    return out.reshape(b, n_q, hd)


def _paged_interpret_mode() -> bool:
    """Test hook: run the paged kernel in Pallas interpret mode on CPU
    so the page-table walk + manual page DMAs are exercised
    hermetically."""
    return bool(os.environ.get("GAIE_PAGED_KERNEL_INTERPRET"))


def _paged_kernel_vmem_bytes(
    page_tokens: int, n_kv: int, g: int, hd: int, c: int
) -> int:
    """VMEM the paged kernel holds live per program (one batch row, all
    ``n_kv`` heads): the double-buffered page (int8 k/v + bf16 scale
    tiles), the query/output blocks, the append blocks, and the
    online-softmax scratch."""
    return n_kv * (
        2 * 2 * page_tokens * hd  # k/v page double buffers (int8)
        + 2 * 2 * max(page_tokens, 128) * 2  # k/v scale tiles (bf16)
        + 2 * g * hd * 4  # q block + output block (f32 worst case)
        + c * (2 * hd + 4)  # append block values (int8 x2) + scales
        + (2 * 128 + hd) * g * 4  # m/l/acc f32 scratch
    )


def use_paged_kernel(
    *,
    s: int,
    kv_int8: bool,
    page_tokens: int,
    n_q: int,
    n_kv: int,
    head_dim: int,
    append_width: int = 0,
    mesh=None,
    backend=None,
) -> bool:
    """Dispatch predicate for the paged decode kernel.

    Single-token decode on a single TPU chip with an int8 paged pool and
    lane-aligned page/head geometry; everything else falls back to
    :func:`paged_decode_gqa_attention_xla` (which is also the reference
    implementation for the bit-identity tests).
    ``GAIE_PAGED_KERNEL_INTERPRET=1`` forces the kernel in interpret
    mode on CPU; ``GAIE_DISABLE_PAGED_KERNEL=1`` forces the twin
    everywhere.
    """
    if os.environ.get("GAIE_DISABLE_PAGED_KERNEL"):
        return False
    if s != 1 or not kv_int8:
        return False
    g = n_q // max(n_kv, 1)
    # Page-size quantum: a page is one DMA tile, so it must either be a
    # whole number of 128-lane tiles or divide 128 while covering the
    # int8 sublane quantum (32) — {32, 64, 128, 256, ...}.  The default
    # ``[llm].kv_page_size`` of 64 sits inside this set by construction.
    page_ok = page_tokens % 128 == 0 or (
        128 % page_tokens == 0 and page_tokens >= 32
    )
    if (
        not page_ok
        or head_dim % 128 != 0
        or n_q % n_kv != 0
        or g > 16
    ):
        return False
    if (
        _paged_kernel_vmem_bytes(
            page_tokens, n_kv, g, head_dim, append_width
        )
        > _VMEM_BUDGET_BYTES
    ):
        return False
    if _paged_interpret_mode():
        return True
    return (backend or platform_of(mesh)) == "tpu" and one_device(mesh)


def _paged_decode_kernel(
    li_ref,  # scalar prefetch: (1,) int32 layer index
    abn_ref,  # scalar prefetch: (1,) int32 valid append-buffer slots
    tab_ref,  # scalar prefetch: (B, n_slot_pages) int32 page table
    len_ref,  # scalar prefetch: (B,) int32 valid kv prefix per row
    q_ref,  # (1, KH, G, HD)
    k_hbm,  # (L, KH, P, HD) int8 — stays in HBM (pl.ANY)
    v_hbm,  # (L, KH, P, HD) int8 — stays in HBM
    ks_hbm,  # (L, KH, P) bf16 — stays in HBM
    vs_hbm,  # (L, KH, P) bf16 — stays in HBM
    # with has_ab: kab, vab (1, KH, 1, C, HD) int8; ksab, vsab
    # (1, KH, 1, 1, C) bf16 — the decode chunk's append buffer (VMEM).
    *rest,
    page_tokens: int,
    scale: float,
    has_ab: bool,
):
    """Page-table-walking decode attention for one batch row.

    Each program owns one batch row across ALL its KV heads: it reads
    the row's valid length, walks ``ceil(len / page_tokens)`` page-table
    entries, and ``make_async_copy``-streams each page's int8 k/v
    (+ bf16 scales) for every head out of the HBM-resident pool into a
    ping-pong VMEM buffer — page ``i+1`` prefetches while page ``i``
    runs the online-softmax update with the heads as its batch dim.
    Taking every head per copy is what keeps the scale DMA legal: the
    (L, KH, P) scale planes tile their (KH, P) dims, so a one-head
    slice is not a whole tile and Mosaic refuses it.  No window slice,
    no pow2 padding: a ragged batch reads exactly the pages it owns.
    The trailing partial page masks to the row length, and the append
    buffer folds after the page walk — the same ``_online_update`` math
    as the contiguous kernel.
    """
    if has_ab:
        kab_ref, vab_ref, ksab_ref, vsab_ref = rest[:4]
        rest = rest[4:]
    o_ref = rest[0]
    kbuf, vbuf, ksbuf, vsbuf, sem, m_ref, l_ref, acc_ref = rest[1:]
    bi = pl.program_id(0)
    kh, g = q_ref.shape[1], q_ref.shape[2]
    pt = page_tokens
    li = li_ref[0]
    length = len_ref[bi]
    n_pages = (length + pt - 1) // pt

    m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
    l_ref[:] = jnp.zeros_like(l_ref)
    acc_ref[:] = jnp.zeros_like(acc_ref)

    # Scale copies take whole 128-lane tiles (the HBM planes tile their
    # token axis by 128): a page narrower than that rides in with its
    # tile-mates and ``page_scales`` selects its lanes afterwards.
    lanes = ksbuf.shape[2]
    sub = lanes // pt

    def page_dma(slot, p):
        page = tab_ref[bi, p]
        base = pl.multiple_of(page * pt, pt)
        sbase = pl.multiple_of(page // sub * lanes, lanes)
        return tuple(
            pltpu.make_async_copy(
                hbm.at[li, :, pl.ds(start, size)],
                buf.at[slot],
                sem.at[slot, j],
            )
            for j, (hbm, buf, start, size) in enumerate(
                (
                    (k_hbm, kbuf, base, pt),
                    (v_hbm, vbuf, base, pt),
                    (ks_hbm, ksbuf, sbase, lanes),
                    (vs_hbm, vsbuf, sbase, lanes),
                )
            )
        )

    def page_scales(buf, slot, p):
        tile = buf[slot].astype(jnp.float32)  # (KH, lanes)
        out = tile[:, :pt]
        which = tab_ref[bi, p] % sub
        for j in range(1, sub):
            out = jnp.where(which == j, tile[:, j * pt : (j + 1) * pt], out)
        return out

    @pl.when(n_pages > 0)
    def _first():
        for cp in page_dma(0, 0):
            cp.start()

    q = q_ref[0]  # (KH, G, HD)

    def body(i, _):
        slot = i % 2

        @pl.when(i + 1 < n_pages)
        def _prefetch():
            for cp in page_dma((i + 1) % 2, i + 1):
                cp.start()

        for cp in page_dma(slot, i):
            cp.wait()
        t_idx = (
            jax.lax.broadcasted_iota(jnp.int32, (kh, g, pt), 2) + i * pt
        )
        mask = t_idx < length
        _online_update(
            q,
            kbuf[slot],
            vbuf[slot],
            page_scales(ksbuf, slot, i),
            page_scales(vsbuf, slot, i),
            mask,
            m_ref,
            l_ref,
            acc_ref,
            scale,
        )
        return 0

    jax.lax.fori_loop(0, n_pages, body, 0)

    if has_ab:
        c = kab_ref.shape[3]
        j_idx = jax.lax.broadcasted_iota(jnp.int32, (kh, g, c), 2)
        ab_mask = j_idx < abn_ref[0]
        _online_update(
            q,
            kab_ref[0, :, 0],
            vab_ref[0, :, 0],
            ksab_ref[0, :, 0, 0].astype(jnp.float32),
            vsab_ref[0, :, 0, 0].astype(jnp.float32),
            ab_mask,
            m_ref,
            l_ref,
            acc_ref,
            scale,
        )

    denom = jnp.maximum(l_ref[:, :1], 1e-30)
    o_ref[0] = (acc_ref[:] / denom).reshape(kh, g, -1).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("page_tokens", "interpret")
)
def paged_decode_gqa_attention(
    q: jnp.ndarray,
    k8: jnp.ndarray,
    v8: jnp.ndarray,
    ks: jnp.ndarray,
    vs: jnp.ndarray,
    layer: jnp.ndarray,
    kv_lengths: jnp.ndarray,
    page_table: jnp.ndarray,
    append=None,
    *,
    page_tokens: int,
    interpret=None,
) -> jnp.ndarray:
    """Paged decode attention for one layer of the flat pool.

    Args:
      q: (B, n_q_heads, HD) — the single decode token's queries.
      k8, v8: (L, KH, P, HD) int8 flat pool values (P = total_pages *
        page_tokens); the pool stays in HBM (``pl.ANY``) and the kernel
        DMAs exactly the pages each lane owns.
      ks, vs: (L, KH, P) bf16 dequant scales.
      layer: int32 scalar — which layer's pool to read.
      kv_lengths: (B,) int32 ragged valid lengths — there is no
        ``window``: lane ``b`` walks ``ceil(kv_lengths[b] /
        page_tokens)`` page-table entries and stops.
      page_table: (B, n_slot_pages) int32 logical-page -> pool-page map.
      append: optional ``(k_ab, v_ab, ks_ab, vs_ab, count)`` — same
        contract as :func:`decode_gqa_attention`.
      page_tokens: static tokens per page (multiple of 128 on TPU).

    Returns:
      (B, n_q_heads, HD) in q's dtype — equal to
      :func:`paged_decode_gqa_attention_xla` to float tolerance (the
      gate tests/test_paged_kv.py holds in interpret mode and
      ``chip_smoke.py`` on the chip), not bitwise: the online softmax
      normalizes in page order.
    """
    if interpret is None:
        interpret = _paged_interpret_mode()
    b, n_q, hd = q.shape
    n_kv = k8.shape[1]
    g = n_q // n_kv
    has_ab = append is not None
    scale_lanes = max(page_tokens, 128)
    if k8.shape[2] % scale_lanes:
        # The last page's scale tile would read past the plane (and
        # interpret mode would clamp the read onto the wrong lanes).
        raise ValueError(
            f"paged pool token axis {k8.shape[2]} must be a multiple of "
            f"{scale_lanes} (PagedKVPool pads its leaves to it)"
        )

    def row_map(*tail):
        return lambda bi, li, abn, tab, lens: (bi,) + tail

    def ab_map(*tail):
        return lambda bi, li, abn, tab, lens: (li[0], 0, bi) + tail

    in_specs = [
        pl.BlockSpec((1, n_kv, g, hd), row_map(0, 0, 0)),
        pl.BlockSpec(memory_space=pl.ANY),  # k pool stays in HBM
        pl.BlockSpec(memory_space=pl.ANY),  # v pool stays in HBM
        pl.BlockSpec(memory_space=pl.ANY),  # k scales stay in HBM
        pl.BlockSpec(memory_space=pl.ANY),  # v scales stay in HBM
    ]
    operands = [q.reshape(b, n_kv, g, hd), k8, v8, ks, vs]
    if has_ab:
        k_ab, v_ab, ks_ab, vs_ab, count = append
        c = k_ab.shape[3]
        in_specs += [
            pl.BlockSpec((1, n_kv, 1, c, hd), ab_map(0, 0)),
            pl.BlockSpec((1, n_kv, 1, c, hd), ab_map(0, 0)),
            # Scales ride as (L, KH, B, 1, C): one row's (1, C) block
            # then equals the array's last two dims, which the TPU
            # lowering requires of a block that is not (8, 128)-aligned.
            pl.BlockSpec((1, n_kv, 1, 1, c), ab_map(0, 0)),
            pl.BlockSpec((1, n_kv, 1, 1, c), ab_map(0, 0)),
        ]
        operands += [
            k_ab, v_ab, ks_ab[:, :, :, None], vs_ab[:, :, :, None]
        ]
        abn = jnp.asarray(count, jnp.int32).reshape(1)
    else:
        abn = jnp.zeros((1,), jnp.int32)

    out = pl.pallas_call(
        functools.partial(
            _paged_decode_kernel,
            page_tokens=page_tokens,
            scale=hd**-0.5,
            has_ab=has_ab,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(b,),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, n_kv, g, hd), row_map(0, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, n_kv, page_tokens, hd), jnp.int8),
                pltpu.VMEM((2, n_kv, page_tokens, hd), jnp.int8),
                pltpu.VMEM((2, n_kv, scale_lanes), ks.dtype),
                pltpu.VMEM((2, n_kv, scale_lanes), vs.dtype),
                pltpu.SemaphoreType.DMA((2, 4)),
                pltpu.VMEM((n_kv * g, 128), jnp.float32),
                pltpu.VMEM((n_kv * g, 128), jnp.float32),
                pltpu.VMEM((n_kv * g, hd), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, n_kv, g, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=_VMEM_BUDGET_BYTES,
        ),
        interpret=interpret,
        name="paged_decode_gqa_attention",
    )(
        jnp.asarray(layer, jnp.int32).reshape(1),
        abn,
        page_table.astype(jnp.int32),
        kv_lengths.astype(jnp.int32),
        *operands,
    )
    return out.reshape(b, n_q, hd)
