"""Pallas TPU decode attention over the stacked int8 KV cache.

TPU-native replacement for the paged-KV decode attention inside
TensorRT-LLM (consumed by the reference via the NIM container,
``deploy/compose/docker-compose-nim-ms.yaml:2-22``; SURVEY.md §2.8).

Why a kernel: the XLA decode path must ``dynamic_slice`` each layer's KV
window out of the stacked cache before the attention einsums, and XLA
materializes that slice in HBM (4.3 ms of a 26.6 ms decode step at b=192,
window 256, in a 2026-07 run that is in no ledger).  The kernel copies KV blocks
straight out of the full ``(L, KH, B, T, HD)`` cache, which stays in HBM
— the layer index rides in as a scalar-prefetch operand — so there is no
intermediate copy, and it copies only what is live: a program takes 16
batch rows and walks each row's own ``ceil(kv_length / block)`` blocks,
none for a row of length 0.  (Until PR 25 a dense grid read the whole window for
every row and the lengths only masked; on the v5e that was 2.0 s of a
10 s serving window of which a third was live — PERF.md.)

Semantics match :func:`ops.attention.gqa_attention` specialized to s == 1:
key slot ``t`` is visible iff ``t < kv_length[b]`` (the decode caller's
``kv_length = position + 1`` makes this the causal mask), int8 k/v convert
to the query dtype inside the dot (HBM streams int8 bytes only), and the
per-(token, head) dequant scales fold into scores / softmax weights.
Rows with ``kv_length == 0`` produce exact zeros.

Inside a decode chunk the kernel also WRITES: it takes the step's fresh
quantised K/V beside the chunk's append buffer, puts every row's into
the buffer's slot of this step in VMEM, folds the buffer, and hands the
buffer's four leaves back aliased onto its operands.  An XLA write into
a buffer laid out for the kernel costs per call, not per byte (22-25 us
a layer call on the v5e: PERF.md section 6, PR 54), so inside the layer
loop nothing but the Mosaic call touches the leaves.

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

# The kernel shares the W8A8 streaming kernel's VMEM ceiling: both
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

# KV slots per block of a row's walk: one DMA and one online-softmax
# update.  Measured on the v5e (PERF.md, PR 25): at 256 the update's
# fixed cost shows (a full batch is 34 % slower than a dense grid), at
# 1,024 a ragged batch reads too far past its rows; 512 is within 9 % of
# the dense grid on full rows and faster on everything shorter.
BLOCK_T = 512

# Rows whose scales, queries, append slots and outputs ride in one VMEM
# block: the second-to-minor tile of the bf16 scale planes.
_ROW_GROUP = 16

# The least query heads a KV head's block of queries holds.  Plain
# multi-head attention (Ouro: 16 query heads on 16 KV heads) brings a
# group of ONE, which the v5e's compiler refuses: the group axis is the
# second-minor dim of the scores, and at size 1 its length mask no longer
# lowers to a vector compare (``LLO_CHECK ... lhs->ProducesVreg()``, the
# described chip, PR 51).  A lone query head rides with a zero one beside
# it, whose output is dropped; both fit the sublane tile one took.
_MIN_GROUP = 2


def _block_t(cache_len: int, window: int) -> int:
    """Slots per block for a cache of ``cache_len`` slots under a window
    of ``window``: the largest of BLOCK_T, 256 and 128 that divides the
    cache, so a row's last block never reads past it, and is no wider
    than the window (a short context costs one small block a row); a
    cache shorter than 128 is one block."""
    for bt in (BLOCK_T, 256, 128):
        if cache_len % bt == 0 and bt <= max(window, 128):
            return bt
    return cache_len


def kv_tokens_read(kv_lengths, cache_len: int, window: int) -> int:
    """Cache positions one call of the kernel reads for rows of these
    (host) lengths: each row's length in whole blocks."""
    bt = _block_t(cache_len, window)
    return sum(-(-int(n) // bt) * bt for n in kv_lengths)


def _scale_width(window: int, cache_len: int) -> int:
    """Slots of the scale planes a program holds: ``window`` widened to
    whole blocks (never past the cache, which whole blocks tile)."""
    bt = _block_t(cache_len, window)
    return min(-(-window // bt) * bt, cache_len)


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


def _put_fresh_rows(ab_refs, fresh_refs, out_refs, slot):
    """The group's blocks of the append buffer with the step's fresh rows
    at slot ``slot``, written to ``out_refs`` (which alias the leaves).

    ``ab_refs`` are the blocks as the call found them (values
    (1, KH, 16, C, HD) int8, scales (1, KH, 16, C) bf16) and
    ``fresh_refs`` the 16 rows' quantised K/V of this step ((16, KH, HD)
    int8, (16, KH) bf16): every row's goes in, whether it decodes or
    not.  A head at a time, in a loop that is traced once (an unrolled
    one doubled the kernel's trace, 0.85 s a decode-chunk program of
    Ouro's set-up on the chip's host: PERF.md section 6, PR 54).  The
    head's rows come out of the fresh block as a masked sum and go into
    the slot as a select, both in float32, because a packed sublane
    takes no dynamic index; an int8 and a bfloat16 both survive the
    round trip bit for bit."""
    fresh = [ref[...].astype(jnp.float32) for ref in fresh_refs]

    def put_head(h, _):
        for new, ab_ref, out_ref in zip(fresh, ab_refs, out_refs):
            of_head = jax.lax.broadcasted_iota(jnp.int32, new.shape, 1) == h
            row = jnp.sum(jnp.where(of_head, new, 0.0), axis=1, keepdims=True)
            block = ab_ref[0, h].astype(jnp.float32)  # (16, C[, HD])
            at_slot = jax.lax.broadcasted_iota(jnp.int32, block.shape, 1) == slot
            out_ref[0, h] = jnp.where(at_slot, row, block).astype(out_ref.dtype)
        return 0

    jax.lax.fori_loop(0, ab_refs[0].shape[1], put_head, 0)


def _fold_append_group(
    q_ref, kab_ref, vab_ref, ksab_ref, vsab_ref, count,
    m_ref, l_ref, acc_ref, scale,
):
    """Start the group's 16 online-softmax states from the append
    buffer: ``_online_update``'s step from the empty state, a KV head at
    a time with the rows as the batch dim, so the fold costs one short
    chain a head and group where it cost one a row.  State refs are
    (16, KH*G, 128 | HD), a row's rows ordered (head, group)."""
    rows, kh, g = q_ref.shape[0], q_ref.shape[1], q_ref.shape[2]
    c = kab_ref.shape[3]
    mask = jax.lax.broadcasted_iota(jnp.int32, (rows, g, c), 2) < count
    for h in range(kh):
        q = q_ref[:, h]  # (16, G, HD)
        s = jax.lax.dot_general(
            q,
            kab_ref[0, h].astype(q.dtype),
            dimension_numbers=(((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )
        s = s * scale
        s = s * ksab_ref[0, h].astype(jnp.float32)[:, None, :]
        s = jnp.where(mask, s, _NEG_INF)
        m = jnp.max(s, axis=-1, keepdims=True)
        p = jnp.exp(s - m) * mask
        pv = (p * vsab_ref[0, h].astype(jnp.float32)[:, None, :]).astype(
            q.dtype
        )
        acc = jax.lax.dot_general(
            pv,
            vab_ref[0, h].astype(q.dtype),
            dimension_numbers=(((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )  # (16, G, HD)
        head = slice(h * g, (h + 1) * g)
        m_ref[:, head, :] = jnp.broadcast_to(m, (rows, g, m_ref.shape[2]))
        l_ref[:, head, :] = jnp.broadcast_to(
            jnp.sum(p, axis=-1, keepdims=True), (rows, g, l_ref.shape[2])
        )
        acc_ref[:, head, :] = acc


def _decode_kernel(
    li_ref,  # scalar prefetch: (1,) int32 layer index
    slot_ref,  # scalar prefetch: (1,) int32 append slot of this step
    len_ref,  # scalar prefetch: (B,) int32 valid kv prefix per row
    q_ref,  # (16, KH, G, HD) — the program's group of 16 rows
    k_hbm,  # (L, KH, B, T, HD) int8 — stays in HBM (pl.ANY)
    v_hbm,  # (L, KH, B, T, HD) int8 — stays in HBM
    ks_ref,  # (1, KH, 16, W) bf16 — the group's scale planes
    vs_ref,  # (1, KH, 16, W) bf16
    # with has_ab: kab, vab (1, KH, 16, C, HD) int8 and ksab, vsab
    # (1, KH, 16, C) bf16, the group's rows of the append buffer; then
    # the group's fresh rows, k8, v8 (16, KH, HD) int8 and ks, vs
    # (16, KH) bf16.  Outputs: o (16, KH, G, HD) and, with has_ab, the
    # four append blocks again, which alias the leaves.
    *rest,
    block_t: int,
    scale: float,
    has_ab: bool,
):
    """Decode attention for a group of 16 batch rows: a walk over each
    row's own blocks of the contiguous cache.

    A row is walked across ALL its KV heads (they are the batch dim of
    ``_online_update``): ``ceil(len / block_t)`` blocks, none for a row
    of length 0, each block's int8 k/v ``make_async_copy``-streamed out
    of the HBM-resident cache into a ping-pong VMEM buffer — block
    ``i + 1`` is fetched while block ``i`` computes, and during a row's
    last block the first block of the NEXT row that has any (of this
    group or a later one), so only the call's very first copy is
    exposed.  The grid runs in order on one core, so the buffer slot and
    the "my first block is already on its way" flag ride from row to row
    and group to group in SMEM.  The trailing partial block masks to the
    row's length.

    What a one-row copy cannot bring (the bf16 scale planes tile
    ``(B, T)`` by (16, 128), so one row is not a whole tile) comes in
    through BlockSpecs for the group: scales, and with them queries,
    append slots and the output block.  The append buffer takes the
    step's fresh rows (:func:`_put_fresh_rows`) and folds first, for all
    16 rows at once (:func:`_fold_append_group`), and the outputs are
    normalized and stored once at the end, so a row that reads nothing
    costs a few scalar operations.
    """
    if has_ab:
        ab_refs, fresh_refs, rest = rest[:4], rest[4:8], rest[8:]
        o_ref, out_refs, rest = rest[0], rest[1:5], rest[5:]
    else:
        o_ref, rest = rest[0], rest[1:]
    kbuf, vbuf, sem, state, m_ref, l_ref, acc_ref = rest
    rows, kh, g = q_ref.shape[0], q_ref.shape[1], q_ref.shape[2]
    first_row = pl.program_id(0) * rows
    n_rows = pl.num_programs(0) * rows
    bt = block_t
    li = li_ref[0]
    width = ks_ref.shape[3]

    def n_blocks(row):
        return (jnp.minimum(len_ref[row], width) + bt - 1) // bt

    def block_dma(slot, row, i):
        start = pl.multiple_of(i * bt, bt)
        return tuple(
            pltpu.make_async_copy(
                hbm.at[li, :, row, pl.ds(start, bt)],
                buf.at[slot],
                sem.at[slot, j],
            )
            for j, (hbm, buf) in enumerate(((k_hbm, kbuf), (v_hbm, vbuf)))
        )

    # state[0]: buffer slot of the next block to compute; state[1]: 1 if
    # an earlier row already started the next walked row's first copy.
    @pl.when(first_row == 0)
    def _reset():
        state[0] = 0
        state[1] = 0

    if has_ab:
        # The step's rows go into the blocks that are handed back, and
        # the fold reads those.
        _put_fresh_rows(ab_refs, fresh_refs, out_refs, slot_ref[0])
        _fold_append_group(
            q_ref, *out_refs, slot_ref[0] + 1,
            m_ref, l_ref, acc_ref, scale,
        )
    else:
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    def walk_row(r, _):
        b = first_row + r
        length = jnp.minimum(len_ref[b], width)
        n = (length + bt - 1) // bt
        slot0 = state[0]

        @pl.when((n > 0) & (state[1] == 0))
        def _first():
            for cp in block_dma(slot0, b, 0):
                cp.start()

        def row_of_group(tile):
            """Row ``r`` of a (KH, 16, n) group tile, as f32 (KH, n): a
            masked sum, because a packed sublane takes no dynamic
            index."""
            tile = tile.astype(jnp.float32)
            which = jax.lax.broadcasted_iota(jnp.int32, tile.shape, 1)
            return jnp.sum(jnp.where(which == r, tile, 0.0), axis=1)

        def body(i, _):
            slot = (slot0 + i) % 2

            @pl.when(i + 1 < n)
            def _prefetch():
                for cp in block_dma(1 - slot, b, i + 1):
                    cp.start()

            @pl.when(i + 1 == n)
            def _prefetch_next_row():
                nxt = jax.lax.while_loop(
                    lambda j: (j < n_rows)
                    & (n_blocks(jnp.minimum(j, n_rows - 1)) == 0),
                    lambda j: j + 1,
                    b + 1,
                )

                @pl.when(nxt < n_rows)
                def _start():
                    for cp in block_dma(1 - slot, nxt, 0):
                        cp.start()

                state[0] = 1 - slot
                state[1] = (nxt < n_rows).astype(jnp.int32)

            for cp in block_dma(slot, b, i):
                cp.wait()
            start = pl.multiple_of(i * bt, bt)
            t_idx = (
                jax.lax.broadcasted_iota(jnp.int32, (kh, g, bt), 2) + i * bt
            )
            _online_update(
                q_ref[r],
                kbuf[slot],
                vbuf[slot],
                row_of_group(ks_ref[0, :, :, pl.ds(start, bt)]),
                row_of_group(vs_ref[0, :, :, pl.ds(start, bt)]),
                t_idx < length,
                m_ref.at[r],
                l_ref.at[r],
                acc_ref.at[r],
                scale,
            )
            return 0

        jax.lax.fori_loop(0, n, body, 0)
        return 0

    jax.lax.fori_loop(0, rows, walk_row, 0)

    denom = jnp.maximum(l_ref[:, :, :1], 1e-30)
    o_ref[:] = (
        (acc_ref[:] / denom).reshape(rows, kh, g, -1).astype(o_ref.dtype)
    )


def _decode_kernel_vmem_bytes(
    block_t: int, width: int, n_kv: int, g: int, hd: int, c: int
) -> int:
    """VMEM the contiguous kernel holds: the ping-pong k/v blocks, and
    for the row's group of 16 the double-buffered scale planes, append
    slots as they come in and as they go out again, fresh rows, queries
    and outputs, plus the online-softmax scratch."""
    rows = _ROW_GROUP
    return n_kv * (
        2 * 2 * block_t * hd  # k/v block double buffers (int8)
        + 2 * 2 * rows * width * 2  # k/v scale planes (bf16) x 2 buffers
        # append values + scales, in and out, x 2 buffers each
        + 2 * 2 * 2 * rows * c * (hd + 2)
        + 2 * 2 * rows * (hd + 2)  # the step's fresh rows x 2 buffers
        + 2 * 2 * rows * g * hd * 4  # q + output blocks (f32 worst case)
        + rows * (2 * 128 + hd) * g * 4  # m/l/acc f32 scratch
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
    cache_len: int | None = None,
    append_width: int = 0,
    mesh=None,
    backend=None,
) -> bool:
    """Dispatch predicate for the decode kernel.

    Single-token decode on a single TPU chip with an int8 cache and
    MXU/tile-aligned shapes; everything else falls back to the XLA path
    (which is also the reference implementation for tests).
    ``cache_len`` is the cache's slot count (``window`` when the caller
    does not say): the kernel walks the cache itself in blocks that must
    tile it, and ``window`` only sizes the scale planes it holds.
    """
    if os.environ.get("GAIE_DISABLE_DECODE_KERNEL"):
        return False
    if s != 1 or not kv_int8:
        return False
    if not _interpret_mode():
        if (backend or platform_of(mesh)) != "tpu" or not one_device(mesh):
            return False
    t = cache_len or window
    g = max(n_q // max(n_kv, 1), _MIN_GROUP)
    return (
        batch % _ROW_GROUP == 0
        # Exact-tiling gate, mirroring ``_block_t``: a cache of whole
        # 128-slot tiles walks in 512-, 256- or 128-slot blocks, and a
        # shorter one (the small pow2 buckets 32 and 64 — reachable from
        # any short-context decode) is a single block, whole int8
        # sublane tiles deep.  tests/test_decode_attention.py pins the
        # gate for every reachable bucket; tests/test_chip_compile.py
        # asks the v5e compiler about a 64-slot window of a 256-slot
        # cache.
        and (t % 128 == 0 or (t <= 256 and t % 32 == 0))
        and head_dim % 128 == 0
        and n_q % n_kv == 0
        and g <= 16
        and _decode_kernel_vmem_bytes(
            _block_t(t, window), _scale_width(window, t), n_kv, g,
            head_dim, append_width,
        )
        <= _VMEM_BUDGET_BYTES
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
    copies OOM at serving batch (seen on the chip in 2026-07, before the
    ledger).  When
    :func:`use_decode_kernel` also holds, attention runs in the Pallas
    kernel; otherwise :func:`decode_gqa_attention_xla` computes the same
    contract with einsums — slower (it materializes the per-layer KV
    window) but correct at full batch.  Off-TPU the scatter path stays
    the default test oracle; ``GAIE_FORCE_APPEND_BUFFER=1`` opts in.
    """
    if s != 1 or not kv_int8:
        return False
    if use_decode_kernel(
        s=s, kv_int8=kv_int8, batch=batch, window=window,
        n_q=n_q, n_kv=n_kv, head_dim=head_dim, mesh=mesh, backend=backend,
    ):
        return True
    if n_q % n_kv != 0:
        return False
    if os.environ.get("GAIE_FORCE_APPEND_BUFFER"):
        return True
    return (backend or platform_of(mesh)) == "tpu" and one_device(mesh)


@jax.named_scope("kv_write")
def write_append_rows(leaf, fresh, layer, slot):
    """An append leaf (L, KH, B, C, ...) with ``fresh`` (B, S, KH, ...)
    at slots [slot, slot + S) of layer ``layer``, every row: XLA's form
    of the write (a contiguous ``dynamic_update_slice``), the decode
    twin's with S 1."""
    fresh_t = jnp.transpose(fresh, (2, 0, 1) + tuple(range(3, fresh.ndim)))
    return jax.lax.dynamic_update_slice(
        leaf,
        fresh_t[None],
        (jnp.asarray(layer, jnp.int32), 0, 0, jnp.asarray(slot, jnp.int32))
        + (0,) * (leaf.ndim - 4),
    )


def _slice_layer_window(buf, li, w):
    """Layer ``li``'s first ``w`` slots of a (L, KH, B, T, ...) buffer:
    (KH, B, w, ...)."""
    return jax.lax.dynamic_slice(
        buf,
        (li,) + (0,) * (buf.ndim - 1),
        (1,) + buf.shape[1:3] + (w,) + buf.shape[4:],
    )[0]


def _cache_buffer_attention_xla(
    q, k8, v8, ks, vs, layer, kv_lengths, append, buf_base, *, window
):
    """The XLA math of the append-buffer decode: slice layer ``layer``'s
    first ``window`` slots out of the stacked (L, KH, B, T, ...) cache
    (and the whole of the append buffer's layer), then attend both.

    ``q`` is (B, 1, n_q, HD), the fresh token's queries; the cache's
    window slot ``t`` contributes iff ``t < kv_lengths[b]`` and the
    (optional) append buffer contributes slots ``j <= buf_base``, the
    ones written so far.  Masked window slots contribute EXACT zeros
    (``where`` before the max + multiplicative mask), so two callers
    whose windows agree on the unmasked slots produce bit-identical
    outputs regardless of what garbage fills the rest.
    """
    li = jnp.asarray(layer, jnp.int32)
    append_w = None
    if append is not None:
        append_w = tuple(
            _slice_layer_window(leaf, li, leaf.shape[3]) for leaf in append
        )
    k_w = _slice_layer_window(k8, li, window)
    v_w = _slice_layer_window(v8, li, window)
    ks_w = _slice_layer_window(ks, li, window)
    vs_w = _slice_layer_window(vs, li, window)
    b, s, n_q, hd = q.shape
    n_kv = k_w.shape[0]
    g = n_q // n_kv
    scale = hd**-0.5

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
        j_idx = jnp.arange(k_ab.shape[2], dtype=jnp.int32)
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
):
    """XLA twin of :func:`decode_gqa_attention` — identical contract,
    einsum math, no shape-alignment requirements.

    The full-batch fallback when the Pallas kernel is off
    (``GAIE_DISABLE_DECODE_KERNEL``), unsupported (odd shapes), or
    regressed: it keeps the append-buffer protocol — the big cache is
    only ever SLICED here, never scattered into — so the decode
    executable shares the kernel path's memory/layout profile instead of
    the scatter path's (which OOMs at serving batch).  Cost vs the
    kernel: the per-layer KV window materializes as an XLA slice (the
    round-2 4.3 ms/step item the kernel exists to kill), and the step's
    fresh rows go into the append leaves as four
    ``dynamic_update_slice`` (:func:`write_append_rows`), which on the
    chip cost what PERF.md section 6, PR 54 says.
    """
    if append is not None:
        leaves, fresh, slot = append
        buf = tuple(
            write_append_rows(leaf, new[:, None], layer, slot)
            for leaf, new in zip(leaves, fresh)
        )
        buf_base = jnp.asarray(slot, jnp.int32)
    else:
        buf, buf_base = None, jnp.int32(0)
    out = _cache_buffer_attention_xla(
        q[:, None], k8, v8, ks, vs, layer, kv_lengths, buf, buf_base,
        window=window,
    )[:, 0]
    return out if buf is None else (out, buf)


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
):
    """Decode attention for one layer of the stacked cache.

    Args:
      q: (B, n_q_heads, HD) — the single decode token's queries, rope
        already applied.
      k8, v8: (L, KH, B, T, HD) int8 stacked cache values; they stay in
        HBM and the kernel copies the blocks each row owns.
      ks, vs: (L, KH, B, T) bf16 dequant scales.
      layer: int32 scalar — which layer's cache to read.
      kv_lengths: (B,) int32 — cache slots [0, kv_lengths[b]) are
        attended, and they are what is read: row ``b`` costs
        ``ceil(kv_lengths[b] / block)`` block copies.  0 means the row
        reads nothing from the cache: it attends the append buffer
        alone (exact zeros without one), which is what the decode chunk
        asks for rows that do not decode.
      append: optional ``(leaves, fresh, slot)`` — the decode chunk's
        append buffer and this step's part in it.  ``leaves`` are the
        buffer's four (``models.llama.init_append_buffer``): values
        (L, KH, B, C, HD) int8, scales (L, KH, B, C) bf16.  ``fresh``
        is the step's quantised K/V as ``_quantize_kv`` makes it, ``k8,
        v8`` (B, KH, HD) int8 and ``ks, vs`` (B, KH) bf16, and ``slot``
        an int32 scalar (all rows share it).  The kernel itself puts
        every row's fresh K/V into slot ``slot`` of layer ``layer``,
        whether the row decodes or not (the chunk's flush reads every
        row), then folds slots [0, slot] before the rows' walks, 16 rows
        at a time.  The leaves come back as outputs aliased onto their
        inputs: the call writes a group's blocks of the one layer and
        nothing else touches the buffers.
      window: static; the caller guarantees ``kv_lengths <= window``.
        It no longer bounds the kernel's reads of k/v (the lengths do);
        it sizes the scale planes a program holds for its 16 rows, and
        a length beyond it is clipped to it.

    Returns:
      (B, n_q_heads, HD) in q's dtype; with ``append``, a pair of that
      and the four leaves with the fresh rows in.
    """
    if interpret is None:
        interpret = _interpret_mode()
    b, n_q, hd = q.shape
    n_kv = k8.shape[1]
    q = q.reshape(b, n_kv, n_q // n_kv, hd)
    if q.shape[2] < _MIN_GROUP:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, _MIN_GROUP - q.shape[2]), (0, 0)))
    g = q.shape[2]
    cache_len = k8.shape[3]
    bt = _block_t(cache_len, window)
    width = _scale_width(window, cache_len)
    has_ab = append is not None
    rows = _ROW_GROUP

    def group_map(*tail):
        return lambda gi, li, slot, lens: (gi,) + tail

    def layer_group_map(*tail):
        return lambda gi, li, slot, lens: (li[0], 0, gi) + tail

    in_specs = [
        pl.BlockSpec((rows, n_kv, g, hd), group_map(0, 0, 0)),
        pl.BlockSpec(memory_space=pl.ANY),  # k cache stays in HBM
        pl.BlockSpec(memory_space=pl.ANY),  # v cache stays in HBM
        pl.BlockSpec((1, n_kv, rows, width), layer_group_map(0)),
        pl.BlockSpec((1, n_kv, rows, width), layer_group_map(0)),
    ]
    operands = [q, k8, v8, ks, vs]
    out_specs = [pl.BlockSpec((rows, n_kv, g, hd), group_map(0, 0, 0))]
    out_shape = [jax.ShapeDtypeStruct((b, n_kv, g, hd), q.dtype)]
    aliases = {}
    if has_ab:
        leaves, fresh, slot = append
        c = leaves[0].shape[3]
        ab_specs = 2 * [
            pl.BlockSpec((1, n_kv, rows, c, hd), layer_group_map(0, 0))
        ] + 2 * [pl.BlockSpec((1, n_kv, rows, c), layer_group_map(0))]
        # The leaves go out as they came in, a group's blocks of one
        # layer rewritten: operands count from the three prefetched
        # scalars, outputs from the attention's.
        aliases = {3 + len(operands) + i: 1 + i for i in range(4)}
        in_specs += ab_specs
        in_specs += 2 * [pl.BlockSpec((rows, n_kv, hd), group_map(0, 0))]
        in_specs += 2 * [pl.BlockSpec((rows, n_kv), group_map(0))]
        operands += [*leaves, *fresh]
        out_specs += ab_specs
        # Held to HBM, and through the aliases the operands with them:
        # left to its own choice XLA brings a whole scale leaf into VMEM
        # before every call and takes it out again after (two copies of
        # 12.6 MB a layer call at Ouro's widths, PERF.md section 6, PR 54).
        out_shape += [pltpu.HBM(x.shape, x.dtype) for x in leaves]
        slot = jnp.asarray(slot, jnp.int32).reshape(1)
    else:
        slot = jnp.zeros((1,), jnp.int32)

    out, *leaves = pl.pallas_call(
        functools.partial(
            _decode_kernel,
            block_t=bt,
            scale=hd**-0.5,
            has_ab=has_ab,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b // rows,),
            in_specs=in_specs,
            out_specs=out_specs,
            scratch_shapes=[
                pltpu.VMEM((2, n_kv, bt, hd), jnp.int8),
                pltpu.VMEM((2, n_kv, bt, hd), jnp.int8),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.SMEM((2,), jnp.int32),
                pltpu.VMEM((rows, n_kv * g, 128), jnp.float32),
                pltpu.VMEM((rows, n_kv * g, 128), jnp.float32),
                pltpu.VMEM((rows, n_kv * g, hd), jnp.float32),
            ],
        ),
        out_shape=out_shape,
        input_output_aliases=aliases,
        compiler_params=pltpu.CompilerParams(
            # In order on one core: the buffer slot and the next row's
            # first copy ride from one program to the next.
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_BUDGET_BYTES,
        ),
        interpret=interpret,
        name="decode_gqa_attention",
    )(
        jnp.asarray(layer, jnp.int32).reshape(1),
        slot,
        kv_lengths.astype(jnp.int32),
        *operands,
    )
    out = out[:, :, : n_q // n_kv].reshape(b, n_q, hd)
    return (out, tuple(leaves)) if has_ab else out
