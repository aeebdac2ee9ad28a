"""Pallas TPU attention over a GQA layer's state rows
(``models/hybrid.py``'s ``full``, ``cca`` and ``window`` mixers).  Over
rows a position (``full``, ``cca``): a walk over the rows a slot holds,
where :func:`ops.gqa.attend_rows` reads the rows it could hold; two
kernels under one layout contract, the decode step's
(:func:`attend_rows_walk`, below) and a prefill chunk's
(:func:`attend_rows_chunk`, after it).  Over a ring (``window``): a
prefill chunk's (:func:`attend_ring_chunk`, at the end of the file), the
flash form of :func:`ops.gqa.attend_ring`.  What still reaches XLA's
forms: every call the gates refuse (float32 state, several devices, the
CPU without the interpret switch, shapes off the tiles or past the VMEM
budget) and a window layer's decode step, which keeps ``attend_ring``'s
wide product over the ring as it lies.

A decode step (one token a row, or a token and its draft) attends over
``(b, T, KH * D)`` rows of which a slot of length ``n`` holds ``n``.
XLA's form reads every slot's first ``window`` rows and masks: at 32
slots x 8,192 that is 2 x 537 MB a layer a step for rows that hold a
third of it, with float32 scores written and read back (PERF.md, PR 34).
The kernel leaves K and V in HBM and walks each row's own
``ceil(length / block)`` blocks with double-buffered copies, none for a
row of length 0, keeping a float32 online softmax; a KV head is a
lane-aligned slice of the block in VMEM, so nothing is copied out of the
rows and the queries are not widened.

Layout contract: ``k_rows``, ``v_rows`` ``(b, T, KH * D)``, row ``t`` the
keys of position ``t``, **written before they are read** (the mixer
writes the step's rows, then attends: no append buffer).  Key ``t`` is
visible to query ``i`` iff ``t <= q_pos[b, i]`` and ``t < window``; a row
whose length is 0 reads nothing and yields exact zeros.  ``window`` is a
static upper bound of the walk, not what is read.

:func:`ops.decode_attention.decode_gqa_attention` is the model (block
choice, the copy that runs ahead from row to row, the VMEM budget, the
interpret hook); this one has no int8 scales, no append buffer and no
layer index.

A prefill chunk (16-256 queries a row at consecutive positions, 1-8 rows
a program) had float32 scores of (heads, 256, window) written and read
back five times or more by XLA, a row at a time (3.0 ms a layer and row
for 32 heads at 8,192, 0.41 at 2,048; gathered first from ``leaf[slot,
:window]``: PERF.md, PR 41).  The chunk kernel takes which slot each row
of the call is as an operand (that IS the gather) and walks the blocks
that slot holds up to the chunk's last position that counts, one KV head
of one chunk a grid step: its queries head after head (heads x queries,
D), the block's lanes of that head alone copied, the online softmax kept
for 512 query rows a product.  Only the block or two that overlap the
chunk's own positions take the causal compare.

A window layer's prefill chunk had float32 scores of (rows, heads, 256,
R + 256) written and read back by XLA for all rows at once (1.0 ms a
layer for four rows of 32 heads over 1,280 keys, 0.2 in the kernel:
PERF.md, PR 42).  The ring kernel holds one KV head of one row's ring in
VMEM whole (``R x D``), works out which position each ring row holds from
the chunk's first position, and runs the chunk kernel's online softmax
over the ring and then the call's own rows.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from generativeaiexamples_tpu.ops.decode_attention import _ROW_GROUP, _block_t, _interpret_mode
from generativeaiexamples_tpu.ops.dispatch import one_device, platform_of
from generativeaiexamples_tpu.ops.gqa import _NEG, _STEP_QUERIES
from generativeaiexamples_tpu.ops.qmm import _VMEM_BUDGET_BYTES

F32 = jnp.float32
_STAT_LANES = 128  # lanes of a running maximum or sum (one lane tile)


def _row_group(b: int) -> int:
    """Rows whose queries and outputs ride in one VMEM block: the llama
    kernel's 16 where they divide the batch."""
    return next(r for r in (_ROW_GROUP, 8, 4, 2, 1) if b % r == 0)


def walk_lengths(q_pos, n_valid, window: int):
    """(b,) int32 rows each slot's walk covers: up to its last query's
    position, at most ``window``; 0 for a row with nothing that counts."""
    n = jnp.minimum(q_pos[:, -1].astype(jnp.int32) + 1, window)
    return jnp.where(n_valid > 0, n, 0)


def rows_walked(lengths, rows: int, window: int):
    """Rows of K (and as many of V) one call copies for walks of these
    lengths: each in whole blocks."""
    bt = _block_t(rows, window)
    return jnp.sum((lengths + bt - 1) // bt * bt).astype(jnp.int32)


def _walk_vmem_bytes(block_t: int, width: int, group: int, heads: int, d: int) -> int:
    """VMEM the kernel holds over bf16 rows: the ping-pong K and V blocks,
    the group's double-buffered queries and outputs, the online-softmax
    scratch."""
    return (
        2 * 2 * block_t * width * 2
        + 2 * 2 * group * heads * d * 2
        + heads * (2 * _STAT_LANES + d) * 4
    )


def _bf16_rows_on_one_chip(q_dtype, rows_dtype, width: int, head_dim: int, mesh) -> bool:
    """What both gates ask first: bf16 queries over bf16 rows, rows and
    heads of whole lane tiles, one TPU device (or the interpret hook)."""
    if not jnp.dtype(q_dtype) == jnp.dtype(rows_dtype) == jnp.bfloat16:
        return False
    if not _interpret_mode() and (platform_of(mesh) != "tpu" or not one_device(mesh)):
        return False
    return width % 128 == 0 and head_dim % 128 == 0


def use_row_walk(
    *, s: int, q_dtype, rows_dtype, width: int, head_dim: int, rows: int, window: int,
    batch: int, n_q: int, mesh=None, apart: bool = False,
) -> bool:
    """The gate, from what a traced step can observe: a decode step
    (``s <= _STEP_QUERIES`` queries a row, every row in one call) of bf16
    queries over bf16 rows of whole lane tiles and whole blocks, on one
    TPU device.  Everything else is :func:`ops.gqa.attend_rows`'."""
    if s > _STEP_QUERIES or apart:
        return False
    if not _bf16_rows_on_one_chip(q_dtype, rows_dtype, width, head_dim, mesh):
        return False
    bt = _block_t(rows, window)
    return (
        rows % bt == 0
        and bt % 16 == 0  # whole bf16 sublane tiles
        and _walk_vmem_bytes(bt, width, _row_group(batch), s * n_q, head_dim)
        <= _VMEM_BUDGET_BYTES
    )


def _walk_kernel(
    len_ref,  # scalar prefetch: (B,) int32 rows each slot's walk covers
    pos_ref,  # scalar prefetch: (B * s,) int32 the queries' positions
    q_ref,  # (rows, KH, s * G, D): the program's group of rows
    k_hbm,  # (B, T, KH * D): stays in HBM (pl.ANY)
    v_hbm,  # (B, T, KH * D): stays in HBM
    o_ref,  # (rows, KH, s * G, D)
    kbuf,  # (2, block_t, KH * D) VMEM
    vbuf,
    sem,  # DMA (2 slots, K and V)
    state,  # SMEM (2,)
    m_ref,  # (KH, s * G, 128) float32
    l_ref,
    acc_ref,  # (KH, s * G, D) float32
    *,
    block_t: int,
    s: int,
    scale: float,
):
    """A group of rows, each walked over its own blocks across all its KV
    heads.  Block ``i + 1`` is fetched while block ``i`` computes, and
    during a row's last block the first block of the next row that has
    any (of this group or a later one): the grid runs in order on one
    core, so the buffer slot and the "my first block is on its way" flag
    ride from row to row and group to group in SMEM, and only the call's
    first copy is exposed."""
    rows, kh, sg, d = q_ref.shape
    g = sg // s
    bt = block_t
    first_row = pl.program_id(0) * rows
    n_rows = pl.num_programs(0) * rows

    def n_blocks(row):
        return (len_ref[row] + bt - 1) // bt

    def block_dma(slot, row, i):
        start = pl.multiple_of(i * bt, bt)
        return tuple(
            pltpu.make_async_copy(
                hbm.at[row, pl.ds(start, bt)], buf.at[slot], sem.at[slot, j]
            )
            for j, (hbm, buf) in enumerate(((k_hbm, kbuf), (v_hbm, vbuf)))
        )

    # state[0]: buffer slot of the next block to compute; state[1]: 1 if
    # an earlier row already started the next walked row's first copy.
    @pl.when(first_row == 0)
    def _reset():
        state[0] = 0
        state[1] = 0

    def walk_row(r, _):
        b = first_row + r
        length = len_ref[b]
        n = n_blocks(b)
        slot0 = state[0]

        @pl.when((n > 0) & (state[1] == 0))
        def _first():
            for cp in block_dma(slot0, b, 0):
                cp.start()

        m_ref[:] = jnp.full_like(m_ref, _NEG)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)
        # The last key each of a head's s * G query rows may see: query
        # ``i`` of the step owns rows [i * G, (i + 1) * G).
        query = jax.lax.broadcasted_iota(jnp.int32, (sg, bt), 0) // g
        last = jnp.full((sg, bt), pos_ref[b * s], jnp.int32)
        for i in range(1, s):
            last = jnp.where(query == i, pos_ref[b * s + i], last)
        last = jnp.minimum(last, length - 1)

        def body(i, _):
            slot = (slot0 + i) % 2

            @pl.when(i + 1 < n)
            def _prefetch():
                for cp in block_dma(1 - slot, b, i + 1):
                    cp.start()

            @pl.when(i + 1 == n)
            def _prefetch_next_row():
                nxt = jax.lax.while_loop(
                    lambda j: (j < n_rows) & (n_blocks(jnp.minimum(j, n_rows - 1)) == 0),
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
            t_idx = jax.lax.broadcasted_iota(jnp.int32, (sg, bt), 1) + i * bt
            mask = t_idx <= last
            for h in range(kh):
                lanes = pl.ds(h * d, d)
                k = kbuf[slot, :, lanes]  # (bt, D)
                v = vbuf[slot, :, lanes]
                sc = jax.lax.dot_general(
                    q_ref[r, h], k, (((1,), (1,)), ((), ())), preferred_element_type=F32
                ) * scale
                sc = jnp.where(mask, sc, _NEG)
                m_prev, l_prev = m_ref[h, :, :1], l_ref[h, :, :1]
                m_new = jnp.maximum(m_prev, jnp.max(sc, axis=-1, keepdims=True))
                alpha = jnp.exp(m_prev - m_new)
                # The multiplicative mask keeps a query that sees nothing
                # of this block (or of any) at exact zeros.
                p = jnp.exp(sc - m_new) * mask
                l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
                pv = jax.lax.dot_general(
                    p.astype(v.dtype), v, (((1,), (0,)), ((), ())), preferred_element_type=F32
                )
                acc_ref[h] = acc_ref[h] * alpha + pv
                m_ref[h] = jnp.broadcast_to(m_new, m_ref.shape[1:])
                l_ref[h] = jnp.broadcast_to(l_new, l_ref.shape[1:])
            return 0

        jax.lax.fori_loop(0, n, body, 0)
        o_ref[r] = (acc_ref[:] / jnp.maximum(l_ref[:, :, :1], 1e-30)).astype(o_ref.dtype)
        return 0

    jax.lax.fori_loop(0, rows, walk_row, 0)


def attend_rows_walk(q, k_rows, v_rows, q_pos, lengths, *, n_kv: int, window: int, interpret=None):
    """:func:`ops.gqa.attend_rows` over the first ``window`` rows, for a
    decode step: q (b, s, H, D) rotated, ``s <= 2``; k_rows, v_rows
    (b, T, KH * D) whole (not cut to the window); q_pos (b, s);
    ``lengths`` (b,) from :func:`walk_lengths`.  Returns (b, s, H, D) in
    q's dtype."""
    if interpret is None:
        interpret = _interpret_mode()
    bt = _block_t(k_rows.shape[1], window)
    return _walk(q, k_rows, v_rows, q_pos, lengths, n_kv=n_kv, block_t=bt, interpret=interpret)


# A function of its own under ``jit``: the window enters through the
# block alone, so the layers of a step and the decode chunks of every
# window share one trace of the kernel, and a program lowers it once a
# shape (tracing it anew for each cost K-EXAONE's cell 13 s of set-up).
@functools.partial(jax.jit, static_argnames=("n_kv", "block_t", "interpret"))
def _walk(q, k_rows, v_rows, q_pos, lengths, *, n_kv: int, block_t: int, interpret: bool):
    b, s, h, d = q.shape
    g = h // n_kv
    width = k_rows.shape[2]
    rows = _row_group(b)
    # A KV head's queries side by side: (b, KH, s * G, D).
    qh = q.reshape(b, s, n_kv, g, d).transpose(0, 2, 1, 3, 4).reshape(b, n_kv, s * g, d)
    group = pl.BlockSpec((rows, n_kv, s * g, d), lambda gi, lens, pos: (gi, 0, 0, 0))
    out = pl.pallas_call(
        functools.partial(_walk_kernel, block_t=block_t, s=s, scale=d**-0.5),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b // rows,),
            in_specs=[
                group,
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=group,
            scratch_shapes=[
                pltpu.VMEM((2, block_t, width), k_rows.dtype),
                pltpu.VMEM((2, block_t, width), v_rows.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.SMEM((2,), jnp.int32),
                pltpu.VMEM((n_kv, s * g, _STAT_LANES), F32),
                pltpu.VMEM((n_kv, s * g, _STAT_LANES), F32),
                pltpu.VMEM((n_kv, s * g, d), F32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct(qh.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            # In order on one core: the buffer slot and the next row's
            # first copy ride from one program to the next.
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_BUDGET_BYTES,
        ),
        interpret=interpret,
        name="gqa_rows_decode_attention",
    )(lengths.astype(jnp.int32), q_pos.astype(jnp.int32).reshape(b * s), qh, k_rows, v_rows)
    return out.reshape(b, n_kv, s, g, d).transpose(0, 2, 1, 3, 4).reshape(b, s, h, d)


# -- a prefill chunk ---------------------------------------------------------------

# Query rows of one product and one online-softmax update: two heads' 256
# queries, or more heads of a KV head side by side where the chunk is
# shorter.  On the v5e (my chip call 2, PR 41: 32 heads x 256 queries over
# 6,400 rows of 8,192) 256 rows take 0.296 ms, 512 0.262, and blocks of
# 1,024 or 2,048 rows no less than the walk's 512.
_CHUNK_TILE = 512


def chunk_lengths(pos, valid, window: int):
    """(b,) int32 rows each chunk's walk covers: up to its last position
    that counts, at most ``window``; 0 for a row with none (a group's
    padding)."""
    last = jnp.max(jnp.where(valid, pos.astype(jnp.int32), -1), axis=1)
    return jnp.minimum(last + 1, window).astype(jnp.int32)


def _chunk_tile(rows: int) -> int:
    """Query rows a product of a KV head's ``rows`` (heads x queries)."""
    return next(t for t in (_CHUNK_TILE, 256, 128, 64, 32, 16, rows) if rows % t == 0)


def _chunk_vmem_bytes(block_t: int, rows: int, d: int) -> int:
    """VMEM the chunk kernel holds for one row's KV head: the ping-pong K
    and V blocks of that head, its double-buffered queries and outputs,
    the online-softmax scratch."""
    return 2 * 2 * block_t * d * 2 + 2 * 2 * rows * d * 2 + rows * (2 * _STAT_LANES + d) * 4


def use_row_chunk(
    *, s: int, q_dtype, rows_dtype, width: int, head_dim: int, rows: int, window: int,
    n_q: int, mesh=None,
) -> bool:
    """The chunk kernel's gate, from what a traced program can observe: a
    prefill chunk (``s > _STEP_QUERIES`` queries a row at consecutive
    positions) of bf16 queries over bf16 rows of whole lane tiles and
    whole blocks, on one TPU device.  Everything else is
    :func:`ops.gqa.attend_rows`'."""
    if s <= _STEP_QUERIES:
        return False
    if not _bf16_rows_on_one_chip(q_dtype, rows_dtype, width, head_dim, mesh):
        return False
    bt = _block_t(rows, window)
    per_kv = s * n_q // (width // head_dim)  # a KV head's query rows
    return (
        rows % bt == 0
        and bt % 128 == 0  # scores of whole lane tiles
        and per_kv % 16 == 0  # whole bf16 sublane tiles
        and _chunk_vmem_bytes(bt, per_kv, head_dim) <= _VMEM_BUDGET_BYTES
    )


def _head_after_head(q, n_kv: int):
    """q (b, s, H, D) as a KV head's queries, head after head:
    (b, KH, G * s, D), row ``r`` of a KV head query ``r % s``."""
    b, s, h, d = q.shape
    g = h // n_kv
    return q.reshape(b, s, n_kv, g, d).transpose(0, 2, 3, 1, 4).reshape(b, n_kv, g * s, d)


def _query_after_query(out, s: int):
    """:func:`_head_after_head`'s inverse: (b, KH, G * s, D) -> (b, s, H, D)."""
    b, n_kv, gs, d = out.shape
    g = gs // s
    return out.reshape(b, n_kv, g, s, d).transpose(0, 3, 1, 2, 4).reshape(b, s, n_kv * g, d)


def _chunk_update(sc, mask, v, m_ref, l_ref, acc_ref):
    """One block into a float32 online softmax of many query rows: ``sc``
    (R, block) scaled scores, ``mask`` (R, block) which keys each row may
    see (None: all), ``v`` (block, D) the block's values.  What a block
    costs beside its products is the reductions across lanes, one a row
    and statistic (0.57 ms for 32 heads x 256 queries over 6,400 rows with
    the walk's update, 0.30 with this one: my chip call 2, PR 41), so the
    running maximum is kept in every lane of ``m_ref`` (R, 128), where it
    meets the scores tile by tile and the accumulator ``acc_ref`` (R, D) as
    it lies, and ``l_ref`` (R, 128) keeps a sum a LANE (the probabilities'
    lane tiles added up), reduced across lanes once, when the walk ends.
    The probabilities go to the values' dtype before their product, as
    ``gqa._pv``'s do."""
    lanes = m_ref.shape[1]
    tiles = sc.shape[1] // lanes
    if mask is not None:
        sc = jnp.where(mask, sc, _NEG)
    m_prev = m_ref[...]
    top = sc[:, :lanes]
    for j in range(1, tiles):
        top = jnp.maximum(top, sc[:, j * lanes : (j + 1) * lanes])
    m_new = jnp.maximum(m_prev, jnp.max(top, axis=-1, keepdims=True))  # (R, 128), a value a row
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(sc - jnp.tile(m_new, (1, tiles)))
    if mask is not None:
        p = p * mask  # a query that sees nothing stays at exact zeros
    part = p[:, :lanes]
    for j in range(1, tiles):
        part = part + p[:, j * lanes : (j + 1) * lanes]
    pv = jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())), preferred_element_type=F32
    )
    d = acc_ref.shape[1]
    acc_ref[...] = acc_ref[...] * jnp.tile(alpha, (1, d // lanes)) + pv
    l_ref[...] = l_ref[...] * alpha + part
    m_ref[...] = m_new


def _chunk_kernel(
    len_ref,  # scalar prefetch: (B,) int32 rows each chunk's walk covers
    slot_ref,  # scalar prefetch: (B,) int32 the slot whose rows a chunk reads
    pos_ref,  # scalar prefetch: (B,) int32 the position of a chunk's first query
    q_ref,  # (1, 1, G * s, D): one KV head's queries of one chunk, head-major
    k_hbm,  # (slots, T, KH * D): stays in HBM (pl.ANY)
    v_hbm,
    o_ref,  # (1, 1, G * s, D)
    kbuf,  # (2, block_t, D) VMEM: one KV head's lanes of a block
    vbuf,
    sem,  # DMA (2 slots, K and V)
    state,  # SMEM (2,)
    m_ref,  # (G * s, 128) float32
    l_ref,
    acc_ref,  # (G * s, D) float32
    *,
    block_t: int,
    s: int,
    tile: int,
    scale: float,
):
    """One chunk's queries of one KV head, walked over the blocks its slot
    holds.  The grid runs (chunk, KV head) in order on one core, so the
    copies run ahead as the walk's do: block ``i + 1`` while block ``i``
    computes, and during a program's last block the first block of the
    next program that has any (the chunk's next head, or the next chunk
    that is no padding).  A block whose every key lies at or before the
    chunk's first position needs no causal compare; the one or two that
    overlap the chunk's own positions take it."""
    b, h = pl.program_id(0), pl.program_id(1)
    n_chunks, kh = pl.num_programs(0), pl.num_programs(1)
    gs, d = q_ref.shape[2:]
    bt = block_t
    length, first = len_ref[b], pos_ref[b]

    def n_blocks(row):
        return (len_ref[row] + bt - 1) // bt

    def block_dma(buf, row, head, i):
        start = pl.multiple_of(i * bt, bt)
        lanes = pl.ds(pl.multiple_of(head * d, d), d)
        return tuple(
            pltpu.make_async_copy(
                hbm.at[slot_ref[row], pl.ds(start, bt), lanes], vm.at[buf], sem.at[buf, j]
            )
            for j, (hbm, vm) in enumerate(((k_hbm, kbuf), (v_hbm, vbuf)))
        )

    # state[0]: buffer slot of the next block to compute; state[1]: 1 if
    # an earlier program already started this one's first copy.
    @pl.when((b == 0) & (h == 0))
    def _reset():
        state[0] = 0
        state[1] = 0

    n = n_blocks(b)
    buf0 = state[0]

    @pl.when((n > 0) & (state[1] == 0))
    def _first():
        for cp in block_dma(buf0, b, h, 0):
            cp.start()

    m_ref[...] = jnp.full_like(m_ref, _NEG)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)

    def block(i, masked: bool):
        buf = (buf0 + i) % 2

        @pl.when(i + 1 < n)
        def _prefetch():
            for cp in block_dma(1 - buf, b, h, i + 1):
                cp.start()

        @pl.when(i + 1 == n)
        def _prefetch_next_program():
            row = jax.lax.while_loop(
                lambda j: (j < n_chunks) & (n_blocks(jnp.minimum(j, n_chunks - 1)) == 0),
                lambda j: j + 1,
                b + 1,
            )
            same = h + 1 < kh
            more = same | (row < n_chunks)

            @pl.when(more)
            def _start():
                nxt = jnp.where(same, b, jnp.minimum(row, n_chunks - 1))
                for cp in block_dma(1 - buf, nxt, jnp.where(same, h + 1, 0), 0):
                    cp.start()

            state[0] = 1 - buf
            state[1] = more.astype(jnp.int32)

        for cp in block_dma(buf, b, h, i):
            cp.wait()
        k, v = kbuf[buf], vbuf[buf]  # (bt, D)

        def product(j, _):
            rows = pl.ds(pl.multiple_of(j * tile, tile), tile)
            sc = jax.lax.dot_general(
                q_ref[0, 0, rows, :], k, (((1,), (1,)), ((), ())), preferred_element_type=F32
            ) * scale
            mask = None
            if masked:
                # Row ``r`` of a KV head's queries is query ``r % s`` of
                # the chunk, at position ``first + r % s``.
                query = (jax.lax.broadcasted_iota(jnp.int32, (tile, bt), 0) + j * tile) % s
                last = jnp.minimum(first + query, length - 1)
                mask = jax.lax.broadcasted_iota(jnp.int32, (tile, bt), 1) + i * bt <= last
            _chunk_update(sc, mask, v, m_ref.at[rows], l_ref.at[rows], acc_ref.at[rows])
            return 0

        jax.lax.fori_loop(0, gs // tile, product, 0)
        return 0

    # Blocks that end at or before the first query's position (and inside
    # the length) are seen whole by every query.
    whole = jnp.minimum(jnp.maximum(jnp.minimum(first, length - 1) + 1, 0) // bt, n)
    jax.lax.fori_loop(0, whole, lambda i, _: block(i, False), 0)
    jax.lax.fori_loop(whole, n, lambda i, _: block(i, True), 0)
    total = jnp.sum(l_ref[...], axis=-1, keepdims=True)
    o_ref[0, 0] = (acc_ref[...] / jnp.maximum(total, 1e-30)).astype(o_ref.dtype)


def attend_rows_chunk(
    q, k_rows, v_rows, q_pos, lengths, *, n_kv: int, window: int, slot=None, interpret=None
):
    """:func:`ops.gqa.attend_rows` over the first ``window`` rows, for
    prefill chunks, each read from its slot's rows where they lie: q
    (b, s, H, D) rotated, at consecutive positions ``q_pos`` (b, s);
    k_rows, v_rows (slots, T, KH * D) whole; row ``i`` of the call is slot
    ``slot[i]`` (absent: slot ``i``); ``lengths`` (b,) from
    :func:`chunk_lengths`.  Returns (b, s, H, D) in q's dtype: exact zeros
    for a row of length 0, which copies nothing."""
    if interpret is None:
        interpret = _interpret_mode()
    b = q.shape[0]
    slot = jnp.arange(b, dtype=jnp.int32) if slot is None else slot.astype(jnp.int32)
    bt = _block_t(k_rows.shape[1], window)
    return _chunk(
        q, k_rows, v_rows, q_pos[:, 0].astype(jnp.int32), lengths.astype(jnp.int32), slot,
        n_kv=n_kv, block_t=bt, interpret=interpret,
    )


# Under ``jit`` for the walk's reason: the layers of a program and the
# programs of every window share one trace of the kernel.
@functools.partial(jax.jit, static_argnames=("n_kv", "block_t", "interpret"))
def _chunk(q, k_rows, v_rows, first, lengths, slot, *, n_kv: int, block_t: int, interpret: bool):
    b, s, h, d = q.shape
    g = h // n_kv
    qh = _head_after_head(q, n_kv)
    head = pl.BlockSpec((1, 1, g * s, d), lambda bi, hi, *_: (bi, hi, 0, 0))
    out = pl.pallas_call(
        functools.partial(
            _chunk_kernel, block_t=block_t, s=s, tile=_chunk_tile(g * s), scale=d**-0.5
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b, n_kv),
            in_specs=[
                head,
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=head,
            scratch_shapes=[
                pltpu.VMEM((2, block_t, d), k_rows.dtype),
                pltpu.VMEM((2, block_t, d), v_rows.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.SMEM((2,), jnp.int32),
                pltpu.VMEM((g * s, _STAT_LANES), F32),
                pltpu.VMEM((g * s, _STAT_LANES), F32),
                pltpu.VMEM((g * s, d), F32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct(qh.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            # In order on one core: the buffer slot and the next program's
            # first copy ride from one program to the next.
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_BUDGET_BYTES,
        ),
        interpret=interpret,
        name="gqa_rows_chunk_attention",
    )(lengths, slot, first, qh, k_rows, v_rows)
    return _query_after_query(out, s)


# -- a window layer's prefill chunk ------------------------------------------------


def _ring_blocks(ring: int, s: int) -> tuple[int, int, int]:
    """(rows of a block of the ring, the call's own rows widened to whole
    lane tiles of keys, keys of a block of them)."""
    keys = -(-s // _STAT_LANES) * _STAT_LANES
    return _block_t(ring, ring), keys, next(t for t in (512, 256, 128) if keys % t == 0)


def _ring_vmem_bytes(ring: int, s: int, rows: int, d: int) -> int:
    """VMEM the ring kernel holds for one row's KV head: that head of the
    ring twice (this program's and the next one's on its way), the call's
    own rows, queries and outputs double-buffered, the online-softmax
    scratch, and a product's float32 scores and probabilities."""
    bt, keys, own = _ring_blocks(ring, s)
    return (
        2 * 2 * ring * d * 2
        + 2 * 2 * keys * d * 2
        + 2 * 2 * rows * d * 2
        + rows * (2 * _STAT_LANES + d) * 4
        + 4 * _chunk_tile(rows) * max(bt, own) * 4
    )


def use_ring_chunk(
    *, s: int, q_dtype, rows_dtype, width: int, head_dim: int, ring: int, n_q: int, mesh=None,
) -> bool:
    """The ring kernel's gate, from what a traced program can observe: a
    prefill chunk (``s > _STEP_QUERIES`` queries a row at consecutive
    positions) of bf16 queries over a bf16 ring of whole lane tiles and
    whole blocks, on one TPU device.  Everything else is
    :func:`ops.gqa.attend_ring`."""
    if s <= _STEP_QUERIES:
        return False
    if not _bf16_rows_on_one_chip(q_dtype, rows_dtype, width, head_dim, mesh):
        return False
    per_kv = s * n_q // (width // head_dim)  # a KV head's query rows
    return (
        _block_t(ring, ring) % 128 == 0  # whole blocks, scores of whole lane tiles
        and s % 16 == 0 and per_kv % 16 == 0  # whole bf16 sublane tiles
        and _ring_vmem_bytes(ring, s, per_kv, head_dim) <= _VMEM_BUDGET_BYTES
    )


def _ring_kernel(
    live_ref,  # scalar prefetch: (B,) int32, 0 for a row with nothing that counts
    pos_ref,  # scalar prefetch: (B,) int32 the position of a chunk's first query
    q_ref,  # (1, 1, G * s, D): one KV head's queries of one chunk, head-major
    kn_ref,  # (1, keys, D): that head of the call's own rows (zeros past s)
    vn_ref,
    k_hbm,  # (B, R, KH * D): the rings before the call, in HBM (pl.ANY)
    v_hbm,
    o_ref,  # (1, 1, G * s, D)
    kbuf,  # (2, R, D) VMEM: one KV head's lanes of a ring
    vbuf,
    sem,  # DMA (2 slots, K and V)
    state,  # SMEM (2,)
    m_ref,  # (G * s, 128) float32
    l_ref,
    acc_ref,  # (G * s, D) float32
    *,
    block_t: int,
    own_t: int,
    s: int,
    window: int,
    tile: int,
    scale: float,
):
    """One chunk's queries of one KV head over that head of its ring as
    it was, then over the call's own rows, in one online softmax.  The
    grid runs (chunk, KV head) in order on one core and a head of a ring
    is one copy, so while a program computes, the ring of the next one
    that has any (the chunk's next head, or the next chunk that is no
    padding) is on its way; a pad row copies nothing.

    With the last position written ``first - 1``, the ring wraps at
    ``wrap = first mod R``: rows below it hold this lap's positions
    ``first - wrap + r``, rows from it on the lap before's, ``R`` less
    (negative in the first lap: another occupant's leftovers).  A query
    at ``first + i`` sees what lies after ``first + i - window``; a block
    that the chunk's last query sees whole needs no compare."""
    b, h = pl.program_id(0), pl.program_id(1)
    n_chunks, kh = pl.num_programs(0), pl.num_programs(1)
    gs, d = q_ref.shape[2:]
    ring, keys, bt = kbuf.shape[1], kn_ref.shape[1], block_t

    def ring_dma(buf, row, head):
        lanes = pl.ds(pl.multiple_of(head * d, d), d)
        return tuple(
            pltpu.make_async_copy(hbm.at[row, pl.ds(0, ring), lanes], vm.at[buf], sem.at[buf, j])
            for j, (hbm, vm) in enumerate(((k_hbm, kbuf), (v_hbm, vbuf)))
        )

    # state[0]: buffer slot of the next ring to compute; state[1]: 1 if an
    # earlier program already started this one's copy.
    @pl.when((b == 0) & (h == 0))
    def _reset():
        state[0] = 0
        state[1] = 0

    def update(j, k, v, sees):
        rows = pl.ds(pl.multiple_of(j * tile, tile), tile)
        sc = jax.lax.dot_general(
            q_ref[0, 0, rows, :], k, (((1,), (1,)), ((), ())), preferred_element_type=F32
        ) * scale
        mask = None
        if sees is not None:
            # Row ``r`` of a KV head's queries is query ``r % s`` of the
            # chunk: a column of queries against a row of keys.
            mask = sees((jax.lax.broadcasted_iota(jnp.int32, (tile, 1), 0) + j * tile) % s)
        _chunk_update(sc, mask, v, m_ref.at[rows], l_ref.at[rows], acc_ref.at[rows])
        return 0

    @pl.when(live_ref[b] == 0)
    def _padding():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(live_ref[b] > 0)
    def _attend():
        buf = state[0]

        @pl.when(state[1] == 0)
        def _first():
            for cp in ring_dma(buf, b, h):
                cp.start()

        row = jax.lax.while_loop(
            lambda j: (j < n_chunks) & (live_ref[jnp.minimum(j, n_chunks - 1)] == 0),
            lambda j: j + 1,
            b + 1,
        )
        same = h + 1 < kh
        more = same | (row < n_chunks)

        @pl.when(more)
        def _next_program():
            nxt = jnp.where(same, b, jnp.minimum(row, n_chunks - 1))
            for cp in ring_dma(1 - buf, nxt, jnp.where(same, h + 1, 0)):
                cp.start()

        state[0] = 1 - buf
        state[1] = more.astype(jnp.int32)
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)
        first = pos_ref[b]
        wrap = first % ring
        lap = first - wrap  # the position ring row 0 holds, where it holds this lap's
        floor = first - window  # query ``i`` sees positions after ``floor + i``
        for cp in ring_dma(buf, b, h):
            cp.wait()

        def products(k, v, sees):
            jax.lax.fori_loop(0, gs // tile, lambda j, _: update(j, k, v, sees), 0)

        def ring_block(lo: int):
            k, v = kbuf[buf, lo : lo + bt], vbuf[buf, lo : lo + bt]
            # The oldest position of the block, unless the ring wraps in it.
            oldest = lap + lo - jnp.where(wrap <= lo, ring, 0)
            whole = ((wrap <= lo) | (wrap >= lo + bt)) & (oldest >= 0) & (oldest > floor + s - 1)

            def compared():
                r = jax.lax.broadcasted_iota(jnp.int32, (1, bt), 1) + lo
                held = lap + r - jnp.where(r >= wrap, ring, 0)
                # Query ``i`` sees a row that holds a position after
                # ``floor + i``; one that holds none is after no query's.
                after = jnp.where(held >= 0, held - floor, 0)
                products(k, v, lambda query: after > query)

            pl.when(whole)(lambda: products(k, v, None))
            pl.when(jnp.logical_not(whole))(compared)

        def own_block(lo: int):
            # Key ``j`` is the chunk's query ``j``'s own; the keys past
            # ``s`` lie after every query.
            key = jax.lax.broadcasted_iota(jnp.int32, (1, own_t), 1) + lo

            def behind(query):
                return (key <= query) & (key + window > query) if s > window else key <= query

            products(kn_ref[0, lo : lo + own_t], vn_ref[0, lo : lo + own_t], behind)

        for lo in range(0, ring, bt):
            ring_block(lo)
        for lo in range(0, keys, own_t):
            own_block(lo)
        total = jnp.sum(l_ref[...], axis=-1, keepdims=True)
        o_ref[0, 0] = (acc_ref[...] / jnp.maximum(total, 1e-30)).astype(o_ref.dtype)


def attend_ring_chunk(
    q, k_new, v_new, ring_k, ring_v, q_pos, n_valid, *, n_kv: int, window: int, interpret=None
):
    """:func:`ops.gqa.attend_ring` for prefill chunks: q (b, s, H, D)
    rotated, at consecutive positions ``q_pos`` (b, s); k_new, v_new
    (b, s, KH * D) the call's rows; ring_k, ring_v (b, R, KH * D) the rings
    before the call; ``n_valid`` (b,) the tokens of each row that count.
    Returns (b, s, H, D) in q's dtype: exact zeros for a row with none,
    whose ring is not read."""
    if interpret is None:
        interpret = _interpret_mode()
    return _ring_chunk(
        q, k_new, v_new, ring_k, ring_v, q_pos[:, 0].astype(jnp.int32),
        (n_valid > 0).astype(jnp.int32), n_kv=n_kv, window=window, interpret=interpret,
    )


# Under ``jit`` for the walk's reason: the window layers of a program and
# the programs of every window share one trace of the kernel.
@functools.partial(jax.jit, static_argnames=("n_kv", "window", "interpret"))
def _ring_chunk(q, k_new, v_new, ring_k, ring_v, first, live, *, n_kv: int, window: int, interpret: bool):
    b, s, h, d = q.shape
    g = h // n_kv
    ring = ring_k.shape[1]
    bt, keys, own_t = _ring_blocks(ring, s)
    qh = _head_after_head(q, n_kv)
    k_new, v_new = (jnp.pad(x, ((0, 0), (0, keys - s), (0, 0))) for x in (k_new, v_new))
    head = pl.BlockSpec((1, 1, g * s, d), lambda bi, hi, *_: (bi, hi, 0, 0))
    own = pl.BlockSpec((1, keys, d), lambda bi, hi, *_: (bi, 0, hi))
    out = pl.pallas_call(
        functools.partial(
            _ring_kernel, block_t=bt, own_t=own_t, s=s, window=window,
            tile=_chunk_tile(g * s), scale=d**-0.5,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, n_kv),
            in_specs=[
                head, own, own,
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=head,
            scratch_shapes=[
                pltpu.VMEM((2, ring, d), ring_k.dtype),
                pltpu.VMEM((2, ring, d), ring_v.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.SMEM((2,), jnp.int32),
                pltpu.VMEM((g * s, _STAT_LANES), F32),
                pltpu.VMEM((g * s, _STAT_LANES), F32),
                pltpu.VMEM((g * s, d), F32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct(qh.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            # In order on one core: the buffer slot and the next program's
            # ring ride from one program to the next.
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_BUDGET_BYTES,
        ),
        interpret=interpret,
        name="gqa_ring_chunk_attention",
    )(live, first, qh, k_new, v_new, ring_k, ring_v)
    return _query_after_query(out, s)
