"""Pallas TPU kernel for a prefill chunk's walk over latent rows:
:func:`ops.mla.attend_blocks` (``models/hybrid.py``'s ``mla`` mixer, a
chunk of 16-256 queries a row at consecutive positions, 1-8 rows a
program) with a block's expansion, scores and probabilities kept on the
chip.

XLA's form wrote a block's float32 scores of heads x queries x block to
HBM and read them back three to four times a row-block, a row at a time
(128 heads x 256 x 1,024 x 4 B = 134 MB: 0.67 ms for the 0.11 ms of
products they stand between, beside 0.19 ms of expansion; PERF.md, PR 47
and PR 48).  The kernel takes which slot each row of the call is, its
first position and its length as scalar-prefetch operands (that IS the
in-place read), leaves the latent leaf in HBM and copies the blocks a
slot holds up to the row's length, double-buffered, none for a row of
length 0, which yields exact zeros.  One grid step is one group of heads
of one row: a block of latent rows (``block x width`` bf16) stays in
VMEM while the group's heads pass over it, each head's slice of
``W_kvb`` expands it (rounded to the rows' dtype where ``attend_blocks``
rounds it), its keys are the head's nope part beside the block's one
rope key as it lies in the row, and ``ops/gqa_decode.py``'s
``_chunk_update`` folds the float32 scores into a float32 online softmax
(probabilities rounded to the values' dtype before their product).

The mask: key ``t`` is seen by query ``i`` iff ``t <= q_pos[i]``, ``t``
lies in the blocks walked and, where ``allowed`` is given and the row
``selects``, ``allowed[row, i, t]`` (an indexer's choice,
:func:`ops.mla.select_mask`'s result as int8; a tile of queries x block
a step).  Only the blocks that overlap the chunk's own positions take
the causal compare.  A query that keeps nothing of a block contributes
exact zeros to its sums, whatever the blocks before it held.

What :func:`use_latent_chunk` refuses stays ``attend_blocks``, the XLA
twin and the tests' oracle: float32 state, a decode step, several
devices, the CPU without the interpret switch, shapes off the tiles or
past the VMEM budget.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from generativeaiexamples_tpu.ops.decode_attention import _interpret_mode
from generativeaiexamples_tpu.ops.gqa import _NEG, _STEP_QUERIES
from generativeaiexamples_tpu.ops.gqa_decode import (
    _STAT_LANES,
    _bf16_rows_on_one_chip,
    _chunk_update,
)
from generativeaiexamples_tpu.ops.qmm import _VMEM_BUDGET_BYTES

F32 = jnp.float32
_LANES = 128
# Heads of one grid step, the most that divide the layer's and fit the
# VMEM budget (PERF.md, PR 48: the sweep over 4 / 8 / 16).
_HEADS_A_STEP = (8, 4, 2, 1)


def _up(n: int) -> int:
    """``n`` filled up to whole lane tiles."""
    return -(-n // _LANES) * _LANES


def _mask_rows(s: int) -> int:
    """Queries of a mask tile: ``s`` filled up to whole int8 sublane tiles."""
    return -(-s // 32) * 32


def _vmem_bytes(hg: int, s: int, bt: int, width: int, rank: int, nk: int, v: int, masked: bool) -> int:
    """VMEM one grid step holds over bf16 rows: the ping-pong latent
    blocks (and mask tiles), the group's double-buffered ``W_kvb`` slices,
    queries and outputs, its online-softmax scratch, and one head's
    expansion, scores and probabilities."""
    dk = nk + width - rank
    return (
        2 * bt * width * 2
        + (2 * _mask_rows(s) * bt if masked else 0)
        + 2 * hg * (rank * (nk + v) + s * dk + s * v) * 2
        + hg * s * (2 * _STAT_LANES + v) * 4
        + bt * (nk + v) * 6 + bt * dk * 2 + s * bt * 10
    )


def _heads_a_step(heads: int, **sizes) -> int:
    """Heads of a grid step; 0 where not even one fits the budget."""
    return next(
        (g for g in _HEADS_A_STEP
         if heads % g == 0 and _vmem_bytes(g, **sizes) <= _VMEM_BUDGET_BYTES),
        0,
    )


def use_latent_chunk(
    *, s: int, q_dtype, rows_dtype, width: int, rank: int, nope: int, v_dim: int, heads: int,
    rows: int, window: int, block: int, masked: bool, mesh=None,
) -> bool:
    """The gate, from what a traced program can observe: a prefill chunk
    (``s > _STEP_QUERIES`` queries a row at consecutive positions) of bf16
    queries over a bf16 leaf whose rows, latent and values are whole lane
    tiles, in whole blocks of whole lane tiles, on one TPU device, with
    the tiles of at least one head under the VMEM budget.  Everything
    else is :func:`ops.mla.attend_blocks`."""
    if s <= _STEP_QUERIES:
        return False
    if not _bf16_rows_on_one_chip(q_dtype, rows_dtype, width, v_dim, mesh):
        return False
    bt = math.gcd(min(window, rows), block)
    return (
        rank % _LANES == 0
        and bt % _LANES == 0  # scores of whole lane tiles
        and s % 16 == 0  # whole bf16 sublane tiles
        and _heads_a_step(
            heads, s=s, bt=bt, width=width, rank=rank, nk=_up(nope), v=v_dim, masked=masked
        ) > 0
    )


def _expand(rows, w):
    """A block's latents through a head's slice of ``W_kvb``, rounded to
    the rows' dtype as XLA's ``jnp.dot`` of them is."""
    return jnp.dot(rows, w, preferred_element_type=F32).astype(rows.dtype)


def _kernel(
    len_ref,  # scalar prefetch: (B,) int32 rows each chunk's walk covers
    slot_ref,  # scalar prefetch: (B,) int32 the slot whose rows a chunk reads
    pos_ref,  # scalar prefetch: (B,) int32 the position of a chunk's first query
    sel_ref,  # scalar prefetch: (B,) int32, 1 where ``allowed`` counts for the row
    q_ref,  # (1, hg, s, dk): a group of heads' queries, nope | rope as the keys lie
    w_ref,  # (hg, rank, nk + v): their slices of ``W_kvb``
    lat_hbm,  # (slots, T, width): stays in HBM (pl.ANY)
    *rest,  # [allowed_hbm (B, s, T) int8 in HBM,] o_ref, scratch
    block_t: int,
    rank: int,
    nk: int,
    scale: float,
    masked: bool,
):
    """One chunk's queries of one group of heads, walked over the blocks
    its slot holds.  The grid runs (chunk, group) in order on one core, so
    the copies run ahead as ``gqa_decode._chunk_kernel``'s do: block
    ``i + 1`` while block ``i`` computes, and during a program's last
    block the first block of the next program that has any (the chunk's
    next group, or the next chunk that is no padding)."""
    if masked:
        allowed_hbm, o_ref, latbuf, albuf, sem, state, m_ref, l_ref, acc_ref = rest
    else:
        o_ref, latbuf, sem, state, m_ref, l_ref, acc_ref = rest
    b, g = pl.program_id(0), pl.program_id(1)
    n_chunks, n_groups = pl.num_programs(0), pl.num_programs(1)
    hg, s = q_ref.shape[1:3]
    bt = block_t
    first = pos_ref[b]

    def n_blocks(row):
        return (len_ref[row] + bt - 1) // bt

    def copies(buf, row, i, act):
        """Start or await the copy of block ``i`` of ``row``'s slot, and
        of its mask tile where the row selects."""
        start = pl.multiple_of(i * bt, bt)
        act(pltpu.make_async_copy(
            lat_hbm.at[slot_ref[row], pl.ds(start, bt)], latbuf.at[buf], sem.at[buf, 0]
        ))
        if masked:
            @pl.when(sel_ref[row] != 0)
            def _tile():
                act(pltpu.make_async_copy(
                    allowed_hbm.at[row, :, pl.ds(start, bt)], albuf.at[buf], sem.at[buf, 1]
                ))

    def start(buf, row, i):
        copies(buf, row, i, lambda cp: cp.start())

    # state[0]: buffer slot of the next block to compute; state[1]: 1 if
    # an earlier program already started this one's first copy.
    @pl.when((b == 0) & (g == 0))
    def _reset():
        state[0] = 0
        state[1] = 0

    n = n_blocks(b)
    buf0 = state[0]

    @pl.when((n > 0) & (state[1] == 0))
    def _first():
        start(buf0, b, 0)

    m_ref[...] = jnp.full_like(m_ref, _NEG)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)

    def block(i, causal: bool):
        buf = (buf0 + i) % 2

        @pl.when(i + 1 < n)
        def _prefetch():
            start(1 - buf, b, i + 1)

        @pl.when(i + 1 == n)
        def _prefetch_next_program():
            row = jax.lax.while_loop(
                lambda j: (j < n_chunks) & (n_blocks(jnp.minimum(j, n_chunks - 1)) == 0),
                lambda j: j + 1,
                b + 1,
            )
            same = g + 1 < n_groups
            more = same | (row < n_chunks)

            @pl.when(more)
            def _start():
                start(1 - buf, jnp.where(same, b, jnp.minimum(row, n_chunks - 1)), 0)

            state[0] = 1 - buf
            state[1] = more.astype(jnp.int32)

        copies(buf, b, i, lambda cp: cp.wait())
        seen = None
        if causal:
            # Query ``r`` of the chunk is at position ``first + r``.
            seen = (
                jax.lax.broadcasted_iota(jnp.int32, (s, bt), 1) + i * bt
                <= jax.lax.broadcasted_iota(jnp.int32, (s, bt), 0) + first
            )

        def heads(mask):
            def head(h, _):
                # A head's keys' nope part and values: the block through
                # its slice of ``W_kvb``, rounded where XLA's form rounds.
                kv = _expand(latbuf[buf, :, :rank], w_ref[h])
                # The block's one rope key stands where it lies in the row
                # (and the row's zero columns after it, against zeros).
                k = jnp.concatenate([kv[:, :nk], latbuf[buf, :, rank:]], axis=1)
                sc = jax.lax.dot_general(
                    q_ref[0, h], k, (((1,), (1,)), ((), ())), preferred_element_type=F32
                ) * scale
                _chunk_update(sc, mask, kv[:, nk:], m_ref.at[h], l_ref.at[h], acc_ref.at[h])
                return 0

            jax.lax.fori_loop(0, hg, head, 0)

        if not masked:
            heads(seen)
        else:
            selects = sel_ref[b] != 0

            @pl.when(selects)
            def _selected():
                kept = (albuf[buf].astype(jnp.int32) != 0)[:s]
                heads(kept if seen is None else kept & seen)

            @pl.when(jnp.logical_not(selects))
            def _dense():
                heads(seen)

        return 0

    # Blocks that end at or before the first query's position are seen
    # whole by every query.
    whole = jnp.minimum((first + 1) // bt, n)
    jax.lax.fori_loop(0, whole, lambda i, _: block(i, False), 0)
    jax.lax.fori_loop(whole, n, lambda i, _: block(i, True), 0)
    total = jnp.sum(l_ref[...], axis=-1, keepdims=True)
    o_ref[0] = (acc_ref[...] / jnp.maximum(total, 1e-30)).astype(o_ref.dtype)


@jax.named_scope("layer/mla/attn")
def attend_latent_chunk(
    q_nope, q_rope, latent, w_kvb, q_pos, lengths, slot=None, allowed=None, *, rank, nope,
    v_dim, window, block, scale=None, selects=None, interpret=None,
):
    """:func:`ops.mla.attend_blocks` for prefill chunks, each read from
    its slot's rows where they lie: q_nope (b, s, H, nope), q_rope
    (b, s, H, rope) rotated, at consecutive positions ``q_pos`` (b, s);
    latent (slots, T, width) the whole leaf; row ``i`` of the call is slot
    ``slot[i]`` (absent: slot ``i``); ``lengths`` (b,) the rows each holds
    once the call's tokens are written, of which the first ``window`` may
    be seen, in blocks of ``block`` (as ``attend_blocks`` cuts them);
    ``allowed`` (b, s, T') int8 or bool, ``T' >= min(window, T)``, the
    pairs an indexer kept, for the rows ``selects`` (b,) bool names
    (absent: all).  Returns (b, s, H, v_dim) in q's dtype: exact zeros for
    a row of length 0, which copies nothing."""
    if interpret is None:
        interpret = _interpret_mode()
    b = q_nope.shape[0]
    span = min(window, latent.shape[1])
    if scale is None:
        scale = (nope + q_rope.shape[-1]) ** -0.5
    slot = jnp.arange(b, dtype=jnp.int32) if slot is None else slot.astype(jnp.int32)
    if allowed is None:
        selects = jnp.zeros((b,), jnp.int32)
    else:
        allowed = allowed.astype(jnp.int8)
        selects = jnp.ones((b,), jnp.int32) if selects is None else selects.astype(jnp.int32)
    return _latent_chunk(
        q_nope, q_rope, latent, w_kvb, q_pos[:, 0].astype(jnp.int32),
        jnp.minimum(lengths.astype(jnp.int32), span), slot, selects, allowed,
        rank=rank, nope=nope, v_dim=v_dim, block_t=math.gcd(span, block), scale=float(scale),
        interpret=interpret,
    )


# Under ``jit`` for ``gqa_decode._chunk``'s reason: the layers of a program
# and the programs of every window share one trace of the kernel.
@functools.partial(
    jax.jit, static_argnames=("rank", "nope", "v_dim", "block_t", "scale", "interpret")
)
def _latent_chunk(
    q_nope, q_rope, latent, w_kvb, first, lengths, slot, selects, allowed, *, rank: int,
    nope: int, v_dim: int, block_t: int, scale: float, interpret: bool,
):
    b, s, H, rope = q_rope.shape
    width = latent.shape[2]
    nk, masked = _up(nope), allowed is not None
    dk = nk + width - rank
    hg = _heads_a_step(
        H, s=s, bt=block_t, width=width, rank=rank, nk=nk, v=v_dim, masked=masked
    ) or 1

    def filled(x, n):
        return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, n - x.shape[-1])])

    # A head's queries as its keys lie: the nope part on whole lane tiles,
    # the rope part against the row's columns from ``rank`` on.
    q = jnp.concatenate([filled(q_nope, nk), filled(q_rope, width - rank)], axis=-1)
    q = q.transpose(0, 2, 1, 3)  # (b, H, s, dk)
    w = w_kvb.reshape(rank, H, nope + v_dim)
    w = jnp.concatenate([filled(w[..., :nope], nk), w[..., nope:]], axis=-1)
    w = w.transpose(1, 0, 2)  # (H, rank, nk + v)
    group = lambda width: pl.BlockSpec((1, hg, s, width), lambda bi, gi, *_: (bi, gi, 0, 0))
    in_specs = [
        group(dk),
        pl.BlockSpec((hg, rank, nk + v_dim), lambda bi, gi, *_: (gi, 0, 0)),
        pl.BlockSpec(memory_space=pl.ANY),
    ]
    operands = [q, w, latent]
    scratch = [pltpu.VMEM((2, block_t, width), latent.dtype)]
    if masked:
        in_specs.append(pl.BlockSpec(memory_space=pl.ANY))
        # A tile of whole int8 sublane tiles of queries.
        operands.append(jnp.pad(allowed, ((0, 0), (0, _mask_rows(s) - s), (0, 0))))
        scratch.append(pltpu.VMEM((2, _mask_rows(s), block_t), jnp.int8))
    scratch += [
        pltpu.SemaphoreType.DMA((2, 2)),
        pltpu.SMEM((2,), jnp.int32),
        pltpu.VMEM((hg, s, _STAT_LANES), F32),
        pltpu.VMEM((hg, s, _STAT_LANES), F32),
        pltpu.VMEM((hg, s, v_dim), F32),
    ]
    out = pl.pallas_call(
        functools.partial(
            _kernel, block_t=block_t, rank=rank, nk=nk, scale=scale, masked=masked
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(b, H // hg),
            in_specs=in_specs,
            out_specs=group(v_dim),
            scratch_shapes=scratch,
        ),
        out_shape=jax.ShapeDtypeStruct((b, H, s, v_dim), q_nope.dtype),
        compiler_params=pltpu.CompilerParams(
            # In order on one core: the buffer slot and the next program's
            # first copy ride from one program to the next.
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_BUDGET_BYTES,
        ),
        interpret=interpret,
        name="latent_chunk_attention",
    )(lengths, slot, first, selects, *operands)
    return out.transpose(0, 2, 1, 3)
