"""Pallas TPU kernel for a decode step's walk over latent rows:
:func:`ops.mla.attend_absorbed_blocks` (``models/hybrid.py``'s ``mla``
mixer, one query a slot or a token and its draft) for every row of the
call in ONE ``pallas_call`` a layer.

XLA's form was a ``lax.map`` over the slots, a ``lax.cond`` a slot and a
``fori_loop`` over the slot's blocks, each iteration a ``dynamic_slice``
and half a dozen small fusions on 32 query rows: 8-11 us a block of 1.5 MB
that its bytes pay in 1.9 us (0.94 ms a layer with 16 rows of 14,000
decoding on a v5e where this kernel takes 0.28, 0.31 with 4 where it
takes 0.10: PERF.md, PR 56).  The kernel takes which
slot each row of the call is, its length and its queries' positions as
scalar-prefetch operands, leaves the latent leaf in HBM and copies the
blocks a slot holds up to the row's length, double-buffered, the next
walked row's first block during a row's last, none for a row of length 0,
which yields exact zeros.  The queries come folded
(:func:`ops.mla.whole_row_queries`: ``W_kvb``'s key half inside them, the
rope query beside it) and stand against a block's whole rows as they lie;
the weighted sum runs over the rows' latent columns alone (a lane-aligned
view of the block), a float32 online softmax
(``ops/gqa_decode.py::_chunk_update``) between them.  The value half of
``W_kvb`` is applied by XLA afterwards, as the twin applies it.

The mask: key ``t`` is seen by query ``i`` iff ``t <= q_pos[i]`` and
``t < length``.  Only the blocks that reach past the first query's
position or the row's end take the compare.  ``window`` is a static bound
of the walk, not what is read.  One more operand, ``allowed (b, s, span)
int8`` tiled as ``ops/mla_chunk.py`` tiles its mask, would make this the
walk of an indexer's selection; no caller hands one yet.

What :func:`use_latent_decode` refuses stays ``attend_absorbed_blocks``,
the XLA twin and the tests' oracle: float32 state, a prefill chunk,
several devices, the CPU without the interpret switch, shapes off the
tiles or past the VMEM budget.
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
from generativeaiexamples_tpu.ops.mla import whole_row_queries
from generativeaiexamples_tpu.ops.qmm import _VMEM_BUDGET_BYTES

F32 = jnp.float32


def _vmem_bytes(sh: int, bt: int, width: int, rank: int) -> int:
    """VMEM one grid step holds over bf16 rows: the ping-pong latent
    blocks, a row's double-buffered queries and outputs, its
    online-softmax scratch, and a block's scores and probabilities."""
    return (
        2 * bt * width * 2
        + 2 * sh * (width + rank) * 2
        + sh * (2 * _STAT_LANES + rank) * 4
        + sh * bt * 10
    )


def use_latent_decode(
    *, s: int, q_dtype, rows_dtype, width: int, rank: int, heads: int, rows: int, window: int,
    block: int, mesh=None,
) -> bool:
    """The gate, from what a traced step can observe: a decode step
    (``s <= _STEP_QUERIES`` queries a row) of bf16 queries over a bf16
    leaf whose rows and latent are whole lane tiles, in whole blocks of
    whole lane tiles, on one TPU device, with a block and its scores
    under the VMEM budget.  Everything else is
    :func:`ops.mla.attend_absorbed_blocks`."""
    if s > _STEP_QUERIES:
        return False
    if not _bf16_rows_on_one_chip(q_dtype, rows_dtype, width, rank, mesh):
        return False
    bt = math.gcd(min(window, rows), block)
    return (
        bt % _STAT_LANES == 0  # scores of whole lane tiles
        and (s * heads) % 16 == 0  # whole bf16 sublane tiles
        and _vmem_bytes(s * heads, bt, width, rank) <= _VMEM_BUDGET_BYTES
    )


def _kernel(
    len_ref,  # scalar prefetch: (B,) int32 rows each row's walk covers
    slot_ref,  # scalar prefetch: (B,) int32 the slot whose rows a row reads
    pos_ref,  # scalar prefetch: (B * s,) int32 the queries' positions
    q_ref,  # (1, s * H, width): a row's folded queries, query after query
    lat_hbm,  # (slots, T, width): stays in HBM (pl.ANY)
    o_ref,  # (1, s * H, rank)
    latbuf,  # (2, block_t, width) VMEM
    sem,  # DMA (2,)
    state,  # SMEM (2,)
    m_ref,  # (s * H, 128) float32
    l_ref,
    acc_ref,  # (s * H, rank) float32
    *,
    block_t: int,
    s: int,
    scale: float,
):
    """One row's queries walked over the blocks its slot holds.  The grid
    runs the rows in order on one core, so the copies run ahead as
    ``gqa_decode._walk_kernel``'s do: block ``i + 1`` while block ``i``
    computes, and during a row's last block the first block of the next
    row that has any; the buffer slot and the "my first block is on its
    way" flag ride from row to row in SMEM, and only the call's first
    copy is exposed."""
    b, n_rows = pl.program_id(0), pl.num_programs(0)
    sh, rank = acc_ref.shape
    heads = sh // s
    bt = block_t
    length = len_ref[b]

    def n_blocks(row):
        return (len_ref[row] + bt - 1) // bt

    def copy(buf, row, i):
        start = pl.multiple_of(i * bt, bt)
        return pltpu.make_async_copy(
            lat_hbm.at[slot_ref[row], pl.ds(start, bt)], latbuf.at[buf], sem.at[buf]
        )

    # state[0]: buffer slot of the next block to compute; state[1]: 1 if
    # an earlier row already started this one's first copy.
    @pl.when(b == 0)
    def _reset():
        state[0] = 0
        state[1] = 0

    n = n_blocks(b)
    buf0 = state[0]

    @pl.when((n > 0) & (state[1] == 0))
    def _first():
        copy(buf0, b, 0).start()

    m_ref[...] = jnp.full_like(m_ref, _NEG)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)
    positions = [pos_ref[b * s + i] for i in range(s)]

    def seen(i):
        """Which keys of block ``i`` each of the s * H query rows may see:
        query ``j`` of the step owns rows [j * H, (j + 1) * H)."""
        query = jax.lax.broadcasted_iota(jnp.int32, (sh, bt), 0) // heads
        last = jnp.full((sh, bt), positions[0], jnp.int32)
        for j in range(1, s):
            last = jnp.where(query == j, positions[j], last)
        keys = jax.lax.broadcasted_iota(jnp.int32, (sh, bt), 1) + i * bt
        return keys <= jnp.minimum(last, length - 1)

    def block(i, compare: bool):
        buf = (buf0 + i) % 2

        @pl.when(i + 1 < n)
        def _prefetch():
            copy(1 - buf, b, i + 1).start()

        @pl.when(i + 1 == n)
        def _prefetch_next_row():
            nxt = jax.lax.while_loop(
                lambda j: (j < n_rows) & (n_blocks(jnp.minimum(j, n_rows - 1)) == 0),
                lambda j: j + 1,
                b + 1,
            )

            @pl.when(nxt < n_rows)
            def _start():
                copy(1 - buf, nxt, 0).start()

            state[0] = 1 - buf
            state[1] = (nxt < n_rows).astype(jnp.int32)

        copy(buf, b, i).wait()
        sc = jax.lax.dot_general(
            q_ref[0], latbuf[buf], (((1,), (1,)), ((), ())), preferred_element_type=F32
        ) * scale
        _chunk_update(
            sc, seen(i) if compare else None, latbuf[buf, :, :rank], m_ref, l_ref, acc_ref
        )
        return 0

    # Blocks that end at or before every query's position, inside the
    # row's length, are seen whole.
    first = functools.reduce(jnp.minimum, positions)
    whole = jnp.clip(jnp.minimum(first + 1, length) // bt, 0, n)
    jax.lax.fori_loop(0, whole, lambda i, _: block(i, False), 0)
    jax.lax.fori_loop(whole, n, lambda i, _: block(i, True), 0)
    total = jnp.sum(l_ref[...], axis=-1, keepdims=True)
    o_ref[0] = (acc_ref[...] / jnp.maximum(total, 1e-30)).astype(o_ref.dtype)


@jax.named_scope("layer/mla/attn")
def attend_latent_decode(
    q_nope, q_rope, latent, w_kvb, q_pos, lengths, slot=None, *, rank, nope, v_dim, window,
    block, scale=None, interpret=None,
):
    """:func:`ops.mla.attend_absorbed_blocks` for every row of a decode
    step at once, each read from its slot's rows where they lie: q_nope
    (b, s, H, nope), q_rope (b, s, H, rope) rotated, ``s <= 2``, at
    positions ``q_pos`` (b, s); latent (slots, T, width) the whole leaf;
    row ``i`` of the call is slot ``slot[i]`` (absent: slot ``i``);
    ``lengths`` (b,) the rows each holds once the step's tokens are
    written, of which the first ``window`` may be seen, in blocks of
    ``block`` (as ``attend_absorbed_blocks`` cuts them).  Returns
    (b, s, H, v_dim) in q's dtype: exact zeros for a row of length 0,
    which copies nothing."""
    if interpret is None:
        interpret = _interpret_mode()
    b, s, H, _ = q_nope.shape
    span = min(window, latent.shape[1])
    if scale is None:
        scale = (nope + q_rope.shape[-1]) ** -0.5
    w = w_kvb.reshape(rank, H, nope + v_dim)
    q = whole_row_queries(q_nope, q_rope, w, nope, latent.shape[2])
    o_row = _latent_decode(
        q.reshape(b, s * H, -1), latent, q_pos.astype(jnp.int32).reshape(b * s),
        jnp.minimum(lengths.astype(jnp.int32), span),
        jnp.arange(b, dtype=jnp.int32) if slot is None else slot.astype(jnp.int32),
        s=s, rank=rank, block_t=math.gcd(span, block), scale=float(scale), interpret=interpret,
    )
    return jnp.einsum("bshr,rhd->bshd", o_row.reshape(b, s, H, rank), w[..., nope:])


# Under ``jit`` for ``gqa_decode._walk``'s reason: the window enters
# through the block alone, so the layers of a step and the decode chunks of
# every window share one trace of the kernel.
@functools.partial(jax.jit, static_argnames=("s", "rank", "block_t", "scale", "interpret"))
def _latent_decode(
    q, latent, pos, lengths, slot, *, s: int, rank: int, block_t: int, scale: float,
    interpret: bool,
):
    b, sh, width = q.shape
    row = lambda n: pl.BlockSpec((1, sh, n), lambda bi, *_: (bi, 0, 0))
    return pl.pallas_call(
        functools.partial(_kernel, block_t=block_t, s=s, scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b,),
            in_specs=[row(width), pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=row(rank),
            scratch_shapes=[
                pltpu.VMEM((2, block_t, width), latent.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SMEM((2,), jnp.int32),
                pltpu.VMEM((sh, _STAT_LANES), F32),
                pltpu.VMEM((sh, _STAT_LANES), F32),
                pltpu.VMEM((sh, rank), F32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, sh, rank), q.dtype),
        compiler_params=pltpu.CompilerParams(
            # In order on one core: the buffer slot and the next row's
            # first copy ride from one program to the next.
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_BUDGET_BYTES,
        ),
        interpret=interpret,
        name="latent_decode_attention",
    )(lengths, slot, pos, q, latent)
