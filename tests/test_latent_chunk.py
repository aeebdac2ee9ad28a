"""A prefill chunk's walk over the latent rows its slot holds
(``ops/mla_chunk.py``'s kernel), in Pallas interpret mode on the CPU,
against ``ops.mla.attend_blocks``, its XLA twin: the numbers in float32
and in bf16, the mask, what is read, the gate, and through
``models/hybrid.py``'s mixer the path taken and the counters."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from generativeaiexamples_tpu.models import hybrid
from generativeaiexamples_tpu.ops import dispatch, mla, mla_chunk

T = 512  # four blocks of 128
BLOCK = 128
SLOTS = 9
S = 32
V = 128
BF16 = jnp.bfloat16
# Both families' ratios at an eighth and a sixteenth of the widths: dots3's
# (nope 2 x rope, nope = values / 1, a row with zero columns after its rope
# key) and Mistral-Small-4's (nope = rope, half the values'); heads, rank,
# nope, rope, a row's width as stored.
FAMILIES = {
    "dots3": dict(H=8, rank=64, nope=32, rope=16, width=128),
    "mistral4": dict(H=4, rank=32, nope=16, rope=16, width=128),
}
TOL = {jnp.float32: dict(atol=2e-5, rtol=2e-5), BF16: dict(atol=2e-2, rtol=2e-2)}


def _operands(b, family, dtype, seed=0, s=S):
    H, rank, nope, rope, width = (FAMILIES[family][k] for k in ("H", "rank", "nope", "rope", "width"))
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q_nope = jax.random.normal(ks[0], (b, s, H, nope), jnp.float32).astype(dtype)
    q_rope = jax.random.normal(ks[1], (b, s, H, rope), jnp.float32).astype(dtype)
    latent = jax.random.normal(ks[2], (SLOTS, T, width), jnp.float32)
    latent = latent.at[..., rank + rope :].set(0).astype(dtype)  # as the mixer writes a row
    w_kvb = (jax.random.normal(ks[3], (rank, H * (nope + V)), jnp.float32) * rank**-0.5).astype(dtype)
    return q_nope, q_rope, latent, w_kvb, dict(rank=rank, nope=nope, v_dim=V)


def _chunks(starts, counts, s=S):
    start, counts = jnp.asarray(starts, jnp.int32), jnp.asarray(counts, jnp.int32)
    pos = start[:, None] + jnp.arange(s, dtype=jnp.int32)[None]
    return pos, jnp.where(counts > 0, start + counts, 0)


def _twin(q_nope, q_rope, latent, w_kvb, pos, lengths, slot, allowed, *, window, **sizes):
    """``attend_blocks`` a row at a time, as the mixer's XLA path calls it."""
    rows = []
    for i in range(q_nope.shape[0]):
        if int(lengths[i]) == 0:
            rows.append(jnp.zeros((q_nope.shape[1], q_nope.shape[2], V), q_nope.dtype))
            continue
        one = slice(i, i + 1)
        rows.append(mla.attend_blocks(
            q_nope[one], q_rope[one], latent, w_kvb, pos[one], lengths[one], block=BLOCK,
            slot=slot[one], window=window, allowed=None if allowed is None else allowed[one],
            **sizes,
        )[0])
    return np.asarray(jnp.stack(rows), np.float32)


def _poisoned(latent, slot, lengths, window):
    """NaN where no chunk may read: every block past its slot's last one
    (or the window), every row of a slot no chunk of the call is."""
    reach = np.zeros(SLOTS, np.int64)
    walked = np.minimum(-(-np.asarray(lengths) // BLOCK) * BLOCK, window)
    reach[np.asarray(slot)[walked > 0]] = walked[walked > 0]
    beyond = jnp.asarray((np.arange(T)[None, :] >= reach[:, None])[:, :, None])
    return jnp.where(beyond, jnp.nan, latent), int(reach.sum())


# Chunks that start a slot, cross a block's edge (120 -> 152), end on one
# (224 -> 256), fill the slot's last rows, count 5 of their tokens (a length
# that ends inside a block), and a group's padding, in 1, 4 and 8 rows a call.
ROWS = {
    1: ([120], [S]),
    4: ([0, 120, 57, 224], [S, S, 0, S]),
    8: ([0, 120, 224, T - S, 300, 57, 8, 200], [S, S, S, S, 5, 0, S, 17]),
}


def _selection(key, pos, density=0.3):
    """A random choice among the positions each query sees."""
    seen = jnp.arange(T)[None, None, :] <= pos[:, :, None]
    return (jax.random.uniform(key, pos.shape + (T,)) < density) & seen


@pytest.mark.parametrize("dtype", [jnp.float32, BF16], ids=["f32", "bf16"])
@pytest.mark.parametrize("family", sorted(FAMILIES))
@pytest.mark.parametrize("selected", [False, True], ids=["dense", "selected"])
@pytest.mark.parametrize("b", sorted(ROWS))
def test_a_chunk_gets_attend_blocks_numbers_from_its_slots_blocks_alone(b, selected, family, dtype):
    starts, counts = ROWS[b]
    q_nope, q_rope, latent, w_kvb, sizes = _operands(b, family, dtype, seed=b)
    pos, lengths = _chunks(starts, counts)
    slot = jnp.asarray(np.random.RandomState(b).permutation(SLOTS)[:b], jnp.int32)
    allowed = _selection(jax.random.PRNGKey(b + 7), pos) if selected else None
    want = _twin(q_nope, q_rope, latent, w_kvb, pos, lengths, slot, allowed, window=T, **sizes)
    unread, walked = _poisoned(latent, slot, lengths, T)
    got = np.asarray(mla_chunk.attend_latent_chunk(
        q_nope, q_rope, unread, w_kvb, pos, lengths, slot, allowed, window=T, block=BLOCK,
        interpret=True, **sizes,
    ), np.float32)
    assert np.isfinite(got).all()  # nothing past a slot's blocks, nothing of another slot
    np.testing.assert_allclose(got, want, **TOL[dtype])
    assert (got[np.asarray(lengths) == 0] == 0).all()  # padding: exact zeros, nothing read
    assert int(mla.rows_in_blocks(lengths, T, BLOCK).sum()) == walked


@pytest.mark.parametrize("dtype", [jnp.float32, BF16], ids=["f32", "bf16"])
def test_a_query_that_keeps_nothing_of_the_first_block_it_walks_is_not_poisoned(dtype):
    """Before any block it keeps a query's running maximum is still the
    mask's value, and ``exp(0)`` a key would count: the walk zeroes them
    (``_walk_blocks``' rule), so must the kernel.  Query 3 keeps keys of
    the third block alone, query 4 nothing at all."""
    q_nope, q_rope, latent, w_kvb, sizes = _operands(2, "dots3", dtype, seed=3)
    pos, lengths = _chunks([300, 300], [S, S])
    slot = jnp.asarray([6, 2], jnp.int32)
    allowed = _selection(jax.random.PRNGKey(1), pos)
    allowed = allowed.at[0, 3, : 2 * BLOCK].set(False).at[0, 4].set(False)
    want = _twin(q_nope, q_rope, latent, w_kvb, pos, lengths, slot, allowed, window=T, **sizes)
    got = np.asarray(mla_chunk.attend_latent_chunk(
        q_nope, q_rope, latent, w_kvb, pos, lengths, slot, allowed, window=T, block=BLOCK,
        interpret=True, **sizes,
    ), np.float32)
    np.testing.assert_allclose(got, want, **TOL[dtype])
    assert (got[0, 4] == 0).all() and np.abs(got[0, 3]).max() > 0


@pytest.mark.parametrize("selected", [False, True], ids=["dense", "selected"])
def test_a_window_shorter_than_the_leaf_bounds_the_walk(selected):
    """A program built for a window of 256 of the leaf's 512 rows: a row
    past it sees the window's rows and reads no block beyond."""
    q_nope, q_rope, latent, w_kvb, sizes = _operands(3, "mistral4", BF16, seed=5)
    pos, lengths = _chunks([224, 100, 400], [S, S, S])
    slot = jnp.asarray([1, 7, 4], jnp.int32)
    allowed = _selection(jax.random.PRNGKey(2), pos)[..., :256] if selected else None
    want = _twin(q_nope, q_rope, latent, w_kvb, pos, lengths, slot, allowed, window=256, **sizes)
    unread, walked = _poisoned(latent, slot, lengths, 256)
    got = np.asarray(mla_chunk.attend_latent_chunk(
        q_nope, q_rope, unread, w_kvb, pos, lengths, slot, allowed, window=256, block=BLOCK,
        interpret=True, **sizes,
    ), np.float32)
    assert np.isfinite(got).all() and walked == 256 + 256 + 256
    np.testing.assert_allclose(got, want, **TOL[BF16])


def test_rows_that_do_not_select_are_walked_dense_beside_those_that_do():
    """``selects`` names the rows whose ``allowed`` counts (the mixer's:
    rows longer than ``index_topk``); the others' tiles are not read."""
    q_nope, q_rope, latent, w_kvb, sizes = _operands(3, "dots3", BF16, seed=8)
    pos, lengths = _chunks([200, 40, 330], [S, S, S])
    slot = jnp.asarray([0, 8, 3], jnp.int32)
    allowed = _selection(jax.random.PRNGKey(4), pos)
    selects = jnp.asarray([True, False, True])
    got = np.asarray(mla_chunk.attend_latent_chunk(
        q_nope, q_rope, latent, w_kvb, pos, lengths, slot,
        jnp.where(selects[:, None, None], allowed, False).astype(jnp.int8), selects=selects,
        window=T, block=BLOCK, interpret=True, **sizes,
    ), np.float32)
    sparse = _twin(q_nope, q_rope, latent, w_kvb, pos, lengths, slot, allowed, window=T, **sizes)
    dense = _twin(q_nope, q_rope, latent, w_kvb, pos, lengths, slot, None, window=T, **sizes)
    np.testing.assert_allclose(got[[0, 2]], sparse[[0, 2]], **TOL[BF16])
    np.testing.assert_allclose(got[1], dense[1], **TOL[BF16])


def test_without_a_slot_row_i_reads_slot_i():
    q_nope, q_rope, latent, w_kvb, sizes = _operands(3, "mistral4", BF16, seed=4)
    pos, lengths = _chunks([130, 0, 300], [S, S, S])
    run = lambda slot: np.asarray(mla_chunk.attend_latent_chunk(
        q_nope, q_rope, latent, w_kvb, pos, lengths, slot, window=T, block=BLOCK,
        interpret=True, **sizes,
    ), np.float32)
    np.testing.assert_array_equal(run(None), run(jnp.arange(3)))


def test_a_group_of_padding_alone_reads_nothing():
    q_nope, q_rope, latent, w_kvb, sizes = _operands(4, "dots3", BF16, seed=3)
    pos, lengths = _chunks([17, 0, 90, 5], [0, 0, 0, 0])
    got = mla_chunk.attend_latent_chunk(
        q_nope, q_rope, jnp.full_like(latent, jnp.nan), w_kvb, pos, lengths,
        jnp.asarray([9, 99, -1, 3]), jnp.ones((4, S, T), bool), window=T, block=BLOCK,
        interpret=True, **sizes,
    )
    assert lengths.tolist() == [0, 0, 0, 0] and (np.asarray(got, np.float32) == 0).all()


# -- the gate --------------------------------------------------------------------------

# dots3-note-prev's chunk: 128 heads, rows of 640 over a rank of 512.
ADMITTED = dict(
    s=256, q_dtype=BF16, rows_dtype=BF16, width=640, rank=512, nope=128, v_dim=128, heads=128,
    rows=16384, window=16384, block=1024, masked=True,
)
MISTRAL4 = dict(ADMITTED, width=384, rank=256, nope=64, heads=32, rows=32768, window=32768, masked=False)
# LongCat-Flash's: dots3's rows under 64 heads and no indexer's mask.
LONGCAT = dict(ADMITTED, heads=64, masked=False)


@pytest.fixture
def interpret(monkeypatch):
    monkeypatch.setenv("GAIE_DECODE_KERNEL_INTERPRET", "1")


@pytest.mark.parametrize("s", [16, 32, 64, 128, 256])
@pytest.mark.parametrize("shape", [ADMITTED, MISTRAL4, LONGCAT], ids=["dots3", "mistral4", "longcat_flash"])
def test_the_gate_admits_the_chunk_buckets_of_both_families(shape, s, interpret):
    assert mla_chunk.use_latent_chunk(**{**shape, "s": s})
    sizes = dict(s=s, bt=1024, width=shape["width"], rank=shape["rank"],
                 nk=mla_chunk._up(shape["nope"]), v=128, masked=shape["masked"])
    assert mla_chunk._heads_a_step(shape["heads"], **sizes) == mla_chunk._HEADS_A_STEP[0]
    assert mla_chunk._vmem_bytes(mla_chunk._HEADS_A_STEP[0], **sizes) <= mla_chunk._VMEM_BUDGET_BYTES


@pytest.mark.parametrize(
    "change",
    [
        dict(rows_dtype=jnp.float32),  # the rehearsals' and references' state
        dict(q_dtype=jnp.float32),
        dict(s=2),  # a decode step and its draft: the absorbed forms'
        dict(s=1),
        dict(s=24),  # queries that are no whole sublane tiles
        dict(width=576),  # Ling's rows: a leaf off the lane tile
        dict(rank=448, width=512),  # a rope key that starts inside a lane tile
        dict(v_dim=64),  # values that are no lane tile
        dict(rows=16384 + 64, window=16384 + 64),  # blocks of 64 rows: scores off the lane tile
        dict(s=4096, block=4096),  # a cold batch whose scores would not fit VMEM
    ],
    ids=["f32_rows", "f32_queries", "decode_step_and_draft", "decode_step", "ragged_queries",
         "ragged_rows", "ragged_rank", "half_tile_values", "short_blocks", "too_many_queries"],
)
def test_the_gate_refuses(change, interpret):
    assert mla_chunk.use_latent_chunk(**ADMITTED)
    assert not mla_chunk.use_latent_chunk(**{**ADMITTED, **change})


def test_two_devices_and_the_cpu_take_attend_blocks(monkeypatch):
    monkeypatch.delenv("GAIE_DECODE_KERNEL_INTERPRET", raising=False)
    assert not mla_chunk.use_latent_chunk(**ADMITTED)  # the CPU, no interpret hook
    from generativeaiexamples_tpu.ops import gqa_decode

    monkeypatch.setattr(gqa_decode, "platform_of", lambda mesh: "tpu")
    assert mla_chunk.use_latent_chunk(**ADMITTED)
    assert mla_chunk.use_latent_chunk(**ADMITTED, mesh=Mesh(np.array(jax.devices()[:1]), ("x",)))
    assert not mla_chunk.use_latent_chunk(**ADMITTED, mesh=Mesh(np.array(jax.devices()[:2]), ("x",)))


def test_a_mask_tile_of_sixteen_queries_is_filled_up_to_a_whole_int8_tile():
    """The shortest chunk bucket: the kernel's mask tiles hold 32 queries
    (int8 sublane tiles), of which the chunk's 16 count."""
    q_nope, q_rope, latent, w_kvb, sizes = _operands(2, "dots3", BF16, seed=6, s=16)
    pos, lengths = _chunks([250, 130], [16, 16], s=16)
    slot = jnp.asarray([5, 0], jnp.int32)
    allowed = _selection(jax.random.PRNGKey(9), pos)
    want = _twin(q_nope, q_rope, latent, w_kvb, pos, lengths, slot, allowed, window=T, **sizes)
    got = np.asarray(mla_chunk.attend_latent_chunk(
        q_nope, q_rope, latent, w_kvb, pos, lengths, slot, allowed, window=T, block=BLOCK,
        interpret=True, **sizes,
    ), np.float32)
    np.testing.assert_allclose(got, want, **TOL[BF16])


# -- through the mixer: the path taken, the rows written, the counters ------------------

# The tiny presets with a latent and values of one lane tile and bf16 all
# through, so that the gate admits their chunks (rows of 256: the rope key
# and 120 zero columns after the latent).
WIDE = {"kv_lora_rank": 128, "v_head_dim": 128, "torch_dtype": "bfloat16"}
MIXERS = {
    "dots3_note": (hybrid.DOTS3_NOTE_TINY, " k=24"), "mistral4": (hybrid.MISTRAL4_TINY, ""),
    "longcat_flash": (hybrid.LONGCAT_FLASH_TINY, ""),  # rescaled latents and no mask
}


@pytest.mark.parametrize("family", sorted(MIXERS))
def test_the_mixer_takes_the_kernel_where_the_gate_admits_and_counts_its_rows(family, monkeypatch):
    """``_mla_mixer`` over four rows of a state of six slots (one past a
    block's edge, one shorter than ``index_topk``, which does not select,
    one padding) on the kernel and on its twin: the same output where a
    token counts, the same rows written, ``kernel_latent`` the rows read
    on the one and absent on the other."""
    import dataclasses

    preset, k = MIXERS[family]
    cfg = hybrid.from_hf_config({**preset, **WIDE}, max_len=T, kv_dtype="bfloat16")
    cfg = dataclasses.replace(cfg, latent_block=BLOCK)
    layer = cfg.layers_of("mla")[0]
    lp = hybrid.init_params(cfg, jax.random.PRNGKey(0))["layers"][layer]
    sz = cfg.latent_sizes("mla")
    key = jax.random.PRNGKey(1)
    st = {
        name: (jax.random.normal(jax.random.fold_in(key, i), leaf.shape, jnp.float32) * 0.5).astype(leaf.dtype)
        for i, (name, leaf) in enumerate(hybrid.init_state(cfg, 6, T)[layer].items())
    }
    st["latent"] = st["latent"].at[..., sz.kv_lora_rank + sz.qk_rope_head_dim :].set(0)
    st["slot"] = jnp.asarray([4, 1, 5, 2], jnp.int32)
    b, s = 4, S
    h = jax.random.normal(jax.random.PRNGKey(2), (b, s, cfg.d_model), jnp.float32).astype(BF16)
    n_valid = jnp.asarray([S, 9, S, 0], jnp.int32)
    pos = jnp.asarray([200, 0, 300, 40], jnp.int32)[:, None] + jnp.arange(s, dtype=jnp.int32)[None]
    valid = jnp.arange(s)[None] < n_valid[:, None]

    def run():
        dispatch.TAKEN.clear()
        o, new, read = hybrid._mla_mixer(h, lp, st, pos, valid, n_valid, cfg, T, True)
        return np.asarray(o, np.float32), new, {n: int(r) for n, r in read.items()}, dict(dispatch.TAKEN)

    monkeypatch.setenv("GAIE_DECODE_KERNEL_INTERPRET", "1")
    o, new, read, taken = run()
    monkeypatch.delenv("GAIE_DECODE_KERNEL_INTERPRET")
    o_twin, new_twin, read_twin, taken_twin = run()
    site = f"attn_latent_chunk b={b} s={s} t={T}{k}"
    assert taken[site] == "pallas" and taken_twin[site] == "xla"
    counted = np.asarray(valid)
    np.testing.assert_allclose(o[counted], o_twin[counted], atol=2e-2, rtol=2e-2)
    for name in new_twin:
        np.testing.assert_array_equal(np.asarray(new[name], np.float32), np.asarray(new_twin[name], np.float32))
    # Lengths 232, 9 and 332 in whole blocks of 128; the pad row reads nothing.
    assert read.pop("kernel_latent") == read["read_latent"] == 2 * BLOCK + BLOCK + 3 * BLOCK
    assert "kernel_latent" not in read_twin and read == read_twin
    assert cfg.row_counters[:3] == hybrid.LATENT_COUNTERS == ("read_latent", "dense_latent", "kernel_latent")
