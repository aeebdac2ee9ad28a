"""A decode step's walk over the latent rows its slots hold
(``ops/mla_decode.py``'s kernel), in Pallas interpret mode on the CPU,
against ``ops.mla.attend_absorbed_blocks``, its XLA twin: the numbers in
float32 and in bf16, what is read, the gate, and through
``models/hybrid.py``'s mixer the path taken and the counters."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from generativeaiexamples_tpu.models import hybrid
from generativeaiexamples_tpu.ops import dispatch, mla, mla_decode
# The chunk kernel's test state (nine slots of 512 rows, blocks of 128) and
# its poison: NaN in every block no row of a call may read.
from tests.test_latent_chunk import BF16, BLOCK, SLOTS, TOL, T, V, _poisoned

# The families' widths as their cells hold them (Mistral-Small-4: 32 heads,
# rows of 384 = a latent of 256, a rope key of 64 and 64 zero columns;
# dots3-note-prev: 128 heads, rows of 640 = 512 + 64 + 64; LongCat-Flash:
# 64 heads over rows of 640, with no indexer in front of its step) and at
# an eighth of them, the interpreter's size.
FAMILIES = {
    "mistral4": dict(H=32, rank=256, nope=64, rope=64, width=384),
    "dots3": dict(H=128, rank=512, nope=128, rope=64, width=640),
    "longcat_flash": dict(H=64, rank=512, nope=128, rope=64, width=640),
    "mistral4_eighth": dict(H=4, rank=128, nope=16, rope=16, width=256),
}


def _operands(b, s, family, dtype, seed=0):
    H, rank, nope, rope, width = (FAMILIES[family][k] for k in ("H", "rank", "nope", "rope", "width"))
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q_nope = jax.random.normal(ks[0], (b, s, H, nope), jnp.float32).astype(dtype)
    q_rope = jax.random.normal(ks[1], (b, s, H, rope), jnp.float32).astype(dtype)
    latent = jax.random.normal(ks[2], (SLOTS, T, width), jnp.float32) * 0.5
    latent = latent.at[..., rank + rope :].set(0).astype(dtype)  # as the mixer writes a row
    w_kvb = (jax.random.normal(ks[3], (rank, H * (nope + V)), jnp.float32) * rank**-0.5).astype(dtype)
    return q_nope, q_rope, latent, w_kvb, dict(rank=rank, nope=nope, v_dim=V)


def _step(lengths, s):
    """A step of ``s`` queries a row that ends each row at its length: the
    positions, and the lengths (0: a slot that does not decode)."""
    lengths = jnp.asarray(lengths, jnp.int32)
    return lengths[:, None] - s + jnp.arange(s, dtype=jnp.int32)[None], lengths


def _twin(q_nope, q_rope, latent, w_kvb, pos, lengths, slot, *, window, **sizes):
    """``attend_absorbed_blocks`` a row at a time, as the mixer's XLA path
    calls it."""
    rows = []
    for i in range(q_nope.shape[0]):
        if int(lengths[i]) == 0:
            rows.append(jnp.zeros(q_nope.shape[1:3] + (V,), q_nope.dtype))
            continue
        one = slice(i, i + 1)
        rows.append(mla.attend_absorbed_blocks(
            q_nope[one], q_rope[one], latent, w_kvb, pos[one], lengths[one], block=BLOCK,
            slot=slot[one], window=window, **sizes,
        )[0])
    return np.asarray(jnp.stack(rows), np.float32)


# A slot that does not decode, a row of one token (two for a token and its
# draft), a length on a block's edge, one past it, the whole window, one
# inside a block; the rows in another order than their slots.
LENGTHS = {1: [0, 1, 128, 129, T, 300, 0], 2: [0, 2, 128, 129, T, 300, 0]}
SLOT = [3, 0, 8, 5, 1, 7, 2]
# The kernel at the shape classes its gate tells apart, not their product:
# each family's widths once in the step's own form (bf16, one query a row),
# the small widths in float32 and with a draft beside the token.
CASES = [
    ("mistral4", 1, BF16), ("dots3", 1, BF16), ("longcat_flash", 1, BF16), ("mistral4", 2, BF16),
    ("mistral4_eighth", 1, jnp.float32), ("mistral4_eighth", 2, jnp.float32),
    ("mistral4_eighth", 2, BF16),
]


@pytest.mark.parametrize(
    "family, s, dtype", CASES, ids=[f"{f}-s{s}-{jnp.dtype(d).name}" for f, s, d in CASES]
)
@pytest.mark.parametrize("slotted", [True, False], ids=["slot", "no_slot"])
@pytest.mark.parametrize("window", [T, 256], ids=["whole_leaf", "short_window"])
def test_a_step_gets_attend_absorbed_blocks_numbers_from_its_slots_blocks_alone(
    window, slotted, family, s, dtype
):
    b = len(SLOT)
    q_nope, q_rope, latent, w_kvb, sizes = _operands(b, s, family, dtype, seed=s)
    lengths = [min(n, window) for n in LENGTHS[s]]  # a step past the window is another program's
    pos, lengths = _step(lengths, s)
    slot = jnp.asarray(SLOT if slotted else range(b), jnp.int32)
    want = _twin(q_nope, q_rope, latent, w_kvb, pos, lengths, slot, window=window, **sizes)
    unread, walked = _poisoned(latent, slot, lengths, window)
    got = np.asarray(mla_decode.attend_latent_decode(
        q_nope, q_rope, unread, w_kvb, pos, lengths, slot if slotted else None, window=window,
        block=BLOCK, interpret=True, **sizes,
    ), np.float32)
    assert np.isfinite(got).all()  # nothing past a slot's blocks, nothing of another slot
    np.testing.assert_allclose(got, want, **TOL[dtype])
    assert (got[np.asarray(lengths) == 0] == 0).all()  # exact zeros, nothing read
    assert int(mla.rows_in_blocks(lengths, window, BLOCK).sum()) == walked


def test_a_draft_sees_one_row_more_than_its_token():
    """Two queries a row: the token at ``p`` must not see the draft's row
    ``p + 1``, on a block's edge (the draft's row opens a block) and
    inside one."""
    q_nope, q_rope, latent, w_kvb, sizes = _operands(2, 2, "mistral4_eighth", jnp.float32, seed=9)
    pos, lengths = _step([257, 300], 2)
    slot = jnp.asarray([6, 2], jnp.int32)
    run = lambda rows: np.asarray(mla_decode.attend_latent_decode(
        q_nope, q_rope, rows, w_kvb, pos, lengths, slot, window=T, block=BLOCK, interpret=True,
        **sizes,
    ), np.float32)
    got = run(latent)
    # Another row at the draft's position moves the draft's output alone.
    moved = run(latent.at[slot, lengths - 1].add(1.0))
    np.testing.assert_array_equal(got[:, 0], moved[:, 0])
    assert np.abs(got[:, 1] - moved[:, 1]).max() > 1e-3
    np.testing.assert_allclose(
        got, _twin(q_nope, q_rope, latent, w_kvb, pos, lengths, slot, window=T, **sizes),
        **TOL[jnp.float32],
    )


def test_a_step_in_which_no_slot_decodes_reads_nothing():
    q_nope, q_rope, latent, w_kvb, sizes = _operands(4, 1, "mistral4_eighth", BF16, seed=3)
    pos, lengths = _step([0, 0, 0, 0], 1)
    got = mla_decode.attend_latent_decode(
        q_nope, q_rope, jnp.full_like(latent, jnp.nan), w_kvb, pos, lengths,
        jnp.asarray([9, 99, -1, 3]), window=T, block=BLOCK, interpret=True, **sizes,
    )
    assert (np.asarray(got, np.float32) == 0).all()


# -- the gate --------------------------------------------------------------------------

# Mistral-Small-4's decode step in its cell: 16 slots of 32,768 rows of 384.
ADMITTED = dict(
    s=1, q_dtype=BF16, rows_dtype=BF16, width=384, rank=256, heads=32, rows=32768,
    window=32768, block=2048,
)
DOTS3 = dict(ADMITTED, width=640, rank=512, heads=128, rows=16384, window=16384)
LONGCAT = dict(DOTS3, heads=64)


@pytest.fixture
def interpret(monkeypatch):
    monkeypatch.setenv("GAIE_DECODE_KERNEL_INTERPRET", "1")


@pytest.mark.parametrize("window", [2048, 4096, 8192, 16384, 32768])
@pytest.mark.parametrize("s", [1, 2])
@pytest.mark.parametrize("shape", [ADMITTED, DOTS3, LONGCAT], ids=["mistral4", "dots3", "longcat_flash"])
def test_the_gate_admits_the_decode_windows_of_both_families(shape, s, window, interpret):
    window = min(window, shape["rows"])
    assert mla_decode.use_latent_decode(**{**shape, "s": s, "window": window})
    assert mla_decode._vmem_bytes(
        s * shape["heads"], 2048, shape["width"], shape["rank"]
    ) <= mla_decode._VMEM_BUDGET_BYTES // 2


@pytest.mark.parametrize(
    "change",
    [
        dict(rows_dtype=jnp.float32),  # the rehearsals' and references' state
        dict(q_dtype=jnp.float32),
        dict(s=3),  # more queries a row than a step has: a chunk's
        dict(s=256),
        dict(width=320),  # Mistral-Small-4's row before it was filled up to the lanes
        dict(width=576, rank=512),  # Ling's rows: a leaf off the lane tile
        dict(rank=192, width=256),  # a rope key that starts inside a lane tile
        dict(heads=4),  # queries that are no whole bf16 sublane tile
        dict(rows=32768 + 64, window=32768 + 64),  # blocks of 64 rows: scores off the lane tile
        dict(heads=4096, block=4096),  # scores that would not fit VMEM
    ],
    ids=["f32_rows", "f32_queries", "three_queries", "a_chunk", "ragged_rows", "lings_rows",
         "ragged_rank", "few_heads", "short_blocks", "too_many_queries"],
)
def test_the_gate_refuses(change, interpret):
    assert mla_decode.use_latent_decode(**ADMITTED)
    assert not mla_decode.use_latent_decode(**{**ADMITTED, **change})


def test_two_devices_and_the_cpu_take_attend_absorbed_blocks(monkeypatch):
    monkeypatch.delenv("GAIE_DECODE_KERNEL_INTERPRET", raising=False)
    assert not mla_decode.use_latent_decode(**ADMITTED)  # the CPU, no interpret hook
    from generativeaiexamples_tpu.ops import gqa_decode

    monkeypatch.setattr(gqa_decode, "platform_of", lambda mesh: "tpu")
    assert mla_decode.use_latent_decode(**ADMITTED)
    assert mla_decode.use_latent_decode(**ADMITTED, mesh=Mesh(np.array(jax.devices()[:1]), ("x",)))
    assert not mla_decode.use_latent_decode(**ADMITTED, mesh=Mesh(np.array(jax.devices()[:2]), ("x",)))


# -- through the mixer: the path taken, the rows written, the counters ------------------

# The tiny preset with a latent and values of one lane tile, sixteen heads
# and bf16 all through, so that the gate admits its decode step (rows of
# 256: the rope key and 120 zero columns after the latent).
WIDE = {"kv_lora_rank": 128, "v_head_dim": 128, "num_attention_heads": 16, "torch_dtype": "bfloat16"}


@pytest.mark.parametrize("preset", ["MISTRAL4_TINY", "LONGCAT_FLASH_TINY"])
def test_the_mixer_takes_the_kernel_where_the_gate_admits_and_counts_its_rows(preset, monkeypatch):
    """(Mistral-Small-4's form, and LongCat-Flash's: rescaled latents, the
    plain frequencies.)  ``_mla_mixer``'s decode step over four rows of a state of six slots
    (one on a block's edge, one of a single token, one that does not
    decode) on the kernel and on its twin: the same output where a token
    counts, the same rows written, ``kernel_latent`` the rows read on the
    one and absent on the other."""
    cfg = hybrid.from_hf_config({**getattr(hybrid, preset), **WIDE}, max_len=T, kv_dtype="bfloat16")
    cfg = dataclasses.replace(cfg, latent_decode_block=BLOCK)
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
    b, s = 4, 1
    h = jax.random.normal(jax.random.PRNGKey(2), (b, s, cfg.d_model), jnp.float32).astype(BF16)
    n_valid = jnp.asarray([1, 1, 1, 0], jnp.int32)
    pos = jnp.asarray([255, 0, 300, 40], jnp.int32)[:, None]
    valid = n_valid[:, None] > 0

    def run():
        dispatch.TAKEN.clear()
        o, new, read = hybrid._mla_mixer(h, lp, st, pos, valid, n_valid, cfg, T, True)
        return np.asarray(o, np.float32), new, {n: int(r) for n, r in read.items()}, dict(dispatch.TAKEN)

    monkeypatch.setenv("GAIE_DECODE_KERNEL_INTERPRET", "1")
    o, new, read, taken = run()
    monkeypatch.delenv("GAIE_DECODE_KERNEL_INTERPRET")
    o_twin, new_twin, read_twin, taken_twin = run()
    site = f"attn_latent_decode b={b} t={T}"
    assert taken[site] == "pallas" and taken_twin[site] == "xla"
    counted = np.asarray(valid)
    np.testing.assert_allclose(o[counted], o_twin[counted], atol=2e-2, rtol=2e-2)
    for name in new_twin:
        np.testing.assert_array_equal(np.asarray(new[name], np.float32), np.asarray(new_twin[name], np.float32))
    # Lengths 256, 1 and 301 in whole blocks of 128; the idle slot reads nothing.
    assert read.pop("kernel_latent") == read["read_latent"] == 2 * BLOCK + BLOCK + 3 * BLOCK
    assert "kernel_latent" not in read_twin and read == read_twin
    assert cfg.row_counters[:3] == hybrid.LATENT_COUNTERS == ("read_latent", "dense_latent", "kernel_latent")
