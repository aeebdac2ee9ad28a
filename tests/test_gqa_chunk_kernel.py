"""A prefill chunk's attention over the rows its slot holds
(``ops/gqa_decode.py``'s chunk kernel), in Pallas interpret mode on the
CPU, against ``ops.gqa.attend_rows``: the numbers, what is read, the gate,
and through ``models/hybrid.py`` the rows written and the counters."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from generativeaiexamples_tpu.engine.serving_models import HybridServing
from generativeaiexamples_tpu.models import hybrid
from generativeaiexamples_tpu.ops import dispatch, gqa, gqa_decode

D = 128
T = 384  # three blocks of 128
BLOCK = 128
SLOTS = 7
HEADS = [(8, 2), (32, 4), (64, 8)]  # ZAYA's, Mellum's, K-EXAONE's
BF16 = jnp.bfloat16


@pytest.fixture
def interpret(monkeypatch):
    monkeypatch.setenv("GAIE_DECODE_KERNEL_INTERPRET", "1")


def _operands(b, s, h, kh, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (b, s, h, D), jnp.float32).astype(BF16)
    k = jax.random.normal(ks[1], (SLOTS, T, kh * D), jnp.float32).astype(BF16)
    v = jax.random.normal(ks[2], (SLOTS, T, kh * D), jnp.float32).astype(BF16)
    return q, k, v


def _chunks(starts, counts, s):
    start = jnp.asarray(starts, jnp.int32)
    pos = start[:, None] + jnp.arange(s, dtype=jnp.int32)[None]
    valid = jnp.arange(s)[None] < jnp.asarray(counts, jnp.int32)[:, None]
    return pos, valid


# Chunks of 16 that start a slot, cross a block's edge (120 -> 136), end on
# one (240 -> 256), fill the slot's last rows, count 5 of their 16 tokens,
# and a group's padding; chunks of 256 that start a slot, end with it, cross
# both edges, and the padding.  Under the window of 256: what fits it.
ROWS = {
    (16, T): ([0, 120, 240, T - 16, 100, 57], [16, 16, 16, 16, 5, 0]),
    (16, 256): ([0, 120, 240, 100, 57], [16, 16, 16, 5, 0]),
    (256, T): ([0, T - 256, 100, 3, 9], [256, 256, 256, 77, 0]),
    (256, 256): ([0, 0, 0], [256, 130, 0]),
}


@pytest.mark.parametrize("window", [T, 256], ids=["whole", "window_256"])
@pytest.mark.parametrize("h,kh", HEADS)
@pytest.mark.parametrize("s", [16, 256])
def test_a_chunk_gets_attend_rows_numbers_from_its_slots_blocks_alone(s, h, kh, window):
    starts, counts = ROWS[s, window]
    b = len(starts)
    q, k, v = _operands(b, s, h, kh, seed=s + h)
    pos, valid = _chunks(starts, counts, s)
    slot = jnp.asarray(np.random.RandomState(s + h).permutation(SLOTS)[:b], jnp.int32)
    lengths = gqa_decode.chunk_lengths(pos, valid, window)
    ends = np.asarray([st + n if n else 0 for st, n in zip(starts, counts)])
    assert lengths.tolist() == ends.tolist()
    want = np.asarray(gqa.attend_rows(q, k[slot, :window], v[slot, :window], pos, n_kv=kh), np.float32)
    # What no chunk may touch: every block past its slot's last one, and
    # every row of a slot that no chunk of the call is (a pad row's too).
    reach = np.zeros(SLOTS, np.int64)
    reach[np.asarray(slot)] = -(-ends // BLOCK) * BLOCK
    poison = jnp.asarray((np.arange(T)[None, :] >= reach[:, None])[:, :, None])
    got = gqa_decode.attend_rows_chunk(
        q, jnp.where(poison, jnp.nan, k), jnp.where(poison, jnp.nan, v), pos, lengths,
        n_kv=kh, window=window, slot=slot, interpret=True,
    )
    got, counted = np.asarray(got, np.float32), np.asarray(valid)
    np.testing.assert_allclose(got[counted], want[counted], atol=2e-2, rtol=2e-2)
    assert np.isfinite(got).all()  # a token that does not count sees what its row holds
    assert (got[ends == 0] == 0).all()  # padding: exact zeros, nothing read
    assert int(gqa_decode.rows_walked(lengths, T, window)) == int(reach.sum())


@pytest.mark.parametrize("h,kh", HEADS)
def test_without_a_slot_row_i_reads_slot_i(h, kh):
    q, k, v = _operands(3, 16, h, kh, seed=4)
    pos, valid = _chunks([130, 0, 300], [16, 16, 16], 16)
    lengths = gqa_decode.chunk_lengths(pos, valid, T)
    plain = gqa_decode.attend_rows_chunk(q, k, v, pos, lengths, n_kv=kh, window=T, interpret=True)
    named = gqa_decode.attend_rows_chunk(
        q, k, v, pos, lengths, n_kv=kh, window=T, slot=jnp.arange(3), interpret=True
    )
    np.testing.assert_array_equal(np.asarray(plain, np.float32), np.asarray(named, np.float32))
    want = gqa.attend_rows(q, k[:3], v[:3], pos, n_kv=kh)
    np.testing.assert_allclose(
        np.asarray(plain, np.float32), np.asarray(want, np.float32), atol=2e-2, rtol=2e-2
    )


@pytest.mark.parametrize("s", [16, 256])
def test_a_group_of_padding_alone_reads_nothing(s):
    q, k, v = _operands(4, s, 8, 2, seed=3)
    pos, valid = _chunks([17, 0, 90, 5], [0, 0, 0, 0], s)
    lengths = gqa_decode.chunk_lengths(pos, valid, T)
    got = gqa_decode.attend_rows_chunk(
        q, jnp.full_like(k, jnp.nan), jnp.full_like(v, jnp.nan), pos, lengths,
        n_kv=2, window=T, slot=jnp.asarray([9, 99, -1, 3]), interpret=True,
    )
    assert lengths.tolist() == [0, 0, 0, 0] and (np.asarray(got, np.float32) == 0).all()
    assert int(gqa_decode.rows_walked(lengths, T, T)) == 0


def test_a_module_one_position_behind_counts_from_its_first_row_that_counts():
    """``_module_behind``'s chunk starts at position -1, which does not
    count: the walk still covers every row the counted positions wrote."""
    pos, valid = _chunks([-1, 127], [16, 16], 16)
    valid = valid & (pos >= 0)
    assert gqa_decode.chunk_lengths(pos, valid, T).tolist() == [15, 143]
    q, k, v = _operands(2, 16, 8, 2, seed=5)
    got = gqa_decode.attend_rows_chunk(
        q, k, v, pos, gqa_decode.chunk_lengths(pos, valid, T), n_kv=2, window=T, interpret=True
    )
    want = gqa.attend_rows(q, k[:2], v[:2], pos, n_kv=2)
    got, want, counted = np.asarray(got, np.float32), np.asarray(want, np.float32), np.asarray(valid)
    np.testing.assert_allclose(got[counted], want[counted], atol=2e-2, rtol=2e-2)
    assert (got[0, 0] == 0).all()  # the query at -1 sees no row


# -- the gate --------------------------------------------------------------------------

ADMITTED = dict(
    s=256, q_dtype=BF16, rows_dtype=BF16, width=4 * D, head_dim=D, rows=8192, window=8192, n_q=32,
)


@pytest.mark.parametrize("h,kh", HEADS)
@pytest.mark.parametrize("s", [16, 32, 64, 128, 256])
@pytest.mark.parametrize("window", [2048, 8192])
def test_the_gate_admits_every_chunk_bucket_of_the_three_cells(s, h, kh, window, interpret):
    shape = {**ADMITTED, "s": s, "width": kh * D, "n_q": h, "window": window}
    assert gqa_decode.use_row_chunk(**shape)
    per_kv = s * h // kh
    assert per_kv % gqa_decode._chunk_tile(per_kv) == 0
    assert gqa_decode._chunk_vmem_bytes(512, per_kv, D) <= gqa_decode._VMEM_BUDGET_BYTES // 4


@pytest.mark.parametrize(
    "change",
    [
        dict(rows_dtype=jnp.float32),  # the rehearsals' and references' state
        dict(q_dtype=jnp.float32),
        dict(s=2),  # a decode step: the walk's
        dict(s=24, n_q=5, width=5 * D),  # a KV head's queries are no whole sublane tiles
        dict(width=4 * 64, head_dim=64),  # a KV head that is no lane tile
        dict(rows=8200),  # blocks do not tile the rows
        dict(rows=64, window=64),  # a cold batch shorter than a lane tile of keys
        dict(s=8192, n_q=64, width=8 * D),  # a cold batch whose queries would not fit VMEM
    ],
    ids=["f32_rows", "f32_queries", "decode_step", "ragged_queries", "half_tile_heads",
         "ragged_rows", "short_rows", "too_many_queries"],
)
def test_the_gate_refuses(change, interpret):
    assert gqa_decode.use_row_chunk(**ADMITTED)
    assert not gqa_decode.use_row_chunk(**{**ADMITTED, **change})


def test_two_devices_and_the_cpu_take_attend_rows(monkeypatch):
    monkeypatch.delenv("GAIE_DECODE_KERNEL_INTERPRET", raising=False)
    assert not gqa_decode.use_row_chunk(**ADMITTED)  # the CPU, no interpret hook
    monkeypatch.setattr(gqa_decode, "platform_of", lambda mesh: "tpu")
    assert gqa_decode.use_row_chunk(**ADMITTED)
    assert gqa_decode.use_row_chunk(**ADMITTED, mesh=Mesh(np.array(jax.devices()[:1]), ("x",)))
    assert not gqa_decode.use_row_chunk(**ADMITTED, mesh=Mesh(np.array(jax.devices()[:2]), ("x",)))


# -- through the model: the rows written in place, the path taken, the counters ---------------------

MAX_LEN = 384
CHUNK = 16


def _cfg(preset: dict, draft: str = "") -> hybrid.HybridConfig:
    """A tiny preset with heads of one lane tile and bf16 all through, so
    that the gate admits its chunks; one period deep (three window layers
    and a full one)."""
    return hybrid.from_hf_config(
        {**preset, "num_hidden_layers": 4, "head_dim": D, "torch_dtype": "bfloat16"},
        max_len=MAX_LEN, kv_dtype="bfloat16", draft=draft,
    )


CASES = {
    "mellum": (hybrid.MELLUM_TINY, "", "attn_full"),
    "exaone_draft_on": (hybrid.EXAONE_TINY, "mtp", "attn_full"),
}


def _state_after(serving, params, prompts, slots, rows_at_once: bool):
    """The slots' state after each prompt's chunks, through
    ``prefill_rows`` (every prompt's chunk ``i`` in one program, padded to
    4 rows) or ``prefill_row`` (a chunk a program)."""
    cache = serving.init_state(SLOTS, MAX_LEN)
    # What an earlier occupant left: rows that a new prompt must not see.
    cache = jax.tree.map(lambda x: jnp.full_like(x, 0.5), cache)
    hidden, aux = {}, 0
    most = max(len(p) for p in prompts)
    for at in range(0, most, CHUNK):
        group = [(i, p[at:at + CHUNK]) for i, p in enumerate(prompts) if len(p) > at]
        if rows_at_once:
            pad = 4 - len(group)
            tokens = jnp.asarray([list(c) + [0] * (CHUNK - len(c)) for _, c in group] + [[0] * CHUNK] * pad)
            counts = jnp.asarray([len(c) for _, c in group] + [0] * pad, jnp.int32)
            where = jnp.asarray([slots[i] for i, _ in group] + [0] * pad, jnp.int32)
            start = jnp.where(counts > 0, at, 0).astype(jnp.int32)
            cache, h, c = serving.prefill_rows(params, cache, tokens, start, counts, where, MAX_LEN)
            aux = aux + np.asarray(c)
            for row, (i, chunk) in enumerate(group):
                hidden[i] = h[row, len(chunk) - 1]
        else:
            for i, chunk in group:
                tokens = jnp.asarray([list(chunk) + [0] * (CHUNK - len(chunk))])
                cache, h, c = serving.prefill_row(
                    params, cache, tokens, jnp.int32(at), jnp.int32(len(chunk)), jnp.int32(slots[i]), MAX_LEN
                )
                aux = aux + np.asarray(c)
                hidden[i] = h[0, len(chunk) - 1]
    return cache, hidden, dict(zip(serving.counter_names, np.asarray(aux).tolist()))


def _assert_close(a, b):
    """Equal to bf16's rounding, but for the few positions at which the
    rounding tipped a router's choice in one grouping and not the other
    (and the window layers' eight positions behind them)."""
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    off = np.abs(a - b) > 6e-2 + 6e-2 * np.abs(b)
    assert off.mean() < 0.03, (off.mean(), np.abs(a - b).max())


@pytest.mark.parametrize("case", sorted(CASES))
def test_chunks_in_place_leave_what_a_row_at_a_time_leaves(case, interpret, monkeypatch):
    monkeypatch.setenv("GAIE_MOE_KERNEL_INTERPRET", "1")
    preset, draft, site = CASES[case]
    cfg = _cfg(preset, draft)
    serving = HybridServing(cfg, None, MAX_LEN)
    assert serving.rows_in_place
    params = serving.prepare_params(None, quantize=False, matmul_kernel="xla", seed=1)
    rng = np.random.RandomState(7)
    # Across a block's edge, short of a chunk, and one chunk alone.
    prompts = [rng.randint(1, cfg.vocab_size, n).tolist() for n in (150, 40, 16)]
    slots = [5, 2, 6]
    dispatch.TAKEN.clear()
    together, h_rows, counters = _state_after(serving, params, prompts, slots, True)
    taken = {s_: p for s_, p in dispatch.TAKEN.items() if "_chunk" in s_}
    assert taken[f"{site}_chunk b=4 s={CHUNK} t={MAX_LEN}"] == "pallas"
    if draft:
        assert taken[f"mtp_attn_full_chunk b=4 s={CHUNK} t={MAX_LEN}"] == "pallas"
    dispatch.TAKEN.clear()
    apart, h_row, _ = _state_after(serving, params, prompts, slots, False)
    assert dispatch.TAKEN[f"{site}_chunk b=1 s={CHUNK} t={MAX_LEN}"] == "pallas"
    for i in range(len(prompts)):
        _assert_close(h_rows[i], h_row[i])
    used = set(slots)
    for layer_a, layer_b in zip(together, apart):
        for name in layer_a:
            a, b = np.asarray(layer_a[name], np.float32), np.asarray(layer_b[name], np.float32)
            for slot in range(SLOTS):
                if slot not in used:
                    assert (a[slot] == 0.5).all(), (name, slot)  # no other slot's rows are touched
                    continue
                n = len(prompts[slots.index(slot)])
                if name in hybrid.ROW_LEAVES:
                    _assert_close(a[slot, :n], b[slot, :n])
                    assert (a[slot, n:] == 0.5).all(), (name, slot)
                else:
                    _assert_close(a[slot], b[slot])
    # Each live chunk's length in whole blocks against rows x window.
    calls = len(cfg.layers_of("full")) + (1 if draft else 0)
    assert 0 < counters["attn_rows_read_full_prefill"] < counters["attn_rows_dense_full_prefill"]
    assert counters["attn_rows_dense_full_prefill"] == calls * 10 * 4 * MAX_LEN
    if not draft:
        walked = sum(-(-min(at + CHUNK, len(p)) // BLOCK) * BLOCK for p in prompts for at in range(0, len(p), CHUNK))
        assert counters["attn_rows_read_full_prefill"] == calls * walked


@pytest.mark.parametrize("h,kh", HEADS)
@pytest.mark.parametrize("site", ["attn_full", "attn_cca"])
def test_full_rows_writes_a_groups_rows_where_they_belong_and_reads_them_there(site, h, kh, monkeypatch):
    """``_full_rows`` with ``slot`` (the ``cca`` kind's chunk programs, and
    the ``full`` kind's since they go in place) on the kernel and on its
    twin (ZAYA's bf16 stack does not run on the CPU: its layer is held to
    the twin here, at its head counts and the others')."""
    b, s = 4, CHUNK
    q, old_k, old_v = _operands(b, s, h, kh, seed=h)
    ks = jax.random.split(jax.random.PRNGKey(11), 2)
    k, v = (jax.random.normal(key, (b, s, kh * D), jnp.float32).astype(BF16) for key in ks)
    pos, valid = _chunks([120, 0, 300, 40], [16, 9, 16, 0], s)
    slot = jnp.asarray([4, 1, 6, 3], jnp.int32)
    n_valid = valid.sum(-1).astype(jnp.int32)

    def run():
        dispatch.TAKEN.clear()
        o, new_k, new_v, read = hybrid._full_rows(
            q, k, v, old_k, old_v, pos, valid, n_valid, n_kv=kh, window=T, apart=True,
            scope=f"layer/{site}", site=site, mesh=None, slot=slot,
        )
        return np.asarray(o, np.float32), new_k, new_v, [int(r) for r in read], dict(dispatch.TAKEN)

    monkeypatch.setenv("GAIE_DECODE_KERNEL_INTERPRET", "1")
    o, new_k, new_v, read, taken = run()
    monkeypatch.delenv("GAIE_DECODE_KERNEL_INTERPRET")
    o_twin, twin_k, twin_v, read_twin, taken_twin = run()
    assert taken == {f"{site}_chunk b={b} s={s} t={T}": "pallas"}
    assert taken_twin == {f"{site}_chunk b={b} s={s} t={T}": "xla"}
    counted = np.asarray(valid)
    np.testing.assert_allclose(o[counted], o_twin[counted], atol=2e-2, rtol=2e-2)
    assert (o[3] == 0).all()  # the pad row
    for new, twin, old, rows in ((new_k, twin_k, old_k, k), (new_v, twin_v, old_v, v)):
        np.testing.assert_array_equal(np.asarray(new, np.float32), np.asarray(twin, np.float32))
        want = np.asarray(old, np.float32).copy()
        for i in range(b):
            n = int(n_valid[i])
            want[int(slot[i]), int(pos[i, 0]) : int(pos[i, 0]) + n] = np.asarray(rows, np.float32)[i, :n]
        np.testing.assert_array_equal(np.asarray(new, np.float32), want)  # and no other row
    # Lengths 136, 9 and 316 in whole blocks, against 4 rows x the window.
    assert read == [0, 2 * BLOCK + BLOCK + 3 * BLOCK, 0, b * T]
    assert read_twin == [0, b * T, 0, b * T]
