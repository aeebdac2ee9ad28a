"""A window layer's prefill chunk over its ring and its own rows
(``ops/gqa_decode.py``'s ring kernel), in Pallas interpret mode on the CPU,
against ``ops.gqa.attend_ring``: the numbers, what is read, the gate, and
through ``models/hybrid.py`` the rings written and the counters."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from generativeaiexamples_tpu.engine.serving_models import HybridServing
from generativeaiexamples_tpu.models import hybrid
from generativeaiexamples_tpu.ops import dispatch, gqa, gqa_decode
from tests.test_gqa_chunk_kernel import CHUNK, MAX_LEN, SLOTS, _assert_close, _state_after

D = 128
HEADS = [(32, 4), (64, 8)]  # Mellum's, K-EXAONE's
BUCKETS = [16, 32, 64, 128, 256]  # the chunk buckets of the two cells
BF16 = jnp.bfloat16


@pytest.fixture
def interpret(monkeypatch):
    monkeypatch.setenv("GAIE_DECODE_KERNEL_INTERPRET", "1")


def _operands(b, s, h, kh, ring, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    shapes = [(b, s, h, D), (b, s, kh * D), (b, s, kh * D), (b, ring, kh * D), (b, ring, kh * D)]
    return [jax.random.normal(k, shape, jnp.float32).astype(BF16) for k, shape in zip(ks, shapes)]


def _positions(firsts, s):
    return jnp.asarray(firsts, jnp.int32)[:, None] + jnp.arange(s, dtype=jnp.int32)[None]


def _both(operands, firsts, counts, kh, window):
    """(the kernel's, ``attend_ring``'s) outputs in float32, the rings of
    the rows with nothing that counts poisoned for the kernel."""
    q, k_new, v_new, ring_k, ring_v = operands
    pos, n_valid = _positions(firsts, q.shape[1]), jnp.asarray(counts, jnp.int32)
    want = gqa.attend_ring(q, k_new, v_new, ring_k, ring_v, pos, n_kv=kh, window=window)
    pad = (n_valid == 0)[:, None, None]
    got = gqa_decode.attend_ring_chunk(
        q, k_new, v_new, jnp.where(pad, jnp.nan, ring_k), jnp.where(pad, jnp.nan, ring_v),
        pos, n_valid, n_kv=kh, window=window, interpret=True,
    )
    return np.asarray(got, np.float32), np.asarray(want, np.float32)


# The chunk's first position: 0 (a ring that holds nothing of this
# prompt), inside the first lap (rows past it hold nothing), exactly the
# ring, past a wrap that is on no block's edge, far past it, and a wrap on
# a block's edge in the ring's middle; the fifth row's last tokens do not
# count, the last row is a group's padding.
def _firsts(ring):
    return [0, ring // 2 - 3, ring, 2 * ring + 77, 37 * ring + ring // 2 + 5, 3 * ring + ring // 2, 9]


# Interpret mode pays for every program of the grid (rows x KV heads), so
# the sweep is over what the gate tells apart and no wider.  Every bucket,
# the smallest and the largest at a ring of one block (K-EXAONE's) and of
# two (Mellum's), the ones between at one of them, every kind of first
# position: at 16 query heads over 2, which is both cells' 8 queries a KV
# head and more than one KV head.  One KV head alone at the smaller
# buckets.  The cells' own head counts once each, at their largest chunk
# and their own ring, over a wrap, a row whose last tokens do not count
# and the padding.
RINGS = [(16, 128), (16, 1024), (32, 1024), (64, 128), (128, 1024), (256, 128), (256, 1024)]
EVERY_KIND, WRAP_PARTLY_COUNTED_PAD = (0, 1, 2, 3, 4, 5, 6), (3, 4, 6)
SWEEP = (
    [(s, ring, 16, 2, EVERY_KIND) for s, ring in RINGS]
    + [(s, ring, 8, 1, EVERY_KIND) for s, ring in RINGS[:5]]
    + [(256, 1024, *HEADS[0], WRAP_PARTLY_COUNTED_PAD), (256, 128, *HEADS[1], WRAP_PARTLY_COUNTED_PAD)]
)


@pytest.mark.parametrize(
    "s,ring,h,kh,kinds", SWEEP, ids=[f"{s}-{ring}-{h}-{kh}-{len(kinds)}rows" for s, ring, h, kh, kinds in SWEEP]
)
def test_a_chunk_gets_attend_rings_numbers(s, ring, h, kh, kinds):
    firsts, counts = _firsts(ring), [s, s, s, s, s - 5, s, 0]
    firsts, counts = [firsts[i] for i in kinds], [counts[i] for i in kinds]
    got, want = _both(_operands(len(firsts), s, h, kh, ring, seed=s + h), firsts, counts, kh, ring)
    live = np.asarray(counts) > 0
    # Every query of a live row, those that do not count too: the masks do
    # not know which tokens count.
    np.testing.assert_allclose(got[live], want[live], atol=2e-2, rtol=2e-2)
    assert (got[~live] == 0).all()  # padding: exact zeros, its ring not read


@pytest.mark.parametrize("first", [0, 40, 128, 333, 128 * 9])
def test_a_ring_wider_than_the_window_masks_by_the_window(first):
    """``R`` rows under a window of fewer: the compare is the window's."""
    s, h, kh, ring, window = 64, 8, 2, 256, 128
    got, want = _both(_operands(1, s, h, kh, ring, seed=first), [first], [s], kh, window)
    np.testing.assert_allclose(got, want, atol=2e-2, rtol=2e-2)


@pytest.mark.parametrize("s", [16, 256])
def test_a_group_of_padding_alone_reads_nothing(s):
    q, k_new, v_new, ring_k, ring_v = _operands(4, s, 8, 2, 128, seed=3)
    got = gqa_decode.attend_ring_chunk(
        q, k_new, v_new, jnp.full_like(ring_k, jnp.nan), jnp.full_like(ring_v, jnp.nan),
        _positions([17, 0, 90, 5], s), jnp.zeros((4,), jnp.int32), n_kv=2, window=128,
        interpret=True,
    )
    assert (np.asarray(got, np.float32) == 0).all()


def test_a_pad_row_between_live_rows_is_passed_over():
    """The copies run ahead from program to program: over a pad row to the
    next row that has a ring to read."""
    s, h, kh, ring = 16, 8, 2, 128
    firsts, counts = [200, 5, 9, 300, 0], [16, 0, 0, 16, 0]
    got, want = _both(_operands(5, s, h, kh, ring, seed=8), firsts, counts, kh, ring)
    live = np.asarray(counts) > 0
    np.testing.assert_allclose(got[live], want[live], atol=2e-2, rtol=2e-2)
    assert (got[~live] == 0).all()


# -- the gate --------------------------------------------------------------------------

ADMITTED = dict(s=256, q_dtype=BF16, rows_dtype=BF16, width=4 * D, head_dim=D, ring=1024, n_q=32)


@pytest.mark.parametrize("h,kh,ring", [(32, 4, 1024), (64, 8, 128)], ids=["mellum", "k-exaone"])
@pytest.mark.parametrize("s", BUCKETS)
def test_the_gate_admits_every_chunk_bucket_of_the_two_cells(s, h, kh, ring, interpret):
    assert gqa_decode.use_ring_chunk(**{**ADMITTED, "s": s, "width": kh * D, "n_q": h, "ring": ring})
    per_kv = s * h // kh
    assert per_kv % gqa_decode._chunk_tile(per_kv) == 0
    assert gqa_decode._ring_vmem_bytes(ring, s, per_kv, D) <= gqa_decode._VMEM_BUDGET_BYTES // 2


@pytest.mark.parametrize(
    "change",
    [
        dict(rows_dtype=jnp.float32),  # the rehearsals' and references' state
        dict(q_dtype=jnp.float32),
        dict(s=2),  # a decode step: ``attend_ring``'s wide form
        dict(s=1),
        dict(s=24, n_q=5, width=5 * D),  # a KV head's queries are no whole sublane tiles
        dict(width=4 * 64, head_dim=64),  # a KV head that is no lane tile
        dict(ring=1000),  # blocks do not tile the ring
        dict(ring=64),  # a ring shorter than a lane tile of keys
        dict(s=4096),  # a cold batch whose queries would not fit VMEM
    ],
    ids=["f32_rows", "f32_queries", "verify_step", "decode_step", "ragged_queries",
         "half_tile_heads", "ragged_ring", "short_ring", "too_many_queries"],
)
def test_the_gate_refuses(change, interpret):
    assert gqa_decode.use_ring_chunk(**ADMITTED)
    assert not gqa_decode.use_ring_chunk(**{**ADMITTED, **change})


def test_two_devices_and_the_cpu_take_attend_ring(monkeypatch):
    monkeypatch.delenv("GAIE_DECODE_KERNEL_INTERPRET", raising=False)
    assert not gqa_decode.use_ring_chunk(**ADMITTED)  # the CPU, no interpret hook
    monkeypatch.setattr(gqa_decode, "platform_of", lambda mesh: "tpu")
    assert gqa_decode.use_ring_chunk(**ADMITTED)
    assert gqa_decode.use_ring_chunk(**ADMITTED, mesh=Mesh(np.array(jax.devices()[:1]), ("x",)))
    assert not gqa_decode.use_ring_chunk(**ADMITTED, mesh=Mesh(np.array(jax.devices()[:2]), ("x",)))


# -- through the model: the rings written, the path taken, the counters ------------------------------

RING = 128  # the window of the variants below: one block, shorter than two of the prompts


def _cfg(preset: dict, draft: str = "") -> hybrid.HybridConfig:
    """A tiny preset with heads of one lane tile, a window of one lane tile
    of keys and bf16 all through, so that the gate admits its chunks; one
    period deep (three window layers and a full one)."""
    return hybrid.from_hf_config(
        {**preset, "num_hidden_layers": 4, "head_dim": D, "sliding_window": RING, "torch_dtype": "bfloat16"},
        max_len=MAX_LEN, kv_dtype="bfloat16", draft=draft,
    )


CASES = {"mellum": (hybrid.MELLUM_TINY, ""), "exaone_draft_on": (hybrid.EXAONE_TINY, "mtp")}


@pytest.mark.parametrize("case", sorted(CASES))
def test_a_groups_chunks_leave_the_rings_a_row_at_a_time_leaves(case, interpret, monkeypatch):
    monkeypatch.setenv("GAIE_MOE_KERNEL_INTERPRET", "1")
    preset, draft = CASES[case]
    cfg = _cfg(preset, draft)
    serving = HybridServing(cfg, None, MAX_LEN)
    params = serving.prepare_params(None, quantize=False, matmul_kernel="xla", seed=1)
    rng = np.random.RandomState(7)
    # Past the ring and across its wrap, short of a chunk, and one chunk alone.
    prompts = [rng.randint(1, cfg.vocab_size, n).tolist() for n in (150, 40, 16)]
    slots = [5, 2, 6]
    dispatch.TAKEN.clear()
    together, h_rows, counters = _state_after(serving, params, prompts, slots, True)
    assert dispatch.TAKEN[f"attn_window_chunk b=4 s={CHUNK} t={RING}"] == "pallas"
    dispatch.TAKEN.clear()
    apart, h_row, alone = _state_after(serving, params, prompts, slots, False)
    assert dispatch.TAKEN[f"attn_window_chunk b=1 s={CHUNK} t={RING}"] == "pallas"
    for i in range(len(prompts)):
        _assert_close(h_rows[i], h_row[i])
    used = set(slots)
    rings = 0
    for layer_a, layer_b in zip(together, apart):
        for name in layer_a:
            a, b = np.asarray(layer_a[name], np.float32), np.asarray(layer_b[name], np.float32)
            rings += name in hybrid.RING_LEAVES
            for slot in range(SLOTS):
                if slot not in used:
                    assert (a[slot] == 0.5).all(), (name, slot)  # no other slot's rows are touched
                    continue
                n = len(prompts[slots.index(slot)])
                if name in hybrid.ROW_LEAVES:
                    _assert_close(a[slot, :n], b[slot, :n])
                elif name in hybrid.RING_LEAVES:
                    _assert_close(a[slot], b[slot])
                    # A prompt shorter than the ring leaves the rows past it as they were.
                    assert (a[slot, n:] == 0.5).all() or n >= RING, (name, slot)
                else:
                    _assert_close(a[slot], b[slot])
    layers = len(cfg.layers_of("window"))
    assert rings == 2 * layers
    # A live chunk reads its ring, a pad row none: 10 + 3 + 1 chunks in 10
    # programs of 4 rows.
    chunks = sum(-(-len(p) // CHUNK) for p in prompts)
    assert counters["attn_rows_read_window_prefill"] == layers * chunks * RING
    assert alone["attn_rows_read_window_prefill"] == layers * chunks * RING
    assert counters["attn_rows_dense_window_prefill"] == layers * 10 * 4 * MAX_LEN


def test_the_twin_counts_every_row_of_the_call(monkeypatch):
    """Where the gate refuses (here: the CPU without the interpret switch)
    the chunk is ``attend_ring``'s and every row's ring is read, a pad
    row's too; a decode step is ``attend_ring``'s on either path."""
    monkeypatch.delenv("GAIE_DECODE_KERNEL_INTERPRET", raising=False)
    monkeypatch.setenv("GAIE_MOE_KERNEL_INTERPRET", "1")
    cfg = _cfg(hybrid.MELLUM_TINY)
    serving = HybridServing(cfg, None, MAX_LEN)
    params = serving.prepare_params(None, quantize=False, matmul_kernel="xla", seed=1)
    prompts = [np.random.RandomState(3).randint(1, cfg.vocab_size, 40).tolist()]
    dispatch.TAKEN.clear()
    _, _, counters = _state_after(serving, params, prompts, [4], True)
    assert dispatch.TAKEN[f"attn_window_chunk b=4 s={CHUNK} t={RING}"] == "xla"
    layers = len(cfg.layers_of("window"))
    assert counters["attn_rows_read_window_prefill"] == layers * 3 * 4 * RING


@pytest.mark.parametrize("s", [1, 2])
def test_a_decode_step_keeps_attend_rings_wide_form(s, interpret):
    """One or two queries a row: the site is ``attn_window``, XLA's, and
    the ring kernel's name is nowhere in the lowered step."""
    cfg = _cfg(hybrid.MELLUM_TINY)
    params = jax.eval_shape(lambda: hybrid.init_params(cfg, jax.random.PRNGKey(0)))
    state = jax.eval_shape(lambda: hybrid.init_state(cfg, 4, MAX_LEN))

    def step(params, state, tokens, start, n_valid):
        return hybrid.forward(params, cfg, tokens, start, n_valid, state, window=MAX_LEN)

    ints = jax.ShapeDtypeStruct((4,), jnp.int32)
    dispatch.TAKEN.clear()
    text = jax.jit(step).lower(params, state, jax.ShapeDtypeStruct((4, s), jnp.int32), ints, ints).as_text()
    assert dispatch.TAKEN[f"attn_window b=4 s={s} t={RING}"] == "xla"
    assert not any("attn_window_chunk" in site for site in dispatch.TAKEN)
    assert "gqa_ring_chunk_attention" not in text
