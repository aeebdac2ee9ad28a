"""A prefill call's KDA scan as a kernel (``ops/kda.py::kda_chunk_rows``),
in Pallas interpret mode on the CPU, against its XLA twin ``kda_chunked``
(and, once, the token recurrence): the numbers, what is passed over, the
gate, and what ``models/hybrid.py`` records and counts."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from generativeaiexamples_tpu.engine.serving_models import HybridServing
from generativeaiexamples_tpu.models import hybrid
from generativeaiexamples_tpu.ops import dispatch, kda
from test_hybrid_ops import _kda_inputs, _token_by_token

F32 = jnp.float32
# (H, K = V): the cell's heads, and the narrowest the gate admits.
WIDTHS = {"cell": (32, 128), "narrow": (4, 128)}
# kda_chunked's own tolerance against the token recurrence
# (tests/test_hybrid_ops.py); kernel against twin lands far inside it.
TOL = dict(rtol=2e-4, atol=2e-5)


@pytest.fixture
def interpret(monkeypatch):
    monkeypatch.setenv("GAIE_DECODE_KERNEL_INTERPRET", "1")


@functools.lru_cache(maxsize=None)  # several tests read a case
def _both(widths, b, s, gate, counts, carried=True):
    """(inputs, the twin's (o, state), the kernel's): rows of which the
    first ``counts[i]`` tokens count, the others with ``g = 0`` and
    ``beta = 0`` as the mixer gives them."""
    H, K = WIDTHS[widths]
    q, k, v, g, beta, S0 = _kda_inputs(b * s + H, b, s, H, K, gate)
    if not carried:
        S0 = jnp.zeros_like(S0)
    n = jnp.asarray(counts, jnp.int32)
    on = (jnp.arange(s)[None, :] < n[:, None]).astype(F32)
    g, beta = g * on[:, :, None, None], beta * on[:, :, None]
    want = kda.kda_chunked(q, k, v, g, beta, S0)
    got = kda.kda_chunk_rows(q, k, v, g, beta, S0, n, interpret=True)
    return (q, k, v, g, beta, S0), [np.asarray(a) for a in want], [np.asarray(a) for a in got]


def _assert_same(want, got, counts):
    (want_o, want_s), (got_o, got_s) = want, got
    np.testing.assert_allclose(got_s, want_s, **TOL)
    for row, n in enumerate(counts):
        np.testing.assert_allclose(got_o[row, :n], want_o[row, :n], **TOL)


def test_the_cells_widths_give_the_twins_numbers():
    # Two rows of four head groups each; one short, so its last blocks are passed over.
    _, want, got = _both("cell", 2, 256, "mixed", (256, 100))
    _assert_same(want, got, (256, 100))


@pytest.mark.parametrize("rows, s", [(1, 256), (2, 256), (4, 256), (8, 256), (2, 64)])
def test_every_group_size_gives_the_twins_numbers(rows, s):
    _, want, got = _both("narrow", rows, s, "mixed", (s,) * rows)
    _assert_same(want, got, (s,) * rows)


@pytest.mark.parametrize("carried", [True, False], ids=["carried_state", "zero_state"])
@pytest.mark.parametrize("gate", ["near_floor", "near_zero", "mixed"])
def test_gates_at_both_ends_of_their_range(gate, carried):
    _, want, got = _both("narrow", 2, 64, gate, (64, 64), carried)
    _assert_same(want, got, (64, 64))


@pytest.mark.parametrize("gate", ["near_floor", "near_zero", "mixed"])
def test_the_kernel_matches_the_token_recurrence(gate):
    (q, k, v, g, beta, S0), _, (got_o, got_s) = _both("narrow", 2, 64, gate, (64, 64))
    want_o, want_s = _token_by_token(q, k, v, g, beta, S0)
    np.testing.assert_allclose(got_o, want_o, **TOL)
    np.testing.assert_allclose(got_s, want_s, **TOL)


# Two pad rows among four live ones, the first row a pad: a pad row names
# the blocks of the step before it, and there is none before row 0.
PADDED = (0, 256, 0, 256, 128, 256)


def test_a_pad_row_keeps_its_state_bit_for_bit_and_yields_zeros():
    (*_, S0), (_, want_s), (got_o, got_s) = _both("narrow", len(PADDED), 256, "mixed", PADDED)
    pad = np.asarray(PADDED) == 0
    assert (got_s[pad] == np.asarray(S0)[pad]).all()
    assert (want_s[pad] == np.asarray(S0)[pad]).all()  # the twin's identity update, bit for bit
    assert (got_o[pad] == 0).all()


def test_a_pad_rows_neighbours_get_what_they_get_alone():
    (q, k, v, g, beta, S0), want, got = _both("narrow", len(PADDED), 256, "mixed", PADDED)
    _assert_same(want, got, PADDED)
    live = np.flatnonzero(PADDED)
    alone = kda.kda_chunk_rows(
        *(a[live] for a in (q, k, v, g, beta, S0)), jnp.asarray(PADDED, jnp.int32)[live], interpret=True
    )
    assert (np.asarray(alone[0]) == got[0][live]).all() and (np.asarray(alone[1]) == got[1][live]).all()


@pytest.mark.parametrize("n", [20, 128, 256])
def test_blocks_past_the_count_are_passed_over(n):
    """The state is the twin's (the skipped tokens leave it bit-equal
    there), the outputs the twin's over the positions that count and up
    to the end of the block that holds the count, and zeros from the
    first block of four sub-chunks that starts at or past the count."""
    _, want, got = _both("narrow", 2, 256, "mixed", (n, 256))
    _assert_same(want, got, (n, 256))
    block = kda._subs_a_block(256) * kda.SUB
    first_skipped = -(-n // block) * block
    assert block == 64 and (got[0][0, first_skipped:] == 0).all()
    np.testing.assert_allclose(got[0][0, n:first_skipped], want[0][0, n:first_skipped], **TOL)


@pytest.mark.parametrize("s, subs", [(16, 1), (32, 2), (48, 1), (64, 4), (96, 2)])
def test_a_block_is_as_many_sub_chunks_as_divide_the_call(s, subs):
    assert kda._subs_a_block(s) == subs
    if s in (32, 48):  # the two forms that 64 and 256 do not walk
        _, want, got = _both("narrow", 1, s, "mixed", (s,))
        _assert_same(want, got, (s,))


ADMITTED = dict(state_dtype=F32, k_dim=128, v_dim=128, heads=32, s=256)


@pytest.mark.parametrize("change", [
    {"state_dtype": jnp.bfloat16},  # the state's path is float32
    {"k_dim": 16, "v_dim": 16},     # ling-tiny's heads: no lane tile
    {"k_dim": 192},
    {"s": 24},                      # not whole sub-chunks
    {"heads": 12},                  # no whole sublane tiles of heads
    {"s": 1 << 14},                 # a group's blocks past half the VMEM budget
], ids=["bf16_state", "tiny_heads", "k_192", "s_24", "heads_12", "too_long"])
def test_the_gate_sends_everything_else_to_the_twin(change, interpret):
    assert kda.use_chunk_kernel(**ADMITTED)
    assert not kda.use_chunk_kernel(**{**ADMITTED, **change})


def test_the_gate_asks_for_one_tpu_device(monkeypatch):
    monkeypatch.delenv("GAIE_DECODE_KERNEL_INTERPRET", raising=False)
    assert not kda.use_chunk_kernel(**ADMITTED)  # the CPU
    monkeypatch.setattr(kda, "platform_of", lambda mesh: "tpu")
    assert kda.use_chunk_kernel(**ADMITTED)
    assert not kda.use_chunk_kernel(**ADMITTED, mesh=Mesh(np.array(jax.devices()[:2]), ("x",)))
    # A sublane tile of heads a grid step at the cell's widths (fewer where
    # the model has fewer), inside half the budget.
    assert kda._heads_a_chunk(32) == 8 and kda._heads_a_chunk(4) == 4 and kda._heads_a_chunk(12) == 0
    assert kda._chunk_vmem_bytes(8, 256, 128, 128, 32) <= kda._VMEM_BUDGET_BYTES // 2


# -- through the model: which path a traced call took, the numbers, the counters --------------

MAX_LEN = 64
# The first three layers of ``ling-tiny`` (KDA mixers; a dense MLP, then
# experts) with heads of one lane tile, so that the gate admits its
# prefill calls; float32 as the preset is.
WIDE = hybrid.from_hf_config(
    {**hybrid.LING_TINY, "head_dim": 128, "num_hidden_layers": 3}, max_len=MAX_LEN, kv_dtype="float32"
)
SLOTS, COUNTS, CHUNK = [4, 1, 3, 0], [32, 0, 20, 7], 32


def _prefill_rows(cfg):
    serving = HybridServing(cfg, None, MAX_LEN)
    params = serving.prepare_params(None, quantize=False, matmul_kernel="xla", seed=1)
    # Every slot's state is something, and no row starts at 0: the rows carry it.
    state = jax.tree.map(
        lambda x: jax.random.normal(jax.random.PRNGKey(x.size % 97), x.shape, x.dtype) * 0.1,
        serving.init_state(6, MAX_LEN),
    )
    tokens = jax.random.randint(jax.random.PRNGKey(2), (len(SLOTS), CHUNK), 0, cfg.vocab_size)
    dispatch.TAKEN.clear()
    cache, hidden, aux = jax.jit(serving.prefill_rows, static_argnums=(6,))(
        params, state, tokens, jnp.full((len(SLOTS),), 8, jnp.int32), jnp.asarray(COUNTS, jnp.int32),
        jnp.asarray(SLOTS, jnp.int32), MAX_LEN,
    )
    kept = [np.asarray(st["S"]) for st in cache if "S" in st]
    return np.asarray(hidden), kept, dict(zip(serving.counter_names, np.asarray(aux).tolist())), dict(dispatch.TAKEN)


@pytest.fixture(scope="module")
def both_paths():
    """A chunk program's call for four rows (one a pad, one short) of the
    widened ``ling-tiny`` on XLA's path and on the kernel's."""
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("GAIE_MOE_KERNEL_INTERPRET", "1")
        mp.delenv("GAIE_DECODE_KERNEL_INTERPRET", raising=False)
        xla = _prefill_rows(WIDE)
        mp.setenv("GAIE_DECODE_KERNEL_INTERPRET", "1")
        pallas = _prefill_rows(WIDE)
    return {"xla": xla, "pallas": pallas}


@pytest.mark.parametrize("path", ["xla", "pallas"])
def test_kernel_paths_names_the_path_a_chunk_program_took(both_paths, path):
    taken = both_paths[path][-1]
    assert taken[f"kda_chunk b={len(SLOTS)} s={CHUNK} h={WIDE.n_heads}"] == path
    assert not any(site.startswith("kda_step") for site in taken)


def test_prefill_rows_gives_the_hidden_states_and_the_state_of_xlas_path(both_paths):
    (xla_h, xla_s, *_), (pal_h, pal_s, *_) = both_paths["xla"], both_paths["pallas"]
    for row, n in enumerate(COUNTS):
        np.testing.assert_allclose(pal_h[row, :n], xla_h[row, :n], rtol=2e-4, atol=2e-4)
    assert len(pal_s) == len(WIDE.layers_of("kda"))
    for was, now in zip(xla_s, pal_s):
        np.testing.assert_allclose(now, was, rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("path, scanned", [("xla", 0), ("pallas", sum(COUNTS))])
def test_the_prefill_counters_read_the_tokens_that_count(both_paths, path, scanned):
    counters = both_paths[path][2]
    layers = len(WIDE.layers_of("kda"))
    assert counters["attn_rows_read_state_prefill"] == layers * scanned
    assert counters["attn_rows_dense_state_prefill"] == layers * sum(COUNTS)
    assert counters["attn_rows_read_state_decode"] == counters["attn_rows_dense_state_decode"] == 0


@pytest.mark.parametrize("why, cfg, s", [
    ("tiny_heads", hybrid.PRESETS["ling-tiny"](), 32),
    ("s_24", WIDE, 24),
])
def test_a_call_the_gate_refuses_lands_on_the_twin(why, cfg, s, interpret):
    dispatch.TAKEN.clear()
    b = 2
    params = jax.eval_shape(lambda: hybrid.init_params(cfg, jax.random.PRNGKey(0)))
    state = jax.eval_shape(lambda: hybrid.init_state(cfg, b, MAX_LEN))
    jax.eval_shape(
        lambda p, st: hybrid.forward(
            p, cfg, jnp.zeros((b, s), jnp.int32), jnp.zeros((b,), jnp.int32),
            jnp.full((b,), s, jnp.int32), st, window=MAX_LEN,
        ),
        params, state,
    )
    assert dispatch.TAKEN[f"kda_chunk b={b} s={s} h={cfg.n_heads}"] == "xla"
