"""The decode step's row walk over a full GQA layer's state rows
(``ops/gqa_decode.py``), in Pallas interpret mode on the CPU, against
``ops.gqa.attend_rows``: the numbers, what is read, the gate and the
counters ``models/hybrid.py`` sums from the device's own lengths."""

import dataclasses

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
HEADS = [(4, 2), (32, 4), (64, 8)]
BF16 = jnp.bfloat16


@pytest.fixture
def interpret(monkeypatch):
    monkeypatch.setenv("GAIE_DECODE_KERNEL_INTERPRET", "1")


def _rows(b, s, h, kh, seed=0, t=T):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (b, s, h, D), jnp.float32).astype(BF16)
    k = jax.random.normal(ks[1], (b, t, kh * D), jnp.float32).astype(BF16)
    v = jax.random.normal(ks[2], (b, t, kh * D), jnp.float32).astype(BF16)
    return q, k, v


def _step(lengths, s):
    """Positions and counts of a step whose rows end at ``lengths``."""
    lengths = np.asarray(lengths, np.int32)
    n_valid = (lengths > 0).astype(np.int32) * s
    pos = np.stack([np.maximum(lengths - s + i, 0) for i in range(s)], axis=1)
    return jnp.asarray(pos, jnp.int32), jnp.asarray(n_valid)


def _walk(q, k, v, pos, n_valid, kh, window):
    lengths = gqa_decode.walk_lengths(pos, n_valid, window)
    out = gqa_decode.attend_rows_walk(q, k, v, pos, lengths, n_kv=kh, window=window, interpret=True)
    return np.asarray(out, np.float32), np.asarray(lengths)


# Ragged rows: nothing, one row, a row short of a block, a whole block, a
# row past it, every row of the state, and two in between.
LENGTHS = [0, 1, BLOCK - 1, BLOCK, BLOCK + 1, T, 300, 7]


@pytest.mark.parametrize("window", [T, 2 * BLOCK], ids=["whole", "window_256"])
@pytest.mark.parametrize("h,kh", HEADS)
@pytest.mark.parametrize("s", [1, 2])
def test_the_walk_gives_attend_rows_numbers_and_reads_whole_blocks_of_live_rows(s, h, kh, window):
    lengths = [n if n == 0 else max(n, s) for n in LENGTHS]
    q, k, v = _rows(len(lengths), s, h, kh, seed=s + h)
    pos, n_valid = _step(lengths, s)
    want = np.asarray(gqa.attend_rows(q, k[:, :window], v[:, :window], pos, n_kv=kh), np.float32)
    # What no walk may touch: every block past a row's last one.
    seen = np.minimum(lengths, window)
    past = np.arange(T)[None, :] >= (-(-seen // BLOCK) * BLOCK)[:, None]
    poison = jnp.asarray(past[:, :, None])
    got, walked = _walk(q, jnp.where(poison, jnp.nan, k), jnp.where(poison, jnp.nan, v), pos, n_valid, kh, window)
    assert walked.tolist() == seen.tolist()
    live = np.asarray(lengths) > 0
    np.testing.assert_allclose(got[live], want[live], atol=2e-2, rtol=2e-2)
    assert (got[~live] == 0).all()  # a row of length 0: exact zeros, nothing read
    assert int(gqa_decode.rows_walked(jnp.asarray(walked), T, window)) == int((-(-seen // BLOCK) * BLOCK).sum())


@pytest.mark.parametrize("h,kh", HEADS)
def test_the_second_query_sees_one_row_more_than_the_first(h, kh):
    lengths = [BLOCK + 1, 40, 2, 2 * BLOCK]  # the draft's row opens a block, lies inside one, ...
    q, k, v = _rows(len(lengths), 2, h, kh, seed=9)
    pos, n_valid = _step(lengths, 2)
    both, _ = _walk(q, k, v, pos, n_valid, kh, T)
    alone, _ = _walk(q[:, :1], k, v, pos[:, :1], n_valid // 2, kh, T)
    np.testing.assert_allclose(both[:, 0], alone[:, 0], atol=1e-6)
    # The row the draft wrote moves the second query's output and only that.
    at = (jnp.arange(len(lengths)), pos[:, 1])
    moved, _ = _walk(q, k.at[at].multiply(-3.0), v.at[at].add(5.0), pos, n_valid, kh, T)
    np.testing.assert_array_equal(moved[:, 0], both[:, 0])
    assert (np.abs(moved[:, 1] - both[:, 1]).max(axis=(1, 2)) > 1e-2).all()


@pytest.mark.parametrize("s", [1, 2])
def test_a_step_in_which_no_row_counts_reads_nothing(s):
    q, k, v = _rows(4, s, 4, 2, seed=3)
    pos, n_valid = _step([0, 0, 0, 0], s)
    got, walked = _walk(q, jnp.full_like(k, jnp.nan), jnp.full_like(v, jnp.nan), pos + 17, n_valid, 2, T)
    assert walked.tolist() == [0, 0, 0, 0] and (got == 0).all()


# -- the gate --------------------------------------------------------------------------

ADMITTED = dict(
    s=2, q_dtype=BF16, rows_dtype=BF16, width=8 * D, head_dim=D, rows=8192, window=8192,
    batch=32, n_q=64,
)


@pytest.mark.parametrize(
    "change",
    [
        dict(rows_dtype=jnp.float32),  # the rehearsals' and references' state
        dict(q_dtype=jnp.float32),
        dict(s=3),  # a prefill chunk
        dict(apart=True),
        dict(width=8 * 64, head_dim=64),  # a KV head that is no lane tile
        dict(rows=8200),  # blocks do not tile the rows
    ],
    ids=["f32_rows", "f32_queries", "three_queries", "rows_apart", "half_tile_heads", "ragged_rows"],
)
def test_the_gate_refuses(change, interpret):
    assert gqa_decode.use_row_walk(**ADMITTED)
    assert not gqa_decode.use_row_walk(**{**ADMITTED, **change})


def test_two_devices_take_attend_rows(monkeypatch):
    """The interpret hook stands in for the platform and the device count,
    so the mesh is asked of a gate that believes it is on the chip."""
    monkeypatch.delenv("GAIE_DECODE_KERNEL_INTERPRET", raising=False)
    monkeypatch.setattr(gqa_decode, "platform_of", lambda mesh: "tpu")
    two = Mesh(np.array(jax.devices()[:2]), ("x",))
    assert two.size == 2
    assert gqa_decode.use_row_walk(**ADMITTED, mesh=Mesh(np.array(jax.devices()[:1]), ("x",)))
    assert not gqa_decode.use_row_walk(**ADMITTED, mesh=two)


def test_the_cpu_without_the_interpret_hook_takes_attend_rows(monkeypatch):
    monkeypatch.delenv("GAIE_DECODE_KERNEL_INTERPRET", raising=False)
    assert not gqa_decode.use_row_walk(**ADMITTED)
    monkeypatch.setattr(gqa_decode, "platform_of", lambda mesh: "tpu")
    for s, window in [(1, 8192), (2, 512)]:  # both cells' steps, where the platform is the chip's
        assert gqa_decode.use_row_walk(**{**ADMITTED, "s": s, "window": window})


# -- through the model: which path a traced step took, and the counters -----------------------------

MAX_LEN = 384


def _cfg(preset: dict, draft: str = "") -> hybrid.HybridConfig:
    """A tiny preset with heads of one lane tile and bf16 all through, so
    that the gate admits its decode steps; one period deep (three window
    layers and a full one)."""
    return hybrid.from_hf_config(
        {**preset, "num_hidden_layers": 4, "head_dim": D, "torch_dtype": "bfloat16"},
        max_len=MAX_LEN, kv_dtype="bfloat16", draft=draft,
    )


CASES = {
    "mellum": (hybrid.MELLUM_TINY, ""),
    "exaone_draft_off": (hybrid.EXAONE_TINY, ""),
    "exaone_draft_on": (hybrid.EXAONE_TINY, "mtp"),
}


def _decode_chunk(cfg, lengths, live, steps, window):
    serving = HybridServing(cfg, None, MAX_LEN)
    b = len(lengths)
    key = jax.random.PRNGKey(0)
    params = serving.prepare_params(None, quantize=False, matmul_kernel="xla", seed=1)
    args = [
        params, serving.init_state(b, MAX_LEN), jnp.full((b,), 5, jnp.int32),
        jnp.asarray(lengths, jnp.int32), key, jnp.zeros((b,), jnp.float32),
        jnp.ones((b,), jnp.float32), jnp.zeros((b,), jnp.int32), steps, window,
        jnp.asarray(live),
    ]
    out = serving.make_decode_chunk()(*args)
    return dict(zip(serving.counter_names, np.asarray(out[-1]).tolist()))


@pytest.mark.parametrize("case", sorted(CASES))
def test_a_decode_chunk_counts_what_the_walk_copies(case, interpret, monkeypatch):
    monkeypatch.setenv("GAIE_MOE_KERNEL_INTERPRET", "1")
    preset, draft = CASES[case]
    cfg = _cfg(preset, draft)
    # Far enough from a block's edge that no row crosses one in the chunk,
    # whether or not a draft is kept; the last slot does not decode.
    lengths, live, steps = [5, 140, 300, 200], [True, True, True, False], 2
    dispatch.TAKEN.clear()
    counters = _decode_chunk(cfg, lengths, live, steps, MAX_LEN)
    full = len(cfg.layers_of("full"))
    # A drafting chunk: the module's catch-up, then stack and module a step.
    calls = 1 + steps * (full + 1) if draft else steps * full
    walked = BLOCK + 2 * BLOCK + 3 * BLOCK
    assert counters["attn_rows_read_full_decode"] == calls * walked
    assert counters["attn_rows_dense_full_decode"] == calls * len(lengths) * MAX_LEN
    assert counters["attn_rows_read_full_prefill"] == counters["attn_rows_dense_full_prefill"] == 0
    s = 2 if draft else 1
    taken = {site: path for site, path in dispatch.TAKEN.items() if "attn_" in site}
    assert taken[f"attn_full b=4 s={s} t={MAX_LEN}"] == "pallas"
    assert all(path == "xla" for site, path in taken.items() if "attn_window" in site)
    if draft:
        assert taken[f"mtp_attn_full b=4 s=1 t={MAX_LEN}"] == "pallas"
        assert taken[f"mtp_attn_full b=4 s=2 t={MAX_LEN}"] == "pallas"


@pytest.mark.parametrize("case", sorted(CASES))
def test_without_the_kernel_the_counters_read_the_whole_window_and_taken_says_xla(case, monkeypatch):
    monkeypatch.delenv("GAIE_DECODE_KERNEL_INTERPRET", raising=False)
    preset, draft = CASES[case]
    cfg = _cfg(preset, draft)
    dispatch.TAKEN.clear()
    counters = _decode_chunk(cfg, [5, 140, 300, 200], [True, True, True, False], 1, 256)
    assert counters["attn_rows_read_full_decode"] == counters["attn_rows_dense_full_decode"] > 0
    assert counters["attn_rows_dense_full_decode"] % (4 * 256) == 0
    assert {p for site, p in dispatch.TAKEN.items() if "attn_full" in site} == {"xla"}


@pytest.mark.parametrize("case", ["mellum", "exaone_draft_on"])
def test_a_prefill_chunk_and_float32_state_stay_on_attend_rows(case, interpret):
    """A chunk of three queries is no decode step (the walk's gate) and no
    whole sublane tiles of queries (the chunk kernel's,
    ``tests/test_gqa_chunk_kernel.py``); float32 state is neither's."""
    preset, draft = CASES[case]
    for cfg in (_cfg(preset, draft), dataclasses.replace(_cfg(preset, draft), kv_dtype="float32")):
        dispatch.TAKEN.clear()
        b, s = 2, 3 if cfg.kv_dtype == "bfloat16" else 1
        params = jax.eval_shape(lambda: hybrid.init_params(cfg, jax.random.PRNGKey(0)))
        state = jax.eval_shape(lambda: hybrid.init_state(cfg, b, MAX_LEN))
        jax.eval_shape(
            lambda p, st: hybrid.forward(
                p, cfg, jnp.zeros((b, s), jnp.int32), jnp.zeros((b,), jnp.int32),
                jnp.full((b,), s, jnp.int32), st, window=MAX_LEN,
            ),
            params, state,
        )
        site = "attn_full_chunk" if s == 3 else "attn_full"
        assert dispatch.TAKEN[f"{site} b={b} s={s} t={MAX_LEN}"] == "xla"
        assert {p for k, p in dispatch.TAKEN.items() if "attn_" in k} == {"xla"}
