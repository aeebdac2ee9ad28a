"""The decode step's KDA state update as a kernel over the rows that
decode (``ops/kda.py::kda_step_rows``), in Pallas interpret mode on the
CPU, against its XLA twin ``kda_step``: the numbers, what is left alone,
the gate, and the counters ``models/hybrid.py`` sums."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from generativeaiexamples_tpu.engine.serving_models import HybridServing
from generativeaiexamples_tpu.models import hybrid
from generativeaiexamples_tpu.ops import dispatch, kda, moe

F32 = jnp.float32
# (slots, H, K = V): ``ling-tiny``'s heads, and the cell's.
SHAPES = {"ling_tiny": (8, 4, 16), "cell": (32, 32, 128)}
# Live rows of the slots: none, one, three eighths in scattered slots
# (the cell's 12 of 32), all.
LIVE = {"none": 0.0, "one": None, "scattered": 0.375, "all": 1.0}


@pytest.fixture
def interpret(monkeypatch):
    monkeypatch.setenv("GAIE_DECODE_KERNEL_INTERPRET", "1")


def _live(b: int, how: str) -> np.ndarray:
    n = 1 if LIVE[how] is None else int(b * LIVE[how])
    live = np.zeros(b, bool)
    live[np.random.default_rng(b).permutation(b)[:n]] = True
    return live


def _step(b, h, d, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    q = kda.l2_normalize(jax.random.normal(ks[0], (b, h, d))) * d**-0.5
    k = kda.l2_normalize(jax.random.normal(ks[1], (b, h, d)))
    v = jax.random.normal(ks[2], (b, h, d))
    g = -5.0 * jax.nn.sigmoid(jax.random.normal(ks[3], (b, h, d)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (b, h)))
    state = jax.random.normal(ks[5], (b, h, d, d))
    return q, k, v, g, beta, state


@functools.lru_cache(maxsize=None)  # two tests read each case
def _both(shape, how):
    """(live, the state before, the twin's (o, state) with dead rows given
    ``g = 0``, ``beta = 0`` as the mixer gives them, the kernel's)."""
    b, h, d = SHAPES[shape]
    live = _live(b, how)
    q, k, v, g, beta, state = _step(b, h, d, seed=h)
    on = jnp.asarray(live, F32)
    want = kda.kda_step(q, k, v, g * on[:, None, None], beta * on[:, None], state)
    got = kda.kda_step_rows(q, k, v, g, beta, state, jnp.asarray(live), interpret=True)
    return live, np.asarray(state), [np.asarray(a) for a in want], [np.asarray(a) for a in got]


@pytest.mark.parametrize("how", sorted(LIVE))
@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_live_rows_get_kda_steps_numbers(shape, how):
    live, _, (want_o, want_s), (got_o, got_s) = _both(shape, how)
    np.testing.assert_allclose(got_o[live], want_o[live], rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(got_s[live], want_s[live], rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("how", sorted(LIVE))
@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_a_row_that_does_not_decode_keeps_its_bits_and_yields_zeros(shape, how):
    live, before, (_, want_s), (got_o, got_s) = _both(shape, how)
    assert (got_s[~live] == before[~live]).all()
    assert (want_s[~live] == before[~live]).all()  # the twin's identity update, bit for bit
    assert (got_o[~live] == 0).all()


@pytest.mark.parametrize("how", sorted(LIVE))
def test_the_list_the_kernel_walks_is_the_live_slots_in_order(how):
    live = _live(32, how)
    idx, n = kda.live_slots(jnp.asarray(live))
    assert int(n) == live.sum()
    assert np.asarray(idx)[: live.sum()].tolist() == np.flatnonzero(live).tolist()
    assert (np.asarray(idx)[live.sum():] == 0).all()


ADMITTED = dict(state_dtype=F32, k_dim=128, v_dim=128, heads=32)


@pytest.mark.parametrize("change", [
    {"state_dtype": jnp.bfloat16},  # the state's path is float32
    {"k_dim": 16, "v_dim": 16},     # ling-tiny's heads: no lane tile
    {"k_dim": 192},
    {"k_dim": 2048, "v_dim": 2048},  # one head's block past the VMEM budget
], ids=["bf16_state", "tiny_heads", "k_192", "too_wide"])
def test_the_gate_sends_everything_else_to_the_twin(change, interpret):
    assert kda.use_step_kernel(**ADMITTED)
    assert not kda.use_step_kernel(**{**ADMITTED, **change})


def test_the_gate_asks_for_one_tpu_device(monkeypatch):
    monkeypatch.delenv("GAIE_DECODE_KERNEL_INTERPRET", raising=False)
    assert not kda.use_step_kernel(**ADMITTED)  # the CPU
    monkeypatch.setattr(kda, "platform_of", lambda mesh: "tpu")
    assert kda.use_step_kernel(**ADMITTED)
    assert not kda.use_step_kernel(**ADMITTED, mesh=Mesh(np.array(jax.devices()[:2]), ("x",)))
    assert kda._heads_a_step(32, 128, 128) == 32  # a whole row a grid step at the cell's widths


# -- through the model: which path a traced step took, the tokens, the counters ---------------

MAX_LEN = 64
# The first three layers of ``ling-tiny`` (KDA mixers; a dense MLP, then
# experts) with heads of one lane tile, so that the gate admits its decode
# steps; float32 as the preset is.
WIDE = hybrid.from_hf_config(
    {**hybrid.LING_TINY, "head_dim": 128, "num_hidden_layers": 3}, max_len=MAX_LEN, kv_dtype="float32"
)
LENGTHS, ALIVE, STEPS = [5, 0, 17, 9, 0, 30], [True, False, True, True, False, True], 8


def _decode_chunk(cfg, steps=STEPS):
    serving = HybridServing(cfg, None, MAX_LEN)
    b = len(LENGTHS)
    params = serving.prepare_params(None, quantize=False, matmul_kernel="xla", seed=1)
    # Every slot's state is something, so that a dead row's would show.
    state = jax.tree.map(
        lambda x: jax.random.normal(jax.random.PRNGKey(x.size % 97), x.shape, x.dtype) * 0.1,
        serving.init_state(b, MAX_LEN),
    )
    before = [np.asarray(st["S"]) for st in state if "S" in st]
    dispatch.TAKEN.clear()
    cache, toks, aux = serving.make_decode_chunk()(
        params, state, jnp.arange(b, dtype=jnp.int32) + 3, jnp.asarray(LENGTHS, jnp.int32),
        jax.random.PRNGKey(0), jnp.zeros((b,), F32), jnp.ones((b,), F32),
        jnp.zeros((b,), jnp.int32), steps, MAX_LEN, jnp.asarray(ALIVE),
    )
    after = [np.asarray(st["S"]) for st in cache if "S" in st]
    return np.asarray(toks), dict(zip(serving.counter_names, np.asarray(aux).tolist())), before, after


@pytest.fixture(scope="module")
def both_paths():
    """Eight greedy decode steps of the widened ``ling-tiny`` on XLA's
    path and on the kernel's."""
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("GAIE_MOE_KERNEL_INTERPRET", "1")
        mp.delenv("GAIE_DECODE_KERNEL_INTERPRET", raising=False)
        xla = (*_decode_chunk(WIDE), dict(dispatch.TAKEN))
        mp.setenv("GAIE_DECODE_KERNEL_INTERPRET", "1")
        pallas = (*_decode_chunk(WIDE), dict(dispatch.TAKEN))
    return {"xla": xla, "pallas": pallas}


@pytest.mark.parametrize("path", ["xla", "pallas"])
def test_kernel_paths_names_the_path_a_decode_chunk_took(both_paths, path):
    taken = both_paths[path][-1]
    assert taken[f"kda_step b={len(LENGTHS)} h={WIDE.n_heads}"] == path


def test_eight_decode_steps_give_the_same_greedy_tokens_on_both_paths(both_paths):
    live = np.asarray(ALIVE)
    xla, pallas = both_paths["xla"][0], both_paths["pallas"][0]
    assert xla.shape == (STEPS, len(LENGTHS))
    assert (xla[:, live] == pallas[:, live]).all()


@pytest.mark.parametrize("path", ["xla", "pallas"])
def test_a_slot_that_does_not_decode_keeps_its_state_through_the_chunk(both_paths, path):
    _, _, before, after = both_paths[path][:4]
    dead = ~np.asarray(ALIVE)
    assert len(before) == len(WIDE.layers_of("kda"))
    for was, now in zip(before, after):
        assert (was[dead] == now[dead]).all() and not (was[~dead] == now[~dead]).all()


@pytest.mark.parametrize("path, read", [("xla", len(LENGTHS)), ("pallas", sum(ALIVE))])
def test_the_counters_read_live_slots_on_the_kernel_and_every_slot_on_xla(both_paths, path, read):
    counters = both_paths[path][1]
    layers = len(WIDE.layers_of("kda"))
    assert counters["attn_rows_read_state_decode"] == STEPS * layers * read
    assert counters["attn_rows_dense_state_decode"] == STEPS * layers * len(LENGTHS)
    assert counters["attn_rows_read_state_prefill"] == counters["attn_rows_dense_state_prefill"] == 0


def test_a_prefill_call_counts_no_state_and_takes_no_step(interpret):
    cfg, b, s = WIDE, 2, 24
    dispatch.TAKEN.clear()
    params = jax.eval_shape(lambda: hybrid.init_params(cfg, jax.random.PRNGKey(0)))
    state = jax.eval_shape(lambda: hybrid.init_state(cfg, b, MAX_LEN))
    serving = HybridServing(cfg, None, MAX_LEN)
    assert serving.counter_names[len(moe.COUNTERS):] == (
        "moe_experts_touched_decode", "moe_expert_layer_steps_decode", "moe_choices_local_decode",
        "attn_rows_read_state_decode", "attn_rows_dense_state_decode",
        "attn_rows_read_state_prefill", "attn_rows_dense_state_prefill",
    )
    _, _, counters = jax.eval_shape(
        lambda p, st: hybrid.forward(
            p, cfg, jnp.zeros((b, s), jnp.int32), jnp.zeros((b,), jnp.int32),
            jnp.full((b,), s, jnp.int32), st, window=MAX_LEN,
        ),
        params, state,
    )
    assert counters.shape == (cfg.n_counters,) == (len(moe.COUNTERS) + 2,)
    assert not any(site.startswith("kda_step") for site in dispatch.TAKEN)


def test_the_tiny_preset_stays_on_the_twin(interpret):
    cfg = hybrid.PRESETS["ling-tiny"]()
    assert cfg.row_counters == hybrid.STATE_COUNTERS
    dispatch.TAKEN.clear()
    b = 2
    params = jax.eval_shape(lambda: hybrid.init_params(cfg, jax.random.PRNGKey(0)))
    state = jax.eval_shape(lambda: hybrid.init_state(cfg, b, MAX_LEN))
    jax.eval_shape(
        lambda p, st: hybrid.forward(
            p, cfg, jnp.zeros((b, 1), jnp.int32), jnp.zeros((b,), jnp.int32),
            jnp.ones((b,), jnp.int32), st, window=MAX_LEN,
        ),
        params, state,
    )
    assert dispatch.TAKEN[f"kda_step b={b} h={cfg.n_heads}"] == "xla"
