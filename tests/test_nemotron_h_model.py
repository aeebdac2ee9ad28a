"""``models/hybrid.py``'s ``mamba`` kind, the layer that is its mixer alone
and the experts in a latent (the ``nemotron_h`` family:
NVIDIA-Nemotron-3-Super-120B-A12B) against the plain reference,
``models/nemotron_h_reference.py``, at a tiny size that keeps the ratios
of the benchmark's cut: the letters ``MEM*EM`` (all three kinds, a
mixer-only pair, a state carried past attention), 8 Mamba heads of 16 in 2
groups with a state of 16, blocks of 4 tokens here (so chunks of 1, 2, 3,
5, 9 cross block and call boundaries alike), 4 query heads on 2 key-value
heads, 4 of 8 experts held and 3 a token in a latent of half the hidden
size.  Seeded random float32 weights; logits are compared, never sampled
tokens.

Tolerance: both sides are float32 at the highest matmul precision
(conftest.py) and differ by the order of their sums (the block scan
against the recurrence); logits are O(3), 2e-4 absolute is some tens of
float32 ulps, and each mechanism left out (the controls below) moves a
logit by 1e-2 or more.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from generativeaiexamples_tpu.engine.serving_models import HybridServing, serving_model
from generativeaiexamples_tpu.models import hybrid
from generativeaiexamples_tpu.models import nemotron_h_reference as ref
from generativeaiexamples_tpu.ops import moe

ATOL = 2e-4
CFG = dataclasses.replace(hybrid.PRESETS["nemotron_h-tiny"](), ssm_block=4)
T = 64
N = 40  # tokens a row
MAMBA = CFG.layers_of("mamba")
LEAVES = ("ssm", "conv")


MODEL = serving_model(CFG, None, T)
# The calls the scheduler's programs make, compiled once a shape for the file.
CHUNK = jax.jit(MODEL.prefill_row, static_argnums=(6,))
ROWS = jax.jit(MODEL.prefill_rows, static_argnums=(6,))
STEP = jax.jit(MODEL.decode_step, static_argnums=(5,))


@pytest.fixture(scope="module")
def params():
    # The seeded selection bias as it is (normal of 0.05, so that selection
    # and weighting differ); balancing has its own test.
    return hybrid.init_params(CFG, jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def tokens():
    return np.random.RandomState(0).randint(0, CFG.vocab_size, size=(3, N)).astype(np.int32)


@pytest.fixture(scope="module")
def want(params, tokens):
    """The reference's full forward over each whole row."""
    return [np.asarray(ref.all_logits(params, CFG, row)) for row in tokens]


@functools.lru_cache(maxsize=None)
def _program(cfg, window):
    return jax.jit(lambda p, t, s, n, st: hybrid.forward(p, cfg, t, s, n, st, window=window))


def _forward(params, toks, start, n_valid, state, window=T, cfg=CFG):
    hidden, state, counters = _program(cfg, window)(
        params, jnp.asarray(toks), jnp.asarray(start, jnp.int32), jnp.asarray(n_valid, jnp.int32), state)
    return np.asarray(hybrid.logits(params, cfg, hidden)), state, counters


def _recurrent(state, slot):
    return [np.asarray(state[i][n])[slot] for i in MAMBA for n in LEAVES]


def _pieces(params, state, row, slot, pieces, width=9, start=0):
    """``row[start:]`` through ``prefill_row`` in pieces of the given
    lengths (each padded to ``width``); returns (state, logits of every
    piece's positions, side by side)."""
    model, chunk = MODEL, CHUNK
    got, at = [], start
    for count in pieces:
        piece = np.zeros((1, width), np.int32)
        piece[0, :count] = row[at : at + count]
        state, hidden, _ = chunk(
            params, state, jnp.asarray(piece), jnp.int32(at), jnp.int32(count), jnp.int32(slot), T)
        got.append(np.asarray(model.logits(params, hidden))[0, :count])
        at += count
    return state, np.concatenate(got)


# -- the configuration -----------------------------------------------------------


def test_the_published_keys_pair_88_letters_into_48_layers():
    whole = hybrid.from_hf_config(hybrid.NEMOTRON3_SUPER, max_len=64)
    assert isinstance(whole, hybrid.MambaConfig) and whole.n_layers == 48
    kinds = whole.layer_kinds
    assert sum(k == ("mamba", "experts") for k in kinds) == 32
    assert sum(k == ("full", "experts") for k in kinds) == 8
    assert sum(k == ("mamba", "none") for k in kinds) == 8
    assert ref.letters(whole) == hybrid.NEMOTRON3_SUPER["hybrid_override_pattern"]
    cut = hybrid.PRESETS["nemotron-3-super-120b-a12b-l11e128"]()
    assert ref.letters(cut) == "MEMEMEM*EME" and cut.layer_kinds == kinds[:6] and cut.max_seq_len == 8192
    assert (cut.d_model, cut.n_heads, cut.n_kv_heads, cut.attn_head_dim) == (4096, 32, 2, 128)
    assert (cut.mamba_heads, cut.mamba_head_dim, cut.mamba_groups, cut.ssm_state) == (128, 64, 8, 128)
    assert (cut.mamba_inner, cut.mamba_conv_channels, cut.conv_kernel, cut.ssm_block) == (8192, 10240, 4, 128)
    assert (cut.n_experts, cut.experts_held, cut.n_experts_per_tok) == (512, 128, 22)
    assert (cut.moe_latent, cut.moe_d_ff, cut.shared_d_ff, cut.expert_act) == (1024, 2688, 5376, "relu2")
    assert (cut.n_group, cut.topk_group, cut.routed_scaling, cut.norm_topk) == (1, 1, 5.0, True)
    assert cut.score_function == "sigmoid" and cut.router_bias and not cut.tie_embeddings
    assert cut.rope_full == hybrid.NO_ROPE and not cut.qk_norm  # the attention layers are not rotated
    assert (cut.vocab_size, cut.norm_eps, cut.dt_init) == (32768, 1e-5, (0.001, 0.1, 0.0001))
    assert not cut.rows_only and cut.draft == ""
    assert cut.row_counters == hybrid.ATTN_COUNTERS + hybrid.STATE_COUNTERS + hybrid.SSM_COUNTERS
    assert ref.letters(CFG) == "MEM*EM"  # the tiny size: every kind and a mixer-only pair


@pytest.mark.parametrize("pattern, pairs", [
    ("M", (("mamba", "none"),)),
    ("*E", (("full", "experts"),)),
    ("MEM*EM", (("mamba", "experts"), ("mamba", "none"), ("full", "experts"), ("mamba", "none"))),
    ("MM**", (("mamba", "none"),) * 2 + (("full", "none"),) * 2),
])
def test_a_pattern_pairs(pattern, pairs):
    assert hybrid.pair_pattern(pattern) == pairs


@pytest.mark.parametrize("bad, match", [
    ({"hybrid_override_pattern": "MEEM*E"}, "does not pair"),
    ({"hybrid_override_pattern": "EMEM*E"}, "does not pair"),
    ({"hybrid_override_pattern": "ME-M*E"}, "not served"),
    ({"hybrid_override_pattern": "MEM"}, "fewer layers"),
    ({"mlp_hidden_act": "silu"}, "relu2"),
    ({"mamba_proj_bias": True}, "biases"),
    ({"use_conv_bias": False}, "bias"),
    ({"sliding_window": 4096}, "sliding window"),
    ({"mamba_num_heads": 6}, "expand x hidden_size"),
])
def test_what_the_family_does_not_serve_is_refused_with_the_reason(bad, match):
    with pytest.raises(ValueError, match=match):
        hybrid.from_hf_config({**hybrid.NEMOTRON_H_TINY, **bad}, max_len=64)


def test_the_other_families_count_the_rows_they_counted():
    names = {name: hybrid.PRESETS[name]().row_counters for name in hybrid.PRESETS}
    assert names["ling-tiny"] == hybrid.STATE_COUNTERS  # KDA beside MLA: its state alone
    assert names["mistral4-tiny"] == hybrid.LATENT_COUNTERS
    for preset in ("mellum-tiny", "exaone_moe-tiny", "zaya-tiny"):
        assert names[preset] == hybrid.ATTN_COUNTERS


def test_check_supported_refuses_drafts_over_mamba_state():
    model = serving_model(CFG, None, T)
    model.check_supported()
    model.draft = "mtp"  # a prediction module over mamba state, were one held
    with pytest.raises(ValueError, match="mamba state"):
        model.check_supported()
    with pytest.raises(ValueError, match="no prediction module"):
        hybrid.from_hf_config(hybrid.NEMOTRON_H_TINY, max_len=64, draft="mtp")


@pytest.mark.parametrize("asked, match", [
    (dict(quantize=True, matmul_kernel="xla"), "int8 weights"),
    (dict(quantize=False, matmul_kernel="pallas_w8a8"), "int8 weights"),
])
def test_int8_weights_and_state_are_refused(asked, match):
    with pytest.raises(ValueError, match=match):
        serving_model(CFG, None, T).prepare_params(None, seed=0, **asked)
    with pytest.raises(ValueError, match="int8 state"):
        serving_model(dataclasses.replace(CFG, kv_dtype="int8"), None, T).check_supported()


# -- parameters and state against the table of the cut ---------------------------


def test_a_mixer_only_pair_has_no_mlp_parameters(params):
    kinds = CFG.layer_kinds
    for kind, lp in zip(kinds, params["layers"]):
        has_mlp = any(n.startswith(("mlp_norm", "router", "w_lat", "w_up", "w_down", "w_gu")) for n in lp)
        assert has_mlp == (kind[1] == "experts"), (kind, sorted(lp))
    alone = params["layers"][1]
    assert kinds[1] == ("mamba", "none")
    assert sorted(alone) == sorted(
        ["attn_norm", "w_in", "conv_w", "conv_b", "ssm_a_log", "ssm_dt_bias", "ssm_d", "ssm_norm", "w_out"])
    # Experts without a gate: one up matrix, in the latent; so the shared one, on the stream.
    experts = params["layers"][0]
    assert "w_gu_e" not in experts and "w_gu_s" not in experts
    assert experts["w_up_e"].shape == (4, 32, 32) and experts["w_down_e"].shape == (4, 32, 32)
    assert experts["w_lat_down"].shape == (64, 32) and experts["w_lat_up"].shape == (32, 64)
    assert experts["w_up_s"].shape == (64, 64) and experts["router"].shape == (64, 8)


def test_seeded_weights_give_decays_that_need_the_float32_state(params):
    lp = params["layers"][0]
    assert all(lp[n].dtype == jnp.float32 for n in ("ssm_a_log", "ssm_dt_bias", "ssm_d"))
    dt = np.asarray(jax.nn.softplus(lp["ssm_dt_bias"]))
    a = np.exp(np.asarray(lp["ssm_a_log"]))
    assert (dt >= 0.001 - 1e-6).all() and (dt <= 0.1 + 1e-6).all() and (a >= 1).all() and (a <= 16).all()
    decay = np.exp(-dt * a)
    assert 0.2 < decay.min() and decay.max() < 0.9995
    assert (np.asarray(lp["ssm_d"]) == 1).all() and np.abs(np.asarray(lp["conv_b"])).max() > 0.01


def test_the_tiny_size_holds_the_bytes_worked_by_hand():
    """The table of the cut at the tiny size: parameters a part, state a
    slot, a snapshot."""
    shapes = jax.eval_shape(lambda: hybrid.init_params(CFG, jax.random.PRNGKey(0)))
    size = lambda lp, *names: sum(lp[n].size for n in names)
    m = shapes["layers"][1]
    inner, C = 8 * 16, 8 * 16 + 2 * 2 * 16  # 128, 192
    assert size(m, "w_in") == 64 * (inner + C + 8) and size(m, "w_out") == inner * 64
    assert size(m, "conv_w", "conv_b") == 5 * C and size(m, "ssm_a_log", "ssm_dt_bias", "ssm_d") == 24
    e = shapes["layers"][0]
    assert size(e, "router", "router_bias") == 64 * 8 + 8
    assert size(e, "w_lat_down", "w_lat_up") == 2 * 64 * 32
    assert size(e, "w_up_e", "w_down_e") == 4 * 2 * 32 * 32 and size(e, "w_up_s", "w_down_s") == 2 * 64 * 64
    a = shapes["layers"][2]
    assert size(a, "w_qkv", "w_o") == 64 * (4 + 2 * 2) * 16 + 64 * 64
    # A slot: S (8, 16, 16) float32 and a tail of 3 x 192 a mamba layer; K and V rows of 2 x 16.
    state = hybrid.init_state(CFG, 2, T)
    assert [sorted(layer) for layer in state] == [["conv", "ssm"], ["conv", "ssm"], ["k", "v"], ["conv", "ssm"]]
    assert state[0]["ssm"].shape == (2, 8, 16, 16) and state[0]["ssm"].dtype == jnp.float32
    assert state[0]["conv"].shape == (2, 3, C) and state[2]["k"].shape == (2, T, 32)
    one = 8 * 16 * 16 * 4 + 3 * C * 4
    assert hybrid.state_bytes(CFG, 2, T) == {"full": 2 * 2 * T * 32 * 4, "window": 0, "recurrent": 2 * 3 * one}
    assert CFG.snapshot_bytes(T) == serving_model(CFG, None, T).snapshot_bytes == 3 * one


def test_the_cut_holds_the_bytes_the_issue_counts():
    cut = hybrid.PRESETS["nemotron-3-super-120b-a12b-l11e128"]()
    shapes = jax.eval_shape(lambda: hybrid.init_params(cut, jax.random.PRNGKey(0)))
    weights = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(shapes))
    assert weights == pytest.approx(9.30e9, rel=0.002)
    size = lambda lp, names: sum(lp[n].size for n in lp if n.startswith(names))
    m = shapes["layers"][3]  # the mixer-only pair
    assert m["w_in"].shape == (4096, 18560) and size(m, ("w_in", "w_out", "conv", "ssm")) == 109_635_968
    e = shapes["layers"][0]
    assert size(e, ("w_up_e", "w_down_e")) == 128 * 5_505_024 == 704_643_072
    assert size(e, ("router", "w_lat", "w_up_s", "w_down_s", "mlp_norm")) == 54_530_560
    a = shapes["layers"][4]
    assert size(a, ("w_qkv", "w_o")) == 35_651_584
    state = hybrid.state_bytes(cut, 32, 8192)
    assert state == {"full": 32 * 8192 * 1024, "window": 0, "recurrent": 32 * 5 * (4_194_304 + 61_440)}
    assert cut.snapshot_bytes(8192) == 5 * (4_194_304 + 61_440) == 21_278_720


# -- the program against the reference -------------------------------------------


def test_a_cold_batch_matches_the_reference_and_pads_move_nothing(params, tokens, want):
    lengths = np.array([N, 31, 17], np.int32)
    got, state, counters = _forward(params, tokens, np.zeros(3), lengths, hybrid.init_state(CFG, 3, T))
    for row, n in enumerate(lengths):
        np.testing.assert_allclose(got[row, :n], want[row][:n], atol=ATOL)
    # The state and the tail are those of the last token that counts: row
    # 2's after 17 tokens equal a run of 17 alone.
    _, alone, _ = _forward(params, tokens[2:, :17], np.zeros(1), [17], hybrid.init_state(CFG, 1, T))
    for i in MAMBA:
        for name in LEAVES:
            np.testing.assert_allclose(np.asarray(state[i][name])[2], np.asarray(alone[i][name])[0], atol=1e-5)
    k = np.asarray(state[2]["k"])
    assert not k[2, 17:].any() and k[2, :17].all()
    c = dict(zip(moe.COUNTERS + CFG.row_counters, np.asarray(counters).tolist()))
    # Three choices a token that counts, two expert layers; the scan's
    # blocks of 4 over every row's 40 positions, three mamba layers.
    assert c["choices_routed"] == 2 * 3 * (N + 31 + 17) and c["expert_layer_steps"] == 2
    assert c["ssm_tokens"] == 3 * (N + 31 + 17) and c["ssm_blocks"] == 3 * 3 * 10
    assert c["read_state"] == c["dense_state"] == 0  # a prefill call
    assert c["read_full"] == c["dense_full"] == 3 * T and c["read_window"] == 0


@pytest.mark.parametrize("pieces", [(1, 2, 3, 5, 9), (4, 8, 8)], ids=["uneven", "whole_blocks"])
def test_chunks_of_any_length_then_decode_steps_match_the_reference(params, tokens, want, pieces):
    """A prompt in chunks of 1, 2, 3, 5, 9 tokens with blocks of 4 (state
    and tail cross every boundary; a chunk of one token takes the step, not
    the scan), then one token a step through ``decode_step`` beside a slot
    that does not decode."""
    model = MODEL
    row, n_prefill = tokens[0], sum(pieces)
    state, got = _pieces(params, model.init_state(2, T), row, 1, pieces)
    np.testing.assert_allclose(got, want[0][:n_prefill], atol=ATOL)
    assert not any(np.asarray(leaf)[0].any() for layer in state for leaf in layer.values())  # slot 0 untouched
    step = STEP
    for pos in range(n_prefill, N):
        state, logits, counters = step(
            params, state, jnp.asarray([0, row[pos]]), jnp.asarray([0, pos]), jnp.asarray([0, 1]), T)
        np.testing.assert_allclose(np.asarray(logits)[1], want[0][pos], atol=ATOL)
    assert not any(np.asarray(leaf)[0].any() for layer in state for leaf in layer.values())
    c = dict(zip(moe.COUNTERS + CFG.row_counters, np.asarray(counters).tolist()))
    assert c["read_state"] == c["dense_state"] == 3 * 2 and c["ssm_blocks"] == 0  # XLA's step: every slot


def test_a_row_that_does_not_decode_keeps_its_state_bit_for_bit(params, tokens, want):
    """Two slots prefilled; a decode step in which only slot 1 counts
    leaves slot 0's ``S``, tail and rows as they were (its token is finite
    junk), and slot 0 then decodes on from them as if nothing had happened."""
    model = MODEL
    state = model.init_state(2, T)
    for slot in (0, 1):
        state, _ = _pieces(params, state, tokens[slot], slot, (8, 8, 8))
    before = _recurrent(state, 0)
    step = STEP
    state, logits, _ = step(
        params, state, jnp.asarray([5, tokens[1][24]]), jnp.asarray([24, 24]), jnp.asarray([0, 1]), T)
    np.testing.assert_allclose(np.asarray(logits)[1], want[1][24], atol=ATOL)
    for a, b in zip(before, _recurrent(state, 0)):
        np.testing.assert_array_equal(a, b)
    assert not np.asarray(state[2]["k"])[0, 24:].any()
    state, logits, _ = step(
        params, state, jnp.asarray([tokens[0][24], 0]), jnp.asarray([24, 25]), jnp.asarray([1, 0]), T)
    np.testing.assert_allclose(np.asarray(logits)[0], want[0][24], atol=ATOL)


@pytest.mark.parametrize("through", ["prefill_row", "prefill_rows"])
def test_a_slot_reused_from_position_0_ignores_stale_state(params, tokens, want, through):
    """The slot's last occupant left ``S``, a tail and rows; a prompt that
    starts at 0 starts from zero state whatever the slot held."""
    model = MODEL
    state, _ = _pieces(params, model.init_state(2, T), tokens[2], 1, (8, 8, 8))
    assert all(t.any() for t in _recurrent(state, 1))
    if through == "prefill_row":
        _, got = _pieces(params, state, tokens[0], 1, (8, 8))
    else:
        program = ROWS
        got = []
        for at in (0, 8):
            toks = np.stack([tokens[0][at : at + 8], np.zeros(8, np.int32), np.zeros(8, np.int32)])
            state, hidden, _ = program(
                params, state, jnp.asarray(toks), jnp.asarray([at, 0, 0], jnp.int32),
                jnp.asarray([8, 0, 0], jnp.int32), jnp.asarray([1, 0, 0], jnp.int32), T)
            got.append(np.asarray(model.logits(params, hidden))[0])
        got = np.concatenate(got)
    np.testing.assert_allclose(got, want[0][:16], atol=ATOL)


def test_a_prefix_hit_grafts_the_rows_and_restores_the_state_by_leaf(params, tokens, want):
    """A snapshot taken at a chunk boundary holds ``S`` and the tail of
    every mamba layer and no K/V row; a hit there grafts the source slot's
    rows, restores the state, and the suffix then reads as a cold
    prefill's, though the source has moved on."""
    model = MODEL
    assert not model.cut_anywhere
    row = tokens[0]
    state, _ = _pieces(params, model.init_state(3, T), row, 0, (8, 8))
    snap = jax.jit(model.save_state)(state, jnp.int32(0))
    assert [sorted(layer) for layer in snap] == [sorted(LEAVES)] * 3  # the attention layer has no entry
    assert sum(leaf.size * leaf.dtype.itemsize for layer in snap for leaf in layer.values()) == model.snapshot_bytes
    state, _ = _pieces(params, state, row, 0, (8, 8), start=16)  # the source moves on
    other = np.concatenate([row[:16], tokens[1][16:]])
    state = jax.jit(model.graft_prefix, static_argnums=(3,))(state, jnp.int32(0), jnp.int32(2), 16)
    state = jax.jit(model.restore_state)(state, jnp.int32(2), snap)
    state, got = _pieces(params, state, other, 2, (8, 8, 8), start=16)
    other_want = np.asarray(ref.all_logits(params, CFG, other))
    np.testing.assert_allclose(got, other_want[16:], atol=ATOL)
    # Without the restore the suffix starts from the state of nothing.
    bare = jax.jit(model.graft_prefix, static_argnums=(3,))(state, jnp.int32(0), jnp.int32(1), 16)
    _, lost = _pieces(params, bare, other, 1, (8,), start=16)
    assert np.abs(lost - other_want[16:24]).max() > 0.05


def test_the_chunks_of_several_slots_go_through_one_program(params, tokens, want):
    """``prefill_rows`` (the scheduler's chunk program) over three rows at
    once, one of them padding: each live row continues from its own slot's
    ``S`` and tail, and the pad row's slot keeps what it held, bit for bit."""
    model = MODEL
    assert model.rows_in_place and not model.one_window
    program = ROWS
    state = model.init_state(4, T)
    state = tuple({n: leaf.at[3].set(7.0) for n, leaf in layer.items()} for layer in state)
    slots = np.array([2, 0, 3], np.int32)
    for at in range(0, 24, 8):
        toks = np.zeros((3, 8), np.int32)
        toks[0], toks[1] = tokens[0][at : at + 8], tokens[1][at : at + 8]
        state, hidden, counters = program(
            params, state, jnp.asarray(toks), jnp.asarray([at, at, 5], jnp.int32),
            jnp.asarray([8, 8, 0], jnp.int32), jnp.asarray(slots), T)
        got = np.asarray(model.logits(params, hidden))
        for r in (0, 1):
            np.testing.assert_allclose(got[r], want[r][at : at + 8], atol=ATOL)
    for layer in state:
        for leaf in layer.values():
            assert (np.asarray(leaf)[3] == 7.0).all() and not np.asarray(leaf)[1].any()
    c = dict(zip(model.counter_names, np.asarray(counters).tolist()))
    assert c["moe_choices_routed"] == 2 * 3 * 16 and c["moe_expert_layer_steps"] == 2
    assert c["moe_choices_local_decode"] == c["moe_experts_touched_decode"] == 0  # a prefill program
    # Two blocks of 4 a row, the pad row's too: a third of the scan's work is padding.
    assert c["attn_rows_ssm_tokens_prefill"] == 3 * 16 and c["attn_rows_ssm_blocks_prefill"] == 3 * 3 * 2
    assert c["attn_rows_ssm_blocks_decode"] == c["attn_rows_read_state_prefill"] == 0


def test_the_decode_chunk_counts_local_choices_and_state_slots(params, tokens):
    """``moe_choices_local_decode`` beside ``moe_experts_touched_decode``
    (``decode_rows_per_expert`` is their ratio) and the mamba layers' slots
    under ``STATE_COUNTERS``, from a decode chunk alone."""
    model = MODEL
    names = model.counter_names
    n = len(moe.COUNTERS)
    assert names[n : n + 3] == (
        "moe_experts_touched_decode", "moe_expert_layer_steps_decode", "moe_choices_local_decode")
    for preset in ("ling-tiny", "zaya-tiny"):
        assert HybridServing(hybrid.PRESETS[preset](), None, T).counter_names[n : n + 3] == names[n : n + 3]
    state = model.init_state(2, T)
    for slot in (0, 1):
        state, _ = _pieces(params, state, tokens[slot], slot, (8, 8))
    chunk = model.make_decode_chunk()
    assert chunk.__name__ == "decode_chunk"  # what ``decode_step_dev_ms`` reads in the trace
    steps = 4
    state, toks, aux = chunk(
        params, state, jnp.asarray(tokens[:2, 16]), jnp.asarray([16, 16], jnp.int32),
        jax.random.PRNGKey(0), jnp.zeros((2,)), jnp.ones((2,)), jnp.zeros((2,), jnp.int32),
        steps, T, jnp.asarray([True, False]))
    c = dict(zip(names, np.asarray(aux).tolist()))
    assert toks.shape == (steps, 2)
    assert c["moe_expert_layer_steps_decode"] == 2 * steps and c["moe_choices_routed"] == 2 * 3 * steps
    # One live row: each local choice is an expert of its own.
    assert 0 < c["moe_choices_local_decode"] == c["moe_choices_local"] == c["moe_experts_touched_decode"]
    assert c["attn_rows_read_state_decode"] == c["attn_rows_dense_state_decode"] == 3 * 2 * steps
    assert c["attn_rows_dense_full_decode"] == steps * 2 * T and c["attn_rows_ssm_blocks_prefill"] == 0


# -- the controls ------------------------------------------------------------------


def _int8(a, axis):
    scale = jnp.max(jnp.abs(a), axis=axis, keepdims=True) / 127.0 + 1e-30
    return jnp.round(a / scale) * scale


def _int8_mlp(h, w_up, w_down):
    """``_mlp`` with int8 weights (a scale an output channel) and int8
    activations (a scale a token) in both products."""
    project = lambda x, w: _int8(x, -1) @ _int8(w.astype(jnp.float32), 0)
    return project(ref._act(project(h, w_up)), w_down)


def _rope_on(q, k, theta=10000.0):
    """The rotation the config's ``rope_theta`` would give (half-split
    pairs over the whole head), which the family's attention does not have."""
    d = q.shape[-1]
    inv = theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    ang = jnp.arange(q.shape[0], dtype=jnp.float32)[:, None, None] * jnp.asarray(inv, jnp.float32)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    turn = lambda x: jnp.concatenate(
        [x[..., : d // 2] * cos - x[..., d // 2 :] * sin, x[..., d // 2 :] * cos + x[..., : d // 2] * sin], -1)
    return turn(q), turn(k)


def _gate_after_norm(y, z, gain, groups, eps):
    grouped = y.reshape(y.shape[0], groups, -1)
    grouped = grouped * jax.lax.rsqrt(jnp.mean(grouped * grouped, axis=-1, keepdims=True) + eps)
    return grouped.reshape(y.shape) * gain.astype(jnp.float32) * jax.nn.silu(z)


PLAIN_NORM = ref._gated_norm
# What each control puts in the reference's place.
CONTROLS = {
    "w8a8_mlp": {"_mlp": _int8_mlp},  # the nearest precision below, in the experts' products
    "state_bf16": {"_keep": lambda state: jax.lax.reduce_precision(state, exponent_bits=8, mantissa_bits=7)},
    "no_conv": {"_conv": lambda u, lp: u},
    "no_d_skip": {"_skip": lambda y, d, xs: y},
    "norm_whole": {"_gated_norm": lambda y, z, gain, groups, eps: PLAIN_NORM(y, z, gain, 1, eps)},
    "gate_after_norm": {"_gated_norm": _gate_after_norm},
    "relu_not_relu2": {"_act": jax.nn.relu},
    "no_routed_scale": {"_routed_weights": lambda s, scale: s / (s.sum(-1, keepdims=True) + 1e-20)},
    "rope_on": {"_rotate": _rope_on},
}


@pytest.mark.parametrize("control", sorted(CONTROLS))
def test_each_mechanism_left_out_of_the_reference_fails_the_comparison(params, tokens, want, control, monkeypatch):
    """The nine controls of the chip's comparison (``chip_smoke.py --hybrid
    --model nemotron_h --control NAME``): the reference without one
    mechanism, or in the nearest precision below, leaves the program's
    logits by far more than the tolerance."""
    for name, stand_in in CONTROLS[control].items():
        monkeypatch.setattr(ref, name, stand_in)
    plain = ref._layer.__wrapped__
    # A layer program of its own: the one traced before keeps the plain mechanism.
    monkeypatch.setattr(ref, "_layer", jax.jit(
        lambda x, lp, letter, dims_t: plain(x, lp, letter, dims_t), static_argnames=("letter", "dims_t")))
    off = np.asarray(ref.all_logits(params, CFG, tokens[0]))
    monkeypatch.undo()
    worst = np.abs(off - want[0]).max(-1)
    # A state rounded to bfloat16 40 times moves a logit by 2e-3; the rest by 2e-2 and more.
    assert worst.max() > (5 if control == "state_bf16" else 50) * ATOL, worst.max()
    np.testing.assert_allclose(np.asarray(ref.all_logits(params, CFG, tokens[0])), want[0], atol=1e-6)


# -- the experts ---------------------------------------------------------------------


def test_the_four_shares_add_up_to_the_uncut_layer(params):
    """The ``r`` of each share of an ``E`` layer through its own ``W_up``,
    the shared expert counted once, is the uncut layer; program and
    reference alike."""
    cfg8 = dataclasses.replace(CFG, experts_held=8, expert_offset=0)
    lp = hybrid.init_params(dataclasses.replace(cfg8, layer_kinds=cfg8.layer_kinds[:1]), jax.random.PRNGKey(11))["layers"][0]
    h = jax.random.normal(jax.random.PRNGKey(5), (2, 24, CFG.d_model))
    valid = jnp.ones((2, 24), bool)
    whole, counters, _ = hybrid._expert_layer(h, lp, valid, cfg8, None)
    shared = hybrid._relu2(h, lp["w_up_s"], lp["w_down_s"])
    dims = ref._dims(cfg8, None, None)
    parts, ref_parts = [], []
    for rank in range(4):
        cfg = dataclasses.replace(CFG, experts_held=2, expert_offset=2 * rank)
        share = {**lp, "w_up_e": lp["w_up_e"][2 * rank : 2 * rank + 2],
                 "w_down_e": lp["w_down_e"][2 * rank : 2 * rank + 2]}
        y, c, _ = hybrid._expert_layer(h, share, valid, cfg, None)
        parts.append(y - shared)  # every share computes the shared expert alike
        ref_parts.append(ref.routed(h[0], share, {**dims, "held": 2, "offset": 2 * rank}))
        assert int(c[0]) == 48 * 3  # every share routes every token over all 8 outputs
    np.testing.assert_allclose(sum(parts) + shared, whole, atol=2e-5)
    uncut = ref.experts(h[0], lp, dims)
    np.testing.assert_allclose(sum(ref_parts) + ref._mlp(h[0], lp["w_up_s"], lp["w_down_s"]), uncut, atol=2e-5)
    np.testing.assert_allclose(whole[0], uncut, atol=2e-5)
    assert int(counters[0]) == int(counters[1]) == 48 * 3 and int(counters[4]) == 1  # all local, one call


def test_relu2_experts_route_scale_and_renormalise(params):
    """22-of-512 in small: the weights of a token's choices sum to the
    routed scale, and the experts have no gate."""
    lp = params["layers"][0]
    h = jax.random.normal(jax.random.PRNGKey(3), (32, CFG.d_model))
    idx, w = moe.route(
        h, lp["router"], lp["router_bias"], k=3, n_group=1, topk_group=1, norm_topk=True,
        scale=CFG.routed_scaling, score="sigmoid")
    np.testing.assert_allclose(np.asarray(w).sum(-1), 5.0, rtol=1e-5)
    u = jnp.dot(h, lp["w_lat_down"])
    y, _ = moe.expert_mlp(u, idx, w, jnp.ones((32,), bool), lp, offset=0, held=4, act="relu2")
    want = np.zeros((32, 32), np.float32)
    for t in range(32):
        for j, e in enumerate(np.asarray(idx)[t]):
            if e < 4:
                mid = np.maximum(np.asarray(u)[t] @ np.asarray(lp["w_up_e"])[e], 0.0) ** 2
                want[t] += float(w[t, j]) * (mid @ np.asarray(lp["w_down_e"])[e])
    np.testing.assert_allclose(y, want, atol=2e-4)


def test_balancing_evens_the_load_of_22_of_512():
    """The published routing, 22 of 512 sigmoid scores a token with a
    selection bias: the bias ``balance_router_biases`` gives a layer
    (``moe.balanced_bias`` on that layer's own scores) loads the experts far
    more evenly on fresh tokens than no bias does."""
    key = jax.random.PRNGKey(7)
    router = jax.random.normal(key, (64, 512)) * 64**-0.5
    sample, fresh = (jax.random.normal(jax.random.fold_in(key, i), (4096, 64)) for i in (1, 2))
    bias = jax.jit(functools.partial(moe.balanced_bias, k=22, n_group=1, topk_group=1))(
        moe.scores(sample, router))

    def load(bias):
        idx, w = moe.route(fresh, router, bias, k=22, n_group=1, topk_group=1, norm_topk=True, scale=5.0)
        np.testing.assert_allclose(np.asarray(w).sum(-1), 5.0, rtol=1e-5)
        counts = np.bincount(np.asarray(idx).ravel(), minlength=512)
        return counts.max() / counts.mean()

    without, with_bias = load(jnp.zeros((512,))), load(bias)
    assert without > 1.8 and with_bias < 1.35, (without, with_bias)
    # And the model's own balancing reaches every expert layer of the family.
    raw = hybrid.init_params(CFG, key)
    balanced = hybrid.balance_router_biases(raw, CFG, jax.random.fold_in(key, 1))
    for kind, a, b in zip(CFG.layer_kinds, raw["layers"], balanced["layers"]):
        assert ("router_bias" in a) == (kind[1] == "experts")
        if kind[1] == "experts":
            assert np.abs(np.asarray(a["router_bias"]) - np.asarray(b["router_bias"])).max() > 1e-3
