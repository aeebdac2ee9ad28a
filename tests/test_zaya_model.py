"""``models/hybrid.py``'s ``cca`` kind and the ZAYA router (the ``zaya``
family: ZAYA1-8B) against the plain reference, ``models/zaya_reference.py``,
at a tiny size that keeps the ratios of the benchmark's cut: a query latent
of half the hidden size on 2 key-value heads (so the grouped mean and the
value shift have two heads each), three layers (the router's state is
handed on twice), 8 experts and one a token, a tied head.  Seeded random
float32 weights; logits are compared, never sampled tokens.

Tolerance: both sides are float32 at the highest matmul precision
(conftest.py) and differ by the order of their sums.  The head is the
embedding (entries of variance 1), so logits are O(100); 5e-4 absolute is
about 40 float32 ulps of the largest, and each mechanism left out (the
controls below) moves a logit by 1e-1 or more.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from generativeaiexamples_tpu.engine.serving_models import HybridServing, serving_model
from generativeaiexamples_tpu.models import hybrid
from generativeaiexamples_tpu.models import zaya_reference as ref
from generativeaiexamples_tpu.ops import moe

ATOL = 5e-4
CFG = hybrid.PRESETS["zaya-tiny"]()
T = 64
N = 40  # tokens a row
TAILS = hybrid.CCA_TAILS


@pytest.fixture(scope="module")
def params():
    key = jax.random.PRNGKey(0)
    return hybrid.balance_router_biases(
        hybrid.init_params(CFG, key), CFG, jax.random.fold_in(key, 1))


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


def _tails(state, slot):
    return [np.asarray(layer[n])[slot] for layer in state for n in TAILS]


def _pieces(model, params, state, row, slot, pieces, width=8, start=0):
    """``row[start:]`` through ``prefill_row`` in pieces of the given
    lengths (each padded to ``width``); returns (state, logits of every
    piece's positions, side by side)."""
    chunk = jax.jit(model.prefill_row, static_argnums=(6,))
    got, at = [], start
    for count in pieces:
        piece = np.zeros((1, width), np.int32)
        piece[0, :count] = row[at : at + count]
        state, hidden, _ = chunk(
            params, state, jnp.asarray(piece), jnp.int32(at), jnp.int32(count), jnp.int32(slot), T)
        got.append(np.asarray(model.logits(params, hidden))[0, :count])
        at += count
    return state, np.concatenate(got)


def test_the_published_keys_give_the_published_model():
    whole = hybrid.from_hf_config(hybrid.ZAYA1_8B, max_len=64)
    assert isinstance(whole, hybrid.CcaConfig)
    assert whole.layer_kinds == (("cca", "experts"),) * 40
    cut = hybrid.PRESETS["zaya1-8b-l20"]()
    assert cut.layer_kinds == whole.layer_kinds[:20] and cut.max_seq_len == 8192
    assert (cut.d_model, cut.n_heads, cut.n_kv_heads, cut.attn_head_dim) == (2048, 8, 2, 128)
    # The query latent is half the hidden size, the key latent an eighth.
    assert cut.n_heads * cut.attn_head_dim == 1024 and cut.cca_channels == 1280
    assert (cut.conv_time0, cut.conv_time1, cut.rotary_dim, cut.router_hidden) == (2, 2, 64, 256)
    assert (cut.rope_full.theta, cut.rope_full.rope_type) == (5e6, "default")
    assert (cut.n_experts, cut.experts_held, cut.n_experts_per_tok, cut.moe_d_ff) == (16, 16, 1, 2048)
    assert (cut.shared_d_ff, cut.n_group, cut.routed_scaling, cut.norm_topk) == (0, 1, 1.0, False)
    assert cut.score_function == "softmax" and cut.router_bias and cut.tie_embeddings
    assert (cut.vocab_size, cut.norm_eps) == (262272, 1e-5)
    # Rows AND tails in every layer: a hit is cut at a snapshot, which is small.
    assert not cut.rows_only and cut.draft == "" and cut.row_counters == hybrid.ATTN_COUNTERS
    assert cut.snapshot_bytes() == 20 * 5376 == 107_520
    assert CFG.layer_kinds == whole.layer_kinds[:3]  # the tiny size keeps the pattern
    assert CFG.n_heads * CFG.attn_head_dim * 2 == CFG.d_model  # and the latent's ratio


def test_the_cut_holds_the_bytes_the_issue_counts():
    cut = hybrid.PRESETS["zaya1-8b-l20"]()
    shapes = jax.eval_shape(lambda: hybrid.init_params(cut, jax.random.PRNGKey(0)))
    assert "lm_head" not in shapes  # the head is the embedding
    weights = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(shapes))
    assert weights == pytest.approx(9.38e9, rel=0.002)
    layer = shapes["layers"][0]
    count = lambda *names: sum(layer[n].size for n in names)
    assert count("w_qkv") == 2048 * (1024 + 256 + 128 + 128) and count("w_o") == 1024 * 2048
    assert count("conv0_w", "conv0_b", "conv1_w", "conv1_b") == 3 * 1280 + 2 * 10 * 128 * 128 + 1280
    assert sum(layer[n].size for n in layer if n.startswith("router")) == 660_513
    assert count("w_gu_e", "w_down_e") == 16 * 3 * 2048 * 2048 == 201_326_592
    assert sum(x.size for x in layer.values()) == 207_566_883  # 415 MB a layer in bf16
    assert shapes["embed"].size == 262272 * 2048 == 537_133_056
    state = hybrid.state_bytes(cut, 32, 8192)
    # 1,024 B a token a layer; the tails 5,376 B a slot a layer.
    assert state == {"full": 32 * 8192 * 1024 * 20, "window": 0, "recurrent": 32 * 20 * 5376}
    assert state["full"] == 5_368_709_120 and state["recurrent"] == 3_440_640


@pytest.mark.parametrize("bad, match", [
    ({"layer_types": ["hybrid", "hybrid_sliding", "hybrid"]}, "hybrid_sliding"),
    ({"sliding_window": 4096}, "sliding window"),
    ({"num_experts_per_tok": 2}, "one expert a token"),
    ({"attention_bias": True}, "biases"),
    ({"hidden_act": "gelu"}, "silu"),
    ({"num_key_value_heads": 1}, "must be even"),
    ({"cca_time1": 1}, "keeps a tail"),
])
def test_what_the_family_does_not_serve_is_refused_with_the_reason(bad, match):
    with pytest.raises(ValueError, match=match):
        hybrid.from_hf_config({**hybrid.ZAYA_TINY, **bad}, max_len=64)


def test_check_supported_refuses_drafts_over_tails():
    model = serving_model(CFG, None, T)
    model.check_supported()
    model.draft = "mtp"  # a prediction module over a cca layer's tails, were one held
    with pytest.raises(ValueError, match="tails"):
        model.check_supported()


@pytest.mark.parametrize("asked, match", [
    (dict(quantize=True, matmul_kernel="xla"), "int8 weights"),
    (dict(quantize=False, matmul_kernel="pallas_w8a8"), "int8 weights"),
])
def test_int8_weights_are_refused(asked, match):
    with pytest.raises(ValueError, match=match):
        serving_model(CFG, None, T).prepare_params(None, seed=0, **asked)
    with pytest.raises(ValueError, match="int8 state"):
        serving_model(dataclasses.replace(CFG, kv_dtype="int8"), None, T).check_supported()


def test_a_cold_batch_matches_the_reference_and_pads_move_nothing(params, tokens, want):
    lengths = np.array([N, 31, 17], np.int32)
    got, state, counters = _forward(params, tokens, np.zeros(3), lengths, hybrid.init_state(CFG, 3, T))
    for row, n in enumerate(lengths):
        np.testing.assert_allclose(got[row, :n], want[row][:n], atol=ATOL)
    # A padded position wrote no row, and the tails are those of the last
    # token that counts: row 2's after 17 tokens equal a run of 17 alone.
    _, alone, _ = _forward(params, tokens[2:, :17], np.zeros(1), [17], hybrid.init_state(CFG, 1, T))
    for layer, one in zip(state, alone):
        assert not np.asarray(layer["k"])[2, 17:].any() and np.asarray(layer["k"])[2, :17].all()
        for name in TAILS:
            np.testing.assert_allclose(np.asarray(layer[name])[2], np.asarray(one[name])[0], atol=1e-6)
    c = dict(zip(moe.COUNTERS + CFG.row_counters, np.asarray(counters).tolist()))
    # One choice a token that counts, every expert here, one call a layer;
    # the mixer's rows are counted as a full layer's.
    assert c["choices_routed"] == c["choices_local"] == 3 * (N + 31 + 17)
    assert c["expert_layer_steps"] == 3 and c["read_window"] == c["dense_window"] == 0
    assert c["read_full"] == c["dense_full"] == 3 * 3 * T


@pytest.mark.parametrize("pieces", [(1, 2, 3, 5, 7, 4), (8, 8, 6)], ids=["uneven", "whole"])
def test_chunks_of_any_length_then_decode_steps_match_the_reference(params, tokens, want, pieces):
    """A prompt in chunks of 1, 2, 3, 5, ... tokens (the tails and the
    shifted value cross every boundary, a chunk of one token has no history
    of its own at all), then one token a step through ``decode_step`` beside
    a slot that does not decode."""
    model = serving_model(CFG, None, T)
    row, n_prefill = tokens[0], sum(pieces)
    state, got = _pieces(model, params, model.init_state(2, T), row, 1, pieces)
    np.testing.assert_allclose(got, want[0][:n_prefill], atol=ATOL)
    assert not any(np.asarray(leaf)[0].any() for layer in state for leaf in layer.values())  # slot 0 untouched
    step = jax.jit(model.decode_step, static_argnums=(5,))
    for pos in range(n_prefill, N):
        state, logits, counters = step(
            params, state, jnp.asarray([0, row[pos]]), jnp.asarray([0, pos]), jnp.asarray([0, 1]), T)
        np.testing.assert_allclose(np.asarray(logits)[1], want[0][pos], atol=ATOL)
    assert not any(np.asarray(leaf)[0].any() for layer in state for leaf in layer.values())


def test_a_row_that_does_not_decode_keeps_its_tails(params, tokens, want):
    """Two slots prefilled; a decode step in which only slot 1 counts
    leaves slot 0's tails and rows as they were (its token is finite junk),
    and slot 0 then decodes on from them as if nothing had happened."""
    model = serving_model(CFG, None, T)
    state = model.init_state(2, T)
    for slot in (0, 1):
        state, _ = _pieces(model, params, state, tokens[slot], slot, (8, 8, 8))
    before = _tails(state, 0)
    step = jax.jit(model.decode_step, static_argnums=(5,))
    state, logits, _ = step(
        params, state, jnp.asarray([5, tokens[1][24]]), jnp.asarray([24, 24]), jnp.asarray([0, 1]), T)
    np.testing.assert_allclose(np.asarray(logits)[1], want[1][24], atol=ATOL)
    for a, b in zip(before, _tails(state, 0)):
        np.testing.assert_array_equal(a, b)
    assert not any(np.asarray(layer["k"])[0, 24:].any() for layer in state)
    state, logits, _ = step(
        params, state, jnp.asarray([tokens[0][24], 0]), jnp.asarray([24, 25]), jnp.asarray([1, 0]), T)
    np.testing.assert_allclose(np.asarray(logits)[0], want[0][24], atol=ATOL)


@pytest.mark.parametrize("through", ["prefill_row", "prefill_rows"])
def test_a_slot_reused_from_position_0_ignores_stale_tails(params, tokens, want, through):
    """The slot's last occupant left tails and rows; a prompt that starts
    at 0 starts from zero tails whatever the slot held."""
    model = serving_model(CFG, None, T)
    state, _ = _pieces(model, params, model.init_state(2, T), tokens[2], 1, (8, 8, 8))
    assert all(t.any() for t in _tails(state, 1))
    if through == "prefill_row":
        _, got = _pieces(model, params, state, tokens[0], 1, (8, 8))
    else:
        program = jax.jit(model.prefill_rows, static_argnums=(6,))
        got = []
        for at in (0, 8):
            toks = np.stack([tokens[0][at : at + 8], np.zeros(8, np.int32)])
            state, hidden, _ = program(
                params, state, jnp.asarray(toks), jnp.asarray([at, 0], jnp.int32),
                jnp.asarray([8, 0], jnp.int32), jnp.asarray([1, 0], jnp.int32), T)
            got.append(np.asarray(model.logits(params, hidden))[0])
        got = np.concatenate(got)
    np.testing.assert_allclose(got, want[0][:16], atol=ATOL)


def test_a_prefix_hit_grafts_the_rows_and_restores_the_tails_by_leaf(params, tokens, want):
    """A snapshot taken at a chunk boundary holds the tails of every layer
    and no row (107 KB at the cut's sizes, not a copy of the layers); a hit
    there grafts the source slot's rows, restores the tails, and the suffix
    then reads as a cold prefill's, though the source has moved on."""
    model = serving_model(CFG, None, T)
    assert not model.cut_anywhere and model.snapshot_bytes == CFG.snapshot_bytes(T) == 3 * (2 * 96 + 16) * 4
    row = tokens[0]
    state, _ = _pieces(model, params, model.init_state(3, T), row, 0, (8, 8))
    snap = jax.jit(model.save_state)(state, jnp.int32(0))
    assert [sorted(layer) for layer in snap] == [sorted(TAILS)] * 3
    assert sum(leaf.size * leaf.dtype.itemsize for layer in snap for leaf in layer.values()) == model.snapshot_bytes
    state, _ = _pieces(model, params, state, row, 0, (8, 8), start=16)  # the source moves on
    # Another prompt with the same first 16 tokens, into slot 2.
    other = np.concatenate([row[:16], tokens[1][16:]])
    state = jax.jit(model.graft_prefix, static_argnums=(3,))(state, jnp.int32(0), jnp.int32(2), 16)
    state = jax.jit(model.restore_state)(state, jnp.int32(2), snap)
    state, got = _pieces(model, params, state, other, 2, (8, 8, 8), start=16)
    np.testing.assert_allclose(got, np.asarray(ref.all_logits(params, CFG, other))[16:], atol=ATOL)
    # Without the restore the first token after the hit has no history.
    bare = jax.jit(model.graft_prefix, static_argnums=(3,))(state, jnp.int32(0), jnp.int32(1), 16)
    _, lost = _pieces(model, params, bare, other, 1, (8,), start=16)
    assert np.abs(lost - np.asarray(ref.all_logits(params, CFG, other))[16:24]).max() > 0.1


def test_the_chunks_of_several_slots_go_through_one_program(params, tokens, want):
    """``prefill_rows`` (the scheduler's chunk program) over three rows at
    once, one of them padding: each live row takes its history from its own
    slot's tails, and the pad row's slot keeps what it held."""
    model = serving_model(CFG, None, T)
    assert model.chunks_per_program(16) == 8 and model.chunk_windows(16) == (T,)
    program = jax.jit(model.prefill_rows, static_argnums=(6,))
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
    assert c["moe_choices_routed"] == 3 * 16 and c["moe_expert_layer_steps"] == 3
    assert c["moe_experts_touched_decode"] == c["moe_expert_layer_steps_decode"] == 0  # a prefill program
    assert c["attn_rows_dense_full_prefill"] == 3 * 3 * T and c["attn_rows_dense_full_decode"] == 0


def _int8(a, axis):
    scale = jnp.max(jnp.abs(a), axis=axis, keepdims=True) / 127.0 + 1e-30
    return jnp.round(a / scale) * scale


def _int8_project(h, w):
    """A projection with int8 weights (a scale an output channel) and
    int8 activations (a scale a token)."""
    return _int8(h, -1) @ _int8(w.astype(jnp.float32), 0)


def _int8_swiglu(h, w_gu, w_down):
    gu = _int8_project(h, w_gu)
    half = gu.shape[-1] // 2
    return _int8_project(jax.nn.silu(gu[:, :half]) * gu[:, half:], w_down)


# What each control puts in the reference's place.
CONTROLS = {
    "w8a8": {"_swiglu": _int8_swiglu, "_project": _int8_project},  # every projection of a layer
    "no_value_shift": {"_shift_values": lambda now, late: jnp.concatenate([now, late], axis=-1)},
    "no_qk_mean": {"_qk_mean": lambda qp, kp: (jnp.zeros_like(qp), jnp.zeros_like(kp))},
    "no_conv": {"_conv": lambda u, lp, dims: u},
    "no_router_average": {"_router_average": lambda rho, prev, gamma: rho},
    "renormed_top1": {"_top1_weight": lambda p, chosen: chosen.astype(p.dtype)},
}


@pytest.mark.parametrize("control", sorted(CONTROLS))
def test_each_mechanism_left_out_of_the_reference_fails_the_comparison(params, tokens, want, control, monkeypatch):
    """The six controls of the chip's comparison (``chip_smoke.py --hybrid
    --model zaya --control NAME``): the reference without one mechanism
    leaves the program's logits by far more than the tolerance."""
    for name, stand_in in CONTROLS[control].items():
        monkeypatch.setattr(ref, name, stand_in)
    jax.clear_caches()  # a layer traced before this would keep the plain one
    try:
        off = np.asarray(ref.all_logits(params, CFG, tokens[0]))
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    worst = np.abs(off - want[0]).max(-1)
    assert worst.max() > 0.1 > 100 * ATOL
    if control == "no_value_shift":  # position 0 has no token before it: zeros either way...
        assert worst[1:].min() > 1e-2
    np.testing.assert_allclose(np.asarray(ref.all_logits(params, CFG, tokens[0])), want[0], atol=1e-6)


def test_the_head_is_the_embedding(params, tokens):
    assert "lm_head" not in params
    hidden = jax.random.normal(jax.random.PRNGKey(2), (2, 5, CFG.d_model))
    got = hybrid.logits(params, CFG, hidden)
    h = np.asarray(hybrid.rms_norm(hidden, params["final_norm"], CFG.norm_eps))
    np.testing.assert_allclose(got, h @ np.asarray(params["embed"]).T, atol=1e-4)
    np.testing.assert_allclose(ref.head(params, CFG, hidden[0]), got[0], atol=1e-4)
    # An untied model of the family keeps a matrix of its own.
    untied = hybrid.from_hf_config({**hybrid.ZAYA_TINY, "tie_word_embeddings": False}, max_len=64)
    shapes = jax.eval_shape(lambda: hybrid.init_params(untied, jax.random.PRNGKey(0)))
    assert shapes["lm_head"].shape == (CFG.d_model, CFG.vocab_size)


def test_the_router_hands_its_state_from_layer_to_layer(params):
    """Layer ``l``'s router sees ``rho_l + gamma_l rho_{l-1}``: with the
    state of the layer before it routes as the reference does, without it
    some tokens go to another expert."""
    lp0, lp1 = params["layers"][0], params["layers"][1]
    assert 0.25 <= float(lp1["router_gamma"]) <= 0.75
    h = jax.random.normal(jax.random.PRNGKey(3), (64, CFG.d_model))
    dims = ref._dims(CFG, None, None)
    p0, rho0 = moe.mlp_scores(h, lp0, None, eps=CFG.norm_eps)
    want0, want_rho0 = ref.router(h, lp0, None, dims)
    np.testing.assert_allclose(p0, want0, atol=1e-6)
    p1, rho1 = moe.mlp_scores(h, lp1, rho0, eps=CFG.norm_eps)
    want1, want_rho1 = ref.router(h, lp1, want_rho0, dims)
    np.testing.assert_allclose(p1, want1, atol=1e-6)
    np.testing.assert_allclose(rho1, want_rho1, atol=1e-5)
    alone, _ = moe.mlp_scores(h, lp1, None, eps=CFG.norm_eps)
    assert np.abs(np.asarray(alone) - np.asarray(p1)).max() > 1e-2
    idx, w, _ = moe.route_mlp(h, lp1, rho0, eps=CFG.norm_eps)
    sel = np.asarray(p1) + np.asarray(lp1["router_bias"])
    np.testing.assert_array_equal(np.asarray(idx)[:, 0], sel.argmax(-1))
    # The weight is the probability as it is, not renormalised to 1.
    np.testing.assert_allclose(np.asarray(w)[:, 0], np.asarray(p1)[np.arange(64), sel.argmax(-1)], atol=1e-7)
    assert 0.0 < float(np.asarray(w).max()) < 1.0


def test_balancing_evens_the_top1_load_through_the_mlp_router():
    """``balance_router_biases`` through the ZAYA router: each layer's
    selection bias is balanced on that layer's own scores (the state of
    the layer before included), and fresh tokens then load the experts far
    more evenly than with no bias."""
    key = jax.random.PRNGKey(7)
    raw = hybrid.init_params(CFG, key)
    balanced = hybrid.balance_router_biases(raw, CFG, jax.random.fold_in(key, 1))
    flat = tuple({**lp, "router_bias": jnp.zeros_like(lp["router_bias"])} for lp in raw["layers"])
    toks = jax.random.randint(jax.random.PRNGKey(9), (32, 64), 0, CFG.vocab_size)

    def worst_load(params):
        """max / mean of the experts' rows, the worst layer's."""
        x, rho, worst = params["embed"][toks], None, 0.0
        pos = jnp.broadcast_to(jnp.arange(64), (32, 64))
        valid, n_valid = jnp.ones((32, 64), bool), jnp.full((32,), 64)
        for lp, st in zip(params["layers"], hybrid.init_state(CFG, 32, 64)):
            x, _, _ = hybrid._mix(x, lp, st, "cca", pos, valid, n_valid, CFG, 64)
            h = hybrid.rms_norm(x, lp["mlp_norm"], CFG.norm_eps).reshape(-1, CFG.d_model)
            idx, _, _ = moe.route_mlp(h, lp, None if rho is None else rho.reshape(2048, -1), eps=CFG.norm_eps)
            load = np.bincount(np.asarray(idx).ravel(), minlength=CFG.n_experts)
            worst = max(worst, load.max() / load.mean())
            x, _, rho, _ = hybrid._mlp(x, lp, "experts", valid, CFG, None, rho)
        return worst

    without, with_bias = worst_load({**raw, "layers": flat}), worst_load(balanced)
    assert without > 1.6 and with_bias < 1.3, (without, with_bias)


def test_the_shares_of_the_experts_add_up_to_the_uncut_layer(params):
    """Each of two ranks' routed part (4 of 8 experts from its offset)
    summed is the uncut layer's output; program and reference alike."""
    lp = params["layers"][1]
    h = jax.random.normal(jax.random.PRNGKey(5), (2, 24, CFG.d_model))
    rho = jax.random.normal(jax.random.PRNGKey(6), (2, 24, CFG.router_hidden))
    valid = jnp.ones((2, 24), bool)
    whole, counters, out_rho = hybrid._expert_layer(h, lp, valid, CFG, None, rho)
    parts, ref_parts = [], []
    dims = ref._dims(CFG, None, None)
    for rank in range(2):
        cfg = dataclasses.replace(CFG, experts_held=4, expert_offset=4 * rank)
        share = {**lp, "w_gu_e": lp["w_gu_e"][4 * rank : 4 * rank + 4],
                 "w_down_e": lp["w_down_e"][4 * rank : 4 * rank + 4]}
        y, _, r = hybrid._expert_layer(h, share, valid, cfg, None, rho)
        parts.append(y)
        np.testing.assert_array_equal(r, out_rho)  # every rank computes the router alike
        ref_parts.append(ref.mlp(h[0], share, rho[0], {**dims, "held": 4, "offset": 4 * rank})[0])
    np.testing.assert_allclose(sum(parts), whole, atol=1e-5)
    uncut, _ = ref.mlp(h[0], lp, rho[0], dims)
    np.testing.assert_allclose(sum(ref_parts), uncut, atol=1e-5)
    np.testing.assert_allclose(whole[0], uncut, atol=1e-5)
    assert int(counters[0]) == 48 and int(counters[4]) == 1  # one choice a token, one call


def test_the_decode_chunk_exports_the_decode_only_expert_counters(params, tokens):
    """``moe_experts_touched_decode`` / ``moe_expert_layer_steps_decode``:
    filled by a decode chunk, zero from a prefill; the other families'
    servers name them too."""
    model = serving_model(CFG, None, T)
    names = model.counter_names
    assert names[: len(moe.COUNTERS) + 2] == tuple(f"moe_{n}" for n in moe.COUNTERS) + (
        "moe_experts_touched_decode", "moe_expert_layer_steps_decode")
    for preset in ("ling-tiny", "mellum-tiny", "exaone_moe-tiny", "mistral4-tiny"):
        other = HybridServing(hybrid.PRESETS[preset](), None, T).counter_names
        assert other[len(moe.COUNTERS) : len(moe.COUNTERS) + 2] == names[len(moe.COUNTERS) : len(moe.COUNTERS) + 2]
    state = model.init_state(2, T)
    for slot in (0, 1):
        state, _ = _pieces(model, params, state, tokens[slot], slot, (8, 8))
    chunk = model.make_decode_chunk()
    assert chunk.__name__ == "decode_chunk"  # what ``decode_step_dev_ms`` reads in the trace
    steps = 4
    state, toks, aux = chunk(
        params, state, jnp.asarray(tokens[:2, 16]), jnp.asarray([16, 16], jnp.int32),
        jax.random.PRNGKey(0), jnp.zeros((2,)), jnp.ones((2,)), jnp.zeros((2,), jnp.int32),
        steps, T, jnp.asarray([True, False]))
    c = dict(zip(names, np.asarray(aux).tolist()))
    assert toks.shape == (steps, 2)
    # One live row: one expert a layer a step.
    assert c["moe_expert_layer_steps_decode"] == c["moe_expert_layer_steps"] == 3 * steps
    assert c["moe_experts_touched_decode"] == c["moe_experts_touched"] == 3 * steps
    assert c["moe_choices_routed"] == 3 * steps
    assert c["attn_rows_dense_full_decode"] == 3 * steps * 2 * T and c["attn_rows_dense_full_prefill"] == 0
