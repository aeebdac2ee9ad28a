"""``models/hybrid.py``'s ``full`` and ``window`` layer kinds (the
``mellum`` family) against the plain reference,
``models/mellum_reference.py``, at a tiny size that keeps the ratios of
the benchmark's cut: a period of four (window, window, window, full), two
periods deep, a window of 16 under prompts of 3-5 windows, YaRN past its
original context of 32, 3 of 8 softmax-routed experts a token.  Seeded
random float32 weights; logits are compared, never sampled tokens.

Tolerance: both sides are float32 at the highest matmul precision
(conftest.py) and differ by the order of their sums (the ring's two-part
softmax, the sorted dispatch).  Logits are O(4); 2e-4 absolute is about 50
float32 ulps of the largest, and each mechanism switched off (case e)
moves a logit by 1e-2 or more.
"""

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from generativeaiexamples_tpu.engine.serving_models import HybridServing, serving_model
from generativeaiexamples_tpu.models import hybrid
from generativeaiexamples_tpu.models import mellum_reference as ref
from generativeaiexamples_tpu.ops import rope

ATOL = 2e-4
CFG = hybrid.PRESETS["mellum-tiny"]()
W = CFG.sliding_window  # 16
T = 128


@pytest.fixture(scope="module")
def params():
    return hybrid.init_params(CFG, jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def tokens():
    return np.random.RandomState(0).randint(0, CFG.vocab_size, size=(3, 80)).astype(np.int32)


@pytest.fixture(scope="module")
def want(params, tokens):
    """The reference's full forward over each whole row (5 windows)."""
    return [np.asarray(ref.all_logits(params, CFG, row)) for row in tokens]


@functools.lru_cache(maxsize=None)
def _program(cfg, window):
    """One compiled program a configuration, window and shape."""
    return jax.jit(lambda p, t, s, n, st: hybrid.forward(p, cfg, t, s, n, st, window=window))


def _forward(params, toks, start, n_valid, state, window, cfg=CFG):
    hidden, state, counters = _program(cfg, window)(
        params, jnp.asarray(toks), jnp.asarray(start, jnp.int32), jnp.asarray(n_valid, jnp.int32), state)
    return np.asarray(hybrid.logits(params, cfg, hidden)), state, counters


def _equal(a, b):
    return all(
        np.array_equal(np.asarray(x[n]), np.asarray(y[n])) for x, y in zip(a, b) for n in x
    )


def test_the_published_keys_give_the_published_layer_kinds():
    whole = hybrid.from_hf_config(hybrid.MELLUM2_12B, max_len=64)
    assert whole.layer_kinds == ((("window", "experts"),) * 3 + (("full", "experts"),)) * 7
    cut = hybrid.PRESETS["mellum2-12b-a2.5b-l12"]()
    assert cut.layer_kinds == whole.layer_kinds[:12]
    assert (cut.d_model, cut.n_heads, cut.n_kv_heads, cut.attn_head_dim) == (2304, 32, 4, 128)
    assert (cut.n_experts, cut.experts_held, cut.n_experts_per_tok, cut.moe_d_ff) == (64, 64, 8, 896)
    assert (cut.sliding_window, cut.vocab_size, cut.max_seq_len) == (1024, 98304, 8192)
    assert cut.score_function == "softmax" and not cut.router_bias and cut.norm_topk
    assert cut.rope_full.rope_type == "yarn" and cut.rope_window.rope_type == "default"
    assert CFG.layer_kinds == whole.layer_kinds[:8]  # the tiny size keeps the pattern
    layer = hybrid.init_params(CFG, jax.random.PRNGKey(1))["layers"][0]
    assert "router_bias" not in layer and "w_gu_s" not in layer  # no bias, no shared expert
    with pytest.raises(ValueError, match="'dense'.*not served"):
        hybrid.from_hf_config(
            {**hybrid.MELLUM2_12B, "mlp_layer_types": ["dense"] + ["sparse"] * 27}, max_len=64)
    bad = {**hybrid.MELLUM2_12B, "rope_parameters": {
        **hybrid.MELLUM2_12B["rope_parameters"],
        "sliding_attention": {"rope_type": "llama3", "rope_theta": 500000}}}
    with pytest.raises(ValueError, match="rope_type 'llama3' is not served"):
        hybrid.from_hf_config(bad, max_len=64)


def test_a_whole_sequence_of_five_windows_matches_the_reference(params, tokens, want):
    """(a) One call longer than the ring: 80 positions, a window of 16."""
    for n in (48, 80):  # 3 and 5 windows
        got, _, counters = _forward(params, tokens[:1, :n], [0], [n], hybrid.init_state(CFG, 1, T), T)
        np.testing.assert_allclose(got[0], want[0][:n], atol=ATOL)
    # Rows of K read from the state: 6 window layers x their ring, 2 full
    # layers x the window asked for; the window layers as full ones; and
    # the full layers with every window read whole (as XLA's path does).
    assert counters.tolist()[len(hybrid.moe.COUNTERS):] == [6 * W, 2 * T, 6 * T, 2 * T]


def test_chunks_that_do_not_divide_the_window_then_two_windows_of_decode(params, tokens, want):
    """(b) Chunks of 7, 13, 11, 9 and 8 tokens in buckets of 16, then 32
    decode steps through the ring, against the full forward at every
    position."""
    state = hybrid.init_state(CFG, 1, T)
    got, start = [], 0
    for n in (7, 13, 11, 9, 8):
        chunk = np.zeros((1, 16), np.int32)
        chunk[0, :n] = tokens[0, start : start + n]
        lg, state, _ = _forward(params, chunk, [start], [n], state, 64)
        got.append(lg[0, :n])
        start += n
    for pos in range(start, 80):
        lg, state, _ = _forward(params, tokens[:1, pos : pos + 1], [pos], [1], state, T)
        got.append(lg[0])
    np.testing.assert_allclose(np.concatenate(got), want[0], atol=ATOL)


def test_a_restored_ring_and_grafted_rows_equal_a_cold_run_bit_for_bit(params, tokens, want):
    """(c) Slot 0 prefills 48 tokens in chunks of 16 and its state is saved
    at the boundary 32; slot 2 takes the full layers' first rows by
    ``graft_prefix`` and the rings from the snapshot, then runs the same
    third chunk: its state and logits equal slot 0's to the bit."""
    model = HybridServing(CFG, None, T)
    state = model.init_state(3, T)
    prefill = jax.jit(model.prefill_row, static_argnums=(6,))

    def chunk(state, slot, start):
        toks = jnp.asarray(tokens[:1, start : start + 16])
        state, hidden, _ = prefill(params, state, toks, jnp.int32(start), jnp.int32(16), jnp.int32(slot), 64)
        return state, np.asarray(model.logits(params, hidden))[0]

    state, _ = chunk(state, 0, 0)
    state, _ = chunk(state, 0, 16)
    snap = model.save_state(state, 0)
    assert len(snap) == 6 and set(snap[0]) == {"ring_k", "ring_v"}  # the window layers alone
    state, cold = chunk(state, 0, 32)
    np.testing.assert_allclose(cold, want[0][32:48], atol=ATOL)
    state = model.graft_prefix(state, 0, 2, 32)
    state = model.restore_state(state, 2, snap)
    state, warm = chunk(state, 2, 32)
    assert np.array_equal(cold, warm)
    for layer in jax.tree.map(np.asarray, state):
        for name, leaf in layer.items():
            upto = 48 if name in hybrid.ROW_LEAVES else leaf.shape[1]
            assert np.array_equal(leaf[0, :upto], leaf[2, :upto]), name
    # Without the snapshot the rings are another occupant's: not the same.
    state = model.graft_prefix(state, 0, 1, 32)
    _, stale = chunk(state, 1, 32)
    assert np.abs(stale - cold).max() > 1e-2


def test_a_padded_token_and_a_row_that_does_not_decode_leave_both_kinds_untouched(params, tokens):
    """(d)"""
    state = hybrid.init_state(CFG, 2, T)
    _, state, _ = _forward(params, tokens[:2, :40], [0, 0], [40, 40], state, T)
    before = jax.tree.map(np.asarray, state)
    _, after, _ = _forward(params, tokens[:2, 40:41], [40, 40], [1, 0], state, T)
    for b, a in zip(before, jax.tree.map(np.asarray, after)):
        for name in b:
            assert np.array_equal(b[name][1], a[name][1]), name
            assert not np.array_equal(b[name][0], a[name][0]), name
    pad = np.zeros((1, 32), np.int32)
    pad[0, :16] = tokens[0, 41:57]
    row = jax.tree.map(lambda a: a[:1], after)
    _, untouched, _ = _forward(params, pad, [41], [0], row, T)
    assert _equal(untouched, row)
    # 16 real tokens in a bucket of 32 leave what the 16 alone leave.
    _, padded, _ = _forward(params, pad, [41], [16], row, T)
    _, exact, _ = _forward(params, pad[:, :16], [41], [16], row, T)
    for p, e in zip(padded, exact):
        for name in p:
            np.testing.assert_allclose(np.asarray(p[name]), np.asarray(e[name]), atol=1e-5)


CONTROLS = {
    "no_window": dict(sliding_window=T),
    "no_yarn": dict(rope_full=dataclasses.replace(CFG.rope_window)),
    "top_k_not_renormalised": dict(norm_topk=False),
    "sigmoid_for_softmax": dict(score_function="sigmoid"),
}


@pytest.mark.parametrize("control", sorted(CONTROLS))
def test_each_mechanism_switched_off_fails_the_whole_sequence_comparison(control, params, tokens, want):
    """(e) The comparison of (a) sees each mechanism."""
    cfg = dataclasses.replace(CFG, **CONTROLS[control])
    got, _, _ = _forward(params, tokens[:1], [0], [80], hybrid.init_state(cfg, 1, T), T, cfg)
    assert np.abs(got[0] - want[0]).max() > 1e-2
    if control == "no_window":  # the first window's positions see the same keys
        np.testing.assert_allclose(got[0, :W], want[0][:W], atol=ATOL)


def test_yarn_frequencies_and_factor_at_the_published_parameters():
    """(f) theta 500000, factor 16, original 8192, beta 32 / 1, head 128:
    dim(r) = 128 ln(8192 / (2 pi r)) / (2 ln 500000) is 18.08 at r = 32 and
    34.98 at r = 1, so pairs 0-18 keep theta^(-2i/128), pairs 35-63 are
    divided by 16, and pair i between is weighted (i - 18) / 17."""
    spec = hybrid.PRESETS["mellum2-12b-a2.5b-l12"]().rope_full
    assert math.isclose(spec.attention_factor, 1.2772588722239782)
    assert math.isclose(spec.attention_factor, 0.1 * math.log(16) + 1, rel_tol=1e-12)
    inv = rope.spec_frequencies(spec, 128)
    assert inv.shape == (64,) and inv.dtype == np.float32
    by_hand = {
        0: 1.0,
        10: 0.12868737,  # 500000^(-20/128)
        18: 0.024955409,  # the ramp starts: still plain
        26: 0.0048394213 * (1 - 8 / 17) + 0.0048394213 / 16 * 8 / 17,
        35: 0.00076449699 / 16,  # the ramp has ended
        63: 2.4551408e-06 / 16,
    }
    for i, value in by_hand.items():
        assert math.isclose(float(inv[i]), value, rel_tol=1e-5), (i, float(inv[i]), value)
    plain = rope.spec_frequencies(hybrid.PRESETS["mellum2-12b-a2.5b-l12"]().rope_window, 128)
    np.testing.assert_allclose(plain, 500000.0 ** (-np.arange(64) / 64.0), rtol=1e-6)
    np.testing.assert_allclose(inv, ref.inv_freq(ref._rope_tuple(spec), 128), rtol=1e-6)
    # cos and sin carry the factor: position 0 is the vector times 1.277.
    x = jnp.ones((1, 1, 1, 128), jnp.float32)
    np.testing.assert_allclose(
        rope.apply_rope_spec(x, jnp.zeros((1, 1), jnp.int32), spec), 1.2772588722239782 * x, rtol=1e-6)


@pytest.mark.parametrize("max_len", [64, 2048, 8192])
def test_a_window_layers_state_does_not_grow_with_max_len(max_len):
    """(g) At the published sizes: a ring of at most sliding_window (+ one
    prefill chunk) rows at any max_len; the full layers' rows do grow."""
    cfg = hybrid.PRESETS["mellum2-12b-a2.5b-l12"]()
    shapes = jax.eval_shape(lambda: hybrid.init_state(cfg, 2, max_len))
    for (mixer, _), layer in zip(cfg.layer_kinds, shapes):
        rows = {leaf.shape[1] for leaf in layer.values()}
        assert {leaf.shape[2] for leaf in layer.values()} == {4 * 128}
        assert rows == ({max_len} if mixer == "full" else {min(1024, max_len)})
        assert max(rows) <= 1024 + 256 or mixer == "full"
    by_kind = hybrid.state_bytes(cfg, 32, max_len)
    row = 2 * 4 * 128 * 2  # K and V, 4 heads of 128, bf16
    assert by_kind == {"full": 3 * 32 * max_len * row, "window": 9 * 32 * min(1024, max_len) * row,
                       "recurrent": 0}
    assert cfg.snapshot_bytes(max_len) == 9 * min(1024, max_len) * row
    if max_len >= 1024:
        assert by_kind["window"] == 603_979_776 and cfg.snapshot_bytes(max_len) == 18_874_368


def test_what_is_not_served_is_refused_with_the_reason():
    model = serving_model(CFG, None, T)
    assert isinstance(model, HybridServing) and not model.cut_anywhere
    model.check_supported()
    with pytest.raises(ValueError, match="fused GQA projections"):
        model.prepare_params(None, quantize=True, matmul_kernel="xla", seed=0)
    with pytest.raises(ValueError, match="int8 state"):
        serving_model(dataclasses.replace(CFG, kv_dtype="int8"), None, T).check_supported()
    from generativeaiexamples_tpu.engine.lora import LoRAConfig, init_lora_params

    with pytest.raises(ValueError, match="LoRA is not served"):
        init_lora_params(CFG, LoRAConfig(), jax.random.PRNGKey(0))
    # No selection bias to balance: the parameters come back as they were.
    p = model.prepare_params(None, quantize=False, matmul_kernel="xla", seed=0)
    assert hybrid.balance_router_biases(p, CFG, jax.random.PRNGKey(1)) is p
    assert model.counter_names[-8:] == (
        "attn_rows_read_window_decode", "attn_rows_read_full_decode",
        "attn_rows_dense_window_decode", "attn_rows_dense_full_decode",
        "attn_rows_read_window_prefill", "attn_rows_read_full_prefill",
        "attn_rows_dense_window_prefill", "attn_rows_dense_full_prefill",
    )
