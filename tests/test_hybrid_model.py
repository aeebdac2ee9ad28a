"""``models/hybrid.py`` (layer kinds) against its plain reference,
``models/hybrid_reference.py``, at a tiny size that keeps every ratio of
the benchmark's cut: a period of 6 after a dense first layer, 2 of 8
routing groups held, 4 kept.  Seeded random float32 weights; logits are
compared, never sampled tokens.

Tolerance: both sides are float32 at the highest matmul precision
(conftest.py), so they differ by the order of their sums — the chunk-wise
KDA form, the absorbed MLA form, the sorted dispatch.  Logits are O(4);
2e-4 absolute is about 50 float32 ulps of the largest, and a missing
term, a wrong mask or a stale state moves a logit by 1e-2 or more.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from generativeaiexamples_tpu.engine.serving_models import HybridServing, serving_model
from generativeaiexamples_tpu.models import hybrid
from generativeaiexamples_tpu.models import hybrid_reference as ref

ATOL = 2e-4
CFG = dataclasses.replace(hybrid.PRESETS["ling-tiny"](), expert_offset=8)
T = 128


@pytest.fixture(scope="module")
def params():
    return hybrid.init_params(CFG, jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def tokens():
    return np.random.RandomState(0).randint(0, CFG.vocab_size, size=(3, 96)).astype(np.int32)


@pytest.fixture(scope="module")
def want(params, tokens):
    """The reference's full forward over each whole row."""
    return [np.asarray(ref.all_logits(params, CFG, row)) for row in tokens]


def test_the_cut_keeps_each_layers_published_kind():
    kinds = hybrid.PRESETS["ling-3.0-flash-vl-l7e128"]().layer_kinds
    assert kinds == (
        ("kda", "dense"), ("kda", "experts"), ("kda", "experts"), ("kda", "experts"),
        ("mla", "experts"), ("kda", "experts"), ("kda", "experts"),
    )
    assert CFG.layer_kinds == kinds  # the tiny size keeps the pattern
    whole = hybrid.from_hf_config(hybrid.LING_FLASH_VL, max_len=8)
    assert len(whole.layers_of("kda")) == 35 and len(whole.layers_of("mla")) == 7
    assert [mlp for _, mlp in whole.layer_kinds].count("dense") == 2
    assert whole.n_experts == whole.experts_held == 512


def _forward(params, toks, start, n_valid, state, window):
    hidden, state, counters = jax.jit(
        lambda p, t, s, n, st: hybrid.forward(p, CFG, t, s, n, st, window=window)
    )(params, jnp.asarray(toks), jnp.asarray(start, jnp.int32), jnp.asarray(n_valid, jnp.int32), state)
    return np.asarray(hybrid.logits(params, CFG, hidden)), state, counters


def test_prefill_in_one_piece_matches_the_reference(params, tokens, want):
    got, _, _ = _forward(params, tokens[:1], [0], [96], hybrid.init_state(CFG, 1, T), T)
    np.testing.assert_allclose(got[0], want[0], atol=ATOL)


def test_chunked_prefill_then_decoding_matches_the_full_forward(params, tokens, want):
    """Three chunks (one of them short and padded), then 16 decode steps
    through the state, against the reference's forward over all 96."""
    state = hybrid.init_state(CFG, 1, T)
    got = []
    for start, n, bucket in ((0, 32, 32), (32, 32, 32), (64, 16, 32)):
        chunk = np.zeros((1, bucket), np.int32)
        chunk[0, :n] = tokens[0, start : start + n]
        lg, state, _ = _forward(params, chunk, [start], [n], state, T)
        got.append(lg[0, :n])
    for pos in range(80, 96):
        lg, state, _ = _forward(params, tokens[:1, pos : pos + 1], [pos], [1], state, T)
        got.append(lg[0])
    np.testing.assert_allclose(np.concatenate(got), want[0], atol=ATOL)


def test_batched_cold_admission_of_unequal_rows_matches_each_row_alone(params, tokens, want):
    lengths = [96, 41, 70]
    got, state, _ = _forward(params, tokens, [0, 0, 0], lengths, hybrid.init_state(CFG, 3, 96), 96)
    for r, n in enumerate(lengths):
        np.testing.assert_allclose(got[r, :n], want[r][:n], atol=ATOL)
    # ... and the state each row is left with is the state at its own
    # length: one more token decodes as the reference's next position.
    big = HybridServing(CFG, None, T).graft_rows(
        hybrid.init_state(CFG, 4, T), state, jnp.asarray([1, 2]), jnp.asarray([0, 3])
    )
    step = np.zeros((4, 1), np.int32)
    step[0, 0], step[3, 0] = tokens[1, 41], tokens[2, 70]
    lg, _, _ = _forward(params, step, [41, 0, 0, 70], [1, 0, 0, 1], big, T)
    np.testing.assert_allclose(lg[0, 0], want[1][41], atol=ATOL)
    np.testing.assert_allclose(lg[3, 0], want[2][70], atol=ATOL)


def test_a_parked_row_and_a_padded_position_leave_state_bit_equal(params, tokens):
    state = hybrid.init_state(CFG, 2, T)
    _, state, _ = _forward(params, tokens[:2, :48], [0, 0], [48, 48], state, T)
    before = jax.tree.map(np.asarray, state)
    # Row 1 does not decode (n_valid 0); row 0 does.
    _, after, counters = _forward(params, tokens[:2, 48:49], [48, 48], [1, 0], state, T)
    for b, a in zip(before, jax.tree.map(np.asarray, after)):
        for name in b:
            assert np.array_equal(b[name][1], a[name][1]), name
            assert not np.array_equal(b[name][0], a[name][0]), name
    assert int(counters[0]) == CFG.n_experts_per_tok * 6  # one token routed, six expert layers
    # A chunk's padding: positions that do not count leave every leaf of
    # the state as it was (two sub-chunks of nothing but padding) ...
    pad = np.zeros((1, 32), np.int32)
    pad[0, :16] = tokens[0, 48:64]
    row = jax.tree.map(lambda a: a[:1], after)
    _, untouched, _ = _forward(params, pad, [49], [0], row, T)
    for u, r in zip(untouched, row):
        for name in u:
            assert np.array_equal(np.asarray(u[name]), np.asarray(r[name])), name
    # ... and 16 real tokens in a bucket of 32 leave the state that the 16
    # alone leave (another compiled program, so equal to rounding).
    _, padded, _ = _forward(params, pad, [49], [16], row, T)
    _, exact, _ = _forward(params, pad[:, :16], [49], [16], row, T)
    for p, e in zip(padded, exact):
        for name in p:
            np.testing.assert_allclose(np.asarray(p[name]), np.asarray(e[name]), atol=1e-5)


def test_the_four_shares_add_up_to_the_uncut_reference(params):
    """The test that ties the share to the model: one expert layer, all 32
    experts in the uncut reference; each of four shares of 8 computes its
    part through the program's sorted dispatch; the shared expert is
    counted once."""
    whole = dataclasses.replace(CFG, experts_held=32, expert_offset=0)
    lp = dict(hybrid.init_params(
        dataclasses.replace(whole, layer_kinds=(("kda", "experts"),)), jax.random.PRNGKey(5)
    )["layers"][0])
    h = jnp.asarray(np.random.RandomState(2).randn(1, 40, CFG.d_model), jnp.float32)
    valid = jnp.ones((1, 40), bool)
    with jax.default_matmul_precision("highest"):
        uncut = ref.expert_layer(h[0], lp, ref._dims(whole))
    total = 0.0
    for share in range(4):
        cfg = dataclasses.replace(CFG, expert_offset=8 * share)
        mine = {**lp, "w_gu_e": lp["w_gu_e"][8 * share : 8 * share + 8],
                "w_down_e": lp["w_down_e"][8 * share : 8 * share + 8]}
        y, counters, _ = hybrid._expert_layer(h, mine, valid, cfg, None)
        shared = hybrid._swiglu(h[0], lp["w_gu_s"], lp["w_down_s"])
        total = total + (y[0] - shared)  # this share's routed part alone
        # The plain reference, given the same share, leaves out the same.
        with jax.default_matmul_precision("highest"):
            part = ref.expert_layer(h[0], mine, ref._dims(cfg), (8 * share, 8))
        np.testing.assert_allclose(y[0], part, atol=ATOL)
    np.testing.assert_allclose(total + shared, uncut, atol=ATOL)


def test_what_is_not_served_is_refused_with_the_reason():
    model = serving_model(CFG, None, T)
    assert isinstance(model, HybridServing) and not model.cut_anywhere
    model.check_supported()
    drafting = serving_model(CFG, None, T)
    drafting.draft = "mtp"  # a prediction module over KDA state, were one held
    with pytest.raises(ValueError, match="rolled back"):
        drafting.check_supported()
    with pytest.raises(ValueError, match="QUANT_TARGETS"):
        model.prepare_params(None, quantize=True, matmul_kernel="xla", seed=0)
    with pytest.raises(ValueError, match="QUANT_TARGETS"):
        model.prepare_params(None, quantize=False, matmul_kernel="pallas_w8a8", seed=0)
    from generativeaiexamples_tpu.engine.lora import LoRAConfig, init_lora_params

    with pytest.raises(ValueError, match="LoRA is not served"):
        init_lora_params(CFG, LoRAConfig(), jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="int8 state"):
        serving_model(dataclasses.replace(CFG, kv_dtype="int8"), None, T).check_supported()


def test_snapshot_bytes_count_the_recurrent_state():
    real = hybrid.PRESETS["ling-3.0-flash-vl-l7e128"]()
    # 6 KDA layers x (32 x 128 x 128 float32 + 3 x 12288 bf16 tail inputs)
    assert real.snapshot_bytes() == 6 * (32 * 128 * 128 * 4 + 3 * 12288 * 2)
    state = hybrid.init_state(CFG, 2, T)
    snap = HybridServing(CFG, None, T).save_state(state, 1)
    assert sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(snap)) == CFG.snapshot_bytes()
