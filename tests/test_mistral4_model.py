"""``models/hybrid.py``'s ``mla`` kind in the ``mistral4`` family's form
(a low-rank query, YaRN on the latent's rotary part, a query scale that
grows with the position, no output gate, prefill in blocks over the cached
latent rows) against the plain reference, ``models/mistral4_reference.py``,
at a tiny size that keeps the ratios of the benchmark's cut: every layer
latent attention with experts and a shared one, 8 of 32 experts held and 2
a token, a query rank under the hidden size, and an original context of 32
under prompts of 80, so that ``a(p)`` takes three values and YaRN's ramp
is crossed.  Seeded random float32 weights; logits are compared, never
sampled tokens.

Tolerance: both sides are float32 at the highest matmul precision
(conftest.py) and differ by the order of their sums (the online softmax
over blocks, the absorbed products, the sorted dispatch).  Logits are
O(4); 2e-4 absolute is about 50 float32 ulps of the largest, and each
mechanism switched off (the controls below) moves a logit by 1e-2 or more.
"""

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from generativeaiexamples_tpu.engine.serving_models import serving_model
from generativeaiexamples_tpu.models import hybrid
from generativeaiexamples_tpu.models import mistral4_reference as ref
from generativeaiexamples_tpu.ops import rope
from generativeaiexamples_tpu.ops.rope import RopeSpec

ATOL = 2e-4
CFG = hybrid.PRESETS["mistral4-tiny"]()
T = 128
ORIGINAL = CFG.rope_latent.original_max  # 32


@pytest.fixture(scope="module")
def params():
    return hybrid.init_params(CFG, jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def tokens():
    return np.random.RandomState(0).randint(0, CFG.vocab_size, size=(3, 80)).astype(np.int32)


@pytest.fixture(scope="module")
def want(params, tokens):
    """The reference's full forward over each whole row."""
    return [np.asarray(ref.all_logits(params, CFG, row)) for row in tokens]


@functools.lru_cache(maxsize=None)
def _program(cfg, window):
    return jax.jit(lambda p, t, s, n, st: hybrid.forward(p, cfg, t, s, n, st, window=window))


def _forward(params, toks, start, n_valid, state, window, cfg=CFG):
    hidden, state, counters = _program(cfg, window)(
        params, jnp.asarray(toks), jnp.asarray(start, jnp.int32), jnp.asarray(n_valid, jnp.int32), state)
    return np.asarray(hybrid.logits(params, cfg, hidden)), state, counters


def test_the_published_keys_give_the_published_model():
    whole = hybrid.from_hf_config(hybrid.MISTRAL_SMALL_4, max_len=64)
    assert isinstance(whole, hybrid.LatentConfig)
    assert whole.layer_kinds == (("mla", "experts"),) * 36
    assert (whole.n_experts, whole.experts_held, whole.vocab_size) == (128, 128, 131072)
    cut = hybrid.PRESETS["mistral-small-4-119b-l6e32"]()
    assert cut.layer_kinds == whole.layer_kinds[:6]
    assert (cut.d_model, cut.n_heads, cut.q_lora_rank, cut.kv_lora_rank) == (4096, 32, 1024, 256)
    assert (cut.qk_nope_head_dim, cut.qk_rope_head_dim, cut.v_head_dim) == (64, 64, 128)
    assert (cut.n_experts, cut.experts_held, cut.n_experts_per_tok) == (128, 32, 4)
    assert (cut.moe_d_ff, cut.shared_d_ff, cut.vocab_size, cut.max_seq_len) == (2048, 2048, 32768, 32768)
    assert cut.score_function == "softmax" and not cut.router_bias and cut.norm_topk
    assert (cut.n_group, cut.topk_group, cut.routed_scaling) == (1, 1, 1.0)
    spec = cut.rope_latent
    assert (spec.rope_type, spec.theta, spec.factor, spec.original_max) == ("yarn", 10000.0, 128.0, 8192)
    # mscale / mscale_all_dim = 1: the rotation itself is not scaled; the
    # softmax scale carries m^2, m = 0.1 ln 128 + 1.
    assert spec.attention_factor == 1.0
    assert cut.softmax_mscale == pytest.approx(0.1 * math.log(128) + 1) == pytest.approx(1.4852, abs=1e-4)
    assert cut.attn_scale_beta == 0.1 and not cut.mla_out_gate
    assert (cut.latent_block, cut.latent_decode_block) == (1024, 2048)
    # A state of rows alone: a hit is cut at any row, and a snapshot holds nothing.
    assert cut.rows_only and cut.snapshot_bytes() == 0 and cut.draft == ""
    assert cut.row_counters == hybrid.LATENT_COUNTERS and cut.n_counters == len(hybrid.moe.COUNTERS) + 3
    # 320 values a token a layer, stored in rows of whole lanes.
    assert cut.kv_lora_rank + cut.qk_rope_head_dim == 320 and cut.latent_width == 384
    assert CFG.layer_kinds == whole.layer_kinds[:3]  # the tiny size keeps the pattern


def test_the_cut_holds_the_bytes_the_issue_counts():
    cut = hybrid.PRESETS["mistral-small-4-119b-l6e32"]()
    shapes = jax.eval_shape(lambda: hybrid.init_params(cut, jax.random.PRNGKey(0)))
    weights = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(shapes))
    assert weights == pytest.approx(10.85e9, rel=0.01)
    layer = shapes["layers"][0]
    attention = sum(layer[n].size for n in ("w_qa", "w_qb", "w_kva", "w_kvb", "w_o"))
    assert attention == 4096 * 1024 + 1024 * 4096 + 4096 * 320 + 256 * 6144 + 4096 * 4096
    assert "w_gate" not in layer and "w_q" not in layer and "router_bias" not in layer
    state = hybrid.state_bytes(cut, 16, 32768)
    assert state == {"full": 16 * 32768 * 384 * 2 * 6, "window": 0, "recurrent": 0}
    # Of which the latent and the rope key are 640 B a token a layer: 2.01 GB.
    assert state["full"] * 320 // 384 == 16 * 32768 * 640 * 6 == 2_013_265_920


@pytest.mark.parametrize("bad, match", [
    ({"first_k_dense_replace": 1}, "leading dense layer"),
    ({"scoring_func": "sigmoid"}, "softmax"),
    ({"n_group": 2}, "routing groups"),
    ({"q_lora_rank": None}, "low-rank query"),
    ({"rope_interleave": False}, "interleaved"),
    ({"sliding_window": 4096}, "sliding window"),
    ({"rope_parameters": {"rope_type": "default", "rope_theta": 10000}}, "YaRN"),
])
def test_what_the_family_does_not_serve_is_refused_with_the_reason(bad, match):
    with pytest.raises(ValueError, match=match):
        hybrid.from_hf_config({**hybrid.MISTRAL4_TINY, **bad}, max_len=64)


@pytest.mark.parametrize("field", ["latent_block", "latent_decode_block"])
def test_a_latent_config_attends_in_blocks_or_not_at_all(field):
    with pytest.raises(ValueError, match="latent_block"):
        dataclasses.replace(CFG, **{field: 0})


def test_yarn_frequencies_of_the_program_and_of_the_reference_agree():
    """``ops/rope.py``'s (used by the GQA kinds and the latent path alike)
    against the reference's own, written from the description; at the
    published sizes the ramp runs from pair 12 to pair 25 of 32."""
    for spec, d in ((CFG.rope_latent, 8), (hybrid.PRESETS["mistral-small-4-119b-l6e32"]().rope_latent, 64)):
        mine = rope.spec_frequencies(spec, d)
        theirs = ref.yarn_frequencies(d, spec.theta, spec.factor, spec.original_max, spec.beta_fast, spec.beta_slow)
        np.testing.assert_allclose(mine, theirs, rtol=1e-6)
    plain = 10000.0 ** (-np.arange(0, 64, 2) / 64)
    np.testing.assert_allclose(theirs[:13], plain[:13], rtol=1e-6)
    np.testing.assert_allclose(theirs[25:], plain[25:] / 128, rtol=1e-6)
    assert np.all(theirs[13:25] < plain[13:25]) and np.all(theirs[13:25] > plain[13:25] / 128)


def test_cold_forward_matches_the_reference_on_both_sides_of_the_original_context(params, tokens, want):
    lengths = np.array([80, 61, 33], np.int32)
    got, state, counters = _forward(
        params, tokens, np.zeros(3), lengths, hybrid.init_state(CFG, 3, T), T)
    for row, n in enumerate(lengths):
        np.testing.assert_allclose(got[row, :n], want[row][:n], atol=ATOL)
        assert n > ORIGINAL  # a(p) and YaRN's ramp both matter in every row
    # Rows read: each row's own whole blocks of 16 up to its length (a row
    # attends alone, in a cold batch as in a chunk program), in each of
    # three layers; dense: three windows a layer.
    assert list(np.asarray(counters)[-3:]) == [3 * (80 + 64 + 48), 3 * 3 * T, 0]
    # A padded position wrote nothing: the rows past a row's length are zero.
    for layer in state:
        lat = np.asarray(layer["latent"])
        assert lat.shape == (3, T, CFG.latent_width) and not lat[2, 33:].any() and lat[2, :33, :24].all()
        assert not lat[..., 24:].any()  # the columns that fill a row up to whole lanes


@pytest.mark.parametrize("start", [0, 23])
def test_chunked_prefill_then_decode_through_the_cache_matches_the_reference(params, tokens, want, start):
    """Chunks of 16 (the last padded) through the serving model's
    ``prefill_row`` over blocks of 16 rows, smaller than the window of
    128; then one token a step through ``decode_step`` (the absorbed
    form, a row at a time over the blocks the row holds).  ``start`` 23: the first chunk starts at no multiple of the
    chunk, as after a prefix hit cut at row 23 (the rows before it come
    from a cold prefill of the same tokens)."""
    model = serving_model(CFG, None, T)
    row, n, n_prefill = tokens[0], 80, 70
    state = model.init_state(2, T)
    chunk = jax.jit(model.prefill_row, static_argnums=(6,))
    step = jax.jit(model.decode_step, static_argnums=(5,))
    if start:
        head = np.zeros((1, 32), np.int32)
        head[0, :start] = row[:start]
        state, _, _ = chunk(params, state, jnp.asarray(head), jnp.int32(0), jnp.int32(start), jnp.int32(1), T)
    for at in range(start, n_prefill, 16):
        count = min(16, n_prefill - at)
        piece = np.zeros((1, 16), np.int32)
        piece[0, :count] = row[at : at + count]
        state, hidden, _ = chunk(params, state, jnp.asarray(piece), jnp.int32(at), jnp.int32(count), jnp.int32(1), T)
        got = np.asarray(model.logits(params, hidden))[0, :count]
        np.testing.assert_allclose(got, want[0][at : at + count], atol=ATOL)
    assert not any(np.asarray(layer["latent"])[0].any() for layer in state)  # slot 0 untouched
    for pos in range(n_prefill, n):
        state, logits, counters = step(
            params, state, jnp.asarray([0, row[pos]]), jnp.asarray([0, pos]), jnp.asarray([0, 1]), T)
        np.testing.assert_allclose(np.asarray(logits)[1], want[0][pos], atol=ATOL)
    # The decode step walked the one decoding row's whole blocks of 16 up
    # to its 80 rows in each layer; the other slot read nothing.
    assert list(np.asarray(counters)[-3:]) == [3 * 80, 3 * 2 * T, 0]


def test_the_chunks_of_several_slots_read_their_rows_in_place(params, tokens, want):
    """``prefill_rows`` (the scheduler's chunk program) over three slots at
    once, one of them padding: the state is handed whole, each row's
    blocks are read from its slot and its new rows written there; the
    logits are the reference's and the other slots' rows stay as they
    were."""
    model = serving_model(CFG, None, T)
    assert model.cut_anywhere and model.chunk_windows(16) == (T,)
    program = jax.jit(model.prefill_rows, static_argnums=(6,))
    state = model.init_state(4, T)
    slots, rows = np.array([2, 0, 3], np.int32), (0, 1)  # the third row is padding
    marker = jnp.full_like(state[0]["latent"][3], 7.0)
    state = tuple({"latent": layer["latent"].at[3].set(marker)} for layer in state)
    read = []
    for at in range(0, 48, 16):
        toks = np.zeros((3, 16), np.int32)
        for r in rows:
            toks[r] = tokens[r, at : at + 16]
        state, hidden, counters = program(
            params, state, jnp.asarray(toks), jnp.asarray([at, at, 5], jnp.int32),
            jnp.asarray([16, 16, 0], jnp.int32), jnp.asarray(slots), T)
        got = np.asarray(model.logits(params, hidden))
        for r in rows:
            np.testing.assert_allclose(got[r], want[r][at : at + 16], atol=ATOL)
        read.append(list(np.asarray(counters)[-3:]))
    # Two live rows, whole blocks of 16 up to their length, three layers;
    # the dense count takes all three rows of the program's window.
    assert read == [[3 * 2 * (at + 16), 3 * 3 * T, 0] for at in range(0, 48, 16)]  # float32: no kernel
    for layer in state:
        lat = np.asarray(layer["latent"])
        assert (lat[3] == 7.0).all() and not lat[1].any()  # the pad row's slot, a slot not named
        assert lat[2, :48, :24].all() and not lat[2, 48:].any()


def test_absorbed_rows_and_blocks_agree_with_ling_s_forms_on_unpadded_rows():
    """The three forms of this family against the two of Ling's on the
    same rows: ``attend_blocks`` (blocks of 16 of a window of 64, two
    rows of unequal length) and ``attend_absorbed_blocks`` against
    ``attend_expanded`` and ``attend_absorbed``."""
    from generativeaiexamples_tpu.ops import mla

    rank, nope, rp, vd, H, s, Tw = 16, 8, 8, 16, 4, 16, 64
    keys = jax.random.split(jax.random.PRNGKey(3), 5)
    q_nope = jax.random.normal(keys[0], (2, s, H, nope))
    q_rope = jax.random.normal(keys[1], (2, s, H, rp))
    latent = jax.random.normal(keys[2], (2, Tw, rank + rp))
    w_kvb = jax.random.normal(keys[3], (rank, H * (nope + vd))) * rank**-0.5
    starts = jnp.asarray([40, 7])
    q_pos = starts[:, None] + jnp.arange(s)[None, :]
    sizes = dict(rank=rank, nope=nope, v_dim=vd, scale=0.3)
    want = mla.attend_expanded(q_nope, q_rope, latent, w_kvb, q_pos, **sizes)
    got = mla.attend_blocks(q_nope, q_rope, latent, w_kvb, q_pos, starts + s, block=16, **sizes)
    np.testing.assert_allclose(got, want, atol=1e-5)
    # Rows wider than the latent and the rope key (zero columns): the same.
    wide = jnp.concatenate([latent, jnp.zeros((2, Tw, 104))], axis=-1)
    got = mla.attend_blocks(q_nope, q_rope, wide, w_kvb, q_pos, starts + s, block=16, **sizes)
    np.testing.assert_allclose(got, want, atol=1e-5)
    # One row of a state of many slots, read in place.
    got = mla.attend_blocks(
        q_nope[1:], q_rope[1:], wide, w_kvb, q_pos[1:], starts[1:] + s, block=16,
        slot=jnp.asarray([1]), window=48, **sizes)
    np.testing.assert_allclose(got, want[1:], atol=1e-5)
    one = slice(0, 1)  # a decode step: one query a row
    want1 = mla.attend_absorbed(q_nope[:, one], q_rope[:, one], latent, w_kvb, q_pos[:, one], **sizes)
    np.testing.assert_allclose(want1, want[:, one], atol=1e-5)
    for rows in (latent, wide):
        got1 = mla.attend_absorbed_blocks(
            q_nope[:, one], q_rope[:, one], rows, w_kvb, q_pos[:, one], starts + 1, block=16, **sizes)
        np.testing.assert_allclose(got1, want1, atol=1e-5)
    assert list(np.asarray(mla.rows_in_blocks(jnp.asarray([0, 1, 16, 17, 64, 90]), 64, 16))) == [0, 16, 16, 32, 64, 64]


def test_the_four_shares_of_the_experts_add_up_to_the_uncut_layer(params):
    """Each rank's routed part (8 of 32 experts from its offset) summed
    over the four ranks, with the shared expert counted once, is the uncut
    layer's output; program and reference alike."""
    lp = params["layers"][0]
    h = jax.random.normal(jax.random.PRNGKey(5), (2, 24, CFG.d_model))
    valid = jnp.ones((2, 24), bool)
    whole_cfg = dataclasses.replace(CFG, experts_held=32)
    rng = jax.random.PRNGKey(6)
    all_gu = jax.random.normal(rng, (32, CFG.d_model, 2 * CFG.moe_d_ff)) * CFG.d_model**-0.5
    all_down = jax.random.normal(jax.random.fold_in(rng, 1), (32, CFG.moe_d_ff, CFG.d_model)) * CFG.moe_d_ff**-0.5
    shared = hybrid._swiglu(h.reshape(-1, CFG.d_model), lp["w_gu_s"], lp["w_down_s"]).reshape(h.shape)
    whole, _, _ = hybrid._expert_layer(h, {**lp, "w_gu_e": all_gu, "w_down_e": all_down}, valid, whole_cfg, None)
    parts, ref_parts = [], []
    dims = ref._dims(CFG, None, None)
    for rank in range(4):
        cfg = dataclasses.replace(CFG, expert_offset=8 * rank)
        share = {**lp, "w_gu_e": all_gu[8 * rank : 8 * rank + 8], "w_down_e": all_down[8 * rank : 8 * rank + 8]}
        y, counters, _ = hybrid._expert_layer(h, share, valid, cfg, None)
        parts.append(y - shared)
        ref_parts.append(ref.routed_experts(h[0], share, {**dims, "offset": 8 * rank}))
    np.testing.assert_allclose(sum(parts) + shared, whole, atol=1e-5)
    uncut = ref.routed_experts(h[0], {**lp, "w_gu_e": all_gu, "w_down_e": all_down}, {**dims, "held": 32, "offset": 0})
    np.testing.assert_allclose(sum(ref_parts), uncut, atol=1e-5)
    np.testing.assert_allclose((whole - shared)[0], uncut, atol=1e-5)
    # Every token chose 2 of the 32: the four shares saw all of them between them.
    assert int(counters[0]) == 2 * 48


@pytest.mark.parametrize("control, served", [
    ("no_attn_scale", lambda c: dataclasses.replace(c, attn_scale_beta=0.0)),
    ("plain_rope", lambda c: dataclasses.replace(
        c, rope_latent=RopeSpec(theta=c.rope_latent.theta, original_max=ORIGINAL))),
    ("no_mscale", lambda c: dataclasses.replace(c, softmax_mscale=1.0)),
])
def test_each_mechanism_switched_off_leaves_the_reference(params, tokens, want, control, served):
    got, _, _ = _forward(
        params, tokens[:1], np.zeros(1), np.array([80]), hybrid.init_state(CFG, 1, T), T, cfg=served(CFG))
    worst = np.abs(got[0] - want[0]).max(-1)
    assert worst[ORIGINAL:].max() > 1e-2
    if control == "no_attn_scale":  # a(p) is 1 below the original context
        assert worst[:ORIGINAL].max() < ATOL


def test_the_query_scale_steps_at_each_multiple_of_the_original_context():
    from generativeaiexamples_tpu.ops import mla

    a = np.asarray(mla.position_scale(jnp.asarray([0, 8191, 8192, 16383, 16384, 24576]), 0.1, 8192))
    np.testing.assert_allclose(a, [1, 1, 1 + 0.1 * math.log(2), 1 + 0.1 * math.log(2),
                                   1 + 0.1 * math.log(3), 1 + 0.1 * math.log(4)], rtol=1e-6)
    np.testing.assert_allclose(a[[2, 4, 5]], [1.0693, 1.1099, 1.1386], atol=1e-4)


def test_rope_spec_reads_the_latent_lineage_s_mscale_keys():
    section = hybrid.MISTRAL_SMALL_4["rope_parameters"]
    assert rope.rope_spec(section).attention_factor == 1.0
    assert rope.rope_spec({**section, "mscale_all_dim": 0.5}).attention_factor == pytest.approx(
        (0.1 * math.log(128) + 1) / (0.05 * math.log(128) + 1))
    # Without the pair, transformers' default; with its own key, that.
    bare = {k: v for k, v in section.items() if not k.startswith("mscale")}
    assert rope.rope_spec(bare).attention_factor == pytest.approx(0.1 * math.log(128) + 1)
    assert rope.rope_spec({**section, "attention_factor": 1.25}).attention_factor == 1.25
