"""``models/hybrid.py``'s ``longcat_flash`` family (a published layer as
two ``mla`` entries: a ``shortcut`` layer that starts the expert branch
and a ``dense_add`` layer that adds it; a router whose last outputs are
identity experts) against the plain reference,
``models/longcat_flash_reference.py``, at a tiny size that keeps the
ratios of the benchmark's cut: two published layers (four sublayers), 4
of 16 real experts held beside 8 identity outputs (a third of the router,
as published) and 3 a token, a query rank under the hidden size, both
normed latents rescaled.  Seeded random float32 weights; logits are
compared, never sampled tokens.

Tolerance: both sides are float32 at the highest matmul precision
(conftest.py) and differ by the order of their sums (the online softmax
over blocks, the absorbed products, the sorted dispatch).  Logits are
O(4); 2e-4 absolute is about 50 float32 ulps of the largest, and each
mechanism moved or left out (the controls below) moves a logit by 1e-2 or
more.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from generativeaiexamples_tpu.engine.serving_models import serving_model
from generativeaiexamples_tpu.models import hybrid
from generativeaiexamples_tpu.models import longcat_flash_reference as ref
from generativeaiexamples_tpu.ops import moe

ATOL = 2e-4
CFG = hybrid.PRESETS["longcat_flash-tiny"]()
T = 128
ZERO = moe.COUNTERS.index("choices_zero")


@pytest.fixture(scope="module")
def params():
    key = jax.random.PRNGKey(0)
    return hybrid.balance_router_biases(hybrid.init_params(CFG, key), CFG, jax.random.fold_in(key, 1))


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
    whole = hybrid.from_hf_config(hybrid.LONGCAT_FLASH_CHAT, max_len=64)
    assert isinstance(whole, hybrid.ShortcutLatentConfig)
    # ``num_layers`` counts published layers: two entries and two state entries each.
    assert whole.layer_kinds == (("mla", "shortcut"), ("mla", "dense_add")) * 28
    assert (whole.n_experts, whole.experts_held, whole.zero_experts, whole.router_outputs) == (512, 512, 256, 768)
    cut = hybrid.PRESETS["longcat-flash-chat-l4e16"]()
    assert cut.layer_kinds == whole.layer_kinds[:8] and len(cut.layers_of("mla")) == 8
    assert (cut.d_model, cut.n_heads, cut.q_lora_rank, cut.kv_lora_rank) == (6144, 64, 1536, 512)
    assert (cut.qk_nope_head_dim, cut.qk_rope_head_dim, cut.v_head_dim) == (128, 64, 128)
    assert (cut.n_experts, cut.experts_held, cut.zero_experts, cut.n_experts_per_tok) == (512, 16, 256, 12)
    assert (cut.d_ff, cut.moe_d_ff, cut.shared_d_ff, cut.vocab_size, cut.max_seq_len) == (12288, 2048, 0, 16384, 16384)
    assert cut.score_function == "softmax" and cut.router_bias and not cut.norm_topk
    assert (cut.n_group, cut.topk_group, cut.routed_scaling) == (1, 1, 6.0)
    assert cut.latent_rescale and not cut.mla_out_gate and cut.rope_latent is None
    assert (cut.rope_theta, cut.norm_eps, cut.attn_scale_beta, cut.softmax_mscale) == (1e7, 1e-5, 0.0, 1.0)
    assert (cut.latent_block, cut.latent_decode_block) == (1024, 2048)
    # A state of rows alone: a hit is cut at any row, and a snapshot holds nothing.
    assert cut.rows_only and cut.snapshot_bytes() == 0 and cut.draft == ""
    assert cut.row_counters == hybrid.LATENT_COUNTERS and cut.n_counters == len(moe.COUNTERS) + 3
    # 576 values a token a sublayer, stored in rows of whole lanes.
    assert cut.kv_lora_rank + cut.qk_rope_head_dim == 576 and cut.latent_width == 640
    assert CFG.layer_kinds == whole.layer_kinds[:4]  # the tiny size keeps the pattern
    assert (CFG.n_experts, CFG.experts_held, CFG.zero_experts, CFG.n_experts_per_tok) == (16, 4, 8, 3)


def test_the_cut_holds_the_bytes_the_issue_counts():
    cut = hybrid.PRESETS["longcat-flash-chat-l4e16"]()
    shapes = jax.eval_shape(lambda: hybrid.init_params(cut, jax.random.PRNGKey(0)))
    weights = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(shapes))
    assert weights == pytest.approx(10.35e9, rel=0.01)
    first, second = shapes["layers"][:2]
    for half in (first, second):
        attention = sum(half[n].size for n in ("w_qa", "w_qb", "w_kva", "w_kvb", "w_o"))
        assert attention == 6144 * 1536 + 1536 * 12288 + 6144 * 576 + 512 * 16384 + 8192 * 6144
        assert half["w_gu"].shape == (6144, 24576) and half["w_down"].shape == (12288, 6144)
        assert "w_gate" not in half and "w_q" not in half and "w_gu_s" not in half
    # The router keeps its published width, identity outputs and all; the
    # experts belong to the first entry alone.
    assert first["router"].shape == (6144, 768) and first["router_bias"].shape == (768,)
    assert first["w_gu_e"].shape == (16, 6144, 4096) and first["w_down_e"].shape == (16, 2048, 6144)
    assert not {"router", "router_bias", "w_gu_e", "w_down_e"} & set(second)
    state = hybrid.state_bytes(cut, 16, 16384)
    assert state == {"full": 16 * 16384 * 640 * 2 * 8, "window": 0, "recurrent": 0}
    assert state["full"] == 2_684_354_560  # 10,240 B a token over eight sublayers


@pytest.mark.parametrize("bad, match", [
    ({"attention_method": "MHA"}, "latent attention"),
    ({"zero_expert_type": "copy"}, "identity"),
    ({"mla_scale_q_lora": False}, "both on or both off"),
    ({"q_lora_rank": None}, "low-rank query"),
    ({"attention_bias": True}, "biases"),
    ({"rope_scaling": {"type": "yarn", "factor": 4}}, "rope_scaling"),
])
def test_what_the_family_does_not_serve_is_refused_with_the_reason(bad, match):
    with pytest.raises(ValueError, match=match):
        hybrid.from_hf_config({**hybrid.LONGCAT_FLASH_TINY, **bad}, max_len=64)
    with pytest.raises(ValueError, match="draft"):
        hybrid.from_hf_config(hybrid.LONGCAT_FLASH_TINY, max_len=64, draft="mtp")


@pytest.mark.parametrize("kinds", [
    (("mla", "shortcut"),),  # a branch that nothing adds
    (("mla", "dense_add"),),  # an add with no branch before it
    (("mla", "shortcut"), ("mla", "dense"), ("mla", "dense_add")),  # not the layer after
    (("mla", "shortcut"), ("mla", "shortcut"), ("mla", "dense_add")),
])
def test_a_branch_is_added_by_the_layer_after_the_one_that_starts_it(kinds):
    with pytest.raises(ValueError, match="shortcut"):
        dataclasses.replace(CFG, layer_kinds=kinds)


def test_cold_forward_matches_the_reference(params, tokens, want):
    lengths = np.array([80, 61, 33], np.int32)
    got, state, counters = _forward(
        params, tokens, np.zeros(3), lengths, hybrid.init_state(CFG, 3, T), T)
    for row, n in enumerate(lengths):
        np.testing.assert_allclose(got[row, :n], want[row][:n], atol=ATOL)
    counters = np.asarray(counters)
    # Rows read: each row's own whole blocks of 16 up to its length, in each
    # of four sublayers; dense: three windows a sublayer.
    assert list(counters[-3:]) == [4 * (80 + 64 + 48), 4 * 3 * T, 0]
    # The experts ran once a PUBLISHED layer: 3 choices for each of the 174
    # tokens that count, in each of two expert layers; every choice is a real
    # expert held, a real expert of another share, or an identity expert.
    assert counters[0] == 2 * 3 * 174 and counters[4] == 2
    assert 0 < counters[1] < counters[0] - counters[ZERO] and 0 < counters[ZERO]
    assert len(state) == 4 and all(set(layer) == {"latent"} for layer in state)
    # A padded position wrote nothing: the rows past a row's length are zero.
    for layer in state:
        lat = np.asarray(layer["latent"])
        assert lat.shape == (3, T, CFG.latent_width) and not lat[2, 33:].any() and lat[2, :33, :24].all()
        assert not lat[..., 24:].any()  # the columns that fill a row up to whole lanes


def test_the_balanced_bias_spreads_the_choices_over_all_the_router_s_outputs(params):
    """``balance_router_biases`` runs over the real and the identity
    outputs alike: a third of the choices fall on identity experts (8 of
    24 outputs), a sixth on the 4 real experts held."""
    toks = np.random.RandomState(3).randint(0, CFG.vocab_size, size=(8, 64)).astype(np.int32)
    _, _, counters = _forward(
        params, toks, np.zeros(8), np.full(8, 64), hybrid.init_state(CFG, 8, 64), 64)
    routed, local, zero = (int(np.asarray(counters)[i]) for i in (0, 1, ZERO))
    assert routed == 2 * 3 * 512
    assert zero / routed == pytest.approx(8 / 24, abs=0.04)
    assert local / routed == pytest.approx(4 / 24, abs=0.04)
    assert params["layers"][0]["router_bias"].shape == (24,)


def test_a_sample_too_large_to_balance_whole_goes_through_in_groups(params, monkeypatch):
    """At the published widths the sample's float32 combine (96 rows x 256
    tokens x 12 choices x 6,144) is 7.2 GB beside 10.35 GB of weights: it
    goes through each layer in 16 groups of 6 rows, and the biases are the
    whole sample's either way."""
    seen = {}
    real = hybrid._balanced_biases

    def spy(params, cfg, tokens, groups):
        seen.update(rows=tokens.shape[0], groups=groups)
        raise RuntimeError("shapes alone")

    monkeypatch.setattr(hybrid, "_balanced_biases", spy)
    for preset, want in (
        ("longcat-flash-chat-l4e16", {"rows": 96, "groups": 16}),
        ("deepseek-v3.2-l5e16", {"rows": 32, "groups": 1}),  # 1.75 GiB: whole, as it always went
    ):
        with pytest.raises(RuntimeError, match="shapes alone"):
            hybrid.balance_router_biases({}, hybrid.PRESETS[preset](), jax.random.PRNGKey(0))
        assert seen == want
    toks = jax.random.randint(jax.random.PRNGKey(4), (8, 64), 0, CFG.vocab_size, jnp.int32)
    whole, grouped = real(params, CFG, toks, 1), real(params, CFG, toks, 4)
    assert len(whole) == 2 and all(b.shape == (24,) for b in whole)
    for a, b in zip(whole, grouped):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_the_zero_choices_count_by_hand():
    """Six tokens, 3 choices each over 4 real + 2 identity outputs, of which
    the real experts 1-2 are held: the counters against a hand count, and
    the identity term against ``w x``."""
    D, F = 8, 4
    keys = jax.random.split(jax.random.PRNGKey(2), 3)
    x = jax.random.normal(keys[0], (6, D))
    lp = {"w_gu_e": jax.random.normal(keys[1], (2, D, 2 * F)), "w_down_e": jax.random.normal(keys[2], (2, F, D))}
    idx = jnp.asarray([[0, 1, 4], [4, 5, 2], [3, 0, 1], [5, 4, 3], [2, 1, 0], [4, 2, 5]], jnp.int32)
    w = jnp.arange(1, 19, dtype=jnp.float32).reshape(6, 3) / 10
    valid = jnp.asarray([True, True, True, True, True, False])  # the last token is padding
    both, counters = moe.expert_mlp(x, idx, w, valid, lp, offset=1, held=2, zero_from=4)
    real, plain = moe.expert_mlp(x, idx, w, valid, lp, offset=1, held=2)
    counters, plain = np.asarray(counters), np.asarray(plain)
    # Choices of the five tokens that count: 15; on experts 1-2: 1, 1, 1, 0, 2;
    # on outputs 4-5: 1, 2, 0, 2, 0.
    assert (counters[0], counters[1], counters[ZERO]) == (15, 5, 5) and plain[ZERO] == 0
    assert list(plain[:ZERO]) == list(counters[:ZERO])  # an identity choice is no absent expert's
    w_zero = np.asarray([0.3, 0.4 + 0.5, 0.0, 1.0 + 1.1, 0.0, 0.0], np.float32)
    np.testing.assert_allclose(both - real, w_zero[:, None] * np.asarray(x), atol=1e-6)
    assert not np.asarray(both)[5].any()


@pytest.mark.parametrize("start", [23])
def test_chunked_prefill_then_decode_through_the_cache_matches_the_reference(params, tokens, want, start):
    """Chunks of 16 (the last padded) through the serving model's
    ``prefill_row`` over blocks of 16 rows, smaller than the window of
    128; then one token a step through ``decode_step``.  ``start`` 23: the
    first chunk starts at no multiple of the chunk, as after a prefix hit
    cut at row 23."""
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
    # The decode step walked the one decoding row's whole blocks of 16 up to
    # its 80 rows in each sublayer; the other slot read nothing and routed
    # nowhere: 3 choices in each of two expert layers.
    counters = np.asarray(counters)
    assert list(counters[-3:]) == [4 * 80, 4 * 2 * T, 0]
    assert counters[0] == 6 and counters[4] == 2 and 0 <= counters[ZERO] <= 6


def test_the_chunks_of_several_slots_read_their_rows_in_place(params, tokens, want):
    """``prefill_rows`` (the scheduler's chunk program) over three slots at
    once, one of them padding: the logits are the reference's and the other
    slots' rows stay as they were; a pad row routes nowhere, identity
    experts included."""
    model = serving_model(CFG, None, T)
    assert model.cut_anywhere and model.chunk_windows(16) == (T,) and model.chunks_per_program(16) == 8
    program = jax.jit(model.prefill_rows, static_argnums=(6,))
    state = model.init_state(4, T)
    slots, rows = np.array([2, 0, 3], np.int32), (0, 1)  # the third row is padding
    marker = jnp.full_like(state[0]["latent"][3], 7.0)
    state = tuple({"latent": layer["latent"].at[3].set(marker)} for layer in state)
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
        by_name = dict(zip(model.counter_names, np.asarray(counters).tolist()))
        assert by_name["attn_rows_read_latent_prefill"] == 4 * 2 * (at + 16)
        assert by_name["moe_choices_routed"] == 2 * 3 * 32 and "moe_choices_zero" in by_name
    for layer in state:
        lat = np.asarray(layer["latent"])
        assert (lat[3] == 7.0).all() and not lat[1].any()  # the pad row's slot, a slot not named
        assert lat[2, :48, :24].all() and not lat[2, 48:].any()


def _layer_program(params, cfg, h):
    """One published layer of the program (entries 0 and 1) over ``h``
    (b, s, D) from nothing: the stream after the layer."""
    b, s, _ = h.shape
    pos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    valid, n_valid = jnp.ones((b, s), bool), jnp.full((b,), s, jnp.int32)
    state = hybrid.init_state(cfg, b, s)
    x, pending = h, None
    for (mixer, mlp), lp, st in zip(cfg.layer_kinds[:2], params["layers"][:2], state):
        x, _, _ = hybrid._mix(x, lp, st, mixer, pos, valid, n_valid, cfg, s)
        x, _, _, pending = hybrid._mlp(x, lp, mlp, valid, cfg, None, None, pending)
    assert pending is None
    return x


def test_the_shares_of_the_experts_add_up_to_the_uncut_layer(params):
    """The share test, at 32 real + 16 identity outputs in 8 shares of 4:
    the shares' expert parts summed, plus the identity part and the dense
    path counted once, equal the uncut reference's layer; program and
    reference alike."""
    cfg = dataclasses.replace(CFG, n_experts=32, zero_experts=16, experts_held=4, n_experts_per_tok=5)
    D, F = cfg.d_model, cfg.moe_d_ff
    keys = jax.random.split(jax.random.PRNGKey(6), 5)
    all_gu = jax.random.normal(keys[0], (32, D, 2 * F)) * D**-0.5
    all_down = jax.random.normal(keys[1], (32, F, D)) * F**-0.5
    first = {
        **params["layers"][0],
        "router": jax.random.normal(keys[2], (D, 48)) * D**-0.5,
        "router_bias": jax.random.normal(keys[3], (48,)) * 0.02,
    }
    second = params["layers"][1]
    h = jax.random.normal(keys[4], (2, 24, D))
    whole_cfg = dataclasses.replace(cfg, experts_held=32)
    whole_lp = {**first, "w_gu_e": all_gu, "w_down_e": all_down}
    whole = _layer_program({"layers": (whole_lp, second)}, whole_cfg, h)
    uncut = ref.layer(h[0], whole_lp, second, tuple(sorted(ref._dims(whole_cfg, None, None, True).items())))
    np.testing.assert_allclose(whole[0], uncut, atol=2e-5)

    # The branch of each share alone, and what every chip computes alike:
    # each reads the first sublayer's normed post-attention stream.
    valid, n_valid = jnp.ones((2, 24), bool), jnp.full((2,), 24, jnp.int32)
    pos = jnp.broadcast_to(jnp.arange(24, dtype=jnp.int32), (2, 24))
    h1, _, _ = hybrid._mix(h, first, hybrid.init_state(cfg, 2, 24)[0], "mla", pos, valid, n_valid, cfg, 24)
    u = hybrid.rms_norm(h1, first["mlp_norm"], cfg.norm_eps)
    parts, ref_parts, zero = [], [], 0
    dims = ref._dims(cfg, None, None, False)
    expert_layer = jax.jit(hybrid._expert_layer, static_argnums=(3, 4))  # a program a share, not an operation at a time
    for rank in range(8):
        share_cfg = dataclasses.replace(cfg, expert_offset=4 * rank)
        share = {**first, "w_gu_e": all_gu[4 * rank : 4 * rank + 4], "w_down_e": all_down[4 * rank : 4 * rank + 4]}
        with_zero, counters, _ = expert_layer(u, share, valid, share_cfg, None)
        real_only, _, _ = expert_layer(u, share, valid, dataclasses.replace(share_cfg, zero_experts=0, n_experts=48), None)
        parts.append(real_only)
        identity = with_zero - real_only  # the same on every share: a token's own chip adds it
        ref_parts.append(ref.experts(u[0], share, {**dims, "offset": 4 * rank}))
        zero = int(counters[ZERO])
    ref_identity = ref.experts(u[0], share, {**dims, "held": 0, "identity": True})
    ref_whole = ref.experts(u[0], whole_lp, {**dims, "held": 32, "offset": 0, "identity": True})
    np.testing.assert_allclose(sum(ref_parts) + ref_identity, ref_whole, atol=2e-5)
    np.testing.assert_allclose((sum(parts) + identity)[0], ref_whole, atol=2e-5)
    np.testing.assert_allclose(identity[0], ref_identity, atol=2e-5)
    # The summed branch in the uncut layer's place gives the uncut layer.
    summed = _with_branch(params, cfg, h, first, second, sum(parts) + identity)
    np.testing.assert_allclose(summed, whole, atol=2e-5)
    assert 0 < zero < 5 * 48  # some of the 5 choices of the 48 tokens fell on identity experts


def _with_branch(params, cfg, h, first, second, m):
    """The published layer with ``m`` in the expert branch's place: the
    dense path and both sublayers counted once."""
    b, s, _ = h.shape
    pos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    valid, n_valid = jnp.ones((b, s), bool), jnp.full((b,), s, jnp.int32)
    state = hybrid.init_state(cfg, b, s)
    x, _, _ = hybrid._mix(h, first, state[0], "mla", pos, valid, n_valid, cfg, s)
    x, _, _, _ = hybrid._mlp(x, first, "dense", valid, cfg, None)
    x, _, _ = hybrid._mix(x, second, state[1], "mla", pos, valid, n_valid, cfg, s)
    x, _, _, pending = hybrid._mlp(x, second, "dense_add", valid, cfg, None, None, m)
    assert pending is None
    return x


def test_moving_the_branch_or_dropping_the_identity_term_leaves_the_reference(params, tokens, want, monkeypatch):
    """Controls of the comparison itself: the expert branch added before
    the second sublayer (a plain ``experts`` layer's place), and the
    identity choices dropped, each move the logits past the tolerance."""
    def early(x, lp, mlp, valid, cfg, mesh, rho=None, pending=None):
        """``_mlp`` with the branch added where it starts."""
        x, counters, rho, pending = real_mlp(x, lp, mlp, valid, cfg, mesh, rho, pending)
        if mlp == "shortcut":
            return x + pending, counters, rho, jnp.zeros_like(pending)
        return x, counters, rho, pending

    real_mlp = hybrid._mlp
    run = lambda cfg: jax.jit(lambda p, t, st: hybrid.forward(
        p, cfg, t, jnp.zeros((1,), jnp.int32), jnp.full((1,), 80, jnp.int32), st, window=T))
    state = hybrid.init_state(CFG, 1, T)
    sound, _, _ = run(CFG)(params, jnp.asarray(tokens[:1]), state)
    np.testing.assert_allclose(hybrid.logits(params, CFG, sound)[0], want[0], atol=ATOL)
    monkeypatch.setattr(hybrid, "_mlp", early)
    moved, _, _ = run(CFG)(params, jnp.asarray(tokens[:1]), state)
    assert np.abs(np.asarray(hybrid.logits(params, CFG, moved))[0] - want[0]).max() > 1e-2
    monkeypatch.setattr(hybrid, "_mlp", real_mlp)
    # The identity outputs routed to but never added: what ``zero_from``
    # None makes of them (absent experts).
    real_expert_mlp = moe.expert_mlp
    monkeypatch.setattr(
        moe, "expert_mlp", lambda *a, zero_from=None, **kw: real_expert_mlp(*a, **kw))
    dropped, _, counters = run(CFG)(params, jnp.asarray(tokens[:1]), state)
    assert np.abs(np.asarray(hybrid.logits(params, CFG, dropped))[0] - want[0]).max() > 1e-2
    assert int(counters[ZERO]) == 0
    # The reference's own controls say the same of the reference.
    monkeypatch.setattr(ref, "_join", lambda h4, m: h4)
    assert np.abs(np.asarray(ref.all_logits(params, CFG, tokens[0][:40])) - want[0][:40]).max() > 1e-2
