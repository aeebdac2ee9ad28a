"""``models/hybrid.py``'s ``dots3_note`` family (an ``mla`` layer whose
queries attend the rows a learned indexer selects, beside ``mla_window``
layers: latent attention of other sizes over a ring of latent rows)
against the plain reference, ``models/dots3_note_reference.py``, at a tiny
size that keeps the ratios of the benchmark's cut: the same six layers
(full + dense, full, sliding x 3, full), two kinds whose heads, ranks,
head sizes and thetas all differ, an indexer of 2 heads that keeps 24 rows
under prompts of 80, a window of 13 under chunks of 16, 2 of 16 experts
held (an eighth) and 2 a token.  Seeded random float32 weights; logits and
selected sets are compared, never sampled tokens.

Tolerance: both sides are float32 at the highest matmul precision
(conftest.py) and differ by the order of their sums (the online softmax
over blocks, the absorbed products, the sorted dispatch).  Logits are
O(4); 2e-4 absolute is about 50 float32 ulps of the largest, and each
mechanism switched off (the controls below) moves a logit by 1e-2 or more.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import chip_smoke
from generativeaiexamples_tpu.engine.serving_models import serving_model
from generativeaiexamples_tpu.models import dots3_note_reference as ref
from generativeaiexamples_tpu.models import hybrid
from generativeaiexamples_tpu.ops import mla

ATOL = 2e-4
CFG = hybrid.PRESETS["dots3_note-tiny"]()
T = 128
TOPK, WINDOW = CFG.index_topk, CFG.sliding_window  # 24, 13
FULL = CFG.layers_of("mla")  # 0, 1, 5


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


def _named(counters, cfg=CFG):
    return dict(zip(cfg.row_counters, np.asarray(counters)[len(hybrid.moe.COUNTERS):].tolist()))


def test_the_published_keys_give_the_published_model():
    whole = hybrid.from_hf_config(hybrid.DOTS3_NOTE_PREV, max_len=64)
    assert isinstance(whole, hybrid.IndexedLatentConfig)
    assert len(whole.layers_of("mla")) == 13 and len(whole.layers_of("mla_window")) == 33
    assert whole.layer_kinds[0] == ("mla", "dense") and whole.layer_kinds[1] == ("mla", "experts")
    assert {mlp for _, mlp in whole.layer_kinds[1:]} == {"experts"}
    assert (whole.n_experts, whole.experts_held, whole.vocab_size) == (256, 256, 152064)
    cut = hybrid.PRESETS["dots3-note-prev-l6e32"]()
    assert cut.layer_kinds == whole.layer_kinds[:6] == (
        ("mla", "dense"), ("mla", "experts"), ("mla_window", "experts"),
        ("mla_window", "experts"), ("mla_window", "experts"), ("mla", "experts"))
    assert cut.latent_sizes("mla") == hybrid.LatentSizes(128, 1024, 512, 128, 64, 128, 8e7)
    assert cut.latent_sizes("mla_window") == hybrid.LatentSizes(64, 1024, 1024, 192, 64, 128, 5e4)
    assert (cut.index_n_heads, cut.index_head_dim, cut.index_topk, cut.sliding_window) == (64, 128, 2048, 513)
    assert (cut.d_model, cut.d_ff, cut.moe_d_ff, cut.shared_d_ff) == (5120, 13824, 1536, 1536)
    assert (cut.n_experts, cut.experts_held, cut.n_experts_per_tok, cut.vocab_size) == (256, 32, 8, 19008)
    assert cut.score_function == "sigmoid" and cut.router_bias and cut.norm_topk
    assert (cut.n_group, cut.topk_group, cut.routed_scaling, cut.norm_eps) == (1, 1, 1.0, 1e-5)
    assert cut.mla_out_gate and cut.latent_rescale and cut.rope_latent is None and cut.max_seq_len == 16384
    # Rings beside rows: a hit needs a snapshot, and no draft is served.
    assert not cut.rows_only and cut.draft == "" and not cut.has_attn_counters
    assert cut.row_counters == (
        "read_latent", "dense_latent", "kernel_latent", "index_pairs", "read_selected", "read_index",
        "seen_latent", "read_window", "dense_window")
    # 576 and 1,088 values a row, stored in whole lanes.
    assert (cut.latent_width, cut.row_width("mla_window"), cut.ring_rows(16384)) == (640, 1152, 513)
    assert CFG.layer_kinds == cut.layer_kinds  # the tiny size keeps the pattern


def test_the_cut_holds_the_bytes_the_issue_counts():
    cut = hybrid.PRESETS["dots3-note-prev-l6e32"]()
    shapes = jax.eval_shape(lambda: hybrid.init_params(cut, jax.random.PRNGKey(0)))
    weights = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(shapes))
    assert weights == 10_022_188_544
    D = 5120
    full = D * 1024 + 1024 * 128 * 192 + D * 576 + 512 * 128 * 256 + 128 * 128 * D + D * 128
    index = 1024 * 64 * 128 + D * 128 + D * 64
    sliding = D * 1024 + 1024 * 64 * 256 + D * 1088 + 1024 * 64 * 320 + 64 * 128 * D + D * 64
    names = ("w_qa", "w_qb", "w_kva", "w_kvb", "w_o", "w_gate")
    assert sum(shapes["layers"][1][n].size for n in names) == full == 134_676_480
    assert sum(shapes["layers"][1][n].size for n in ("w_qi", "w_ki", "w_wi")) == index == 9_371_648
    assert sum(shapes["layers"][2][n].size for n in names) == sliding == 90_832_896
    assert "w_qi" not in shapes["layers"][2] and "router_bias" in shapes["layers"][1]
    assert "router" not in shapes["layers"][0] and shapes["layers"][0]["w_gu"].shape == (D, 2 * 13824)
    state = hybrid.state_bytes(cut, 16, 16384)
    assert state == {
        "full": 3 * 16 * 16384 * (640 + 128) * 2, "window": 3 * 16 * 513 * 1152 * 2, "recurrent": 0}
    assert state["full"] == 1_207_959_552 and state["window"] == 56_733_696
    assert cut.snapshot_bytes(16384) == 3 * 513 * 1152 * 2 == 3_545_856


def test_the_tiny_size_by_hand(params):
    """``init_params``, ``init_state``, ``state_bytes`` and
    ``snapshot_bytes`` at the tiny size, worked by hand."""
    lp = params["layers"]
    assert lp[1]["w_qa"].shape == (64, 24) and lp[1]["w_qb"].shape == (24, 4 * 16)
    assert lp[1]["w_kva"].shape == (64, 16 + 8) and lp[1]["w_kvb"].shape == (16, 4 * (8 + 16))
    assert lp[1]["w_o"].shape == (4 * 16, 64) and lp[1]["w_gate"].shape == (64, 4)
    assert lp[1]["w_qi"].shape == (24, 2 * 16) and lp[1]["w_ki"].shape == (64, 16)
    assert lp[1]["ki_norm"].shape == lp[1]["ki_norm_b"].shape == (16,) and lp[1]["w_wi"].shape == (64, 2)
    assert lp[2]["w_qa"].shape == (64, 16) and lp[2]["w_qb"].shape == (16, 2 * 20)
    assert lp[2]["w_kva"].shape == (64, 24 + 4) and lp[2]["w_kvb"].shape == (24, 2 * (16 + 8))
    assert lp[2]["w_o"].shape == (2 * 8, 64) and lp[2]["w_gate"].shape == (64, 2) and "w_qi" not in lp[2]
    assert lp[2]["w_gu_e"].shape == (2, 64, 64) and lp[2]["router"].shape == (64, 16)
    state = hybrid.init_state(CFG, 3, T)
    assert [sorted(layer) for layer in state] == [
        ["index_k", "latent"], ["index_k", "latent"], ["ring_latent"], ["ring_latent"],
        ["ring_latent"], ["index_k", "latent"]]
    # A tiny row is 24 and 28 values: whole lanes of 128 either way.
    assert state[0]["latent"].shape == (3, T, 128) and state[0]["index_k"].shape == (3, T, 16)
    assert state[2]["ring_latent"].shape == (3, 13, 128)
    assert hybrid.state_bytes(CFG, 3, T) == {
        "full": 3 * 3 * T * (128 + 16) * 4, "window": 3 * 3 * 13 * 128 * 4, "recurrent": 0}
    assert CFG.snapshot_bytes(T) == 3 * 13 * 128 * 4
    model = serving_model(CFG, None, T)
    assert not model.cut_anywhere and model.rows_in_place and model.chunk_windows(16) == (T,)
    assert model.snapshot_bytes == 3 * 13 * 128 * 4
    assert model.counter_names[-18:-9] == tuple(f"attn_rows_{n}_decode" for n in CFG.row_counters)


@pytest.mark.parametrize("bad, match", [
    ({"scoring_func": "softmax"}, "sigmoid"),
    ({"n_group": 2}, "routing groups"),
    ({"q_lora_rank": None}, "low-rank"),
    ({"attention_gate_type": "elementwise"}, "attention_gate_type"),
    ({"rope_scaling": {"type": "yarn"}}, "rope_scaling"),
    ({"index_topk": 0}, "indexer"),
    ({"layer_types": ["linear_attention"] * 6}, "not served"),
])
def test_what_the_family_does_not_serve_is_refused_with_the_reason(bad, match):
    with pytest.raises(ValueError, match=match):
        hybrid.from_hf_config({**hybrid.DOTS3_NOTE_TINY, **bad}, max_len=64)


def test_cold_forward_matches_the_reference_past_the_selection_and_the_window(params, tokens, want):
    lengths = np.array([80, 61, 20], np.int32)
    got, state, counters = _forward(
        params, tokens, np.zeros(3), lengths, hybrid.init_state(CFG, 3, T), T)
    for row, n in enumerate(lengths):
        np.testing.assert_allclose(got[row, :n], want[row][:n], atol=ATOL)
    c = _named(counters)
    # Three full layers: the block walk reads whole blocks of 16 up to each
    # row's length and scores every row of them for every query, kept or
    # not; the rows of 80 and 61 select, the row of 20 keeps every position
    # it sees.
    seen = sum(n * (n + 1) // 2 for n in lengths)
    scored = 80 * 80 + 64 * 61 + 32 * 20
    assert c["read_latent"] == 3 * (80 + 64 + 32) and c["dense_latent"] == 3 * 3 * T
    assert c["seen_latent"] == 3 * seen and c["read_selected"] == 3 * scored > 3 * seen
    assert c["read_index"] == 3 * (80 + 64) and c["index_pairs"] == 3 * (80 * 80 + 64 * 61)
    # Three window layers: every row's ring, against the window a full layer sees.
    assert c["read_window"] == 3 * 3 * 13 and c["dense_window"] == 3 * 3 * T
    # A padded position wrote nothing: rows past a row's length are zero.
    for at in FULL:
        lat, keys = np.asarray(state[at]["latent"]), np.asarray(state[at]["index_k"])
        assert not lat[2, 20:].any() and lat[2, :20, :24].all() and not lat[..., 24:].any()
        assert not keys[2, 20:].any() and keys[2, :20].all()


def test_in_float32_the_program_selects_the_reference_s_rows(params, tokens):
    """The sets themselves, as the benchmark's comparison reads them
    (``benchmarks/arch/dots3_note.py``): what ``select_mask`` returned in
    the chunk program's calls and what ``select_rows`` gathered in the
    decode step's, on the program's own stream and the index keys its
    calls wrote, against the reference's full sort: the same pairs, every
    query past ``index_topk`` of the three full layers among them."""
    arch = chip_smoke._bench_arch("dots3_note")
    arch._CHECK.update(decode=8, chunk=16)
    row = tokens[0]
    _, _, overlap = arch.logit_shares(params, CFG, row, len(row))
    got = arch._SELECTED
    assert got.queries == 3 * (80 - TOPK) and got.program == 3 * (80 - TOPK) * TOPK
    assert got.program == got.both == got.reference and overlap == 1.0
    for kind, _, kept in ref.layers(params, CFG, row):
        seen = TOPK if kind[0] == "mla" else WINDOW  # a window: the query's own position among them
        assert (np.asarray(kept).sum(-1) == np.minimum(np.arange(80) + 1, seen)).all()


@pytest.mark.parametrize("chunks", [(16, 16, 16, 16), (5, 16, 9, 16, 16, 2)], ids=["even", "uneven"])
def test_chunked_prefill_then_decode_through_the_cache_matches_the_reference(params, tokens, want, chunks):
    """Chunks (the uneven ones padded to 16) through the serving model's
    ``prefill_row``: they cross ``index_topk`` (24) inside a chunk and the
    window (13) in the first; then one token a step through ``decode_step``
    (every slot's index keys scored in one product, the kept rows
    gathered, the ring beside the step's own row)."""
    model = serving_model(CFG, None, T)
    row, n = tokens[0], 80
    state = model.init_state(2, T)
    chunk = jax.jit(model.prefill_row, static_argnums=(6,))
    step = jax.jit(model.decode_step, static_argnums=(5,))
    at = 0
    for count in chunks:
        piece = np.zeros((1, 16), np.int32)
        piece[0, :count] = row[at : at + count]
        state, hidden, _ = chunk(params, state, jnp.asarray(piece), jnp.int32(at), jnp.int32(count), jnp.int32(1), T)
        got = np.asarray(model.logits(params, hidden))[0, :count]
        np.testing.assert_allclose(got, want[0][at : at + count], atol=ATOL)
        at += count
    assert not any(np.asarray(leaf)[0].any() for layer in state for leaf in layer.values())  # slot 0 untouched
    for pos in range(at, n):
        state, logits, counters = step(
            params, state, jnp.asarray([0, row[pos]]), jnp.asarray([0, pos]), jnp.asarray([0, 1]), T)
        np.testing.assert_allclose(np.asarray(logits)[1], want[0][pos], atol=ATOL)
    c = _named(counters)
    # The last step: both slots' index keys over the window and 24 rows a
    # slot gathered, in three layers; the one decoding slot holds 80 rows.
    assert c["read_index"] == c["index_pairs"] == 3 * 2 * T
    assert c["read_selected"] == c["read_latent"] == 3 * 2 * TOPK and c["seen_latent"] == 3 * 80
    assert c["read_window"] == 3 * 2 * 13


def test_the_chunks_of_several_slots_leave_every_other_leaf_bit_for_bit(params, tokens, want):
    """``prefill_rows`` (the scheduler's chunk program) over three slots at
    once, one of them padding, rows read and written in place and rings
    taken by slot; then a decode step in which one slot does not decode:
    the logits are the reference's, and a padded row, a slot not named and
    a slot that does not decode keep every leaf bit for bit (rings that
    have turned over among them)."""
    model = serving_model(CFG, None, T)
    program = jax.jit(model.prefill_rows, static_argnums=(6,))
    step = jax.jit(model.decode_step, static_argnums=(5,))
    state = model.init_state(4, T)
    marked = []
    for layer in state:  # slot 3, which the pad row names: a marker in every leaf
        marked.append({n: leaf.at[3].set(7.0) for n, leaf in layer.items()})
    state = tuple(marked)
    slots, rows = np.array([2, 0, 3], np.int32), (0, 1)  # the third row is padding
    for at in range(0, 64, 16):
        toks = np.zeros((3, 16), np.int32)
        for r in rows:
            toks[r] = tokens[r, at : at + 16]
        state, hidden, counters = program(
            params, state, jnp.asarray(toks), jnp.asarray([at, at, 5], jnp.int32),
            jnp.asarray([16, 16, 0], jnp.int32), jnp.asarray(slots), T)
        got = np.asarray(model.logits(params, hidden))
        for r in rows:
            np.testing.assert_allclose(got[r], want[r][at : at + 16], atol=ATOL)
    c = dict(zip(model.counter_names, np.asarray(counters).tolist()))  # a served program's, by name
    assert c["attn_rows_read_latent_prefill"] == 3 * 2 * 64  # two live rows
    assert c["attn_rows_read_window_prefill"] == 3 * 2 * 13 and c["attn_rows_read_latent_decode"] == 0
    for layer in state:
        for leaf in layer.values():
            leaf = np.asarray(leaf)
            assert (leaf[3] == 7.0).all() and not leaf[1].any()  # the pad row's slot, a slot not named
    before = jax.tree.map(np.asarray, state)
    # Slot 2 decodes; slots 0 (64 rows held, its rings turned over four
    # times), 1 and 3 do not.
    state, logits, _ = step(
        params, state, jnp.asarray([9, 9, tokens[0, 64], 9]), jnp.asarray([64, 0, 64, 30]),
        jnp.asarray([0, 0, 1, 0]), T)
    np.testing.assert_allclose(np.asarray(logits)[2], want[0][64], atol=ATOL)
    for was, now in zip(before, state):
        for name in was:
            for slot in (0, 1, 3):
                np.testing.assert_array_equal(was[name][slot], np.asarray(now[name])[slot])
            assert (was[name][2] != np.asarray(now[name])[2]).any()


def test_a_slot_reused_from_zero_ignores_stale_index_keys_and_ring_rows(params, tokens, want):
    """A slot whose last occupant left latent rows, index keys (large
    ones, that any query would select) and full rings: a prompt that starts
    at 0 there gets what it gets in a fresh slot."""
    model = serving_model(CFG, None, T)
    program = jax.jit(model.prefill_rows, static_argnums=(6,))
    state = tuple(
        {n: jnp.full_like(leaf, 50.0) for n, leaf in layer.items()} for layer in model.init_state(2, T))
    for at in range(0, 48, 16):
        state, hidden, _ = program(
            params, state, jnp.asarray(tokens[1:2, at : at + 16]), jnp.asarray([at], jnp.int32),
            jnp.asarray([16], jnp.int32), jnp.asarray([1], jnp.int32), T)
        np.testing.assert_allclose(
            np.asarray(model.logits(params, hidden))[0], want[1][at : at + 16], atol=ATOL)


def test_a_prefix_hit_at_a_chunk_boundary_equals_a_cold_prefill(params, tokens, want):
    """Slot 0 prefills 32 tokens and is saved; slot 1 takes the first 32
    latent and index-key rows by graft and the three rings from the
    snapshot, then goes on: its logits are a cold prefill's, the
    reference's."""
    model = serving_model(CFG, None, T)
    chunk = jax.jit(model.prefill_row, static_argnums=(6,))
    state = model.init_state(2, T)
    row = tokens[2]
    for at in (0, 16):
        state, _, _ = chunk(params, state, jnp.asarray(row[None, at : at + 16]), jnp.int32(at),
                            jnp.int32(16), jnp.int32(0), T)
    snap = model.save_state(state, jnp.int32(0))
    assert [sorted(s) for s in snap] == [["ring_latent"]] * 3
    assert sum(leaf.size * leaf.dtype.itemsize for s in snap for leaf in s.values()) == model.snapshot_bytes
    # The source moves on: its rings no longer stand at 32.
    state, _, _ = chunk(params, state, jnp.asarray(row[None, 32:48]), jnp.int32(32), jnp.int32(16), jnp.int32(0), T)
    state = model.graft_prefix(state, jnp.int32(0), jnp.int32(1), 32)
    state = model.restore_state(state, jnp.int32(1), snap)
    for at in FULL:
        for name in ("latent", "index_k"):
            leaf = np.asarray(state[at][name])
            np.testing.assert_array_equal(leaf[1, :32], leaf[0, :32])
            assert not leaf[1, 32:].any()
    for at in range(32, 80, 16):
        state, hidden, _ = chunk(params, state, jnp.asarray(row[None, at : at + 16]), jnp.int32(at),
                                 jnp.int32(16), jnp.int32(1), T)
        np.testing.assert_allclose(np.asarray(model.logits(params, hidden))[0], want[2][at : at + 16], atol=ATOL)


def test_an_indexer_that_keeps_every_row_is_the_dense_latent_layer(params, tokens):
    """``index_topk`` no shorter than the prompt: the program takes the
    block walk without a mask in prefill and gathers every row in the
    decode step, and both equal the reference whose selection keeps every
    seen row: the dense ``mla`` path."""
    dense = dataclasses.replace(CFG, index_topk=T)
    want = np.asarray(ref.all_logits(params, dense, tokens[0]))
    got, state, counters = _forward(
        params, tokens[:1, :64], np.zeros(1), np.array([64]), hybrid.init_state(dense, 1, T), T, cfg=dense)
    np.testing.assert_allclose(got[0], want[:64], atol=ATOL)
    c = _named(counters)
    assert c["seen_latent"] == 3 * 64 * 65 // 2 and c["read_selected"] == 3 * 64 * 64
    assert c["index_pairs"] == 0
    for pos in range(64, 70):
        got, state, counters = _forward(params, tokens[:1, pos : pos + 1], [pos], [1], state, T, cfg=dense)
        np.testing.assert_allclose(got[0, 0], want[pos], atol=ATOL)
    # The selected form differs from it where the selection cuts.
    cut, _, _ = _forward(params, tokens[:1, :64], np.zeros(1), np.array([64]), hybrid.init_state(CFG, 1, T), T)
    assert np.abs(cut[0, TOPK:] - want[TOPK:64]).max() > 1e-2
    np.testing.assert_allclose(cut[0, :TOPK], want[:TOPK], atol=ATOL)


def test_the_eight_shares_of_the_experts_add_up_to_the_uncut_layer(params):
    """Each rank's routed part (2 of 16 experts from its offset) summed
    over the eight ranks, with the shared expert counted once, is the uncut
    layer's output; program and reference alike."""
    lp = params["layers"][1]
    h = jax.random.normal(jax.random.PRNGKey(5), (2, 24, CFG.d_model))
    valid = jnp.ones((2, 24), bool)
    whole_cfg = dataclasses.replace(CFG, experts_held=16)
    rng = jax.random.PRNGKey(6)
    all_gu = jax.random.normal(rng, (16, CFG.d_model, 2 * CFG.moe_d_ff)) * CFG.d_model**-0.5
    all_down = jax.random.normal(jax.random.fold_in(rng, 1), (16, CFG.moe_d_ff, CFG.d_model)) * CFG.moe_d_ff**-0.5
    shared = hybrid._swiglu(h.reshape(-1, CFG.d_model), lp["w_gu_s"], lp["w_down_s"]).reshape(h.shape)
    whole, _, _ = hybrid._expert_layer(h, {**lp, "w_gu_e": all_gu, "w_down_e": all_down}, valid, whole_cfg, None)
    parts, ref_parts = [], []
    dims = ref._dims(CFG, None, None)
    for rank in range(8):
        cfg = dataclasses.replace(CFG, expert_offset=2 * rank)
        share = {**lp, "w_gu_e": all_gu[2 * rank : 2 * rank + 2], "w_down_e": all_down[2 * rank : 2 * rank + 2]}
        y, counters, _ = hybrid._expert_layer(h, share, valid, cfg, None)
        parts.append(y - shared)
        ref_parts.append(ref.routed_experts(h[0], share, {**dims, "offset": 2 * rank}))
    np.testing.assert_allclose(sum(parts) + shared, whole, atol=1e-5)
    uncut = ref.routed_experts(h[0], {**lp, "w_gu_e": all_gu, "w_down_e": all_down}, {**dims, "held": 16, "offset": 0})
    np.testing.assert_allclose(sum(ref_parts), uncut, atol=1e-5)
    np.testing.assert_allclose((whole - shared)[0], uncut, atol=1e-5)
    assert int(counters[0]) == 2 * 48  # every token chose 2 of the 16


def _last_k(scores, seen, topk):
    """``last_2048`` at the tiny size: the newest ``topk`` rows a query sees."""
    newest = jnp.cumsum(seen[:, ::-1], axis=-1)[:, ::-1]
    return seen & (newest <= topk)


CONTROLS = {
    "no_selection": {"_select": lambda scores, seen, topk: seen},
    "last_2048": {"_select": _last_k},
    "no_index_relu": {"_index_act": lambda dots: dots},
    "no_rescale": {"_rescale": lambda c, d_model, rank: c},
    "no_gate": {"_gate": lambda o, h, w_gate: o},
    "window_512": {"_window_mask": lambda i, j, window: (j <= i) & (j > i - (window - 1))},
    "full_sizes_in_window": {"_window_theta": lambda dims: dims["theta"]},
}


@pytest.mark.parametrize("control", list(CONTROLS))
def test_each_mechanism_left_out_of_the_reference_leaves_the_program(params, tokens, want, control, monkeypatch):
    """Every control of the chip's comparison, against the reference alone:
    the reference with one step changed no longer agrees with the program
    (whose logits are the unchanged reference's: the tests above)."""
    for name, stand_in in CONTROLS[control].items():
        monkeypatch.setattr(ref, name, stand_in)
    jax.clear_caches()  # a layer traced before this would keep the plain one
    try:
        off = np.asarray(ref.all_logits(params, CFG, tokens[0]))
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    worst = np.abs(off - want[0]).max(-1)
    assert worst.max() > 1e-2, worst.max()
    if control in ("no_selection", "last_2048"):  # the first 24 queries keep every row they see
        assert worst[:TOPK].max() < ATOL
    if control == "window_512":  # the first 12 queries see every row
        assert worst[: WINDOW - 1].max() < ATOL


def test_the_kth_largest_is_found_without_a_sort_and_ties_go_to_the_lower_position():
    rng = np.random.RandomState(4)
    scores = rng.randn(5, 64).astype(np.float32)
    scores[0, 10:20] = scores[0, 3]  # ties across the threshold
    scores[1] = 0.5  # one value everywhere
    scores[2, 40:] = -np.inf  # a query that sees 40 positions
    scores[3, 5:] = -np.inf  # fewer than k
    scores[4] = -np.abs(scores[4])  # negative values order too
    for k in (1, 7, 24, 64, 100):
        got = np.asarray(mla.select_mask(jnp.asarray(scores), k))
        order = np.argsort(-scores, axis=-1, kind="stable")
        want = np.zeros_like(got)
        np.put_along_axis(want, order[:, : min(k, 64)], True, axis=-1)
        want &= scores > -np.inf
        np.testing.assert_array_equal(got, want)
        idx, keep = mla.select_rows(jnp.asarray(scores), k)
        rows = np.zeros_like(got)
        np.put_along_axis(rows, np.asarray(idx), np.asarray(keep), axis=-1)
        np.testing.assert_array_equal(rows, want)
