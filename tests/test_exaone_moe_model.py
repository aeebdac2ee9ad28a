"""``models/hybrid.py``'s ``exaone_moe`` family (K-EXAONE-236B-A23B)
against the plain reference, ``models/exaone_moe_reference.py``, at a tiny
size that keeps the ratios of the benchmark's cut: a period of four
(window, window, window, full) after a dense first layer, two periods
deep, a window of 8 under chunks of 16 (a chunk is TWO windows), QK-norm,
the full layers not rotated, 4 of 16 sigmoid-routed experts held and 2 a
token with a shared one, and one prediction module, which the serving
model runs as the draft of its decode step.  Seeded random float32
weights; logits are compared, never sampled tokens.

Tolerance: both sides are float32 at the highest matmul precision
(conftest.py) and differ by the order of their sums (the ring's two-part
softmax, the sorted dispatch).  Logits are O(4); 2e-4 absolute is about 50
float32 ulps of the largest, and each mechanism switched off (case f)
moves a logit by 1e-2 or more.
"""

import dataclasses
import functools
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from generativeaiexamples_tpu.engine.sampler import SamplingParams
from generativeaiexamples_tpu.engine.scheduler import Request, Scheduler
from generativeaiexamples_tpu.engine.serving_models import HybridServing, serving_model
from generativeaiexamples_tpu.models import exaone_moe_reference as ref
from generativeaiexamples_tpu.models import hybrid

ATOL = 2e-4
CFG = hybrid.PRESETS["exaone_moe-tiny"]()
PLAIN = hybrid.from_hf_config(hybrid.EXAONE_TINY, max_len=256, kv_dtype="float32")  # draft off
W = CFG.sliding_window  # 8
L = CFG.n_layers
T = 128
N = 60  # 7.5 windows


@pytest.fixture(scope="module")
def model():
    return HybridServing(CFG, None, T)


@pytest.fixture(scope="module")
def params(model):
    return model.prepare_params(None, quantize=False, matmul_kernel="xla", seed=3)


@pytest.fixture(scope="module")
def tokens():
    return np.random.RandomState(0).randint(0, CFG.vocab_size, size=(3, N)).astype(np.int32)


@pytest.fixture(scope="module")
def want(params, tokens):
    """The reference's full forward over each whole row: (stack logits
    (N, V), module logits (N - 1, V))."""
    return [tuple(np.asarray(a) for a in ref.all_logits(params, CFG, row)) for row in tokens]


@functools.lru_cache(maxsize=None)
def _serving(cfg):
    return HybridServing(cfg, None, T)


@functools.lru_cache(maxsize=None)
def _chunk_program(cfg):
    m = _serving(cfg)

    @jax.jit
    def chunk(params, state, toks, start, n, slot):
        state, hidden, _ = m.prefill_row(params, state, toks, start, n, slot, T)
        return state, m.logits(params, hidden)[0]

    return chunk


def _prefill(params, state, row, pieces, slot=0, cfg=CFG, bucket=16):
    """Chunked prefill of ``row`` in ``pieces`` (their sizes), each padded
    to ``bucket``; returns (state, logits at every position)."""
    got, start = [], 0
    for n in pieces:
        toks = np.zeros((1, bucket), np.int32)
        toks[0, :n] = row[start : start + n]
        state, lg = _chunk_program(cfg)(
            params, state, jnp.asarray(toks), jnp.int32(start), jnp.int32(n), jnp.int32(slot))
        got.append(np.asarray(lg)[:n])
        start += n
    return state, np.concatenate(got)


@functools.lru_cache(maxsize=None)
def _verify_programs(cfg):
    m = _serving(cfg)
    first = jax.jit(lambda p, st, tok, lens, on: m.draft_from_last(p, st, tok, lens, on, T)[:2])
    stack = jax.jit(lambda p, st, tok, dr, lens, on: m.verify_stack(p, st, tok, dr, lens, on, T)[:3])
    module = jax.jit(lambda p, st, h, nxt, lens, n: m.verify_module(p, st, h, nxt, lens, n, T)[:2])
    return first, stack, module


def _verify_walk(params, state, row, start, drafts_right, cfg=CFG, stale=False):
    """Walk ``row`` from ``start`` through the verify step of a one-slot
    state, teacher-forced: step by step the draft is the true next token
    (``drafts_right`` True at that step) or a wrong one.  Returns (state,
    {position: stack logits}, {position: module logits})."""
    first, stack, module = _verify_programs(cfg)
    one = jnp.ones((1,), jnp.int32)
    arr = lambda *v: jnp.asarray(v, jnp.int32)
    state, mlg = first(params, state, arr(row[start]), arr(start), one)
    logits, modules = {}, {start - 1: np.asarray(mlg)[0]}
    pos, at, step = start, start, 0
    while pos + 2 < len(row):
        right = bool(drafts_right[step % len(drafts_right)])
        draft = int(row[pos + 1]) if right else (int(row[pos + 1]) + 1) % cfg.vocab_size
        state, hidden, lg = stack(params, state, arr(row[pos]), arr(draft), arr(at), one)
        logits[pos] = np.asarray(lg)[0, 0]
        n = 2 if right else 1
        if right:
            logits[pos + 1] = np.asarray(lg)[0, 1]
        state, mlg = module(
            params, state, hidden, arr(row[pos + 1], row[pos + 2])[None], arr(at), arr(n))
        modules[pos + n - 1] = np.asarray(mlg)[0]
        pos += n
        at += 2 if stale else n
        step += 1
    return state, logits, modules


def _worst(got: dict, want) -> float:
    return max(float(np.abs(v - want[p]).max()) for p, v in got.items())


# -- (h) the mapping ----------------------------------------------------------------


def test_the_published_keys_give_the_published_layer_kinds():
    whole = hybrid.from_hf_config(hybrid.K_EXAONE_236B, max_len=64, draft="mtp")
    period = (("window", "experts"),) * 3 + (("full", "experts"),)
    assert whole.layer_kinds == (("window", "dense"),) + (period * 12)[1:]
    assert (whole.n_experts, whole.experts_held, whole.mtp_layers, whole.draft) == (128, 128, 1, "mtp")
    cut = hybrid.PRESETS["k-exaone-236b-a23b-l5e16"]()
    assert cut.layer_kinds == whole.layer_kinds[:5]
    assert (cut.d_model, cut.n_heads, cut.n_kv_heads, cut.attn_head_dim) == (6144, 64, 8, 128)
    assert (cut.d_ff, cut.moe_d_ff, cut.shared_d_ff) == (18432, 2048, 2048)
    assert (cut.n_experts, cut.experts_held, cut.expert_offset, cut.n_experts_per_tok) == (128, 16, 0, 8)
    assert (cut.n_group, cut.topk_group, cut.routed_scaling, cut.norm_topk) == (1, 1, 2.5, True)
    assert (cut.score_function, cut.router_bias, cut.qk_norm) == ("sigmoid", True, True)
    assert (cut.sliding_window, cut.vocab_size, cut.max_seq_len, cut.norm_eps) == (128, 19200, 8192, 1e-5)
    assert cut.rope_full.rope_type == "none" and cut.rope_window.theta == 1e6
    assert cut.ring_rows(8192) == 128 and cut.mtp_layers == 1
    assert CFG.layer_kinds == whole.layer_kinds[:8] and PLAIN.mtp_layers == 0 and PLAIN.draft == ""
    p = hybrid.init_params(CFG, jax.random.PRNGKey(1))
    assert {"q_norm", "k_norm", "w_gu"} <= set(p["layers"][0]) and "router" not in p["layers"][0]
    assert {"router_bias", "w_gu_s", "w_gu_e"} <= set(p["layers"][1])
    assert set(p["mtp"]) == {"enorm", "hnorm", "eh_proj", "layer", "final_norm"}
    assert p["mtp"]["eh_proj"].shape == (2 * CFG.d_model, CFG.d_model)
    # The stack's parameters are the same with the module held and without.
    q = hybrid.init_params(PLAIN, jax.random.PRNGKey(1))
    assert "mtp" not in q and all(
        np.array_equal(a, b) for a, b in zip(jax.tree.leaves(p["layers"]), jax.tree.leaves(q["layers"])))
    K = hybrid.K_EXAONE_236B
    for keys, message in (
        ({"num_nextn_predict_layers": 2}, "num_nextn_predict_layers 2 is not served"),
        ({"mtp_layer_types": ["sliding_attention"]}, "mtp_layer_types .* is not served"),
        ({"scoring_func": "softmax", "n_group": 4}, "scoring_func other than sigmoid"),
        ({"rope_parameters": {"rope_type": "yarn", "rope_theta": 1e6, "factor": 4}}, "rope_type 'yarn' is not served"),
        ({"layer_types": ["linear_attention"] * 48}, "are not served"),
        ({"first_k_dense_replace": 2}, "disagree"),
    ):
        with pytest.raises(ValueError, match=message):
            hybrid.from_hf_config({**K, **keys}, max_len=64, draft="mtp")
    with pytest.raises(ValueError, match="only the model's own module"):
        hybrid.from_hf_config(K, max_len=64, draft="ngram")
    with pytest.raises(ValueError, match="no prediction module here"):
        hybrid.from_hf_config(hybrid.MELLUM2_12B, max_len=64, draft="mtp")
    with pytest.raises(ValueError, match="more than one prediction module"):
        dataclasses.replace(CFG, mtp_layers=2)


# -- (a) the whole sequence -----------------------------------------------------------


def test_a_whole_sequence_and_the_modules_logits_match_the_reference(model, params, tokens, want):
    """(a) One call of 5 and of 7.5 windows from nothing: the stack's
    logits, and the prediction module's over the same call (one position
    behind: its rows are filled to N - 2, and its catch-up with the next
    token gives the draft at N - 1)."""
    cold = jax.jit(model.prefill_cold)
    for n in (5 * W, N):
        hidden, state, counters = cold(params, jnp.asarray(tokens[:1, :n]), jnp.asarray([n]))
        np.testing.assert_allclose(model.logits(params, hidden)[0], want[0][0][:n], atol=ATOL)
    # The module's own logits at every position: its block over the stack's
    # output with the next token's embedding.
    pos = jnp.arange(N - 1, dtype=jnp.int32)[None]
    x, rows, _ = hybrid.mtp_forward(
        params, CFG, hidden[:, :-1], jnp.asarray(tokens[:1, 1:]), pos, pos >= 0,
        hybrid.init_state(CFG, 1, N)[L], window=N)
    np.testing.assert_allclose(hybrid.mtp_logits(params, CFG, x)[0], want[0][1], atol=ATOL)
    # The cold call left the same rows (to N - 2) and the last hidden.
    np.testing.assert_allclose(state[L]["k"][0, : N - 1], rows["k"][0, : N - 1], atol=1e-5)
    np.testing.assert_allclose(state[L + 1]["h_last"][0], hidden[0, -1], atol=0)
    # Rows read: 6 window layers x their ring, (2 full layers + the module) x N.
    # behind the expert counters and those exported again for decode steps
    first = len(hybrid.moe.COUNTERS) + len(model.DECODE_MOE)
    assert counters.tolist()[first : first + 8] == [0, 0, 0, 0, 6 * W, 3 * N, 6 * N, 3 * N]


# -- (b) chunks, then the verify step -------------------------------------------------


@pytest.mark.parametrize("pieces", [(16, 16, 8), (7, 13, 11, 9)], ids=["two_windows", "uneven"])
@pytest.mark.parametrize("drafts", [(True,), (False,), (True, False, False, True, True)],
                         ids=["true", "wrong", "mixed"])
def test_chunks_then_the_verify_step_match_the_full_forward(pieces, drafts, params, tokens, want):
    """(b) Chunked prefill in chunks of two windows, and in pieces that do
    not divide the window, then the rest of the row through the verify
    step with true drafts, wrong drafts and a mixture: the stack's logits
    at every position and the module's against the full forward."""
    row = tokens[1]
    state, got = _prefill(params, _serving(CFG).init_state(1, T), row, pieces)
    np.testing.assert_allclose(got, want[1][0][: sum(pieces)], atol=ATOL)
    _, logits, modules = _verify_walk(params, state, row, sum(pieces), drafts)
    assert len(logits) >= N - sum(pieces) - 3
    assert _worst(logits, want[1][0]) <= ATOL and _worst(modules, want[1][1]) <= ATOL


# -- (c) the decode chunk: greedy and sampled rows ---------------------------------------


def _chunks(cfg, params, tokens, lengths, temp, n_steps, live=None, oracle=None, key=0):
    """Decode ``n_steps`` from prompts prefilled cold; returns each row's
    emitted tokens.  ``oracle`` (b, T) replaces the module's drafts by the
    token it holds at the drafted position (a test's way to have drafts
    accepted with random weights)."""
    m = _serving(cfg)
    if oracle is not None:
        m = _Oracle(cfg, None, T, oracle)
    b = len(lengths)
    width = 64
    toks = np.zeros((b, width), np.int32)
    for i, n in enumerate(lengths):
        toks[i, :n] = tokens[i, :n]
    hidden, small, _ = jax.jit(m.prefill_cold)(params, jnp.asarray(toks), jnp.asarray(lengths))
    state = m.graft_rows(m.init_state(b, T), small, jnp.arange(b), jnp.arange(b))
    last = jnp.take_along_axis(hidden, (jnp.asarray(lengths) - 1)[:, None, None], axis=1)[:, 0]
    first = jnp.argmax(m.logits(params, last), -1).astype(jnp.int32)
    out = m.make_decode_chunk()(
        params, state, first, jnp.asarray(lengths, jnp.int32), jax.random.PRNGKey(key),
        jnp.full((b,), temp, jnp.float32), jnp.ones((b,), jnp.float32), jnp.zeros((b,), jnp.int32),
        n_steps, T, None if live is None else jnp.asarray(live),
    )
    if cfg.draft:
        _, toks_out, counts, _, aux = out
        toks_out, counts = np.asarray(toks_out), np.asarray(counts)
        rows = [[int(t) for r in range(n_steps) for t in toks_out[r, i, : counts[r, i]]] for i in range(b)]
        return [[int(first[i])] + rows[i] for i in range(b)], dict(zip(m.counter_names, np.asarray(aux).tolist()))
    _, toks_out, aux = out
    toks_out = np.asarray(toks_out)
    return [[int(first[i])] + toks_out[:, i].tolist() for i in range(b)], None


class _Oracle(HybridServing):
    """The serving model with the module's drafts replaced: the state is
    moved exactly as it is, the draft is what ``oracle`` says."""

    def __init__(self, cfg, mesh, max_len, oracle):
        super().__init__(cfg, mesh, max_len)
        self.oracle = jnp.asarray(oracle, jnp.int32)

    def _said(self, at):
        rows = jnp.arange(self.oracle.shape[0])
        return 50.0 * jax.nn.one_hot(self.oracle[rows, jnp.minimum(at, self.oracle.shape[1] - 1)], self.cfg.vocab_size)

    def draft_from_last(self, params, cache, tokens, lengths, counts, window):
        cache, _, c = super().draft_from_last(params, cache, tokens, lengths, counts, window)
        return cache, self._said(lengths + 1), c

    def verify_module(self, params, cache, hidden, next_tokens, lengths, n_emit, window):
        cache, _, c = super().verify_module(params, cache, hidden, next_tokens, lengths, n_emit, window)
        return cache, self._said(lengths + n_emit + 1), c


def test_greedy_rows_emit_the_plain_chunks_tokens_whatever_the_drafts(params, tokens):
    """(c) Token for token: the draft on (the module's own drafts, which
    random weights reject; an oracle's, all right; an oracle right at some
    positions) against the draft off, over 24 steps of three rows of
    unequal lengths; a row that does not decode emits nothing and keeps
    its state."""
    lengths = [20, 33, 9]
    plain, _ = _chunks(PLAIN, {k: v for k, v in params.items() if k != "mtp"}, tokens, lengths, 0.0, 48)
    own, counters = _chunks(CFG, params, tokens, lengths, 0.0, 24)
    for a, b in zip(own, plain):
        assert a == b[: len(a)] and len(a) >= 25
    assert counters["draft_proposed"] == 72 and counters["verify_positions"] == 144
    assert counters["decode_tokens_emitted"] == 72 + counters["draft_accepted"]
    assert counters["draft_rows_rewritten"] == 2 * (72 - counters["draft_accepted"])
    # An oracle that knows the plain stream: every draft is kept, two tokens a step.
    full = np.zeros((3, T), np.int32)
    for i, n in enumerate(lengths):
        full[i, n : n + 49] = plain[i]
    right, counters = _chunks(CFG, params, tokens, lengths, 0.0, 24, oracle=full)
    for a, b in zip(right, plain):
        assert len(a) == 49 and a == b
    assert counters["draft_accepted"] == 72 and counters["decode_tokens_emitted"] == 144
    assert counters["draft_rows_rewritten"] == 0
    # Right at two positions in three: a mixture a row.
    mixed = np.where(np.arange(T)[None, :] % 3 == 0, (full + 1) % CFG.vocab_size, full)
    some, counters = _chunks(CFG, params, tokens, lengths, 0.0, 24, oracle=mixed)
    for a, b in zip(some, plain):
        assert 25 < len(a) < 49 and a == b[: len(a)]
    assert 0 < counters["draft_accepted"] < 72
    # A row that does not decode.
    held, counters = _chunks(CFG, params, tokens, lengths, 0.0, 8, live=[True, False, True], oracle=full)
    assert len(held[1]) == 1 and held[0] == plain[0][:17] and counters["verify_positions"] == 32


def test_sampled_rows_take_one_token_a_step_from_the_plain_sampler(params, tokens):
    """(c) A sampled row emits one token a step, drawn by the plain sampler
    with the plain chunk's key from the first position's logits: its
    stream is the plain chunk's token for token (so the emitted
    distribution is the plain sampler's), and no draft is offered for
    it."""
    lengths = [20, 33, 9]
    plain, _ = _chunks(PLAIN, {k: v for k, v in params.items() if k != "mtp"}, tokens, lengths, 0.8, 8, key=5)
    sampled, counters = _chunks(CFG, params, tokens, lengths, 0.8, 8, key=5)
    assert sampled == plain and all(len(r) == 9 for r in sampled)
    greedy, _ = _chunks(PLAIN, {k: v for k, v in params.items() if k != "mtp"}, tokens, lengths, 0.0, 8)
    assert sampled != greedy  # the draw is a draw
    assert counters["draft_proposed"] == 0 and counters["decode_tokens_emitted"] == 24
    assert counters["verify_positions"] == 48


# -- (d) the state after a rejected draft -------------------------------------------------


def test_after_rejected_drafts_the_state_is_that_of_a_run_that_never_drafted(params, tokens, want):
    """(d) The ring argument, at a window of 8 where every row wraps: a row
    walked through the verify step with WRONG drafts at every step, against
    the same row walked with TRUE drafts (no rejection ever): where the
    state can be read again it is the same.  A full layer's rows up to the
    length, the module's rows, ``h_last``; a ring's rows that hold a
    position a later query may see (the last window - 1 positions)."""
    row, start = tokens[2], 24
    state0, _ = _prefill(params, _serving(CFG).init_state(1, T), row, (16, 8))
    rejected, logits, _ = _verify_walk(params, state0, row, start, (False,))
    kept, _, _ = _verify_walk(params, state0, row, start, (True,))
    assert _worst(logits, want[2][0]) <= ATOL
    assert max(logits) == N - 3
    length = N - 2  # both walks have written the true tokens of positions 0..N-3
    for (mixer, _), a, b in zip(CFG.layer_kinds, rejected[:L], kept[:L]):
        if mixer == "full":
            np.testing.assert_allclose(a["k"][0, :length], b["k"][0, :length], atol=1e-5)
            np.testing.assert_allclose(a["v"][0, :length], b["v"][0, :length], atol=1e-5)
        else:
            # Ring row p % W for the positions the next query (at `length`)
            # may see: length - W + 1 .. length - 1.
            for p in range(length - W + 1, length):
                np.testing.assert_allclose(a["ring_k"][0, p % W], b["ring_k"][0, p % W], atol=1e-5)
    np.testing.assert_allclose(rejected[L]["k"][0, : length - 1], kept[L]["k"][0, : length - 1], atol=1e-5)
    np.testing.assert_allclose(rejected[L + 1]["h_last"], kept[L + 1]["h_last"], atol=1e-5)
    # The ring's stale row is there (position `length`, the last wrong
    # draft's, where position `length - W` was) and is the one the ring's
    # rule masks; so is the full layer's row past the length.
    stale = length % W
    assert not np.allclose(rejected[1]["ring_k"][0, stale], kept[1]["ring_k"][0, stale], atol=1e-3)
    assert not np.allclose(rejected[3]["k"][0, length], kept[3]["k"][0, length], atol=1e-3)


# -- (e) snapshots and grafts ---------------------------------------------------------------


def test_a_restored_snapshot_and_grafted_rows_equal_a_cold_run_bit_for_bit(model, params, tokens, want):
    """(e) Slot 0 prefills 48 tokens in chunks of 16 (two windows each: a
    ring wraps twice inside a chunk) and its state is saved at the
    boundary 32; slot 2 takes the full layers' and the module's rows by
    ``graft_prefix`` and the rings and ``h_last`` from the snapshot, then
    runs the same third chunk: state and logits equal slot 0's to the
    bit."""
    state = model.init_state(3, T)
    state, _ = _prefill(params, state, tokens[0], (16, 16), slot=0)
    snap = model.save_state(state, 0)
    assert [set(s) for s in snap] == [{"ring_k", "ring_v"}] * 6 + [{"h_last"}]

    def third(state, slot):
        toks = jnp.asarray(tokens[:1, 32:48])
        return _chunk_program(CFG)(params, state, toks, jnp.int32(32), jnp.int32(16), jnp.int32(slot))

    state, cold = third(state, 0)
    np.testing.assert_allclose(cold, want[0][0][32:48], atol=ATOL)
    state = model.restore_state(model.graft_prefix(state, 0, 2, 32), 2, snap)
    state, warm = third(state, 2)
    assert np.array_equal(cold, warm)
    for i, layer in enumerate(jax.tree.map(np.asarray, state)):
        for name, leaf in layer.items():
            upto = (47 if i == L else 48) if name in hybrid.ROW_LEAVES else leaf.shape[1]
            assert np.array_equal(leaf[0, :upto], leaf[2, :upto]), (i, name)
    # Then decoding from both slots gives the same module drafts.
    first, _, _ = _verify_programs(CFG)
    two = jax.tree.map(lambda a: a[jnp.asarray([0, 2])], state)
    _, mlg = first(params, two, jnp.asarray([5, 5]), jnp.asarray([48, 48]), jnp.ones((2,), jnp.int32))
    assert np.array_equal(np.asarray(mlg[0]), np.asarray(mlg[1]))
    # Without the snapshot the rings and h_last are another occupant's.
    _, stale = third(model.graft_prefix(state, 0, 1, 32), 1)
    assert np.abs(np.asarray(stale) - np.asarray(cold)).max() > 1e-2
    assert model.snapshot_bytes == 6 * 2 * W * 2 * 16 * 4 + CFG.d_model * 4
    assert model.state_bytes(2)["draft"] == 2 * (2 * T * 2 * 16 * 4 + CFG.d_model * 4)


# -- (f) controls ------------------------------------------------------------------------------


CONTROLS = {
    "no_qk_norm": dict(qk_norm=False),
    "rope_on_full": dict(rope_full=CFG.rope_window),
    "no_window": dict(sliding_window=T),
    "unscaled_routing_weights": dict(routed_scaling=1.0),
    "no_shared_expert": dict(shared_d_ff=0),
}


@pytest.mark.parametrize("control", sorted(CONTROLS))
def test_each_mechanism_switched_off_fails_the_whole_sequence_comparison(control, params, tokens, want):
    """(f) The comparison of (a) sees each mechanism."""
    cfg = dataclasses.replace(CFG, **CONTROLS[control])
    hidden, _, _ = jax.jit(_serving(cfg).prefill_cold)(params, jnp.asarray(tokens[:1]), jnp.asarray([N]))
    got = np.asarray(_serving(cfg).logits(params, hidden))[0]
    assert np.abs(got - want[0][0]).max() > 1e-2
    if control == "no_window":  # the first window's positions see the same keys
        np.testing.assert_allclose(got[:W], want[0][0][:W], atol=ATOL)


def test_a_rejected_position_counted_as_written_fails_the_verify_comparison(params, tokens, want):
    """(f) ``stale_reject``: after a rejected draft the next step starts two
    positions on, so the draft's stale row is read as a token's: the
    comparison of (b) on wrong drafts sees it."""
    state, _ = _prefill(params, _serving(CFG).init_state(1, T), tokens[1], (16, 16, 8))
    _, logits, _ = _verify_walk(params, state, tokens[1], 40, (False,), stale=True)
    assert _worst(logits, want[1][0]) > 1e-2


# -- (g) the shares -------------------------------------------------------------------------------


def test_the_four_shares_add_up_to_the_uncut_reference(params):
    """(g) The test that ties the share to the model: one expert layer, all
    16 experts in the uncut reference (``held`` 16, ``offset`` 0); each of
    four shares of 4 computes its routed part through the program's sorted
    dispatch; the shared expert is counted once."""
    whole = dataclasses.replace(CFG, experts_held=16, expert_offset=0)
    lp = dict(hybrid.init_params(
        dataclasses.replace(whole, layer_kinds=(("full", "experts"),)), jax.random.PRNGKey(5)
    )["layers"][0])
    h = jnp.asarray(np.random.RandomState(2).randn(1, 40, CFG.d_model), jnp.float32)
    valid = jnp.ones((1, 40), bool)
    with jax.default_matmul_precision("highest"):
        uncut = ref.mlp(h[0], lp, ref._dims(whole, 16, 0), "experts")
    total = 0.0
    for share in range(4):
        cfg = dataclasses.replace(CFG, expert_offset=4 * share)
        mine = {**lp, "w_gu_e": lp["w_gu_e"][4 * share : 4 * share + 4],
                "w_down_e": lp["w_down_e"][4 * share : 4 * share + 4]}
        y, counters, _ = hybrid._expert_layer(h, mine, valid, cfg, None)
        shared = hybrid._swiglu(h[0], lp["w_gu_s"], lp["w_down_s"])
        total = total + (y[0] - shared)  # this share's routed part alone
        # The plain reference, given the same share, leaves out the same.
        with jax.default_matmul_precision("highest"):
            part = ref.mlp(h[0], mine, ref._dims(cfg, 4, 4 * share), "experts")
        np.testing.assert_allclose(y[0], part, atol=ATOL)
        assert int(counters[0]) == 40 * 2
    np.testing.assert_allclose(total + shared, uncut, atol=ATOL)


# -- what is not served ------------------------------------------------------------------------------


def test_what_is_not_served_is_refused_with_the_reason(model):
    assert isinstance(serving_model(CFG, None, T), HybridServing) and model.draft == "mtp"
    model.check_supported()  # the model's own draft over rings and rows is served
    with pytest.raises(ValueError, match="int8 weights are not served"):
        model.prepare_params(None, quantize=True, matmul_kernel="xla", seed=0)
    with pytest.raises(ValueError, match="int8 state"):
        serving_model(dataclasses.replace(CFG, kv_dtype="int8"), None, T).check_supported()
    # A draft over KDA state: refused, with the reason that stays true.
    kda = dataclasses.replace(CFG, layer_kinds=(("kda", "experts"), ("full", "experts")))
    with pytest.raises(ValueError, match="KDA state.*rolled back"):
        serving_model(kda, None, T).check_supported()
    assert model.counter_names[-5:] == HybridServing.DRAFT_COUNTERS
    assert serving_model(PLAIN, None, T).counter_names[-1] == "attn_rows_dense_full_prefill"


# -- (c) through the scheduler: a full house (rows that end inside a step: ------------------------
# tests/test_own_draft_serving.py) -----------------------------------------------------------------


def _generate(scheduler, prompts, ns, temperature=0.0):
    scheduler.stop()
    outs = [[] for _ in prompts]
    done = [threading.Event() for _ in prompts]
    for i, p in enumerate(prompts):
        assert scheduler.submit(Request(
            token_ids=list(p),
            sampling=SamplingParams(temperature=temperature, top_p=1.0, max_tokens=ns[i]),
            on_token=outs[i].append, on_done=lambda _r, i=i: done[i].set(),
            eos_id=None, id=f"t{i}-{len(p)}",
        ))
    scheduler.start()
    assert all(ev.wait(300) for ev in done)
    return outs


def test_a_full_house_goes_ahead_under_the_draft_with_the_lengths_on_the_device(tokens):
    """(c) Five requests on four slots: every slot holds a request, so each
    decode chunk is dispatched before the one in front of it is fetched,
    and how far that one's kept drafts took a row only the device knows
    (``Scheduler._carried_len``).  With drafts right at two positions in
    three the streams equal the draft-off ones token for token, rows that
    end inside a chunk whose successor is already on the device included
    (the successor's tokens for them are dropped)."""
    prompts = [tokens[2, :9].tolist(), tokens[0, :20].tolist(), tokens[1, :31].tolist(),
               tokens[0, :45].tolist(), np.random.RandomState(7).randint(0, 512, size=100).tolist()]
    ns = [10, 10, 13, 50, 40]  # positions 9-19, 20-30, 31-44, 45-95, 100-128 (max_len)
    streams, snaps = {}, {}
    for name, cfg in (("off", PLAIN), ("on", CFG)):
        s = Scheduler(cfg, None, max_batch=4, max_len=T, decode_chunk_size=4, seed=3,
                      prefill_chunk_tokens=32, prefix_cache="shared")
        if name == "on":
            table = np.zeros((4, T + 2), np.int32)
            for p, o in zip(prompts, streams["off"]):
                table[:, len(p) : len(p) + len(o)] = o
            table = np.where(np.arange(T + 2)[None, :] % 3 == 0, (table + 1) % 512, table)
            s._decode_chunk = _Oracle(cfg, None, T, table).make_decode_chunk()
        s.start()
        try:
            streams[name] = _generate(s, prompts, ns)
            snaps[name] = s.stats.snapshot()
        finally:
            s.stop()
    assert streams["on"] == streams["off"]
    assert [len(o) for o in streams["on"]] == [10, 10, 13, 50, 28]
    on = snaps["on"]
    assert on["decode_chunks_ahead"] > 0 and snaps["off"]["decode_chunks_ahead"] > 0
    assert on["spec_accepted"] > 0 and on["decode_tokens_dropped"] > 0
    # Two tokens a step where a draft was kept: fewer chunks than the plain run's.
    assert on["decode_chunks"] < snaps["off"]["decode_chunks"]
