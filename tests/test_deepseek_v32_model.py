"""``models/hybrid.py``'s ``deepseek_v32`` family (the indexed ``mla`` layer
in every layer, routing in groups, and the model's own prediction module,
a latent block with an indexer of its own, as the draft of every decode
step) against the plain reference, ``models/deepseek_v32_reference.py``,
at a tiny size that keeps the ratios of the benchmark's cut: a dense first
layer and two expert layers, an indexer of 2 heads that keeps 24 rows
under prompts of 80 (a verify step works on both sides of it), YaRN past
an original context of 32, 16 router outputs in 2 groups of which 1 is
kept, 4 held (HALF a group) and 2 a token, the module held.  Seeded random
float32 weights; logits and selected sets are compared, never sampled
tokens.

Tolerance: both sides are float32 at the highest matmul precision
(conftest.py) and differ by the order of their sums (the online softmax
over blocks, the absorbed products, the sorted dispatch).  Logits are
O(4); 2e-4 absolute is about 50 float32 ulps of the largest, and each
mechanism switched off (the controls below) moves a logit by 1e-2 or more.
"""

import contextlib
import dataclasses
import functools
import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import chip_smoke
from generativeaiexamples_tpu.engine.serving_models import HybridServing
from generativeaiexamples_tpu.models import deepseek_v32_reference as ref
from generativeaiexamples_tpu.models import hybrid
from generativeaiexamples_tpu.ops import mla

ATOL = 2e-4
CFG = hybrid.PRESETS["deepseek_v32-tiny"]()
PLAIN = dataclasses.replace(CFG, mtp_layers=0)  # the draft off
L = CFG.n_layers  # 3: the module's block is state entry L
T, N, CHUNK = 128, 80, 16
TOPK = CFG.index_topk  # 24
SLOTS = 2  # the prompt lives in the last; the first holds nothing


@pytest.fixture(scope="module")
def params():
    return _serving(CFG).prepare_params(None, quantize=False, matmul_kernel="xla", seed=3)


@pytest.fixture(scope="module")
def tokens():
    return np.random.RandomState(0).randint(0, CFG.vocab_size, size=(3, N)).astype(np.int32)


@pytest.fixture(scope="module")
def want(params, tokens):
    """The reference over row 0: (stack logits (N, V), module logits
    (N - 1, V), the kept sets of the three layers and of the module)."""
    kept, x = [], None
    for _, x, mask in ref.layers(params, CFG, tokens[0]):
        kept.append(np.asarray(mask))
    xm, mask = ref.mtp_hidden_states(params, CFG, x, tokens[0])
    kept.append(np.asarray(mask))
    return np.asarray(ref.head(params, CFG, x)), np.asarray(ref.mtp_head(params, CFG, xm)), kept


@functools.lru_cache(maxsize=None)
def _serving(cfg):
    return HybridServing(cfg, None, T)


class _Kept:
    """What the program's selections kept, by (block, position): a tap on
    ``mla.select_mask`` (a chunk) and ``mla.select_rows`` (a step) of the
    programs traced under :meth:`tapped`, in the order the blocks are
    traced."""

    def __init__(self):
        self.sets = {}

    def _hand(self, block, chunk, scores, mask):
        s, width = scores.shape[-2:]
        pos = jnp.sum(scores > -jnp.inf, axis=-1) - 1
        if chunk:  # one row at consecutive positions; the module's first may be -1
            pos = pos[..., :1] + jnp.arange(s)

        def keep(pos, mask):
            for p, row in zip(np.asarray(pos), np.asarray(mask)):
                if p >= 0 and row.any():
                    self.sets[block, int(p)] = row

        jax.debug.callback(keep, pos.reshape(-1), mask.reshape(-1, width))

    @contextlib.contextmanager
    def tapped(self, blocks):
        plain_mask, plain_rows = mla.select_mask, mla.select_rows
        order = itertools.count()

        def select_mask(scores, k):
            mask = plain_mask(scores, k)
            self._hand(blocks[next(order)], True, scores, mask)
            return mask

        def select_rows(scores, k):
            idx, keep = plain_rows(scores, k)
            n = scores.shape[0]  # one row a slot and position
            mask = jnp.zeros(scores.shape, bool).at[jnp.arange(n)[:, None], idx].set(keep)
            self._hand(blocks[next(order)], False, scores, mask)
            return idx, keep

        mla.select_mask, mla.select_rows = select_mask, select_rows
        try:
            yield
        finally:
            mla.select_mask, mla.select_rows = plain_mask, plain_rows
        assert next(order) == len(blocks)


KEPT = _Kept()
STACK, MODULE = list(range(L)), [L]
MINE = jnp.arange(SLOTS) == SLOTS - 1
ON = MINE.astype(jnp.int32)


def _at(value):
    """``value`` for the prompt's slot, 0 for the one that holds nothing."""
    return jnp.where(MINE, jnp.asarray(value, jnp.int32), 0)


@functools.lru_cache(maxsize=None)
def _programs(cfg):
    """The calls the scheduler's programs make: a chunk of the last slot
    beside a pad row through ``prefill_rows`` (in place, one window), the
    module's catch-up, the verify step's two halves, and the one-query
    step; all over both slots, all tapped."""
    m = _serving(cfg)
    slots = jnp.arange(SLOTS, dtype=jnp.int32)

    @jax.jit
    def chunk(params, state, toks, start, n):
        with KEPT.tapped(STACK + MODULE if cfg.draft else STACK):
            state, hidden, aux = m.prefill_rows(
                params, state, jnp.where(MINE[:, None], toks[None], 0), _at(start), _at(n), slots, T)
        return state, m.logits(params, hidden[-1]), aux

    @jax.jit
    def first(params, state, tok, lens):
        with KEPT.tapped(MODULE):
            state, lg, _ = m.draft_from_last(params, state, _at(tok), _at(lens), ON, T)
        return state, lg[-1]

    @jax.jit
    def stack(params, state, tok, draft, lens):
        with KEPT.tapped(STACK):
            state, hidden, lg, c = m.verify_stack(params, state, _at(tok), _at(draft), _at(lens), ON, T)
        return state, hidden, lg[-1], c

    @jax.jit
    def module(params, state, hidden, following, lens, n):
        with KEPT.tapped(MODULE):
            state, lg, _ = m.verify_module(
                params, state, hidden, jnp.where(MINE[:, None], following[None], 0), _at(lens), _at(n), T)
        return state, lg[-1]

    @jax.jit
    def one(params, state, tok, lens):
        with KEPT.tapped(STACK):
            state, lg, _ = m.decode_step(params, state, _at(tok), _at(lens), ON, T)
        return state, lg[-1]

    return m, chunk, first, stack, module, one


def _prefill(params, row, n_prefill, cfg=CFG, state=None, start=0):
    """Chunked prefill of ``row[start:n_prefill]`` in chunks of ``CHUNK``
    (the last padded); returns (state, logits at those positions)."""
    m, chunk = _programs(cfg)[:2]
    state = m.init_state(SLOTS, T) if state is None else state
    got = []
    for at in range(start, n_prefill, CHUNK):
        n = min(CHUNK, n_prefill - at)
        toks = np.zeros((CHUNK,), np.int32)
        toks[:n] = row[at : at + n]
        state, lg, _ = chunk(params, state, jnp.asarray(toks), at, n)
        got.append(np.asarray(lg)[:n])
    return state, np.concatenate(got)


def _verify_walk(params, state, row, start, drafts_right, stop=None):
    """Walk ``row`` from ``start`` through the verify step, teacher-forced:
    step by step the draft is the true next token (``drafts_right`` True at
    that step) or a wrong one.  Yields after each step (state, position,
    positions emitted, the stack's logits (2, V), the module's (V,))."""
    _, _, first, stack, module, _ = _programs(CFG)
    state, mlg = first(params, state, row[start], start)
    yield state, start - 1, 0, None, np.asarray(mlg)
    pos, step = start, 0
    while pos + 2 < (stop or len(row)):
        right = bool(drafts_right[step % len(drafts_right)])
        draft = int(row[pos + 1]) if right else (int(row[pos + 1]) + 1) % CFG.vocab_size
        state, hidden, lg, _ = stack(params, state, row[pos], draft, pos)
        n = 2 if right else 1
        state, mlg = module(params, state, hidden, jnp.asarray(row[pos + 1 : pos + 3]), pos, n)
        yield state, pos, n, np.asarray(lg), np.asarray(mlg)
        pos += n
        step += 1


# -- (f) the mapping ------------------------------------------------------------------


def _millions(cfg, mixer, mlp):
    """Parameters of one layer, the norms and the router's bias left out."""
    shapes = hybrid._layer_shapes(cfg, mixer, mlp)
    return sum(
        int(np.prod(shape)) for name, (shape, _) in shapes.items()
        if "norm" not in name and name != "router_bias"
    ) / 1e6


def test_the_catalog_row_gives_the_counted_parameters():
    """(f) The public keys whole: 61 indexed latent layers, three of them
    dense, 256 experts in 8 groups of which 4 are kept, YaRN with its
    magnitude term in the softmax scale, one module whose block is a latent
    layer; and the cut's parameters as ISSUE 53 counts them."""
    whole = hybrid.from_hf_config(hybrid.DEEPSEEK_V32, max_len=64, draft="mtp")
    assert type(whole) is hybrid.PredictingLatentConfig
    assert whole.layer_kinds == (("mla", "dense"),) * 3 + (("mla", "experts"),) * 58
    assert (whole.n_experts, whole.experts_held, whole.n_group, whole.topk_group) == (256, 256, 8, 4)
    assert (whole.index_n_heads, whole.index_head_dim, whole.index_topk) == (64, 128, 2048)
    assert (whole.mtp_layers, whole.mtp_kind, whole.draft) == (1, ("mla", "experts"), "mtp")
    assert not (whole.mla_out_gate or whole.latent_rescale or whole.layers_of("mla_window"))
    assert whole.rope_latent.rope_type == "yarn" and whole.rope_latent.attention_factor == 1.0
    assert whole.softmax_mscale == pytest.approx(0.1 * np.log(40) + 1) == pytest.approx(1.3689, abs=1e-4)
    assert not whole.rows_only  # h_last exists only as of the last token
    cut = hybrid.PRESETS["deepseek-v3.2-l5e16"]()
    assert cut.layer_kinds == (("mla", "dense"),) + (("mla", "experts"),) * 4
    assert (cut.n_experts, cut.experts_held, cut.expert_offset, cut.vocab_size) == (256, 16, 0, 16160)
    mixer = _millions(cut, "mla", "none")
    assert mixer == pytest.approx(201.07, abs=1e-2)  # 201.064
    assert _millions(cut, "mla", "dense") == pytest.approx(597.43, abs=1e-2)
    assert _millions(cut, "mla", "experts") == pytest.approx(951.58, abs=1e-2)
    shapes = jax.eval_shape(lambda: hybrid.init_params(cut, jax.random.PRNGKey(0)))
    assert sum(x.size for x in jax.tree.leaves(shapes)) == pytest.approx(5689.9e6, rel=1e-4)
    # A latent row in whole lanes and an index key, six blocks: 9,216 B a token.
    by = hybrid.state_bytes(cut, 16, 16384)
    assert by["full"] + by["draft"] == 16 * 16384 * 9216 + 16 * 7168 * 2
    assert cut.snapshot_bytes(16384) == 7168 * 2  # h_last and nothing else
    off = hybrid.from_hf_config({**hybrid.DEEPSEEK_V32, **hybrid.DEEPSEEK_V32_L5E16_CUT}, max_len=64)
    assert (off.mtp_layers, off.draft, off.rows_only) == (0, "", True)


@pytest.mark.parametrize("bad, kwargs, match", [
    ({"quantization_config": {"quant_method": "fp8"}}, {}, "FP8"),
    ({"torch_dtype": "float8_e4m3fn"}, {}, "FP8"),
    ({"num_nextn_predict_layers": 2}, {"draft": "mtp"}, "exactly one prediction module"),
    ({}, {"draft": "ngram"}, "only the model's own module"),
    ({"rope_scaling": None}, {}, "YaRN"),
    ({"scoring_func": "softmax"}, {}, "sigmoid"),
    ({"index_topk": 0}, {}, "indexer"),
    ({}, {"kv_dtype": "int8"}, "int8 state"),
], ids=["fp8", "fp8_dtype", "two_modules", "other_draft", "no_yarn", "softmax", "no_indexer", "int8"])
def test_what_the_family_does_not_serve_is_refused_with_the_reason(bad, kwargs, match):
    """(f) What ``not_served`` lists, each with its reason."""
    with pytest.raises(ValueError, match=match):
        hybrid.from_hf_config({**hybrid.DEEPSEEK_V32_TINY, **bad}, max_len=64, **kwargs).state_dtype


def test_a_draft_over_latent_rows_is_supported_and_the_other_refusals_stand():
    """``check_supported`` passes ``draft="mtp"`` over latent rows and
    index keys, and still refuses a module whose block keeps a ring."""
    model = _serving(CFG)
    model.check_supported()
    assert model.draft == "mtp" and model.one_window and model.rows_in_place and not model.cut_anywhere
    with pytest.raises(ValueError, match="rows a position"):
        dataclasses.replace(CFG, mtp_kind=("mla_window", "experts"))
    names = model.counter_names
    assert names[-5:] == HybridServing.DRAFT_COUNTERS
    assert names[-7:-5] == ("attn_rows_gathered_verify", "attn_rows_needed_verify")


# -- (a) chunks, then verify steps: logits and kept sets ------------------------------


@pytest.mark.parametrize("n_prefill, drafts", [(48, (True,)), (16, (True, False, False, True)), (40, (False,))],
                         ids=["past_topk_true", "before_topk_mixed", "wrong"])
def test_chunks_then_verify_steps_match_the_reference_in_logits_and_kept_sets(
        n_prefill, drafts, params, tokens, want):
    """(a) Chunked prefill in place beside a pad row, then the rest of the
    row through the verify step (true drafts, wrong ones, a mixture),
    starting before ``index_topk`` and past it: the stack's logits at both
    positions of a step, the module's, and the sets every block keeps for
    each position, against the reference's full forward pass."""
    logits, modules, kept = want
    row = tokens[0]
    KEPT.sets.clear()
    state, got = _prefill(params, row, n_prefill)
    np.testing.assert_allclose(got, logits[:n_prefill], atol=ATOL)
    seen = last = 0
    for state, pos, n, lg, mlg in _verify_walk(params, state, row, n_prefill, drafts):
        np.testing.assert_allclose(mlg, modules[pos + max(n, 1) - 1], atol=ATOL)
        if lg is not None:
            np.testing.assert_allclose(lg[:n], logits[pos : pos + n], atol=ATOL)
            seen, last = seen + n, pos + n - 1
    assert seen >= N - n_prefill - 3
    jax.effects_barrier()
    # A verify step's second position with a WRONG draft keeps a set of its
    # own too, which the next step overwrites: what stands at the end is
    # what the true tokens kept, up to the last position a step emitted.
    judged = 0
    for (block, pos), mine in sorted(KEPT.sets.items()):
        if pos > last or pos >= kept[block].shape[0]:
            continue  # a last wrong draft's; the module has no position N - 1
        np.testing.assert_array_equal(mine[: kept[block].shape[1]], kept[block][pos], err_msg=f"{block} {pos}")
        judged += pos >= TOPK
    # Every block at every position past index_topk, on both sides of the
    # prefill's end (a chunk that ends under index_topk keeps every row and
    # calls no selection).
    assert judged >= (L + 1) * (N - max(TOPK, n_prefill) - 4)


def test_a_verify_step_is_the_step_form_and_counts_both_positions(params, tokens):
    """No chunk site is recorded for a decode program: with an indexer and
    two queries a slot the mixer gathers (``attn_latent_sparse_verify``),
    in the stack and in the module's block; the counters count both
    positions, and the rows needed are the union of the two kept sets of
    the slot that decodes."""
    from generativeaiexamples_tpu.ops import dispatch

    row = tokens[1]
    state, _ = _prefill(params, row, 48)
    _, _, first, stack, module, _ = _programs(CFG)
    state, _ = first(params, state, row[48], 48)
    state, hidden, _, c = stack(params, state, row[48], row[49], 48)
    module(params, state, hidden, jnp.asarray(row[49:51]), 48, 2)
    paths = dispatch.TAKEN
    for site in (f"index_scores b={SLOTS} s=2 t={T}", f"attn_latent_sparse_verify b={SLOTS} t={T} k={TOPK}",
                 f"mtp_index_scores b={SLOTS} s=2 t={T}", f"mtp_attn_latent_sparse_verify b={SLOTS} t={T} k={TOPK}",
                 f"mtp_index_scores b={SLOTS} s=1 t={T}", f"mtp_attn_latent_sparse_decode b={SLOTS} t={T} k={TOPK}"):
        assert paths[site] == "xla", (site, sorted(paths))
    assert not [s for s in paths if "chunk" in s and " s=2 " in s]
    by = dict(zip(CFG.row_counters + CFG.step_counters, np.asarray(c)[len(hybrid.moe.COUNTERS):].tolist()))
    assert by["read_latent"] == by["read_selected"] == by["gathered_verify"] == L * SLOTS * 2 * TOPK
    assert by["index_pairs"] == L * SLOTS * 2 * T and by["read_index"] == L * SLOTS * T
    assert by["seen_latent"] == L * (49 + 50)
    # Two adjacent positions of one slot: between one set and one row more, and two sets.
    assert L * (TOPK + 1) <= by["needed_verify"] <= L * 2 * TOPK


def test_the_rows_needed_are_the_union_of_the_kept_sets_ties_and_all():
    """``mla.rows_needed`` rebuilds each kept set from its last entry: equal
    to the union of what ``select_rows`` gathered, with scores that tie at
    the cut (an index score is exactly 0 where every head's ``relu`` is),
    queries that see fewer than ``k`` rows, and queries that do not count."""
    rng = np.random.RandomState(7)
    scores = np.round(rng.randn(5, 2, 64), 1).astype(np.float32)  # many ties
    scores = np.where(rng.rand(5, 2, 64) < 0.3, 0.0, scores).astype(np.float32)
    scores[1, :, 10:] = -np.inf  # sees 10 rows: keeps them all
    scores[2, 1] = -np.inf  # a position that does not count
    scores[3] = -np.inf  # a slot that does not decode
    counts = np.isfinite(scores).any(-1)
    for k in (8, 24, 64):
        idx, keep = mla.select_rows(jnp.asarray(scores).reshape(10, 64), k)
        idx, keep = np.asarray(idx).reshape(5, 2, -1), np.asarray(keep).reshape(5, 2, -1)
        want = [len({int(i) for t in range(2) for i, on in zip(idx[b, t], keep[b, t]) if on}) for b in range(5)]
        got = mla.rows_needed(jnp.asarray(scores), jnp.asarray(idx), jnp.asarray(keep), jnp.asarray(counts))
        assert np.asarray(got).tolist() == want, k
        assert want[3] == 0 and want[1] == 10


# -- (b) a rejected draft leaves nothing behind ----------------------------------------


def test_a_stream_of_rejected_drafts_equals_one_query_steps_row_for_row(params, tokens):
    """(b) The same stream decoded with forced-wrong drafts through the
    verify step and through one-query steps: the logits, and after every
    step the latent rows and index keys of every layer up to the row's
    length (the rejected position ``p + 1`` lies past it: stale there,
    masked, and written over by the next step)."""
    row = tokens[2]
    state, _ = _prefill(params, row, 32)
    one = _programs(CFG)[5]
    plain = state
    pos = 32
    for state, at, n, lg, _ in _verify_walk(params, state, row, 32, (False,), stop=60):
        if lg is None:
            continue
        assert (at, n) == (pos, 1)
        plain, want_lg = one(params, plain, row[pos], pos)
        np.testing.assert_allclose(lg[0], want_lg, atol=ATOL)
        pos += 1
        for layer in range(L):
            for leaf in ("latent", "index_k"):
                np.testing.assert_allclose(
                    state[layer][leaf][-1, :pos], plain[layer][leaf][-1, :pos], atol=1e-5,
                    err_msg=f"layer {layer} {leaf} at {pos}")
        # The stale row is there, past the length, and the slot that holds nothing holds nothing.
        assert float(jnp.abs(state[0]["latent"][-1, pos]).max()) > 0
        assert float(jnp.abs(state[0]["latent"][0]).max()) == 0
    assert pos == 58  # 26 steps, a token each


# -- (c) the verify chunk's tokens are the plain chunk's ---------------------------------


class _Oracle(HybridServing):
    """The serving model with the module's drafts replaced: the state is
    moved exactly as it is, the draft is what ``oracle`` says."""

    def __init__(self, cfg, mesh, max_len, oracle):
        super().__init__(cfg, mesh, max_len)
        self.oracle = jnp.asarray(oracle, jnp.int32)

    def _said(self, at):
        rows = jnp.arange(self.oracle.shape[0])
        told = self.oracle[rows, jnp.minimum(at, self.oracle.shape[1] - 1)]
        return 50.0 * jax.nn.one_hot(told, self.cfg.vocab_size)

    def draft_from_last(self, params, cache, tokens, lengths, counts, window):
        cache, _, c = super().draft_from_last(params, cache, tokens, lengths, counts, window)
        return cache, self._said(lengths + 1), c

    def verify_module(self, params, cache, hidden, next_tokens, lengths, n_emit, window):
        cache, _, c = super().verify_module(params, cache, hidden, next_tokens, lengths, n_emit, window)
        return cache, self._said(lengths + n_emit + 1), c


def _chunks(cfg, params, tokens, lengths, n_steps, oracle=None, live=None):
    """Decode ``n_steps`` greedily from prompts prefilled cold; returns
    each row's emitted tokens and the chunk's counters by name."""
    m = _serving(cfg) if oracle is None else _Oracle(cfg, None, T, oracle)
    b = len(lengths)
    toks = np.zeros((b, 64), np.int32)
    for i, n in enumerate(lengths):
        toks[i, :n] = tokens[i, :n]
    hidden, small, _ = jax.jit(m.prefill_cold)(params, jnp.asarray(toks), jnp.asarray(lengths))
    state = m.graft_rows(m.init_state(b, T), small, jnp.arange(b), jnp.arange(b))
    last = jnp.take_along_axis(hidden, (jnp.asarray(lengths) - 1)[:, None, None], axis=1)[:, 0]
    first = jnp.argmax(m.logits(params, last), -1).astype(jnp.int32)
    out = m.make_decode_chunk()(
        params, state, first, jnp.asarray(lengths, jnp.int32), jax.random.PRNGKey(0),
        jnp.zeros((b,), jnp.float32), jnp.ones((b,), jnp.float32), jnp.zeros((b,), jnp.int32),
        n_steps, T, None if live is None else jnp.asarray(live),
    )
    if not cfg.draft:
        return [[int(first[i])] + np.asarray(out[1])[:, i].tolist() for i in range(b)], None
    _, toks_out, counts, _, aux = out
    toks_out, counts = np.asarray(toks_out), np.asarray(counts)
    rows = [[int(t) for r in range(n_steps) for t in toks_out[r, i, : counts[r, i]]] for i in range(b)]
    return [[int(first[i])] + rows[i] for i in range(b)], dict(zip(m.counter_names, np.asarray(aux).tolist()))


def test_greedy_rows_emit_the_plain_chunks_tokens_whatever_the_drafts(params, tokens):
    """(c) Token for token: the draft on (the module's own drafts, which
    random weights reject; an oracle's, right at two positions in three)
    against the draft off, over rows on both sides of ``index_topk``; a
    row that does not decode emits nothing.  ``draft_rows_rewritten``
    counts a row for every layer of the stack a rejection leaves."""
    lengths = [20, 33, 9]
    stack_only = {k: v for k, v in params.items() if k != "mtp"}
    plain, _ = _chunks(PLAIN, stack_only, tokens, lengths, 32)
    own, counters = _chunks(CFG, params, tokens, lengths, 16)
    for a, b in zip(own, plain):
        assert a == b[: len(a)] and len(a) >= 17
    assert counters["draft_proposed"] == 48 and counters["verify_positions"] == 96
    assert counters["decode_tokens_emitted"] == 48 + counters["draft_accepted"]
    assert counters["draft_rows_rewritten"] == L * (48 - counters["draft_accepted"])
    assert counters["attn_rows_gathered_verify"] > counters["attn_rows_needed_verify"] > 0
    full = np.zeros((3, T), np.int32)
    for i, n in enumerate(lengths):
        full[i, n : n + 33] = plain[i]
    mixed = np.where(np.arange(T)[None, :] % 3 == 0, (full + 1) % CFG.vocab_size, full)
    some, counters = _chunks(CFG, params, tokens, lengths, 16, oracle=mixed, live=[True, False, True])
    assert len(some[1]) == 1 and counters["verify_positions"] == 64
    for i in (0, 2):
        assert 17 < len(some[i]) < 33 and some[i] == plain[i][: len(some[i])]
    assert 0 < counters["draft_accepted"] < 32
    assert counters["draft_rows_rewritten"] == L * (32 - counters["draft_accepted"])


# -- (d) the shares of the experts ------------------------------------------------------


def test_the_four_shares_half_a_group_each_add_up_to_the_uncut_layer(params):
    """(d) Each rank's routed part (4 of 16 experts from its offset: HALF
    of one of the two routing groups) summed over the four ranks, with the
    shared expert counted once, is the uncut layer's output; program and
    reference alike.  A token's choices land on a rank only when its kept
    group is that rank's."""
    lp = params["layers"][1]
    h = jax.random.normal(jax.random.PRNGKey(5), (2, 24, CFG.d_model))
    valid = jnp.ones((2, 24), bool)
    rng = jax.random.PRNGKey(6)
    all_gu = jax.random.normal(rng, (16, CFG.d_model, 2 * CFG.moe_d_ff)) * CFG.d_model**-0.5
    all_down = jax.random.normal(jax.random.fold_in(rng, 1), (16, CFG.moe_d_ff, CFG.d_model)) * CFG.moe_d_ff**-0.5
    shared = hybrid._swiglu(h.reshape(-1, CFG.d_model), lp["w_gu_s"], lp["w_down_s"]).reshape(h.shape)
    whole_lp = {**lp, "w_gu_e": all_gu, "w_down_e": all_down}
    whole, _, _ = hybrid._expert_layer(h, whole_lp, valid, dataclasses.replace(CFG, experts_held=16), None)
    dims = ref._dims(CFG, None, None)
    parts, ref_parts, local = [], [], []
    for rank in range(4):
        cfg = dataclasses.replace(CFG, expert_offset=4 * rank)
        share = {**lp, "w_gu_e": all_gu[4 * rank : 4 * rank + 4], "w_down_e": all_down[4 * rank : 4 * rank + 4]}
        y, counters, _ = hybrid._expert_layer(h, share, valid, cfg, None)
        parts.append(y - shared)
        ref_parts.append(ref.routed_experts(h[0], share, {**dims, "offset": 4 * rank}))
        local.append(int(counters[1]))
    np.testing.assert_allclose(sum(parts) + shared, whole, atol=1e-5)
    uncut = ref.routed_experts(h[0], whole_lp, {**dims, "held": 16, "offset": 0})
    np.testing.assert_allclose(sum(ref_parts), uncut, atol=1e-5)
    np.testing.assert_allclose((whole - shared)[0], uncut, atol=1e-5)
    np.testing.assert_allclose(
        whole[0], ref.mlp(h[0], whole_lp, {**dims, "held": 16, "offset": 0}, "experts"), atol=1e-5)
    assert int(counters[0]) == 2 * 48 and sum(local) == 2 * 48  # every token chose 2 of the 16
    # One group kept of two: a token's two choices are both in ranks 0-1 or both in ranks 2-3.
    assert (local[0] + local[1]) % 2 == 0


# -- (g) a prefix hit at the template's boundary ----------------------------------------


def test_a_prefix_hit_at_the_template_restores_h_last_and_verifies_as_a_cold_row(params, tokens, want):
    """(g) The state of a model whose every block keeps rows alone still
    holds ``h_last`` as of the last token: the snapshot saved at the
    template's boundary holds that and nothing else; a hit grafts the rows
    (the module's among them), restores it, prefills its own suffix, and
    its first verify step equals the cold row's."""
    m = _serving(CFG)
    row = tokens[0]
    state, _ = _prefill(params, row, CHUNK)  # the template, in the last slot
    snap = m.save_state(state, SLOTS - 1)
    assert [sorted(part) for part in snap] == [["h_last"]]
    np.testing.assert_array_equal(snap[0]["h_last"], state[L + 1]["h_last"][-1])
    # Another request on the same template, then this one's slot is taken over by a hit from it.
    other = np.concatenate([row[:CHUNK], tokens[1][CHUNK:40]])
    state, _ = _prefill(params, other, 40, state=state, start=CHUNK)
    state = m.graft_prefix(state, SLOTS - 1, SLOTS - 1, CHUNK)
    state = m.restore_state(state, SLOTS - 1, snap)
    state, got = _prefill(params, row, 48, state=state, start=CHUNK)
    np.testing.assert_allclose(got, want[0][CHUNK:48], atol=ATOL)
    cold, _ = _prefill(params, row, 48)
    steps = [
        next(itertools.islice(_verify_walk(params, st, row, 48, (True,)), 1, None))
        for st in (state, cold)
    ]
    (_, _, _, hit_lg, hit_mlg), (_, _, _, cold_lg, cold_mlg) = steps
    np.testing.assert_allclose(hit_lg, cold_lg, atol=1e-5)
    np.testing.assert_allclose(hit_mlg, cold_mlg, atol=1e-5)
    np.testing.assert_allclose(hit_lg, want[0][48:50], atol=ATOL)
    # Without the restore the module's first row past the boundary joins the wrong h_last.
    lost = m.graft_prefix(_prefill(params, other, 40, state=_prefill(params, row, CHUNK)[0], start=CHUNK)[0],
                          SLOTS - 1, SLOTS - 1, CHUNK)
    lost, _ = _prefill(params, row, 48, state=lost, start=CHUNK)
    for leaf in ("latent", "index_k"):  # the module's row AT the boundary
        np.testing.assert_allclose(state[L][leaf][-1, CHUNK - 1], cold[L][leaf][-1, CHUNK - 1], atol=1e-5)
        assert float(jnp.abs(lost[L][leaf][-1, CHUNK - 1] - cold[L][leaf][-1, CHUNK - 1]).max()) > 1e-2


# -- (e) the controls -------------------------------------------------------------------

CONTROLS = ("no_groups", "no_mscale", "draft_shares_set", "no_selection", "last_2048", "no_index_relu")


@pytest.mark.parametrize("control", CONTROLS)
def test_each_mechanism_left_out_of_the_reference_leaves_the_program(params, tokens, want, control, monkeypatch):
    """(e) The controls of the chip's comparison, against the reference
    alone: the reference with one step changed stands apart from the
    program (whose logits are the unchanged reference's to ``ATOL``: the
    tests above) by 1e-2 or more, in the stack and in the module."""
    for name, stand_in in chip_smoke.deepseek_v32_patches(ref)[control].items():
        monkeypatch.setattr(ref, name, stand_in)
    ref._layer.clear_cache()  # a layer traced before this would keep the plain one
    try:
        off, off_m = (np.asarray(a) for a in ref.all_logits(params, CFG, tokens[0]))
    finally:
        monkeypatch.undo()
        ref._layer.clear_cache()
    worst = np.abs(off - want[0]).max(-1)
    assert worst.max() > 1e-2 and np.abs(off_m - want[1]).max() > 1e-2, (worst.max(),)
    if control in ("no_selection", "last_2048"):
        assert worst[:TOPK].max() < ATOL  # the first 24 queries keep every row they see
    if control == "draft_shares_set":  # position 0 has no position before it
        assert worst[0] < ATOL < worst[1]
