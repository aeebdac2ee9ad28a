"""The serving-path forms of the new mechanisms against their plain
forms: chunk-wise KDA against the token-by-token recurrence, absorbed MLA
against expanded, group-limited sigmoid routing and plain softmax routing
against a brute-force choice, sorted expert dispatch against every expert computed whole."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from generativeaiexamples_tpu.models import hybrid_reference as ref
from generativeaiexamples_tpu.models import mellum_reference as mellum_ref
from generativeaiexamples_tpu.ops import kda, mla, moe

F32 = jnp.float32


def _kda_inputs(seed, b, s, H, K, gate):
    r = np.random.RandomState(seed)
    q = kda.l2_normalize(jnp.asarray(r.randn(b, s, H, K), F32)) * K**-0.5
    k = kda.l2_normalize(jnp.asarray(r.randn(b, s, H, K), F32))
    v = jnp.asarray(r.randn(b, s, H, K), F32)
    # The safe gate's range is (-5, 0): "near -5" forgets the state in a
    # token, "near 0" keeps it over the whole run.
    lo, hi = {"near_floor": (-4.999, -4.5), "near_zero": (-1e-3, -1e-6),
              "mixed": (-4.999, -1e-6)}[gate]
    g = jnp.asarray(r.uniform(lo, hi, size=(b, s, H, K)), F32)
    beta = jnp.asarray(r.uniform(0.05, 0.95, size=(b, s, H)), F32)
    S0 = jnp.asarray(r.randn(b, H, K, K) * 0.1, F32)
    return q, k, v, g, beta, S0


def _token_by_token(q, k, v, g, beta, S0):
    def step(S, x):
        o, S = kda.kda_step(*x, S)
        return S, o

    xs = tuple(jnp.moveaxis(a, 1, 0) for a in (q, k, v, g, beta))
    S, o = jax.lax.scan(step, S0, xs)
    return jnp.moveaxis(o, 0, 1), S


@pytest.mark.parametrize("gate", ["near_floor", "near_zero", "mixed"])
def test_kda_chunkwise_matches_the_token_recurrence(gate):
    q, k, v, g, beta, S0 = _kda_inputs(3, 2, 80, 3, 16, gate)
    want_o, want_S = _token_by_token(q, k, v, g, beta, S0)
    got_o, got_S = kda.kda_chunked(q, k, v, g, beta, S0)
    # float32 at the highest precision on both sides; the chunk form
    # multiplies by exp(+-G) with |G| <= 80 and solves a 16 x 16 unit
    # triangular system, which costs a few ulps of the largest term.
    np.testing.assert_allclose(got_o, want_o, rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(got_S, want_S, rtol=2e-4, atol=2e-5)


def test_kda_a_token_with_beta_and_gate_zero_leaves_state_bit_equal():
    q, k, v, g, beta, S0 = _kda_inputs(4, 2, 32, 2, 16, "mixed")
    off = jnp.zeros_like(beta)
    _, S = kda.kda_chunked(q, k, v, g * 0.0, off, S0)
    assert np.array_equal(np.asarray(S), np.asarray(S0))
    _, S = kda.kda_step(q[:, 0], k[:, 0], v[:, 0], g[:, 0] * 0.0, off[:, 0], S0)
    assert np.array_equal(np.asarray(S), np.asarray(S0))


def test_kda_step_is_the_references_token():
    """The decode step against the plain reference's own recurrence."""
    q, k, v, g, beta, _ = _kda_inputs(5, 1, 24, 2, 16, "mixed")

    def token(S, x):
        q_t, k_t, v_t, g_t, b_t = x
        S = S * jnp.exp(g_t)[:, :, None]
        S = S + b_t[:, None, None] * k_t[:, :, None] * (
            v_t - jnp.einsum("hkv,hk->hv", S, k_t))[:, None, :]
        return S, jnp.einsum("hkv,hk->hv", S, q_t)

    _, want = jax.lax.scan(token, jnp.zeros((2, 16, 16)), (q[0], k[0], v[0], g[0], beta[0]))
    got, _ = _token_by_token(q, k, v, g, beta, jnp.zeros((1, 2, 16, 16)))
    np.testing.assert_allclose(got[0], want, rtol=1e-5, atol=1e-6)


def test_mla_absorbed_matches_expanded():
    r = np.random.RandomState(0)
    b, s, T, H, rank, nope, rope, vd = 2, 5, 40, 4, 32, 16, 8, 16
    q_nope = jnp.asarray(r.randn(b, s, H, nope), F32)
    q_rope = jnp.asarray(r.randn(b, s, H, rope), F32)
    latent = jnp.asarray(r.randn(b, T, rank + rope), F32)
    w_kvb = jnp.asarray(r.randn(rank, H * (nope + vd)) * rank**-0.5, F32)
    q_pos = jnp.asarray([[30, 31, 32, 33, 34], [3, 4, 5, 6, 7]], jnp.int32)
    kw = dict(rank=rank, nope=nope, v_dim=vd)
    a = mla.attend_expanded(q_nope, q_rope, latent, w_kvb, q_pos, **kw)
    c = mla.attend_absorbed(q_nope, q_rope, latent, w_kvb, q_pos, **kw)
    # The same sums in another order, float32.
    np.testing.assert_allclose(a, c, rtol=1e-4, atol=1e-5)
    # Causal: rows past a query's position change nothing.
    latent2 = latent.at[:, 36:].set(99.0)
    a2 = mla.attend_expanded(q_nope, q_rope, latent2, w_kvb, q_pos, **kw)
    np.testing.assert_allclose(a, a2, rtol=1e-6)


def test_rope_interleaved_rotates_pairs_by_position():
    x = jnp.asarray(np.random.RandomState(1).randn(1, 3, 2, 8), F32)
    pos = jnp.asarray([[0, 5, 9]], jnp.int32)
    y = mla.rope_interleaved(x, pos, 6e6)
    np.testing.assert_allclose(y[:, 0], x[:, 0], atol=1e-6)  # position 0: identity
    np.testing.assert_allclose(
        jnp.linalg.norm(y, axis=-1), jnp.linalg.norm(x, axis=-1), rtol=1e-5)
    want = ref._rope_pairs(jnp.broadcast_to(x[0, 1:2], (6, 2, 8)), 6e6)[5]
    np.testing.assert_allclose(y[0, 1], want, rtol=1e-5, atol=1e-6)


DIMS = {"E": 32, "G": 8, "topk_group": 4, "k": 4, "norm_topk": True, "scale": 2.5}
# The two routers served: sigmoid scores, a selection bias and a group
# limit (``hybrid_reference.routing``); softmax over all outputs, neither
# bias nor groups, weights renormalised to one (``mellum_reference.routing``).
ROUTERS = {
    "sigmoid_grouped": dict(
        route=dict(n_group=8, topk_group=4, scale=2.5), bias=True, total=2.5,
        brute=lambda x, w, bias: ref.routing(x, {"router": w, "router_bias": bias}, DIMS),
    ),
    "softmax_plain": dict(
        route=dict(n_group=1, topk_group=1, scale=1.0, score="softmax"), bias=False, total=1.0,
        brute=lambda x, w, bias: mellum_ref.routing(x, {"router": w}, {"k": 4, "norm_topk": True}),
    ),
}


@pytest.mark.parametrize("ties", [False, True])
@pytest.mark.parametrize("router", sorted(ROUTERS))
def test_group_limited_choice_matches_brute_force(router, ties):
    kind = ROUTERS[router]
    r = np.random.RandomState(7)
    n, D, E = 64, 16, 32
    x = jnp.asarray(r.randn(n, D), F32)
    w = jnp.asarray(r.randn(D, E), F32)
    bias = jnp.asarray(r.randn(E) * 0.3, F32) if kind["bias"] else None
    if ties:
        # Equal scores everywhere: every group and every expert ties, so
        # the rule (the lower index wins) decides the whole choice.
        w, bias = jnp.zeros_like(w), (jnp.zeros((E,), F32) if kind["bias"] else None)
    idx, weights = moe.route(x, w, bias, k=4, norm_topk=True, **kind["route"])
    dense = kind["brute"](x, w, bias)  # (n, E)
    got = np.zeros((n, E), np.float32)
    np.put_along_axis(got, np.asarray(idx), np.asarray(weights), axis=1)
    np.testing.assert_allclose(got, dense, rtol=1e-5, atol=1e-7)
    if ties:
        assert np.asarray(idx)[0].tolist() == [0, 1, 2, 3]
    if kind["route"]["n_group"] > 1:
        # Group limit: a token's experts lie in at most topk_group groups.
        assert max(len(set(row // 4)) for row in np.asarray(idx)) <= 4
    np.testing.assert_allclose(weights.sum(-1), kind["total"], rtol=1e-5)


def test_softmax_scores_under_a_selection_bias_keep_their_own_weights():
    """LongCat-Flash's router: softmax over all outputs (real experts, then
    identity experts), the bias only selects, the weights are the chosen
    scores times the scaling factor, not renormalised; the reference's
    stable sort and ``lax.top_k`` agree on a tie (the lower index)."""
    from generativeaiexamples_tpu.models import longcat_flash_reference

    r = np.random.RandomState(9)
    x, w = jnp.asarray(r.randn(64, 16), F32), jnp.asarray(r.randn(16, 48), F32)
    bias = jnp.asarray(r.randn(48) * 0.05, F32)
    for router, b in ((w, bias), (jnp.zeros_like(w), jnp.zeros_like(bias))):
        idx, weights = moe.route(x, router, b, k=5, n_group=1, topk_group=1, norm_topk=False,
                                 scale=6.0, score="softmax")
        dense = longcat_flash_reference.routing(
            x, {"router": router, "router_bias": b}, {"k": 5, "scale": 6.0})
        got = np.zeros((64, 48), np.float32)
        np.put_along_axis(got, np.asarray(idx), np.asarray(weights), axis=1)
        np.testing.assert_allclose(got, dense, rtol=1e-5, atol=1e-7)
    assert np.asarray(idx)[0].tolist() == [0, 1, 2, 3, 4]  # every score ties
    assert float(weights.sum(-1).max()) == pytest.approx(6 * 5 / 48)


def test_softmax_weights_without_renormalising_are_the_probabilities():
    r = np.random.RandomState(8)
    x, w = jnp.asarray(r.randn(16, 8), F32), jnp.asarray(r.randn(8, 32), F32)
    idx, weights = moe.route(x, w, None, k=4, n_group=1, topk_group=1, norm_topk=False,
                             scale=1.0, score="softmax")
    p = jax.nn.softmax(x @ w, axis=-1)
    np.testing.assert_allclose(weights, jnp.take_along_axis(p, idx, axis=-1), rtol=1e-5)
    assert float(weights.sum(-1).max()) < 1.0


def _experts(seed, held, D, F):
    r = np.random.RandomState(seed)
    return {
        "w_gu_e": jnp.asarray(r.randn(held, D, 2 * F) * D**-0.5, F32),
        "w_down_e": jnp.asarray(r.randn(held, F, D) * F**-0.5, F32),
    }


@pytest.mark.parametrize("zero_from", [None, 24], ids=["all_real", "identity_from_24"])
@pytest.mark.parametrize("kernel", ["dense_stand_in", "gmm_interpret"])
def test_sorted_dispatch_matches_every_expert_computed_whole(kernel, zero_from, monkeypatch):
    """``zero_from`` 24: the router's outputs 24-31 are identity experts
    (LongCat-Flash's zero-computation experts): a choice there adds ``w x``,
    has no row in the grouped products and is counted apart."""
    if kernel == "gmm_interpret":
        monkeypatch.setenv("GAIE_MOE_KERNEL_INTERPRET", "1")
    r = np.random.RandomState(11)
    n, D, F, held, offset = 48, 128, 128, 8, 8
    x = jnp.asarray(r.randn(n, D), F32)
    lp = _experts(2, held, D, F)
    idx = jnp.asarray(np.stack([r.choice(32, 4, replace=False) for _ in range(n)]), jnp.int32)
    w = jnp.asarray(r.uniform(0.1, 1.0, size=(n, 4)), F32)
    valid = jnp.asarray(np.arange(n) < 40)
    y, counters = moe.expert_mlp(x, idx, w, valid, lp, offset=offset, held=held, zero_from=zero_from)
    want = np.zeros((n, D), np.float32)
    rows = np.zeros(held, int)
    zero = 0
    for t in range(40):
        for e, wt in zip(np.asarray(idx)[t], np.asarray(w)[t]):
            if zero_from is not None and e >= zero_from:
                want[t] += wt * np.asarray(x[t])
                zero += 1
            if offset <= e < offset + held:
                gu = x[t] @ lp["w_gu_e"][e - offset]
                want[t] += wt * np.asarray((jax.nn.silu(gu[:F]) * gu[F:]) @ lp["w_down_e"][e - offset])
                rows[e - offset] += 1
    np.testing.assert_allclose(y, want, rtol=1e-4, atol=1e-5)
    assert not np.asarray(y)[40:].any()  # a padded position routes nowhere
    # one call; under a row tile of 128 rows and whole K, a stream an expert with a row
    assert counters.tolist() == [40 * 4, rows.sum(), (rows > 0).sum(), rows.max(), 1, (rows > 0).sum(), zero]
    assert (zero > 0) == (zero_from is not None)


def test_balanced_bias_evens_the_experts_load():
    """Aux-loss-free balancing to its fixed point on a sample: with the
    bias, fresh tokens of the same distribution load the experts far more
    evenly than without, and the choice stays group-limited."""
    r = np.random.RandomState(3)
    D, E = 32, 32
    w = jnp.asarray(r.randn(D, E) * D**-0.5, F32)
    shift = jnp.asarray(r.randn(D) * 0.5, F32)  # a common direction: some experts run hot

    def tokens(seed, n):
        return jnp.asarray(np.random.RandomState(seed).randn(n, D), F32) + shift

    bias = moe.balanced_bias(moe.scores(tokens(0, 4096), w), k=4, n_group=8, topk_group=4)

    def worst_load(b):
        idx, _ = moe.route(tokens(1, 4096), w, b, k=4, n_group=8, topk_group=4,
                           norm_topk=True, scale=2.5)
        load = np.bincount(np.asarray(idx).ravel(), minlength=E)
        return load.max() / load.mean()

    assert worst_load(jnp.zeros((E,), F32)) > 1.5
    assert worst_load(bias) < 1.25  # 512 rows an expert: the fullest of 32 reads ~1.1 by chance


# -- the latent path of the ``mistral4`` family ----------------------------------


@pytest.mark.parametrize("block", [8, 16, 48, 64, 4096])
def test_mla_blocks_with_an_online_softmax_match_the_whole_window(block):
    """``attend_blocks`` over blocks that divide the window of 96 (a size
    that does not is cut to their common divisor) against
    ``attend_expanded``: the same sums a block at a time."""
    r = np.random.RandomState(2)
    b, s, T, H, rank, nope, rope, vd = 2, 12, 96, 3, 32, 16, 8, 16
    q_nope = jnp.asarray(r.randn(b, s, H, nope), F32)
    q_rope = jnp.asarray(r.randn(b, s, H, rope), F32)
    latent = jnp.asarray(r.randn(b, T, rank + rope), F32)
    w_kvb = jnp.asarray(r.randn(rank, H * (nope + vd)) * rank**-0.5, F32)
    q_pos = jnp.asarray([70, 5])[:, None] + jnp.arange(s)[None, :]
    kw = dict(rank=rank, nope=nope, v_dim=vd)
    want = mla.attend_expanded(q_nope, q_rope, latent, w_kvb, q_pos, **kw)
    lengths = q_pos[:, -1] + 1
    got = mla.attend_blocks(q_nope, q_rope, latent, w_kvb, q_pos, lengths, block=block, **kw)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
    # Only the whole blocks up to the longest row are read: rows past
    # them may hold anything.
    cut = int(mla.rows_in_blocks(lengths, T, block).max())
    assert cut == min(-(-82 // np.gcd(T, block)) * np.gcd(T, block), T)
    spoiled = latent.at[:, cut:].set(jnp.nan)
    got2 = mla.attend_blocks(q_nope, q_rope, spoiled, w_kvb, q_pos, lengths, block=block, **kw)
    np.testing.assert_array_equal(np.asarray(got2), np.asarray(got))
    # A row that holds nothing reads nothing and gives zeros, not 0 / 0.
    none = mla.attend_blocks(q_nope, q_rope, latent, w_kvb, q_pos, jnp.zeros((b,), jnp.int32), block=block, **kw)
    assert not np.asarray(none).any()


def test_rope_interleaved_takes_yarn_frequencies_from_a_spec():
    from generativeaiexamples_tpu.models import mistral4_reference
    from generativeaiexamples_tpu.ops.rope import RopeSpec

    spec = RopeSpec(theta=10000.0, rope_type="yarn", factor=8.0, original_max=32, attention_factor=1.0)
    x = jnp.asarray(np.random.RandomState(4).randn(1, 90, 2, 8), F32)
    pos = jnp.arange(90, dtype=jnp.int32)[None]
    y = mla.rope_interleaved(x, pos, 10000.0, spec)
    inv = mistral4_reference.yarn_frequencies(8, 10000.0, 8.0, 32, 32.0, 1.0)
    np.testing.assert_allclose(y[0], mistral4_reference._rope_pairs(x[0], inv), rtol=1e-5, atol=1e-5)
    # Past the original context the blend departs from the plain frequencies.
    plain = mla.rope_interleaved(x, pos, 10000.0)
    assert float(jnp.abs(y - plain)[0, 40:].max()) > 0.1
    # An attention factor multiplies cos and sin.
    scaled = mla.rope_interleaved(x, pos, 10000.0, RopeSpec(**{**spec.__dict__, "attention_factor": 1.5}))
    np.testing.assert_allclose(scaled, 1.5 * y, rtol=1e-5, atol=1e-6)


def _lings_mixer_as_it_was(h, lp, st, pos, valid, n_valid, cfg, window, apart):
    """``models/hybrid.py::_mla_mixer`` as of the commit before the
    ``mistral4`` family, operation for operation."""
    import functools

    from generativeaiexamples_tpu.models import hybrid
    from generativeaiexamples_tpu.models.llama import rms_norm

    b, s, _ = h.shape
    H, rank = cfg.n_heads, cfg.kv_lora_rank
    nope, rope, vd = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    q = jnp.dot(h, lp["w_q"]).reshape(b, s, H, nope + rope)
    q_nope = q[..., :nope]
    q_rope = mla.rope_interleaved(q[..., nope:], pos, cfg.rope_theta)
    ckr = jnp.dot(h, lp["w_kva"])
    c = rms_norm(ckr[..., :rank], lp["kv_norm"], cfg.norm_eps)
    k_rope = mla.rope_interleaved(ckr[..., rank:], pos, cfg.rope_theta)
    new = jnp.concatenate([c, k_rope], axis=-1).astype(st["latent"].dtype)
    T = st["latent"].shape[1]
    at = jnp.where(valid, pos, T)
    latent = st["latent"].at[jnp.arange(b)[:, None], at].set(new, mode="drop")
    attend = functools.partial(
        mla.attend_absorbed if s == 1 else mla.attend_expanded,
        w_kvb=lp["w_kvb"], rank=rank, nope=nope, v_dim=vd,
    )
    o = hybrid._attend(
        lambda qn, qr, lat, p: attend(qn, qr, lat, q_pos=p),
        n_valid, apart, q_nope, q_rope, latent[:, :window], pos,
    )
    gate = jax.nn.sigmoid(jnp.dot(h, lp["w_gate"], preferred_element_type=F32))
    o = (o.astype(F32) * gate[..., None]).astype(h.dtype)
    out = jnp.dot(o.reshape(b, s, H * vd), lp["w_o"])
    return out, {"latent": latent}


@pytest.mark.parametrize("s, apart", [(24, False), (24, True), (1, False)], ids=["chunk", "rows_apart", "decode"])
def test_lings_latent_layer_is_bit_for_bit_what_it_was(s, apart):
    """The ``mla`` kind serves Ling's form and the ``mistral4`` family's
    from one function; for Ling's configuration it computes what it
    computed, to the last bit (operation by operation, outside ``jit``),
    and its parameters and counters are the ones it had."""
    from generativeaiexamples_tpu.models import hybrid

    cfg = hybrid.PRESETS["ling-tiny"]()
    layer = cfg.layers_of("mla")[0]
    lp = hybrid.init_params(cfg, jax.random.PRNGKey(0))["layers"][layer]
    assert list(lp)[:8] == ["attn_norm", "mlp_norm", "w_q", "w_kva", "kv_norm", "w_kvb", "w_gate", "w_o"]
    assert cfg.row_counters == hybrid.STATE_COUNTERS and cfg.n_counters == len(moe.COUNTERS) + 2 and not cfg.rows_only
    r = np.random.RandomState(5)
    b, T = 3, 64
    h = jnp.asarray(r.randn(b, s, cfg.d_model), F32)
    st = {"latent": jnp.asarray(r.randn(b, T, cfg.latent_width), F32)}
    start = jnp.asarray([30, 0, 7], jnp.int32)
    n_valid = jnp.asarray([s, max(s - 5, 1), 0 if apart else 1], jnp.int32)
    steps = jnp.arange(s, dtype=jnp.int32)[None, :]
    pos, valid = start[:, None] + steps, steps < n_valid[:, None]
    want, want_st = _lings_mixer_as_it_was(h, lp, st, pos, valid, n_valid, cfg, T, apart)
    got, got_st, read = hybrid._mla_mixer(h, lp, st, pos, valid, n_valid, cfg, T, apart)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    np.testing.assert_array_equal(np.asarray(got_st["latent"]), np.asarray(want_st["latent"]))
    assert set(got_st) == {"latent"}


# -- compressed convolutional attention: the pieces before the attention ---------


def _in_pieces(fn, x, tail, pieces):
    """``fn(x_piece, tail) -> (y, xin)`` over ``x`` cut into pieces, each
    continued from the tail the piece before left."""
    out, at = [], 0
    for n in pieces:
        y, xin = fn(x[:, at : at + n], tail)
        tail = kda.next_tail(xin, jnp.full((x.shape[0],), n, jnp.int32), tail.shape[1] + 1)
        out.append(y)
        at += n
    return jnp.concatenate(out, axis=1), tail


@pytest.mark.parametrize("width", [2, 3])
def test_head_conv_mixes_a_heads_channels_and_continues_from_its_tail(width):
    """``cca.head_conv`` against the sum written out: ``z_t[j] = sum_w
    a_{t - (W-1-w)}[j] W[w, j] + b[j]`` with zeros before the first token;
    cut into pieces of 1, 2, 3 and 5 tokens it gives the same, and a head's
    output depends on no other head."""
    from generativeaiexamples_tpu.ops import cca

    r = np.random.RandomState(0)
    b, s, J, d = 2, 11, 3, 4
    x = jnp.asarray(r.randn(b, s, J * d), F32)
    w = jnp.asarray(r.randn(width, J, d, d), F32)
    bias = jnp.asarray(r.randn(J * d), F32)
    zeros = jnp.zeros((b, width - 1, J * d), F32)
    y, xin = cca.head_conv(x, zeros, w, bias)
    assert y.shape == (b, s, J, d) and xin.shape == (b, s + width - 1, J * d)
    xh = np.asarray(x).reshape(b, s, J, d)
    want = np.zeros((b, s, J, d), np.float32) + np.asarray(bias).reshape(J, d)
    for t in range(s):
        for tap in range(width):
            back = width - 1 - tap
            if t - back >= 0:
                want[:, t] += np.einsum("bjd,jde->bje", xh[:, t - back], np.asarray(w)[tap])
    np.testing.assert_allclose(y, want, atol=1e-5)
    pieces, _ = _in_pieces(lambda p, tail: cca.head_conv(p, tail, w, bias), x, zeros, (1, 2, 3, 5))
    np.testing.assert_allclose(pieces, y, atol=1e-6)
    other = x.at[:, :, d:].add(1.0)  # every head but the first
    np.testing.assert_array_equal(np.asarray(cca.head_conv(other, zeros, w, bias)[0])[:, :, 0], np.asarray(y)[:, :, 0])


def test_the_depthwise_step_and_the_value_shift_share_kda_s_tail():
    """Step one of the convolution is ``kda.causal_conv`` at a kernel of 2,
    and ``cca.shift`` is a tail of one: in pieces both give what they give
    whole, and ``next_tail`` moves neither past tokens that do not count."""
    from generativeaiexamples_tpu.ops import cca

    r = np.random.RandomState(1)
    x = jnp.asarray(r.randn(2, 11, 6), F32)
    w = jnp.asarray(r.randn(2, 6), F32)
    zeros = jnp.zeros((2, 1, 6), F32)
    y, _ = kda.causal_conv(x, zeros, w)
    before = np.concatenate([np.zeros((2, 1, 6), np.float32), np.asarray(x)[:, :-1]], axis=1)
    np.testing.assert_allclose(y, np.asarray(w)[0] * before + np.asarray(w)[1] * np.asarray(x), atol=1e-6)
    shifted, xin = cca.shift(x, zeros)
    np.testing.assert_array_equal(np.asarray(shifted), before)
    for fn in (lambda p, t: kda.causal_conv(p, t, w), cca.shift):
        whole, _ = fn(x, zeros)
        pieces, tail = _in_pieces(fn, x, zeros, (1, 2, 3, 5))
        np.testing.assert_allclose(pieces, whole, atol=1e-6)
        np.testing.assert_array_equal(np.asarray(tail), np.asarray(x)[:, -1:])
    # Row 0 counts 4 of its tokens, row 1 none: its tail stays what it was.
    tail = jnp.asarray(r.randn(2, 1, 6), F32)
    _, xin = cca.shift(x, tail)
    moved = kda.next_tail(xin, jnp.asarray([4, 0], jnp.int32), 2)
    np.testing.assert_array_equal(np.asarray(moved)[0], np.asarray(x)[0, 3:4])
    np.testing.assert_array_equal(np.asarray(moved)[1], np.asarray(tail)[1])


def test_the_qk_mean_goes_to_a_query_head_and_to_its_groups_key_head():
    from generativeaiexamples_tpu.ops import cca

    r = np.random.RandomState(2)
    b, s, H, G, d = 2, 3, 4, 2, 5
    z = jnp.asarray(r.randn(b, s, H + G, d), F32)
    u = jnp.asarray(r.randn(b, s, (H + G) * d), F32)
    q, k = cca.add_qk_mean(z, u, H)
    uh = np.asarray(u).reshape(b, s, H + G, d)
    for i in range(H):
        m = (uh[:, :, i] + uh[:, :, H + i // 2]) / 2
        np.testing.assert_allclose(np.asarray(q)[:, :, i], np.asarray(z)[:, :, i] + m, atol=1e-6)
    for g in range(G):
        m = ((uh[:, :, 2 * g] + uh[:, :, 2 * g + 1]) / 2 + uh[:, :, H + g]) / 2
        np.testing.assert_allclose(np.asarray(k)[:, :, g], np.asarray(z)[:, :, H + g] + m, atol=1e-6)


def test_partial_rotation_turns_the_first_part_of_a_head_and_passes_the_rest():
    from generativeaiexamples_tpu.ops import rope

    spec = rope.RopeSpec(theta=5e6)
    x = jnp.asarray(np.random.RandomState(3).randn(2, 5, 3, 16), F32)
    pos = jnp.asarray([[0, 1, 2, 3, 4], [9, 10, 11, 12, 13]], jnp.int32)
    got = rope.apply_rope_partial(x, pos, spec, 8)
    np.testing.assert_array_equal(np.asarray(got)[..., 8:], np.asarray(x)[..., 8:])
    # Half-split pairs (j, j + 4) of the first 8, frequencies of a head 8 wide.
    inv = 5e6 ** (-np.arange(0, 8, 2) / 8)
    ang = np.asarray(pos, np.float64)[..., None, None] * inv
    x1, x2 = np.asarray(x)[..., :4], np.asarray(x)[..., 4:8]
    np.testing.assert_allclose(np.asarray(got)[..., :4], x1 * np.cos(ang) - x2 * np.sin(ang), atol=1e-5)
    np.testing.assert_allclose(np.asarray(got)[..., 4:8], x2 * np.cos(ang) + x1 * np.sin(ang), atol=1e-5)
    np.testing.assert_array_equal(
        np.asarray(rope.apply_rope_partial(x, pos, spec, 16)), np.asarray(rope.apply_rope_spec(x, pos, spec)))
    np.testing.assert_array_equal(np.asarray(got)[0, 0], np.asarray(x)[0, 0])  # position 0 turns nothing


# -- an indexer's scores, the selection, attention over what it keeps, a ring of latent rows --


def _latent_case(seed, b=2, s=16, T=64, H=4, rank=16, nope=8, rope=8, vd=16, HI=2, dI=16):
    keys = jax.random.split(jax.random.PRNGKey(seed), 8)
    return {
        "q_nope": jax.random.normal(keys[0], (b, s, H, nope)),
        "q_rope": jax.random.normal(keys[1], (b, s, H, rope)),
        "latent": jax.random.normal(keys[2], (b, T, rank + rope)),
        "w_kvb": jax.random.normal(keys[3], (rank, H * (nope + vd))) * rank**-0.5,
        "q_i": jax.random.normal(keys[4], (b, s, HI, dI)),
        "w_i": jax.random.normal(keys[5], (b, s, HI)),
        "index_k": jax.random.normal(keys[6], (b, T, dI)),
        "sizes": dict(rank=rank, nope=nope, v_dim=vd, scale=0.3),
    }


def _masked_attention(c, q_pos, mask):
    """Attention over the whole window under an explicit (b, s, T) mask,
    keys and values expanded: the oracle of the selected forms."""
    z, (rank, nope, vd) = c["sizes"], (c["sizes"][k] for k in ("rank", "nope", "v_dim"))
    b, T, _ = c["latent"].shape
    H = c["q_nope"].shape[2]
    kv = jnp.dot(c["latent"][..., :rank], c["w_kvb"]).reshape(b, T, H, nope + vd)
    scores = (
        jnp.einsum("bshd,bthd->bhst", c["q_nope"], kv[..., :nope])
        + jnp.einsum("bshd,btd->bhst", c["q_rope"], c["latent"][..., rank:])
    ) * z["scale"]
    probs = jax.nn.softmax(jnp.where(mask[:, None], scores, -jnp.inf), axis=-1)
    return jnp.einsum("bhst,bthd->bshd", probs, kv[..., nope:])


@pytest.mark.parametrize("block", [8, 16, 64])
def test_index_scores_in_blocks_and_the_selected_block_walk_match_the_whole_window(block):
    """The indexer's scores a block of keys at a time up to the rows'
    lengths (one row of a state of many slots read in place, too), the
    ``k`` largest as a mask, and ``attend_blocks`` over the kept pairs,
    against the scores of the whole window, a stable sort and a masked
    softmax.  A query that sees fewer than ``k`` rows keeps them all; a
    block of which a query keeps nothing adds nothing."""
    c = _latent_case(21)
    starts = jnp.asarray([40, 3])
    q_pos = starts[:, None] + jnp.arange(16)[None, :]
    lengths, k = starts + 16, 12
    seen = jnp.arange(64)[None, None, :] <= q_pos[:, :, None]
    whole = jnp.where(seen, mla.index_scores(c["q_i"], c["w_i"], c["index_k"]), -jnp.inf)
    want_i = jnp.einsum(
        "bsht,bsh->bst", jax.nn.relu(jnp.einsum("bshd,btd->bsht", c["q_i"], c["index_k"])), c["w_i"])
    np.testing.assert_allclose(np.where(seen, whole, 0), np.where(seen, want_i, 0), atol=1e-5)
    got_i = mla.index_scores_blocks(c["q_i"], c["w_i"], c["index_k"], q_pos, lengths, block=block)
    np.testing.assert_allclose(np.where(seen, got_i, 0), np.where(seen, whole, 0), atol=1e-5)
    assert np.isneginf(np.asarray(got_i)[~np.asarray(seen)]).all()
    one = mla.index_scores_blocks(
        c["q_i"][1:], c["w_i"][1:], c["index_k"], q_pos[1:], lengths[1:], block=block,
        slot=jnp.asarray([1]), window=32)
    np.testing.assert_allclose(np.where(seen[1:, :, :32], one, 0), np.where(seen[1:, :, :32], whole[1:, :, :32], 0), atol=1e-5)
    rank_of = jnp.argsort(jnp.argsort(-whole, axis=-1, stable=True), axis=-1)
    want_mask = seen & (rank_of < k)
    mask = mla.select_mask(got_i, k)
    np.testing.assert_array_equal(np.asarray(mask), np.asarray(want_mask))
    assert (np.asarray(mask).sum(-1) == np.minimum(np.asarray(q_pos) + 1, k)).all()
    want = _masked_attention(c, q_pos, want_mask)
    got = mla.attend_blocks(
        c["q_nope"], c["q_rope"], c["latent"], c["w_kvb"], q_pos, lengths, block=block,
        allowed=mask, **c["sizes"])
    np.testing.assert_allclose(got, want, atol=1e-5)
    # Every pair allowed: the walk without a mask, to the last bit.
    plain = mla.attend_blocks(
        c["q_nope"], c["q_rope"], c["latent"], c["w_kvb"], q_pos, lengths, block=block, **c["sizes"])
    every = mla.attend_blocks(
        c["q_nope"], c["q_rope"], c["latent"], c["w_kvb"], q_pos, lengths, block=block,
        allowed=jnp.ones((2, 16, 64), bool), **c["sizes"])
    np.testing.assert_array_equal(np.asarray(every), np.asarray(plain))


@pytest.mark.parametrize("k", [8, 24, 64, 100])
def test_a_decode_step_over_the_gathered_rows_matches_the_masked_window(k):
    """``select_rows`` + ``attend_selected`` (the rows gathered whole, zero
    columns and all, one query a slot) against the masked softmax over the
    whole window; ``k`` past what a slot holds keeps every row it sees and
    is ``attend_absorbed``."""
    c = _latent_case(22, s=1)
    wide = jnp.concatenate([c["latent"], jnp.zeros((2, 64, 104))], axis=-1)  # rows in whole lanes
    q_pos = jnp.asarray([[50], [9]])
    seen = jnp.arange(64)[None, :] <= q_pos
    scores = jnp.where(seen, mla.index_scores(c["q_i"], c["w_i"], c["index_k"])[:, 0], -jnp.inf)
    idx, keep = mla.select_rows(scores, k)
    assert idx.shape == (2, min(k, 64)) and (np.asarray(keep).sum(-1) == np.minimum([51, 10], k)).all()
    rank_of = jnp.argsort(jnp.argsort(-scores, axis=-1, stable=True), axis=-1)
    want = _masked_attention(c, q_pos, (seen & (rank_of < k))[:, None])
    got = mla.attend_selected(  # one query a slot: a set a slot and position
        c["q_nope"], c["q_rope"], wide, c["w_kvb"], idx[:, None], keep[:, None], **c["sizes"])
    np.testing.assert_allclose(got, want, atol=1e-5)
    if k >= 64:
        dense = mla.attend_absorbed(c["q_nope"], c["q_rope"], c["latent"], c["w_kvb"], q_pos, **c["sizes"])
        np.testing.assert_allclose(got, dense, atol=1e-5)


@pytest.mark.parametrize("s, start", [(16, 0), (16, 37), (5, 11), (1, 40), (2, 3), (1, 0)],
                         ids=["first_chunk", "turned_over", "short_chunk", "decode", "two_queries", "empty_ring"])
def test_a_ring_of_latent_rows_beside_the_call_s_own_matches_the_windowed_whole(s, start):
    """``attend_latent_ring``: a ring of 13 rows (shorter than a chunk of
    16), filled position by position as the serving path fills it
    (``p % 13``; stale rows of another occupant where nothing was written),
    against attention over every position under the mask ``i - 13 < j <=
    i``; a chunk takes the expanded form, a decode step the absorbed."""
    from generativeaiexamples_tpu.ops import gqa

    R = window = 13
    c = _latent_case(23, b=2, s=s, T=64)
    rows = jnp.concatenate([c["latent"], jnp.zeros((2, 64, 104))], axis=-1)
    ring = jnp.full((2, R, rows.shape[2]), 9.0)  # the last occupant's leftovers
    for p in range(start):
        ring = ring.at[:, p % R].set(rows[:, p])
    q_pos = jnp.full((2, 1), start) + jnp.arange(s)[None, :]
    j = jnp.arange(64)[None, None, :]
    mask = (j <= q_pos[:, :, None]) & (j > q_pos[:, :, None] - window)
    want = _masked_attention(c, q_pos, mask)
    got = mla.attend_latent_ring(
        c["q_nope"], c["q_rope"], rows[:, start : start + s], ring, c["w_kvb"], q_pos,
        window=window, **c["sizes"])
    np.testing.assert_allclose(got, want, atol=1e-5)
    # What the call then writes: its last 13 rows, each at ``p % 13``.
    at = gqa.ring_slots(q_pos, jnp.ones((2, s), bool), jnp.full((2,), s), R)
    assert (np.asarray(at)[:, -min(s, R):] == np.asarray(q_pos)[:, -min(s, R):] % R).all()
    assert (np.asarray(at)[:, : max(s - R, 0)] == R).all()
