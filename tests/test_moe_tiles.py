"""``ops/moe.py``'s one tile rule for the grouped expert products, and the
count of expert streams that comes with it: what ``_tiles`` returns for
the widths served, and ``expert_mlp`` / ``stacked_expert_mlp`` through
megablox's ``gmm`` (interpret mode, small widths) with those tiles
against the dense stand-in."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from generativeaiexamples_tpu.ops import moe

F32 = jnp.float32
# (a, b) of the products served: gate-up (or ``relu2``'s up) and down of
# K-EXAONE, ZAYA, Mistral-Small-4, Ling, Nemotron, Mellum; Mixtral's.
WIDTHS = [
    (6144, 4096), (2048, 6144), (2048, 4096), (2048, 2048), (4096, 4096),
    (2560, 1536), (768, 2560), (1024, 2688), (2688, 1024), (2304, 1792), (896, 2304),
    (4096, 14336), (14336, 4096),
]


@pytest.mark.parametrize("tm", [128, 256])
@pytest.mark.parametrize("a, b", WIDTHS)
def test_tiles_divide_the_sizes_and_fit_the_budget(a, b, tm):
    got_tm, tk, tn = moe._tiles(a, b, tm, 2)
    assert got_tm == tm
    assert tk % 128 == 0 and tn % 128 == 0 and a % tk == 0 and b % tn == 0
    assert moe._vmem_bytes(tm, tk, tn, 2) <= moe.VMEM_BUDGET_BYTES
    assert tk * tn * 2 >= 1_800_000  # megabytes, not the 0.26-0.9 of before PR 45
    if tk < a:
        assert tk * tn * 2 < moe.SPLIT_TILE_BYTES
    # Whole K is taken where it fits and the rows are not walked too often.
    wider = [t for t in range(128, b + 1, 128)
             if b % t == 0 and moe._vmem_bytes(tm, a, t, 2) <= moe.VMEM_BUDGET_BYTES]
    if wider and b // wider[-1] <= moe.MAX_LHS_PASSES:
        assert (tk, tn) == (a, wider[-1])
    else:
        assert tk < a


@pytest.mark.parametrize("tm", [128, 256])
def test_mixtrals_tiles_are_what_they_were(tm):
    """PR 37's ``_wide_tiling``, letter for letter: that cell is a control."""
    assert moe._tiles(4096, 14336, tm, 2) == (tm, 1024, 1792)
    assert moe._tiles(14336, 4096, tm, 2) == (tm, 1792, 1024)


@pytest.mark.parametrize("a, b, want", [
    (2304, 1792, (2304, 896)),   # Mellum's gate-up: the whole of K, half the columns
    (1024, 2688, (1024, 2688)),  # Nemotron's up: the whole matrix, one pass over the rows
    (6144, 4096, (6144, 256)),   # K-EXAONE's gate-up: whole K leaves 256 columns
    (64, 96, (128, 128)),        # sizes no multiple of 128 divides: gmm masks the rest
    (128, 384, (128, 384)),
])
def test_whole_k_where_it_fits(a, b, want):
    assert moe._tiles(a, b, moe.ROW_TILE, 2)[1:] == want
    # float32 halves what fits
    tk, tn = moe._tiles(a, b, moe.ROW_TILE, 4)[1:]
    assert moe._vmem_bytes(moe.ROW_TILE, tk, tn, 4) <= moe.VMEM_BUDGET_BYTES


def _streams_by_hand(rows, tm, whole_k):
    ends = np.cumsum(rows)
    starts = ends - rows
    tiles = -(-ends // tm) - starts // tm
    return int(np.where(rows > 0, 1 if whole_k else tiles, 0).sum())


@pytest.mark.parametrize("act", moe.ACTIVATIONS)
@pytest.mark.parametrize("k_tiles", ["whole_k", "split_k"])
def test_expert_mlp_with_the_rules_tiles_is_the_dense_stand_in(k_tiles, act, monkeypatch):
    """Four experts held of eight, 400 choices of which about half land
    here: groups of ~50 rows over two row tiles, so at least one straddles.
    ``split_k`` squeezes the budgets until the rule splits K in two: the
    count of streams is then the row tiles each group touches."""
    if k_tiles == "split_k":
        monkeypatch.setattr(moe, "VMEM_BUDGET_BYTES", 700_000)
        monkeypatch.setattr(moe, "SPLIT_TILE_BYTES", 128 * 128 * 4 + 1)
    n, D, F, held, offset, k = 100, 256, 128, 4, 2, 4
    first = moe._tiles(D, F if act == "relu2" else 2 * F, moe.ROW_TILE, 4)
    assert (first[1] == D) == (k_tiles == "whole_k")
    r = np.random.RandomState(45)
    x = jnp.asarray(r.randn(n, D), F32)
    lp = {
        "w_up_e" if act == "relu2" else "w_gu_e":
            jnp.asarray(r.randn(held, D, F if act == "relu2" else 2 * F) * D**-0.5, F32),
        "w_down_e": jnp.asarray(r.randn(held, F, D) * F**-0.5, F32),
    }
    idx = jnp.asarray(np.stack([r.choice(8, k, replace=False) for _ in range(n)]), jnp.int32)
    w = jnp.asarray(r.uniform(0.1, 1.0, size=(n, k)), F32)
    valid = jnp.asarray(np.arange(n) < 96)
    want, counted = moe.expert_mlp(x, idx, w, valid, lp, offset=offset, held=held, act=act)
    monkeypatch.setenv("GAIE_MOE_KERNEL_INTERPRET", "1")
    got, counters = moe.expert_mlp(x, idx, w, valid, lp, offset=offset, held=held, act=act)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
    assert counters.tolist() == counted.tolist()  # the count does not ask which path ran

    chosen = np.asarray(idx)[:96]
    rows = np.array([(chosen == e).sum() for e in range(offset, offset + held)])
    c = dict(zip(moe.COUNTERS, counters.tolist()))
    by_hand = _streams_by_hand(rows, moe.ROW_TILE, k_tiles == "whole_k")
    assert c["expert_streams"] == by_hand
    assert c["experts_touched"] == held and c["choices_local"] == rows.sum() > moe.ROW_TILE
    if k_tiles == "whole_k":
        assert c["expert_streams"] == c["experts_touched"]
    else:
        assert c["expert_streams"] > c["experts_touched"]  # a group lies across the tiles' edge


def test_an_expert_with_no_row_streams_nothing():
    starts = jnp.asarray([0, 0, 130, 130, 256, 300], jnp.int32)  # groups of 0, 130, 0, 126, 44 rows
    assert int(moe._streams(starts, (128, 256, 128), 256)) == 3
    assert int(moe._streams(starts, (128, 128, 128), 256)) == 2 + 1 + 1


@pytest.mark.parametrize("choices", [24, 300], ids=["row_tile_128", "row_tile_256"])
def test_stacked_expert_mlp_with_the_rules_tiles_is_the_dense_stand_in(choices, monkeypatch):
    """Four experts that are groups 4..8 of a stack of 12; 12 or 150 tokens
    of two choices (a row tile of 128 or of 256)."""
    n, D, F, E, G, k = choices // 2, 128, 256, 4, 12, 2
    assert moe._row_tile(n * k, E) == (128 if choices == 24 else 256)
    r = np.random.RandomState(7)
    x = jnp.asarray(r.randn(n, D), F32)
    stack = {
        "w_gate_e": jnp.asarray(r.randn(G, D, F) * D**-0.5, F32),
        "w_up_e": jnp.asarray(r.randn(G, D, F) * D**-0.5, F32),
        "w_down_e": jnp.asarray(r.randn(G, F, D) * F**-0.5, F32),
    }
    idx = jnp.asarray(np.stack([r.choice(E, k, replace=False) for _ in range(n)]), jnp.int32)
    w = jnp.asarray(r.uniform(0.1, 1.0, size=(n, k)), F32)
    valid = jnp.asarray(np.arange(n) < n - 3)
    run = jax.jit(lambda: moe.stacked_expert_mlp(
        x, idx, w, valid, stack, first=jnp.int32(4), n_experts=E))
    want = run()
    monkeypatch.setenv("GAIE_MOE_KERNEL_INTERPRET", "1")
    got = jax.jit(lambda: moe.stacked_expert_mlp(
        x, idx, w, valid, stack, first=jnp.int32(4), n_experts=E))()
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    by_hand = np.zeros((n, D), np.float32)
    for t in range(n - 3):
        for e, wt in zip(np.asarray(idx)[t], np.asarray(w)[t]):
            h = jax.nn.silu(x[t] @ stack["w_gate_e"][4 + e]) * (x[t] @ stack["w_up_e"][4 + e])
            by_hand[t] += wt * np.asarray(h @ stack["w_down_e"][4 + e])
    np.testing.assert_allclose(got, by_hand, rtol=1e-4, atol=1e-5)
