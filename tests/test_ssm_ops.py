"""``ops/ssm.py`` (Mamba-2's state-space mixer as the serving path runs
it) against the recurrence it states, a token at a time in NumPy float64:

    S_t = exp(dt_t A) S_(t-1) + dt_t x_t (x) B_t ;  y_t = S_t C_t + D x_t

Tiny sizes (8 heads of 4 in 2 groups, a state of 6, blocks of 4), float32
at the highest matmul precision (conftest.py): the block form and the
recurrence differ by the order of their sums.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from generativeaiexamples_tpu.ops import ssm

H, P, G, N, BLOCK = 8, 4, 2, 6, 4
ATOL = 2e-5


def _inputs(b, s, seed=0):
    rng = np.random.RandomState(seed)
    return dict(
        x=rng.randn(b, s, H, P).astype(np.float32),
        # A head's decay a token from 0.2 to 0.999, as seeded weights give.
        dt=np.exp(rng.uniform(np.log(1e-3), np.log(0.3), (b, s, H))).astype(np.float32),
        a=-rng.uniform(1.0, 16.0, (H,)).astype(np.float32),
        b_in=rng.randn(b, s, G, N).astype(np.float32),
        c_in=rng.randn(b, s, G, N).astype(np.float32),
        d_skip=rng.randn(H).astype(np.float32),
        state=rng.randn(b, H, P, N).astype(np.float32),
    )


def _recurrence(x, dt, a, b_in, c_in, d_skip, state):
    """The equations above, float64, a token at a time."""
    x, dt, a, b_in, c_in, d_skip = (np.asarray(t, np.float64) for t in (x, dt, a, b_in, c_in, d_skip))
    S = np.asarray(state, np.float64).copy()
    of_head = np.arange(H) // (H // G)
    ys = np.zeros(x.shape)
    for t in range(x.shape[1]):
        decay = np.exp(dt[:, t] * a)  # (b, H)
        S = decay[..., None, None] * S + (dt[:, t, :, None] * x[:, t])[..., None] * b_in[:, t, of_head][:, :, None, :]
        ys[:, t] = np.einsum("bhpn,bhn->bhp", S, c_in[:, t, of_head]) + d_skip[:, None] * x[:, t]
    return ys, S


@pytest.mark.parametrize("s", [1, 3, 4, 5, 9, 16], ids=lambda s: f"s{s}")
@pytest.mark.parametrize("carried", [False, True], ids=["from_zero", "carried"])
def test_the_block_scan_is_the_recurrence(s, carried):
    """Lengths that are no multiple of a block (the last block is padded
    with tokens of ``dt`` 0) and shorter than one, from zero state and
    from a carried one."""
    inp = _inputs(2, s, seed=s)
    if not carried:
        inp["state"] = np.zeros_like(inp["state"])
    y, S = ssm.ssm_scan(**{k: jnp.asarray(v) for k, v in inp.items()}, block=BLOCK)
    want_y, want_S = _recurrence(**inp)
    np.testing.assert_allclose(y, want_y, atol=ATOL)
    np.testing.assert_allclose(S, want_S, atol=ATOL)
    assert y.dtype == S.dtype == jnp.float32


def test_the_state_crosses_calls_as_it_crosses_blocks():
    """A sequence in calls of 1, 2, 3, 5 and 9 tokens, the state handed
    from call to call, is the sequence in one call."""
    inp = _inputs(2, 20)
    whole_y, whole_S = ssm.ssm_scan(**{k: jnp.asarray(v) for k, v in inp.items()}, block=BLOCK)
    S, at, ys = jnp.asarray(inp["state"]), 0, []
    for n in (1, 2, 3, 5, 9):
        piece = {k: jnp.asarray(inp[k][:, at : at + n]) for k in ("x", "dt", "b_in", "c_in")}
        y, S = ssm.ssm_scan(**piece, a=jnp.asarray(inp["a"]), d_skip=jnp.asarray(inp["d_skip"]), state=S, block=BLOCK)
        ys.append(y)
        at += n
    np.testing.assert_allclose(jnp.concatenate(ys, axis=1), whole_y, atol=ATOL)
    np.testing.assert_allclose(S, whole_S, atol=ATOL)


def test_the_step_is_one_token_of_the_recurrence():
    inp = _inputs(3, 1, seed=5)
    want_y, want_S = _recurrence(**inp)
    one = {k: jnp.asarray(inp[k][:, 0]) for k in ("x", "dt", "b_in", "c_in")}
    y, S = ssm.ssm_step(**one, a=jnp.asarray(inp["a"]), d_skip=jnp.asarray(inp["d_skip"]),
                        state=jnp.asarray(inp["state"]))
    np.testing.assert_allclose(y, want_y[:, 0], atol=ATOL)
    np.testing.assert_allclose(S, want_S, atol=ATOL)


@pytest.mark.parametrize("form", ["step", "scan"])
def test_a_token_that_does_not_count_leaves_the_state_bit_for_bit(form):
    """``dt`` 0 decays by exactly 1 and adds exactly 0: row 1 of the call
    counts for nothing, and its state comes back as it went in."""
    s = 1 if form == "step" else 6
    inp = _inputs(2, s, seed=7)
    inp["dt"][1] = 0.0
    args = {k: jnp.asarray(v) for k, v in inp.items()}
    if form == "step":
        args = {k: (v[:, 0] if k in ("x", "dt", "b_in", "c_in") else v) for k, v in args.items()}
        _, S = ssm.ssm_step(**args)
    else:
        _, S = ssm.ssm_scan(**args, block=BLOCK)
    np.testing.assert_array_equal(np.asarray(S)[1], inp["state"][1])
    assert np.abs(np.asarray(S)[0] - inp["state"][0]).max() > 1e-3


def test_the_tail_of_a_row_is_padding_for_the_scan():
    """The first 5 of 9 tokens count (``dt`` 0 after them): outputs there
    and the state are those of a call of 5."""
    inp = _inputs(1, 9, seed=9)
    inp["dt"][:, 5:] = 0.0
    y, S = ssm.ssm_scan(**{k: jnp.asarray(v) for k, v in inp.items()}, block=BLOCK)
    short = {k: (v[:, :5] if k in ("x", "dt", "b_in", "c_in") else v) for k, v in inp.items()}
    want_y, want_S = _recurrence(**short)
    np.testing.assert_allclose(np.asarray(y)[:, :5], want_y, atol=ATOL)
    np.testing.assert_allclose(S, want_S, atol=ATOL)


@pytest.mark.parametrize("s, block, want", [(256, 128, (128, 2)), (9, 4, (4, 3)), (3, 4, (3, 1)), (1, 128, (1, 1))])
def test_blocks_of(s, block, want):
    assert ssm.blocks_of(s, block) == want


@pytest.mark.parametrize("groups", [1, 2, 4])
def test_the_gated_norm_gates_then_norms_a_group_at_a_time(groups):
    rng = np.random.RandomState(3)
    y, z = rng.randn(2, 5, 16), rng.randn(2, 5, 16)
    gain = rng.uniform(0.5, 1.5, 16)
    got = ssm.gated_group_norm(jnp.asarray(y, jnp.float32), jnp.asarray(z, jnp.float32),
                               jnp.asarray(gain, jnp.float32), groups, 1e-5)
    v = (y * z / (1.0 + np.exp(-z))).reshape(2, 5, groups, -1)
    want = (v / np.sqrt((v * v).mean(-1, keepdims=True) + 1e-5)).reshape(2, 5, 16) * gain
    np.testing.assert_allclose(got, want, atol=1e-5)
    # Not the norm before the gate, and not one norm over all channels.
    if groups > 1:
        whole = ssm.gated_group_norm(jnp.asarray(y, jnp.float32), jnp.asarray(z, jnp.float32),
                                     jnp.asarray(gain, jnp.float32), 1, 1e-5)
        assert np.abs(np.asarray(whole) - want).max() > 1e-2
