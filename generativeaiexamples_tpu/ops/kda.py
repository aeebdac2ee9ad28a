"""Kimi Delta Attention (arXiv:2510.26692): the serving-path forms.

A KDA head keeps a state ``S`` of shape (d_k, d_v).  Per token, with a
per-channel log-gate ``g`` in (floor, 0), ``alpha = exp(g)``, a write
strength ``beta`` in (0, 1), and L2-normalised ``q`` (scaled) and ``k``:

    S' = Diag(alpha_t) S_(t-1)
    S_t = S' + beta_t k_t (v_t - S'^T k_t)^T
    o_t = S_t^T q_t

Three forms:

* :func:`kda_step` — one token a row, the decode step, in plain XLA: two
  fusions that between them pass three times over every slot's state;
* :func:`kda_step_rows` — the same step as a Pallas TPU kernel that reads
  and writes the state of the rows that decode, once, in place, and
  leaves the other slots' state in HBM (:func:`use_step_kernel` is the
  gate, ``kda_step`` the twin it falls back to; docs/kernels.md);
* :func:`kda_chunked` — a ``lax.scan`` over sub-chunks of ``SUB`` tokens,
  each solved in closed form (the WY form of the delta rule).  Inside a
  sub-chunk keys are scaled by ``exp(-G)`` and queries by ``exp(+G)``,
  ``G`` the gate cumulated from the sub-chunk's start; the gate's floor
  of -5 a step bounds ``|G|`` by ``5 * SUB = 80``, inside float32's
  range (e^80 = 5.5e34), which is what fixes ``SUB`` at 16.

A token with ``beta = 0`` and ``g = 0`` leaves the state exactly as it
was: padded positions, rows that do not decode and a chunk's padding
are given those.

The state and every product that touches it are float32 at the
highest matmul precision: they are a small share of a layer's
operations (4 * d_k * d_v a head and token, against 2 * 63M for the
projections) and the state is read again by every later token.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from generativeaiexamples_tpu.ops.decode_attention import _interpret_mode
from generativeaiexamples_tpu.ops.dispatch import one_device, platform_of
from generativeaiexamples_tpu.ops.qmm import _VMEM_BUDGET_BYTES

SUB = 16
F32 = jnp.float32
HI = jax.lax.Precision.HIGHEST


def kda_gate(f, a_log, dt_bias, floor: float):
    """The safe gate: ``floor * sigmoid(exp(A_h) * (f + dt_bias))`` in
    (floor, 0) a channel (``floor`` is ``kda_lower_bound``, -5).

    f: (..., H, K) float; a_log: (H,); dt_bias: (H, K)."""
    return floor * jax.nn.sigmoid(
        jnp.exp(a_log.astype(F32))[:, None] * (f.astype(F32) + dt_bias.astype(F32))
    )


def l2_normalize(x, eps: float = 1e-6):
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


def causal_conv(x, tail, weight):
    """Depthwise causal convolution over the sequence, one filter a
    channel, continued from ``tail``.

    x: (b, s, C) the new inputs; tail: (b, W-1, C) the inputs before
    them; weight: (W, C), ``weight[-1]`` multiplying the current input.
    Returns (y (b, s, C) float32, xin (b, s+W-1, C)) — ``xin`` is what
    the caller cuts the next tail from."""
    w = weight.shape[0]
    xin = jnp.concatenate([tail.astype(x.dtype), x], axis=1)
    s = x.shape[1]
    y = sum(
        xin[:, j : j + s].astype(F32) * weight[j].astype(F32) for j in range(w)
    )
    return y, xin


def next_tail(xin, n_valid, width: int):
    """The last ``width - 1`` inputs of each row once ``n_valid`` (b,) new
    ones count: rows ``n_valid + [0, width-1)`` of ``xin``."""
    idx = n_valid[:, None] + jnp.arange(width - 1, dtype=jnp.int32)[None, :]
    return jnp.take_along_axis(xin, idx[:, :, None], axis=1)


@jax.named_scope("layer/kda/scan")
def kda_step(q, k, v, g, beta, state):
    """One token a row.  q, k, g: (b, H, K); v: (b, H, V); beta: (b, H);
    state: (b, H, K, V) float32.  Returns (o (b, H, V) f32, state)."""
    q, k, v, g, beta = (a.astype(F32) for a in (q, k, v, g, beta))
    decayed = state * jnp.exp(g)[..., None]
    read = jnp.einsum("bhkv,bhk->bhv", decayed, k, precision=HI)
    u = beta[..., None] * (v - read)
    state = decayed + k[..., None] * u[..., None, :]
    o = jnp.einsum("bhkv,bhk->bhv", state, q, precision=HI)
    return o, state


def _heads_a_step(h: int, k: int, v: int) -> int:
    """Heads of a row whose state rides in one grid step: the most that
    divide ``h`` with the block's four buffers (in and out, each double)
    inside half the VMEM budget.  At H 32, K = V 128 that is the whole
    row, 2 MB in and 2 MB out a step: a grid step costs ~0.35 us whether
    it copies or not, and the 20 slots of 32 that do not decode each cost
    one."""
    fits = [d for d in range(1, h + 1) if h % d == 0 and 4 * d * k * v * 4 <= _VMEM_BUDGET_BYTES // 2]
    return max(fits, default=0)


def use_step_kernel(*, state_dtype, k_dim: int, v_dim: int, heads: int, mesh=None) -> bool:
    """The gate of :func:`kda_step_rows`, from what a traced step can
    observe: a float32 state whose heads are whole lane tiles, on one TPU
    device.  Everything else is :func:`kda_step`'s."""
    if not _interpret_mode() and (platform_of(mesh) != "tpu" or not one_device(mesh)):
        return False
    return (
        jnp.dtype(state_dtype) == F32
        and k_dim % 128 == 0
        and v_dim % 128 == 0
        and _heads_a_step(heads, k_dim, v_dim) > 0
    )


def live_slots(live):
    """(b,) bool -> (the slots that are live, in order, then zeros; how
    many): the list the step kernel walks."""
    b = live.shape[0]
    slots = jnp.arange(b, dtype=jnp.int32)
    rank = jnp.cumsum(live.astype(jnp.int32)) - 1
    at = live[None, :] & (rank[None, :] == slots[:, None])  # (place, slot)
    return jnp.sum(jnp.where(at, slots[None, :], 0), axis=1), jnp.sum(live.astype(jnp.int32))


def _step_kernel(idx_ref, n_ref, cols_ref, v_ref, s_in, o_ref, s_out, *, hb: int):
    """One listed row's ``hb`` heads.  cols_ref (1, 1, K, 4 * hb): ``q``,
    ``k``, ``exp(g)`` and ``beta k`` of each head as columns, so that a
    head's is a lane slice that broadcasts along a (K, V) tile's lanes;
    v_ref, o_ref (1, 1, hb, V); s_in, s_out (1, hb, K, V), one buffer in
    HBM.  A listed row past the count has the block indices of the step
    before it: nothing is copied for it and it computes nothing."""
    del idx_ref
    i, n = pl.program_id(0), n_ref[0]

    @pl.when(i < n)
    def _live():
        for h in range(hb):
            q, k, a, kb = (cols_ref[0, 0, :, c * hb + h : c * hb + h + 1] for c in range(4))
            decayed = s_in[0, h] * a
            read = jnp.sum(decayed * k, axis=0, keepdims=True)  # (1, V)
            new = decayed + kb * (v_ref[0, 0, h : h + 1, :] - read)
            s_out[0, h] = new
            o_ref[0, 0, h : h + 1, :] = jnp.sum(new * q, axis=0, keepdims=True)

    # No live row: the one block the index maps name is written back as
    # it was read.
    @pl.when((n == 0) & (i == 0) & (pl.program_id(1) == 0))
    def _none():
        s_out[...] = s_in[...]


@functools.partial(jax.jit, static_argnames=("hb", "interpret"))
def _step_rows(idx, n, cols, v, state, *, hb: int, interpret: bool):
    b, H, K, V = state.shape
    groups = H // hb

    def at(i, j, idx, n):
        """Block indices of grid step (listed row i, head group j): past
        the count, those of the last live row's last group."""
        row = idx[jnp.minimum(i, jnp.maximum(n[0] - 1, 0))]
        return row, jnp.where(i < n[0], j, groups - 1)

    vec = pl.BlockSpec((1, 1, hb, V), lambda i, j, idx, n: (*at(i, j, idx, n), 0, 0))
    tile = pl.BlockSpec((1, hb, K, V), lambda i, j, idx, n: (*at(i, j, idx, n), 0, 0))
    o, state = pl.pallas_call(
        functools.partial(_step_kernel, hb=hb),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, groups),
            in_specs=[
                pl.BlockSpec((1, 1, K, 4 * hb), lambda i, j, idx, n: (*at(i, j, idx, n), 0, 0)),
                vec,
                tile,
            ],
            out_specs=[vec, tile],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((b, groups, hb, V), F32),
            jax.ShapeDtypeStruct(state.shape, F32),
        ],
        # The state leaf in place (operands count the two prefetched).
        input_output_aliases={4: 1},
        compiler_params=pltpu.CompilerParams(
            # In order: a row past the count leans on the blocks of the
            # step before it.
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_BUDGET_BYTES,
        ),
        interpret=interpret,
        name="kda_step_rows",
    )(idx, n.reshape(1), cols, v.reshape(b, groups, hb, V), state)
    return o.reshape(b, H, V), state


@jax.named_scope("layer/kda/scan")
def kda_step_rows(q, k, v, g, beta, state, live, *, interpret=None):
    """:func:`kda_step` for the rows of ``live`` (b,) bool, as a Pallas
    kernel: each live row's state is read once, updated in VMEM and
    written once, into the buffer it came from; a row that is not live
    is neither read nor written, and its output is exact zeros.  Float32
    throughout, the reductions on the VPU.  Returns (o (b, H, V) f32,
    state)."""
    if interpret is None:
        interpret = _interpret_mode()
    b, H, K, V = state.shape
    hb = _heads_a_step(H, K, V)
    q, k, v, g, beta = (a.astype(F32) for a in (q, k, v, g, beta))
    # (4, b, H, K) -> (b, H / hb, K, 4 * hb): 64 KB a row at H 32, K 128.
    cols = jnp.stack([q, k, jnp.exp(g), beta[..., None] * k])
    cols = cols.reshape(4, b, H // hb, hb, K).transpose(1, 2, 4, 0, 3).reshape(b, H // hb, K, 4 * hb)
    idx, n = live_slots(live)
    o, state = _step_rows(idx, n, cols, v, state, hb=hb, interpret=interpret)
    return jnp.where(live[:, None, None], o, 0.0), state


def _sub_chunk(state, q, k, v, g, beta):
    """One sub-chunk in closed form.  q, k, g: (b, H, C, K); v: (b, H, C,
    V); beta: (b, H, C); state (b, H, K, V)."""
    c = q.shape[2]
    G = jnp.cumsum(g, axis=2)  # (b, H, C, K), in [-5 C, 0]
    k_in = k * jnp.exp(G)  # key t as the state at t sees it
    k_out = k * jnp.exp(-G)
    q_in = q * jnp.exp(G)
    # M[t, s] = k_s^T Diag(exp(G_t - G_s)) k_t, used for s < t.
    M = jnp.einsum("bhtk,bhsk->bhts", k_in, k_out, precision=HI)
    strict = jnp.tril(jnp.ones((c, c), dtype=bool), -1)
    A = jnp.where(strict, M, 0.0) * beta[..., None]
    rhs = beta[..., None] * (
        v - jnp.einsum("bhtk,bhkv->bhtv", k_in, state, precision=HI)
    )
    # (I + A) U = rhs, A strictly lower triangular.
    U = jax.scipy.linalg.solve_triangular(
        A + jnp.eye(c, dtype=F32), rhs, lower=True, unit_diagonal=True
    )
    P = jnp.einsum("bhtk,bhsk->bhts", q_in, k_out, precision=HI)
    causal = jnp.tril(jnp.ones((c, c), dtype=bool))
    o = jnp.einsum("bhtk,bhkv->bhtv", q_in, state, precision=HI) + jnp.einsum(
        "bhts,bhsv->bhtv", jnp.where(causal, P, 0.0), U, precision=HI
    )
    last = G[:, :, -1]  # (b, H, K)
    state = state * jnp.exp(last)[..., None] + jnp.einsum(
        "bhsk,bhsv->bhkv", k_out * jnp.exp(last)[:, :, None, :], U, precision=HI
    )
    return state, o


@jax.named_scope("layer/kda/scan")
def kda_chunked(q, k, v, g, beta, state, sub: int = SUB):
    """A run of tokens a row.  q, k, g: (b, s, H, K); v: (b, s, H, V);
    beta: (b, s, H); state: (b, H, K, V) float32.  ``s`` is padded to a
    multiple of ``sub`` with tokens that leave the state alone.
    Returns (o (b, s, H, V) f32, state)."""
    b, s, H, K = q.shape
    pad = (-s) % sub
    q, k, v, g, beta = (a.astype(F32) for a in (q, k, v, g, beta))
    if pad:
        q, k, v, g = (
            jnp.pad(a, ((0, 0), (0, pad), (0, 0), (0, 0))) for a in (q, k, v, g)
        )
        beta = jnp.pad(beta, ((0, 0), (0, pad), (0, 0)))
    n = (s + pad) // sub

    def split(a):  # (b, n*sub, H, X) -> (n, b, H, sub, X)
        return a.reshape(b, n, sub, H, -1).transpose(1, 0, 3, 2, 4)

    xs = (split(q), split(k), split(v), split(g), split(beta[..., None])[..., 0])

    def body(st, x):
        return _sub_chunk(st, *x)

    state, o = jax.lax.scan(body, state, xs)
    o = o.transpose(1, 0, 3, 2, 4).reshape(b, n * sub, H, -1)
    return o[:, :s], state
