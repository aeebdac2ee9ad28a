"""Kimi Delta Attention (arXiv:2510.26692): the serving-path forms.

A KDA head keeps a state ``S`` of shape (d_k, d_v).  Per token, with a
per-channel log-gate ``g`` in (floor, 0), ``alpha = exp(g)``, a write
strength ``beta`` in (0, 1), and L2-normalised ``q`` (scaled) and ``k``:

    S' = Diag(alpha_t) S_(t-1)
    S_t = S' + beta_t k_t (v_t - S'^T k_t)^T
    o_t = S_t^T q_t

Four forms:

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
  range (e^80 = 5.5e34), which is what fixes ``SUB`` at 16;
* :func:`kda_chunk_rows` — the same closed form as a Pallas TPU kernel
  for a prefill call's rows: a group of heads' state stays in VMEM for
  the whole row, which is walked in blocks of up to four sub-chunks; a
  block reads the state once and updates it once, and between its
  sub-chunks the keys are carried to the next boundary by factors
  ``exp(.) <= 1``, so nothing but a sub-chunk's own ``exp(-G)`` is ever
  raised; rows that count no token and blocks past a row's count are
  passed over (:func:`use_chunk_kernel` is the gate, ``kda_chunked`` the
  twin it falls back to and the tests' reference; docs/kernels.md).

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


def _lane_tiles_on_one_tpu(state_dtype, k_dim: int, v_dim: int, mesh) -> bool:
    """What both kernels' gates ask first: a float32 state whose heads are
    whole lane tiles, on one TPU device (or interpret mode)."""
    if not _interpret_mode() and (platform_of(mesh) != "tpu" or not one_device(mesh)):
        return False
    return jnp.dtype(state_dtype) == F32 and k_dim % 128 == 0 and v_dim % 128 == 0


def use_step_kernel(*, state_dtype, k_dim: int, v_dim: int, heads: int, mesh=None) -> bool:
    """The gate of :func:`kda_step_rows`, from what a traced step can
    observe: a float32 state whose heads are whole lane tiles, on one TPU
    device.  Everything else is :func:`kda_step`'s."""
    return _lane_tiles_on_one_tpu(state_dtype, k_dim, v_dim, mesh) and _heads_a_step(heads, k_dim, v_dim) > 0


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


# -- the chunk kernel ------------------------------------------------------------------------

# Heads of a row a grid step of the chunk kernel holds: a sublane tile, so
# that a block of q, k, v or o is cut from (b, s, H, X) where it lies.
_CHUNK_HEADS = 8
# Heads of a step whose walk is one loop body: they are independent, so
# their products and substitutions interleave; the body is traced and
# lowered once a chunk program, and its size is set-up time.
_HEADS_UNROLLED = 2


def _subs_a_block(s: int) -> int:
    """Sub-chunks of ``SUB`` tokens a block of the chunk kernel's walk: the
    state is read and updated once a block, and four is as far as that
    pays (docs/kernels.md)."""
    return next(m for m in (4, 2, 1) if s % (m * SUB) == 0)


def _heads_a_chunk(h: int) -> int:
    """Heads of a row a grid step of the chunk kernel holds: a sublane
    tile of them, or all of a model's fewer; 0 where neither divides."""
    return _CHUNK_HEADS if h % _CHUNK_HEADS == 0 else h if h < _CHUNK_HEADS else 0


def _chunk_vmem_bytes(hb: int, s: int, k: int, v: int, h: int) -> int:
    """What a grid step of the chunk kernel holds in VMEM: the row's q, k
    and cumulated gate (s, hb, K) and v, o (s, hb, V), each double; beta
    (s, H padded to a lane tile), double; the group's state as it lies in
    HBM and transposed."""
    return 4 * (2 * s * hb * (3 * k + 2 * v) + 2 * s * max(h, 128) + 2 * hb * k * v)


def use_chunk_kernel(*, state_dtype, k_dim: int, v_dim: int, heads: int, s: int, mesh=None) -> bool:
    """The gate of :func:`kda_chunk_rows`, from what a traced call can
    observe: a float32 state whose heads are whole lane tiles and come in
    whole sublane tiles (or fewer than one), whole sub-chunks of tokens, a
    grid step that fits half the VMEM budget, on one TPU device.
    Everything else is :func:`kda_chunked`'s."""
    hb = _heads_a_chunk(heads)
    return (
        _lane_tiles_on_one_tpu(state_dtype, k_dim, v_dim, mesh)
        and hb > 0
        and s > 0
        and s % SUB == 0
        and _chunk_vmem_bytes(hb, s, k_dim, v_dim, heads) <= _VMEM_BUDGET_BYTES // 2
    )


def _substitute(A, x, at: int):
    """``(I + L) U = x`` for ``L`` the strictly lower (SUB, SUB) block of
    ``A`` at lanes ``at``: by substitution, a column of ``L`` a step (row
    ``t`` of ``x`` is final at step ``t``; ``L``'s zeros leave the rows
    above alone), the two sublane tiles apart so that the later steps
    touch the lower one only."""
    half = SUB // 2
    a_lo, a_hi, x_lo, x_hi = A[:half], A[half:], x[:half], x[half:]
    for t in range(half):
        row = x_lo[t : t + 1, :]
        if t < half - 1:
            x_lo = x_lo - a_lo[:, at + t : at + t + 1] * row
        x_hi = x_hi - a_hi[:, at + t : at + t + 1] * row
    for t in range(half, SUB - 1):
        x_hi = x_hi - a_hi[:, at + t : at + t + 1] * x_hi[t - half : t - half + 1, :]
    return jnp.concatenate([x_lo, x_hi], axis=0)


def _chunk_kernel(
    src_ref, n_ref, q_ref, k_ref, v_ref, g_ref, beta_ref, s_in, o_ref, s_out, land, ST, sem, *, m: int
):
    """One row's group of heads.  q_ref, k_ref, g_ref (1, s, hb, K), ``g``
    cumulated from each sub-chunk's start; v_ref, o_ref (1, s, hb, V);
    beta_ref (1, s, H); s_in, s_out (b, H, K, V), one buffer in HBM; land
    (hb, K, V) the group's state as it lies there, ST (hb, V, K) each
    head's transposed, so that a gate scales its lanes.  A head's row is
    walked in blocks of ``m`` sub-chunks.  A row that counts no token
    copies nothing and writes zeros; a block that starts at or past the
    row's count likewise."""
    del src_ref
    i, j = pl.program_id(0), pl.program_id(1)
    hb, V, K = ST.shape
    s, H = beta_ref.shape[1:]
    bt = m * SUB
    n_blocks = s // bt
    n_valid = n_ref[i]
    blocks = jnp.minimum((n_valid + bt - 1) // bt, n_blocks)
    group = pl.ds(j * hb, hb)
    nt = (((1,), (1,)), ((), ()))  # a (t, K) . b (s, K) -> (t, s)
    tn = (((0,), (0,)), ((), ()))  # a (s, V) . b (s, K) -> (V, K)
    head_lane = jax.lax.broadcasted_iota(jnp.int32, (bt, H), 1)
    t_ = jax.lax.broadcasted_iota(jnp.int32, (SUB, bt), 0)
    s_ = jax.lax.broadcasted_iota(jnp.int32, (SUB, bt), 1)

    def dot(a, b, dims=(((1,), (0,)), ((), ()))):
        return jax.lax.dot_general(a, b, dims, precision=HI, preferred_element_type=F32)

    def sub(x, a):
        return x[a * SUB : (a + 1) * SUB]

    def block(h, c):
        """Block ``c`` of head ``h`` of the group."""
        rows = pl.ds(pl.multiple_of(c * bt, bt), bt)
        G, k = g_ref[0, rows, h, :], k_ref[0, rows, h, :]
        beta = jnp.sum(jnp.where(head_lane == j * hb + h, beta_ref[0, rows, :], 0.0), axis=1, keepdims=True)
        up = jnp.exp(G)
        # Keys (times beta: the rows of the systems) and queries as the
        # state at their sub-chunk's start sees them; keys as their own
        # sub-chunk's later tokens see them.
        kb_in, q_in, k_out = k * up * beta, q_ref[0, rows, h, :] * up, k * jnp.exp(-G)
        vb = v_ref[0, rows, h, :] * beta
        starts, later, since, k_rd, q_rd = [], [], None, [], []
        for a in range(m):
            whole = sub(G, a)[SUB - 1 :, :]  # the sub-chunk's whole gate, (1, K)
            starts.append(later)  # the keys before, as the state at this sub-chunk's start sees them
            through = jnp.exp(whole)
            later = [x * through for x in later] + [sub(k, a) * jnp.exp(whole - sub(G, a))]
            scale = 1.0 if since is None else jnp.exp(since)  # from the block's start to the sub-chunk's
            k_rd.append(sub(kb_in, a) * scale)
            q_rd.append(sub(q_in, a) * scale)
            since = whole if since is None else since + whole
        # What the block's keys and queries find in the state it starts from, (2 bt, V).
        read = dot(jnp.concatenate(k_rd + q_rd, axis=0), ST[h], nt)
        u, p_rows = [], []
        for a in range(m):
            # M over P: this sub-chunk's rows against the keys before and its
            # own (token t of it is token a SUB + t of the block).
            keys = starts[a] + [sub(k_out, a)]
            if a < m - 1:
                keys.append(jnp.zeros(((m - 1 - a) * SUB, K), F32))
            ours = jnp.concatenate([sub(kb_in, a), sub(q_in, a)], axis=0)
            mp = dot(ours, jnp.concatenate(keys, axis=0), nt)  # (2 SUB, bt)
            A = jnp.where(s_ < a * SUB + t_, mp[:SUB], 0.0)
            p_rows.append(jnp.where(s_ <= a * SUB + t_, mp[SUB:], 0.0))
            x = sub(vb, a) - sub(read, a)
            if a:  # less what the rows solved so far put into the state
                x = x - dot(A, jnp.concatenate(u + [jnp.zeros(((m - a) * SUB, V), F32)], axis=0))
            u.append(_substitute(A, x, a * SUB))
        u = jnp.concatenate(u, axis=0)
        o_ref[0, rows, h, :] = read[bt:] + dot(jnp.concatenate(p_rows, axis=0), u)
        ST[h] = ST[h] * jnp.exp(since) + dot(u, jnp.concatenate(later, axis=0), tn)

    together = _HEADS_UNROLLED if hb % _HEADS_UNROLLED == 0 else 1

    def heads(hh, carry):
        mine = [hh * together + x for x in range(together)]
        for h in mine:
            ST[h] = land[h].T

        def blocks_of(c, carry):
            for h in mine:
                block(h, c)
            return carry

        jax.lax.fori_loop(0, blocks, blocks_of, None)
        for h in mine:
            land[h] = ST[h].T
        return carry

    def zeros(c, carry):
        o_ref[0, pl.ds(pl.multiple_of(c * bt, bt), bt)] = jnp.zeros((bt, hb, V), F32)
        return carry

    @pl.when(n_valid > 0)
    def _live():
        load = pltpu.make_async_copy(s_in.at[i, group], land, sem)
        load.start()
        load.wait()
        jax.lax.fori_loop(0, hb // together, heads, None)
        store = pltpu.make_async_copy(land, s_out.at[i, group], sem)
        store.start()
        store.wait()

    jax.lax.fori_loop(blocks, n_blocks, zeros, None)


@functools.partial(jax.jit, static_argnames=("m", "interpret"))
def _chunk_rows(src, n, q, k, v, G, beta, state, *, m: int, interpret: bool):
    b, H, K, V = state.shape
    s, hb = q.shape[1], _heads_a_chunk(H)
    groups = H // hb

    def at(i, j, src, n):
        """Block indices of grid step (row i, head group j): a row that
        counts nothing names the blocks of the step before it (the last
        live row's last group), so nothing is copied for it."""
        return src[i], 0, jnp.where(n[i] > 0, j, groups - 1), 0

    def wide(x):
        return pl.BlockSpec((1, s, hb, x), at)

    return pl.pallas_call(
        functools.partial(_chunk_kernel, m=m),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, groups),
            in_specs=[
                wide(K), wide(K), wide(V), wide(K),
                pl.BlockSpec((1, s, H), lambda i, j, src, n: (src[i], 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=[
                pl.BlockSpec((1, s, hb, V), lambda i, j, src, n: (i, 0, j, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            scratch_shapes=[
                pltpu.VMEM((hb, K, V), F32), pltpu.VMEM((hb, V, K), F32), pltpu.SemaphoreType.DMA(()),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((b, s, H, V), F32),
            jax.ShapeDtypeStruct(state.shape, F32),
        ],
        # The state leaf in place (operands count the two prefetched).
        input_output_aliases={7: 1},
        compiler_params=pltpu.CompilerParams(
            # In order: a row that counts nothing leans on the blocks of
            # the step before it.
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_BUDGET_BYTES,
        ),
        interpret=interpret,
        name="kda_chunk_rows",
    )(src, n, q, k, v, G, beta, state)


@jax.named_scope("layer/kda/scan")
def kda_chunk_rows(q, k, v, g, beta, state, n_valid, *, interpret=None):
    """:func:`kda_chunked` as a Pallas kernel, for rows of which the first
    ``n_valid`` (b,) tokens count and the others come with ``g = 0`` and
    ``beta = 0`` (as ``models/hybrid.py::_kda_mixer`` gives them): a grid
    step holds a group of heads' state in VMEM for the whole row and walks
    each head's row in blocks of up to four sub-chunks, each sub-chunk
    solved in ``_sub_chunk``'s closed form (the unit-triangular system by
    substitution); the state is read once and written once, into the
    buffer it came from.  A row that counts nothing is neither read nor
    written and its output is zeros, as is the output of a block that
    starts at or past ``n_valid`` (nothing reads those positions, and
    their tokens leave the state bit for bit as it was).  ``s`` a
    multiple of ``SUB``.  Float32 throughout, the products at the highest
    precision.  Returns (o (b, s, H, V) f32, state)."""
    if interpret is None:
        interpret = _interpret_mode()
    b, s, H, K = q.shape
    q, k, v, g, beta = (a.astype(F32) for a in (q, k, v, g, beta))
    G = jnp.cumsum(g.reshape(b, s // SUB, SUB, H, K), axis=2).reshape(b, s, H, K)  # in [-5 SUB, 0]
    rows = jnp.arange(b, dtype=jnp.int32)
    src = jnp.maximum(jax.lax.cummax(jnp.where(n_valid > 0, rows, -1)), 0)
    return _chunk_rows(
        src, n_valid.astype(jnp.int32), q, k, v, G, beta, state, m=_subs_a_block(s), interpret=interpret
    )
