"""A share of a wide expert layer: routing over all the published experts
(DeepSeek-V3's ``noaux_tc``: sigmoid scores, a selection bias, a group
limit; or the plain softmax top-k of a router with none of the three; or
the ZAYA router, a small MLP that carries its state from layer to layer
and picks one expert: :func:`route_mlp`), and the products of the experts
held here, which may be all of them.

The layer is told which experts it holds (``offset``, ``held``).  It
routes every token over all ``E`` router outputs, computes what its own
experts give for the choices that land on them, and leaves out what the
absent experts would add: on one chip the layer runs without its
exchange, and the partial result is what goes on (the four shares add up
to the whole: ``tests/test_hybrid_model.py``).

Dispatch is sorted, not one-hot: the local choices are ordered by
expert, their token rows gathered, and the three products run as grouped
matrix multiplications over the experts that received a row —
``jax.experimental.pallas.ops.tpu.megablox.gmm`` on the TPU, which
skips experts with no row, so a decode step streams the experts it
touches and not all that are held.  Off the TPU (tier-1 tests, the
rehearsal) a dense product over the experts held stands in.

``stacked_expert_mlp`` is the same dispatch for a few wide experts that
lie in a stack of several layers' (Mixtral's 8 of 4,096 x 14,336 under
``models/llama.py``'s scan): each expert's rows start at a row tile of
their own.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp

from generativeaiexamples_tpu.ops.dispatch import (
    one_device,
    platform_of,
    record,
)

F32 = jnp.float32
# Rows of the sorted choices are padded to a multiple of this (gmm's row
# tile); padding rows belong to no group and are never computed.
ROW_TILE = 128
# What the counters vector holds, in order (``models.hybrid`` sums it
# over layers and steps; ``Stats`` exports each under ``moe_<name>``).
COUNTERS = (
    "choices_routed", "choices_local", "experts_touched", "expert_rows_max",
    # Calls of ``expert_mlp``: one a layer and step, so ``experts_touched``
    # over it is the experts a layer's step streamed.
    "expert_layer_steps",
)


def select(sel, *, k, n_group, topk_group):
    """Group-limited top-k over selection scores ``sel`` (n, E): a group's
    score is the sum of its two largest, the best ``topk_group`` groups
    are kept and the ``k`` largest inside them taken.  Returns (n, k)
    int32.  Ties go to the lower index (``lax.top_k``).  One group is no
    limit: the plain top-k over all of ``sel``."""
    n, E = sel.shape
    if n_group == 1:
        return jax.lax.top_k(sel, k)[1].astype(jnp.int32)
    grouped = sel.reshape(n, n_group, E // n_group)
    group_score = jax.lax.top_k(grouped, 2)[0].sum(-1)  # (n, n_group)
    _, keep = jax.lax.top_k(group_score, topk_group)
    kept = jnp.zeros((n, n_group), bool).at[jnp.arange(n)[:, None], keep].set(True)
    masked = jnp.where(kept[:, :, None], grouped, -jnp.inf).reshape(n, E)
    return jax.lax.top_k(masked, k)[1].astype(jnp.int32)


SCORE_FUNCTIONS = {"sigmoid": jax.nn.sigmoid, "softmax": jax.nn.softmax}


def scores(x, w_router, score: str = "sigmoid"):
    """Routing scores in float32: (n, D) x (D, E) -> (n, E); each
    output's sigmoid, or the softmax over all E."""
    return SCORE_FUNCTIONS[score](
        jnp.dot(x.astype(F32), w_router.astype(F32),
                precision=jax.lax.Precision.HIGHEST)
    )


@jax.named_scope("layer/moe/router")
def route(x, w_router, bias, *, k, n_group, topk_group, norm_topk, scale,
          score: str = "sigmoid"):
    """Scores (``score``: sigmoid or softmax), group-limited top-k on
    ``score + bias``, weights from the scores alone.  ``bias`` None and
    ``n_group`` 1 give the plain top-k of a router with neither.

    x: (n, D); w_router: (D, E); bias: (E,) float32 or None.  Returns
    (idx (n, k) int32 over all E, weights (n, k) float32)."""
    s = scores(x, w_router, score)
    sel = s if bias is None else s + bias.astype(F32)
    idx = select(sel, k=k, n_group=n_group, topk_group=topk_group)
    w = jnp.take_along_axis(s, idx, axis=-1)
    if norm_topk:
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    return idx, w * scale


def balanced_bias(s, *, k, n_group, topk_group, iters: int = 75):
    """The selection bias that evens the experts' load on a sample of
    tokens with routing scores ``s`` (n, E) (:func:`scores` of a linear
    router, :func:`mlp_scores` of the ZAYA router): DeepSeek-V3's
    aux-loss-free balancing run to its fixed point — each round the bias
    of an expert over the mean load goes down a step and of one under it
    up, the step decaying from 0.03 to 6e-5.  This is what
    ``moe_router_enable_expert_bias`` leaves in a trained checkpoint;
    random weights with an arbitrary bias load the experts, and so each
    chip's share, unevenly by a few percent that change with the seed."""
    E = s.shape[1]

    def step(i, bias):
        idx = select(s + bias, k=k, n_group=n_group, topk_group=topk_group)
        load = jnp.zeros((E,), F32).at[idx.reshape(-1)].add(1.0)
        return bias - 0.03 * 0.92**i * jnp.sign(load - load.mean())

    return jax.lax.fori_loop(0, iters, step, jnp.zeros((E,), F32))


def mlp_scores(x, lp, prev, *, eps: float):
    """The ZAYA router's scores (arXiv:2511.17127): a down-projection of
    the token to the router's own width, the previous layer's router state
    added in (``router_gamma`` times it; ``prev`` None: the first layer,
    which has none before it), an RMSNorm, three products with a GELU
    (exact, the erf form) after the first two, and the softmax over the
    experts.  Float32 at the highest precision throughout, as
    :func:`scores`: one expert a token means a rounded score is another
    expert.

    x: (n, D); lp: the layer's ``router_*`` leaves; prev: (n, R) float32
    or None.  Returns (p (n, E) float32, rho (n, R) float32: this layer's
    router state after its average, which the next layer is handed)."""
    hi = jax.lax.Precision.HIGHEST
    w = lambda name: lp[name].astype(F32)
    with jax.named_scope("layer/moe/router/down"):
        rho = jnp.dot(x.astype(F32), w("router_down"), precision=hi) + w("router_down_b")
    if prev is not None:
        with jax.named_scope("layer/moe/router/average"):
            rho = rho + w("router_gamma") * prev
    with jax.named_scope("layer/moe/router/mlp"):
        y = rho * jax.lax.rsqrt(jnp.mean(rho * rho, axis=-1, keepdims=True) + eps)
        y = y * w("router_norm")
        for i in (1, 2):
            y = jnp.dot(y, w(f"router_w{i}"), precision=hi) + w(f"router_b{i}")
            y = jax.nn.gelu(y, approximate=False)
        logits = jnp.dot(y, w("router_w3"), precision=hi) + w("router_b3")
        return jax.nn.softmax(logits, axis=-1), rho


def route_mlp(x, lp, prev, *, eps: float):
    """:func:`route` for the ZAYA router: the one expert with the largest
    ``p + router_bias`` (a tie to the lower index), weighted by its
    probability as it is (renormalised over one choice it would be 1).
    Returns (idx (n, 1) int32, weights (n, 1) float32, rho (n, R))."""
    p, rho = mlp_scores(x, lp, prev, eps=eps)
    idx = select(p + lp["router_bias"].astype(F32), k=1, n_group=1, topk_group=1)
    return idx, jnp.take_along_axis(p, idx, axis=-1), rho


def use_gmm(mesh) -> bool:
    if os.environ.get("GAIE_MOE_KERNEL_INTERPRET") == "1":
        return True
    return platform_of(mesh) == "tpu" and one_device(mesh)


def _grouped(xs, w, group_sizes, pallas: bool, tiling):
    """(m, a) x (held, a, b) by row groups -> (m, b)."""
    if pallas:
        from jax.experimental.pallas.ops.tpu.megablox import gmm

        return gmm(
            xs, w, group_sizes, preferred_element_type=xs.dtype, tiling=tiling,
            interpret=platform_of(None) != "tpu",
        )
    # Dense stand-in: every row through every expert held, the row's own
    # group picked out.  Tiny sizes only.
    held = w.shape[0]
    ends = jnp.cumsum(group_sizes)
    group = jnp.searchsorted(ends, jnp.arange(xs.shape[0]), side="right")
    every = jnp.einsum("ma,eab->emb", xs, w, preferred_element_type=F32)
    pick = jax.nn.one_hot(group, held, dtype=F32)  # rows past the groups: zero
    return jnp.einsum("emb,me->mb", every, pick).astype(xs.dtype)


def _tile_of(x: int, options) -> int:
    """The first of ``options`` that divides ``x``, else 128."""
    return next((t for t in options if x % t == 0), 128)


def _tiling(a: int, b: int) -> tuple[int, int, int]:
    """gmm tiles for an (m, a) x (a, b) product: whole rows of 128, the
    widest column tiles that divide the sizes and fit VMEM.  A width that
    is 7 or 9 times 128 (896, 2,304) divides by none of the powers of
    two: it takes 896 or 1,152, since a tile of 128 or 256 costs one grid
    step per 64-128 KB of weights and the steps, not the stream, then set
    the time (the v5e: 1.8 ms for a product of 64 experts of 2,304 x
    1,792 in 4,977 steps of (256, 256), against 0.65 ms to stream them;
    PERF.md section 6, PR 31)."""
    return (
        ROW_TILE,
        _tile_of(a, (1152, 896, 512, 256, 128)),
        _tile_of(b, (896, 768, 512, 256, 128)),
    )


# An expert's form: SwiGLU (``w_gu_e``, gate and up side by side), or
# ``relu2``, ``W2 relu(W1 x)^2`` with no gate (``w_up_e``).
ACTIVATIONS = ("swiglu", "relu2")


def expert_mlp(x, idx, weights, valid, lp, *, offset: int, held: int, mesh=None,
               act: str = "swiglu"):
    """What the experts held give: ``sum_i w_i E_i(x)`` over the choices
    ``i`` with ``offset <= idx_i < offset + held``.

    x: (n, D); idx, weights: (n, k); valid: (n,) bool (a padded position
    routes nowhere); lp: ``w_gu_e`` (held, D, 2F) gate and up side by
    side (``act`` ``relu2``: ``w_up_e`` (held, D, F), no gate),
    ``w_down_e`` (held, F, D).  Returns (y (n, D), counters int32 in the
    order of ``COUNTERS``)."""
    n, d = x.shape
    k = idx.shape[1]
    f = lp["w_down_e"].shape[1]
    pallas = record(f"moe_experts n={n} held={held}", use_gmm(mesh))
    with jax.named_scope("layer/moe/dispatch"):
        local = (idx >= offset) & (idx < offset + held) & valid[:, None]
        group = jnp.where(local, idx - offset, held).reshape(-1)  # (n k,)
        m = -(-n * k // ROW_TILE) * ROW_TILE
        group = jnp.pad(group, (0, m - n * k), constant_values=held)
        order = jnp.argsort(group, stable=True)  # local choices first, by expert
        starts = jnp.searchsorted(
            group[order], jnp.arange(held + 1, dtype=group.dtype), side="left"
        ).astype(jnp.int32)
        sizes = starts[1:] - starts[:-1]  # rows of each expert held
        n_local = starts[held]
        xs = x[jnp.minimum(order // k, n - 1)]
    with jax.named_scope("layer/moe/experts"):
        if act == "relu2":
            h = _grouped(xs, lp["w_up_e"], sizes, pallas, _tiling(d, f))
            mid = jnp.square(jax.nn.relu(h.astype(F32))).astype(x.dtype)
        else:
            h = _grouped(xs, lp["w_gu_e"], sizes, pallas, _tiling(d, 2 * f))
            mid = (jax.nn.silu(h[:, :f].astype(F32)) * h[:, f:].astype(F32)).astype(x.dtype)
        ys = _grouped(mid, lp["w_down_e"], sizes, pallas, _tiling(f, d))
    with jax.named_scope("layer/moe/combine"):
        # Back to (token, choice) order by a gather.  Rows past the local
        # choices were never computed: zero, not whatever the buffer held.
        back = jnp.argsort(order)[: n * k]
        per_choice = ys[back].reshape(n, k, d).astype(F32)
        w_local = jnp.where(local, weights, 0.0)
        y = jnp.where(local[..., None], per_choice * w_local[..., None], 0.0).sum(1)
    counters = jnp.stack(
        [valid.sum().astype(jnp.int32) * k, n_local, (sizes > 0).sum(), sizes.max(), 1]
    ).astype(jnp.int32)
    return y.astype(x.dtype), counters


def _row_tile(choices: int, n_experts: int) -> int:
    """Rows of ``gmm``'s row tile where each expert's rows start at a tile
    of their own: twice what an expert receives on average, in whole
    tiles of 128 and at most 256.  Nearly every expert then fits one tile,
    so its matrices stream once, and a tile of 256 rows multiplies for
    about as long as its weights stream on the v5e; 512 rows multiply for
    twice as long (PERF.md section 6, PR 37: the three products of a layer
    of Mixtral's take 4.5 / 5.0 / 9.4 ms at 128 / 256 / 512 for one chunk,
    7.6 / 5.1 / 9.6 for two)."""
    return min(2, max(1, -(-2 * choices // (n_experts * ROW_TILE)))) * ROW_TILE


def _wide_tiling(a: int, b: int, tm: int) -> tuple[int, int, int]:
    """gmm tiles for a few experts of thousands of columns: a grid step's
    weight tile is 3.7 MB (two of them, the row tile and the accumulator
    are 12 MB of VMEM at 256 rows), not ``_tiling``'s 0.9 MB, so the
    steps' fixed cost is a smaller share of the stream: Mixtral's gate
    product 1.99 -> 1.59 ms, its down product 2.07 -> 1.67 (PERF.md
    section 6, PR 37)."""
    return (
        tm,
        _tile_of(a, (1792, 1024, 512, 256, 128)),
        _tile_of(b, (1792, 1024, 896, 512, 256, 128)),
    )


def stacked_expert_mlp(x, idx, weights, valid, stack, *, first, n_experts: int, mesh=None):
    """``sum_i w_i E_i(x)`` over a token's ``k`` choices, for experts that
    are groups ``first .. first + n_experts`` of a stack of G: the expert
    leaves of several layers viewed as (G, ...), so that the layer loop
    hands the grouped products the stack whole and no slice of it is
    copied (every group outside the layer's own is empty, and an empty
    group is skipped).  Dropless by construction.

    The choices are ordered by expert and each expert's rows start at a
    row tile of their own (``_row_tile``): a tile that two experts share
    is a visit, and a stream of its weight tiles, for each of them.

    x: (n, D); idx, weights: (n, k), idx in [0, n_experts); valid: (n,)
    bool (a padded position routes nowhere); stack: ``w_gate_e``,
    ``w_up_e`` (G, D, F), ``w_down_e`` (G, F, D); first: int32 scalar,
    traced.  Returns y (n, D)."""
    n, d = x.shape
    k = idx.shape[1]
    G, f = stack["w_down_e"].shape[:2]
    pallas = record(f"moe_experts n={n} held={G}", use_gmm(mesh))
    tm = _row_tile(n * k, n_experts)
    # Rows enough for any routing: each expert pads its last tile.
    m = (n * k + n_experts * (tm - 1)) // tm * tm
    with jax.named_scope("layer/moe/dispatch"):
        expert = jnp.where(valid[:, None], idx, n_experts).reshape(-1)  # (n k,)
        order = jnp.argsort(expert, stable=True)  # valid choices first, by expert
        by_expert = expert[order]
        starts = jnp.searchsorted(
            by_expert, jnp.arange(n_experts + 1, dtype=expert.dtype), side="left"
        ).astype(jnp.int32)
        sizes = -(-(starts[1:] - starts[:-1]) // tm) * tm  # whole tiles
        tile_starts = jnp.cumsum(sizes) - sizes
        e = jnp.minimum(by_expert, n_experts - 1)
        # Where each sorted choice lies; a choice that does not count: past the end.
        row = jnp.where(
            by_expert < n_experts,
            tile_starts[e] + jnp.arange(n * k, dtype=jnp.int32) - starts[e],
            m,
        )
        xs = x[jnp.zeros((m,), jnp.int32).at[row].set(order // k, mode="drop")]
        group_sizes = jax.lax.dynamic_update_slice(
            jnp.zeros((G,), jnp.int32), sizes, (first,)
        )
    with jax.named_scope("layer/moe/experts"):
        gate = _grouped(xs, stack["w_gate_e"], group_sizes, pallas, _wide_tiling(d, f, tm))
        up = _grouped(xs, stack["w_up_e"], group_sizes, pallas, _wide_tiling(d, f, tm))
        act = (jax.nn.silu(gate.astype(F32)) * up.astype(F32)).astype(x.dtype)
        ys = _grouped(act, stack["w_down_e"], group_sizes, pallas, _wide_tiling(f, d, tm))
    with jax.named_scope("layer/moe/combine"):
        # Back to (token, choice) order by a gather.  A row past the tiles
        # that hold a choice was never computed: zero, not what the buffer held.
        at = jnp.zeros((n * k,), jnp.int32).at[order].set(jnp.minimum(row, m - 1))
        per_choice = ys[at].reshape(n, k, d).astype(F32)
        y = jnp.where(valid[:, None, None], per_choice * weights[..., None], 0.0).sum(1)
    return y.astype(x.dtype)
