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

Both hand ``gmm`` the tiles of one rule, :func:`_tiles`, read from the
product's shapes alone: a weight tile of megabytes that holds the whole
of K wherever that fits, so that a product streams each expert it
touches once (``expert_streams`` in ``COUNTERS`` counts it).
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
    # Times the call's first product (gate-up, or ``relu2``'s up: the
    # larger share of an expert's bytes) streams an expert's matrix, as
    # the kernel's pipeline fetches it: ``_streams``.  Over
    # ``experts_touched`` it is 1.0 where every expert streams once.
    "expert_streams",
    # Choices that fell on identity experts (router outputs from
    # ``zero_from`` on: they add ``w x`` and compute nothing); they count
    # under ``choices_routed`` too, never under ``choices_local``.
    "choices_zero",
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


# What a grid step of ``gmm`` may hold in VMEM by ``_vmem_bytes``' count,
# of the 16 MiB that Mosaic gives a kernel unasked (megablox asks for no
# more): on the v5e's compiler the refusals start at 14.5 MiB by that
# count (PERF.md section 6, PR 45).
VMEM_BUDGET_BYTES = 14 << 20
# A weight tile that splits K stays under this: two buffers of it under
# half of the scoped limit.
SPLIT_TILE_BYTES = 4 << 20
# A product walks its rows once for each column tile: a whole-K tile is
# taken only where that is at most this many passes over the lhs.
MAX_LHS_PASSES = 16


def _vmem_bytes(tm: int, tk: int, tn: int, itemsize: int) -> int:
    """A grid step's blocks: two buffers each of the weight tile, the lhs
    tile and the output tile, and the float32 accumulator."""
    return 2 * itemsize * (tk * tn + tm * tk + tm * tn) + 4 * tm * tn


def _divisors(x: int) -> list[int]:
    """The multiples of 128 that divide ``x``, ascending; a size that none
    divides takes 128 (``gmm`` masks the remainder)."""
    return [t for t in range(128, x + 1, 128) if x % t == 0] or [128]


def _tiles(a: int, b: int, tm: int, itemsize: int) -> tuple[int, int, int]:
    """``gmm``'s tiles (tm, tk, tn) for an (m, a) x (a, b) product of
    ``itemsize`` bytes an element under a row tile of ``tm``: multiples of
    128 that divide the sizes, a weight tile of megabytes.

    ``gmm``'s grid is (column tiles, visits of a (row tile, group) pair,
    k tiles), and its pipeline fetches a block only when the block's
    index changes.  With one k tile the visits of a group that spans
    several row tiles ask for the same weight block, so the expert
    streams once, and the lhs tile is fetched once a row tile and not
    once a visit; with two or more, every visit streams the expert again
    and every grid step its lhs tile (Mellum's chunk programs: 1.2-1.7
    streams an expert with tk = a / 2; Mistral-Small-4's 47 visits of 32
    experts).  So:

    * ``tk = a`` with the widest ``tn`` whose blocks fit
      ``VMEM_BUDGET_BYTES``, the whole matrix where that fits (one pass
      over the rows), unless the rows would be walked more than
      ``MAX_LHS_PASSES`` times (Mixtral's 4,096 x 14,336: 28 passes over
      rows of 4,096 cost more than its tile-aligned groups save);
    * else the largest weight tile under ``SPLIT_TILE_BYTES`` that fits,
      the one nearest to square of several: (1024, 1792) and
      (1792, 1024) for Mixtral's products, PR 37's tiles (3.7 MB: 1.99 ->
      1.59 ms a product against tiles of 0.9 MB).

    Tiles of 0.26-0.9 MB cost a grid step per tile and the lhs tile anew
    at every step: K-EXAONE's gate-up product 1.43 -> 1.11 ms a call,
    Ling's down 1.27 -> 0.73, Mistral-Small-4's gate-up 2.82 -> 1.90 on
    the v5e (PERF.md section 6, PR 45: every candidate beside these)."""
    ks, ns = _divisors(a), _divisors(b)
    if ks[-1] >= a:  # a tile can hold the whole of K
        wide = [tn for tn in ns if _vmem_bytes(tm, ks[-1], tn, itemsize) <= VMEM_BUDGET_BYTES]
        if wide and -(-b // wide[-1]) <= MAX_LHS_PASSES:
            return tm, ks[-1], wide[-1]
    fits = [
        (tk, tn) for tk in ks for tn in ns
        if tk * tn * itemsize < SPLIT_TILE_BYTES
        and _vmem_bytes(tm, tk, tn, itemsize) <= VMEM_BUDGET_BYTES
    ]
    tk, tn = max(fits, key=lambda t: (t[0] * t[1], -max(t) / min(t)))
    return tm, tk, tn


def _streams(starts, tiling: tuple[int, int, int], a: int):
    """Times a product with ``tiling`` streams an expert's matrix, summed
    over the groups that begin at ``starts`` (held + 1,): one k tile, once
    each group that has a row; else once each row tile its rows touch."""
    tm, tk, _ = tiling
    begin, end = starts[:-1], starts[1:]
    tiles = 1 if tk >= a else -(-end // tm) - begin // tm
    return jnp.where(end > begin, tiles, 0).sum()


# An expert's form: SwiGLU (``w_gu_e``, gate and up side by side), or
# ``relu2``, ``W2 relu(W1 x)^2`` with no gate (``w_up_e``).
ACTIVATIONS = ("swiglu", "relu2")


def expert_mlp(x, idx, weights, valid, lp, *, offset: int, held: int, mesh=None,
               act: str = "swiglu", zero_from: int | None = None):
    """What the experts held give: ``sum_i w_i E_i(x)`` over the choices
    ``i`` with ``offset <= idx_i < offset + held``; with ``zero_from``, the
    router's outputs from that one on are identity experts (LongCat-Flash's
    zero-computation experts: no weights, no rows in the grouped products),
    and every choice ``idx_i >= zero_from`` adds ``w_i x``, whichever share
    of the real experts is held (a token's own chip adds them: they need
    no exchange).

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
        first = _tiles(d, f if act == "relu2" else 2 * f, ROW_TILE, x.dtype.itemsize)
        streams = _streams(starts, first, d)
    with jax.named_scope("layer/moe/experts"):
        if act == "relu2":
            h = _grouped(xs, lp["w_up_e"], sizes, pallas, first)
            mid = jnp.square(jax.nn.relu(h.astype(F32))).astype(x.dtype)
        else:
            h = _grouped(xs, lp["w_gu_e"], sizes, pallas, first)
            mid = (jax.nn.silu(h[:, :f].astype(F32)) * h[:, f:].astype(F32)).astype(x.dtype)
        ys = _grouped(mid, lp["w_down_e"], sizes, pallas, _tiles(f, d, ROW_TILE, x.dtype.itemsize))
    with jax.named_scope("layer/moe/combine"):
        # Back to (token, choice) order by a gather.  Rows past the local
        # choices were never computed: zero, not whatever the buffer held.
        back = jnp.argsort(order)[: n * k]
        per_choice = ys[back].reshape(n, k, d).astype(F32)
        w_local = jnp.where(local, weights, 0.0)
        y = jnp.where(local[..., None], per_choice * w_local[..., None], 0.0).sum(1)
    n_zero = 0
    if zero_from is not None:
        with jax.named_scope("layer/moe/zero"):
            zero = (idx >= zero_from) & valid[:, None]
            y = y + jnp.where(zero, weights, 0.0).sum(1, keepdims=True) * x.astype(F32)
            n_zero = zero.sum()
    counters = jnp.stack(
        [valid.sum().astype(jnp.int32) * k, n_local, (sizes > 0).sum(), sizes.max(), 1, streams,
         n_zero]
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
        wide = _tiles(d, f, tm, x.dtype.itemsize)
        gate = _grouped(xs, stack["w_gate_e"], group_sizes, pallas, wide)
        up = _grouped(xs, stack["w_up_e"], group_sizes, pallas, wide)
        act = (jax.nn.silu(gate.astype(F32)) * up.astype(F32)).astype(x.dtype)
        ys = _grouped(act, stack["w_down_e"], group_sizes, pallas, _tiles(f, d, tm, x.dtype.itemsize))
    with jax.named_scope("layer/moe/combine"):
        # Back to (token, choice) order by a gather.  A row past the tiles
        # that hold a choice was never computed: zero, not what the buffer held.
        at = jnp.zeros((n * k,), jnp.int32).at[order].set(jnp.minimum(row, m - 1))
        per_choice = ys[at].reshape(n, k, d).astype(F32)
        y = jnp.where(valid[:, None, None], per_choice * weights[..., None], 0.0).sum(1)
    return y.astype(x.dtype)
