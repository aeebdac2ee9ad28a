"""The ``dots3_note`` family (dots3-note-prev's language model): latent
attention in two kinds in one stack.  A ``full`` layer (128 heads over a
latent of ``kv_lora_rank`` 512 and a rope key of 64: 1,152 B a token in
bf16) attends only the ``index_topk`` 2,048 rows a learned indexer selects
(``index_n_heads`` 64 heads of ``index_head_dim`` 128 score every earlier
position from one cached index key a token: 256 B more); a ``sliding``
layer (64 heads over a latent of ``swa_kv_lora_rank`` 1,024: 2,176 B a
row) sees the last ``sliding_window_size`` 513 positions from a ring of
latent rows.  Both kinds have a low-rank query, a sigmoid gate a head and
rescaled latents; layer 0 has a dense SwiGLU, the others sigmoid-routed
experts, 8 a token of ``num_experts_published`` 256, with one shared
expert; the configuration holds a share of the experts
(``n_routed_experts`` of them, from ``engine.expert_offset``).

What a row of ``benchmarks/README.md``'s layout table would say (that file
is not a ``model_config`` PR's to edit): ``arch/dots3_note.py`` maps
``configs/dots3-note-prev-l6e32.json`` to the program's
``IndexedLatentConfig`` and holds its counts; ``dots3_note_reference.py``
beside ``run.py`` is the plain float32 reference (a copy of
``generativeaiexamples_tpu/models/dots3_note_reference.py``);
``traffic/doc-mid.json`` and ``traffic/doc-mid-closed.json`` are the
cell's mix (documents of 2.8k-14k tokens under one template, 16 waiting
clients); ``layer_metrics/prefill_selected_rows_pct.py`` and
``decode_index_rows_pct.py`` read the counters the indexer added.

``llama_config`` calls the program's own mapping from the public keys
(``models.hybrid.from_hf_config``) and returns its
``IndexedLatentConfig``, which ``Scheduler`` takes as it takes a
``LlamaConfig``.  ``last_logits`` below holds the program's logits, from
its chunked prefill and its decode step, and the sets its indexer selects
to the reference's before it hands the reference's logits to the harness.
The counts further down are what the ALGORITHM needs, from shapes alone
(the indexer over every causal pair, attention over ``min(t + 1, 2048)``
rows, the index keys and the selected rows a decode step must read, the
rings), not what the program does; ``tests/test_arch_dots3_note.py`` holds
them to the table of the configuration's cut worked by hand.
"""

from __future__ import annotations

import contextlib
import functools
import itertools
import json
import threading

import jax
import jax.numpy as jnp
import numpy as np

import dots3_note_reference

BF16 = 2


def llama_config(model: dict, engine: dict):
    """The public config.json keys -> the program's ``IndexedLatentConfig``."""
    from generativeaiexamples_tpu.models import hybrid

    if not hasattr(hybrid, "IndexedLatentConfig"):
        # The commit before the one that added the family: fail at once.
        raise SystemExit("benchmarks/arch/dots3_note.py: this program has no dots3_note family "
                         "(models/hybrid.py lacks IndexedLatentConfig)")
    if engine["weight_dtype"] != "bfloat16":
        raise ValueError("this family is served with bf16 weights only")
    if int(engine["experts_held"]) != int(model["n_routed_experts"]):
        raise ValueError("engine.experts_held and n_routed_experts (the experts held) disagree")
    ref = model["reference"]
    # ``last_logits`` is called without the configuration: its limits,
    # the server's chunk and the positions that go through the decode
    # step are kept from here.
    _CHECK.update(limits=dict(ref["logit_share_limits"]), decode=int(ref["decode_positions"]),
                  chunk=int(engine["prefill_chunk_tokens"]),
                  overlap_floor=float(ref["index_overlap_floor"]))
    cfg = hybrid.from_hf_config(
        model, max_len=int(engine["max_len"]), expert_offset=int(engine["expert_offset"]),
        kv_dtype=str(engine["kv_dtype"]),
    )
    by_kind = hybrid.state_bytes(cfg, int(engine["max_batch"]), int(engine["max_len"]))
    shapes = jax.eval_shape(lambda: hybrid.init_params(cfg, jax.random.PRNGKey(0)))
    print(json.dumps({
        "bench": "state bytes", "max_len": int(engine["max_len"]),
        "weight_bytes": sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(shapes)),
        "state_bytes_full": by_kind["full"], "state_bytes_window": by_kind["window"],
        # Of a full layer's stored latent row the latent and the rope key
        # fill this many bytes: the rest are zeros up to whole lanes.
        "latent_row_bytes_used": int(row_bytes(model, engine)["full"]),
        "latent_row_bytes_stored": cfg.latent_width * cfg.state_dtype.itemsize,
        "index_key_bytes": int(row_bytes(model, engine)["index"]),
        "snapshot_bytes": cfg.snapshot_bytes(int(engine["max_len"])),
    }), flush=True)
    return cfg


# -- the comparison that decides ``correct`` -------------------------------------
#
# As ``arch/mistral4.py``'s (the program's logits against the reference's
# at every position of the prompt, through the calls the scheduler's
# programs make in the measured window and at their shapes: a state of
# ``max_len`` rows a slot, the prompt but its last ``decode_positions``
# tokens a chunk at a time through ``prefill_rows`` beside a pad row, those
# last tokens one a step through ``decode_step`` over every slot; the
# shares ``p10``, ``p50``, ``p90`` and ``decode_p50`` held to
# ``reference.logit_share_limits``), and one reading more.  A selection is
# a step function: where a query's 2,048th and 2,049th scores lie within
# bf16 rounding, program and reference keep different rows, and the logits
# alone cannot tell a sound indexer from one that keeps other rows of like
# weight.  So the rows those very calls KEEP are read too: while the
# check's chunk program and decode step are traced, the two selections the
# ``mla`` mixer calls (``ops/mla.py::select_mask`` in a chunk,
# ``select_rows`` in a decode step) also hand what they return to the host
# (``_tapped``), where it is tallied against the reference's sets of that
# layer at those positions: ``index_overlap`` is the share of the (query,
# row) pairs the program kept that the reference kept too, over the
# queries past ``index_topk`` (before it every row is kept) of all full
# layers, the prefilled positions from the chunk program's mask and the
# last ``decode_positions`` from the decode step's gather; it has a floor,
# ``reference.index_overlap_floor``.  Nothing is computed again for it: the
# queries are the program's own stream, the keys what its calls wrote, the
# kept rows what its attention was given.  The reference prompts are
# longer than twice ``index_topk``, so at their end the selection keeps
# under half of the rows.

_CHECK: dict = {}
QUANTILES = {"p10": 0.1, "p50": 0.5, "p90": 0.9}
# Positions of a float32 (positions, vocabulary) block of logits.
BLOCK = 256


# The check's state: the slot the prompt lives in and one before it that
# holds nothing, so that a row of a call is not the slot of its number.
CHECK_SLOTS = 2


class _Selected:
    """The (query, row) pairs the check's programs kept, tallied against
    the reference's as the programs run: ``expect`` before a prompt, then
    the selections' taps call it from the runtime's threads."""

    def __init__(self):
        self.lock = threading.Lock()
        self.expect([], 0, 0, 0)

    def expect(self, kept: list, topk: int, n_prefill: int, n: int) -> None:
        """``kept``: a full layer each, the reference's (positions,
        positions) sets; a chunk's queries are judged from ``topk`` to
        ``n_prefill``, a decode step's from there to ``n``."""
        self.kept = kept
        self.judged = {"chunk": (topk, n_prefill), "step": (max(topk, n_prefill), n)}
        self.program = self.both = self.reference = self.queries = 0

    def __call__(self, layer: int, phase: str, pos, mask) -> None:
        pos, mask = np.asarray(pos), np.asarray(mask)
        lo, hi = self.judged[phase]
        rows = np.flatnonzero((pos >= lo) & (pos < hi))
        if not rows.size:
            return
        want = self.kept[layer][pos[rows]]
        mine = mask[rows]
        with self.lock:
            self.program += int(mine.sum())
            self.both += int((mine[:, : want.shape[1]] & want).sum())
            self.reference += int(want.sum())
            self.queries += int(rows.size)


_SELECTED = _Selected()


@contextlib.contextmanager
def _tapped(phase: str, n_full: int):
    """A step program TRACED under this hands what its selections return
    to ``_SELECTED`` whenever it runs: the ``mla`` layers are traced in
    their order, so the n-th selection traced is the n-th full layer's.
    A chunk's selection (one row of the batch, ``s`` queries at
    consecutive positions, the first of which always counts and sees
    position + 1 scores) gives its mask; a decode step's (a query a slot)
    the rows it gathers, as a mask."""
    from generativeaiexamples_tpu.ops import mla

    plain_mask, plain_rows = mla.select_mask, mla.select_rows
    order = itertools.count()

    def hand(pos, mask):
        jax.debug.callback(functools.partial(_SELECTED, next(order), phase), pos, mask)

    def select_mask(scores, k):
        mask = plain_mask(scores, k)
        s, T = scores.shape[-2:]
        first = jnp.sum(scores[..., 0, :] > -jnp.inf, axis=-1) - 1
        hand((first[..., None] + jnp.arange(s)).reshape(-1), mask.reshape(-1, T))
        return mask

    def select_rows(scores, k):
        idx, keep = plain_rows(scores, k)
        b, T = scores.shape
        mask = jnp.zeros((b, T), bool).at[jnp.arange(b)[:, None], idx].set(keep)
        hand(jnp.sum(scores > -jnp.inf, axis=-1) - 1, mask)
        return idx, keep

    mla.select_mask, mla.select_rows = select_mask, select_rows
    try:
        yield
    finally:
        mla.select_mask, mla.select_rows = plain_mask, plain_rows
    if next(order) != n_full:
        raise RuntimeError("the selections traced are not one a full layer: index_overlap cannot be read")


@functools.lru_cache(maxsize=4)
def _programs(cfg, chunk_tokens: int):
    """The serving model, and the two calls the scheduler's programs make
    of it, each returning the prompt's float32 logits: a chunk of the last
    slot beside a pad row through ``prefill_rows`` at the chunk programs'
    widest window, and ``decode_step`` over every slot at the widest
    decode window; both tapped (``_tapped``)."""
    from generativeaiexamples_tpu.engine.serving_models import serving_model

    max_len = cfg.max_seq_len
    model = serving_model(cfg, None, max_len)
    window = model.chunk_windows(chunk_tokens)[-1]
    slots = jnp.arange(CHECK_SLOTS, dtype=jnp.int32)
    mine = slots == CHECK_SLOTS - 1
    n_full = len(cfg.layers_of("mla"))

    @functools.partial(jax.jit, donate_argnums=(1,))
    def chunk(params, state, tokens, start, n):
        rows = jnp.where(mine[:, None], tokens[None], 0)
        with _tapped("chunk", n_full):
            state, hidden, _ = model.prefill_rows(
                params, state, rows, jnp.where(mine, start, 0), jnp.where(mine, n, 0), slots, window)
        return state, model.logits(params, hidden[-1:])[0].astype(jnp.float32)

    @functools.partial(jax.jit, donate_argnums=(1,))
    def step(params, state, token, pos):
        with _tapped("step", n_full):
            state, logits, _ = model.decode_step(
                params, state, jnp.where(mine, token, 0), jnp.where(mine, pos, 0),
                mine.astype(jnp.int32), max_len)
        return state, logits[-1:].astype(jnp.float32)

    return model, chunk, step


@jax.jit
def _shares(got, want):
    """Each position's |got - want|_rms / |want|_rms."""
    return jnp.sqrt(((got - want) ** 2).mean(-1)) / jnp.sqrt((want**2).mean(-1))


def logit_shares(params, cfg, tokens, pad_to: int, served=None):
    """((n,) shares, (V,) reference logits at the last position, the
    index overlap): the program's logits against the reference's at every
    position of one prompt, a block of positions at a time, and the share
    of the (query, row) pairs its indexer kept that the reference kept.
    ``served`` (absent: ``cfg``) is the configuration the program runs,
    which a control changes."""
    n = len(tokens)
    n_prefill = max(1, n - _CHECK["decode"])
    served = served or cfg
    model, chunk, step = _programs(served, _CHECK["chunk"])
    # The reference over the prompt padded to one length: one compiled
    # reference for every prompt of a run (every layer is causal, so no
    # position before the pad sees it).
    padded = list(tokens) + [0] * (pad_to - n)
    kept_full, x = [], None
    for kind, x, kept in dots3_note_reference.layers(params, cfg, padded):
        if kind[0] == "mla":
            kept_full.append(np.asarray(kept))
    _SELECTED.expect(kept_full, served.index_topk, n_prefill, n)
    want = lambda lo, hi: dots3_note_reference.head(params, cfg, x[lo:hi])
    state = model.init_state(CHECK_SLOTS, cfg.max_seq_len)
    toks = np.zeros((pad_to + _CHECK["chunk"],), np.int32)
    toks[:n] = tokens
    shares = []
    for start in range(0, n_prefill, _CHECK["chunk"]):
        piece = toks[start : start + _CHECK["chunk"]]
        count = min(n_prefill - start, len(piece))
        state, got = chunk(params, state, jnp.asarray(piece), jnp.int32(start), jnp.int32(count))
        for lo in range(0, count, BLOCK):
            hi = min(lo + BLOCK, count)
            shares.append(np.asarray(_shares(got[lo:hi], want(start + lo, start + hi))))
    decoded = []
    for pos in range(n_prefill, n):
        state, got = step(params, state, jnp.int32(toks[pos]), jnp.int32(pos))
        decoded.append(got)
    want_tail = want(n_prefill, n) if decoded else None
    if decoded:
        shares.append(np.asarray(_shares(jnp.concatenate(decoded), want_tail)))
    last = want_tail[-1] if decoded else want(n - 1, n)[0]
    jax.effects_barrier()
    if _SELECTED.queries != len(kept_full) * max(0, n - served.index_topk):
        raise RuntimeError(
            f"{_SELECTED.queries} selections were read where {len(kept_full)} full layers' "
            f"queries from {served.index_topk} to {n} select")
    # A prompt no longer than ``index_topk`` has no query that selects.
    share_kept = _SELECTED.both / _SELECTED.program if _SELECTED.program else 1.0
    return np.concatenate(shares), np.asarray(last), share_kept


def share_quantiles(share, n_decoded: int) -> dict:
    """Quantiles of those shares over a prompt's prefilled positions, and
    the median over the positions that went through the decode step."""
    share = np.asarray(share, np.float64)
    prefilled = share[: len(share) - n_decoded] if n_decoded else share
    out = {name: float(np.quantile(prefilled, q)) for name, q in QUANTILES.items()}
    if n_decoded:
        out["decode_p50"] = float(np.quantile(share[-n_decoded:], 0.5))
    return out


def outside_limits(shares: dict, index_overlap: float) -> list:
    """The readings that leave their limit: a logit share over its own,
    ``index_overlap`` under its floor."""
    outside = sorted(k for k, v in shares.items() if not v <= _CHECK["limits"][k])
    if not index_overlap >= _CHECK["overlap_floor"]:
        outside.append("index_overlap")
    return outside


def last_logits(params, cfg, tokens, pad_to: int = 0):
    """The float32 reference's logits at the prompt's last position, if
    the program's logits over the prompt lie within the limits of the
    reference's and its indexer keeps the reference's rows; else logits no
    served token agrees with (one entry more than the vocabulary, and the
    maximum there: gap 1)."""
    n, pad_to = len(tokens), max(pad_to, len(tokens))
    share, want_last, index_overlap = logit_shares(params, cfg, tokens, pad_to)
    shares = share_quantiles(share, min(_CHECK["decode"], n - 1))
    outside = outside_limits(shares, index_overlap)
    print(json.dumps({"bench": "logit check", **shares, "index_overlap": index_overlap,
                      "outside": outside}), flush=True)
    if outside:
        return np.append(np.zeros(want_last.shape[0], np.float32), np.float32(1.0))
    return want_last


# -- the counts ------------------------------------------------------------------


def _kinds(model: dict) -> list:
    """The kept layers' kinds: ``full_attention`` or ``sliding_attention``."""
    return list(model["layer_types"])[: int(model["num_hidden_layers"])]


def _sizes(model: dict, kind: str) -> dict:
    pre = "" if kind == "full_attention" else "swa_"
    return {
        "H": int(model[pre + "num_attention_heads"]), "r_q": int(model[pre + "q_lora_rank"]),
        "r": int(model[pre + "kv_lora_rank"]), "nope": int(model[pre + "qk_nope_head_dim"]),
        "rope": int(model[pre + "qk_rope_head_dim"]), "vd": int(model[pre + "v_head_dim"]),
    }


def part_params(model: dict) -> dict:
    """Parameters of one of each part."""
    D = int(model["hidden_size"])
    expert = 3 * D * int(model["moe_intermediate_size"])

    def attention(kind):
        z = _sizes(model, kind)
        # W_qa, W_qb, W_kva, W_kvb, W_o, W_g
        return (D * z["r_q"] + z["r_q"] * z["H"] * (z["nope"] + z["rope"]) + D * (z["r"] + z["rope"])
                + z["r"] * z["H"] * (z["nope"] + z["vd"]) + z["H"] * z["vd"] * D + D * z["H"])

    HI, dI = int(model["index_n_heads"]), int(model["index_head_dim"])
    return {
        "full": attention("full_attention"),
        "sliding": attention("sliding_attention"),
        # W_qI, W_kI, W_w (the LayerNorm's 2 x 128 are left out)
        "indexer": int(model["q_lora_rank"]) * HI * dI + D * dI + D * HI,
        "dense": 3 * D * int(model["intermediate_size"]),
        "router": D * int(model.get("num_experts_published", model["n_routed_experts"])),
        "expert": expert,
        "shared": expert * int(model["n_shared_experts"]),
        "head": D * int(model["vocab_size"]),
    }


def layer_counts(model: dict) -> dict:
    """How many of the kept layers are full, sliding, dense, of experts."""
    kinds = _kinds(model)
    dense = min(int(model["first_k_dense_replace"]), len(kinds))
    return {
        "full": kinds.count("full_attention"), "sliding": kinds.count("sliding_attention"),
        "dense": dense, "experts": len(kinds) - dense,
    }


def experts_touched(model: dict, rows: float) -> float:
    """Expected distinct experts HELD of one layer that ``rows`` tokens
    touch: a token takes ``k`` distinct router outputs of ``E``, so it
    misses a given expert with probability 1 - k / E."""
    E = int(model.get("num_experts_published", model["n_routed_experts"]))
    miss = 1.0 - int(model["num_experts_per_tok"]) / E
    return int(model["n_routed_experts"]) * (1.0 - miss**rows)


def row_bytes(model: dict, engine: dict) -> dict:
    """What the algorithm reads of one position: a full layer's latent and
    rope key, its index key, a sliding layer's latent and rope key (the
    program stores the latent rows filled up to whole lanes of 128)."""
    item = 4 if engine["kv_dtype"] == "float32" else BF16
    full, sliding = _sizes(model, "full_attention"), _sizes(model, "sliding_attention")
    return {
        "full": float(full["r"] + full["rope"]) * item,
        "index": float(int(model["index_head_dim"])) * item,
        "sliding": float(sliding["r"] + sliding["rope"]) * item,
    }


def decode_step_bytes(model: dict, engine: dict, live_kv_tokens: float) -> float:
    """One decode step over the batch: the attention projections, the
    indexers, the dense MLP, routers, shared experts and the head once; the
    experts held that the decoding rows touch (``engine.roofline_decode_rows``
    rows: the signature carries only the tokens); and of the slots' state,
    a full layer, the index key of every live token and the latent row of
    the ``index_topk`` it selects (every row of a slot that holds fewer: the
    live tokens are spread evenly over the decoding rows), a sliding layer,
    the ring of each decoding row."""
    p, n = part_params(model), layer_counts(model)
    rows = float(engine.get("roofline_decode_rows", engine["max_batch"]))
    once = (n["full"] * (p["full"] + p["indexer"]) + n["sliding"] * p["sliding"]
            + n["dense"] * p["dense"] + n["experts"] * (p["router"] + p["shared"]) + p["head"])
    touched = n["experts"] * experts_touched(model, rows) * p["expert"]
    by = row_bytes(model, engine)
    held = live_kv_tokens / rows if rows else 0.0
    selected = rows * min(held, float(model["index_topk"]))
    state = n["full"] * (live_kv_tokens * by["index"] + selected * by["full"])
    state += n["sliding"] * rows * min(held, float(model["sliding_window_size"])) * by["sliding"]
    return BF16 * (once + touched) + state


def prefill_flops(model: dict, new_tokens: float, attn_pairs: float) -> float:
    """Operations to prefill ``new_tokens``: 2 a parameter and token for
    the attention projections of either kind (``W_kvb``'s expansion of the
    token's own latent once), the indexer's projections, the dense MLP,
    the router, the shared expert and the token's choices that land on the
    experts held (``k x held / E`` of them on average: one); the indexer's
    score for every (query, visible key) pair, 2 x 64 x 128; and attention
    (QK^T over nope + rope and PV over ``v_head_dim`` a head, on expanded
    keys) for the pairs a layer ATTENDS: a full layer ``min(t + 1, topk)``
    of a query's ``t + 1``, a sliding layer ``min(t + 1, window)``.  The
    signature carries the causal pairs alone, so a query's position is
    taken from them as if the tokens were one prompt from 0 (pairs =
    n (n + 1) / 2): what a mix of prompts attends differs by the shape of
    its lengths, and the count says so here, not a reading."""
    p, n = part_params(model), layer_counts(model)
    E = int(model.get("num_experts_published", model["n_routed_experts"]))
    local = int(model["num_experts_per_tok"]) * int(model["n_routed_experts"]) / E
    active = (n["full"] * (p["full"] + p["indexer"]) + n["sliding"] * p["sliding"]
              + n["dense"] * p["dense"]
              + n["experts"] * (p["router"] + p["shared"] + local * p["expert"]))

    def attended(limit: float) -> float:
        """Of ``attn_pairs`` causal pairs over ``new_tokens`` queries, those
        with the key among the query's ``limit`` newest... or highest:
        sum_t min(t + 1, limit) for one prompt of the mean length the pairs
        imply, scaled to the tokens."""
        if new_tokens <= 0 or attn_pairs <= 0:
            return 0.0
        mean_seen = attn_pairs / new_tokens  # a query's mean visible keys
        length = max(2.0 * mean_seen - 1.0, 1.0)  # of one prompt from 0 with that mean
        if length <= limit:
            return attn_pairs
        kept = limit * (limit + 1.0) / 2.0 + (length - limit) * limit
        return attn_pairs * kept / (length * (length + 1.0) / 2.0)

    full, sliding = _sizes(model, "full_attention"), _sizes(model, "sliding_attention")
    pair = lambda z: 2.0 * z["H"] * (z["nope"] + z["rope"] + z["vd"])
    index_pair = 2.0 * int(model["index_n_heads"]) * int(model["index_head_dim"])
    return (
        2.0 * active * new_tokens
        + n["full"] * (index_pair * attn_pairs + pair(full) * attended(float(model["index_topk"])))
        + n["sliding"] * pair(sliding) * attended(float(model["sliding_window_size"]))
    )
