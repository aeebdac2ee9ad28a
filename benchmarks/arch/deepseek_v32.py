"""The ``deepseek_v32`` family (DeepSeek-V3.2): latent attention in EVERY
layer (128 heads over a latent of ``kv_lora_rank`` 512 and a rope key of
64: 1,152 B a token in bf16), each with the lightning indexer
(``index_n_heads`` 64 heads of ``index_head_dim`` 128 score every earlier
position from one cached index key a token, 256 B more; a query attends
the ``index_topk`` 2,048 highest), YaRN on the rotary parts with its
``mscale`` squared in the softmax scale; ``first_k_dense_replace`` leading
dense layers, then sigmoid-routed experts, 8 a token of
``num_experts_published`` 256 chosen inside the 4 best of 8 groups, with
one shared expert; the configuration holds a share of the experts
(``n_routed_experts`` of them, from ``engine.expert_offset``: half a
group); and one prediction module, a whole block of the same kind with its
own indexer and rows, which the engine serves as the draft of every decode
step (``engine.draft`` ``mtp``): a step verifies two positions a row, each
over the rows it selects for itself.

What a row of ``benchmarks/README.md``'s layout table would say (that file
is not a ``model_config`` PR's to edit): ``arch/deepseek_v32.py`` maps
``configs/deepseek-v3.2-l5e16.json`` to the program's
``PredictingLatentConfig`` and holds its counts and its comparison;
``deepseek_v32_reference.py`` beside ``run.py`` is the plain float32
reference (a copy of
``generativeaiexamples_tpu/models/deepseek_v32_reference.py``);
``traffic/doc-reason.json`` and ``traffic/doc-reason-closed.json`` are the
cell's mix (documents of 2.8k-10.5k tokens under one template, answers of
256-3,072 tokens, 20 waiting clients on 16 slots);
``layer_metrics/verify_gather_pct.py`` and
``draft_rows_rewritten_per_token.py`` read the counters the step form
added.

``llama_config`` calls the program's own mapping from the public keys
(``models.hybrid.from_hf_config``).  ``last_logits`` below holds the
program's logits, from its chunked prefill and from its VERIFY step on
true and on wrong drafts, the prediction module's, and the sets both
indexers select, to the reference's before it hands the reference's logits
to the harness.  The counts further down are what the ALGORITHM needs,
from shapes alone; ``tests/test_arch_deepseek_v32.py`` holds them to the
table of the configuration's cut worked by hand.
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

import deepseek_v32_reference

BF16 = 2


def llama_config(model: dict, engine: dict):
    """The public config.json keys -> the program's
    ``PredictingLatentConfig``.  The draft is part of the model:
    ``engine.draft`` ``mtp`` holds the prediction module and serves it."""
    from generativeaiexamples_tpu.models import hybrid

    if not hasattr(hybrid, "PredictingLatentConfig"):
        # The commit before the one that added the family: fail at once.
        raise SystemExit("benchmarks/arch/deepseek_v32.py: this program has no deepseek_v32 "
                         "family (models/hybrid.py lacks PredictingLatentConfig)")
    if engine["weight_dtype"] != "bfloat16":
        raise ValueError("this family is served with bf16 weights only")
    if int(engine["experts_held"]) != int(model["n_routed_experts"]):
        raise ValueError("engine.experts_held and n_routed_experts (the experts held) disagree")
    ref = model["reference"]
    # ``last_logits`` is called without the configuration: its limits,
    # the server's chunk and the positions that go through the verify
    # step are kept from here.
    _CHECK.update(limits=dict(ref["logit_share_limits"]), verify=int(ref["verify_positions"]),
                  chunk=int(engine["prefill_chunk_tokens"]),
                  floors=dict(ref["index_overlap_floors"]))
    cfg = hybrid.from_hf_config(
        model, max_len=int(engine["max_len"]), expert_offset=int(engine["expert_offset"]),
        kv_dtype=str(engine["kv_dtype"]), draft=str(engine.get("draft", "")),
    )
    by_kind = hybrid.state_bytes(cfg, int(engine["max_batch"]), int(engine["max_len"]))
    shapes = jax.eval_shape(lambda: hybrid.init_params(cfg, jax.random.PRNGKey(0)))
    print(json.dumps({
        "bench": "state bytes", "max_len": int(engine["max_len"]),
        "weight_bytes": sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(shapes)),
        **{f"state_bytes_{k}": by_kind.get(k, 0) for k in ("full", "window", "draft")},
        "latent_row_bytes_used": int(row_bytes(model, engine)["latent"]),
        "latent_row_bytes_stored": cfg.latent_width * cfg.state_dtype.itemsize,
        "index_key_bytes": int(row_bytes(model, engine)["index"]),
        "snapshot_bytes": cfg.snapshot_bytes(int(engine["max_len"])),
    }), flush=True)
    return cfg


# -- the comparison that decides ``correct`` -------------------------------------
#
# ``arch/dots3_note.py``'s manner (the calls the scheduler's programs make
# in the measured window, at their shapes: a state of ``max_len`` rows a
# slot, the prompt but its last ``verify_positions`` tokens a chunk at a
# time through ``prefill_rows`` beside a pad row, in place) joined to
# ``arch/exaone_moe.py``'s (those last tokens through the VERIFY step, what
# the decode chunk scans, twice from the same state: two at a time with the
# TRUE next token as the draft, the accept branch, both positions' logits
# compared; one at a time with a WRONG draft, the reject branch: the logits
# after it show that the state was left as one token had left it; the
# prediction module's logits of both passes against the reference's
# module).  The steps run over both slots of the check's state at the
# widest decode window, the other slot not decoding.
#
# And the rows those very calls KEEP: while the check's programs are
# traced, the two selections the ``mla`` mixer calls
# (``ops/mla.py::select_mask`` in a chunk, ``select_rows`` in a step, one
# row a position) also hand what they return to the host (``_tapped``),
# where it is tallied against the reference's sets of that block at those
# positions: ``index_overlap_stack`` for the stack's layers,
# ``index_overlap_module`` for the module's block, each the share of the (query, row) pairs the
# program kept that the reference kept too, over the queries past
# ``index_topk``.  Of a verify step BOTH positions are read in the accept
# pass (position ``p + 1`` against the reference's set of ``p + 1``: a
# program that gave it position ``p``'s set reads low here); in the reject
# pass the second position holds a wrong token and is not read.

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
        """``kept``: a block each (the stack's layers, then the module's),
        the reference's (positions, positions) sets; a chunk's queries are
        judged from ``topk`` to ``n_prefill``, a step's from there to
        ``n``."""
        self.kept = kept
        # The module runs one position behind: a chunk leaves its row at
        # ``n_prefill - 1`` to the catch-up step.
        self.judged = {
            ("chunk", False): (topk, n_prefill), ("step", False): (max(topk, n_prefill), n),
            ("chunk", True): (topk, n_prefill - 1), ("step", True): (max(topk, n_prefill - 1), n),
        }
        self.first_only = False
        self.tally = {"stack": [0, 0], "module": [0, 0]}  # kept by the program, by both
        self.queries = 0

    def __call__(self, block: int, phase: str, queries: int, pos, mask) -> None:
        pos, mask = np.asarray(pos), np.asarray(mask)
        module = block == len(self.kept) - 1
        lo, hi = self.judged[phase, module]
        want_all = self.kept[block]
        ok = (pos >= lo) & (pos < hi) & (pos < want_all.shape[0])
        if self.first_only:  # a step's second position holds a wrong draft
            ok &= np.arange(pos.size) % queries == 0
        rows = np.flatnonzero(ok)
        if not rows.size:
            return
        want = want_all[pos[rows]]
        mine = mask[rows]
        cut = min(want.shape[1], mine.shape[1])
        with self.lock:
            tally = self.tally["module" if module else "stack"]
            tally[0] += int(mine.sum())
            tally[1] += int((mine[:, :cut] & want[:, :cut]).sum())
            self.queries += int(rows.size)

    def overlaps(self) -> dict:
        # A prompt no longer than ``index_topk`` has no query that selects.
        return {
            name: (both / mine if mine else 1.0) for name, (mine, both) in self.tally.items()
        }


_SELECTED = _Selected()


@contextlib.contextmanager
def _tapped(phase: str, blocks: list, queries: int = 1):
    """A program TRACED under this hands what its selections return to
    ``_SELECTED`` whenever it runs: the ``mla`` blocks are traced in their
    order, so the n-th selection traced is block ``blocks[n]`` (the
    stack's layers by their index, the module's block last).  A step's
    scores are one row a slot and position, slot-major (``queries``
    positions a slot); its query is at the count of the scores it sees,
    less one (one that does not count sees none: position -1, never
    judged).  A chunk's queries (one row of the batch at consecutive
    positions) follow its first, which for the module's block, one
    position behind, may be -1."""
    from generativeaiexamples_tpu.ops import mla

    plain_mask, plain_rows = mla.select_mask, mla.select_rows
    order = itertools.count()

    def hand(scores, mask):
        s, T = scores.shape[-2:]
        pos = jnp.sum(scores > -jnp.inf, axis=-1) - 1
        if phase == "chunk":
            pos = pos[..., :1] + jnp.arange(s)
        jax.debug.callback(
            functools.partial(_SELECTED, blocks[next(order)], phase, queries),
            pos.reshape(-1), mask.reshape(-1, T))

    def select_mask(scores, k):
        mask = plain_mask(scores, k)
        hand(scores, mask)
        return mask

    def select_rows(scores, k):
        idx, keep = plain_rows(scores, k)
        n, T = scores.shape
        mask = jnp.zeros((n, T), bool).at[jnp.arange(n)[:, None], idx].set(keep)
        hand(scores, mask)
        return idx, keep

    mla.select_mask, mla.select_rows = select_mask, select_rows
    try:
        yield
    finally:
        mla.select_mask, mla.select_rows = plain_mask, plain_rows
    if next(order) != len(blocks):
        raise RuntimeError("the selections traced are not one a block: index_overlap cannot be read")


@functools.lru_cache(maxsize=4)
def _programs(cfg, chunk_tokens: int):
    """The serving model, and the calls the scheduler's programs make of
    it, each returning float32 logits: a chunk of the last slot beside a
    pad row through ``prefill_rows`` at the chunk programs' widest window;
    the module's catch-up (``draft_from_last``) and the verify step
    (``verify_stack`` then ``verify_module``) over every slot at the widest
    decode window; all tapped (``_tapped``).  The steps donate nothing:
    both passes start from one state."""
    from generativeaiexamples_tpu.engine.serving_models import serving_model

    max_len = cfg.max_seq_len
    model = serving_model(cfg, None, max_len)
    window = model.chunk_windows(chunk_tokens)[-1]
    slots = jnp.arange(CHECK_SLOTS, dtype=jnp.int32)
    mine = slots == CHECK_SLOTS - 1
    on = mine.astype(jnp.int32)
    stack, module = list(range(cfg.n_layers)), [cfg.n_layers]

    @functools.partial(jax.jit, donate_argnums=(1,))
    def chunk(params, state, tokens, start, n):
        rows = jnp.where(mine[:, None], tokens[None], 0)
        with _tapped("chunk", stack + module):
            state, hidden, _ = model.prefill_rows(
                params, state, rows, jnp.where(mine, start, 0), jnp.where(mine, n, 0), slots, window)
        return state, model.logits(params, hidden[-1:])[0].astype(jnp.float32)

    @jax.jit
    def first_draft(params, state, token, pos):
        with _tapped("step", module):
            state, logits, _ = model.draft_from_last(
                params, state, jnp.where(mine, token, 0), jnp.where(mine, pos, 0), on, max_len)
        return state, logits[-1].astype(jnp.float32)

    @jax.jit
    def verify(params, state, token, draft, pos, following, n_emit):
        """The stack over [token, draft] at ``pos``, then the module over
        the first ``n_emit`` of those positions with the tokens that
        follow them.  Returns (state, stack logits (2, V), module logits
        (V,) at the last position that counted)."""
        at = jnp.where(mine, pos, 0)
        with _tapped("step", stack, queries=2):
            state, hidden, logits, _ = model.verify_stack(
                params, state, jnp.where(mine, token, 0), jnp.where(mine, draft, 0), at, on, max_len)
        with _tapped("step", module, queries=2):
            state, module_logits, _ = model.verify_module(
                params, state, hidden, jnp.where(mine[:, None], following[None], 0), at,
                jnp.where(mine, n_emit, 0), max_len)
        return state, logits[-1].astype(jnp.float32), module_logits[-1].astype(jnp.float32)

    return model, chunk, first_draft, verify


@jax.jit
def _shares(got, want):
    """Each position's |got - want|_rms / |want|_rms."""
    return jnp.sqrt(((got - want) ** 2).mean(-1)) / jnp.sqrt((want**2).mean(-1))


def logit_shares(params, cfg, tokens, pad_to: int, served=None, stale_reject: bool = False):
    """(dict of share arrays: ``prefill``, ``accept``, ``reject``,
    ``module``; (V,) reference logits at the last position; the index
    overlaps ``{"stack": ., "module": .}``): the program's logits and kept
    sets against the reference's over one prompt.  ``served`` (absent:
    ``cfg``) is the configuration the program runs, which a control
    changes; ``stale_reject`` is the control that counts a rejected
    draft's position as written (the next step starts two positions on)."""
    n = len(tokens)
    n_verify = min(_CHECK["verify"], n - 1)
    n_verify -= n_verify % 2
    n_prefill = n - n_verify
    served = served or cfg
    model, chunk, first_draft, verify = _programs(served, _CHECK["chunk"])
    # The reference over the prompt padded to one length: one compiled
    # reference for every prompt of a run (every layer is causal, so no
    # position before the pad sees it).
    padded = list(tokens) + [0] * (pad_to - n)
    kept, x = [], None
    for _, x, mask in deepseek_v32_reference.layers(params, cfg, padded):
        kept.append(np.asarray(mask))
    xm, mask = deepseek_v32_reference.mtp_hidden_states(params, cfg, x, padded)
    kept.append(np.asarray(mask))
    xm = jnp.concatenate([xm, xm[-1:]])  # the last position has no next token: never read
    _SELECTED.expect(kept, served.index_topk, n_prefill, n)
    want = lambda lo, hi: deepseek_v32_reference.head(params, cfg, x[lo:hi])
    want_m = lambda lo, hi: deepseek_v32_reference.mtp_head(params, cfg, xm[lo:hi])
    state = model.init_state(CHECK_SLOTS, cfg.max_seq_len)
    toks = np.zeros((pad_to + _CHECK["chunk"] + 2,), np.int32)
    toks[:n] = tokens
    tok = lambda i: jnp.int32(toks[i])
    out = {"prefill": [], "accept": [], "reject": [], "module": []}
    for start in range(0, n_prefill, _CHECK["chunk"]):
        piece = toks[start : start + _CHECK["chunk"]]
        count = min(n_prefill - start, len(piece))
        state, got = chunk(params, state, jnp.asarray(piece), jnp.int32(start), jnp.int32(count))
        for lo in range(0, count, BLOCK):
            hi = min(lo + BLOCK, count)
            out["prefill"].append(np.asarray(_shares(got[lo:hi], want(start + lo, start + hi))))
    tail = want(n_prefill, n) if n_verify else want(n - 1, n)
    if n_verify:
        tail_m = want_m(n_prefill - 1, n - 1)  # the module's, from the position before
        mod_at = lambda pos: tail_m[pos - (n_prefill - 1)]
        start_state, got = first_draft(params, state, tok(n_prefill), jnp.int32(n_prefill))
        modules, want_modules = [got], [mod_at(n_prefill - 1)]
        # The accept branch: [x_p, x_{p+1}] with the true x_{p+1} as the draft.
        state, accepted = start_state, []
        for pos in range(n_prefill, n, 2):
            n_emit = 2 if pos + 2 < n else 1  # the last position has no next token
            state, got, mod = verify(
                params, state, tok(pos), tok(pos + 1), jnp.int32(pos),
                jnp.asarray([toks[pos + 1], toks[pos + 2]]), jnp.int32(n_emit))
            accepted.append(got)
            modules.append(mod)
            want_modules.append(mod_at(pos + n_emit - 1))
        out["accept"].append(np.asarray(_shares(jnp.concatenate(accepted), tail)))
        # The reject branch: [x_p, a wrong draft]; one token a step.
        jax.effects_barrier()
        _SELECTED.first_only = True
        state, rejected, at = start_state, [], n_prefill
        for pos in range(n_prefill, n - 1):
            wrong = jnp.int32((int(toks[pos + 1]) + 1) % cfg.vocab_size)
            state, got, mod = verify(
                params, state, tok(pos), wrong, jnp.int32(at),
                jnp.asarray([toks[pos + 1], 0]), jnp.int32(1))
            at += 2 if stale_reject else 1
            rejected.append(got[:1])
            modules.append(mod)
            want_modules.append(mod_at(pos))
        out["reject"].append(np.asarray(_shares(jnp.concatenate(rejected), tail[:-1])))
        out["module"].append(np.asarray(_shares(jnp.stack(modules), jnp.stack(want_modules))))
    jax.effects_barrier()
    if n > served.index_topk and not _SELECTED.queries:
        raise RuntimeError("no selection was read: index_overlap cannot be read")
    shares = {k: np.concatenate(v) if v else np.zeros((0,)) for k, v in out.items()}
    return shares, np.asarray(tail[-1]), _SELECTED.overlaps()


def share_quantiles(shares: dict) -> dict:
    """Quantiles of the prefilled positions' shares, and the medians over
    the accepted, the rejected and the module's positions."""
    prefill = np.asarray(shares["prefill"], np.float64)
    out = {name: float(np.quantile(prefill, q)) for name, q in QUANTILES.items()}
    for part in ("accept", "reject", "module"):
        if len(shares[part]):
            out[f"{part}_p50"] = float(np.quantile(np.asarray(shares[part], np.float64), 0.5))
    return out


def outside_limits(shares: dict, overlaps: dict) -> list:
    """The readings that leave their limit: a logit share over its own, an
    index overlap under its floor."""
    outside = sorted(k for k, v in shares.items() if not v <= _CHECK["limits"][k])
    outside += [f"index_overlap_{k}" for k, v in sorted(overlaps.items())
                if not v >= _CHECK["floors"][k]]
    return outside


def last_logits(params, cfg, tokens, pad_to: int = 0):
    """The float32 reference's logits at the prompt's last position, if
    the program's logits over the prompt lie within the limits of the
    reference's and its indexers keep the reference's rows; else logits no
    served token agrees with (one entry more than the vocabulary, and the
    maximum there: gap 1)."""
    pad_to = max(pad_to, len(tokens))
    share, want_last, overlaps = logit_shares(params, cfg, tokens, pad_to)
    shares = share_quantiles(share)
    outside = outside_limits(shares, overlaps)
    print(json.dumps({"bench": "logit check", **shares,
                      **{f"index_overlap_{k}": v for k, v in overlaps.items()},
                      "outside": outside}), flush=True)
    if outside:
        return np.append(np.zeros(want_last.shape[0], np.float32), np.float32(1.0))
    return want_last


# -- the counts ------------------------------------------------------------------


def modules_held(engine: dict) -> int:
    """Prediction modules behind the stack: one where the engine drafts
    with it, else none is held."""
    return 1 if engine.get("draft") == "mtp" else 0


def part_params(model: dict) -> dict:
    """Parameters of one of each part."""
    D, H = int(model["hidden_size"]), int(model["num_attention_heads"])
    r_q, r = int(model["q_lora_rank"]), int(model["kv_lora_rank"])
    nope, rope, vd = (int(model[k]) for k in ("qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim"))
    HI, dI = int(model["index_n_heads"]), int(model["index_head_dim"])
    expert = 3 * D * int(model["moe_intermediate_size"])
    return {
        # W_qa, W_qb, W_kva, W_kvb, W_o
        "attention": D * r_q + r_q * H * (nope + rope) + D * (r + rope) + r * H * (nope + vd) + H * vd * D,
        # W_qI, W_kI, W_w (the LayerNorm's 2 x 128 are left out)
        "indexer": r_q * HI * dI + D * dI + D * HI,
        "dense": 3 * D * int(model["intermediate_size"]),
        "router": D * int(model.get("num_experts_published", model["n_routed_experts"])),
        "expert": expert,
        "shared": expert * int(model["n_shared_experts"]),
        "eh_proj": 2 * D * D,
        "head": D * int(model["vocab_size"]),
    }


def layer_counts(model: dict) -> dict:
    """How many of the kept layers are dense, of experts."""
    n = int(model["num_hidden_layers"])
    dense = min(int(model["first_k_dense_replace"]), n)
    return {"layers": n, "dense": dense, "experts": n - dense}


def experts_touched(model: dict, positions: float) -> float:
    """Expected distinct experts HELD of one layer that ``positions``
    tokens touch: balanced biases give every router output the same share
    of the choices, groups or none, so a token misses a given expert with
    probability 1 - k / E."""
    E = int(model.get("num_experts_published", model["n_routed_experts"]))
    miss = 1.0 - int(model["num_experts_per_tok"]) / E
    return int(model["n_routed_experts"]) * (1.0 - miss**positions)


def row_bytes(model: dict, engine: dict) -> dict:
    """What the algorithm reads of one position in one block: the latent
    and rope key (the program stores them filled up to whole lanes of
    128), and the index key."""
    item = 4 if engine["kv_dtype"] == "float32" else BF16
    return {
        "latent": float(int(model["kv_lora_rank"]) + int(model["qk_rope_head_dim"])) * item,
        "index": float(int(model["index_head_dim"])) * item,
    }


def decode_step_bytes(model: dict, engine: dict, live_kv_tokens: float) -> float:
    """One decode step over the batch, which with the draft on is a VERIFY
    step: every weight outside the routed experts once (the mixers with
    their indexers, the dense MLP, the routers, the shared experts, the
    module's projection and block, the head once: the stack and the module
    share it); the experts held that the step's positions touch, two
    positions a decoding row in the stack's layers
    (``engine.roofline_decode_rows`` rows: the signature carries only the
    tokens) and one a row in the module's, which at the floor of the
    acceptance routes the one position it accepted; and of the slots' state,
    in every block (the stack's layers and the module's), the index key of
    every live token and the latent rows of ONE position's selection plus
    one row a decoding slot: ``min(length, index_topk) + 1``, the least two
    adjacent positions can need between them (position ``p + 1`` keeps its
    own row; the rest of its set may be position ``p``'s), so that a
    program that gathers a union reads no more than is counted here.  With
    the draft off the module is not held and a step has one position a
    row."""
    p, n = part_params(model), layer_counts(model)
    rows = float(engine.get("roofline_decode_rows", engine["max_batch"]))
    module = modules_held(engine)
    mixer = p["attention"] + p["indexer"]
    once = (n["layers"] * mixer + n["dense"] * p["dense"]
            + n["experts"] * (p["router"] + p["shared"]) + p["head"]
            + module * (p["eh_proj"] + mixer + p["router"] + p["shared"]))
    touched = n["experts"] * experts_touched(model, (2.0 if module else 1.0) * rows)
    touched += module * experts_touched(model, rows)
    by = row_bytes(model, engine)
    held = live_kv_tokens / rows if rows else 0.0
    selected = rows * (min(held, float(model["index_topk"])) + module)
    state = (n["layers"] + module) * (live_kv_tokens * by["index"] + selected * by["latent"])
    return BF16 * (once + touched * p["expert"]) + state


def prefill_flops(model: dict, new_tokens: float, attn_pairs: float, engine: dict | None = None) -> float:
    """Operations to prefill ``new_tokens``: 2 a parameter and token for
    the mixers' and indexers' projections (``W_kvb``'s expansion of the
    token's own latent once), the dense MLP or the router, the shared
    expert and the token's choices that land on the experts held (``k x
    held / E`` of them on average: a half), and the prediction module's
    projection and block the same (``engine`` absent: the configuration's
    module is counted as held, as the cell serves it); the indexer's score
    for every (query, visible key) pair, 2 x 64 x 128, and attention (QK^T
    over nope + rope and PV over ``v_head_dim`` a head, on expanded keys)
    for the ``min(t + 1, index_topk)`` pairs a query ATTENDS, in every
    block (the stack's layers and the module's).  The signature carries the
    causal pairs alone, so a query's position is taken from them as if the
    tokens were one prompt from 0 (pairs = n (n + 1) / 2)."""
    p, n = part_params(model), layer_counts(model)
    module = modules_held(engine if engine is not None else model["engine"])
    E = int(model.get("num_experts_published", model["n_routed_experts"]))
    local = int(model["num_experts_per_tok"]) * int(model["n_routed_experts"]) / E
    mixer = p["attention"] + p["indexer"]
    sparse = mixer + p["router"] + p["shared"] + local * p["expert"]
    active = n["dense"] * (mixer + p["dense"]) + n["experts"] * sparse
    active += module * (p["eh_proj"] + sparse)
    limit = float(model["index_topk"])
    attended = 0.0
    if new_tokens > 0 and attn_pairs > 0:
        mean_seen = attn_pairs / new_tokens  # a query's mean visible keys
        length = max(2.0 * mean_seen - 1.0, 1.0)  # of one prompt from 0 with that mean
        attended = attn_pairs
        if length > limit:
            kept = limit * (limit + 1.0) / 2.0 + (length - limit) * limit
            attended = attn_pairs * kept / (length * (length + 1.0) / 2.0)
    H = int(model["num_attention_heads"])
    pair = 2.0 * H * (int(model["qk_nope_head_dim"]) + int(model["qk_rope_head_dim"])
                      + int(model["v_head_dim"]))
    index_pair = 2.0 * int(model["index_n_heads"]) * int(model["index_head_dim"])
    return 2.0 * active * new_tokens + (n["layers"] + module) * (
        index_pair * attn_pairs + pair * attended)
