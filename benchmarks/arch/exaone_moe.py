"""The ``exaone_moe`` family (K-EXAONE-236B-A23B): grouped-query attention
in two kinds with QK-norm, ``sliding_attention`` layers (a window of 128,
rotated) beside a ``full_attention`` layer every fourth (not rotated), a
leading dense layer, then sigmoid-routed experts with a shared one, of
which the configuration holds a share (``num_experts`` of
``num_experts_published``), and one multi-token-prediction module that the
engine serves as the draft of every decode step (``engine.draft``
``mtp``).

``llama_config`` calls the program's own mapping from the public keys
(``models.hybrid.from_hf_config``) and returns its ``GqaConfig``, which
``Scheduler`` takes as it takes a ``LlamaConfig``.  The reference is
``exaone_moe_reference.py`` beside ``run.py``; ``last_logits`` below holds
the program's logits, from its chunked prefill and from its verify step
on true and on wrong drafts, and the module's, to it before it hands the
reference's to the harness.  The counts further down are what the
algorithm needs, from shapes alone; ``tests/test_arch_exaone_moe.py``
holds them to the table of the configuration's cut worked by hand.
"""

from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

import exaone_moe_reference

BF16 = 2
MIXERS = {"sliding_attention": "window", "full_attention": "full"}
MLPS = {"dense": "dense", "sparse": "experts"}


def llama_config(model: dict, engine: dict):
    """The public config.json keys -> the program's ``GqaConfig``.  The
    draft is part of the model: ``engine.draft`` ``mtp`` holds the
    prediction module and serves it; absent, the module is left out."""
    from generativeaiexamples_tpu.models import hybrid

    if engine["weight_dtype"] != "bfloat16":
        raise ValueError("this family is served with bf16 weights only")
    if int(engine["experts_held"]) != int(model["num_experts"]):
        raise ValueError("engine.experts_held and num_experts (the experts held) disagree")
    ref = model["reference"]
    # ``last_logits`` is called without the configuration: its limits,
    # the server's chunk and the positions that go through the verify
    # step are kept from here.
    _CHECK.update(limits=dict(ref["logit_share_limits"]), verify=int(ref["verify_positions"]),
                  chunk=int(engine["prefill_chunk_tokens"]))
    cfg = hybrid.from_hf_config(
        model, max_len=int(engine["max_len"]), expert_offset=int(engine["expert_offset"]),
        kv_dtype=str(engine["kv_dtype"]), draft=str(engine.get("draft", "")),
    )
    by_kind = hybrid.state_bytes(cfg, int(engine["max_batch"]), int(engine["max_len"]))
    print(json.dumps({"bench": "state bytes", "max_len": int(engine["max_len"]),
                      **{f"state_bytes_{k}": by_kind.get(k, 0) for k in ("full", "window", "draft")},
                      "snapshot_bytes": cfg.snapshot_bytes(int(engine["max_len"]))}), flush=True)
    return cfg


# -- the comparison that decides ``correct`` -------------------------------------
#
# The harness asks for the reference's logits at a prompt's last position
# and holds the server's greedy token to them.  An expert model is not
# smooth, so that check cannot see a precision, and a served token cannot
# see a window, a norm or a rotation.  ``last_logits`` therefore first
# holds the program's logits to the reference's at every position of the
# prompt.  The prompt but its last ``verify_positions`` tokens goes
# through the serving model's own chunked prefill (``prefill_row``, what
# ``Scheduler._prefill_suffix`` runs).  Those last tokens go through its
# verify step (``verify_stack`` and ``verify_module``, what its decode
# chunk scans) twice from the same state: two at a time with the TRUE
# next token as the draft (the accept branch: both positions' logits are
# compared), and one at a time with a WRONG draft (the reject branch: the
# logits after it show that the state was left as one token had left it).
# The prediction module's logits of both passes are held to the
# reference's module.  Each position's error is taken as a share of its
# reference logits' root mean square; of those shares the lowest tenth,
# the median and the ninth tenth over the prefilled positions, and the
# medians over the accepted, the rejected and the module's positions, are
# held to the configuration's ``reference.logit_share_limits``.  A prompt
# outside a limit is handed to the harness as one the served token cannot
# agree with, so it counts against ``min_within`` like a wrong token.

_CHECK: dict = {}
QUANTILES = {"p10": 0.1, "p50": 0.5, "p90": 0.9}
# Positions of a float32 (positions, vocabulary) block of logits.
BLOCK = 256


@functools.lru_cache(maxsize=4)
def _programs(cfg, window: int):
    """The serving model's chunked prefill of slot 0 of a one-slot state
    and the three parts of its verify step, each returning float32
    logits.  Nothing is donated: both passes start from one state."""
    from generativeaiexamples_tpu.engine.serving_models import serving_model

    model = serving_model(cfg, None, window)
    one = jnp.ones((1,), jnp.int32)

    @jax.jit
    def chunk(params, state, tokens, start, n):
        state, hidden, _ = model.prefill_row(
            params, state, tokens, start, n, jnp.int32(0), window)
        return state, model.logits(params, hidden)[0].astype(jnp.float32)

    @jax.jit
    def first_draft(params, state, token, pos):
        state, logits, _ = model.draft_from_last(params, state, token[None], pos[None], one, window)
        return state, logits[0].astype(jnp.float32)

    @jax.jit
    def verify(params, state, token, draft, pos, following, n_emit):
        """The stack over [token, draft] at ``pos``, then the module over
        the first ``n_emit`` of those positions with the tokens that
        follow them.  Returns (state, stack logits (2, V), module logits
        (V,) at the last position that counted)."""
        state, hidden, logits, _ = model.verify_stack(
            params, state, token[None], draft[None], pos[None], one, window)
        state, module, _ = model.verify_module(
            params, state, hidden, following[None], pos[None], n_emit[None], window)
        return state, logits[0].astype(jnp.float32), module[0].astype(jnp.float32)

    return model, chunk, first_draft, verify


@jax.jit
def _shares(got, want):
    """Each position's |got - want|_rms / |want|_rms."""
    return jnp.sqrt(((got - want) ** 2).mean(-1)) / jnp.sqrt((want**2).mean(-1))


def logit_shares(params, cfg, tokens, pad_to: int, served=None, stale_reject: bool = False):
    """(dict of share arrays: ``prefill``, ``accept``, ``reject``,
    ``module``; (V,) reference logits at the last position): the
    program's logits against the reference's over one prompt.  ``served``
    (absent: ``cfg``) is the configuration the program runs, which a
    control changes; ``stale_reject`` is the control that counts a
    rejected draft's position as written (the next step starts two
    positions on)."""
    n = len(tokens)
    n_verify = min(_CHECK["verify"], n - 1)
    n_verify -= n_verify % 2
    n_prefill = n - n_verify
    model, chunk, first_draft, verify = _programs(served or cfg, pad_to)
    # The reference over the prompt padded to whole blocks: one compiled
    # reference for every prompt of a run (every layer is causal, so no
    # position before the pad sees it), its logits a block at a time.
    whole = -(-max(pad_to, n) // BLOCK) * BLOCK
    padded = list(tokens) + [0] * (whole - n)
    x = exaone_moe_reference.hidden_states(params, cfg, padded)
    xm = exaone_moe_reference.mtp_hidden_states(params, cfg, x, padded)
    xm = jnp.concatenate([xm, xm[-1:]])  # the last position has no next token: never read
    blocks = lambda head, rows: jnp.concatenate(
        [head(params, cfg, rows[i : i + BLOCK]) for i in range(0, whole, BLOCK)])
    ref, ref_m = blocks(exaone_moe_reference.head, x), blocks(exaone_moe_reference.mtp_head, xm)
    want = lambda lo, hi: ref[lo:hi]
    want_m = lambda lo, hi: ref_m[lo:hi]
    state = model.init_state(1, pad_to)
    toks = np.zeros((pad_to + _CHECK["chunk"] + 2,), np.int32)
    toks[:n] = tokens
    tok = lambda i: jnp.int32(toks[i])
    out = {"prefill": [], "accept": [], "reject": [], "module": []}
    for start in range(0, n_prefill, _CHECK["chunk"]):
        piece = toks[start : start + _CHECK["chunk"]]
        count = min(n_prefill - start, len(piece))
        state, got = chunk(params, state, jnp.asarray(piece)[None], jnp.int32(start), jnp.int32(count))
        for lo in range(0, count, BLOCK):
            hi = min(lo + BLOCK, count)
            out["prefill"].append(np.asarray(_shares(got[lo:hi], want(start + lo, start + hi))))
    tail = want(n_prefill, n) if n_verify else want(n - 1, n)
    if n_verify:
        tail_m = want_m(n_prefill - 1, n - 1)  # the module's, from the position before
        ref_at = lambda pos: tail[pos - n_prefill]
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
    return {k: np.concatenate(v) if v else np.zeros((0,)) for k, v in out.items()}, np.asarray(tail[-1])


def share_quantiles(shares: dict) -> dict:
    """Quantiles of the prefilled positions' shares, and the medians over
    the accepted, the rejected and the module's positions."""
    prefill = np.asarray(shares["prefill"], np.float64)
    out = {name: float(np.quantile(prefill, q)) for name, q in QUANTILES.items()}
    for part in ("accept", "reject", "module"):
        if len(shares[part]):
            out[f"{part}_p50"] = float(np.quantile(np.asarray(shares[part], np.float64), 0.5))
    return out


def last_logits(params, cfg, tokens, pad_to: int = 0):
    """The float32 reference's logits at the prompt's last position, if
    the program's logits over the prompt lie within the limits of the
    reference's; else logits no served token agrees with (one entry
    more than the vocabulary, and the maximum there: gap 1)."""
    pad_to = max(pad_to, len(tokens))
    share, want_last = logit_shares(params, cfg, tokens, pad_to)
    shares = share_quantiles(share)
    outside = sorted(k for k, v in shares.items() if not v <= _CHECK["limits"][k])
    print(json.dumps({"bench": "logit check", **shares, "outside": outside}), flush=True)
    if outside:
        return np.append(np.zeros(want_last.shape[0], np.float32), np.float32(1.0))
    return want_last


# -- the counts ------------------------------------------------------------------


def layer_kinds(model: dict) -> list[tuple[str, str]]:
    """(mixer, MLP) of each layer kept, under the program's names: the
    first ``num_hidden_layers`` entries of ``layer_types`` and
    ``mlp_layer_types``."""
    n = int(model["num_hidden_layers"])
    return [(MIXERS[a], MLPS[m]) for a, m in zip(model["layer_types"][:n], model["mlp_layer_types"][:n])]


def modules_held(engine: dict) -> int:
    """Prediction modules behind the stack: one where the engine drafts
    with it, else none is held."""
    return 1 if engine.get("draft") == "mtp" else 0


def part_params(model: dict) -> dict:
    """Parameters of one of each part."""
    D, H, KH, hd = (int(model[k]) for k in
                    ("hidden_size", "num_attention_heads", "num_key_value_heads", "head_dim"))
    expert = 3 * D * int(model["moe_intermediate_size"])
    return {
        "attention": D * (H + 2 * KH) * hd + H * hd * D,  # q, k, v and the output
        "dense_mlp": 3 * D * int(model["intermediate_size"]),
        "router": D * int(model.get("num_experts_published", model["num_experts"])),
        "expert": expert,
        "shared": expert * int(model["num_shared_experts"]),
        "eh_proj": 2 * D * D,
        "head": D * int(model["vocab_size"]),
    }


def experts_touched(model: dict, positions: float) -> float:
    """Expected distinct experts HELD of one layer that ``positions``
    tokens touch: a token takes ``k`` distinct router outputs of ``E``,
    so it misses a given expert with probability 1 - k / E."""
    E = int(model.get("num_experts_published", model["num_experts"]))
    miss = 1.0 - int(model["num_experts_per_tok"]) / E
    return int(model["num_experts"]) * (1.0 - miss**positions)


def kv_bytes_per_row(model: dict, engine: dict) -> float:
    """K and V of one position in one layer."""
    item = 4 if engine["kv_dtype"] == "float32" else BF16
    return 2.0 * int(model["num_key_value_heads"]) * int(model["head_dim"]) * item


def decode_step_bytes(model: dict, engine: dict, live_kv_tokens: float) -> float:
    """One decode step over the batch, which with the draft on is a
    VERIFY step: every weight outside the routed experts once (the
    attention projections, the dense MLP, the routers, the shared
    experts, the module's projection and block, the head once: the stack
    and the module share it); the experts held that the step's positions
    touch (bf16), two positions a decoding row in the stack's layers
    (``engine.roofline_decode_rows`` rows: the signature carries only
    the tokens) and one a row in the module's, which at the floor of the
    acceptance routes the one position it accepted; the K/V rows of every
    live token in a full layer and in the module's, and of the last
    ``sliding_window`` positions of every live row in a window layer
    (``rows x min(mean length, window)``).  With the draft off the
    module is not held and a step has one position a row."""
    p, kinds = part_params(model), layer_kinds(model)
    rows = float(engine.get("roofline_decode_rows", engine["max_batch"]))
    module = modules_held(engine)
    per_row = 2.0 if module else 1.0
    once = p["head"]
    touched = 0.0
    for _, mlp in kinds:
        once += p["attention"]
        if mlp == "dense":
            once += p["dense_mlp"]
        else:
            once += p["router"] + p["shared"]
            touched += experts_touched(model, per_row * rows) * p["expert"]
    if module:
        once += p["eh_proj"] + p["attention"] + p["router"] + p["shared"]
        touched += experts_touched(model, rows) * p["expert"]
    mixers = [m for m, _ in kinds]
    window_rows = rows * min(live_kv_tokens / rows, float(model["sliding_window"]))
    kv_rows = (mixers.count("full") + module) * live_kv_tokens + mixers.count("window") * window_rows
    return BF16 * (once + touched) + kv_rows * kv_bytes_per_row(model, engine)


def prefill_flops(model: dict, new_tokens: float, attn_pairs: float, engine: dict | None = None) -> float:
    """Operations to prefill ``new_tokens``: 2 a parameter and token for
    the attention projections, the dense MLP or the router, the shared
    expert and the token's choices that land on the experts held (``k x
    held / E`` of them on average: one), and the prediction module's
    projection and block the same (``engine`` absent: the configuration's
    module is counted as held, as the cell serves it); and QK^T and PV
    over ``head_dim`` a query head for every (query, visible key) pair:
    ``attn_pairs`` in a full layer and in the module's, and in a window
    layer ``min(i + 1, window)`` a position, taken as ``new_tokens x
    min(attn_pairs / new_tokens, window)`` (exact where every new position
    lies past the window; over by at most ``window^2 / 2`` pairs a cold
    prompt)."""
    p, kinds = part_params(model), layer_kinds(model)
    module = modules_held(engine if engine is not None else model["engine"])
    E = int(model.get("num_experts_published", model["num_experts"]))
    local = int(model["num_experts_per_tok"]) * int(model["num_experts"]) / E
    sparse = p["attention"] + p["router"] + p["shared"] + local * p["expert"]
    active = sum(p["attention"] + p["dense_mlp"] if mlp == "dense" else sparse for _, mlp in kinds)
    active += module * (p["eh_proj"] + sparse)
    mixers = [m for m, _ in kinds]
    pair = 4.0 * int(model["num_attention_heads"]) * int(model["head_dim"])
    window_pairs = 0.0
    if new_tokens:
        window_pairs = new_tokens * min(attn_pairs / new_tokens, float(model["sliding_window"]))
    return (
        2.0 * active * new_tokens
        + pair * ((mixers.count("full") + module) * attn_pairs + mixers.count("window") * window_pairs)
    )
