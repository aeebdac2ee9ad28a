"""The ``nemotron_h`` family (NVIDIA-Nemotron-3-Super-120B-A12B): a stack
whose published layers are ONE function each, named by a letter of
``hybrid_override_pattern``: ``M`` a Mamba-2 mixer (128 heads of 64 in 8
groups, a float32 state of 64 x 128 a head, a depthwise convolution of 4
taps, a gated norm a group), ``*`` grouped-query attention (32 query heads
on 2 key-value heads of 128, not rotated), ``E`` a mixture of 22 experts
of 512 that work in a 1,024-wide latent (``W2 relu(W1 u)^2``, no gate; a
sigmoid router with a selection bias on the full hidden state, weights
renormalised and scaled by 5) beside a shared expert on the hidden state
itself.  A slot keeps, an ``M`` layer, ``S`` (4,194,304 B) and the
convolution's last three inputs (61,440 B), as of its last token; a ``*``
layer, K/V rows a position (1,024 B a token in bf16).

What a row of ``benchmarks/README.md``'s layout table would say (that file
is not a ``model_config`` PR's to edit): ``arch/nemotron_h.py`` maps
``configs/nemotron-3-super-120b-a12b-l11e128.json`` to the program's
``MambaConfig`` and holds its counts; ``nemotron_h_reference.py`` beside
``run.py`` is the plain float32 reference (a copy of
``generativeaiexamples_tpu/models/nemotron_h_reference.py``);
``traffic/reason.json`` and ``traffic/reason-closed.json`` are the cell's
mix (K-EXAONE's and ZAYA's, byte for byte); ``layer_metrics/
decode_rows_per_expert.py`` reads the decode steps' expert counters,
``layer_metrics/prefill_ssm_block_fill_pct.py`` the block scan's.

``llama_config`` calls the program's own mapping from the public keys
(``models.hybrid.from_hf_config``) and returns its ``MambaConfig``, which
``Scheduler`` takes as it takes a ``LlamaConfig``.  ``last_logits`` below
holds the program's logits, from its chunk program and its decode step, to
the reference's before it hands the reference's to the harness.  The
counts further down are what the algorithm needs, from shapes alone;
``tests/test_arch_nemotron_h.py`` holds them to the table of the
configuration's cut worked by hand.
"""

from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

import nemotron_h_reference

BF16 = 2
F32 = 4


def llama_config(model: dict, engine: dict):
    """The public config.json keys -> the program's ``MambaConfig``."""
    from generativeaiexamples_tpu.models import hybrid

    if not hasattr(hybrid, "MambaConfig"):
        # The commit before the one that added the family: fail at once.
        raise SystemExit("benchmarks/arch/nemotron_h.py: this program has no nemotron_h "
                         "family (models/hybrid.py lacks MambaConfig)")
    if engine["weight_dtype"] != "bfloat16":
        raise ValueError("this family is served with bf16 weights only")
    if int(engine["experts_held"]) != int(model["n_routed_experts"]):
        raise ValueError("engine.experts_held and n_routed_experts (the experts held) disagree")
    ref = model["reference"]
    # ``last_logits`` is called without the configuration: its limits,
    # the server's chunk and the positions that go through the decode
    # step are kept from here.
    _CHECK.update(limits=dict(ref["logit_share_limits"]), decode=int(ref["decode_positions"]),
                  chunk=int(engine["prefill_chunk_tokens"]))
    cfg = hybrid.from_hf_config(
        model, max_len=int(engine["max_len"]), expert_offset=int(engine["expert_offset"]),
        kv_dtype=str(engine["kv_dtype"]),
    )
    by_kind = hybrid.state_bytes(cfg, int(engine["max_batch"]), int(engine["max_len"]))
    shapes = jax.eval_shape(lambda: hybrid.init_params(cfg, jax.random.PRNGKey(0)))
    pairs = dict(zip(cfg.layer_kinds, shapes["layers"]))  # one pair of each kind
    count = lambda lp, prefixes: sum(lp[n].size for n in lp if n.startswith(prefixes))
    experts = next(lp for (_, mlp), lp in pairs.items() if mlp == "experts")
    print(json.dumps({
        "bench": "state bytes", "max_len": int(engine["max_len"]),
        "weight_bytes": sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(shapes)),
        "state_bytes_full": by_kind["full"], "state_bytes_window": by_kind["window"],
        # ``S`` and the tail of every slot: what a snapshot holds of one.
        "state_bytes_recurrent": by_kind["recurrent"],
        "snapshot_bytes": cfg.snapshot_bytes(int(engine["max_len"])),
        "letters": nemotron_h_reference.letters(cfg),
        # Parameters of one layer of each letter, reckoned from
        # ``init_params``' shapes, and of the embedding and the head.
        "params_mamba": count(
            next(lp for (mixer, _), lp in pairs.items() if mixer == "mamba"),
            ("attn_norm", "w_in", "w_out", "conv", "ssm")),
        "params_attention": count(
            next(lp for (mixer, _), lp in pairs.items() if mixer == "full"),
            ("attn_norm", "w_qkv", "w_o")),
        "params_experts_outside": count(experts, ("mlp_norm", "router", "w_lat", "w_up_s", "w_down_s")),
        "params_experts_held": count(experts, ("w_up_e", "w_down_e")),
        "params_embedding": shapes["embed"].size, "params_head": shapes["lm_head"].size,
    }), flush=True)
    return cfg


# -- the comparison that decides ``correct`` -------------------------------------
#
# The harness asks for the reference's logits at a prompt's last position
# and holds the server's greedy token to them.  An expert model is not
# smooth, so that check cannot see a precision, and a served token cannot
# see a convolution's tail, a state carried in float32 or a norm's groups.
# ``last_logits`` therefore first holds the program's logits to the
# reference's at every position of the prompt, through the calls the
# scheduler's programs make in the measured window and at their shapes: a
# state of ``max_len`` rows a slot (``CHECK_SLOTS`` of them, the prompt in
# the last), the prompt but its last ``decode_positions`` tokens a chunk at
# a time through ``prefill_rows`` at the chunk programs' widest window (the
# chunk beside a pad row: a group program of two; every chunk after the
# first takes its convolution's history from the slot's tail and its scan's
# from the slot's ``S``, two blocks of 128 a chunk), those last tokens one a
# step through ``decode_step`` over every slot of that state (what
# ``decode_chunk`` scans: the state-space step, the row walk over the rows
# the slot holds where the chip admits it, the other slot not decoding and
# its ``S`` and tail not moving).  Each position's error is taken as a share of
# its reference logits' root mean square, and of those shares the lowest
# tenth, the median and the ninth tenth over the prefilled positions, and
# the median over the decoded positions, are held to the configuration's
# ``reference.logit_share_limits``.  A prompt outside a limit is handed to
# the harness as one the served token cannot agree with, so it counts
# against ``min_within`` like a wrong token.

_CHECK: dict = {}
QUANTILES = {"p10": 0.1, "p50": 0.5, "p90": 0.9}
# Positions of a float32 (positions, vocabulary) block of logits: 256 rows
# of 32,768 are 34 MB on each side.
BLOCK = 256
# The check's state: the slot the prompt lives in and one before it that
# holds nothing, so that a row of a call is not the slot of its number.
CHECK_SLOTS = 2


@functools.lru_cache(maxsize=4)
def _programs(cfg, chunk_tokens: int):
    """The serving model, and the two calls the scheduler's programs make
    of it: a chunk of the last slot beside a pad row through
    ``prefill_rows`` at the chunk programs' widest window (returns the
    chunk's hidden states), ``decode_step`` over every slot at the widest
    decode window (returns the float32 logits), and the head over a block
    of hidden states."""
    from generativeaiexamples_tpu.engine.serving_models import serving_model

    max_len = cfg.max_seq_len
    model = serving_model(cfg, None, max_len)
    window = model.chunk_windows(chunk_tokens)[-1]
    slots = jnp.arange(CHECK_SLOTS, dtype=jnp.int32)
    mine = slots == CHECK_SLOTS - 1

    @functools.partial(jax.jit, donate_argnums=(1,))
    def chunk(params, state, tokens, start, n):
        rows = jnp.where(mine[:, None], tokens[None], 0)
        state, hidden, _ = model.prefill_rows(
            params, state, rows, jnp.where(mine, start, 0), jnp.where(mine, n, 0), slots, window)
        return state, hidden[-1]

    @functools.partial(jax.jit, donate_argnums=(1,))
    def step(params, state, token, pos):
        state, logits, _ = model.decode_step(
            params, state, jnp.where(mine, token, 0), jnp.where(mine, pos, 0),
            mine.astype(jnp.int32), max_len)
        return state, logits[-1:].astype(jnp.float32)

    head = jax.jit(lambda params, hidden: model.logits(params, hidden).astype(jnp.float32))
    return model, chunk, step, head


@jax.jit
def _shares(got, want):
    """Each position's |got - want|_rms / |want|_rms."""
    return jnp.sqrt(((got - want) ** 2).mean(-1)) / jnp.sqrt((want**2).mean(-1))


def logit_shares(params, cfg, tokens, pad_to: int):
    """((n,) shares, (V,) reference logits at the last position): the
    program's logits against the reference's at every position of one
    prompt, a block of positions at a time."""
    n = len(tokens)
    n_prefill = max(1, n - _CHECK["decode"])
    model, chunk, step, head = _programs(cfg, _CHECK["chunk"])
    # The reference over the prompt padded to one length: one compiled
    # reference for every prompt of a run (every layer is causal, so no
    # position before the pad sees it).
    x = nemotron_h_reference.hidden_states(params, cfg, list(tokens) + [0] * (pad_to - n))
    want = lambda lo, hi: nemotron_h_reference.head(params, cfg, x[lo:hi])
    state = model.init_state(CHECK_SLOTS, cfg.max_seq_len)
    toks = np.zeros((pad_to + _CHECK["chunk"],), np.int32)
    toks[:n] = tokens
    shares = []
    for start in range(0, n_prefill, _CHECK["chunk"]):
        piece = toks[start : start + _CHECK["chunk"]]
        count = min(n_prefill - start, len(piece))
        state, hidden = chunk(params, state, jnp.asarray(piece), jnp.int32(start), jnp.int32(count))
        for lo in range(0, count, BLOCK):
            hi = min(lo + BLOCK, count)
            shares.append(np.asarray(
                _shares(head(params, hidden[lo:hi]), want(start + lo, start + hi))))
    decoded = []
    for pos in range(n_prefill, n):
        state, got = step(params, state, jnp.int32(toks[pos]), jnp.int32(pos))
        decoded.append(got)
    want_tail = want(n_prefill, n) if decoded else None
    if decoded:
        shares.append(np.asarray(_shares(jnp.concatenate(decoded), want_tail)))
    last = want_tail[-1] if decoded else want(n - 1, n)[0]
    return np.concatenate(shares), np.asarray(last)


def share_quantiles(share, n_decoded: int) -> dict:
    """Quantiles of those shares over a prompt's prefilled positions, and
    the median over the positions that went through the decode step."""
    share = np.asarray(share, np.float64)
    prefilled = share[: len(share) - n_decoded] if n_decoded else share
    out = {name: float(np.quantile(prefilled, q)) for name, q in QUANTILES.items()}
    if n_decoded:
        out["decode_p50"] = float(np.quantile(share[-n_decoded:], 0.5))
    return out


def last_logits(params, cfg, tokens, pad_to: int = 0):
    """The float32 reference's logits at the prompt's last position, if
    the program's logits over the prompt lie within the limits of the
    reference's; else logits no served token agrees with (one entry
    more than the vocabulary, and the maximum there: gap 1)."""
    n, pad_to = len(tokens), max(pad_to, len(tokens))
    share, want_last = logit_shares(params, cfg, tokens, pad_to)
    shares = share_quantiles(share, min(_CHECK["decode"], n - 1))
    outside = sorted(k for k, v in shares.items() if not v <= _CHECK["limits"][k])
    print(json.dumps({"bench": "logit check", **shares, "outside": outside}), flush=True)
    if outside:
        return np.append(np.zeros(want_last.shape[0], np.float32), np.float32(1.0))
    return want_last


# -- the counts ------------------------------------------------------------------


def letters(model: dict) -> str:
    """The letters of the layers kept."""
    return str(model["hybrid_override_pattern"])[: int(model["num_hidden_layers"])]


def part_params(model: dict) -> dict:
    """Parameters of one of each part."""
    D = int(model["hidden_size"])
    H, P = int(model["mamba_num_heads"]), int(model["mamba_head_dim"])
    G, N = int(model["n_groups"]), int(model["ssm_state_size"])
    inner, conv = H * P, H * P + 2 * G * N
    Hq, Gk, d = int(model["num_attention_heads"]), int(model["num_key_value_heads"]), int(model["head_dim"])
    E = int(model.get("num_experts_published", model["n_routed_experts"]))
    latent, F = int(model["moe_latent_size"]), int(model["moe_intermediate_size"])
    Fs = int(model["moe_shared_expert_intermediate_size"]) * int(model.get("n_shared_experts", 1))
    return {
        # W_in (z, xBC, dt) and W_out
        "mamba_proj": D * (inner + conv + H) + inner * D,
        # the convolution's taps and bias, A_log, dt_bias, D, the norm's gain
        "mamba_rest": (int(model["conv_kernel"]) + 1) * conv + 3 * H + inner,
        "attention": D * (Hq + 2 * Gk) * d + Hq * d * D,
        # the router and its selection bias, the latent pair, the shared expert
        "experts_outside": D * E + E + 2 * D * latent + 2 * D * Fs,
        "expert": 2 * latent * F,
        "norm": D,  # one a published layer
        "head": D * int(model["vocab_size"]),
    }


def layer_params(model: dict) -> dict:
    """Parameters of one published layer by letter, outside the routed experts."""
    p = part_params(model)
    return {
        "M": p["mamba_proj"] + p["mamba_rest"] + p["norm"],
        "*": p["attention"] + p["norm"],
        "E": p["experts_outside"] + p["norm"],
    }


def experts_touched(model: dict, rows: float) -> float:
    """Expected distinct experts HELD of one layer that ``rows`` tokens
    touch: a token takes ``k`` router outputs of ``E``, so it misses a
    given expert with probability 1 - k / E."""
    E = int(model.get("num_experts_published", model["n_routed_experts"]))
    miss = 1.0 - int(model["num_experts_per_tok"]) / E
    return int(model["n_routed_experts"]) * (1.0 - miss**rows)


def kv_bytes_per_row(model: dict, engine: dict) -> float:
    """The K and V rows of one position in one ``*`` layer."""
    item = F32 if engine["kv_dtype"] == "float32" else BF16
    return 2.0 * int(model["num_key_value_heads"]) * int(model["head_dim"]) * item


def ssm_state_bytes(model: dict, engine: dict) -> float:
    """What a slot keeps of one ``M`` layer: ``S`` in float32 and the
    convolution's last ``conv_kernel - 1`` inputs in the row dtype."""
    item = F32 if engine["kv_dtype"] == "float32" else BF16
    H, P = int(model["mamba_num_heads"]), int(model["mamba_head_dim"])
    G, N = int(model["n_groups"]), int(model["ssm_state_size"])
    return float(H * P * N * F32 + (int(model["conv_kernel"]) - 1) * (H * P + 2 * G * N) * item)


def decode_step_bytes(model: dict, engine: dict, live_kv_tokens: float) -> float:
    """One decode step over the batch: every layer's parameters outside
    the routed experts and the head once; the experts that the decoding
    rows touch (bf16; ``engine.roofline_decode_rows`` rows: the signature
    carries only the tokens); ``S`` and the tail of those rows in every
    ``M`` layer, read and written; the K and V row of every live token in
    every ``*`` layer, once."""
    p, by_letter = part_params(model), layer_params(model)
    rows = float(engine.get("roofline_decode_rows", engine["max_batch"]))
    kept = letters(model)
    once = sum(by_letter[letter] for letter in kept) + p["head"]
    touched = kept.count("E") * experts_touched(model, rows) * p["expert"]
    return (
        BF16 * (once + touched)
        + kept.count("M") * rows * 2.0 * ssm_state_bytes(model, engine)
        + kept.count("*") * live_kv_tokens * kv_bytes_per_row(model, engine)
    )


def scan_flops_per_token(model: dict) -> float:
    """The block scan's operations a token in one ``M`` layer, in blocks of
    ``chunk_size`` L: C.B over the block (2 L N a group), the block's
    weighted sum of x (2 L P a head), the state's part S C (2 P N a head)
    and the state's update (2 P N a head)."""
    H, P = int(model["mamba_num_heads"]), int(model["mamba_head_dim"])
    G, N, L = int(model["n_groups"]), int(model["ssm_state_size"]), int(model["chunk_size"])
    return 2.0 * L * N * G + 2.0 * L * P * H + 4.0 * P * N * H


def prefill_flops(model: dict, new_tokens: float, attn_pairs: float) -> float:
    """Operations to prefill ``new_tokens``: 2 a parameter and token for
    every layer's projections, the router, the latent pair and the shared
    expert, and for the token's ``k`` experts the share that lands on the
    experts held (``held / E`` of a choice); the block scan of the ``M``
    layers; and for every (query, visible key) pair QK^T and PV over
    ``head_dim`` a query head in the ``*`` layers: 4 x Hq x 128."""
    p, kept = part_params(model), letters(model)
    E = int(model.get("num_experts_published", model["n_routed_experts"]))
    local = int(model["num_experts_per_tok"]) * int(model["n_routed_experts"]) / E
    active = (
        kept.count("M") * p["mamba_proj"] + kept.count("*") * p["attention"]
        + kept.count("E") * (p["experts_outside"] + local * p["expert"])
    )
    pair = 4.0 * int(model["num_attention_heads"]) * int(model["head_dim"])
    return (
        (2.0 * active + kept.count("M") * scan_flops_per_token(model)) * new_tokens
        + kept.count("*") * pair * attn_pairs
    )
