"""The ``zaya`` family (ZAYA1-8B): every layer attends in a compressed
latent (Compressed Convolutional Attention, grouped-query form: 8 query
heads on 2 key-value heads of 128, a two-step causal convolution over the
concatenated q and k latents, the mean of the un-mixed q and k added back,
half of the value heads taken from the previous token, q and k normed to a
fixed length, half of each head rotated) and routes one expert a token of
16 through the ZAYA router, an MLP ``router_hidden_size`` wide that is
handed the previous layer's router state; the head is the embedding.  A
slot keeps, a layer, K/V rows a position (1,024 B a token in bf16) and, as
of its last token, the convolutions' tails and the last token's late
values (5,376 B).

What a row of ``benchmarks/README.md``'s layout table would say (that file
is not a ``model_config`` PR's to edit): ``arch/zaya.py`` maps
``configs/zaya1-8b-l20.json`` to the program's ``CcaConfig`` and holds its
counts; ``zaya_reference.py`` beside ``run.py`` is the plain float32
reference (a copy of ``generativeaiexamples_tpu/models/zaya_reference.py``);
``traffic/reason.json`` and ``traffic/reason-closed.json`` are the cell's
mix (K-EXAONE's, byte for byte: a 256-token system prompt, 64-4,096 unique
tokens, 128-3,072 greedy output tokens, 40 waiting clients);
``layer_metrics/decode_experts_touched_pct.py`` reads the decode steps'
expert counters.

``llama_config`` calls the program's own mapping from the public keys
(``models.hybrid.from_hf_config``) and returns its ``CcaConfig``, which
``Scheduler`` takes as it takes a ``LlamaConfig``.  ``last_logits`` below
holds the program's logits, from its chunk program and its decode step, to
the reference's before it hands the reference's to the harness.  The
counts further down are what the algorithm needs, from shapes alone;
``tests/test_arch_zaya.py`` holds them to the table of the configuration's
cut worked by hand.
"""

from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

import zaya_reference

BF16 = 2


def llama_config(model: dict, engine: dict):
    """The public config.json keys -> the program's ``CcaConfig``."""
    from generativeaiexamples_tpu.models import hybrid

    if not hasattr(hybrid, "CcaConfig"):
        # The commit before the one that added the family: fail at once.
        raise SystemExit("benchmarks/arch/zaya.py: this program has no zaya family "
                         "(models/hybrid.py lacks CcaConfig)")
    if engine["weight_dtype"] != "bfloat16":
        raise ValueError("this family is served with bf16 weights only")
    if int(engine["experts_held"]) != int(model["num_experts"]):
        raise ValueError("engine.experts_held and num_experts (the experts held) disagree")
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
    layer = shapes["layers"][0]
    count = lambda names: sum(layer[n].size for n in names)
    print(json.dumps({
        "bench": "state bytes", "max_len": int(engine["max_len"]),
        "weight_bytes": sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(shapes)),
        "state_bytes_full": by_kind["full"], "state_bytes_window": by_kind["window"],
        # The tails of every slot: what a snapshot holds of one.
        "state_bytes_tails": by_kind["recurrent"],
        "snapshot_bytes": cfg.snapshot_bytes(int(engine["max_len"])),
        # Parameters of one layer by part, reckoned from ``init_params``'
        # shapes, and of the tied matrix.
        "params_attention": count(n for n in layer if n.startswith(("w_qkv", "w_o", "conv", "k_temp"))),
        "params_router": count(n for n in layer if n.startswith("router")),
        "params_experts": count(("w_gu_e", "w_down_e")),
        "params_embedding": shapes["embed"].size, "tied_head": "lm_head" not in shapes,
    }), flush=True)
    return cfg


# -- the comparison that decides ``correct`` -------------------------------------
#
# The harness asks for the reference's logits at a prompt's last position
# and holds the server's greedy token to them.  An expert model is not
# smooth, so that check cannot see a precision, and a served token cannot
# see a convolution's tail, a shifted value or a router's average.
# ``last_logits`` therefore first holds the program's logits to the
# reference's at every position of the prompt, through the calls the
# scheduler's programs make in the measured window and at their shapes: a
# state of ``max_len`` rows a slot (``CHECK_SLOTS`` of them, the prompt in
# the last), the prompt but its last ``decode_positions`` tokens a chunk at
# a time through ``prefill_rows`` at the chunk programs' widest window (the
# chunk beside a pad row: a group program of two; every chunk after the
# first takes its convolutions' history and its late values from the slot's
# tails), those last tokens one a step through ``decode_step`` over every
# slot of that state (what ``decode_chunk`` scans: the row walk over the
# rows the slot holds where the chip admits it, the other slot not decoding
# and its tails not moving).  Each position's error is taken as a share of
# its reference logits' root mean square, and of those shares the lowest
# tenth, the median and the ninth tenth over the prefilled positions, and
# the median over the decoded positions, are held to the configuration's
# ``reference.logit_share_limits``.  A prompt outside a limit is handed to
# the harness as one the served token cannot agree with, so it counts
# against ``min_within`` like a wrong token.

_CHECK: dict = {}
QUANTILES = {"p10": 0.1, "p50": 0.5, "p90": 0.9}
# Positions of a float32 (positions, vocabulary) block of logits: 64 rows
# of 262,272 are 67 MB on each side, beside an engine that fills the chip.
BLOCK = 64
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
    x = zaya_reference.hidden_states(params, cfg, list(tokens) + [0] * (pad_to - n))
    want = lambda lo, hi: zaya_reference.head(params, cfg, x[lo:hi])
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


def part_params(model: dict) -> dict:
    """Parameters of one of each part."""
    D, d = int(model["hidden_size"]), int(model["head_dim"])
    H, G = int(model["num_attention_heads"]), int(model["num_key_value_heads"])
    R = int(model["router_hidden_size"])
    E = int(model.get("num_experts_published", model["num_experts"]))
    heads = H + G  # what the convolutions mix
    return {
        # W_q, W_k, W_v1 and W_v2, W_o; the temperature a key head
        "attention": D * (H + 2 * G) * d + H * d * D + G,
        # step one (a filter and a bias a channel), step two (a d x d
        # matrix a head and tap, a bias a channel)
        "convolutions": (int(model["cca_time0"]) + 1) * heads * d
        + int(model["cca_time1"]) * heads * d * d + heads * d,
        # W_down and its bias, gamma, the norm's gain, W_1, W_2, W_3 with
        # their biases, the selection bias
        "router": D * R + R + 1 + R + 2 * (R * R + R) + R * E + E + E,
        "norms": 2 * D,
        "expert": 3 * D * int(model["moe_intermediate_size"]),
        "head": D * int(model["vocab_size"]),  # the embedding: read once as the head
    }


def experts_touched(model: dict, rows: float) -> float:
    """Expected distinct experts HELD of one layer that ``rows`` tokens
    touch: a token takes one router output of ``E``, so it misses a given
    expert with probability 1 - 1 / E."""
    E = int(model.get("num_experts_published", model["num_experts"]))
    miss = 1.0 - int(model["num_experts_per_tok"]) / E
    return int(model["num_experts"]) * (1.0 - miss**rows)


def kv_bytes_per_row(model: dict, engine: dict) -> float:
    """The K and V rows of one position in one layer."""
    item = 4 if engine["kv_dtype"] == "float32" else BF16
    return 2.0 * int(model["num_key_value_heads"]) * int(model["head_dim"]) * item


def tail_bytes(model: dict, engine: dict) -> float:
    """What a slot keeps of one layer as of its last token: the last
    ``cca_time - 1`` inputs of each convolution step and the late values."""
    item = 4 if engine["kv_dtype"] == "float32" else BF16
    d = int(model["head_dim"])
    H, G = int(model["num_attention_heads"]), int(model["num_key_value_heads"])
    taps = int(model["cca_time0"]) - 1 + int(model["cca_time1"]) - 1
    return float((taps * (H + G) * d + G // 2 * d) * item)


def decode_step_bytes(model: dict, engine: dict, live_kv_tokens: float) -> float:
    """One decode step over the batch: every layer's mixer, router and
    norms and the tied matrix (as the head) once; the experts that the
    decoding rows touch (bf16; ``engine.roofline_decode_rows`` rows: the
    signature carries only the tokens); the K and V row of every live token
    in every layer, once; the tails of the rows that decode."""
    p, layers = part_params(model), int(model["num_hidden_layers"])
    rows = float(engine.get("roofline_decode_rows", engine["max_batch"]))
    once = layers * (p["attention"] + p["convolutions"] + p["router"] + p["norms"]) + p["head"]
    touched = layers * experts_touched(model, rows) * p["expert"]
    return (
        BF16 * (once + touched)
        + layers * live_kv_tokens * kv_bytes_per_row(model, engine)
        + layers * rows * tail_bytes(model, engine)
    )


def prefill_flops(model: dict, new_tokens: float, attn_pairs: float) -> float:
    """Operations to prefill ``new_tokens``: 2 a parameter and token for
    the mixer's projections, both convolution steps, the router's chain and
    the token's one expert (``held / E`` of a choice lands on the experts
    held: all of it here); and for every (query, visible key) pair QK^T and
    PV over ``head_dim`` a query head: 4 x H x 128."""
    p, layers = part_params(model), int(model["num_hidden_layers"])
    E = int(model.get("num_experts_published", model["num_experts"]))
    local = int(model["num_experts_per_tok"]) * int(model["num_experts"]) / E
    active = layers * (p["attention"] + p["convolutions"] + p["router"] + local * p["expert"])
    pair = 4.0 * int(model["num_attention_heads"]) * int(model["head_dim"])
    return 2.0 * active * new_tokens + layers * pair * attn_pairs
