"""The ``mellum`` family (Mellum2-12B-A2.5B): grouped-query attention in
two kinds, ``sliding_attention`` layers that see the last
``sliding_window`` positions beside a ``full_attention`` layer every
fourth, each kind with its own rotary parameters (YaRN on the full
layers), and in every layer softmax-routed experts, all held here, with
neither selection bias nor shared expert.

``llama_config`` calls the program's own mapping from the public keys
(``models.hybrid.from_hf_config``) and returns its ``HybridConfig``, which
``Scheduler`` takes as it takes a ``LlamaConfig``.  The reference is
``mellum_reference.py`` beside ``run.py``; ``last_logits`` below holds the
program's logits, from its chunked prefill and its decode step, to it
before it hands the reference's to the harness.  The counts further down
are what the algorithm needs, from shapes alone;
``tests/test_arch_mellum.py`` holds them to the table of the
configuration's cut worked by hand.
"""

from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

import mellum_reference

BF16 = 2
MIXERS = {"sliding_attention": "window", "full_attention": "full"}


def llama_config(model: dict, engine: dict):
    """The public config.json keys -> the program's ``HybridConfig``."""
    from generativeaiexamples_tpu.models import hybrid

    if engine["weight_dtype"] != "bfloat16":
        raise ValueError("this family is served with bf16 weights only")
    ref = model["reference"]
    # ``last_logits`` is called without the configuration: its limits,
    # the server's chunk and the positions that go through the decode
    # step are kept from here.
    _CHECK.update(limits=dict(ref["logit_share_limits"]), decode=int(ref["decode_positions"]),
                  chunk=int(engine["prefill_chunk_tokens"]))
    cfg = hybrid.from_hf_config(
        model, max_len=int(engine["max_len"]), kv_dtype=str(engine["kv_dtype"])
    )
    by_kind = hybrid.state_bytes(cfg, int(engine["max_batch"]), int(engine["max_len"]))
    print(json.dumps({"bench": "state bytes", "max_len": int(engine["max_len"]),
                      "state_bytes_full": by_kind["full"], "state_bytes_window": by_kind["window"],
                      "snapshot_bytes": cfg.snapshot_bytes(int(engine["max_len"]))}), flush=True)
    return cfg


# -- the comparison that decides ``correct`` -------------------------------------
#
# The harness asks for the reference's logits at a prompt's last position
# and holds the server's greedy token to them.  An expert model is not
# smooth, so that check cannot see a precision, and a served token cannot
# see a window or a frequency.  ``last_logits`` therefore first holds the
# program's logits to the reference's at every position of the prompt: the
# prompt but its last ``decode_positions`` tokens goes through the serving
# model's own chunked prefill (``prefill_row``, what
# ``Scheduler._prefill_suffix`` runs), those last tokens one a step through
# its decode step (``decode_step``, what ``decode_chunk`` scans).  Each
# position's error is taken as a share of its reference logits' root mean
# square, and of those shares the lowest tenth, the median and the ninth
# tenth over the prompt, and the median over the decoded positions, are
# held to the configuration's ``reference.logit_share_limits``.  A prompt
# outside a limit is handed to the harness as one the served token cannot
# agree with, so it counts against ``min_within`` like a wrong token.

_CHECK: dict = {}
QUANTILES = {"p10": 0.1, "p50": 0.5, "p90": 0.9}
# Positions of a float32 (positions, vocabulary) block of logits: 100 MB
# at the published vocabulary, beside the engine's own memory.
BLOCK = 256


@functools.lru_cache(maxsize=2)
def _programs(cfg, window: int):
    """The serving model's chunked prefill of slot 0 of a one-slot state,
    and its decode step, each returning float32 logits."""
    from generativeaiexamples_tpu.engine.serving_models import serving_model

    model = serving_model(cfg, None, window)

    @functools.partial(jax.jit, donate_argnums=(1,))
    def chunk(params, state, tokens, start, n):
        state, hidden, _ = model.prefill_row(
            params, state, tokens, start, n, jnp.int32(0), window)
        return state, model.logits(params, hidden)[0].astype(jnp.float32)

    @functools.partial(jax.jit, donate_argnums=(1,))
    def step(params, state, token, pos):
        state, logits, _ = model.decode_step(
            params, state, token[None], pos[None], jnp.ones((1,), jnp.int32), window)
        return state, logits.astype(jnp.float32)

    return model, chunk, step


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
    model, chunk, step = _programs(cfg, pad_to)
    x = mellum_reference.hidden_states(params, cfg, list(tokens) + [0] * (pad_to - n))
    want = lambda lo, hi: mellum_reference.head(params, cfg, x[lo:hi])
    state = model.init_state(1, pad_to)
    toks = np.zeros((pad_to + _CHECK["chunk"],), np.int32)
    toks[:n] = tokens
    shares = []
    for start in range(0, n_prefill, _CHECK["chunk"]):
        piece = toks[start : start + _CHECK["chunk"]]
        count = min(n_prefill - start, len(piece))
        state, got = chunk(params, state, jnp.asarray(piece)[None], jnp.int32(start), jnp.int32(count))
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
    return np.concatenate(shares), np.asarray(last)


def share_quantiles(share, n_decoded: int) -> dict:
    """Quantiles of those shares over a prompt's positions, and the
    median over the positions that went through the decode step."""
    share = np.asarray(share, np.float64)
    out = {name: float(np.quantile(share, q)) for name, q in QUANTILES.items()}
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


def layer_kinds(model: dict) -> list[str]:
    """The mixer of each layer kept: the first ``num_hidden_layers``
    entries of ``layer_types``."""
    return [MIXERS[t] for t in model["layer_types"][: int(model["num_hidden_layers"])]]


def part_params(model: dict) -> dict:
    """Parameters of one of each part."""
    D, H, KH, hd = (int(model[k]) for k in
                    ("hidden_size", "num_attention_heads", "num_key_value_heads", "head_dim"))
    return {
        "attention": D * (H + 2 * KH) * hd + H * hd * D,  # q, k, v and the output
        "router": D * int(model["num_experts"]),
        "expert": 3 * D * int(model["moe_intermediate_size"]),
        "head": D * int(model["vocab_size"]),
    }


def experts_touched(model: dict, rows: float) -> float:
    """Expected distinct experts of one layer that ``rows`` tokens touch:
    a token takes ``k`` distinct experts of ``E``, so it misses a given
    one with probability 1 - k / E."""
    E = int(model["num_experts"])
    return E * (1.0 - (1.0 - int(model["num_experts_per_tok"]) / E) ** rows)


def kv_bytes_per_row(model: dict, engine: dict) -> float:
    """K and V of one position in one layer."""
    item = 4 if engine["kv_dtype"] == "float32" else BF16
    return 2.0 * int(model["num_key_value_heads"]) * int(model["head_dim"]) * item


def decode_step_bytes(model: dict, engine: dict, live_kv_tokens: float) -> float:
    """One decode step over the batch: the attention weights, routers and
    the head once; the experts the decoding rows touch (bf16); the K/V
    rows of every live token in a full layer and of the last
    ``sliding_window`` positions of every live row in a window layer.  The
    rows decoding at once are ``engine.roofline_decode_rows`` (the
    signature carries only the tokens); the window layers' rows are taken
    as ``rows x min(mean length, window)``, which is exact where every row
    is longer than the window (every prompt of this family's cell is) and
    otherwise over by less than ``rows x window``."""
    p, kinds = part_params(model), layer_kinds(model)
    rows = float(engine.get("roofline_decode_rows", engine["max_batch"]))
    once = len(kinds) * (p["attention"] + p["router"]) + p["head"]
    touched = len(kinds) * experts_touched(model, rows) * p["expert"]
    window_rows = rows * min(live_kv_tokens / rows, float(model["sliding_window"]))
    kv_rows = kinds.count("full") * live_kv_tokens + kinds.count("window") * window_rows
    return BF16 * (once + touched) + kv_rows * kv_bytes_per_row(model, engine)


def prefill_flops(model: dict, new_tokens: float, attn_pairs: float) -> float:
    """Operations to prefill ``new_tokens``: 2 a parameter and token for
    the attention projections, the router and the token's 8 experts (all
    held here); and QK^T and PV over ``head_dim`` a query head for every
    (query, visible key) pair: ``attn_pairs`` in a full layer, and in a
    window layer ``min(i + 1, window)`` a position.  The arguments carry
    the pairs' sum alone, so the window layers' pairs are taken as
    ``new_tokens x min(attn_pairs / new_tokens, window)``: exact where
    every new position lies past the window, and over by at most
    ``window^2 / 2`` pairs a cold prompt (its first ``window`` positions
    see half of it on average): under 2 % of a cold 2.8k-token prompt's
    operations at the published sizes."""
    p, kinds = part_params(model), layer_kinds(model)
    active = len(kinds) * (
        p["attention"] + p["router"] + int(model["num_experts_per_tok"]) * p["expert"]
    )
    pair = 4.0 * int(model["num_attention_heads"]) * int(model["head_dim"])
    window_pairs = 0.0
    if new_tokens:
        window_pairs = new_tokens * min(attn_pairs / new_tokens, float(model["sliding_window"]))
    return (
        2.0 * active * new_tokens
        + pair * (kinds.count("full") * attn_pairs + kinds.count("window") * window_pairs)
    )
