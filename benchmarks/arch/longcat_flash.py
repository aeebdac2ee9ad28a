"""The ``longcat_flash`` family (LongCat-Flash-Chat): a published layer
is TWO latent-attention sublayers (a cache row is the normed, rescaled
key-value latent of ``kv_lora_rank`` 512 beside one rotated rope key of
64: 1,152 B a token a sublayer in bf16, stored in 640 columns), two dense
SwiGLUs of ``ffn_hidden_size`` 12,288 and ONE expert layer that reads the
first sublayer's normed post-attention stream and is added after the
second sublayer's dense MLP (shortcut-connected experts); the router has
``num_experts_published + zero_expert_num`` = 768 outputs, softmax over
them all, 12 a token, of which the last 256 are identity experts that add
``w x`` and compute nothing.  The configuration holds a share of the real
experts (``n_routed_experts`` of them, from ``engine.expert_offset``) and
adds every identity choice of its tokens.

What a row of ``benchmarks/README.md``'s layout table would say (that file
is not a ``model_config`` PR's to edit): ``arch/longcat_flash.py`` maps
``configs/longcat-flash-chat-l4e16.json`` to the program's
``ShortcutLatentConfig`` (``num_layers`` 4 -> 8 entries of ``layer_kinds``)
and holds its counts and its comparison; ``longcat_flash_reference.py``
beside ``run.py`` is the plain float32 reference (a copy of
``generativeaiexamples_tpu/models/longcat_flash_reference.py``);
``traffic/doc-reason.json`` and ``traffic/doc-reason-closed.json``
(DeepSeek-V3.2's mix) are the cell's; ``layer_metrics/
decode_zero_choice_pct.py`` reads the one counter this family added
(``moe_choices_zero``).

``llama_config`` calls the program's own mapping from the public keys
(``models.hybrid.from_hf_config``) and returns its
``ShortcutLatentConfig``, which ``Scheduler`` takes as it takes a
``LlamaConfig``.  ``last_logits`` below holds the program's logits, from
its chunked prefill and its decode step as ``arch/mistral4.py`` drives
them, to this family's reference before it hands the reference's to the
harness.  The counts
further down are what the algorithm needs, from shapes alone: an identity
choice costs no bytes and no operations;
``tests/test_arch_longcat_flash.py`` holds them to the table of the
configuration's cut worked by hand.
"""

from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

import longcat_flash_reference

BF16 = 2


def llama_config(model: dict, engine: dict):
    """The public config.json keys -> the program's ``ShortcutLatentConfig``."""
    from generativeaiexamples_tpu.models import hybrid

    if not hasattr(hybrid, "ShortcutLatentConfig"):
        # The commit before the one that added the family: fail at once.
        raise SystemExit("benchmarks/arch/longcat_flash.py: this program has no longcat_flash "
                         "family (models/hybrid.py lacks ShortcutLatentConfig)")
    if engine["weight_dtype"] != "bfloat16":
        raise ValueError("this family is served with bf16 weights only")
    if int(engine["experts_held"]) != int(model["n_routed_experts"]):
        raise ValueError("engine.experts_held and n_routed_experts (the experts held) disagree")
    ref = model["reference"]
    # ``last_logits`` is called without the configuration: its limits, the
    # server's chunk and the positions that go through the decode step are
    # kept from here.
    _CHECK.update(limits=dict(ref["logit_share_limits"]), decode=int(ref["decode_positions"]),
                  chunk=int(engine["prefill_chunk_tokens"]))
    cfg = hybrid.from_hf_config(
        model, max_len=int(engine["max_len"]), expert_offset=int(engine["expert_offset"]),
        kv_dtype=str(engine["kv_dtype"]),
    )
    by_kind = hybrid.state_bytes(cfg, int(engine["max_batch"]), int(engine["max_len"]))
    shapes = jax.eval_shape(lambda: hybrid.init_params(cfg, jax.random.PRNGKey(0)))
    print(json.dumps({
        "bench": "state bytes", "max_len": int(engine["max_len"]),
        "weight_bytes": sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(shapes)),
        "state_bytes_full": by_kind["full"], "sublayers": len(cfg.layers_of("mla")),
        "router_outputs": cfg.router_outputs,
        # Of a stored row's ``latent_width`` columns the latent and the
        # rope key fill this many: the rest are zeros up to whole lanes.
        "latent_row_bytes_used": int(latent_bytes_per_row(model, engine)),
        "latent_row_bytes_stored": cfg.latent_width * cfg.state_dtype.itemsize,
    }), flush=True)
    return cfg


# -- the comparison that decides ``correct`` -------------------------------------
#
# ``arch/mistral4.py``'s comparison (a state of latent rows alone, one
# token a step) against this family's reference and limits: the program's
# logits at every position of the prompt, through the calls the
# scheduler's programs make in the measured window and at their shapes: a
# state of ``max_len`` rows a slot (``CHECK_SLOTS`` of them, the prompt in
# the last), the prompt but its last ``decode_positions`` tokens a chunk at
# a time through ``prefill_rows`` over the whole slot's window (the chunk
# beside a pad row: a group program of two, the slots' state read and
# written in place, all eight sublayers' walks in ``ops/mla_chunk.py``'s
# kernel), those last tokens one a step through ``decode_step`` over every
# slot of that state (what ``decode_chunk`` scans: ``ops/mla_decode.py``'s
# kernel up to the row's length, the other slot not decoding and not
# read).  Each position's error is taken as a share of its reference
# logits' root mean square, and of those shares the lowest tenth, the
# median and the ninth tenth over the prefilled positions, and the median
# over the decoded positions, are held to the configuration's
# ``reference.logit_share_limits``.  A prompt outside a limit is handed to
# the harness as one the served token cannot agree with, so it counts
# against ``min_within`` like a wrong token.

_CHECK: dict = {}
QUANTILES = {"p10": 0.1, "p50": 0.5, "p90": 0.9}
# Positions of a float32 (positions, vocabulary) block of logits.
BLOCK = 256


# The check's state: the slot the prompt lives in and one before it that
# holds nothing, so that a row of a call is not the slot of its number.
CHECK_SLOTS = 2


@functools.lru_cache(maxsize=4)
def _programs(cfg, chunk_tokens: int):
    """The serving model, and the two calls the scheduler's programs make
    of it, each returning the prompt's float32 logits: a chunk of the last
    slot beside a pad row through ``prefill_rows`` at the chunk programs'
    widest window, and ``decode_step`` over every slot at the widest
    decode window."""
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
        return state, model.logits(params, hidden[-1:])[0].astype(jnp.float32)

    @functools.partial(jax.jit, donate_argnums=(1,))
    def step(params, state, token, pos):
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
    """((n,) shares, (V,) reference logits at the last position): the
    program's logits against the reference's at every position of one
    prompt, a block of positions at a time.  ``served`` (absent: ``cfg``)
    is the configuration the program runs, which a control changes."""
    n = len(tokens)
    n_prefill = max(1, n - _CHECK["decode"])
    model, chunk, step = _programs(served or cfg, _CHECK["chunk"])
    # The reference over the prompt padded to one length: one compiled
    # reference for every prompt of a run (every layer is causal, so no
    # position before the pad sees it).
    x = longcat_flash_reference.hidden_states(params, cfg, list(tokens) + [0] * (pad_to - n))
    want = lambda lo, hi: longcat_flash_reference.head(params, cfg, x[lo:hi])
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
    """Parameters of one of each part; a published layer has two
    ``attention`` and two ``dense``, one ``router`` and its experts."""
    D, H = int(model["hidden_size"]), int(model["num_attention_heads"])
    q_rank, kv_rank = int(model["q_lora_rank"]), int(model["kv_lora_rank"])
    nope, rope, vd = (int(model[k]) for k in ("qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim"))
    return {
        # W_qa and W_qb (the low-rank query pair), W_kva, W_kvb, W_o
        "attention": D * q_rank + q_rank * H * (nope + rope) + D * (kv_rank + rope)
        + kv_rank * H * (nope + vd) + H * vd * D,
        "dense": 3 * D * int(model["ffn_hidden_size"]),
        "router": D * router_outputs(model),
        "expert": 3 * D * int(model["expert_ffn_hidden_size"]),
        "head": D * int(model["vocab_size"]),
    }


def router_outputs(model: dict) -> int:
    """The real experts the router knows, then the identity experts."""
    return int(model.get("num_experts_published", model["n_routed_experts"])) + int(
        model.get("zero_expert_num", 0))


def local_choices(model: dict) -> float:
    """A token's choices that land on the real experts held, the balanced
    bias spreading its ``moe_topk`` choices evenly over all the router's
    outputs: 12 x 16 / 768 = 0.25.  An identity choice lands on no expert."""
    return int(model["moe_topk"]) * int(model["n_routed_experts"]) / router_outputs(model)


def experts_touched(model: dict, rows: float) -> float:
    """Expected distinct real experts HELD, of one expert layer, that
    ``rows`` tokens touch: a token takes ``k`` distinct outputs of the
    router's 768, so it misses a given expert with probability 1 - k / 768."""
    miss = 1.0 - int(model["moe_topk"]) / router_outputs(model)
    return int(model["n_routed_experts"]) * (1.0 - miss**rows)


def latent_bytes_per_row(model: dict, engine: dict) -> float:
    """The latent and the rope key of one position in one sublayer: what
    the algorithm reads of a row (the program stores the row filled up to
    whole lanes of 128)."""
    item = 4 if engine["kv_dtype"] == "float32" else BF16
    return float(int(model["kv_lora_rank"]) + int(model["qk_rope_head_dim"])) * item


def decode_step_bytes(model: dict, engine: dict, live_kv_tokens: float) -> float:
    """One decode step over the batch: both sublayers' attention
    projections and dense MLPs, the router and the head once; the real
    experts held that the decoding rows touch (bf16;
    ``engine.roofline_decode_rows`` rows: the signature carries only the
    tokens); the latent row of every live token in every SUBLAYER, once
    (the absorbed form reads it for all heads).  An identity choice reads
    nothing."""
    p, layers = part_params(model), int(model["num_layers"])
    rows = float(engine.get("roofline_decode_rows", engine["max_batch"]))
    once = layers * (2 * p["attention"] + 2 * p["dense"] + p["router"]) + p["head"]
    touched = layers * experts_touched(model, rows) * p["expert"]
    return BF16 * (once + touched) + 2 * layers * live_kv_tokens * latent_bytes_per_row(model, engine)


def prefill_flops(model: dict, new_tokens: float, attn_pairs: float) -> float:
    """Operations to prefill ``new_tokens``: 2 a parameter and token for
    both sublayers' attention projections (both products of the low-rank
    query, and ``W_kvb``'s expansion of the token's own latent once) and
    dense MLPs, the router, and the token's choices that land on the real
    experts held (0.25 of them on average; an identity choice is one
    multiply-add a value, not counted); and for every (query, visible key)
    pair of every SUBLAYER QK^T over nope + rope and PV over
    ``v_head_dim`` a head: 2 x H x (192 + 128)."""
    p, layers = part_params(model), int(model["num_layers"])
    active = layers * (
        2 * p["attention"] + 2 * p["dense"] + p["router"] + local_choices(model) * p["expert"]
    )
    pair = 2.0 * int(model["num_attention_heads"]) * (
        int(model["qk_nope_head_dim"]) + int(model["qk_rope_head_dim"]) + int(model["v_head_dim"])
    )
    return 2.0 * active * new_tokens + 2 * layers * pair * attn_pairs
