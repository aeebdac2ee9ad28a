"""The ``bailing_hybrid`` family (Ling-3.0-flash-VL's language model): KDA
linear-attention layers beside a latent-attention (MLA) layer every
``layer_group_size`` layers, a leading dense SwiGLU layer, then
sigmoid-routed experts with a shared one, of which the configuration
holds a share (``num_experts`` of ``num_experts_published``).

``llama_config`` calls the program's own mapping from the public keys
(``models.hybrid.from_hf_config``) and returns its ``HybridConfig``, which
``Scheduler`` takes as it takes a ``LlamaConfig``.  The reference is
``ling_reference.py`` beside ``run.py``; ``last_logits`` below holds the
program's logits to it before it hands the reference's to the harness.
The counts further down are what the algorithm needs, from shapes alone;
``tests/test_arch_ling.py`` holds them to the table of the
configuration's cut worked by hand.
"""

from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

import ling_reference

BF16 = 2
# Sub-chunk of the chunk-wise KDA form the counts assume (ops/kda.py: 16,
# which the gate's floor of -5 a step fixes).
KDA_SUB = 16


def llama_config(model: dict, engine: dict):
    """The public config.json keys -> the program's ``HybridConfig``."""
    from generativeaiexamples_tpu.models.hybrid import from_hf_config

    if engine["weight_dtype"] != "bfloat16":
        raise ValueError("this family is served with bf16 weights only")
    # ``last_logits`` is called without the configuration: its limits
    # and the server's chunk are kept from here.
    _CHECK.update(limits=dict(model["reference"]["logit_share_limits"]),
                  chunk=int(engine["prefill_chunk_tokens"]))
    return from_hf_config(
        model,
        max_len=int(engine["max_len"]),
        expert_offset=int(model.get("expert_offset", 0)),
        kv_dtype=str(engine["kv_dtype"]),
    )


# -- the comparison that decides ``correct`` -------------------------------------
#
# The harness asks for the reference's logits at a prompt's last position
# and holds the server's greedy token to them.  An expert model is not
# smooth, so that check cannot see a lower precision (PERF.md section 7
# row 14).  ``last_logits`` therefore first holds the program's logits to
# the reference's at every position of the prompt: each position's error
# as a share of its logits' root mean square, and of those shares the
# lowest tenth (the arithmetic: positions no expert flip has touched), the
# median and the ninth tenth (flips, and any fault in later positions)
# against the configuration's ``reference.logit_share_limits``.  A prompt
# outside a limit is handed to the harness as one the served token cannot
# agree with, so it counts against ``min_within`` like a wrong token.

_CHECK: dict = {}
QUANTILES = {"p10": 0.1, "p50": 0.5, "p90": 0.9}


@functools.lru_cache(maxsize=2)
def _chunk_program(cfg, window: int):
    """The serving model's chunked prefill of slot 0 of a one-slot state,
    returning the chunk's logits: what ``Scheduler._prefill_suffix`` runs."""
    from generativeaiexamples_tpu.engine.serving_models import serving_model

    model = serving_model(cfg, None, window)

    @functools.partial(jax.jit, donate_argnums=(1,))
    def chunk(params, state, tokens, start, n):
        state, hidden, _ = model.prefill_row(
            params, state, tokens, start, n, jnp.int32(0), window)
        return state, model.logits(params, hidden)[0].astype(jnp.float32)

    return model, chunk


def program_logits(params, cfg, tokens, pad_to: int):
    """(pad_to, V) float32: the program's logits at every position of one
    prompt, prefilled cold in the server's chunks, the last one padded."""
    model, chunk = _chunk_program(cfg, pad_to)
    state = model.init_state(1, pad_to)
    padded = np.zeros((pad_to,), np.int32)
    padded[: len(tokens)] = tokens
    out = []
    for start in range(0, pad_to, _CHECK["chunk"]):
        piece = padded[start : start + _CHECK["chunk"]]
        n = max(0, min(len(tokens) - start, len(piece)))
        state, lg = chunk(params, state, jnp.asarray(piece)[None], jnp.int32(start), jnp.int32(n))
        out.append(lg)
    return jnp.concatenate(out)


@jax.jit
def _compare(got, want, last):
    """Each position's |got - want|_rms / |want|_rms, and ``want[last]``."""
    share = jnp.sqrt(((got - want) ** 2).mean(-1)) / jnp.sqrt((want**2).mean(-1))
    return share, jax.lax.dynamic_index_in_dim(want, last, 0, keepdims=False)


def logit_shares(share) -> dict:
    """Quantiles of those shares over a prompt's positions."""
    share = np.asarray(share, np.float64)
    return {name: float(np.quantile(share, q)) for name, q in QUANTILES.items()}


def last_logits(params, cfg, tokens, pad_to: int = 0):
    """The float32 reference's logits at the prompt's last position, if
    the program's logits over the prompt lie within the limits of the
    reference's; else logits no served token agrees with (one entry
    more than the vocabulary, and the maximum there: gap 1)."""
    n, pad_to = len(tokens), max(pad_to, len(tokens))
    want = ling_reference.all_logits(params, cfg, list(tokens) + [0] * (pad_to - n))
    share, want_last = _compare(program_logits(params, cfg, tokens, pad_to), want, n - 1)
    shares = logit_shares(np.asarray(share)[:n])
    outside = sorted(k for k, v in shares.items() if not v <= _CHECK["limits"][k])
    print(json.dumps({"bench": "logit check", **shares, "outside": outside}), flush=True)
    if outside:
        return np.append(np.zeros(want.shape[1], np.float32), np.float32(1.0))
    return np.asarray(want_last)


# -- the counts ------------------------------------------------------------------


def layer_kinds(model: dict) -> list[tuple[str, str]]:
    """(mixer, mlp) of each layer kept, by the family's convention."""
    period, first = int(model["layer_group_size"]), int(model.get("first_layer", 0))
    return [
        ("mla" if (first + j + 1) % period == 0 else "kda",
         "dense" if j < int(model["first_k_dense_replace"]) else "experts")
        for j in range(int(model["num_hidden_layers"]))
    ]


def part_params(model: dict) -> dict:
    """Parameters of one of each part."""
    D, H, K = (int(model[k]) for k in ("hidden_size", "num_attention_heads", "head_dim"))
    W = int(model["short_conv_kernel_size"])
    rank, nope, rope, vd = (
        int(model[k]) for k in ("kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim")
    )
    F, Fm, Fs = (
        int(model[k]) for k in
        ("intermediate_size", "moe_intermediate_size", "moe_shared_expert_intermediate_size")
    )
    return {
        # q, k, v (and their convolution), the gate f, the output gate,
        # beta, A and the gate's bias, the head norm, the output.
        "kda": D * 3 * H * K + W * 3 * H * K + 2 * D * H * K + D * H + H + H * K + K + H * K * D,
        "mla": D * H * (nope + rope) + D * (rank + rope) + rank + rank * H * (nope + vd)
        + D * H + H * vd * D,
        "dense": 3 * D * F,
        "expert": 3 * D * Fm,
        "shared": 3 * D * Fs,
        "router": D * int(model.get("num_experts_published", model["num_experts"])),
        "head": D * int(model["vocab_size"]),
    }


def _counts(model: dict) -> dict:
    kinds = layer_kinds(model)
    return {
        "kda": sum(m == "kda" for m, _ in kinds),
        "mla": sum(m == "mla" for m, _ in kinds),
        "dense": sum(p == "dense" for _, p in kinds),
        "experts": sum(p == "experts" for _, p in kinds),
    }


def local_share(model: dict) -> float:
    """Share of a token's routed choices that lands on the experts held,
    routing being even: 128 / 512."""
    return int(model["num_experts"]) / int(model.get("num_experts_published", model["num_experts"]))


def experts_touched(model: dict, rows: float) -> float:
    """Expected distinct experts held, of one layer, that ``rows`` tokens
    touch: a token takes ``k`` distinct experts of ``E``, so it misses a
    given one with probability 1 - k / E."""
    E = int(model.get("num_experts_published", model["num_experts"]))
    miss = 1.0 - int(model["num_experts_per_tok"]) / E
    return int(model["num_experts"]) * (1.0 - miss ** rows)


def state_bytes_per_row(model: dict, engine: dict) -> float:
    """One slot's recurrent state over the KDA layers: S in float32 and
    the convolution tails in the state's dtype."""
    H, K = int(model["num_attention_heads"]), int(model["head_dim"])
    tail = (int(model["short_conv_kernel_size"]) - 1) * 3 * H * K
    tail_bytes = 4 if engine["kv_dtype"] == "float32" else BF16
    return _counts(model)["kda"] * (H * K * K * 4 + tail * tail_bytes)


def latent_bytes_per_token(model: dict, engine: dict) -> float:
    width = int(model["kv_lora_rank"]) + int(model["qk_rope_head_dim"])
    return _counts(model)["mla"] * width * (4 if engine["kv_dtype"] == "float32" else BF16)


def decode_step_bytes(model: dict, engine: dict, live_kv_tokens: float) -> float:
    """One decode step over the batch: the mixers, dense parts, shared
    experts, routers and head once; the experts the decoding rows touch
    (bf16); every decoding row's recurrent state read and written; the
    latent rows of every live token.  The rows decoding at once are
    ``engine.roofline_decode_rows`` (the signature carries only the tokens)."""
    p, n = part_params(model), _counts(model)
    rows = float(engine.get("roofline_decode_rows", engine["max_batch"]))
    once = (
        n["kda"] * p["kda"] + n["mla"] * p["mla"] + n["dense"] * p["dense"]
        + n["experts"] * (p["shared"] + p["router"]) + p["head"]
    )
    touched = n["experts"] * experts_touched(model, rows) * p["expert"]
    return (
        BF16 * (once + touched)
        + 2.0 * rows * state_bytes_per_row(model, engine)
        + live_kv_tokens * latent_bytes_per_token(model, engine)
    )


def kda_flops_per_token(model: dict) -> float:
    """The chunk-wise form's products a token and KDA layer, beside the
    projections: the state read for keys and queries and its update
    (3 x 2 K V a head), and inside a sub-chunk of C the two C x K score
    products, the C x V weighted sum and the triangular solve."""
    H, K, C = int(model["num_attention_heads"]), int(model["head_dim"]), KDA_SUB
    return H * (6.0 * K * K + 4.0 * C * K + 3.0 * C * K)


def prefill_flops(model: dict, new_tokens: float, attn_pairs: float) -> float:
    """Operations to prefill ``new_tokens``: 2 a parameter and token for
    the mixers, the dense part, router, shared expert and the share of
    the token's 8 experts that lands here; the KDA chunk's products; and
    for every (query, visible key) pair of an MLA layer, QK^T over nope +
    rope and PV over v a head."""
    p, n = part_params(model), _counts(model)
    H = int(model["num_attention_heads"])
    active = (
        n["kda"] * p["kda"] + n["mla"] * p["mla"] + n["dense"] * p["dense"]
        + n["experts"] * (
            p["router"] + p["shared"]
            + int(model["num_experts_per_tok"]) * local_share(model) * p["expert"]
        )
    )
    pair = 2.0 * H * (
        int(model["qk_nope_head_dim"]) + int(model["qk_rope_head_dim"]) + int(model["v_head_dim"])
    )
    return (
        2.0 * active * new_tokens
        + n["kda"] * kda_flops_per_token(model) * new_tokens
        + n["mla"] * pair * attn_pairs
    )
