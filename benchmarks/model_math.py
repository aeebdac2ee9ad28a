"""Bytes and operations a step NEEDS, from shapes alone.

These are the numerators of the roofline shares: what the algorithm has
to move or compute, not what the program happens to do.  The dropless
one-hot MoE dispatch computes all experts for every token; only the
experts a token uses count here, so that waste shows as a low share.

``model`` is a configuration file's top level (the public config.json
keys); ``engine`` its engine block.  These are the counts of the
llama-shaped architecture (``arch/llama.py`` exports them); another
architecture brings its own in its own module.
"""

from __future__ import annotations

# The key under which each family's config.json gives its expert count.
EXPERT_COUNT_KEYS = ("num_local_experts", "num_experts", "n_routed_experts")
DTYPE_BYTES = {"int8": 1, "bfloat16": 2, "float16": 2, "float32": 4}


def n_experts(model: dict) -> int:
    """Routed experts a layer holds; 0 for a dense model."""
    for key in EXPERT_COUNT_KEYS:
        if key in model:
            return int(model[key])
    return 0


def head_dim(model: dict) -> int:
    """The ``head_dim`` key, or hidden size / heads where a family's
    config.json leaves it out."""
    return int(
        model.get("head_dim")
        or int(model["hidden_size"]) // int(model["num_attention_heads"])
    )


def _dims(model: dict) -> dict:
    return {
        "L": int(model["num_hidden_layers"]),
        "D": int(model["hidden_size"]),
        "H": int(model["num_attention_heads"]),
        "KV": int(model["num_key_value_heads"]),
        "HD": head_dim(model),
        "F": int(model["intermediate_size"]),
        "V": int(model["vocab_size"]),
        "E": n_experts(model),
        "K": int(model.get("num_experts_per_tok", 0)),
    }


def layer_params(model: dict) -> dict:
    """Parameters of ONE layer by group: attention projections, the MLP
    (all experts), the router."""
    d = _dims(model)
    attn = d["D"] * (d["H"] + 2 * d["KV"]) * d["HD"] + d["H"] * d["HD"] * d["D"]
    one_mlp = 3 * d["D"] * d["F"]
    if d["E"] > 1:
        return {"attn": attn, "mlp": d["E"] * one_mlp, "router": d["D"] * d["E"],
                "mlp_active": d["K"] * one_mlp}
    return {"attn": attn, "mlp": one_mlp, "router": 0, "mlp_active": one_mlp}


def weight_bytes(model: dict, engine: dict) -> int:
    """Bytes of weights one decode step must read: every layer's
    projections (all experts are touched by a batch of 32 lanes), the
    router and the output head.  The embedding is a row gather and the
    norms are negligible."""
    d = _dims(model)
    lp = layer_params(model)
    w = 1 if engine["weight_dtype"] == "int8" else 2
    # The experts are served in ``engine.expert_weight_dtype``; absent, in
    # bf16, which is what ops.quant.QUANT_TARGETS leaves them in today.
    mlp_w = w
    if d["E"] > 1:
        mlp_w = DTYPE_BYTES[engine.get("expert_weight_dtype", "bfloat16")]
    per_layer = lp["attn"] * w + lp["mlp"] * mlp_w + lp["router"] * 2
    return d["L"] * per_layer + d["D"] * d["V"] * w


def kv_bytes_per_token(model: dict, engine: dict) -> float:
    """K and V of one token over all layers; int8 carries one bf16 scale
    per token and head."""
    d = _dims(model)
    if engine["kv_dtype"] == "int8":
        per_head = d["HD"] + 2
    else:
        per_head = 2 * d["HD"]
    return 2.0 * d["L"] * d["KV"] * per_head


def decode_step_bytes(model: dict, engine: dict, live_kv_tokens: float) -> float:
    """One decode step over the batch: the weights once, and the K/V of
    every live token."""
    return weight_bytes(model, engine) + live_kv_tokens * kv_bytes_per_token(
        model, engine
    )


def active_params(model: dict) -> int:
    """Parameters one token multiplies: attention, the experts it uses,
    the router.  Without embedding and head (the head runs once per
    prompt, not per token)."""
    d = _dims(model)
    lp = layer_params(model)
    return d["L"] * (lp["attn"] + lp["mlp_active"] + lp["router"])


def prefill_flops(model: dict, new_tokens: float, attn_pairs: float) -> float:
    """Operations to prefill ``new_tokens`` tokens: 2 per active
    parameter and token, and 4 x heads x head_dim per layer for every
    (query, visible key) pair — QK^T and PV.  ``attn_pairs`` is the sum
    over prefilled tokens of their position + 1 (causal)."""
    d = _dims(model)
    return 2.0 * active_params(model) * new_tokens + (
        4.0 * d["L"] * d["H"] * d["HD"] * attn_pairs
    )


def causal_pairs(start: int, end: int) -> float:
    """Sum of (position + 1) for positions in [start, end)."""
    return (end * (end + 1) - start * (start + 1)) / 2.0
