"""The llama-shaped architecture: RMSNorm, rotary GQA attention, a dense
SwiGLU MLP or Mixtral's top-k of E experts, untied head.  The default
for a configuration whose file names no ``"arch"``.

An architecture module is ``arch/<name>.py``, named by the ``"arch"`` key
of a configuration's file.  ``run.py`` and the roofline readers take
four functions from it and know nothing else about the model:

``llama_config(model, engine)``   the configuration file's top level (the
    public config.json keys, with the rehearsal's sizes laid over them
    under ``--rehearse``) and its engine block -> the program's model
    configuration, as ``Scheduler`` takes it
``last_logits(params, cfg, tokens, pad_to)``   the plain float32
    reference: logits at the last position of one prompt, from the
    served parameters
``decode_step_bytes(model, engine, live_kv_tokens)``   bytes one decode
    step must read (``decode_hbm_pct``'s numerator)
``prefill_flops(model, new_tokens, attn_pairs)``   operations a prefill
    needs (``prefill_mxu_pct``'s numerator)

This module's reference is ``reference.py`` and its counts are
``model_math.py``, beside ``run.py``.
"""

from __future__ import annotations

from model_math import decode_step_bytes, head_dim, n_experts, prefill_flops  # noqa: F401
from reference import last_logits  # noqa: F401


def llama_config(model: dict, engine: dict):
    """The public config.json keys -> the program's ``LlamaConfig``."""
    from generativeaiexamples_tpu.models.llama import LlamaConfig

    experts = n_experts(model)
    return LlamaConfig(
        vocab_size=int(model["vocab_size"]),
        d_model=int(model["hidden_size"]),
        n_layers=int(model["num_hidden_layers"]),
        n_heads=int(model["num_attention_heads"]),
        n_kv_heads=int(model["num_key_value_heads"]),
        head_dim=head_dim(model),
        d_ff=int(model["intermediate_size"]),
        rope_theta=float(model["rope_theta"]),
        norm_eps=float(model["rms_norm_eps"]),
        max_seq_len=int(engine["max_len"]),
        dtype=str(model.get("torch_dtype", "bfloat16")),
        kv_dtype=str(engine["kv_dtype"]),
        n_experts=experts,
        n_experts_per_tok=int(model.get("num_experts_per_tok", 2)),
        # Serving routes droplessly, as main() sets it for every MoE preset.
        moe_dropless=experts > 1,
        hidden_act=str(model.get("hidden_act", "silu")),
    )
