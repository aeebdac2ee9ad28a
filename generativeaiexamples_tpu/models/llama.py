"""Llama-3 model family as pure-functional JAX.

TPU-native replacement for the LLM the reference serves through NIM /
TensorRT-LLM engines (``deploy/compose/docker-compose-nim-ms.yaml:2-22``,
SURVEY.md §2.8).  Design points:

* **Pure functions over pytrees** — params are nested dicts of arrays; the
  forward pass is jittable and differentiable with no framework state.
* **scan over stacked layers** — per-layer weights carry a leading
  ``n_layers`` axis and the transformer body is one ``lax.scan``, which
  keeps compile time flat in depth and lets XLA pipeline the layer loop.
  A stack of IDENTICAL llama-shaped layers belongs here whatever is done
  with it (Ouro applies it ``ut_steps`` times to a token: one more loop
  around the scan); a stack of differing kinds is ``models/hybrid.py``'s.
* **Declarative sharding** — every param leaf declares logical axes
  (``embed``, ``heads``, ``mlp``, ...) which ``parallel.mesh`` maps to mesh
  axes (tensor parallelism over ICI, fsdp for training).
* **Unified prefill/decode** — one forward handles both: tokens are written
  into an identity-positioned KV cache at their absolute positions and
  masked by a per-sequence valid length (see ``ops.attention``).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Mapping, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from generativeaiexamples_tpu.ops import moe
from generativeaiexamples_tpu.ops.attention import attention
from generativeaiexamples_tpu.ops.dispatch import record
from generativeaiexamples_tpu.ops.quant import q_dot
from generativeaiexamples_tpu.ops.rope import apply_rope
from generativeaiexamples_tpu.parallel.mesh import logical_to_partition

Params = Any  # nested dict pytree of jnp arrays


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 128256
    d_model: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 8
    head_dim: int = 128
    d_ff: int = 14336
    rope_theta: float = 500000.0
    norm_eps: float = 1e-5
    max_seq_len: int = 8192
    dtype: str = "bfloat16"
    # KV-cache storage: "bfloat16" or "int8" (per-token-per-head symmetric
    # scales).  int8 halves both cache HBM footprint and decode attention
    # traffic — it is what lets llama3-8b serve batch 128 on one 16 GB chip.
    kv_dtype: str = "bfloat16"
    # Mixture-of-experts MLP (Mixtral-class geometry): 0 = dense.  Experts
    # shard over the "expert" mesh axis; routing is top-k with GShard-style
    # capacity-dropping einsum dispatch (see _moe_mlp).
    n_experts: int = 0
    n_experts_per_tok: int = 2
    expert_capacity_factor: float = 1.25
    # Dropless MoE: per-expert capacity = full group length, so no token
    # is ever dropped.  Matches HF Mixtral inference semantics exactly
    # (its dispatch is a ragged gather with no capacity), at the cost of
    # E× larger dispatch buffers — the serving presets turn this on;
    # training keeps capacity-factor dropping (the standard GShard
    # efficiency tradeoff).
    moe_dropless: bool = False
    # When True, gradient checkpointing (remat) wraps each layer in training.
    remat: bool = True
    # Gemma-family architectural knobs (llama defaults off):
    # MLP activation — "silu" (llama/mixtral) or "gelu_tanh"
    # (gemma/starcoder2's gelu_pytorch_tanh).
    hidden_act: str = "silu"
    # Multiply token embeddings by sqrt(d_model) (gemma).
    scale_embeddings: bool = False
    # RMSNorm scales by (1 + g) — gemma stores gains zero-centered.
    norm_unit_offset: bool = False
    # GPT-family knobs (starcoder2):
    # "rmsnorm" (llama/gemma) or "layernorm" (mean-centered, with bias).
    norm_type: str = "rmsnorm"
    # Biases on the attention and MLP projections.
    proj_bias: bool = False
    # Gated (SwiGLU-style) MLP vs plain up->act->down (starcoder2 c_fc/
    # c_proj).
    mlp_gated: bool = True
    # Looped-stack knobs (Ouro, arXiv:2510.25741): the whole stack of
    # ``n_layers`` is applied ``ut_steps`` times to every token with the
    # same weights, the final norm after each pass and its output carried
    # into the next.  Every (pass, layer) keeps K/V of its own.
    ut_steps: int = 1
    # Each sub-layer's OUTPUT is normed before the residual add
    # (``attn_post_norm`` / ``mlp_post_norm``), beside the norm on its input.
    sandwich_norm: bool = False
    # The exit gate's threshold: the first pass at which the exit
    # distribution's cumulative mass reaches it gives the logits.  At 1
    # (Ouro's published value) that is the last pass for every token, and
    # the only value served (``LlamaServing.check_supported``).
    early_exit_threshold: float = 1.0

    @property
    def compute_dtype(self):
        return jnp.dtype(self.dtype)

    @property
    def cache_planes(self) -> int:
        """K/V planes a token holds: one a (pass, layer)."""
        return self.n_layers * self.ut_steps

    @property
    def act_fn(self):
        if self.hidden_act == "silu":
            return jax.nn.silu
        if self.hidden_act == "gelu_tanh":
            return lambda x: jax.nn.gelu(x, approximate=True)
        raise ValueError(f"unknown hidden_act {self.hidden_act!r}")


def llama3_8b(**overrides) -> LlamaConfig:
    """meta-llama/Meta-Llama-3-8B(-Instruct) geometry."""
    return dataclasses.replace(LlamaConfig(), **overrides)


def llama3_70b(**overrides) -> LlamaConfig:
    """meta-llama/Meta-Llama-3-70B(-Instruct) geometry."""
    return dataclasses.replace(
        LlamaConfig(
            d_model=8192,
            n_layers=80,
            n_heads=64,
            n_kv_heads=8,
            head_dim=128,
            d_ff=28672,
        ),
        **overrides,
    )


def llama32_1b(**overrides) -> LlamaConfig:
    """meta-llama/Llama-3.2-1B(-Instruct) geometry (the llama3
    vocabulary, 128256)."""
    return dataclasses.replace(
        LlamaConfig(
            d_model=2048,
            n_layers=16,
            n_heads=32,
            n_kv_heads=8,
            head_dim=64,
            d_ff=8192,
            max_seq_len=8192,
        ),
        **overrides,
    )


def llama_tiny(**overrides) -> LlamaConfig:
    """Tiny geometry for hermetic CPU tests and byte-level serving."""
    return dataclasses.replace(
        LlamaConfig(
            vocab_size=512,
            d_model=64,
            n_layers=2,
            n_heads=4,
            n_kv_heads=2,
            head_dim=16,
            d_ff=128,
            max_seq_len=512,
            rope_theta=10000.0,
        ),
        **overrides,
    )


def mixtral_8x7b(**overrides) -> LlamaConfig:
    """mistralai/Mixtral-8x7B geometry: llama-shaped with 8-expert MoE MLPs."""
    return dataclasses.replace(
        LlamaConfig(
            vocab_size=32000,
            d_model=4096,
            n_layers=32,
            n_heads=32,
            n_kv_heads=8,
            head_dim=128,
            d_ff=14336,
            rope_theta=1e6,
            n_experts=8,
            n_experts_per_tok=2,
            # Inference parity: HF Mixtral routes droplessly, so every
            # serving consumer of this preset (engine server, chains,
            # generators) must too or decode diverges token-for-token.
            # The training path overrides this to capacity-factor
            # dispatch (engine/training.py) to keep dispatch tensors
            # bounded.
            moe_dropless=True,
        ),
        **overrides,
    )


def llama_moe_tiny(**overrides) -> LlamaConfig:
    """Tiny MoE geometry for hermetic expert-parallel tests."""
    defaults = {"n_experts": 4, "n_experts_per_tok": 2}
    return dataclasses.replace(llama_tiny(), **{**defaults, **overrides})


_GEMMA_ARCH = {
    "hidden_act": "gelu_tanh",
    "scale_embeddings": True,
    "norm_unit_offset": True,
    "rope_theta": 10000.0,
    "norm_eps": 1e-6,
}


def gemma_2b(**overrides) -> LlamaConfig:
    """google/gemma-2b(-it) geometry: MQA (1 KV head), gelu_tanh MLP,
    sqrt(d_model)-scaled embeddings, (1+g) RMSNorm, tied LM head
    (reference customization recipes: ``models/Gemma/lora.ipynb``)."""
    return dataclasses.replace(
        LlamaConfig(
            vocab_size=256000,
            d_model=2048,
            n_layers=18,
            n_heads=8,
            n_kv_heads=1,
            head_dim=256,
            d_ff=16384,
            **_GEMMA_ARCH,
        ),
        **overrides,
    )


def gemma_7b(**overrides) -> LlamaConfig:
    """google/gemma-7b(-it) geometry (same architecture family)."""
    return dataclasses.replace(
        LlamaConfig(
            vocab_size=256000,
            d_model=3072,
            n_layers=28,
            n_heads=16,
            n_kv_heads=16,
            head_dim=256,
            d_ff=24576,
            **_GEMMA_ARCH,
        ),
        **overrides,
    )


def gemma_tiny(**overrides) -> LlamaConfig:
    """Tiny gemma-architecture geometry for hermetic CPU tests."""
    return gemma_2b(
        **{
            **dict(
                vocab_size=512,
                d_model=64,
                n_layers=2,
                n_heads=4,
                n_kv_heads=1,
                head_dim=16,
                d_ff=128,
                max_seq_len=512,
            ),
            **overrides,
        }
    )


_STARCODER2_ARCH = {
    "hidden_act": "gelu_tanh",
    "norm_type": "layernorm",
    "proj_bias": True,
    "mlp_gated": False,
    "norm_eps": 1e-5,
}


def starcoder2_3b(**overrides) -> LlamaConfig:
    """bigcode/starcoder2-3b geometry: GPT-style LayerNorm + biases,
    plain c_fc/c_proj MLP, GQA, rope, tied LM head (reference
    customization recipes: ``models/StarCoder2/lora.ipynb``).

    ``rope_theta`` follows the published checkpoint config; override per
    checkpoint when loading other family members (sliding-window
    attention is a no-op at contexts <= 4096 and is not modeled).
    """
    return dataclasses.replace(
        LlamaConfig(
            vocab_size=49152,
            d_model=3072,
            n_layers=30,
            n_heads=24,
            n_kv_heads=2,
            head_dim=128,
            d_ff=12288,
            max_seq_len=4096,
            rope_theta=999999.4342952444,
            **_STARCODER2_ARCH,
        ),
        **overrides,
    )


def starcoder2_tiny(**overrides) -> LlamaConfig:
    """Tiny starcoder2-architecture geometry for hermetic CPU tests."""
    return starcoder2_3b(
        **{
            **dict(
                vocab_size=512,
                d_model=64,
                n_layers=2,
                n_heads=4,
                n_kv_heads=2,
                head_dim=16,
                d_ff=128,
                max_seq_len=512,
                rope_theta=10000.0,
            ),
            **overrides,
        }
    )


def ouro_2_6b(**overrides) -> LlamaConfig:
    """ByteDance/Ouro-2.6B geometry: 48 llama-shaped layers (16 heads of
    128, no grouping, SwiGLU of 5,632) that every token passes four times
    over the same weights, sandwich norms, an untied head."""
    return dataclasses.replace(
        LlamaConfig(
            vocab_size=49152,
            d_model=2048,
            n_layers=48,
            n_heads=16,
            n_kv_heads=16,
            head_dim=128,
            d_ff=5632,
            rope_theta=1e6,
            norm_eps=1e-6,
            max_seq_len=65536,
            ut_steps=4,
            sandwich_norm=True,
        ),
        **overrides,
    )


def ouro_tiny(**overrides) -> LlamaConfig:
    """Tiny looped geometry (two layers, four passes) for hermetic CPU
    tests and byte-level serving."""
    defaults = {"ut_steps": 4, "sandwich_norm": True, "norm_eps": 1e-6}
    return dataclasses.replace(llama_tiny(), **{**defaults, **overrides})


PRESETS = {
    "llama3-8b": llama3_8b,
    "llama3-70b": llama3_70b,
    "llama3.2-1b": llama32_1b,
    "llama-tiny": llama_tiny,
    "mixtral-8x7b": mixtral_8x7b,
    "llama-moe-tiny": llama_moe_tiny,
    "gemma-2b": gemma_2b,
    "gemma-7b": gemma_7b,
    "gemma-tiny": gemma_tiny,
    "starcoder2-3b": starcoder2_3b,
    "starcoder2-tiny": starcoder2_tiny,
    "ouro-2.6b": ouro_2_6b,
    "ouro-tiny": ouro_tiny,
}


def param_axes(cfg: LlamaConfig) -> dict:
    """Pytree with (shape, logical_axes) leaves describing every parameter."""
    L, D, H, KV, HD, F, V = (
        cfg.n_layers,
        cfg.d_model,
        cfg.n_heads,
        cfg.n_kv_heads,
        cfg.head_dim,
        cfg.d_ff,
        cfg.vocab_size,
    )
    if cfg.n_experts > 1:
        E = cfg.n_experts
        mlp = {
            "router": ((L, D, E), ("layers", "embed", None)),
            "w_gate_e": ((L, E, D, F), ("layers", "expert", "embed", "mlp")),
            "w_up_e": ((L, E, D, F), ("layers", "expert", "embed", "mlp")),
            "w_down_e": ((L, E, F, D), ("layers", "expert", "mlp", "embed")),
        }
    elif cfg.mlp_gated:
        mlp = {
            "w_gate": ((L, D, F), ("layers", "embed", "mlp")),
            "w_up": ((L, D, F), ("layers", "embed", "mlp")),
            "w_down": ((L, F, D), ("layers", "mlp", "embed")),
        }
    else:  # plain up -> act -> down (starcoder2 c_fc/c_proj)
        mlp = {
            "w_up": ((L, D, F), ("layers", "embed", "mlp")),
            "w_down": ((L, F, D), ("layers", "mlp", "embed")),
        }
    layers = {
        "attn_norm": ((L, D), ("layers", "embed")),
        "wq": ((L, D, H * HD), ("layers", "embed", "heads")),
        "wk": ((L, D, KV * HD), ("layers", "embed", "kv_heads")),
        "wv": ((L, D, KV * HD), ("layers", "embed", "kv_heads")),
        "wo": ((L, H * HD, D), ("layers", "heads", "embed")),
        "mlp_norm": ((L, D), ("layers", "embed")),
        **mlp,
    }
    if cfg.proj_bias:
        layers.update(
            {
                "bq": ((L, H * HD), ("layers", "heads")),
                "bk": ((L, KV * HD), ("layers", "kv_heads")),
                "bv": ((L, KV * HD), ("layers", "kv_heads")),
                "bo": ((L, D), ("layers", "embed")),
                "b_up": ((L, F), ("layers", "mlp")),
                "b_down": ((L, D), ("layers", "embed")),
            }
        )
        if cfg.mlp_gated and cfg.n_experts <= 1:
            layers["b_gate"] = ((L, F), ("layers", "mlp"))
    if cfg.norm_type == "layernorm":
        layers["attn_norm_b"] = ((L, D), ("layers", "embed"))
        layers["mlp_norm_b"] = ((L, D), ("layers", "embed"))
    if cfg.sandwich_norm:
        if cfg.norm_type != "rmsnorm":
            raise ValueError("sandwich norms are RMSNorms (norm_type 'rmsnorm')")
        layers["attn_post_norm"] = ((L, D), ("layers", "embed"))
        layers["mlp_post_norm"] = ((L, D), ("layers", "embed"))
    out = {
        "embed": ((V, D), ("vocab", "embed")),
        "layers": layers,
        "final_norm": ((D,), ("embed",)),
        "lm_head": ((D, V), ("embed", "vocab")),
    }
    if cfg.norm_type == "layernorm":
        out["final_norm_b"] = ((D,), ("embed",))
    if cfg.ut_steps > 1:
        # The exit gate after each pass: a checkpoint has it; unused at
        # ``early_exit_threshold`` 1, the only value served.
        out["exit_gate"] = {"w": ((D,), ("embed",)), "b": ((), ())}
    return out


def _is_leaf(x: Any) -> bool:
    return isinstance(x, tuple) and len(x) == 2 and isinstance(x[0], tuple)


def partition_specs(
    cfg: LlamaConfig, rules: Optional[Mapping[str, Optional[str]]] = None
) -> dict:
    """Pytree of PartitionSpec matching :func:`init_params`'s structure."""
    return jax.tree.map(
        lambda leaf: logical_to_partition(leaf[1], rules),
        param_axes(cfg),
        is_leaf=_is_leaf,
    )


def abstract_params(cfg: LlamaConfig) -> dict:
    return jax.tree.map(
        lambda leaf: jax.ShapeDtypeStruct(leaf[0], cfg.compute_dtype),
        param_axes(cfg),
        is_leaf=_is_leaf,
    )


def init_params(cfg: LlamaConfig, key: jax.Array) -> Params:
    """Random-normal initialization (0.02 std), norms at 1."""
    axes = param_axes(cfg)
    flat, treedef = jax.tree.flatten(axes, is_leaf=_is_leaf)
    keys = jax.random.split(key, len(flat))
    leaves = [
        (jax.random.normal(k, shape, jnp.float32) * 0.02).astype(cfg.compute_dtype)
        for (shape, _), k in zip(flat, keys)
    ]
    params = jax.tree.unflatten(treedef, leaves)
    # Norm gains start at one; biases (norm + projection) at zero.
    for name in ("attn_norm", "mlp_norm", "attn_post_norm", "mlp_post_norm"):
        if name in params["layers"]:
            params["layers"][name] = jnp.ones_like(params["layers"][name])
    params["final_norm"] = jnp.ones_like(params["final_norm"])
    for name in ("bq", "bk", "bv", "bo", "b_gate", "b_up", "b_down",
                 "attn_norm_b", "mlp_norm_b"):
        if name in params["layers"]:
            params["layers"][name] = jnp.zeros_like(params["layers"][name])
    if "final_norm_b" in params:
        params["final_norm_b"] = jnp.zeros_like(params["final_norm_b"])
    return params


def pack_for_serving(params: Params) -> Params:
    """Fuse per-layer projections for the single-chip decode hot path.

    ``wq|wk|wv -> wqkv`` and ``w_gate|w_up -> w_gu`` (concatenated on the
    output axis).  Two reasons, both measured on v5e: fewer kernels means
    fewer serialization points in the layer's dependency chain, and XLA
    streams one wide weight at higher HBM bandwidth than three narrow ones
    issued back-to-back.  Works on raw arrays and on
    :class:`~generativeaiexamples_tpu.ops.quant.QuantizedMatrix` leaves
    (both q and scale concatenate on the output axis).

    Packing crosses head boundaries on the output axis, so it is only valid
    when that axis is unsharded — i.e. single-chip serving or meshes with
    ``tensor == 1``.  Tensor-parallel serving keeps the unpacked layout.
    """
    from generativeaiexamples_tpu.ops.quant import QuantizedMatrix

    def cat(*ms):
        if isinstance(ms[0], QuantizedMatrix):
            return QuantizedMatrix(
                q=jnp.concatenate([m.q for m in ms], axis=-1),
                scale=jnp.concatenate([m.scale for m in ms], axis=-1),
            )
        return jnp.concatenate(ms, axis=-1)

    layers = dict(params["layers"])
    if "bq" in layers:
        # Biased projections (starcoder2 family) stay unpacked: the
        # packed branches in forward() don't add biases.
        return params
    if "wqkv" in layers:
        # Already packed: idempotent no-op.
        return params
    layers["wqkv"] = cat(layers.pop("wq"), layers.pop("wk"), layers.pop("wv"))
    if "w_gate" in layers:  # dense MLP only; MoE experts stay unpacked
        layers["w_gu"] = cat(layers.pop("w_gate"), layers.pop("w_up"))
    return {**params, "layers": layers}


def rms_norm(
    x: jnp.ndarray, gain: jnp.ndarray, eps: float, unit_offset: bool = False
) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    scale = jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    if unit_offset:
        # Gemma convention: zero-centered gains, and the WHOLE product in
        # f32 with one final cast — "Llama does x.to(f16) * w whilst
        # Gemma is (x * w).to(f16)" (HF GemmaRMSNorm).  Downcasting
        # before the gain multiply rounds (1+g) to the param dtype and
        # loses most of g's mantissa (|g| << 1), drifting bf16 serving
        # from HF over depth.
        return (
            (xf * scale) * (1.0 + gain.astype(jnp.float32))
        ).astype(x.dtype)
    return (xf * scale).astype(x.dtype) * gain


def _affine_layer_norm(
    x: jnp.ndarray, gain: jnp.ndarray, bias: jnp.ndarray, eps: float
) -> jnp.ndarray:
    """Mean-centered LayerNorm with bias (GPT/starcoder2 family)."""
    xf = x.astype(jnp.float32)
    mu = xf.mean(axis=-1, keepdims=True)
    var = ((xf - mu) ** 2).mean(axis=-1, keepdims=True)
    out = (xf - mu) * jax.lax.rsqrt(var + eps)
    return out.astype(x.dtype) * gain + bias


def block_norm(x: jnp.ndarray, cfg: LlamaConfig, lp: Mapping, name: str):
    """Per-layer norm dispatch: RMSNorm (llama/gemma) or LayerNorm
    (starcoder2; ``name + "_b"`` holds the bias)."""
    if cfg.norm_type == "layernorm":
        return _affine_layer_norm(x, lp[name], lp[name + "_b"], cfg.norm_eps)
    return rms_norm(x, lp[name], cfg.norm_eps, cfg.norm_unit_offset)


def apply_final_norm(x: jnp.ndarray, cfg: LlamaConfig, params: Params):
    if cfg.norm_type == "layernorm":
        return _affine_layer_norm(
            x, params["final_norm"], params["final_norm_b"], cfg.norm_eps
        )
    return rms_norm(
        x, params["final_norm"], cfg.norm_eps, cfg.norm_unit_offset
    )


def _badd(x: jnp.ndarray, lp: Mapping, name: str) -> jnp.ndarray:
    """Add a projection bias when the param exists (proj_bias configs)."""
    return x + lp[name] if name in lp else x


def _post_norm(out: jnp.ndarray, cfg: LlamaConfig, lp: Mapping, name: str):
    """A sub-layer's output on its way to the residual add: normed where
    the stack has sandwich norms (the leaf exists), as it is elsewhere."""
    if name not in lp:
        return out
    with jax.named_scope("layer/post_norm"):
        return rms_norm(out, lp[name], cfg.norm_eps, cfg.norm_unit_offset)


def init_kv_cache(
    cfg: LlamaConfig, batch: int, max_len: Optional[int] = None
) -> tuple[jnp.ndarray, ...]:
    """KV cache as a tuple of (planes, n_kv_heads, batch, max_len, ...)
    buffers, a plane a layer (a looped stack: a plane a (pass, layer),
    ``cfg.cache_planes``).

    Head-major layout: the Pallas decode kernel
    (``ops.decode_attention``) DMAs per-(head, row-block, kv-block) tiles
    straight out of the stacked cache, which requires the minor-most two
    dims to be (positions, head_dim) — the Mosaic-tileable shape.

    ``kv_dtype="bfloat16"``: ``(k, v)``, each (L, KH, B, T, head_dim).
    ``kv_dtype="int8"``: ``(k8, v8, k_scale, v_scale)`` — int8 values plus
    bf16 per-(token, head) symmetric scales (L, KH, B, T).  bf16 scale
    granularity (~0.4% relative) is far below int8's quantization error and
    halves both the scale buffers' HBM footprint and their per-step scatter
    traffic.
    """
    max_len = max_len or cfg.max_seq_len
    shape = (cfg.cache_planes, cfg.n_kv_heads, batch, max_len, cfg.head_dim)
    # Distinct buffers: the generator donates the cache to each step, and
    # XLA rejects donating one buffer twice.
    if cfg.kv_dtype == "int8":
        return (
            jnp.zeros(shape, jnp.int8),
            jnp.zeros(shape, jnp.int8),
            jnp.zeros(shape[:-1], jnp.bfloat16),
            jnp.zeros(shape[:-1], jnp.bfloat16),
        )
    return jnp.zeros(shape, cfg.compute_dtype), jnp.zeros(shape, cfg.compute_dtype)


def init_append_buffer(cfg: LlamaConfig, batch: int, width: int) -> tuple:
    """A decode chunk's append buffer, empty: the int8
    cache's four leaves with ``width`` slots a row (``forward``:
    ``append_cache``), a plane for every plane of the cache."""
    shape = (cfg.cache_planes, cfg.n_kv_heads, batch, width, cfg.head_dim)
    return (
        jnp.zeros(shape, jnp.int8),
        jnp.zeros(shape, jnp.int8),
        jnp.zeros(shape[:-1], jnp.bfloat16),
        jnp.zeros(shape[:-1], jnp.bfloat16),
    )


def kv_cache_specs(cfg: LlamaConfig, rules=None) -> tuple[P, ...]:
    """One PartitionSpec per cache leaf, matching :func:`init_kv_cache`."""
    spec = logical_to_partition(
        ("layers", "kv_heads", "batch", None, "head_dim"), rules
    )
    if cfg.kv_dtype == "int8":
        scale_spec = logical_to_partition(
            ("layers", "kv_heads", "batch", None), rules
        )
        return spec, spec, scale_spec, scale_spec
    return spec, spec


def embed(params: Params, tokens: jnp.ndarray, dtype) -> jnp.ndarray:
    """Token-embedding lookup; handles the serving int8 table.

    An int8 table (``ops.quant.quantize_embedding``) gathers int8 rows and
    the (V, 1) per-row scales, dequantizing only the gathered rows.
    """
    from generativeaiexamples_tpu.ops.quant import QuantizedMatrix

    table = params["embed"]
    if isinstance(table, QuantizedMatrix):
        rows = jnp.take(table.q, tokens, axis=0).astype(jnp.float32)
        scales = jnp.take(table.scale[:, 0], tokens, axis=0)
        return (rows * scales[..., None]).astype(dtype)
    return jnp.take(table, tokens, axis=0).astype(dtype)


@jax.named_scope("kv_write")
def _quantize_kv(x: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Per-(token, head) symmetric int8: x (b, s, n_kv, hd) -> (q8, scale).

    The quantization arithmetic runs in f32; the stored scale is bf16 to
    match the cache buffers (see :func:`init_kv_cache`).
    """
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1)
    scale = jnp.maximum(amax, 1e-8) / 127.0
    q = jnp.clip(
        jnp.round(x.astype(jnp.float32) / scale[..., None]), -127, 127
    ).astype(jnp.int8)
    return q, scale.astype(jnp.bfloat16)


def _moe_mlp(
    h: jnp.ndarray, lp: Mapping, cfg: LlamaConfig, mesh
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Top-k routed mixture-of-experts MLP (GShard-style einsum dispatch).

    The TPU-native MoE shape: tokens are dispatched into fixed-capacity
    per-expert buffers via one-hot einsums (static shapes — no ragged
    gather), the expert FFN runs batched over a leading expert axis that
    shards over the ``expert`` mesh dimension, and a combine einsum
    weights results back per token.  Tokens beyond an expert's capacity
    are dropped (contribute zero), the standard capacity-factor tradeoff.

    Returns ``(out, aux_loss)`` — aux_loss is the Switch/GShard
    load-balancing term ``E * Σ_e fraction_dispatched_e · mean_prob_e``
    (minimized at uniform routing = 1.0); training adds it scaled by
    ``loss_fn``'s aux weight so routing cannot collapse onto few experts
    and overflow the fixed capacity.
    """
    b_orig, s_orig, d = h.shape
    E, k = cfg.n_experts, cfg.n_experts_per_tok
    # Token-group blocking (canonical GShard): dispatch within fixed-size
    # groups so the one-hot dispatch tensors are O(s · group · k²/E), not
    # O(s²) — without it the (b, s, E, cap) intermediates OOM at real
    # sequence lengths.  Sequences pad up to a group multiple (padded
    # slots are masked out of routing so they never claim capacity);
    # groups fold into the batch dimension and reuse the same dispatch
    # math, with capacity per group.
    group = min(s_orig, 128)
    pad = (-s_orig) % group
    if pad:
        h = jnp.pad(h, ((0, 0), (0, pad), (0, 0)))
    valid = (
        jnp.arange(s_orig + pad) < s_orig
    ).astype(jnp.float32)  # (s_padded,)
    n_groups = (s_orig + pad) // group
    h = h.reshape(b_orig * n_groups, group, d)
    valid = jnp.broadcast_to(
        valid.reshape(n_groups, group)[None], (b_orig, n_groups, group)
    ).reshape(b_orig * n_groups, group)
    b, s = h.shape[:2]
    # A single expert can receive at most s tokens of a group (each
    # (token, expert) pair appears at most once across the k choices).
    if cfg.moe_dropless:
        cap = s
    else:
        cap = max(8, int(cfg.expert_capacity_factor * s * k / E + 0.999))
        cap = min(cap, s)

    with jax.named_scope("layer/moe/router"):
        router_logits = q_dot(h, lp["router"], "router").astype(
            jnp.float32
        )  # (b, s, E)
        probs = jax.nn.softmax(router_logits, axis=-1)
        gate_w, gate_idx = jax.lax.top_k(probs, k)  # (b, s, k)
        gate_w = gate_w / jnp.maximum(gate_w.sum(-1, keepdims=True), 1e-9)

        onehot = jax.nn.one_hot(gate_idx, E, dtype=jnp.float32)  # (b, s, k, E)
        onehot = onehot * valid[:, :, None, None]  # pads never claim capacity
        # Load-balancing aux: fraction of routed choices per expert × mean
        # router probability per expert (valid tokens only), scaled so uniform
        # routing gives 1.
        valid_row = jnp.maximum(valid.sum(axis=1), 1.0)  # (b,)
        frac = onehot.sum(axis=(1, 2)) / (valid_row * k)[:, None]  # (b, E)
        mean_prob = (probs * valid[:, :, None]).sum(axis=1) / valid_row[:, None]
        aux_loss = (E * (frac * mean_prob).sum(-1)).mean()

        flat = onehot.reshape(b, s * k, E)
        # Position of each (token, choice) within its expert's buffer: count of
        # earlier assignments to the same expert.
        pos = jnp.einsum(
            "bte,bte->bt", jnp.cumsum(flat, axis=1) - flat, flat
        ).astype(jnp.int32)
        keep = (pos < cap).astype(jnp.float32)
        pos_oh = jax.nn.one_hot(pos, cap, dtype=jnp.float32) * keep[..., None]
        # dispatch[b, t, e, c] = 1 iff choice t routes to expert e at slot c;
        # summing out the choice axis is lossless (pairs are unique) and
        # yields the canonical (b, s, E, cap) GShard tensors.
        disp_k = (flat[:, :, :, None] * pos_oh[:, :, None, :]).reshape(
            b, s, k, E, cap
        )
        combine = (disp_k * gate_w[..., None, None]).sum(axis=2).astype(h.dtype)
        disp = disp_k.sum(axis=2).astype(h.dtype)  # (b, s, E, cap)

    with jax.named_scope("layer/moe/experts"):
        x_e = jnp.einsum("bsec,bsd->becd", disp, h)  # (b, E, cap, d)
        if mesh is not None:
            from jax.sharding import NamedSharding

            x_e = jax.lax.with_sharding_constraint(
                x_e, NamedSharding(mesh, P("data", "expert", None, None))
            )
        gated = jax.nn.silu(
            jnp.einsum("becd,edf->becf", x_e, lp["w_gate_e"],
                       preferred_element_type=jnp.float32).astype(h.dtype)
        ) * jnp.einsum("becd,edf->becf", x_e, lp["w_up_e"],
                       preferred_element_type=jnp.float32).astype(h.dtype)
        y = jnp.einsum("becf,efd->becd", gated, lp["w_down_e"],
                       preferred_element_type=jnp.float32).astype(h.dtype)
        out = jnp.einsum("bsec,becd->bsd", combine, y)
        out = out.reshape(b_orig, s_orig + pad, d)
    return out[:, :s_orig], aux_loss


# The expert leaves of a layer's parameters, stacked (L, E, ...) like the rest.
EXPERT_LEAVES = ("w_gate_e", "w_up_e", "w_down_e")


def _moe_mlp_sorted(
    h: jnp.ndarray, lp: Mapping, experts: Mapping, li, valid: jnp.ndarray,
    cfg: LlamaConfig, mesh,
) -> jnp.ndarray:
    """The expert MLP of a serving prefill chunk: ``_moe_mlp``'s router,
    then the choices ordered by expert and the three products over the
    rows each expert received (``ops.moe.stacked_expert_mlp``: grouped
    products, dropless by construction), so a chunk multiplies its ``k``
    choices a token and not every expert's capacity, and the rows of
    several chunks share one pass over the experts.

    ``experts`` holds ``EXPERT_LEAVES`` of ALL layers viewed as (L*E, ...):
    the layer loop hands a grouped product the whole stack and the product
    reads layer ``li``'s E groups, because a slice of the stack handed to
    a kernel would be copied first, 2.8 GB a layer at Mixtral's widths.
    ``valid`` (b, s): a position that does not count routes nowhere.
    h: (b, s, d)."""
    b, s, d = h.shape
    E, k = cfg.n_experts, cfg.n_experts_per_tok
    with jax.named_scope("layer/moe/router"):
        router_logits = q_dot(h, lp["router"], "router").astype(jnp.float32)
        probs = jax.nn.softmax(router_logits, axis=-1)
        gate_w, gate_idx = jax.lax.top_k(probs, k)  # (b, s, k)
        gate_w = gate_w / jnp.maximum(gate_w.sum(-1, keepdims=True), 1e-9)
    out = moe.stacked_expert_mlp(
        h.reshape(b * s, d),
        gate_idx.astype(jnp.int32).reshape(b * s, k),
        gate_w.reshape(b * s, k),
        valid.reshape(b * s),
        experts,
        first=li * E,
        n_experts=E,
        mesh=mesh,
    )
    return out.reshape(b, s, d)


def dense_layer(
    x: jnp.ndarray,
    lp: Mapping,
    cfg: LlamaConfig,
    positions: jnp.ndarray,
    kv_lengths: Optional[jnp.ndarray] = None,
    mesh=None,
    tp_axis: Optional[str] = None,
) -> jnp.ndarray:
    """One cacheless dense transformer layer (unpacked wq/wk/wv weights).

    The shared layer body for :func:`forward`'s plain training path and the
    pipeline-parallel runtime (``parallel.pipeline``), which applies it to
    its local layer shard inside ``shard_map`` — keeping one definition of
    the layer math so the two cannot drift.

    ``tp_axis`` — Megatron-style tensor parallelism inside a
    ``shard_map`` body: ``lp`` holds this device's HEAD/MLP shards
    (wq/wk/wv/w_gate/w_up column-sharded, wo/w_down row-sharded over the
    named mesh axis), attention runs over the local heads, and one
    ``psum`` after each of wo and w_down restores the full residual —
    the standard two-collectives-per-layer TP schedule.  Projection
    biases would be added once per shard; the presets that carry them
    (starcoder2) are rejected rather than silently multiplied.
    """
    b, s = x.shape[:2]
    n_q, n_kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    if tp_axis is not None:
        if cfg.proj_bias:
            raise NotImplementedError(
                "tensor-parallel dense_layer with projection biases"
            )
        tp = jax.lax.axis_size(tp_axis)
        if n_q % tp or n_kv % tp:
            raise ValueError(
                f"heads ({n_q} q / {n_kv} kv) not divisible by tp={tp}"
            )
        n_q //= tp
        n_kv //= tp
    with jax.named_scope("layer/norm"):
        h = block_norm(x, cfg, lp, "attn_norm")
    with jax.named_scope("layer/qkv"):
        q = _badd(q_dot(h, lp["wq"], "wq"), lp, "bq").reshape(b, s, n_q, hd)
        k = _badd(q_dot(h, lp["wk"], "wk"), lp, "bk").reshape(b, s, n_kv, hd)
        v = _badd(q_dot(h, lp["wv"], "wv"), lp, "bv").reshape(b, s, n_kv, hd)
    with jax.named_scope("layer/rope"):
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    with jax.named_scope("layer/attn"):
        attn = attention(q, k, v, positions, kv_lengths, mesh=mesh)
    with jax.named_scope("layer/wo"):
        attn_out = _badd(
            q_dot(attn.reshape(b, s, n_q * hd), lp["wo"], "wo"), lp, "bo"
        )
        if tp_axis is not None:
            attn_out = jax.lax.psum(attn_out, tp_axis)
        attn_out = _post_norm(attn_out, cfg, lp, "attn_post_norm")
        x = _shard_activations(x + attn_out, mesh)
    with jax.named_scope("layer/norm"):
        h = block_norm(x, cfg, lp, "mlp_norm")
    with jax.named_scope("layer/mlp"):
        if "w_gate" in lp:
            gated = cfg.act_fn(
                _badd(q_dot(h, lp["w_gate"], "w_gate"), lp, "b_gate")
            ) * _badd(q_dot(h, lp["w_up"], "w_up"), lp, "b_up")
        else:  # plain MLP: up -> act -> down
            gated = cfg.act_fn(
                _badd(q_dot(h, lp["w_up"], "w_up"), lp, "b_up")
            )
        mlp_out = _badd(q_dot(gated, lp["w_down"], "w_down"), lp, "b_down")
        if tp_axis is not None:
            mlp_out = jax.lax.psum(mlp_out, tp_axis)
        mlp_out = _post_norm(mlp_out, cfg, lp, "mlp_post_norm")
        return _shard_activations(x + mlp_out, mesh)


def _shard_activations(x: jnp.ndarray, mesh) -> jnp.ndarray:
    """Pin activations to batch-over-data sharding when a mesh is given."""
    if mesh is not None:
        from jax.sharding import NamedSharding

        return jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, P("data", None, None))
        )
    return x


def forward(
    params: Params,
    cfg: LlamaConfig,
    tokens: jnp.ndarray,
    positions: jnp.ndarray,
    cache: Optional[tuple[jnp.ndarray, jnp.ndarray]] = None,
    kv_lengths: Optional[jnp.ndarray] = None,
    *,
    mesh=None,
    remat: bool = False,
    embeds: Optional[jnp.ndarray] = None,
    kv_bucket: Optional[int] = None,
    cold_prefill: bool = False,
    row_offset=0,
    return_aux: bool = False,
    append_cache: Optional[tuple] = None,
    chunk_valid: Optional[jnp.ndarray] = None,
):
    """Run the transformer body.

    Two modes:
      * ``cache=None`` — cacheless causal self-attention over ``tokens``
        (training / scoring). ``kv_lengths`` optionally masks padding.
      * ``cache=(k, v)`` — serving: new k/v are scattered into the cache at
        ``positions`` and attention runs over the whole cache prefix
        (prefill when s > 1, decode when s == 1).  ``kv_bucket`` (static)
        restricts attention to the first ``kv_bucket`` cache slots — the
        caller guarantees every position written so far is below it, and
        the decode loop grows it in power-of-two steps so attention traffic
        tracks the live sequence length instead of always reading max_len.
        ``cold_prefill`` asserts (a) the cache holds nothing visible to
        these queries and (b) ``positions`` is ``arange(s)`` for every row.
        It lets the int8-KV mode attend over the fresh bf16 k/v (exact)
        instead of reading back the quantized cache, and lowers the cache
        write to a contiguous ``dynamic_update_slice`` instead of a
        scatter; warm multi-token calls must leave it False.
        ``row_offset`` (traced scalar ok) places the written rows at cache
        rows ``[row_offset, row_offset + b)`` — the hook for sub-batched
        prefill over a larger slot cache.

    Returns (hidden_states (b, s, d_model), new_cache_or_None).  Project to
    logits separately via :func:`logits` so serving can project only the
    positions it needs.

    ``embeds`` (b, s, d_model) overrides the token-embedding lookup — the
    hook multimodal models use to prepend projected image features (the
    Neva/DePlot-class VLM bridge in ``models.vision``).

    ``append_cache`` — the serving decode chunk's append-buffer protocol
    (int8 KV, one chip): ``(ab, step)`` where ``ab`` is
    a 4-tuple of (L, KH, B, C, HD) int8 values / (L, KH, B, C) bf16
    scales and ``step`` the chunk-step index.  The fresh token's KV is
    written to ab slot ``step`` of every row, by the decode kernel itself
    where it runs (the leaves go through the Mosaic call aliased, and no
    XLA operation of the layer loop touches them), by a contiguous
    dynamic_update_slice in its XLA twin, and attention runs over the big
    cache's [0, kv_lengths) prefix PLUS ab slots [0, step] — the big
    cache is never written, which keeps the decode executable free of
    the per-token scatter whose preferred layout conflicts with the
    kernel's (measured: 5 GB of entry copies).
    The caller flushes ab into the big cache once per chunk.  Returns
    ``(hidden, cache, ab)`` in this mode.

    ``chunk_valid`` (b, s) bool — the prefill chunks of several slots as
    one program (``LlamaServing.prefill_rows``): which positions count.
    Given, a model with experts dispatches them sorted by expert
    (:func:`_moe_mlp_sorted`) and not one-hot, and a position that does
    not count routes to no expert; a dense model takes no notice.

    A looped stack (``cfg.ut_steps`` > 1) runs the scan over the layers
    that many times in every mode, as one loop around it: the final norm
    follows each pass and its output is the next pass's input, and the
    plane index beside the residual stream goes on counting, so pass ``u``,
    layer ``l`` writes and reads plane ``u * n_layers + l`` of ``cache``
    and of the append buffer and no other pass's.  The hidden states
    returned are the last pass's (``early_exit_threshold`` 1).
    """
    b, s = tokens.shape
    with jax.named_scope("embed"):
        if embeds is not None:
            x = embeds.astype(cfg.compute_dtype)
        else:
            x = embed(params, tokens, cfg.compute_dtype)
        if cfg.scale_embeddings:
            # Gemma: inputs scale by sqrt(d_model) in the activation dtype
            # (HF applies the normalizer to inputs_embeds from any source).
            x = x * jnp.asarray(cfg.d_model**0.5, x.dtype)
        x = _shard_activations(x, mesh)

    n_q, n_kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    t = cache[0].shape[3] if cache is not None else 0
    window = t if kv_bucket is None else min(kv_bucket, t)
    kv_int8 = cache is not None and len(cache) == 4
    if append_cache is not None:
        from generativeaiexamples_tpu.ops.decode_attention import (
            decode_gqa_attention,
            decode_gqa_attention_xla,
            use_append_buffer,
            use_decode_kernel,
        )

        if not (
            kv_lengths is not None
            and use_append_buffer(
                s=s,
                kv_int8=kv_int8,
                batch=b,
                window=window,
                n_q=n_q,
                n_kv=n_kv,
                head_dim=hd,
                mesh=mesh,
            )
        ):
            raise ValueError(
                "append_cache requires the append-buffer protocol "
                "(int8 KV, single chip, one token a row)"
            )
        # Pallas kernel when eligible, else the XLA twin.  ``kv_lengths``
        # is the valid big-cache prefix — fresh tokens' KV never touches
        # the big cache inside this executable; the caller flushes.
        _append_kernel = use_decode_kernel(
            s=s, kv_int8=kv_int8, batch=b, window=window,
            n_q=n_q, n_kv=n_kv, head_dim=hd, cache_len=t,
            append_width=append_cache[0][0].shape[3], mesh=mesh,
        )
        ab_in, append_step = append_cache
        record(f"decode_attention b={b} w={window}", _append_kernel)
        # Who writes the step's fresh rows into the append buffer: the
        # kernel itself, or the twin's dynamic_update_slice.
        record(
            f"decode_append_write b={b} c={ab_in[0].shape[3]}",
            _append_kernel,
        )
    else:
        ab_in = None
        append_step = None

    layers, experts = params["layers"], None
    if chunk_valid is not None and "router" in layers:
        # The expert stacks stay out of the scan's per-layer slices: the
        # loop closes over them whole (see _moe_mlp_sorted).
        experts = {
            n: layers[n].reshape((-1,) + layers[n].shape[2:]) for n in EXPERT_LEAVES
        }
        layers = {n: w for n, w in layers.items() if n not in experts}

    def layer(carry, lp):
        # Serving: the full stacked (L, KH, b, t, ...) cache rides in the
        # scan CARRY and is updated in place by scatter.  Carrying it (vs
        # passing per-layer slices through xs→ys) is what lets XLA alias
        # the while-loop buffer: the xs/ys form double-buffers the cache —
        # +4 GB for llama3-8b batch 64, the difference between fitting a
        # 16 GB chip or OOM.  Attention then reads back only the
        # ``window`` prefix of the layer's slice, so per-step KV traffic
        # tracks live context, not max_len.
        carry_x, kv, ab, li, aux = carry
        if kv is None and "wq" in lp and "w_gate" in lp:
            # Plain cacheless dense layer: the shared implementation.
            carry_x = dense_layer(
                carry_x, lp, cfg, positions, kv_lengths, mesh
            )
            return (carry_x, kv, ab, li + 1, aux), None
        with jax.named_scope("layer/norm"):
            h = block_norm(carry_x, cfg, lp, "attn_norm")
        with jax.named_scope("layer/qkv"):
            if "wqkv" in lp:
                qkv = q_dot(h, lp["wqkv"], "wqkv")
                q = qkv[..., : n_q * hd].reshape(b, s, n_q, hd)
                k = qkv[..., n_q * hd : (n_q + n_kv) * hd].reshape(
                    b, s, n_kv, hd
                )
                v = qkv[..., (n_q + n_kv) * hd :].reshape(b, s, n_kv, hd)
            else:
                q = _badd(q_dot(h, lp["wq"], "wq"), lp, "bq").reshape(
                    b, s, n_q, hd
                )
                k = _badd(q_dot(h, lp["wk"], "wk"), lp, "bk").reshape(
                    b, s, n_kv, hd
                )
                v = _badd(q_dot(h, lp["wv"], "wv"), lp, "bv").reshape(
                    b, s, n_kv, hd
                )
        with jax.named_scope("layer/rope"):
            q = apply_rope(q, positions, cfg.rope_theta)
            k = apply_rope(k, positions, cfg.rope_theta)

        def slice_layer(buf):
            """Layer ``li``'s KV window: (KH, b, window, ...) from the
            head-major (L, KH, B, T, ...) cache, transposed back to the
            (b, window, KH, ...) shape gqa_attention expects.  XLA
            materializes this slice — the Pallas decode kernel below is
            the hot path that avoids it; this is the fallback for warm
            multi-token calls (suffix prefill) and non-TPU backends."""
            sl = jax.lax.dynamic_slice(
                buf,
                (li,) + (0,) * (buf.ndim - 1),
                (1,) + buf.shape[1:3] + (window,) + buf.shape[4:],
            )[0]
            perm = (1, 2, 0) + tuple(range(3, sl.ndim))
            return jnp.transpose(sl, perm)

        def attend_rows(q, k, v, **scales):
            """``attention`` over cache rows; the chunks of several slots
            (``chunk_valid``) attend one after the other, as each does
            alone: XLA's program for two rows' float32 scores at once
            takes five times what two programs of one row take (PERF.md
            section 6, PR 37)."""
            if chunk_valid is None or b == 1:
                return attention(q, k, v, positions, kv_lengths, mesh=mesh, **scales)

            def one(row):
                q, k, v, pos, n, scales = jax.tree.map(lambda x: x[None], row)
                return attention(q, k, v, pos, n, mesh=mesh, **scales)[0]

            return jax.lax.map(one, (q, k, v, positions, kv_lengths, scales))

        def write_cold(buf, fresh, r0):
            """Contiguous rows [r0, r0+b) x slots [0, s) of layer li."""
            with jax.named_scope("kv_write"):
                fresh_t = jnp.transpose(
                    fresh, (2, 0, 1) + tuple(range(3, fresh.ndim))
                )[None]
                return jax.lax.dynamic_update_slice(
                    buf, fresh_t, (li, 0, r0) + (0,) * (buf.ndim - 3)
                )

        def write_at(buf, fresh, *index):
            """Scatter ``fresh`` to layer li's (row, position) index."""
            with jax.named_scope("kv_write"):
                return buf.at[(li, slice(None)) + index].set(fresh)

        # KV writes inside carry their own scope: layer/attn/kv_write.
        with jax.named_scope("layer/attn"):
            if kv is not None and kv_int8 and ab is not None:
                # Append-buffer decode: fresh KV goes to ab slot
                # ``append_step`` and attention runs over
                # cache[0:kv_lengths) + ab[0:step]; no scatter touches the
                # big cache in this executable.  The step hands the fresh
                # rows to the attention, which writes them itself (the
                # kernel into its own block of the buffer, so that no XLA
                # operation touches a leaf inside the layer loop; the twin
                # as ``dynamic_update_slice``).
                k8, ks = _quantize_kv(k)
                v8, vs = _quantize_kv(v)
                step = jnp.asarray(append_step, jnp.int32)
                _decode_attn = (
                    decode_gqa_attention if _append_kernel
                    else decode_gqa_attention_xla
                )
                fresh = (k8[:, 0], v8[:, 0], ks[:, 0], vs[:, 0])
                attn, ab = _decode_attn(
                    q[:, 0], *kv, li, kv_lengths,
                    append=(ab, fresh, step), window=window,
                )
                attn = attn[:, None]
            elif kv is not None and kv_int8:
                k8, ks = _quantize_kv(k)
                v8, vs = _quantize_kv(v)
                if s > 1 and cold_prefill:
                    # Cold prefill writes positions 0..s-1 contiguously (the
                    # cold_prefill contract: positions == arange(s) per row), so
                    # a dynamic_update_slice replaces the general gather/scatter
                    # — profiled ~4x cheaper per layer at b=192 s=128.
                    r0 = jnp.asarray(row_offset, jnp.int32)
                    kv = (
                        write_cold(kv[0], k8, r0),
                        write_cold(kv[1], v8, r0),
                        write_cold(kv[2], ks, r0),
                        write_cold(kv[3], vs, r0),
                    )
                else:
                    bidx = jnp.arange(b, dtype=jnp.int32)[:, None]
                    kv = (
                        write_at(kv[0], k8, bidx, positions),
                        write_at(kv[1], v8, bidx, positions),
                        write_at(kv[2], ks, bidx, positions),
                        write_at(kv[3], vs, bidx, positions),
                    )
                if s > 1 and cold_prefill:
                    # Cold prefill: attend over the fresh bf16 k/v (exact — no
                    # quantization error on the prompt pass).  Only valid when
                    # the caller guarantees the cache holds nothing visible to
                    # these queries; warm multi-token calls (chunked prefill)
                    # must read the cache below.
                    attn = attention(q, k, v, positions, kv_lengths, mesh=mesh)
                else:
                    # NOTE: the Pallas kernel is deliberately NOT used here
                    # even when shapes allow it — this branch scatters into
                    # the big cache in the same executable, and the scatter's
                    # preferred (KH-minor) layout conflicts with the kernel's
                    # required default layout, costing 5 GB of entry copies
                    # (measured).  The kernel path is the append-buffer
                    # protocol above, where the big cache is read-only.
                    attn = attend_rows(
                        q,
                        slice_layer(kv[0]),
                        slice_layer(kv[1]),
                        k_scale=slice_layer(kv[2]),
                        v_scale=slice_layer(kv[3]),
                    )
            elif kv is not None:
                if s > 1 and cold_prefill:
                    r0 = jnp.asarray(row_offset, jnp.int32)
                    kv = (
                        write_cold(kv[0], k, r0),
                        write_cold(kv[1], v, r0),
                    )
                    # Cold prefill: attend over the fresh k/v — nothing in the
                    # cache is visible to these queries, and the written rows
                    # may live at a row_offset while slice_layer always reads
                    # rows [0, b).
                    attn = attention(q, k, v, positions, kv_lengths, mesh=mesh)
                else:
                    bidx = jnp.arange(b, dtype=jnp.int32)[:, None]
                    kv = (
                        write_at(kv[0], k, bidx, positions),
                        write_at(kv[1], v, bidx, positions),
                    )
                    attn = attend_rows(q, slice_layer(kv[0]), slice_layer(kv[1]))
            else:
                attn = attention(q, k, v, positions, kv_lengths, mesh=mesh)
        with jax.named_scope("layer/wo"):
            attn_out = _badd(
                q_dot(attn.reshape(b, s, n_q * hd), lp["wo"], "wo"), lp, "bo"
            )
            attn_out = _post_norm(attn_out, cfg, lp, "attn_post_norm")
            carry_x = _shard_activations(carry_x + attn_out, mesh)

        with jax.named_scope("layer/norm"):
            h = block_norm(carry_x, cfg, lp, "mlp_norm")
        if "router" in lp:
            # Both scope themselves: layer/moe/router, layer/moe/experts.
            if experts is not None:
                mlp_out = _moe_mlp_sorted(h, lp, experts, li, chunk_valid, cfg, mesh)
                layer_aux = 0.0
            else:
                mlp_out, layer_aux = _moe_mlp(h, lp, cfg, mesh)
            with jax.named_scope("layer/moe/experts"):
                mlp_out = _post_norm(mlp_out, cfg, lp, "mlp_post_norm")
                carry_x = _shard_activations(carry_x + mlp_out, mesh)
            return (carry_x, kv, ab, li + 1, aux + layer_aux), None
        with jax.named_scope("layer/mlp"):
            if "w_gu" in lp:
                gu = q_dot(h, lp["w_gu"], "w_gu")
                gated = cfg.act_fn(gu[..., : cfg.d_ff]) * gu[..., cfg.d_ff :]
                mlp_out = q_dot(gated, lp["w_down"], "w_down")
            elif "w_gate" in lp:
                gated = cfg.act_fn(
                    _badd(q_dot(h, lp["w_gate"], "w_gate"), lp, "b_gate")
                ) * _badd(q_dot(h, lp["w_up"], "w_up"), lp, "b_up")
                mlp_out = _badd(
                    q_dot(gated, lp["w_down"], "w_down"), lp, "b_down"
                )
            else:  # plain MLP: up -> act -> down
                gated = cfg.act_fn(
                    _badd(q_dot(h, lp["w_up"], "w_up"), lp, "b_up")
                )
                mlp_out = _badd(
                    q_dot(gated, lp["w_down"], "w_down"), lp, "b_down"
                )
            mlp_out = _post_norm(mlp_out, cfg, lp, "mlp_post_norm")
            carry_x = _shard_activations(carry_x + mlp_out, mesh)
        return (carry_x, kv, ab, li + 1, aux), None

    layer_fn = jax.checkpoint(layer) if (remat and cfg.remat) else layer

    if cfg.n_experts > 1 and "router" not in params["layers"]:
        raise ValueError(
            "config has n_experts > 1 but params carry a dense MLP tree — "
            "the MoE config requires router/w_*_e leaves (load or init "
            "params with the same config)"
        )
    if cfg.n_experts <= 1 and "router" in params["layers"]:
        raise ValueError(
            "params carry MoE leaves (router/w_*_e) but the config is "
            "dense (n_experts <= 1) — use the matching MoE config"
        )

    carry = (x, cache, ab_in, jnp.int32(0), jnp.float32(0.0))
    if cfg.ut_steps > 1:
        if experts is not None:
            raise NotImplementedError(
                "a looped stack with sorted experts: the expert stacks are "
                "indexed by the plane count ``li``, which a second pass "
                "carries past the layers"
            )

        def one_pass(carry, _):
            # ``li`` goes on counting: pass u, layer l reads and writes
            # plane u * n_layers + l of the cache and of the append buffer.
            with jax.named_scope("loop/pass"):
                (x, *rest), _ = jax.lax.scan(layer_fn, carry, layers)
            with jax.named_scope("loop/renorm"):
                x = apply_final_norm(x, cfg, params)
            return (x, *rest), None

        # One body, ``ut_steps`` trips; the last trip's norm is the final one.
        carry, _ = jax.lax.scan(one_pass, carry, None, length=cfg.ut_steps)
        x, cache_out, ab_out, _, aux_total = carry
    else:
        (x, cache_out, ab_out, _, aux_total), _ = jax.lax.scan(
            layer_fn, carry, layers
        )
        with jax.named_scope("final_norm"):
            x = apply_final_norm(x, cfg, params)
    if append_cache is not None:
        return x, cache_out, ab_out
    if return_aux:
        return x, cache_out, aux_total / max(cfg.cache_planes, 1)
    return x, cache_out


@jax.named_scope("lm_head")
def logits(params: Params, hidden: jnp.ndarray) -> jnp.ndarray:
    """Project hidden states to vocab logits, accumulating in f32.

    Operands stay in storage dtype: an astype(f32) on the (d_model, vocab)
    head would materialize a ~2 GB copy in HBM on every decode step."""
    from generativeaiexamples_tpu.ops.quant import QuantizedMatrix

    head = params["lm_head"]
    if isinstance(head, QuantizedMatrix):
        out = jnp.einsum(
            "...d,dv->...v",
            hidden,
            head.q.astype(hidden.dtype),
            preferred_element_type=jnp.float32,
        )
        return out * head.scale[..., 0, :]
    return jnp.einsum(
        "...d,dv->...v", hidden, head, preferred_element_type=jnp.float32
    )
