"""Weight-only int8 quantization for serving.

The reference's serving engines get their memory/bandwidth wins from
TRT-LLM's int8/fp8 engines inside NIM (SURVEY.md §2.8); the TPU-native
equivalent is weight-only int8 with per-output-channel symmetric scales:

* decode throughput on TPU is HBM-bound on weight reads — int8 halves the
  bytes per step (the AQT-style serving recipe);
* full-depth llama3-8b in int8 (~8 GB + scales) fits a single v5e chip's
  16 GB HBM, where bf16 (16 GB weights) cannot.

The quantized weight stays int8 in HBM and is converted to the activation
dtype inside the fused matmul (XLA fuses the convert; the MXU accumulates
in f32 via ``preferred_element_type``).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp


@dataclasses.dataclass
class QuantizedMatrix:
    """int8 weight + per-output-channel f32 scale (symmetric)."""

    q: jnp.ndarray  # int8, shape (..., d_in, d_out)
    scale: jnp.ndarray  # f32, shape (..., 1, d_out)

    @property
    def shape(self):
        return self.q.shape

    @property
    def ndim(self):
        return self.q.ndim


jax.tree_util.register_dataclass(
    QuantizedMatrix, data_fields=["q", "scale"], meta_fields=[]
)


def quantize_matrix(w: jnp.ndarray) -> QuantizedMatrix:
    """Symmetric per-output-channel int8 quantization of (..., d_in, d_out)."""
    amax = jnp.max(jnp.abs(w.astype(jnp.float32)), axis=-2, keepdims=True)
    scale = jnp.maximum(amax, 1e-8) / 127.0
    q = jnp.clip(jnp.round(w.astype(jnp.float32) / scale), -127, 127).astype(
        jnp.int8
    )
    return QuantizedMatrix(q=q, scale=scale)


def dequantize(qm: QuantizedMatrix, dtype=None, *, cfg=None) -> jnp.ndarray:
    """Materialize the full-width weight.

    ``dtype`` defaults to the model's compute dtype — ``cfg.compute_dtype``
    when a :class:`~generativeaiexamples_tpu.models.llama.LlamaConfig` is
    given, else serving's bf16 default — rather than the old hardcoded
    f32, which silently doubled the materialized width for every bf16
    caller.
    """
    if dtype is None:
        dtype = cfg.compute_dtype if cfg is not None else jnp.bfloat16
    return (qm.q.astype(jnp.float32) * qm.scale).astype(dtype)


def _validate_q_dot(x: jnp.ndarray, w: Any, name: Optional[str]) -> None:
    """Shape/dtype validation that names the projection.

    Without it a mispacked weight (e.g. a wqkv concatenated on the wrong
    axis, or a layer-stacked leaf passed where a sliced one is expected)
    surfaces as an opaque XLA dot-dimension error deep inside the scan.
    """
    who = f"projection {name!r}" if name else "q_dot"
    d_in = w.shape[-2]
    if x.ndim < 1 or x.shape[-1] != d_in:
        raise ValueError(
            f"{who}: activation feature width {x.shape[-1] if x.ndim else 0}"
            f" (shape {tuple(x.shape)}) does not match weight d_in {d_in}"
            f" (weight shape {tuple(w.shape)})"
        )
    if not jnp.issubdtype(x.dtype, jnp.floating):
        raise ValueError(
            f"{who}: activations must be floating point, got {x.dtype}"
        )
    if isinstance(w, QuantizedMatrix):
        if w.q.dtype != jnp.int8:
            raise ValueError(
                f"{who}: QuantizedMatrix values must be int8, got "
                f"{w.q.dtype}"
            )
        if w.scale.shape[-1] != w.q.shape[-1] or w.scale.shape[-2] != 1:
            raise ValueError(
                f"{who}: scale shape {tuple(w.scale.shape)} does not "
                f"broadcast against int8 weight {tuple(w.q.shape)} "
                "(expected (..., 1, d_out))"
            )


def q_dot(x: jnp.ndarray, w: Any, name: Optional[str] = None) -> jnp.ndarray:
    """x @ w for plain arrays, QuantizedMatrix, or pre-blocked W8A8.

    The serving matmul entry point, dispatching on the weight's layout:

    * :class:`~generativeaiexamples_tpu.ops.qmm.BlockedQuantizedMatrix`
      (``[llm].matmul_kernel = pallas_w8a8``): per-token-quantized W8A8
      through the streaming Pallas kernel, or its bit-identical XLA
      twin off-TPU (``ops.qmm.q_matmul``).
    * QuantizedMatrix (weight-only int8, the ``xla`` path): the int8
      tensor converts to x's dtype inside the dot (fused by XLA — HBM
      sees only int8 reads) and the per-column scale is applied to the
      (much smaller) output.
    * Plain arrays: a dot with f32 accumulation.

    ``name`` labels shape/dtype validation errors with the projection
    (wqkv, w_gu, ...) instead of an opaque XLA dot error.
    """
    from generativeaiexamples_tpu.ops.qmm import BlockedQuantizedMatrix

    if isinstance(w, BlockedQuantizedMatrix):
        _validate_q_dot(x, w, name)
        from generativeaiexamples_tpu.ops.qmm import q_matmul

        return q_matmul(x, w, name)
    if isinstance(w, QuantizedMatrix):
        _validate_q_dot(x, w, name)
        out = jnp.einsum(
            "...i,io->...o",
            x,
            w.q.astype(x.dtype),
            preferred_element_type=jnp.float32,
        )
        return (out * w.scale[..., 0, :]).astype(x.dtype)
    _validate_q_dot(x, w, name)
    return jnp.einsum(
        "...i,io->...o", x, w, preferred_element_type=jnp.float32
    ).astype(x.dtype)


def qdot(x: jnp.ndarray, w: Any) -> jnp.ndarray:
    """Back-compat alias for :func:`q_dot` (unnamed call sites)."""
    return q_dot(x, w)


# Per-layer projection weights that serving quantizes to int8.  Shared by
# the real quantizer below and the random-init bench path
# (engine.decode.init_random_int8_params) so the two cannot drift.
QUANT_TARGETS = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")


def quantize_embedding(w: jnp.ndarray) -> QuantizedMatrix:
    """Per-row (token) symmetric int8 for an embedding table (V, d).

    The embedding is consumed by row gather, so the natural quantization
    group is the row: scale has shape (V, 1) and the gathered rows
    dequantize exactly like the serving lookup in ``models.llama.embed``.
    """
    amax = jnp.max(jnp.abs(w.astype(jnp.float32)), axis=-1, keepdims=True)
    scale = jnp.maximum(amax, 1e-8) / 127.0
    q = jnp.clip(jnp.round(w.astype(jnp.float32) / scale), -127, 127).astype(
        jnp.int8
    )
    return QuantizedMatrix(q=q, scale=scale)


def quantize_llama_params(
    params: dict, *, include_lm_head: bool = True, include_embed: bool = False
) -> dict:
    """Quantize every layer matmul weight (and optionally head/embedding).

    Norm gains stay in their storage dtype (tiny).  The stacked
    (L, d_in, d_out) layout quantizes per (layer, output-channel), and
    ``lax.scan`` slices the QuantizedMatrix pytree per layer like any
    other stacked parameter.  ``include_embed`` additionally stores the
    embedding table int8 with per-row scales — serving-only (~0.5 GB of
    HBM back on llama3-8b; training keeps the bf16 table).
    """
    layers = dict(params["layers"])
    for name in QUANT_TARGETS:
        if name in layers:  # MoE trees lack the dense MLP leaves
            layers[name] = quantize_matrix(layers[name])
    out = {**params, "layers": layers}
    if include_lm_head:
        out["lm_head"] = quantize_matrix(params["lm_head"])
    if include_embed:
        out["embed"] = quantize_embedding(params["embed"])
    return out


quantize_llama = jax.jit(
    quantize_llama_params,
    static_argnames=("include_lm_head", "include_embed"),
)
