"""Rotary position embeddings (half-split / rotate-half convention).

The half-split layout matches the HF llama checkpoint convention so converted
weights need no permutation at load time.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Mapping

import jax.numpy as jnp
import numpy as np


def rope_frequencies(head_dim: int, theta: float) -> jnp.ndarray:
    """Inverse frequencies for each rotary pair: (head_dim // 2,) f32."""
    exponents = jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim
    return 1.0 / (theta ** exponents)


def apply_rope(
    x: jnp.ndarray, positions: jnp.ndarray, theta: float
) -> jnp.ndarray:
    """Rotate query/key vectors by their absolute positions.

    Args:
      x: (batch, seq, heads, head_dim)
      positions: (batch, seq) int32 absolute positions
      theta: rope base (llama3 uses 500000.0)
    """
    head_dim = x.shape[-1]
    inv_freq = rope_frequencies(head_dim, theta)  # (hd/2,)
    angles = positions.astype(jnp.float32)[..., None] * inv_freq  # (b, s, hd/2)
    # Trig in f32 (angles up to position*1.0 need the mantissa); the
    # rotation arithmetic runs in x's dtype.  In bf16 serving this keeps
    # the (b, s, heads, head_dim) q/k tensors in bf16 end-to-end — an f32
    # astype here materialized two 400 MB+ layout copies per layer in the
    # b=192 prefill profile (~2.4 ms/layer of pure data formatting).
    cos = jnp.cos(angles)[:, :, None, :].astype(x.dtype)  # (b, s, 1, hd/2)
    sin = jnp.sin(angles)[:, :, None, :].astype(x.dtype)
    return _rotate_half(x, cos, sin).astype(x.dtype)


def _rotate_half(x: jnp.ndarray, cos: jnp.ndarray, sin: jnp.ndarray) -> jnp.ndarray:
    """(x1, x2) -> (x1 cos - x2 sin, x2 cos + x1 sin) over x's halves."""
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


# -- per-layer-kind parameters ----------------------------------------------------


@dataclasses.dataclass(frozen=True)
class RopeSpec:
    """One section of a config's ``rope_parameters``: the plain
    frequencies (``rope_type`` ``default``) or YaRN's; ``none`` is a layer
    kind that is not rotated at all (position-free: its scores carry no
    position, the causal mask alone orders it)."""

    theta: float
    rope_type: str = "default"
    factor: float = 1.0
    original_max: int = 0  # original_max_position_embeddings
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: float = 1.0
    truncate: bool = True

    def __post_init__(self) -> None:
        if self.rope_type not in ("default", "yarn", "none"):
            raise ValueError(
                f"rope_type {self.rope_type!r} is not served: only 'default' "
                "and 'yarn' frequencies are implemented (ops/rope.py)"
            )


# A layer kind that is not rotated.
NO_ROPE = RopeSpec(theta=1.0, rope_type="none")


def rope_spec(section: Mapping[str, Any]) -> RopeSpec:
    """A ``rope_parameters`` section of a public config -> ``RopeSpec``."""
    kind = str(section.get("rope_type", "default"))
    spec = RopeSpec(theta=float(section["rope_theta"]), rope_type=kind)
    if kind != "yarn":
        return spec
    factor = float(section["factor"])
    return dataclasses.replace(
        spec,
        factor=factor,
        original_max=int(section["original_max_position_embeddings"]),
        beta_fast=float(section.get("beta_fast", 32.0)),
        beta_slow=float(section.get("beta_slow", 1.0)),
        attention_factor=_yarn_attention_factor(section, factor),
        truncate=bool(section.get("truncate", True)),
    )


def yarn_mscale(factor: float, mscale: float = 1.0) -> float:
    """YaRN's magnitude term: ``0.1 mscale ln(factor) + 1`` (1 at or
    under a factor of 1)."""
    return 1.0 if factor <= 1.0 else 0.1 * mscale * math.log(factor) + 1.0


def _yarn_attention_factor(section: Mapping[str, Any], factor: float) -> float:
    """What cos and sin are multiplied by, as ``transformers`` infers it:
    the section's own ``attention_factor``; else, where it gives both
    ``mscale`` and ``mscale_all_dim`` (the latent-attention lineage, whose
    softmax scale carries the ``mscale_all_dim`` term squared instead),
    the ratio of their terms; else ``0.1 ln(factor) + 1``."""
    if section.get("attention_factor"):
        return float(section["attention_factor"])
    mscale, all_dim = section.get("mscale"), section.get("mscale_all_dim")
    if mscale and all_dim:
        return yarn_mscale(factor, float(mscale)) / yarn_mscale(factor, float(all_dim))
    return yarn_mscale(factor)


@functools.lru_cache(maxsize=None)
def spec_frequencies(spec: RopeSpec, head_dim: int) -> np.ndarray:
    """Inverse frequencies of ``spec``, (head_dim // 2,) float32, computed
    once a configuration (the cache) and on the host.

    YaRN as in ``transformers``' ``_compute_yarn_parameters``: pair ``i``
    turns ``dim(r)`` times over the original context where
    ``dim(r) = head_dim ln(original_max / (2 pi r)) / (2 ln theta)``;
    pairs below ``low = dim(beta_fast)`` keep the plain frequency, pairs
    above ``high = dim(beta_slow)`` are divided by ``factor``, and a
    linear ramp joins them."""
    half = head_dim // 2
    plain = spec.theta ** (-np.arange(0, head_dim, 2, dtype=np.float64) / head_dim)
    if spec.rope_type == "default":
        return plain.astype(np.float32)

    def dim_of(rotations: float) -> float:
        return (
            head_dim
            * math.log(spec.original_max / (rotations * 2.0 * math.pi))
            / (2.0 * math.log(spec.theta))
        )

    low, high = dim_of(spec.beta_fast), dim_of(spec.beta_slow)
    if spec.truncate:
        low, high = math.floor(low), math.ceil(high)
    low, high = max(low, 0), min(high, head_dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(half, dtype=np.float64) - low) / (high - low), 0.0, 1.0)
    return ((1.0 - ramp) * plain + ramp * plain / spec.factor).astype(np.float32)


def apply_rope_spec(x: jnp.ndarray, positions: jnp.ndarray, spec: RopeSpec) -> jnp.ndarray:
    """:func:`apply_rope` with the frequencies of ``spec``; cos and sin
    are multiplied by its ``attention_factor`` (so q.k grows by its
    square).  A ``none`` spec leaves ``x`` as it is."""
    if spec.rope_type == "none":
        return x
    inv_freq = jnp.asarray(spec_frequencies(spec, x.shape[-1]))
    angles = positions.astype(jnp.float32)[..., None] * inv_freq
    cos = (jnp.cos(angles) * spec.attention_factor)[:, :, None, :].astype(x.dtype)
    sin = (jnp.sin(angles) * spec.attention_factor)[:, :, None, :].astype(x.dtype)
    return _rotate_half(x, cos, sin)


def apply_rope_partial(
    x: jnp.ndarray, positions: jnp.ndarray, spec: RopeSpec, rotary_dim: int
) -> jnp.ndarray:
    """:func:`apply_rope_spec` over the first ``rotary_dim`` values of each
    head (``partial_rotary_factor``: half-split pairs inside that part, its
    frequencies those of a head ``rotary_dim`` wide); the rest passes."""
    if rotary_dim >= x.shape[-1]:
        return apply_rope_spec(x, positions, spec)
    turned = apply_rope_spec(x[..., :rotary_dim], positions, spec)
    return jnp.concatenate([turned, x[..., rotary_dim:]], axis=-1)
