"""Token sampling: temperature / top-k / top-p, vectorized per request.

Replaces the sampling config the reference forwards to TRT-LLM via the
OpenAI API (``common/server.py:269-274`` passes temperature/top_p/max_tokens
per request).  Every knob is a per-batch-element array so one jitted decode
step can serve heterogeneous requests (continuous batching).
"""

from __future__ import annotations

import dataclasses
import os
from typing import Optional

import jax
import jax.numpy as jnp

_NEG_INF = -1e30

# Sampling candidate pool: filters operate on the top-CANDIDATES tokens of
# the tempered distribution instead of a full-vocab sort (decode hot path).
CANDIDATES = 128


def exact_sampling_enabled() -> bool:
    """Engine-level opt-out of approximate candidate recall.

    ``GAIE_EXACT_SAMPLING=1`` (or the engine server's ``--exact-sampling``
    flag) switches candidate selection from ``lax.approx_max_k`` (~0.95
    recall of far-tail tokens, ~10x cheaper at 128k vocab) to the exact
    sort.  Trace-time: it selects which program gets compiled, so it is a
    deployment knob rather than a per-request field.
    """
    return os.environ.get("GAIE_EXACT_SAMPLING", "").lower() in (
        "1",
        "true",
        "yes",
    )


@dataclasses.dataclass(frozen=True)
class SamplingParams:
    """Per-request sampling configuration (defaults match the reference
    server's request schema, ``server.py:69-90``)."""

    temperature: float = 0.2
    top_p: float = 0.7
    top_k: int = 0  # 0 = disabled
    max_tokens: int = 1024
    stop_on_eos: bool = True


def _warp(
    logits: jnp.ndarray,
    temperature: jnp.ndarray,
    top_p: jnp.ndarray,
    top_k: jnp.ndarray,
    approx: Optional[bool],
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """The temperature→candidates→top-k/top-p pipeline.

    Returns ``(cand_idx, cand_logits, scaled)``: candidate ids, the
    masked tempered logits over them (filtered = _NEG_INF), and the full
    tempered logits (for the unfiltered-row special case).
    """
    if approx is None:
        approx = not exact_sampling_enabled()
    _, vocab = logits.shape
    temp = jnp.maximum(temperature, 1e-6)[:, None]
    scaled = logits / temp
    k_cap = min(CANDIDATES, vocab)
    if approx and vocab > 2 * CANDIDATES:
        # aggregate_to_topk (default) re-ranks the recalled candidates, so
        # values arrive exact-sorted; only recall of far-tail tokens is
        # approximate.
        sorted_scaled, cand_idx = jax.lax.approx_max_k(scaled, k_cap)
    else:
        sorted_scaled, cand_idx = jax.lax.top_k(scaled, k_cap)
    ranks = jnp.arange(k_cap, dtype=jnp.int32)[None, :]

    # top-k: drop everything past the k-th sorted entry.
    k = jnp.where(top_k > 0, jnp.minimum(top_k, k_cap), k_cap).astype(
        jnp.int32
    )[:, None]
    topk_mask = ranks < k

    # top-p: keep the smallest prefix whose probability mass reaches top_p
    # (the first token always survives: its preceding mass is zero).
    # Probabilities are normalized over the candidate pool; the excluded
    # tail holds ~0 mass at 128 candidates.
    sorted_probs = jax.nn.softmax(sorted_scaled, axis=-1)
    cumulative = jnp.cumsum(sorted_probs, axis=-1)
    before = cumulative - sorted_probs
    topp_mask = before < top_p[:, None]

    keep = topk_mask & topp_mask
    cand_logits = jnp.where(keep, sorted_scaled, _NEG_INF)
    return cand_idx, cand_logits, scaled


@jax.named_scope("sample")
def sample(
    logits: jnp.ndarray,
    key: jax.Array,
    temperature: jnp.ndarray,
    top_p: jnp.ndarray,
    top_k: jnp.ndarray,
    *,
    approx: Optional[bool] = None,
) -> jnp.ndarray:
    """Sample one token per row.

    Args:
      logits: (b, vocab) f32.
      temperature: (b,) — 0 means greedy.
      top_p: (b,) in (0, 1]; 1 disables nucleus filtering.
      top_k: (b,) int32; 0 disables top-k filtering. Values are clamped to
        the CANDIDATES pool (128).
      approx: use ``lax.approx_max_k`` for candidate selection (TPU-fast
        approximate top-k; ~10× cheaper than the exact sort at 128k vocab).
        Exact ``lax.top_k`` otherwise.  Default: approximate unless
        ``GAIE_EXACT_SAMPLING`` is set (:func:`exact_sampling_enabled`).

    Returns:
      (b,) int32 sampled token ids.

    The whole filter+sample pipeline runs on the top-CANDIDATES tokens of
    the tempered distribution: a full 128k-vocab sort/softmax/categorical
    costs milliseconds per decode step on TPU while the probability mass
    beyond the top 128 tokens is negligible (TRT-LLM's sampling layers use
    the same candidate-truncation strategy).
    """
    greedy_tokens = jnp.argmax(logits, axis=-1).astype(jnp.int32)

    # Temperature first, then nucleus/top-k on the tempered distribution —
    # the OpenAI/HF semantics the reference's clients expect.
    cand_idx, cand_logits, scaled = _warp(
        logits, temperature, top_p, top_k, approx
    )
    # Sample within the candidate pool, then map back to vocab ids — no
    # full-vocab materialization anywhere past the top-k selection.
    choice = jax.random.categorical(key, cand_logits, axis=-1)
    sampled = jnp.take_along_axis(cand_idx, choice[:, None], axis=-1)[
        :, 0
    ].astype(jnp.int32)

    # Rows with both filters disabled sample the full untruncated
    # distribution (candidate truncation would bias high-temperature
    # sampling, where the tail past rank 128 carries real mass).  The
    # full-vocab categorical only executes when such a row exists; greedy
    # rows (temperature 0 — e.g. batch-padding slots) never use the
    # sampled value, so they must not trigger it.
    unfiltered = (top_p >= 1.0) & (top_k <= 0) & (temperature > 0.0)
    sampled = jax.lax.cond(
        jnp.any(unfiltered),
        lambda: jnp.where(
            unfiltered,
            jax.random.categorical(key, scaled, axis=-1).astype(jnp.int32),
            sampled,
        ),
        lambda: sampled,
    )
    return jnp.where(temperature <= 0.0, greedy_tokens, sampled)
