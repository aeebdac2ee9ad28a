"""reference.py against models/llama.py on tiny presets, in float32."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import reference
from generativeaiexamples_tpu.models import llama


@pytest.mark.parametrize("preset", ["llama-tiny", "llama-moe-tiny"])
@pytest.mark.parametrize("serving_layout", [False, True, "blocked"])
@pytest.mark.parametrize("pad_to", [0, 48])
def test_reference_matches_program(preset, serving_layout, pad_to):
    cfg = llama.PRESETS[preset]()
    cfg = dataclasses.replace(cfg, dtype="float32", moe_dropless=cfg.n_experts > 1)
    params = llama.init_params(cfg, jax.random.PRNGKey(3))
    if serving_layout:  # int8 projections, packed qkv and gate/up
        from generativeaiexamples_tpu.ops.quant import quantize_llama_params

        params = llama.pack_for_serving(
            quantize_llama_params(params, include_embed=True)
        )
    if serving_layout == "blocked":  # the W8A8 path's tiles, which the control run serves
        from generativeaiexamples_tpu.engine.weights import preblock_llama_params

        blocked = preblock_llama_params(params)
        assert any(hasattr(w, "tiles") for w in blocked["layers"].values())
    tokens = np.random.default_rng(0).integers(0, cfg.vocab_size, size=37)
    with jax.default_matmul_precision("highest"):
        hidden = llama.forward(
            params, cfg, jnp.asarray(tokens)[None], jnp.arange(37)[None]
        )
        hidden = hidden[0] if isinstance(hidden, tuple) else hidden
        want = np.asarray(llama.logits(params, hidden[:, -1:, :])[0, 0])
    if serving_layout == "blocked":  # the same weights, so the same logits as from the unblocked ones
        params = blocked
    got = np.asarray(reference.last_logits(params, cfg, tokens, pad_to))
    # Same float32 mathematics in another order of operations.
    assert np.abs(got - want).max() <= 2e-4 * np.abs(want).max()
    assert int(got.argmax()) == int(want.argmax())


def test_unrenormalised_routing_differs_unless_every_expert_is_taken():
    cfg = dataclasses.replace(llama.PRESETS["llama-moe-tiny"](), dtype="float32", moe_dropless=True)
    assert 1 < cfg.n_experts_per_tok < cfg.n_experts
    params = llama.init_params(cfg, jax.random.PRNGKey(5))
    tokens = np.random.default_rng(1).integers(0, cfg.vocab_size, size=21)
    renorm = np.asarray(reference.last_logits(params, cfg, tokens))
    plain = np.asarray(reference.last_logits(params, cfg, tokens, norm_topk=False))
    assert np.abs(renorm - plain).max() > 1e-3 * np.abs(renorm).max()
    every = dataclasses.replace(cfg, n_experts_per_tok=cfg.n_experts)  # weights sum to one already
    np.testing.assert_allclose(
        reference.last_logits(params, every, tokens, norm_topk=False),
        reference.last_logits(params, every, tokens), rtol=1e-5, atol=1e-6,
    )
