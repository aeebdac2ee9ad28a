"""reference.py against models/llama.py on tiny presets, in float32."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import reference
from generativeaiexamples_tpu.models import llama


@pytest.mark.parametrize("preset", ["llama-tiny", "llama-moe-tiny"])
@pytest.mark.parametrize("serving_layout", [False, True])
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
    tokens = np.random.default_rng(0).integers(0, cfg.vocab_size, size=37)
    with jax.default_matmul_precision("highest"):
        hidden = llama.forward(
            params, cfg, jnp.asarray(tokens)[None], jnp.arange(37)[None]
        )
        hidden = hidden[0] if isinstance(hidden, tuple) else hidden
        want = np.asarray(llama.logits(params, hidden[:, -1:, :])[0, 0])
    got = np.asarray(reference.last_logits(params, cfg, tokens, pad_to))
    # Same float32 mathematics in another order of operations.
    assert np.abs(got - want).max() <= 2e-4 * np.abs(want).max()
    assert int(got.argmax()) == int(want.argmax())
