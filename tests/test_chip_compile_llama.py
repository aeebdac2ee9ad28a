"""``LlamaServing``'s programs at the widths of the llama-shaped cells,
compiled for the described v5e (``tests/chip_compile_lib.py``).
"""

import functools
import json
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from chip_compile_lib import (  # noqa: F401 — ``one_chip`` is the file's fixture
    _append_leaves_are_the_kernels_alone,
    _spec,
    one_chip,
)


@pytest.mark.parametrize("rows", [1, 2])
def test_mixtrals_chunk_program_holds_no_copy_of_an_expert_leaf(one_chip, rows, monkeypatch):
    """``_prefill_suffix_rows`` of ``LlamaServing`` at mixtral-8x7b-l4's
    published widths (4 layers of 8 experts 4,096 x 14,336 in bf16, int8
    dense projections and K/V, 32 slots of 2,048), the two programs its
    family holds (1 and 2 rows x window 2,048): the sorted dispatch's
    grouped products lower through Mosaic, three a layer; and the layer
    loop hands them the expert stacks whole (a bitcast of the parameter,
    (4, 8, ...) viewed as (32, ...)): no copy, slice or fusion result has
    an expert leaf's size or a layer's share of it, 2.8 GB that a chunk
    of ~17 ms cannot pay for."""
    from generativeaiexamples_tpu.engine.scheduler import make_prefill_suffix_rows
    from generativeaiexamples_tpu.engine.serving_models import LlamaServing
    from generativeaiexamples_tpu.ops import moe

    monkeypatch.setattr(moe, "platform_of", lambda mesh: "tpu")
    model, engine = _cell_config("mixtral-8x7b-l4")
    max_len, chunk = int(engine["max_len"]), int(engine["prefill_chunk_tokens"])
    cfg = _llama_config(
        model, engine, n_experts=model["num_local_experts"],
        n_experts_per_tok=model["num_experts_per_tok"], moe_dropless=True,
    )
    serving = LlamaServing(cfg, None, max_len)
    assert serving.chunks_per_program(chunk) == 2
    spec = _spec(one_chip)
    ints, floats = spec((rows,), jnp.int32), spec((rows,), jnp.float32)
    params, state = _described(one_chip, serving, engine)
    compiled = make_prefill_suffix_rows(serving).lower(
        params, state,
        spec((rows, chunk), jnp.int32), ints, ints, ints,
        spec((2,), jnp.uint32), (floats, floats, ints), max_len,
    ).compile()
    text = compiled.as_text()
    assert len(re.findall(r"custom_call_target=\"tpu_custom_call\"", text)) == 3
    # Whatever yields an expert-sized buffer is the parameter itself, its
    # way into the layer loop, or a view of it.
    L, E, D, F = cfg.n_layers, cfg.n_experts, cfg.d_model, cfg.d_ff
    sized = re.findall(
        rf"= bf16\[(?:{L},{E}|{L * E}|{E}|1,{E}),(?:{D},{F}|{F},{D})\]\S* ([\w-]+)\(", text
    )
    assert sized and set(sized) <= {"parameter", "get-tuple-element", "bitcast"}, sized
    assert compiled.memory_analysis().temp_size_in_bytes < 600_000_000


def _cell_config(name: str):
    """``benchmarks/configs/<name>.json``: the public keys and the engine block."""
    configs = Path(__file__).resolve().parents[1] / "benchmarks" / "configs"
    model = json.loads((configs / f"{name}.json").read_text())
    return model, model["engine"]


def _llama_config(model, engine, **experts):
    """The program's configuration of a llama-shaped cell, from its file."""
    from generativeaiexamples_tpu.models import llama

    return llama.LlamaConfig(
        vocab_size=model["vocab_size"], d_model=model["hidden_size"],
        n_layers=model["num_hidden_layers"], n_heads=model["num_attention_heads"],
        n_kv_heads=model["num_key_value_heads"], head_dim=model["head_dim"],
        d_ff=model["intermediate_size"], rope_theta=model["rope_theta"],
        norm_eps=model["rms_norm_eps"], max_seq_len=int(engine["max_len"]), dtype="bfloat16",
        kv_dtype=engine["kv_dtype"], **experts,
    )


def _described(one_chip, serving, engine):
    """The served parameters (int8, packed) and the engine's slots, as
    shapes on the described chip."""
    def described(make):
        return jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
            jax.eval_shape(make),
        )

    params = described(lambda: serving.prepare_params(
        None, quantize=True, matmul_kernel=engine["matmul_kernel"], seed=0))
    return params, described(lambda: serving.init_state(int(engine["max_batch"]), serving.cfg.max_seq_len))


@pytest.fixture(scope="module")
def ouro(one_chip):
    """``LlamaServing`` at ``benchmarks/configs/ouro-2.6b.json``: the
    described parameters (int8, packed) and the cell's 16 slots of 768
    rows, with the decode kernel's gate believing it is on the chip."""
    from generativeaiexamples_tpu.engine.serving_models import LlamaServing
    from generativeaiexamples_tpu.models import llama
    from generativeaiexamples_tpu.ops import decode_attention

    model, engine = _cell_config("ouro-2.6b")
    cfg = llama.PRESETS["ouro-2.6b"](max_seq_len=engine["max_len"], kv_dtype=engine["kv_dtype"])
    assert (cfg.n_layers, cfg.ut_steps, cfg.d_model) == (
        model["num_hidden_layers"], model["total_ut_steps"], model["hidden_size"])
    serving = LlamaServing(cfg, None, cfg.max_seq_len)
    params, state = _described(one_chip, serving, engine)
    was = decode_attention.platform_of
    decode_attention.platform_of = lambda mesh: "tpu"
    yield serving, engine, params, state
    decode_attention.platform_of = was


SLOTS_BYTES = 16 * 768 * 798_720  # 9.81 GB: 192 planes of K and V, int8 and a bf16 scale


def _decode_chunk(one_chip, serving, engine, params, state, window):
    """``serving``'s decode chunk (the engine's slots and steps) under
    ``window``, compiled, and the kernel paths its trace took."""
    from generativeaiexamples_tpu.ops import dispatch

    b, steps = engine["max_batch"], engine["decode_chunk_size"]
    spec = _spec(one_chip)
    ints, floats, flags = spec((b,), jnp.int32), spec((b,), jnp.float32), spec((b,), jnp.bool_)
    dispatch.TAKEN.clear()
    compiled = serving.make_decode_chunk().lower(
        params, state, ints, ints, spec((2,), jnp.uint32), floats, floats, ints, steps, window,
        flags, spec((steps, b), jnp.int32), flags,
    ).compile()
    return compiled, dict(dispatch.TAKEN)


@pytest.mark.parametrize("window", [64, 768])
def test_ouros_decode_chunk_is_one_layer_body_over_192_planes_in_place(one_chip, ouro, window):
    """The decode chunk of ouro-2.6b (8 steps over 16 slots of 768 rows):
    the Pallas kernel is in it ONCE (one layer body, scanned 48 times
    inside a loop of 4 passes inside the steps' scan: not 192 bodies), the
    9.81 GB of slots go through in place, and beside 12.49 GB of weights
    and slots the temporaries are the append buffer's 0.1 GB and little
    else, so the step fits the chip.  The kernel writes a step's fresh
    K/V into the append buffer itself: in the layer body the four leaves
    go from the body's parameter through the Mosaic call to its root and
    no XLA operation makes or takes one (until PR 54: four
    ``dynamic-update-slice`` and a whole scale leaf copied into VMEM and
    out again, every one of the 192 calls of a step)."""
    serving, engine, params, state = ouro
    b, steps = engine["max_batch"], engine["decode_chunk_size"]
    compiled, taken = _decode_chunk(one_chip, serving, engine, params, state, window)
    assert taken == {
        f"decode_attention b={b} w={window}": "pallas",
        f"decode_append_write b={b} c={steps}": "pallas",
    }
    text = compiled.as_text()
    assert len(re.findall(r"custom_call_target=\"tpu_custom_call\"", text)) == 1
    cfg = serving.cfg
    _append_leaves_are_the_kernels_alone(
        text, (cfg.cache_planes, cfg.n_kv_heads, b, steps, cfg.head_dim))
    memory = compiled.memory_analysis()
    print("ouro decode chunk", window, "temporaries", memory.temp_size_in_bytes)
    assert memory.alias_size_in_bytes == SLOTS_BYTES
    assert memory.temp_size_in_bytes < 400_000_000
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes < 13_000_000_000


@pytest.mark.parametrize("window", [256, 2048])
def test_mistrals_decode_chunk_leaves_the_append_buffer_to_the_kernel(one_chip, window, monkeypatch):
    """The decode chunk of mistral-7b (8 steps over 32 slots of 2,048 rows,
    two groups of 16 a call, 8 KV heads): as Ouro's, the layer body holds
    the kernel once and nothing else that makes or takes an append leaf
    (until PR 54 it also sliced a quarter of a scale leaf out, 0.3-0.4 s of
    the cells' 10 s windows), and the 4.36 GB of slots go through in
    place."""
    from generativeaiexamples_tpu.engine.serving_models import LlamaServing
    from generativeaiexamples_tpu.ops import decode_attention

    model, engine = _cell_config("mistral-7b")
    cfg = _llama_config(model, engine)
    serving = LlamaServing(cfg, None, cfg.max_seq_len)
    params, state = _described(one_chip, serving, engine)
    monkeypatch.setattr(decode_attention, "platform_of", lambda mesh: "tpu")
    compiled, taken = _decode_chunk(one_chip, serving, engine, params, state, window)
    b, steps = engine["max_batch"], engine["decode_chunk_size"]
    assert taken == {
        f"decode_attention b={b} w={window}": "pallas",
        f"decode_append_write b={b} c={steps}": "pallas",
    }
    text = compiled.as_text()
    assert len(re.findall(r"custom_call_target=\"tpu_custom_call\"", text)) == 1
    _append_leaves_are_the_kernels_alone(text, (cfg.n_layers, cfg.n_kv_heads, b, steps, cfg.head_dim))
    memory = compiled.memory_analysis()
    print("mistral decode chunk", window, "temporaries", memory.temp_size_in_bytes)
    assert memory.alias_size_in_bytes == b * cfg.max_seq_len * 2 * cfg.n_layers * cfg.n_kv_heads * (cfg.head_dim + 2)
    assert memory.temp_size_in_bytes < 400_000_000


@pytest.mark.parametrize("tokens,kv_bucket", [(256, 256), (32, 384), (256, 512)])
def test_ouros_chunk_program_takes_a_slots_planes_out_and_puts_them_back(one_chip, ouro, tokens, kv_bucket):
    """``LlamaServing.prefill_row`` (the scheduler's ``_prefill_suffix``) at
    three of the mix's shapes: a lone admission's 256@256, a second
    chunk's 32@384 and 256@512.  The slots go through in place; what it
    holds beside them is one slot's 192 planes out and back (0.61 GB
    each way), under 1.1 GB."""
    serving, engine, params, state = ouro
    spec = _spec(one_chip)

    @functools.partial(jax.jit, donate_argnums=(1,), static_argnums=(6,))
    def chunk(params, cache, toks, start, n, slot, kv_bucket):
        cache, hidden, _ = serving.prefill_row(params, cache, toks, start, n, slot, kv_bucket)
        return cache, serving.logits(params, hidden[0, jnp.maximum(n - 1, 0)][None, None, :])[:, 0]

    scalar = spec((), jnp.int32)
    compiled = chunk.lower(
        params, state, spec((1, tokens), jnp.int32), scalar, scalar, scalar, kv_bucket).compile()
    memory = compiled.memory_analysis()
    print("ouro chunk", tokens, kv_bucket, "temporaries", memory.temp_size_in_bytes)
    assert memory.alias_size_in_bytes == SLOTS_BYTES
    assert memory.temp_size_in_bytes < 1_100_000_000
