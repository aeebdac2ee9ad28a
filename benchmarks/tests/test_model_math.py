"""Bytes and operations against hand counts for both configurations."""

import json
from pathlib import Path

import pytest

import model_math

CONFIGS = Path(__file__).resolve().parents[1] / "configs"


def load(name):
    model = json.loads((CONFIGS / f"{name}.json").read_text())
    return model, model["engine"]


def test_mistral_7b_by_hand():
    model, engine = load("mistral-7b")
    attn = 4096 * (32 + 2 * 8) * 128 + 32 * 128 * 4096  # wqkv + wo
    mlp = 3 * 4096 * 14336
    assert attn == 41_943_040 and mlp == 176_160_768
    head = 4096 * 32768
    # int8: one byte a parameter
    assert model_math.weight_bytes(model, engine) == 32 * (attn + mlp) + head == 7_113_539_584
    # K and V, 32 layers, 8 heads, 128 int8 + one bf16 scale
    assert model_math.kv_bytes_per_token(model, engine) == 2 * 32 * 8 * 130 == 66_560
    assert model_math.active_params(model) == 32 * (attn + mlp) == 6_979_321_856
    assert model_math.decode_step_bytes(model, engine, 10_000) == 7_113_539_584 + 665_600_000


def test_mixtral_l4_by_hand():
    model, engine = load("mixtral-8x7b-l4")
    attn = 41_943_040
    expert = 3 * 4096 * 14336
    router = 4096 * 8
    layer_bytes = attn * 1 + 8 * expert * 2 + router * 2  # experts and router stay bf16
    assert layer_bytes == 41_943_040 + 2_818_572_288 + 65_536
    assert model_math.weight_bytes(model, engine) == 4 * layer_bytes + 4096 * 32000
    assert model_math.kv_bytes_per_token(model, engine) == 2 * 4 * 8 * 130 == 8_320
    # a token multiplies 2 of the 8 experts
    assert model_math.active_params(model) == 4 * (attn + 2 * expert + router) == 1_577_189_376


def test_prefill_flops_by_hand():
    model, _ = load("mistral-7b")
    # 256 new tokens after 192 cached ones: positions 192..447
    pairs = sum(p + 1 for p in range(192, 448))
    assert model_math.causal_pairs(192, 448) == pairs
    want = 2 * 6_979_321_856 * 256 + 4 * 32 * 32 * 128 * pairs
    assert model_math.prefill_flops(model, 256, pairs) == pytest.approx(want)


def test_bf16_kv_is_twice_the_head_dim():
    model, engine = load("mistral-7b")
    assert model_math.kv_bytes_per_token(model, {**engine, "kv_dtype": "bfloat16"}) == 2 * 32 * 8 * 256
