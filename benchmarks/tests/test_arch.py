"""The architecture module: the mapping to the program's configuration
(golden values: what run.py built before the mapping moved here), the
two repairs in the counts, and the loader."""

import dataclasses
import json
from pathlib import Path

import pytest

import model_math
import run

CONFIGS = Path(__file__).resolve().parents[1] / "configs"

# LlamaConfig's defaults that no benchmarked configuration sets.
REST = {
    "rope_theta": 1000000.0, "norm_eps": 1e-05, "max_seq_len": 2048, "kv_dtype": "int8",
    "n_experts_per_tok": 2, "expert_capacity_factor": 1.25, "remat": True,
    "hidden_act": "silu", "scale_embeddings": False, "norm_unit_offset": False,
    "norm_type": "rmsnorm", "proj_bias": False, "mlp_gated": True,
}
TINY = {"vocab_size": 512, "d_model": 64, "n_layers": 2, "n_heads": 4, "n_kv_heads": 2,
        "head_dim": 16, "d_ff": 128}
WIDE = {"d_model": 4096, "n_heads": 32, "n_kv_heads": 8, "head_dim": 128, "d_ff": 14336}
GOLDEN = {
    ("mistral-7b", False): {**WIDE, "vocab_size": 32768, "n_layers": 32, "dtype": "bfloat16",
                            "n_experts": 0, "moe_dropless": False},
    ("mistral-7b", True): {**TINY, "dtype": "bfloat16", "n_experts": 0, "moe_dropless": False},
    ("mixtral-8x7b-l4", False): {**WIDE, "vocab_size": 32000, "n_layers": 4, "dtype": "bfloat16",
                                 "n_experts": 8, "moe_dropless": True},
    ("mixtral-8x7b-l4", True): {**TINY, "dtype": "float32", "n_experts": 4, "moe_dropless": True},
}


def sizes(name, rehearse):
    model = json.loads((CONFIGS / f"{name}.json").read_text())
    engine = dict(model["engine"])
    if rehearse:  # as run.py lays them over
        model.update(model["rehearse"]["model"])
        engine.update(model["rehearse"]["engine"])
    return model, engine


@pytest.mark.parametrize("name,rehearse", sorted(GOLDEN))
def test_mapping_is_field_for_field_the_parents(name, rehearse):
    model, engine = sizes(name, rehearse)
    cfg = run.load_arch(model).llama_config(model, engine)
    assert dataclasses.asdict(cfg) == {**REST, **GOLDEN[(name, rehearse)]}


def test_head_dim_is_derived_and_the_familys_expert_key_is_read():
    model, engine = sizes("mixtral-8x7b-l4", False)
    del model["head_dim"]
    model["num_experts"] = model.pop("num_local_experts")  # OLMoE's key
    cfg = run.load_arch(model).llama_config(model, engine)
    assert (cfg.head_dim, cfg.n_experts, cfg.moe_dropless) == (128, 8, True)
    assert model_math.n_experts({"n_routed_experts": 64}) == 64
    assert model_math.n_experts({}) == 0
    whole, _ = sizes("mixtral-8x7b-l4", False)
    assert model_math.weight_bytes(model, engine) == model_math.weight_bytes(whole, engine)


def test_expert_bytes_follow_the_configuration_not_the_expert_count():
    model, engine = sizes("mixtral-8x7b-l4", False)
    experts = 4 * 8 * 3 * 4096 * 14336
    bf16 = model_math.weight_bytes(model, engine)
    int8 = model_math.weight_bytes(model, {**engine, "expert_weight_dtype": "int8"})
    assert bf16 - int8 == experts  # two bytes a weight -> one
    assert bf16 == model_math.weight_bytes(model, {**engine, "expert_weight_dtype": "bfloat16"})
    dense, dense_engine = sizes("mistral-7b", False)
    assert model_math.weight_bytes(
        dense, {**dense_engine, "expert_weight_dtype": "bfloat16"}
    ) == model_math.weight_bytes(dense, dense_engine)  # no experts: the key says nothing


def test_loader_names_what_is_missing(tmp_path, monkeypatch, capsys):
    monkeypatch.setattr(run, "HERE", tmp_path)
    monkeypatch.setattr(run, "REPO", tmp_path)
    with pytest.raises(SystemExit):
        run.load_arch({"arch": "absent"})
    assert "arch/absent.py does not exist" in capsys.readouterr().err
    (tmp_path / "arch").mkdir()
    (tmp_path / "arch" / "half.py").write_text("def last_logits(*a):\n    return None\n")
    with pytest.raises(SystemExit):
        run.load_arch({"arch": "half"})
    err = capsys.readouterr().err
    assert "arch/half.py lacks" in err
    assert "llama_config" in err and "prefill_flops" in err and "last_logits" not in err
