"""Real-checkpoint path rehearsal: HF snapshot ->
converter -> orbax shards -> engine boot, against a locally GENERATED
mid-size HF-format checkpoint (~127M params, not tiny) — so the day real
weights are reachable, serving them is a config change (reference
provisions via compose init jobs,
``deploy/compose/docker-compose-nim-ms.yaml:86-164``)."""

import importlib.util
import json
import os

import numpy as np

from generativeaiexamples_tpu.engine import weights


def _script():
    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "deploy",
        "scripts",
        "fetch_and_convert.py",
    )
    spec = importlib.util.spec_from_file_location("fetch_and_convert", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class TestSafetensorsWriter:
    def test_roundtrip_f32_and_bf16(self, tmp_path):
        import ml_dtypes

        tensors = {
            "a": np.arange(12, dtype=np.float32).reshape(3, 4),
            "b": (np.linspace(-2, 2, 8).astype(ml_dtypes.bfloat16)),
        }
        path = str(tmp_path / "t.safetensors")
        weights.save_safetensors(tensors, path)
        back = weights._open_safetensors(path)
        np.testing.assert_array_equal(back["a"], tensors["a"])
        # BF16 reads back as f32 (the reader's convention) bit-exactly.
        np.testing.assert_array_equal(
            back["b"], tensors["b"].astype(np.float32)
        )


class TestConfigFromHF:
    def test_fields_map(self, tmp_path):
        cfgd = {
            "vocab_size": 1000,
            "hidden_size": 64,
            "num_hidden_layers": 3,
            "num_attention_heads": 4,
            "num_key_value_heads": 2,
            "intermediate_size": 128,
            "rope_theta": 10000.0,
            "rms_norm_eps": 1e-6,
            "max_position_embeddings": 2048,
        }
        (tmp_path / "config.json").write_text(json.dumps(cfgd))
        cfg = weights.llama_config_from_hf(str(tmp_path))
        assert cfg.vocab_size == 1000 and cfg.d_model == 64
        assert cfg.n_layers == 3 and cfg.n_kv_heads == 2
        assert cfg.head_dim == 16  # hidden // heads when unspecified
        assert cfg.max_seq_len == 2048

    def test_head_dim_override(self, tmp_path):
        cfgd = {
            "vocab_size": 1000,
            "hidden_size": 64,
            "num_hidden_layers": 1,
            "num_attention_heads": 4,
            "head_dim": 32,
            "intermediate_size": 128,
        }
        (tmp_path / "config.json").write_text(json.dumps(cfgd))
        assert weights.llama_config_from_hf(str(tmp_path)).head_dim == 32


class TestRehearsal:
    def test_fixture_convert_shard_boot(self, tmp_path):
        """The full offline rehearsal at ~127M params: every stage of the
        production fetch-and-serve workflow minus the network."""
        mod = _script()
        ckpt_dir = mod.generate_fixture(str(tmp_path / "ckpt"))
        # The fixture is a real HF-format checkpoint.
        assert os.path.getsize(
            os.path.join(ckpt_dir, "model.safetensors")
        ) > 200e6
        cfg, params = mod.convert(ckpt_dir)
        assert cfg.d_model == 768 and cfg.n_layers == 12
        mod.shard(cfg, params, str(tmp_path / "orbax"))
        mod.boot(cfg, params, ckpt_dir)


class TestSnapshotComplete:
    def test_multi_shard_requires_every_shard(self, tmp_path):
        mod = _script()
        d = tmp_path / "ckpt"
        d.mkdir()
        (d / "config.json").write_text("{}")
        (d / "model.safetensors.index.json").write_text(
            json.dumps(
                {
                    "weight_map": {
                        "a.weight": "model-00001-of-00002.safetensors",
                        "b.weight": "model-00002-of-00002.safetensors",
                    }
                }
            )
        )
        (d / "model-00001-of-00002.safetensors").write_bytes(b"x")
        # One of two shards present: NOT complete (resume must run).
        assert not mod._snapshot_complete(str(d))
        (d / "model-00002-of-00002.safetensors").write_bytes(b"x")
        assert mod._snapshot_complete(str(d))

    def test_single_file_checkpoint(self, tmp_path):
        mod = _script()
        d = tmp_path / "ckpt"
        d.mkdir()
        assert not mod._snapshot_complete(str(d))
        (d / "config.json").write_text("{}")
        assert not mod._snapshot_complete(str(d))
        (d / "model.safetensors").write_bytes(b"x")
        assert mod._snapshot_complete(str(d))
