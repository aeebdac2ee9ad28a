"""Model weight management: preset resolution + HF checkpoint conversion.

The reference pulls engine weights as opaque NIM containers / NGC downloads
(``docker-compose-nim-ms.yaml:86-164``).  Here weights are explicit: HF
safetensors checkpoints convert directly into our functional param trees
(llama: half-split RoPE keeps HF layout, so conversion is pure reshaping),
and orbax handles sharded native checkpoints.

With no checkpoint available (e.g. zero-egress environments), models run
random-initialized — every code path stays exercisable; only output quality
needs real weights.
"""

from __future__ import annotations

import json
import os
import re
from typing import Optional

import jax
import numpy as np

from generativeaiexamples_tpu.core.logging import get_logger
from generativeaiexamples_tpu.models import llama

logger = get_logger(__name__)

WEIGHTS_DIR_ENV = "GAIE_WEIGHTS_DIR"


# Per-layer projection leaves the W8A8 streaming kernel consumes, in both
# the packed serving layout (pack_for_serving) and the unpacked fallback.
# lm_head/embed/router stay in the weight-only QuantizedMatrix layout:
# the head is handled by models.llama.logits directly, and the router is
# far too small to be bandwidth-bound.
PREBLOCK_TARGETS = (
    "wqkv", "w_gu", "wq", "wk", "wv", "w_gate", "w_up", "w_down", "wo",
)


def preblock_llama_params(params, *, block_n: Optional[int] = None):
    """Pre-block int8 projection leaves into the kernel's tile layout.

    Converts every serving projection that is already a weight-only
    :class:`~generativeaiexamples_tpu.ops.quant.QuantizedMatrix` into a
    :class:`~generativeaiexamples_tpu.ops.qmm.BlockedQuantizedMatrix`
    whose ``(NB, K, BN)`` int8 tiles the Pallas W8A8 kernel DMAs straight
    from HBM.  Runs ONCE at load time — the blocked layout lives in the
    param tree, so no decode step ever re-tiles (asserted by the
    dispatch-count test via ``ops.qmm.BLOCK_EVENTS``).

    Float leaves pass through untouched (blocking only applies to the
    quantized serving path), as does an already-blocked tree (idempotent,
    e.g. an autoscale-grown replica sharing the parent's params).
    """
    from generativeaiexamples_tpu.ops.qmm import (
        BlockedQuantizedMatrix,
        block_matrix,
    )
    from generativeaiexamples_tpu.ops.quant import QuantizedMatrix

    layers = dict(params["layers"])
    for name in PREBLOCK_TARGETS:
        leaf = layers.get(name)
        if isinstance(leaf, BlockedQuantizedMatrix):
            continue  # idempotent
        if isinstance(leaf, QuantizedMatrix):
            layers[name] = block_matrix(leaf, block_n=block_n)
    return {**params, "layers": layers}


def resolve_model_preset(model_name: str) -> str:
    """Map a model name (HF id or NIM-style) to an engine preset."""
    name = model_name.lower()
    if name.startswith("ling") or "/ling" in name:
        # models/hybrid.py's presets (layer kinds), not llama's.
        return "ling-tiny" if "tiny" in name else "ling-3.0-flash-vl-l7e128"
    if name.startswith("mellum") or "/mellum" in name:
        return "mellum-tiny" if "tiny" in name else "mellum2-12b-a2.5b-l12"
    if "exaone" in name:
        return "exaone_moe-tiny" if "tiny" in name else "k-exaone-236b-a23b-l5e16"
    if "mistral-small-4" in name or name.startswith("mistral4"):
        return "mistral4-tiny" if "tiny" in name else "mistral-small-4-119b-l6e32"
    if "zaya" in name:
        return "zaya-tiny" if "tiny" in name else "zaya1-8b-l20"
    if "nemotron" in name:
        return "nemotron_h-tiny" if "tiny" in name else "nemotron-3-super-120b-a12b-l11e128"
    if "dots3" in name:
        return "dots3_note-tiny" if "tiny" in name else "dots3-note-prev-l6e32"
    if "deepseek" in name:
        return "deepseek_v32-tiny" if "tiny" in name else "deepseek-v3.2-l5e16"
    if "longcat" in name:
        return "longcat_flash-tiny" if "tiny" in name else "longcat-flash-chat-l4e16"
    if "mixtral" in name or "8x7b" in name:
        return "mixtral-8x7b"
    if "gemma" in name:
        if "tiny" in name:
            return "gemma-tiny"
        return "gemma-7b" if "7b" in name else "gemma-2b"
    if "starcoder" in name:
        return "starcoder2-tiny" if "tiny" in name else "starcoder2-3b"
    if "moe" in name and "tiny" in name:
        return "llama-moe-tiny"
    if "70b" in name:
        return "llama3-70b"
    # (?<!\d): a bare "1b" substring would also match 11b/21b/51b names.
    if re.search(r"(?<!\d)1b", name) and ("3.2" in name or "llama" in name):
        return "llama3.2-1b"
    if "8b" in name or "llama-3" in name or "llama3" in name:
        return "llama3-8b"
    if "tiny" in name:
        return "llama-tiny"
    logger.warning("unknown model %r; defaulting to llama-tiny preset", model_name)
    return "llama-tiny"


def weights_dir_for(model_name: str) -> Optional[str]:
    """Local checkpoint dir for a model, if one is provisioned."""
    root = os.environ.get(WEIGHTS_DIR_ENV, "")
    if not root:
        return None
    cand = os.path.join(root, model_name.replace("/", "--"))
    return cand if os.path.isdir(cand) else None


def _open_safetensors(path: str):
    """Minimal safetensors reader: returns {name: np.ndarray (lazy copy)}."""
    import mmap

    dtypes = {
        "F32": np.float32,
        "F16": np.float16,
        "BF16": np.uint16,  # reinterpreted below
        "I64": np.int64,
        "I32": np.int32,
    }
    with open(path, "rb") as fh:
        header_len = int.from_bytes(fh.read(8), "little")
        header = json.loads(fh.read(header_len))
        base = 8 + header_len
        mm = mmap.mmap(fh.fileno(), 0, access=mmap.ACCESS_READ)
    tensors = {}
    for name, meta in header.items():
        if name == "__metadata__":
            continue
        dt = dtypes[meta["dtype"]]
        start, end = meta["data_offsets"]
        arr = np.frombuffer(mm, dtype=dt, count=(end - start) // np.dtype(dt).itemsize, offset=base + start)
        arr = arr.reshape(meta["shape"])
        if meta["dtype"] == "BF16":
            # bf16 -> f32 via bit-shift into the high mantissa.
            arr = (arr.astype(np.uint32) << 16).view(np.float32)
        tensors[name] = arr
    return tensors


def _load_safetensors_dir(ckpt_dir: str) -> dict[str, np.ndarray]:
    import glob

    shards = sorted(glob.glob(os.path.join(ckpt_dir, "*.safetensors")))
    if not shards:
        raise FileNotFoundError(f"no safetensors found in {ckpt_dir}")
    tensors: dict[str, np.ndarray] = {}
    for s in shards:
        tensors.update(_open_safetensors(s))
    return tensors


def _stack_layers(
    tensors: dict, fmt: str, n_layers: int, dt, transpose: bool = True
) -> jax.Array:
    """Stack per-layer HF tensors onto a leading layer axis, transposing
    (out, in) -> (in, out) matmul weights.  Shared by every causal-LM
    converter in this module."""
    mats = []
    for i in range(n_layers):
        w = tensors[fmt.format(i)]
        mats.append(w.T if transpose else w)
    return jax.numpy.asarray(np.stack(mats), dtype=dt)


def llama_config_from_hf(ckpt_dir: str, **overrides) -> "llama.LlamaConfig":
    """Build a LlamaConfig from a HF checkpoint's ``config.json``
    (LlamaForCausalLM-class fields) instead of a by-name preset — the
    path real downloaded checkpoints take, where config.json is the
    source of truth for geometry (``deploy/scripts/fetch_and_convert.py``)."""
    import dataclasses

    with open(os.path.join(ckpt_dir, "config.json"), encoding="utf-8") as fh:
        hf = json.load(fh)
    # Refuse non-llama families loudly: gemma/starcoder2 carry the same
    # config keys but need different architecture knobs (gelu_tanh,
    # embedding scaling, layernorm+bias) — converting them through the
    # llama mapping would serve confident garbage with no diagnostic.
    mtype = hf.get("model_type", "llama")
    archs = hf.get("architectures") or []
    if mtype not in ("llama", "mistral", "ouro") or any(
        "Llama" not in a and "Mistral" not in a and "Ouro" not in a
        for a in archs
    ):
        raise ValueError(
            f"checkpoint is model_type={mtype!r} architectures={archs!r}; "
            "llama_config_from_hf only maps the llama/mistral family and "
            "the looped ouro family — use the matching preset + converter "
            "for other families"
        )
    looped = {}
    if mtype == "ouro":
        # The looped stack (modeling_ouro.py): llama-shaped layers with a
        # norm on each sub-layer's output too, applied total_ut_steps times.
        looped = {
            "ut_steps": int(hf["total_ut_steps"]),
            "sandwich_norm": True,
            "early_exit_threshold": float(hf.get("early_exit_threshold", 1.0)),
        }
    n_heads = hf["num_attention_heads"]
    cfg = llama.LlamaConfig(
        vocab_size=hf["vocab_size"],
        d_model=hf["hidden_size"],
        n_layers=hf["num_hidden_layers"],
        n_heads=n_heads,
        n_kv_heads=hf.get("num_key_value_heads", n_heads),
        head_dim=hf.get("head_dim") or hf["hidden_size"] // n_heads,
        d_ff=hf["intermediate_size"],
        rope_theta=float(hf.get("rope_theta", 10000.0)),
        norm_eps=float(hf.get("rms_norm_eps", 1e-5)),
        max_seq_len=min(int(hf.get("max_position_embeddings", 8192)), 8192),
        **looped,
    )
    return dataclasses.replace(cfg, **overrides)


_ST_DTYPES = {"float32": "F32", "float16": "F16", "bfloat16": "BF16"}


def save_safetensors(tensors: dict, path: str) -> None:
    """Write ``{name: np.ndarray}`` as a safetensors file.

    Counterpart of :func:`_open_safetensors` for generating HF-format
    checkpoints locally (the fetch-and-convert rehearsal fixture).
    float32/float16 arrays store natively; ml_dtypes bfloat16 stores as
    BF16 via a uint16 view.
    """
    header: dict = {}
    blobs: list[bytes] = []
    offset = 0
    for name, arr in tensors.items():
        arr = np.ascontiguousarray(arr)
        if arr.dtype.name == "bfloat16":
            st_dt = "BF16"
            raw = arr.view(np.uint16).tobytes()
        else:
            st_dt = _ST_DTYPES[arr.dtype.name]
            raw = arr.tobytes()
        header[name] = {
            "dtype": st_dt,
            "shape": list(arr.shape),
            "data_offsets": [offset, offset + len(raw)],
        }
        blobs.append(raw)
        offset += len(raw)
    head = json.dumps(header).encode()
    with open(path, "wb") as fh:
        fh.write(len(head).to_bytes(8, "little"))
        fh.write(head)
        for raw in blobs:
            fh.write(raw)


def load_hf_llama(cfg: llama.LlamaConfig, ckpt_dir: str) -> llama.Params:
    """Convert a HF llama/Mixtral safetensors checkpoint into our param tree.

    HF layout (model.layers.N.self_attn.q_proj.weight etc., (out, in)) maps
    to ours ((in, out), layers stacked on axis 0).  RoPE convention is
    half-split in both, so no permutation is required.  Mixtral MoE layers
    (``block_sparse_moe.gate`` router + per-expert ``w1``/``w3``/``w2`` =
    gate/up/down) stack onto our (L, E, ...) expert tensors.
    """
    tensors = _load_safetensors_dir(ckpt_dir)
    dt = cfg.compute_dtype

    def t(name: str) -> np.ndarray:
        return tensors[name]

    def stack_layers(fmt: str, transpose: bool = True) -> jax.Array:
        return _stack_layers(tensors, fmt, cfg.n_layers, dt, transpose)

    if cfg.n_experts > 1:

        def stack_experts(fmt: str) -> jax.Array:
            # (L, E, in, out) from HF (out, in) per expert.
            mats = [
                np.stack(
                    [t(fmt.format(i, e)).T for e in range(cfg.n_experts)]
                )
                for i in range(cfg.n_layers)
            ]
            return jax.numpy.asarray(np.stack(mats), dtype=dt)

        mlp = {
            "router": stack_layers(
                "model.layers.{}.block_sparse_moe.gate.weight"
            ),
            "w_gate_e": stack_experts(
                "model.layers.{}.block_sparse_moe.experts.{}.w1.weight"
            ),
            "w_up_e": stack_experts(
                "model.layers.{}.block_sparse_moe.experts.{}.w3.weight"
            ),
            "w_down_e": stack_experts(
                "model.layers.{}.block_sparse_moe.experts.{}.w2.weight"
            ),
        }
    else:
        mlp = {
            "w_gate": stack_layers("model.layers.{}.mlp.gate_proj.weight"),
            "w_up": stack_layers("model.layers.{}.mlp.up_proj.weight"),
            "w_down": stack_layers("model.layers.{}.mlp.down_proj.weight"),
        }

    params = {
        "embed": jax.numpy.asarray(t("model.embed_tokens.weight"), dtype=dt),
        "layers": {
            "attn_norm": stack_layers(
                "model.layers.{}.input_layernorm.weight", transpose=False
            ),
            "wq": stack_layers("model.layers.{}.self_attn.q_proj.weight"),
            "wk": stack_layers("model.layers.{}.self_attn.k_proj.weight"),
            "wv": stack_layers("model.layers.{}.self_attn.v_proj.weight"),
            "wo": stack_layers("model.layers.{}.self_attn.o_proj.weight"),
            "mlp_norm": stack_layers(
                "model.layers.{}.post_attention_layernorm.weight", transpose=False
            ),
            **mlp,
        },
        "final_norm": jax.numpy.asarray(t("model.norm.weight"), dtype=dt),
    }
    if cfg.sandwich_norm:
        # modeling_ouro.py's names for the norms on a sub-layer's output.
        params["layers"]["attn_post_norm"] = stack_layers(
            "model.layers.{}.input_layernorm_2.weight", transpose=False
        )
        params["layers"]["mlp_post_norm"] = stack_layers(
            "model.layers.{}.post_attention_layernorm_2.weight", transpose=False
        )
    if cfg.ut_steps > 1:
        # The exit gate, nn.Linear(hidden_size, 1): weight (1, D), bias (1,).
        params["exit_gate"] = {
            "w": jax.numpy.asarray(t("model.early_exit_gate.weight")[0], dtype=dt),
            "b": jax.numpy.asarray(t("model.early_exit_gate.bias")[0], dtype=dt),
        }
    if "lm_head.weight" in tensors:
        params["lm_head"] = jax.numpy.asarray(t("lm_head.weight").T, dtype=dt)
    else:  # tied embeddings
        params["lm_head"] = params["embed"].T
    logger.info("loaded %d HF tensors from %s", len(tensors), ckpt_dir)
    return params


def bert_config_from_hf(ckpt_dir: str, **overrides):
    """Build a BertConfig from a HF checkpoint's config.json."""
    from generativeaiexamples_tpu.models import bert

    with open(os.path.join(ckpt_dir, "config.json")) as fh:
        c = json.load(fh)
    kw = dict(
        vocab_size=c["vocab_size"],
        d_model=c["hidden_size"],
        n_layers=c["num_hidden_layers"],
        n_heads=c["num_attention_heads"],
        d_ff=c["intermediate_size"],
        max_positions=c["max_position_embeddings"],
        type_vocab_size=c.get("type_vocab_size", 2),
        norm_eps=c.get("layer_norm_eps", 1e-12),
    )
    kw.update(overrides)
    return bert.BertConfig(**kw)


def vit_config_from_hf(ckpt_dir: str, **overrides):
    """Build a ViTConfig from a HF checkpoint's config.json."""
    from generativeaiexamples_tpu.models import vision

    with open(os.path.join(ckpt_dir, "config.json")) as fh:
        c = json.load(fh)
    kw = dict(
        image_size=c["image_size"],
        patch_size=c["patch_size"],
        d_model=c["hidden_size"],
        n_layers=c["num_hidden_layers"],
        n_heads=c["num_attention_heads"],
        d_ff=c["intermediate_size"],
        norm_eps=c.get("layer_norm_eps", 1e-6),
    )
    kw.update(overrides)
    return vision.ViTConfig(**kw)


def _prefixed(tensors: dict[str, np.ndarray], prefix: str) -> dict[str, np.ndarray]:
    """Strip a submodel prefix (e.g. ``bert.``) when present."""
    if any(k.startswith(prefix) for k in tensors):
        return {
            k[len(prefix):]: v for k, v in tensors.items() if k.startswith(prefix)
        } | {k: v for k, v in tensors.items() if not k.startswith(prefix)}
    return tensors


def load_hf_causal_lm(cfg, ckpt_dir: str):
    """Config-dispatched HF causal-LM converter: llama/gemma/mixtral
    checkpoints share one tensor map; the GPT family (layernorm +
    biases, ungated MLP) routes to :func:`load_hf_starcoder2`."""
    if cfg.norm_type == "layernorm" or cfg.proj_bias:
        if cfg.mlp_gated:
            raise ValueError(
                "no HF converter for gated-MLP configs with layernorm/"
                "biases (no published checkpoint family has this shape)"
            )
        return load_hf_starcoder2(cfg, ckpt_dir)
    return load_hf_llama(cfg, ckpt_dir)


def load_hf_starcoder2(cfg, ckpt_dir: str) -> "llama.Params":
    """Convert a HF Starcoder2ForCausalLM checkpoint into our param tree.

    GPT-family layout: LayerNorm (weight+bias) norms, biased q/k/v/o and
    c_fc/c_proj projections, plain (ungated) MLP; ``c_fc -> w_up``,
    ``c_proj -> w_down``.  Rope is half-split like llama, so no
    permutation (``models/StarCoder2/lora.ipynb`` is the reference
    recipe this enables).
    """
    tensors = _load_safetensors_dir(ckpt_dir)
    dt = cfg.compute_dtype
    # Geometry guard: stack_layers indexes by cfg.n_layers, so a config
    # smaller than the checkpoint (e.g. the 3b preset against a 7b/15b
    # checkpoint — resolve_model_preset knows only the 3b geometry) would
    # silently load a truncated model.
    n_ckpt = len(
        {
            k.split(".")[2]
            for k in tensors
            if k.startswith("model.layers.")
        }
    )
    if n_ckpt != cfg.n_layers:
        raise ValueError(
            f"checkpoint has {n_ckpt} layers but config expects "
            f"{cfg.n_layers} — pass a matching preset/overrides "
            "(starcoder2-7b/15b need their own geometry)"
        )

    def t(name: str) -> np.ndarray:
        return tensors[name]

    def stack_layers(fmt: str, transpose: bool = True) -> jax.Array:
        return _stack_layers(tensors, fmt, cfg.n_layers, dt, transpose)

    params = {
        "embed": jax.numpy.asarray(t("model.embed_tokens.weight"), dtype=dt),
        "layers": {
            "attn_norm": stack_layers(
                "model.layers.{}.input_layernorm.weight", transpose=False
            ),
            "attn_norm_b": stack_layers(
                "model.layers.{}.input_layernorm.bias", transpose=False
            ),
            "wq": stack_layers("model.layers.{}.self_attn.q_proj.weight"),
            "bq": stack_layers(
                "model.layers.{}.self_attn.q_proj.bias", transpose=False
            ),
            "wk": stack_layers("model.layers.{}.self_attn.k_proj.weight"),
            "bk": stack_layers(
                "model.layers.{}.self_attn.k_proj.bias", transpose=False
            ),
            "wv": stack_layers("model.layers.{}.self_attn.v_proj.weight"),
            "bv": stack_layers(
                "model.layers.{}.self_attn.v_proj.bias", transpose=False
            ),
            "wo": stack_layers("model.layers.{}.self_attn.o_proj.weight"),
            "bo": stack_layers(
                "model.layers.{}.self_attn.o_proj.bias", transpose=False
            ),
            "mlp_norm": stack_layers(
                "model.layers.{}.post_attention_layernorm.weight",
                transpose=False,
            ),
            "mlp_norm_b": stack_layers(
                "model.layers.{}.post_attention_layernorm.bias",
                transpose=False,
            ),
            "w_up": stack_layers("model.layers.{}.mlp.c_fc.weight"),
            "b_up": stack_layers(
                "model.layers.{}.mlp.c_fc.bias", transpose=False
            ),
            "w_down": stack_layers("model.layers.{}.mlp.c_proj.weight"),
            "b_down": stack_layers(
                "model.layers.{}.mlp.c_proj.bias", transpose=False
            ),
        },
        "final_norm": jax.numpy.asarray(t("model.norm.weight"), dtype=dt),
        "final_norm_b": jax.numpy.asarray(t("model.norm.bias"), dtype=dt),
    }
    if "lm_head.weight" in tensors:
        params["lm_head"] = jax.numpy.asarray(t("lm_head.weight").T, dtype=dt)
    else:  # tied embeddings (starcoder2-3b/7b)
        params["lm_head"] = params["embed"].T
    logger.info(
        "loaded %d HF starcoder2 tensors from %s", len(tensors), ckpt_dir
    )
    return params


def w2v2_config_from_hf(ckpt_dir: str, **overrides):
    """Wav2Vec2Config from a HF checkpoint's ``config.json`` — geometry
    (vocab/width/depth/conv stack) comes from the checkpoint, not a
    preset, so custom-vocab CTC fine-tunes load with the right head and
    decode table size.  Refuses non-wav2vec2 and layer-norm-variant
    checkpoints loudly (the converter below only maps the group-norm
    family)."""
    import dataclasses

    from generativeaiexamples_tpu.models import speech

    with open(os.path.join(ckpt_dir, "config.json"), encoding="utf-8") as fh:
        hf = json.load(fh)
    if hf.get("model_type", "wav2vec2") != "wav2vec2":
        raise ValueError(
            f"checkpoint is model_type={hf.get('model_type')!r}, "
            "not wav2vec2"
        )
    if hf.get("do_stable_layer_norm", False):
        raise ValueError(
            "layer-norm wav2vec2 variant (do_stable_layer_norm=True) is "
            "not supported; use a wav2vec2-base-960h-class checkpoint"
        )
    cfg = speech.Wav2Vec2Config(
        vocab_size=hf.get("vocab_size", 32),
        d_model=hf.get("hidden_size", 768),
        n_layers=hf.get("num_hidden_layers", 12),
        n_heads=hf.get("num_attention_heads", 12),
        d_ff=hf.get("intermediate_size", 3072),
        conv_dim=tuple(hf.get("conv_dim", (512,) * 7)),
        conv_kernel=tuple(hf.get("conv_kernel", (10, 3, 3, 3, 3, 2, 2))),
        conv_stride=tuple(hf.get("conv_stride", (5, 2, 2, 2, 2, 2, 2))),
        pos_conv_kernel=hf.get("num_conv_pos_embeddings", 128),
        pos_conv_groups=hf.get("num_conv_pos_embedding_groups", 16),
        norm_eps=float(hf.get("layer_norm_eps", 1e-5)),
    )
    return dataclasses.replace(cfg, **overrides)


def load_hf_wav2vec2(cfg, ckpt_dir: str):
    """Convert a HF ``Wav2Vec2ForCTC`` checkpoint (wav2vec2-base-960h
    class: group-norm feature extractor, post-LN encoder) into the
    ``models.speech`` wav2vec2 param tree.

    Conv kernels move from HF (out, in, k) to our (k, in, out) TIO
    layout.  The positional conv is weight-normalized in HF — stored as
    ``weight_g``/``weight_v`` (old torch) or
    ``parametrizations.weight.original{0,1}`` (new torch); the effective
    weight ``g * v / ||v||`` is materialized here.
    """
    tensors = _load_safetensors_dir(ckpt_dir)
    # Refuse the LAYER-NORM feature-extractor variant
    # (do_stable_layer_norm=True, e.g. wav2vec2-large-960h-lv60-self):
    # it carries conv biases + per-conv-layer norms and a pre-LN encoder,
    # none of which this group-norm-variant loader maps — loading it
    # silently would transcribe confident garbage.
    if (
        "wav2vec2.feature_extractor.conv_layers.1.layer_norm.weight"
        in tensors
        or "wav2vec2.feature_extractor.conv_layers.0.conv.bias" in tensors
    ):
        raise ValueError(
            "checkpoint is the layer-norm wav2vec2 variant "
            "(do_stable_layer_norm=True); only the group-norm variant "
            "(wav2vec2-base-960h class) is supported"
        )

    # Geometry must match exactly: stack()/the conv loop index by cfg
    # sizes, so a too-small cfg would silently load a TRUNCATED model.
    n_enc = len(
        {
            k.split(".")[3]
            for k in tensors
            if k.startswith("wav2vec2.encoder.layers.")
        }
    )
    n_conv = len(
        {
            k.split(".")[3]
            for k in tensors
            if k.startswith("wav2vec2.feature_extractor.conv_layers.")
        }
    )
    if n_enc != cfg.n_layers or n_conv != len(cfg.conv_dim):
        raise ValueError(
            f"checkpoint geometry ({n_conv} conv / {n_enc} encoder layers) "
            f"does not match config ({len(cfg.conv_dim)} conv / "
            f"{cfg.n_layers} encoder layers)"
        )

    def t(name: str) -> np.ndarray:
        return tensors[f"wav2vec2.{name}"]

    def stack(fmt: str, transpose: bool = True) -> jax.Array:
        mats = []
        for i in range(cfg.n_layers):
            w = tensors[f"wav2vec2.{fmt.format(i)}"]
            mats.append(w.T if transpose else w)
        return jax.numpy.asarray(
            np.stack(mats), dtype=cfg.compute_dtype
        )

    dt = cfg.compute_dtype
    convs = []
    for i in range(len(cfg.conv_dim)):
        leaf = {
            "w": jax.numpy.asarray(
                t(f"feature_extractor.conv_layers.{i}.conv.weight")
                .transpose(2, 1, 0),
                dtype=dt,
            )
        }
        if i == 0:
            leaf["gn_g"] = jax.numpy.asarray(
                t("feature_extractor.conv_layers.0.layer_norm.weight"),
                dtype=dt,
            )
            leaf["gn_b"] = jax.numpy.asarray(
                t("feature_extractor.conv_layers.0.layer_norm.bias"),
                dtype=dt,
            )
        convs.append(leaf)

    pc = "encoder.pos_conv_embed.conv"
    if f"wav2vec2.{pc}.weight_g" in tensors:
        g, v = t(f"{pc}.weight_g"), t(f"{pc}.weight_v")
    else:
        g = t(f"{pc}.parametrizations.weight.original0")
        v = t(f"{pc}.parametrizations.weight.original1")
    # torch weight_norm(dim=2): one norm per kernel position, reduced
    # over the (out, in) dims — every axis EXCEPT dim 2.
    norm = np.sqrt((v.astype(np.float64) ** 2).sum(
        axis=tuple(d for d in range(v.ndim) if d != 2), keepdims=True
    ))
    pos_w = (g * v / np.maximum(norm, 1e-12)).astype(np.float32)

    def lnb(name):
        return (
            jax.numpy.asarray(t(f"{name}.weight"), dtype=dt),
            jax.numpy.asarray(t(f"{name}.bias"), dtype=dt),
        )

    fp_g, fp_b = lnb("feature_projection.layer_norm")
    enc_g, enc_b = lnb("encoder.layer_norm")
    params = {
        "conv_layers": convs,
        "fp_norm_g": fp_g,
        "fp_norm_b": fp_b,
        "fp_w": jax.numpy.asarray(
            t("feature_projection.projection.weight").T, dtype=dt
        ),
        "fp_b": jax.numpy.asarray(
            t("feature_projection.projection.bias"), dtype=dt
        ),
        "pos_conv_w": jax.numpy.asarray(pos_w.transpose(2, 1, 0), dtype=dt),
        "pos_conv_b": jax.numpy.asarray(t(f"{pc}.bias"), dtype=dt),
        "enc_norm_g": enc_g,
        "enc_norm_b": enc_b,
        "layers": {
            "wq": stack("encoder.layers.{}.attention.q_proj.weight"),
            "bq": stack(
                "encoder.layers.{}.attention.q_proj.bias", transpose=False
            ),
            "wk": stack("encoder.layers.{}.attention.k_proj.weight"),
            "bk": stack(
                "encoder.layers.{}.attention.k_proj.bias", transpose=False
            ),
            "wv": stack("encoder.layers.{}.attention.v_proj.weight"),
            "bv": stack(
                "encoder.layers.{}.attention.v_proj.bias", transpose=False
            ),
            "wo": stack("encoder.layers.{}.attention.out_proj.weight"),
            "bo": stack(
                "encoder.layers.{}.attention.out_proj.bias", transpose=False
            ),
            "ln1_g": stack(
                "encoder.layers.{}.layer_norm.weight", transpose=False
            ),
            "ln1_b": stack(
                "encoder.layers.{}.layer_norm.bias", transpose=False
            ),
            "ff_in_w": stack(
                "encoder.layers.{}.feed_forward.intermediate_dense.weight"
            ),
            "ff_in_b": stack(
                "encoder.layers.{}.feed_forward.intermediate_dense.bias",
                transpose=False,
            ),
            "ff_out_w": stack(
                "encoder.layers.{}.feed_forward.output_dense.weight"
            ),
            "ff_out_b": stack(
                "encoder.layers.{}.feed_forward.output_dense.bias",
                transpose=False,
            ),
            "ln2_g": stack(
                "encoder.layers.{}.final_layer_norm.weight", transpose=False
            ),
            "ln2_b": stack(
                "encoder.layers.{}.final_layer_norm.bias", transpose=False
            ),
        },
        "lm_head_w": jax.numpy.asarray(tensors["lm_head.weight"].T, dtype=dt),
        "lm_head_b": jax.numpy.asarray(tensors["lm_head.bias"], dtype=dt),
    }
    logger.info("loaded %d HF wav2vec2 tensors from %s", len(tensors), ckpt_dir)
    return params


def load_hf_bert(cfg, ckpt_dir: str, _tensors=None):
    """Convert a HF BERT checkpoint (arctic-embed-l class) to our tree.

    Accepts plain ``BertModel`` checkpoints and ``bert.``-prefixed task
    models.  The reference serves ``snowflake/arctic-embed-l`` — a BERT
    encoder — through the NeMo Retriever embedding container
    (``common/configuration.py:111-125``); this is the weight path that
    makes our TPU embedder produce the same embeddings.
    """
    tensors = _prefixed(
        _tensors if _tensors is not None else _load_safetensors_dir(ckpt_dir),
        "bert.",
    )
    dt = cfg.compute_dtype

    def t(name: str) -> np.ndarray:
        return tensors[name]

    def stack(fmt: str, transpose: bool) -> jax.Array:
        mats = []
        for i in range(cfg.n_layers):
            w = t(fmt.format(i))
            mats.append(w.T if transpose else w)
        return jax.numpy.asarray(np.stack(mats), dtype=dt)

    lay = "encoder.layer.{}."
    params = {
        "tok_embed": jax.numpy.asarray(
            t("embeddings.word_embeddings.weight"), dtype=dt
        ),
        "pos_embed": jax.numpy.asarray(
            t("embeddings.position_embeddings.weight"), dtype=dt
        ),
        "type_embed": jax.numpy.asarray(
            t("embeddings.token_type_embeddings.weight"), dtype=dt
        ),
        "embed_norm_g": jax.numpy.asarray(t("embeddings.LayerNorm.weight"), dtype=dt),
        "embed_norm_b": jax.numpy.asarray(t("embeddings.LayerNorm.bias"), dtype=dt),
        "layers": {
            "wq": stack(lay + "attention.self.query.weight", True),
            "bq": stack(lay + "attention.self.query.bias", False),
            "wk": stack(lay + "attention.self.key.weight", True),
            "bk": stack(lay + "attention.self.key.bias", False),
            "wv": stack(lay + "attention.self.value.weight", True),
            "bv": stack(lay + "attention.self.value.bias", False),
            "wo": stack(lay + "attention.output.dense.weight", True),
            "bo": stack(lay + "attention.output.dense.bias", False),
            "attn_norm_g": stack(lay + "attention.output.LayerNorm.weight", False),
            "attn_norm_b": stack(lay + "attention.output.LayerNorm.bias", False),
            "w_up": stack(lay + "intermediate.dense.weight", True),
            "b_up": stack(lay + "intermediate.dense.bias", False),
            "w_down": stack(lay + "output.dense.weight", True),
            "b_down": stack(lay + "output.dense.bias", False),
            "mlp_norm_g": stack(lay + "output.LayerNorm.weight", False),
            "mlp_norm_b": stack(lay + "output.LayerNorm.bias", False),
        },
    }
    logger.info("loaded %d HF BERT tensors from %s", len(tensors), ckpt_dir)
    return params


def load_hf_cross_encoder(cfg, ckpt_dir: str):
    """Convert a HF cross-encoder (BertForSequenceClassification) checkpoint.

    Returns ``(encoder_params, rerank_head)`` — the head carries the BERT
    pooler (tanh dense) plus the 1-logit classifier, matching HF scoring
    exactly.  Replaces the NeMo Retriever reranking microservice weights
    (reference ``docker-compose-nim-ms.yaml:59-84``).
    """
    tensors = _load_safetensors_dir(ckpt_dir)
    params = load_hf_bert(cfg, ckpt_dir, _tensors=tensors)
    stripped = _prefixed(tensors, "bert.")
    dt = cfg.compute_dtype
    cls_w = stripped["classifier.weight"]
    if cls_w.shape[0] != 1:
        raise ValueError(
            f"cross-encoder classifier must have 1 logit, got {cls_w.shape}"
        )
    head = {
        "w_pool": jax.numpy.asarray(stripped["pooler.dense.weight"].T, dtype=dt),
        "b_pool": jax.numpy.asarray(stripped["pooler.dense.bias"], dtype=dt),
        "w": jax.numpy.asarray(cls_w.T, dtype=dt),
        "b": jax.numpy.asarray(stripped["classifier.bias"], dtype=dt),
    }
    return params, head


def load_hf_vit(cfg, ckpt_dir: str):
    """Convert a HF ViTModel checkpoint to our vision param tree.

    The conv patch embedding becomes a (patch_dim, d_model) matmul weight
    matching ``vision.patchify``'s (p_row, p_col, channel) flattening —
    the TPU formulation runs patch projection as one MXU matmul instead
    of a convolution.  Basis for the Neva/DePlot-class vision path
    (reference ``custom_pdf_parser.py:42-71``).
    """
    tensors = _prefixed(_load_safetensors_dir(ckpt_dir), "vit.")
    dt = cfg.compute_dtype

    def t(name: str) -> np.ndarray:
        return tensors[name]

    def stack(fmt: str, transpose: bool) -> jax.Array:
        mats = []
        for i in range(cfg.n_layers):
            w = t(fmt.format(i))
            mats.append(w.T if transpose else w)
        return jax.numpy.asarray(np.stack(mats), dtype=dt)

    # Fused qkv: concatenate HF query/key/value along the output dim.
    wqkv, bqkv = [], []
    for i in range(cfg.n_layers):
        ws = [
            t(f"encoder.layer.{i}.attention.attention.{w}.weight").T
            for w in ("query", "key", "value")
        ]
        bs = [
            t(f"encoder.layer.{i}.attention.attention.{w}.bias")
            for w in ("query", "key", "value")
        ]
        wqkv.append(np.concatenate(ws, axis=1))
        bqkv.append(np.concatenate(bs, axis=0))

    conv = t("embeddings.patch_embeddings.projection.weight")  # (D, C, p, p)
    patch_proj = np.transpose(conv, (2, 3, 1, 0)).reshape(cfg.patch_dim, cfg.d_model)

    params = {
        "patch_proj": jax.numpy.asarray(patch_proj, dtype=dt),
        "patch_bias": jax.numpy.asarray(
            t("embeddings.patch_embeddings.projection.bias"), dtype=dt
        ),
        "pos_embed": jax.numpy.asarray(
            t("embeddings.position_embeddings")[0], dtype=dt
        ),
        "cls": jax.numpy.asarray(t("embeddings.cls_token"), dtype=dt),
        "layers": {
            "ln1_g": stack("encoder.layer.{}.layernorm_before.weight", False),
            "ln1_b": stack("encoder.layer.{}.layernorm_before.bias", False),
            "wqkv": jax.numpy.asarray(np.stack(wqkv), dtype=dt),
            "bqkv": jax.numpy.asarray(np.stack(bqkv), dtype=dt),
            "wo": stack("encoder.layer.{}.attention.output.dense.weight", True),
            "bo": stack("encoder.layer.{}.attention.output.dense.bias", False),
            "ln2_g": stack("encoder.layer.{}.layernorm_after.weight", False),
            "ln2_b": stack("encoder.layer.{}.layernorm_after.bias", False),
            "w1": stack("encoder.layer.{}.intermediate.dense.weight", True),
            "b1": stack("encoder.layer.{}.intermediate.dense.bias", False),
            "w2": stack("encoder.layer.{}.output.dense.weight", True),
            "b2": stack("encoder.layer.{}.output.dense.bias", False),
        },
        "final_ln_g": jax.numpy.asarray(t("layernorm.weight"), dtype=dt),
        "final_ln_b": jax.numpy.asarray(t("layernorm.bias"), dtype=dt),
    }
    logger.info("loaded %d HF ViT tensors from %s", len(tensors), ckpt_dir)
    return params


def save_orbax(params, path: str) -> None:
    """Persist a param tree as an orbax checkpoint (sharded-friendly)."""
    import orbax.checkpoint as ocp

    ckptr = ocp.StandardCheckpointer()
    ckptr.save(os.path.abspath(path), params)
    ckptr.wait_until_finished()


def load_orbax(abstract_params, path: str):
    import orbax.checkpoint as ocp

    ckptr = ocp.StandardCheckpointer()
    return ckptr.restore(os.path.abspath(path), abstract_params)


def load_orbax_sharded(cfg, path: str, mesh, rules=None):
    """Restore a llama checkpoint directly onto a device mesh.

    Every leaf is materialized with its serving partition spec's
    NamedSharding, so each host reads only its shards and no process ever
    holds the full unsharded tree in RAM — the load path for weights that
    exceed one host (llama3-70b across a TP mesh; the reference serves
    70B across GPUs the same way, ``docs/support-matrix.md:36-46``).
    """
    import orbax.checkpoint as ocp
    from jax.sharding import NamedSharding

    specs = llama.partition_specs(cfg, rules)
    abstract = jax.eval_shape(
        lambda: llama.init_params(cfg, jax.random.PRNGKey(0))
    )
    abstract = jax.tree.map(
        lambda a, spec: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=NamedSharding(mesh, spec)
        ),
        abstract,
        specs,
    )
    ckptr = ocp.StandardCheckpointer()
    return ckptr.restore(os.path.abspath(path), abstract)
