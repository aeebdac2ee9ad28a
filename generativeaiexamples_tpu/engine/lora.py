"""LoRA fine-tuning for the llama family.

Model-customization parity: the reference ships NeMo LoRA/SFT notebooks
(``models/Gemma/lora.ipynb``, ``models/NeMo/slm/``, SURVEY.md §2.6) that
run in external containers; here adapter tuning is a first-class jittable
path on the same mesh the serving engine uses.

Design: adapters are a separate pytree (stacked over layers like the base
params), gradients flow only through them (the base tree is a constant in
the loss), and the effective weight ``W + (alpha/r)·A@B`` is materialized
inside the rematerialized forward — so optimizer state exists only for the
adapters (the actual memory win of LoRA) while ``models.llama`` stays
unmodified.  ``merge_lora`` bakes adapters into base weights for serving.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
import optax

from generativeaiexamples_tpu.engine import training
from generativeaiexamples_tpu.models import llama

# Per-layer weights eligible for adaptation: name -> (in_dim, out_dim) fn.
_TARGET_DIMS = {
    "wq": lambda c: (c.d_model, c.n_heads * c.head_dim),
    "wk": lambda c: (c.d_model, c.n_kv_heads * c.head_dim),
    "wv": lambda c: (c.d_model, c.n_kv_heads * c.head_dim),
    "wo": lambda c: (c.n_heads * c.head_dim, c.d_model),
    "w_gate": lambda c: (c.d_model, c.d_ff),
    "w_up": lambda c: (c.d_model, c.d_ff),
    "w_down": lambda c: (c.d_ff, c.d_model),
}


@dataclasses.dataclass(frozen=True)
class LoRAConfig:
    rank: int = 8
    alpha: float = 16.0
    targets: tuple = ("wq", "wk", "wv", "wo")

    @property
    def scale(self) -> float:
        return self.alpha / self.rank

    def __post_init__(self):
        unknown = set(self.targets) - set(_TARGET_DIMS)
        if unknown:
            raise ValueError(f"unknown LoRA targets {sorted(unknown)}")


_DENSE_MLP_TARGETS = ("w_gate", "w_up", "w_down")


def init_lora_params(
    cfg: llama.LlamaConfig, lora: LoRAConfig, key: jax.Array
) -> dict:
    """A ~ N(0, 0.02), B = 0 (so the adapted model starts at the base)."""
    if not isinstance(cfg, llama.LlamaConfig):
        raise ValueError(
            f"LoRA is not served for {type(cfg).__name__}: the adapters' "
            "targets are the llama family's stacked projection leaves, and a "
            "model of layer kinds has per-layer leaves of other names"
        )
    if cfg.n_experts > 1:
        bad = [t for t in lora.targets if t in _DENSE_MLP_TARGETS]
        if bad:
            raise ValueError(
                f"LoRA targets {bad} are dense-MLP leaves, but the config "
                "is MoE (n_experts > 1) — those params do not exist; "
                "target attention projections instead"
            )
    if not cfg.mlp_gated and "w_gate" in lora.targets:
        raise ValueError(
            "LoRA target 'w_gate' does not exist on ungated-MLP configs "
            "(mlp_gated=False, e.g. starcoder2); target w_up/w_down"
        )
    out: dict = {}
    keys = jax.random.split(key, len(lora.targets))
    for k, name in zip(keys, lora.targets):
        d_in, d_out = _TARGET_DIMS[name](cfg)
        out[name] = {
            "a": (
                jax.random.normal(k, (cfg.n_layers, d_in, lora.rank), jnp.float32)
                * 0.02
            ).astype(cfg.compute_dtype),
            "b": jnp.zeros((cfg.n_layers, lora.rank, d_out), cfg.compute_dtype),
        }
    return out


def apply_lora(params: llama.Params, lora_params: dict, lora: LoRAConfig) -> llama.Params:
    """Effective params: W + scale * A@B per adapted layer weight.

    Pure function of (base, adapters) — used inside the training loss so
    autodiff reaches only the adapters, and by ``merge_lora`` for serving.
    """
    layers = dict(params["layers"])
    for name, ab in lora_params.items():
        delta = jnp.einsum("lir,lro->lio", ab["a"], ab["b"]) * lora.scale
        layers[name] = params["layers"][name] + delta.astype(params["layers"][name].dtype)
    return {**params, "layers": layers}


def merge_lora(
    params: llama.Params, lora_params: dict, lora: LoRAConfig
) -> llama.Params:
    """Bake adapters into base weights (serving-time merge)."""
    return jax.jit(apply_lora, static_argnums=(2,))(params, lora_params, lora)


def make_lora_train_step(
    cfg: llama.LlamaConfig,
    lora: LoRAConfig,
    optimizer,
    base_params: llama.Params,
    mesh=None,
):
    """train_step(state, batch) over adapter params only; jittable.

    ``state.params`` is the adapter tree; ``base_params`` is closed over as
    a constant (donate/placement handled by the caller's jit).
    """

    def loss(adapters, batch):
        eff = apply_lora(base_params, adapters, lora)
        return training.loss_fn(
            eff, cfg, batch["tokens"], batch["targets"], batch["mask"], mesh
        )

    def train_step(state: training.TrainState, batch):
        l, grads = jax.value_and_grad(loss)(state.params, batch)
        updates, opt_state = optimizer.update(grads, state.opt_state, state.params)
        params = optax.apply_updates(state.params, updates)
        return (
            training.TrainState(params, opt_state, state.step + 1),
            {"loss": l, "grad_norm": optax.global_norm(grads)},
        )

    return train_step


def init_lora_train_state(
    cfg: llama.LlamaConfig,
    lora: LoRAConfig,
    optimizer,
    key: Optional[jax.Array] = None,
) -> training.TrainState:
    adapters = init_lora_params(cfg, lora, key if key is not None else jax.random.PRNGKey(0))
    return training.TrainState(
        params=adapters,
        opt_state=optimizer.init(adapters),
        step=jnp.zeros((), jnp.int32),
    )


# -- SFT data preparation ---------------------------------------------------


def sft_example(
    prompt_ids: Sequence[int],
    response_ids: Sequence[int],
    max_len: int,
    pad_id: int = 0,
) -> dict[str, np.ndarray]:
    """One (prompt, response) pair -> next-token batch row with the loss
    masked to response positions only (standard SFT masking)."""
    ids = list(prompt_ids) + list(response_ids)
    ids = ids[: max_len + 1]
    tokens = ids[:-1]
    targets = ids[1:]
    # Mask: predict only response tokens (positions whose *target* is in
    # the response region).
    mask = [
        1.0 if t >= len(prompt_ids) else 0.0 for t in range(1, len(ids))
    ]
    pad = max_len - len(tokens)
    return {
        "tokens": np.asarray(tokens + [pad_id] * pad, np.int32),
        "targets": np.asarray(targets + [pad_id] * pad, np.int32),
        "mask": np.asarray(mask + [0.0] * pad, np.float32),
    }


def sft_batch(
    pairs: Sequence[tuple[Sequence[int], Sequence[int]]], max_len: int, pad_id: int = 0
) -> dict[str, jnp.ndarray]:
    rows = [sft_example(p, r, max_len, pad_id) for p, r in pairs]
    return {
        k: jnp.asarray(np.stack([r[k] for r in rows])) for k in rows[0]
    }


# -- persistence ------------------------------------------------------------


def save_lora(lora_params: dict, path: str) -> None:
    flat = {
        f"{name}.{ab}": np.asarray(mat)
        for name, d in lora_params.items()
        for ab, mat in d.items()
    }
    np.savez(path, **flat)


def load_lora(path: str, dtype=None) -> dict:
    data = np.load(path)
    out: dict = {}
    for key in data.files:
        name, ab = key.rsplit(".", 1)
        arr = jnp.asarray(data[key], dtype) if dtype else jnp.asarray(data[key])
        out.setdefault(name, {})[ab] = arr
    return out
