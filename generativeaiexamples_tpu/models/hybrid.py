"""A decoder made of layer kinds: each layer names its mixer (``kda`` or
``mla``) and its MLP (``dense`` or ``experts``), owns the parameters of
those kinds and keeps the state of its mixer's kind.

This is the model definition of the ``bailing_hybrid`` family
(Ling-3.0-flash and its -VL sibling's language model): KDA linear
attention (``ops/kda.py``) beside a latent-attention layer every
``layer_group_size`` layers (``ops/mla.py``), a leading dense SwiGLU
layer and then sigmoid-routed experts with a shared one (``ops/moe.py``),
of which this process may hold a share.  A new architecture is a new
layer kind here, not another flag on ``LlamaConfig``; ``models/llama.py``
keeps serving the configurations it serves.

One ``forward`` serves the three ways the engine calls a model: a cold
batch into fresh state, a chunk of one slot's prompt, and one decode
step over every slot.  It is given, for each row, where its tokens
start and how many of them count; a token that does not count (a padded
position, a row that does not decode) writes no latent row and leaves
the recurrent state exactly as it was.

State of a slot, by the layer's mixer:

* ``kda``: ``S`` (H, d_k, d_v) float32 and ``conv``, the last
  ``conv_kernel - 1`` inputs of the q/k/v convolution.  Fixed size; it
  exists only as of the last token it has seen.
* ``mla``: ``latent`` (T, kv_lora_rank + rope) — rows that grow with the
  tokens and can be cut at any length.

What is read from the family's convention and not from a key of the
public config is listed in ``benchmarks/configs/ling-3.0-flash-vl-l7e128.json``
under ``assumed``; the plain reference is ``models/hybrid_reference.py``.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Mapping

import jax
import jax.numpy as jnp

from generativeaiexamples_tpu.models.llama import rms_norm
from generativeaiexamples_tpu.ops import kda, mla, moe

Params = Mapping[str, Any]
F32 = jnp.float32
MIXERS = ("kda", "mla")
MLPS = ("dense", "experts")
N_COUNTERS = len(moe.COUNTERS)


@dataclasses.dataclass(frozen=True)
class HybridConfig:
    vocab_size: int
    d_model: int
    # One (mixer, mlp) pair a layer, in order.
    layer_kinds: tuple[tuple[str, str], ...]
    n_heads: int
    # KDA mixer
    kda_head_dim: int = 128
    conv_kernel: int = 4
    kda_gate_floor: float = -5.0
    # MLA mixer
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 6e6
    # MLPs
    d_ff: int = 6144
    moe_d_ff: int = 768
    shared_d_ff: int = 768
    n_experts: int = 512  # the router's outputs
    experts_held: int = 512  # of which this process holds this many ...
    expert_offset: int = 0  # ... starting at this one
    n_experts_per_tok: int = 8
    n_group: int = 8
    topk_group: int = 4
    routed_scaling: float = 2.5
    norm_topk: bool = True
    norm_eps: float = 1e-6
    max_seq_len: int = 2048
    dtype: str = "bfloat16"
    # The latent rows and the convolution tails; the KDA state is float32
    # whatever this says.  int8 is refused (``state_dtype``).
    kv_dtype: str = "bfloat16"

    def __post_init__(self) -> None:
        for mixer, mlp in self.layer_kinds:
            if mixer not in MIXERS or mlp not in MLPS:
                raise ValueError(f"unknown layer kind ({mixer!r}, {mlp!r})")
        if self.n_experts % self.n_group:
            raise ValueError("n_group must divide n_experts")
        if not 0 <= self.expert_offset <= self.n_experts - self.experts_held:
            raise ValueError("the experts held lie outside the router's outputs")

    @property
    def n_layers(self) -> int:
        return len(self.layer_kinds)

    @property
    def latent_width(self) -> int:
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def conv_channels(self) -> int:
        return 3 * self.n_heads * self.kda_head_dim

    @property
    def state_dtype(self):
        if self.kv_dtype == "int8":
            raise ValueError(
                "int8 state is not served for this model: a recurrent state "
                "is re-read and re-written by every token, so its rounding "
                "compounds; the latent rows have no quantized attention path"
            )
        return jnp.dtype(self.kv_dtype)

    def layers_of(self, mixer: str) -> list[int]:
        return [i for i, (m, _) in enumerate(self.layer_kinds) if m == mixer]

    def snapshot_bytes(self) -> int:
        """Bytes of one slot's recurrent state over the KDA layers."""
        h, k = self.n_heads, self.kda_head_dim
        per_layer = h * k * k * 4 + (self.conv_kernel - 1) * self.conv_channels * (
            self.state_dtype.itemsize
        )
        return len(self.layers_of("kda")) * per_layer


def from_hf_config(
    model: Mapping[str, Any],
    *,
    max_len: int,
    expert_offset: int = 0,
    kv_dtype: str = "bfloat16",
) -> HybridConfig:
    """The public ``config.json`` keys of the ``bailing_hybrid`` family ->
    ``HybridConfig``.

    ``num_experts`` counts the experts held (the chip's share);
    ``num_experts_published`` (absent: the same) the router's outputs.
    ``first_layer`` (absent: 0) is the published index of the first layer
    kept, so that a cut in depth keeps each layer's published kind: layer
    ``i`` is MLA where ``(i + 1) % layer_group_size == 0`` and KDA
    otherwise; the first ``first_k_dense_replace`` layers kept are dense.
    """
    period = int(model["layer_group_size"])
    first = int(model.get("first_layer", 0))
    dense = int(model["first_k_dense_replace"])
    kinds = tuple(
        (
            "mla" if (first + j + 1) % period == 0 else "kda",
            "dense" if j < dense else "experts",
        )
        for j in range(int(model["num_hidden_layers"]))
    )
    if model.get("score_function", "sigmoid") != "sigmoid":
        raise ValueError("only sigmoid routing scores are served")
    held = int(model["num_experts"])
    return HybridConfig(
        vocab_size=int(model["vocab_size"]),
        d_model=int(model["hidden_size"]),
        layer_kinds=kinds,
        n_heads=int(model["num_attention_heads"]),
        kda_head_dim=int(model["head_dim"]),
        conv_kernel=int(model["short_conv_kernel_size"]),
        kda_gate_floor=float(model["kda_lower_bound"]),
        kv_lora_rank=int(model["kv_lora_rank"]),
        qk_nope_head_dim=int(model["qk_nope_head_dim"]),
        qk_rope_head_dim=int(model["qk_rope_head_dim"]),
        v_head_dim=int(model["v_head_dim"]),
        rope_theta=float(model["rope_theta"]),
        d_ff=int(model["intermediate_size"]),
        moe_d_ff=int(model["moe_intermediate_size"]),
        shared_d_ff=int(model["moe_shared_expert_intermediate_size"]),
        n_experts=int(model.get("num_experts_published", held)),
        experts_held=held,
        expert_offset=int(expert_offset),
        n_experts_per_tok=int(model["num_experts_per_tok"]),
        n_group=int(model["n_group"]),
        topk_group=int(model["topk_group"]),
        routed_scaling=float(model["routed_scaling_factor"]),
        norm_topk=bool(model["norm_topk_prob"]),
        norm_eps=float(model["rms_norm_eps"]),
        max_seq_len=int(max_len),
        dtype=str(model.get("torch_dtype", "bfloat16")),
        kv_dtype=kv_dtype,
    )


# -- parameters ---------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("shape", "dtype"))
def _normal(key, scale, shape, dtype):
    return (jax.random.normal(key, shape, F32) * scale).astype(dtype)


def _layer_shapes(cfg: HybridConfig, mixer: str, mlp: str) -> dict:
    """name -> (shape, init) for one layer; init is a fan-in for a normal
    draw, or a constant."""
    D, H, K = cfg.d_model, cfg.n_heads, cfg.kda_head_dim
    shapes: dict = {"attn_norm": ((D,), 1.0), "mlp_norm": ((D,), 1.0)}
    if mixer == "kda":
        shapes.update(
            w_qkv=((D, 3 * H * K), D),
            conv_w=((cfg.conv_kernel, 3 * H * K), cfg.conv_kernel),
            w_f=((D, H * K), D),
            a_log=((H,), 0.0),
            dt_bias=((H, K), 0.0),
            w_b=((D, H), D),
            w_g=((D, H * K), D),
            o_norm=((K,), 1.0),
            w_o=((H * K, D), H * K),
        )
    else:
        qk = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
        shapes.update(
            w_q=((D, H * qk), D),
            w_kva=((D, cfg.latent_width), D),
            kv_norm=((cfg.kv_lora_rank,), 1.0),
            w_kvb=(
                (cfg.kv_lora_rank, H * (cfg.qk_nope_head_dim + cfg.v_head_dim)),
                cfg.kv_lora_rank,
            ),
            w_gate=((D, H), D),
            w_o=((H * cfg.v_head_dim, D), H * cfg.v_head_dim),
        )
    if mlp == "dense":
        shapes.update(w_gu=((D, 2 * cfg.d_ff), D), w_down=((cfg.d_ff, D), cfg.d_ff))
    else:
        F, Fs, E = cfg.moe_d_ff, cfg.shared_d_ff, cfg.experts_held
        shapes.update(
            router=((D, cfg.n_experts), D),
            router_bias=((cfg.n_experts,), 0.0),
            w_gu_e=((E, D, 2 * F), D),
            w_down_e=((E, F, D), F),
            w_gu_s=((D, 2 * Fs), D),
            w_down_s=((Fs, D), Fs),
        )
    return shapes


def init_params(cfg: HybridConfig, key: jax.Array) -> Params:
    """Random parameters, built leaf by leaf in the serving dtype: a
    float32 copy of one layer's experts would not fit beside the rest."""
    dtype = jnp.dtype(cfg.dtype)
    keys = iter(jax.random.split(key, 32 * cfg.n_layers + 8))

    def leaf(name, shape, init):
        if name == "router_bias":
            # Non-zero, so that tests tell selection from weighting; a
            # served model's is balanced (``balance_router_biases``).
            return _normal(next(keys), 0.05, shape, F32)
        if name in ("a_log", "dt_bias"):
            return _normal(next(keys), 0.5, shape, F32)
        if isinstance(init, float):
            return jnp.full(shape, init, dtype)
        return _normal(next(keys), float(init) ** -0.5, shape, dtype)

    layers = tuple(
        {n: leaf(n, s, i) for n, (s, i) in _layer_shapes(cfg, *kind).items()}
        for kind in cfg.layer_kinds
    )
    return {
        "embed": _normal(next(keys), 1.0, (cfg.vocab_size, cfg.d_model), dtype),
        "layers": layers,
        "final_norm": jnp.ones((cfg.d_model,), dtype),
        "lm_head": _normal(
            next(keys), cfg.d_model**-0.5, (cfg.d_model, cfg.vocab_size), dtype
        ),
    }


def balance_router_biases(params: Params, cfg: HybridConfig, key: jax.Array) -> Params:
    """Give every expert layer the selection bias that evens its experts'
    load on what that layer really sees: random prompts of 256 tokens
    (a prefill chunk), one for every eight router outputs (64 at 512: about
    250 choices an expert), go through the model layer by layer, and each
    expert layer's bias is balanced on its own inputs
    (``ops.moe.balanced_bias``) before they go on through it.  Random
    weights stand in for a checkpoint whose
    ``moe_router_enable_expert_bias`` training has done this."""
    if not any(mlp == "experts" for _, mlp in cfg.layer_kinds):
        return params
    rows = max(4, cfg.n_experts // 8)
    biases = iter(_balanced_biases(
        params, cfg, jax.random.randint(key, (rows, 256), 0, cfg.vocab_size, jnp.int32)
    ))
    layers = tuple(
        {**lp, "router_bias": next(biases)} if "router_bias" in lp else lp
        for lp in params["layers"]
    )
    return {**params, "layers": layers}


@functools.partial(jax.jit, static_argnames=("cfg",))
def _balanced_biases(params, cfg: HybridConfig, tokens):
    """``forward`` over whole rows from nothing, with each expert layer's
    bias balanced on that layer's inputs before they pass through it."""
    b, s = tokens.shape
    pos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    valid, n_valid = jnp.ones((b, s), bool), jnp.full((b,), s, jnp.int32)
    x = params["embed"][tokens]
    out = []
    for (mixer, mlp), lp, st in zip(cfg.layer_kinds, params["layers"], init_state(cfg, b, s)):
        x, _ = _mix(x, lp, st, mixer, pos, valid, n_valid, cfg, s)
        if mlp == "experts":
            h = rms_norm(x, lp["mlp_norm"], cfg.norm_eps)
            bias = moe.balanced_bias(
                h.reshape(-1, h.shape[-1]), lp["router"], k=cfg.n_experts_per_tok,
                n_group=cfg.n_group, topk_group=cfg.topk_group,
            )
            out.append(bias)
            lp = {**lp, "router_bias": bias}
        x, _ = _mlp(x, lp, mlp, valid, cfg, None)
    return out


# -- state ----------------------------------------------------------------------


def init_state(cfg: HybridConfig, batch: int, max_len: int) -> tuple:
    """Zero state for ``batch`` rows: one dict a layer, of its mixer's kind."""
    H, K = cfg.n_heads, cfg.kda_head_dim
    sd = cfg.state_dtype
    out = []
    for mixer, _ in cfg.layer_kinds:
        if mixer == "kda":
            out.append(
                {
                    "S": jnp.zeros((batch, H, K, K), F32),
                    "conv": jnp.zeros(
                        (batch, cfg.conv_kernel - 1, cfg.conv_channels), sd
                    ),
                }
            )
        else:
            out.append({"latent": jnp.zeros((batch, max_len, cfg.latent_width), sd)})
    return tuple(out)


# -- layers ---------------------------------------------------------------------


def _kda_mixer(h, lp, st, valid, n_valid, cfg: HybridConfig):
    b, s, _ = h.shape
    H, K = cfg.n_heads, cfg.kda_head_dim
    with jax.named_scope("layer/kda/proj"):
        qkv = jnp.dot(h, lp["w_qkv"])
        f = jnp.dot(h, lp["w_f"])
        out_gate = jnp.dot(h, lp["w_g"])
        write = jnp.dot(h, lp["w_b"], preferred_element_type=F32)
    with jax.named_scope("layer/kda/conv"):
        y, xin = kda.causal_conv(qkv, st["conv"], lp["conv_w"])
        tail = kda.next_tail(xin, n_valid, cfg.conv_kernel)
        q, k, v = jnp.split(jax.nn.silu(y).reshape(b, s, 3 * H, K), 3, axis=2)
        q = kda.l2_normalize(q) * K**-0.5
        k = kda.l2_normalize(k)
    with jax.named_scope("layer/kda/gate"):
        on = valid[:, :, None].astype(F32)
        g = kda.kda_gate(
            f.reshape(b, s, H, K), lp["a_log"], lp["dt_bias"], cfg.kda_gate_floor
        ) * on[..., None]
        beta = jax.nn.sigmoid(write) * on
    if s == 1:
        o, S = kda.kda_step(q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0], st["S"])
        o = o[:, None]
    else:
        o, S = kda.kda_chunked(q, k, v, g, beta, st["S"])
    with jax.named_scope("layer/kda/out"):
        o = rms_norm(o, lp["o_norm"].astype(F32), cfg.norm_eps)
        o = o * jax.nn.sigmoid(out_gate.astype(F32)).reshape(b, s, H, K)
        out = jnp.dot(o.reshape(b, s, H * K).astype(h.dtype), lp["w_o"])
    return out, {"S": S, "conv": tail.astype(st["conv"].dtype)}


def _mla_mixer(h, lp, st, pos, valid, cfg: HybridConfig, window: int):
    b, s, _ = h.shape
    H, rank = cfg.n_heads, cfg.kv_lora_rank
    nope, rope, vd = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    with jax.named_scope("layer/mla/q"):
        q = jnp.dot(h, lp["w_q"]).reshape(b, s, H, nope + rope)
        q_nope = q[..., :nope]
        q_rope = mla.rope_interleaved(q[..., nope:], pos, cfg.rope_theta)
    with jax.named_scope("layer/mla/kv"):
        ckr = jnp.dot(h, lp["w_kva"])
        c = rms_norm(ckr[..., :rank], lp["kv_norm"], cfg.norm_eps)
        k_rope = mla.rope_interleaved(ckr[..., rank:], pos, cfg.rope_theta)
        new = jnp.concatenate([c, k_rope], axis=-1).astype(st["latent"].dtype)
        T = st["latent"].shape[1]
        # A token that does not count is written nowhere.
        at = jnp.where(valid, pos, T)
        latent = st["latent"].at[jnp.arange(b)[:, None], at].set(new, mode="drop")
    attend = mla.attend_absorbed if s == 1 else mla.attend_expanded
    o = attend(
        q_nope, q_rope, latent[:, :window], lp["w_kvb"], pos,
        rank=rank, nope=nope, v_dim=vd,
    )
    with jax.named_scope("layer/mla/wo"):
        gate = jax.nn.sigmoid(jnp.dot(h, lp["w_gate"], preferred_element_type=F32))
        o = (o.astype(F32) * gate[..., None]).astype(h.dtype)
        out = jnp.dot(o.reshape(b, s, H * vd), lp["w_o"])
    return out, {"latent": latent}


def _swiglu(h, w_gu, w_down):
    gu = jnp.dot(h, w_gu)
    half = gu.shape[-1] // 2
    act = jax.nn.silu(gu[..., :half].astype(F32)) * gu[..., half:].astype(F32)
    return jnp.dot(act.astype(h.dtype), w_down)


def _expert_layer(h, lp, valid, cfg: HybridConfig, mesh):
    b, s, d = h.shape
    x = h.reshape(b * s, d)
    idx, w = moe.route(
        x, lp["router"], lp["router_bias"], k=cfg.n_experts_per_tok,
        n_group=cfg.n_group, topk_group=cfg.topk_group,
        norm_topk=cfg.norm_topk, scale=cfg.routed_scaling,
    )
    y, counters = moe.expert_mlp(
        x, idx, w, valid.reshape(-1), lp,
        offset=cfg.expert_offset, held=cfg.experts_held, mesh=mesh,
    )
    with jax.named_scope("layer/moe/shared"):
        y = y + _swiglu(x, lp["w_gu_s"], lp["w_down_s"])
    return y.reshape(b, s, d), counters


def _mix(x, lp, st, mixer, pos, valid, n_valid, cfg: HybridConfig, window: int):
    """The mixer's half of a layer: (x + mixer(norm(x)), new state)."""
    h = rms_norm(x, lp["attn_norm"], cfg.norm_eps)
    if mixer == "kda":
        y, st = _kda_mixer(h, lp, st, valid, n_valid, cfg)
    else:
        y, st = _mla_mixer(h, lp, st, pos, valid, cfg, window)
    return x + y, st


def _mlp(x, lp, mlp, valid, cfg: HybridConfig, mesh):
    """The MLP's half: (x + mlp(norm(x)), the expert layer's counters)."""
    h = rms_norm(x, lp["mlp_norm"], cfg.norm_eps)
    if mlp == "dense":
        with jax.named_scope("layer/mlp"):
            return x + _swiglu(h, lp["w_gu"], lp["w_down"]), 0
    y, counters = _expert_layer(h, lp, valid, cfg, mesh)
    return x + y, counters


def forward(
    params: Params,
    cfg: HybridConfig,
    tokens: jnp.ndarray,
    start: jnp.ndarray,
    n_valid: jnp.ndarray,
    state: tuple,
    *,
    window: int,
    mesh=None,
):
    """tokens (b, s) at positions ``start[b] + [0, s)``, of which the first
    ``n_valid[b]`` count; ``state`` is these rows' state; MLA layers
    attend over the first ``window`` latent rows.  Returns (hidden
    (b, s, D), state, counters (N_COUNTERS,) int32 summed over layers)."""
    b, s = tokens.shape
    x = params["embed"][tokens]
    steps = jnp.arange(s, dtype=jnp.int32)[None, :]
    pos = start[:, None].astype(jnp.int32) + steps
    valid = steps < n_valid[:, None]
    counters = jnp.zeros((N_COUNTERS,), jnp.int32)
    out_state = []
    for (mixer, mlp), lp, st in zip(cfg.layer_kinds, params["layers"], state):
        x, st = _mix(x, lp, st, mixer, pos, valid, n_valid.astype(jnp.int32), cfg, window)
        x, c = _mlp(x, lp, mlp, valid, cfg, mesh)
        counters = counters + c
        out_state.append(st)
    return x, tuple(out_state), counters


def logits(params: Params, cfg: HybridConfig, hidden: jnp.ndarray) -> jnp.ndarray:
    """Final norm and the untied head, accumulated in float32."""
    h = rms_norm(hidden, params["final_norm"], cfg.norm_eps)
    return jnp.einsum(
        "...d,dv->...v", h, params["lm_head"], preferred_element_type=F32
    )


# -- presets --------------------------------------------------------------------

# The language model's keys of inclusionAI/Ling-3.0-flash-VL's config.json
# that give it its shape (the file of the benchmark's configuration holds
# them all; the vision tower has no key there and is not modelled).
LING_FLASH_VL = {
    "num_hidden_layers": 42, "hidden_size": 2560, "intermediate_size": 6144,
    "first_k_dense_replace": 2, "moe_intermediate_size": 768,
    "moe_shared_expert_intermediate_size": 768, "num_experts": 512,
    "num_experts_per_tok": 8, "n_group": 8, "topk_group": 4,
    "routed_scaling_factor": 2.5, "norm_topk_prob": True,
    "score_function": "sigmoid", "num_attention_heads": 32, "head_dim": 128,
    "kv_lora_rank": 512, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "v_head_dim": 128, "rope_theta": 6000000, "rms_norm_eps": 1e-06,
    "vocab_size": 157184, "layer_group_size": 6, "short_conv_kernel_size": 4,
    "kda_lower_bound": -5,
}
# One chip's share of a four-chip host, seven layers deep: published
# layers 1-7 (a dense KDA layer, then KDA, KDA, KDA, MLA, KDA, KDA with
# experts), 128 of the 512 experts, a quarter of the vocabulary.
LING_L7E128_CUT = {
    "num_hidden_layers": 7, "first_layer": 1, "first_k_dense_replace": 1,
    "num_experts": 128, "num_experts_published": 512, "vocab_size": 39296,
}
# Every ratio of the cut at sizes a CPU test runs: a period of 6 after a
# dense first layer, 2 of 8 routing groups held, 4 groups kept.
LING_TINY = {
    **LING_FLASH_VL, **LING_L7E128_CUT,
    "hidden_size": 64, "intermediate_size": 128, "moe_intermediate_size": 32,
    "moe_shared_expert_intermediate_size": 32, "num_experts": 8,
    "num_experts_published": 32, "num_experts_per_tok": 4,
    "num_attention_heads": 4, "head_dim": 16, "kv_lora_rank": 32,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "vocab_size": 512, "torch_dtype": "float32",
}


def ling_flash_vl_l7e128() -> HybridConfig:
    return from_hf_config({**LING_FLASH_VL, **LING_L7E128_CUT}, max_len=2048)


def ling_tiny() -> HybridConfig:
    return from_hf_config(LING_TINY, max_len=256, kv_dtype="float32")


PRESETS = {
    "ling-3.0-flash-vl-l7e128": ling_flash_vl_l7e128,
    "ling-tiny": ling_tiny,
}
