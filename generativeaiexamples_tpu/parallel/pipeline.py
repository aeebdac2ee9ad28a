"""Pipeline parallelism: GPipe-style layer stages over the ``pipe`` mesh axis.

The reference has no pipeline parallelism anywhere (SURVEY.md §2.9 — all
model parallelism lives inside TRT-LLM, which itself only does TP in the
NIM deployment); this is a TPU-native stretch capability completing the
dp/fsdp/pp/sp/ep axis set.

Design (idiomatic JAX, no microbatch Python loops):

* **Stage = contiguous layer shard.** The stacked (L, ...) layer weights
  shard over ``pipe`` on their leading axis (``pipeline_rules`` maps the
  ``layers`` logical axis to the ``pipe`` mesh axis), so stage ``k``
  holds layers ``[k·L/S, (k+1)·L/S)`` — no resharding, no per-stage
  parameter trees.
* **Schedule as one ``lax.scan``** inside ``shard_map``: at tick ``t``
  stage ``k`` runs microbatch ``j = t - k`` through its local layers
  (an inner scan using :func:`models.llama.dense_layer` — the same layer
  math as the non-pipelined forward), then hands activations to stage
  ``k+1`` via ``ppermute``.  ``T = n_micro + S - 1`` ticks fill and
  drain the bubble.
* **Stage-local embedding and head.** Parameters for embedding/norm/head
  replicate (they are small next to the layer stacks), but the WORK is
  stage-local: ``lax.cond`` on the stage index computes token embeddings
  on stage 0 only and the LM head + cross entropy on the last stage only.
  The loss FORWARD's only inter-stage communication is therefore the
  ppermute hand-off of one microbatch activation per tick plus a SCALAR
  loss psum — no (b, s, d) activation broadcast
  (``tests/test_pipeline.py`` pins this on the compiled HLO).  The
  backward pass additionally all-reduces the replicated params'
  cotangents (embed/head/norms — param-sized, inherent to replicating
  them), which the pin deliberately does not cover.
  ``pipeline_forward`` (hidden-states API, used for inference-style
  calls) still broadcasts the final hidden states, since its contract is
  replicated output.

Composes with the ``data`` axis (batch shards per data group before
microbatching) AND with ``tensor`` inside each stage: when the mesh has a
``tensor`` axis > 1, head/MLP weights additionally column/row-shard over
it and the stage body runs Megatron-style TP — local-head attention and
local-mlp matmuls with one ``psum`` after each of wo and w_down
(``models.llama.dense_layer(tp_axis="tensor")``), the two collectives
per layer riding the innermost (fastest-ICI) mesh axis while ppermute
hand-offs ride ``pipe``.  This is the dp×pp×tp composition a 70B-class
serving/training deployment needs (the reference's only model-parallel
knob is TRT-LLM's ``INFERENCE_GPU_COUNT``,
``deploy/compose/docker-compose-nim-ms.yaml:20``).  Embedding and
LM-head stay replicated over ``tensor`` (small next to the layer
stacks); dense configs only (MoE routes through ``forward``'s general
path).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from generativeaiexamples_tpu.models import llama
from generativeaiexamples_tpu.parallel.mesh import default_rules


# Why a looped stack has no pipeline (``_pipeline_run`` and
# ``LlamaServing.check_supported`` both say it).
LOOPED_PIPELINE = (
    "a looped stack is not served as a pipeline: the last stage's output "
    "would go back to the first stage ut_steps - 1 times a token, and the "
    "GPipe schedule here hands a micro-batch down the stages once"
)


def pipeline_rules(tensor: bool = False) -> dict:
    """Sharding rules for the pipelined train/forward path: layer stacks
    shard over ``pipe``; with ``tensor=True`` the head/MLP axes
    additionally shard over the ``tensor`` mesh axis (Megatron TP inside
    each stage); embedding/head/norms replicate either way."""
    rules = default_rules()
    rules.update(
        layers="pipe",
        vocab=None,
        heads="tensor" if tensor else None,
        kv_heads="tensor" if tensor else None,
        mlp="tensor" if tensor else None,
    )
    return rules


def _pipeline_run(
    params,
    cfg: llama.LlamaConfig,
    tokens: jnp.ndarray,
    positions: jnp.ndarray,
    mesh,
    kv_lengths: Optional[jnp.ndarray],
    n_micro: Optional[int],
    targets: Optional[jnp.ndarray] = None,
    mask: Optional[jnp.ndarray] = None,
):
    """Shared GPipe schedule.  ``targets`` selects the mode:

    * hidden mode (``targets is None``): returns replicated final hidden
      states — costs one masked (b, s, d) psum broadcast off the last
      stage, inherent to the replicated-output contract.
    * loss mode: the LM head + masked cross entropy run on the LAST
      stage inside the shard_map (``lax.cond`` skips the vocab matmul on
      every other stage), and the only cross-stage collectives are the
      per-tick ppermute plus two scalar psums.
    """
    if cfg.n_experts > 1:
        raise NotImplementedError("pipeline supports dense configs")
    if cfg.ut_steps > 1:
        raise NotImplementedError(LOOPED_PIPELINE)
    S = mesh.shape["pipe"]
    if cfg.n_layers % S:
        raise ValueError(f"{cfg.n_layers} layers not divisible by pipe={S}")
    M = n_micro or S
    b, s = tokens.shape
    dp = mesh.shape.get("data", 1)
    if b % (dp * M):
        raise ValueError(
            f"batch {b} must be a multiple of data({dp}) × n_micro({M})"
        )
    loss_mode = targets is not None
    tp = mesh.shape.get("tensor", 1)
    tp_axis = "tensor" if tp > 1 else None
    if tp > 1 and (cfg.n_heads % tp or cfg.n_kv_heads % tp or cfg.d_ff % tp):
        raise ValueError(
            f"heads/kv/d_ff ({cfg.n_heads}/{cfg.n_kv_heads}/{cfg.d_ff}) "
            f"not divisible by tensor={tp}"
        )

    spec_tree = llama.partition_specs(cfg, pipeline_rules(tensor=tp > 1))
    data_spec = P("data", None)

    @functools.partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(
            spec_tree, data_spec, data_spec,
            P("data") if kv_lengths is not None else P(),
            data_spec if loss_mode else P(),
            data_spec if loss_mode else P(),
        ),
        out_specs=P() if loss_mode else P("data", None, None),
        check_vma=False,
    )
    def run(p, tok, pos, kvl, tgt, msk):
        stage = jax.lax.axis_index("pipe")
        lb = tok.shape[0]  # per-data-shard batch
        mb = lb // M
        d = cfg.d_model

        def make_embeds():
            x = jnp.take(p["embed"], tok, axis=0).astype(cfg.compute_dtype)
            if cfg.scale_embeddings:  # gemma sqrt(d_model) input scale
                x = x * jnp.asarray(d**0.5, x.dtype)
            return x.reshape(M, mb, s, d)

        # Stage-local embedding: only stage 0 consumes tokens; the other
        # stages skip the gather entirely (cond, not where — the branch
        # never executes there).
        x_mb = jax.lax.cond(
            stage == 0,
            make_embeds,
            lambda: jnp.zeros((M, mb, s, d), cfg.compute_dtype),
        )
        pos_mb = pos.reshape(M, mb, s)
        kvl_mb = kvl.reshape(M, mb) if kv_lengths is not None else None

        def local_layers(x, pos_b, kv_b):
            def lay(carry, lp):
                return (
                    llama.dense_layer(
                        carry, lp, cfg, pos_b, kv_b, None, tp_axis=tp_axis
                    ),
                    None,
                )
            x, _ = jax.lax.scan(lay, x, p["layers"])
            return x

        def tick(carry, t):
            state, outs = carry
            j = jnp.clip(t - stage, 0, M - 1)
            x_in = jnp.where(stage == 0, x_mb[j], state)
            kv_b = kvl_mb[j] if kvl_mb is not None else None
            y = local_layers(x_in, pos_mb[j], kv_b)
            nxt = jax.lax.ppermute(
                y, "pipe", [(i, (i + 1) % S) for i in range(S)]
            )
            done_j = jnp.clip(t - (S - 1), 0, M - 1)
            is_done = (stage == S - 1) & (t >= S - 1)
            outs = outs.at[done_j].set(
                jnp.where(is_done, y, outs[done_j])
            )
            return (nxt, outs), None

        zeros = jnp.zeros((mb, s, d), cfg.compute_dtype)
        outs0 = jnp.zeros((M, mb, s, d), cfg.compute_dtype)
        (_, outs), _ = jax.lax.scan(
            tick, (zeros, outs0), jnp.arange(M + S - 1)
        )
        if loss_mode:
            # Stage-local head: vocab projection + CE only where the
            # results actually live; everything else contributes zeros to
            # two SCALAR psums.
            def head_loss():
                from generativeaiexamples_tpu.engine.training import (
                    cross_entropy_terms,
                )

                hidden = llama.apply_final_norm(
                    outs.reshape(lb, s, d), cfg, p
                )
                total, count = cross_entropy_terms(p, hidden, tgt, msk)
                return total.astype(jnp.float32), count.astype(jnp.float32)

            total, count = jax.lax.cond(
                stage == S - 1,
                head_loss,
                lambda: (jnp.float32(0.0), jnp.float32(0.0)),
            )
            total = jax.lax.psum(total, ("pipe", "data"))
            count = jax.lax.psum(count, ("pipe", "data"))
            return -total / jnp.maximum(count, 1.0)

        # Hidden mode: replicate results off the last stage (masked psum).
        outs = jnp.where(stage == S - 1, outs, jnp.zeros_like(outs))
        outs = jax.lax.psum(outs, "pipe")
        hidden = outs.reshape(lb, s, d)
        return llama.apply_final_norm(hidden, cfg, p)

    dummy = jnp.zeros((), jnp.int32)
    return run(
        params, tokens, positions,
        kv_lengths if kv_lengths is not None else dummy,
        targets if targets is not None else dummy,
        mask if mask is not None else dummy,
    )


def pipeline_forward(
    params,
    cfg: llama.LlamaConfig,
    tokens: jnp.ndarray,
    positions: jnp.ndarray,
    mesh,
    kv_lengths: Optional[jnp.ndarray] = None,
    n_micro: Optional[int] = None,
) -> jnp.ndarray:
    """Cacheless forward through pipeline stages; returns hidden states.

    ``params`` must be sharded with :func:`pipeline_rules` (layer leaves
    split over ``pipe``).  The batch must divide ``data × n_micro``.
    """
    return _pipeline_run(
        params, cfg, tokens, positions, mesh, kv_lengths, n_micro
    )


def pipeline_loss_fn(
    params,
    cfg: llama.LlamaConfig,
    tokens: jnp.ndarray,
    targets: jnp.ndarray,
    mask: jnp.ndarray,
    mesh,
    n_micro: Optional[int] = None,
) -> jnp.ndarray:
    """Masked next-token cross entropy, computed ON the last pipeline
    stage (scalar collectives only — no activation broadcast)."""
    b, s = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    return _pipeline_run(
        params, cfg, tokens, positions, mesh, None, n_micro,
        targets=targets, mask=mask,
    )


def make_pipeline_train_step(cfg: llama.LlamaConfig, optimizer, mesh):
    """Pipelined train step: ``training.make_train_step`` with the
    pipelined loss (one shared optimizer-update/metrics implementation)."""
    from generativeaiexamples_tpu.engine.training import make_train_step

    return make_train_step(cfg, optimizer, mesh, loss=pipeline_loss_fn)
