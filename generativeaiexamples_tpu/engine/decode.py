"""Shared decode-path builders for the batch generator and the scheduler.

One implementation of the device-side chunked decode scan and the
params/cache preparation, so the two serving frontends (offline
``LlamaGenerator`` and continuous-batching ``Scheduler``) cannot drift.

The append-buffer flush geometry lives beside it
(``ops.decode_attention.flush_clip_start``): a chunk's flush is
``decode_chunk_size`` wide, and the scheduler keeps parked histories and
admitted prompts clear of the tail scratch zone that width defines.
"""

from __future__ import annotations

import functools
import os
from typing import Optional

import jax
import jax.numpy as jnp

from generativeaiexamples_tpu.core.logging import get_logger
from generativeaiexamples_tpu.engine.sampler import sample
from generativeaiexamples_tpu.models import llama

logger = get_logger(__name__)


def prepare_params(
    cfg: llama.LlamaConfig,
    params,
    mesh,
    *,
    quantize: bool = False,
    pack: bool = False,
    matmul_kernel: Optional[str] = None,
    seed: int = 0,
):
    """Init (if needed), mesh-shard, and optionally quantize/pack params.

    ``params=None`` means random weights from ``seed`` (no checkpoint).

    ``quantize`` converts every projection to weight-only int8
    (``ops.quant``) — halves decode HBM traffic and fits full-depth
    llama3-8b on one 16 GB chip.  ``pack`` fuses qkv and gate/up
    projections (``llama.pack_for_serving``); only applied when the mesh
    has no tensor-parallel axis, since packing crosses the sharded head
    boundary.

    ``matmul_kernel`` selects the serving matmul path
    (``[llm].matmul_kernel``): ``"xla"``/None keeps the weight-only int8
    layout; ``"pallas_w8a8"`` pre-blocks the int8 projections ONCE here
    into the ``(NB, K, BN)`` tile layout the streaming W8A8 Pallas kernel
    DMAs from HBM (``ops.qmm``).  Blocking applies after packing so the
    fused wqkv / w_gu leaves stream as single kernel calls, and only
    where one device holds the params (no mesh, or a one-device mesh
    such as a replica's slice): the blocked layout is not mesh-sharded.
    Asking for it where it cannot apply — float projections, a
    multi-device mesh — is an error, not a silent stay on XLA.
    """
    if matmul_kernel not in (None, "xla", "pallas_w8a8"):
        raise ValueError(
            f"unknown matmul_kernel {matmul_kernel!r} "
            "(expected 'xla' or 'pallas_w8a8')"
        )
    if params is None:
        if quantize:
            # Build leaves directly in int8: materializing full-depth bf16
            # first (16 GB for llama3-8b) would not fit HBM alongside the
            # quantized copy.
            logger.info("initializing random int8 llama params (%s)", cfg)
            params = init_random_int8_params(cfg, jax.random.PRNGKey(seed))
        else:
            logger.info("initializing random llama params (%s)", cfg)
            params = llama.init_params(cfg, jax.random.PRNGKey(seed))
    elif quantize:
        from generativeaiexamples_tpu.ops.quant import quantize_llama_params

        params = quantize_llama_params(params, include_embed=True)
    if mesh is not None:
        from generativeaiexamples_tpu.ops.quant import QuantizedMatrix
        from generativeaiexamples_tpu.parallel.mesh import shard_pytree

        from jax.sharding import PartitionSpec as P

        specs = llama.partition_specs(cfg)

        def _quant_spec(p, s):
            if not isinstance(p, QuantizedMatrix):
                return s
            # The scale broadcasts against q over its size-1 axes (matmul
            # weights: (..., 1, d_out); embedding: (V, 1)), so its spec is
            # q's with None wherever scale is 1 — a size-1 axis cannot be
            # sharded.
            parts = tuple(s) + (None,) * (p.q.ndim - len(tuple(s)))
            scale_parts = tuple(
                None if dim == 1 else part
                for dim, part in zip(p.scale.shape, parts[-p.scale.ndim:])
            )
            return QuantizedMatrix(q=s, scale=P(*scale_parts))

        specs = jax.tree.map(
            _quant_spec,
            params,
            specs,
            is_leaf=lambda x: isinstance(x, QuantizedMatrix),
        )
        params = shard_pytree(params, specs, mesh)
    if pack and (mesh is None or mesh.shape.get("tensor", 1) == 1):
        params = llama.pack_for_serving(params)
    if matmul_kernel == "pallas_w8a8":
        from generativeaiexamples_tpu.engine.weights import (
            preblock_llama_params,
        )
        from generativeaiexamples_tpu.ops.dispatch import one_device
        from generativeaiexamples_tpu.ops.qmm import BlockedQuantizedMatrix

        if not one_device(mesh):
            raise ValueError(
                "matmul_kernel='pallas_w8a8' needs the params on one "
                f"device; the mesh has {mesh.size}"
            )
        params = preblock_llama_params(params)
        if not any(
            isinstance(leaf, BlockedQuantizedMatrix)
            for leaf in params["layers"].values()
        ):
            raise ValueError(
                "matmul_kernel='pallas_w8a8' needs int8 projections: pass "
                "quantize=True or pre-quantized params"
            )
    return params


def init_random_int8_params(cfg: llama.LlamaConfig, key: jax.Array):
    """Random serving params with projections born int8: what
    ``benchmarks/run.py``, ``chip_smoke.py`` and the tests serve.

    Quantizes leaf-by-leaf under jit so peak HBM never holds a full bf16
    copy of the model next to the int8 one.
    """
    import dataclasses

    from generativeaiexamples_tpu.ops.quant import (
        QUANT_TARGETS,
        quantize_embedding,
        quantize_matrix,
    )

    params = llama.init_params(dataclasses.replace(cfg, n_layers=1), key)
    # Broadcast the single random layer to full depth in int8 (weights for
    # measuring and testing: values are random either way, but
    # shapes/dtypes are real).
    quant1 = jax.jit(quantize_matrix)
    layers = {}
    for name, leaf in params["layers"].items():
        if name in QUANT_TARGETS:
            qm = quant1(leaf)
            layers[name] = jax.tree.map(
                lambda a: jnp.broadcast_to(
                    a, (cfg.n_layers,) + a.shape[1:]
                ),
                qm,
            )
        else:
            layers[name] = jnp.broadcast_to(
                leaf, (cfg.n_layers,) + leaf.shape[1:]
            )
    out = {**params, "layers": layers}
    out["lm_head"] = quant1(params["lm_head"])
    out["embed"] = jax.jit(quantize_embedding)(params["embed"])
    return out


def prepare_cache(cfg: llama.LlamaConfig, batch: int, max_len: int, mesh):
    """Allocate the slot KV cache, sharded over the mesh when given."""
    cache = llama.init_kv_cache(cfg, batch, max_len)
    if mesh is not None:
        from jax.sharding import NamedSharding

        specs = llama.kv_cache_specs(cfg)
        cache = tuple(
            jax.device_put(c, NamedSharding(mesh, spec))
            for c, spec in zip(cache, specs)
        )
    return cache


@jax.named_scope("kv_write")
def _flush_append_buffer(cache, ab, starts, max_len: int):
    """Write the chunk's append buffer into the big cache, one scatter per
    leaf.

    Each row r's C slots land at cache positions [starts[r],
    starts[r] + C) of every plane/head — the scatter windows span
    (L, KH, C, HD) with contiguous (C, HD) runs under the default layout,
    so XLA neither re-layouts the cache (the per-token scatter's
    KH-windowed form prefers a KH-minor layout that conflicts with the
    Pallas kernel — measured as 5 GB of entry copies) nor pays per-token
    scatter overhead: one flush per chunk.

    Rows whose history cannot advance (parked/garbage lanes at
    ``max_len - 1``) clip to the tail garbage zone [T - C, T) — the
    boundary is :func:`ops.decode_attention.flush_clip_start`, which the
    scheduler's parking margin AND its admission length bound both
    derive from so no live KV is ever placed inside the zone.
    """
    from generativeaiexamples_tpu.ops.decode_attention import (
        flush_clip_start,
    )

    b = cache[0].shape[2]
    c = ab[0].shape[3]
    start = jnp.clip(starts, 0, flush_clip_start(max_len, c)).astype(
        jnp.int32
    )
    idx = jnp.stack(
        [jnp.arange(b, dtype=jnp.int32), start], axis=1
    )  # (b, 2)

    def flush_leaf(big, small):
        if big.ndim == 5:
            dn = jax.lax.ScatterDimensionNumbers(
                update_window_dims=(0, 1, 3, 4),
                inserted_window_dims=(2,),
                scatter_dims_to_operand_dims=(2, 3),
            )
        else:
            dn = jax.lax.ScatterDimensionNumbers(
                update_window_dims=(0, 1, 3),
                inserted_window_dims=(2,),
                scatter_dims_to_operand_dims=(2, 3),
            )
        return jax.lax.scatter(
            big, idx, small, dn,
            indices_are_sorted=False,
            unique_indices=False,
        )

    return tuple(flush_leaf(bg, sm) for bg, sm in zip(cache, ab))


def pin_default_layout(cache):
    """Constrain cache leaves to the default (descending) layout.

    Executables that CREATE the cache (cold prefill) are free to pick any
    output layout; the Pallas decode kernel's executable pins the default
    layout at its boundary.  If they disagree, cross-executable donation
    silently fails and the multi-GB cache is double-buffered — measured as
    the difference between llama3-8b 2k-context batch 96 fitting a 16 GB
    chip or OOM.  Single-device only (with a mesh, layouts ride sharding).
    """
    from jax.experimental.layout import Layout, with_layout_constraint

    return tuple(
        with_layout_constraint(
            c, Layout(major_to_minor=tuple(range(c.ndim)))
        )
        for c in cache
    )


def carry_tokens(tokens, carried, carry):
    """A chunk's input tokens: the host's ``tokens``, and for the rows
    ``carry`` (batch,) bool marks the last row of ``carried``, the
    (n_steps, batch) tokens of the chunk before this one (with what an
    admission's graft landed there since), which never left the device.
    ``None`` for either takes the host's for every row."""
    if carried is None or carry is None:
        return tokens
    return jnp.where(carry, carried[-1], tokens)


def make_decode_chunk_fn(cfg: llama.LlamaConfig, mesh, max_len: int):
    """Compiled multi-step decode: ``lax.scan`` of forward+sample.

    Signature: ``fn(params, cache, tokens, lengths, key, temp, top_p,
    top_k, n_steps, kv_bucket=None, live=None, carried=None, carry=None)``
    (the last two: :func:`carry_tokens`) with the cache donated
    and ``n_steps``/``kv_bucket`` static (bucketed by callers).  Returns
    ``(cache, toks)`` with toks shaped (n_steps, batch).  One host
    round-trip per chunk instead of per token: a device→host sync costs
    more than a decode step.  ``kv_bucket`` caps the cache prefix attention reads
    (callers pass a power-of-two ≥ every position the chunk will write);
    the XLA twin slices that window, while the Pallas kernel reads each
    row's own ``ceil(length / block)`` blocks and no longer the window.

    ``live`` (batch,) bool says which rows decode.  On the append-buffer
    path a row that does not (a parked prefix, a warming or empty slot,
    a slot admitted after the tick's snapshot) attends with
    ``kv_lengths`` 0: nothing of its cache is read, it folds the append
    buffer alone, so its tokens are finite and — as before — never
    emitted.  Its write positions, append-buffer slots and the flush are
    those of ``lengths``, exactly as without ``live``: its fresh K/V is
    written into the buffer like any row's, because the flush reads
    every row.  ``None`` attends every row over its length.

    Two equivalent implementations, chosen at trace time:

    * **Append-buffer** (TPU, int8 KV): per-step KV goes to a small
      (L, KH, B, n_steps, HD) append buffer, and attention streams the
      big-cache window plus the buffer through ``ops.decode_attention``
      — the Pallas kernel when shapes align and it is enabled, else its
      XLA einsum twin (``decode_gqa_attention_xla``), so disabling the
      kernel never falls back to big-cache scatters (which OOM at
      serving batch).  Whichever attends also writes: the kernel takes
      the step's quantised rows beside the buffer's four leaves, puts
      them into its own block in VMEM and hands the leaves back aliased
      onto its operands, so inside the layer loop the scan's carry is
      updated by the Mosaic call and by no XLA operation (an XLA write
      into a buffer laid out for the kernel cost 22-25 us a layer call,
      4.3-4.9 ms of Ouro's 30.4 ms step: PERF.md section 6, PR 54); the
      twin writes them as contiguous ``dynamic_update_slice``.  One
      windowed scatter flushes the buffer at chunk end.  The big cache
      is read-only inside the step, which is what keeps its layout
      kernel-compatible.
    * **XLA reference** (CPU tests, bf16 KV, multi-chip): per-step scatter
      into the big cache + slice/einsum attention — the semantics oracle.
    """
    from generativeaiexamples_tpu.ops.decode_attention import (
        use_append_buffer,
    )

    @functools.partial(jax.jit, donate_argnums=(1,), static_argnums=(8, 9))
    def decode_chunk(
        params,
        cache,
        tokens,
        lengths,
        key,
        temp,
        top_p,
        top_k,
        n_steps,
        kv_bucket=None,
        live=None,
        carried=None,
        carry=None,
    ):
        tokens = carry_tokens(tokens, carried, carry)
        window = min(kv_bucket, max_len) if kv_bucket else max_len
        kv_int8 = len(cache) == 4
        b = cache[0].shape[2]
        if use_append_buffer(
            s=1,
            kv_int8=kv_int8,
            batch=b,
            window=window,
            n_q=cfg.n_heads,
            n_kv=cfg.n_kv_heads,
            head_dim=cfg.head_dim,
            mesh=mesh,
        ):
            # Valid big-cache slots per row: the current token's write
            # position (its KV lives in the append buffer this chunk).
            lengths0 = jnp.minimum(lengths, max_len - 1)
            # What attention reads of each row: nothing for a row that
            # does not decode.  Positions and the flush keep lengths0.
            attended = (
                lengths0 if live is None else jnp.where(live, lengths0, 0)
            )
            ab = llama.init_append_buffer(cfg, b, n_steps)

            def body(carry, step):
                ab, tok, key = carry
                key, sub = jax.random.split(key)
                positions = jnp.minimum(lengths0 + step, max_len - 1)[
                    :, None
                ]
                hidden, _, ab = llama.forward(
                    params,
                    cfg,
                    tok[:, None],
                    positions,
                    cache,
                    attended,
                    mesh=mesh,
                    kv_bucket=kv_bucket,
                    append_cache=(ab, step),
                )
                lg = llama.logits(params, hidden)[:, 0]
                tok = sample(lg, sub, temp, top_p, top_k)
                return (ab, tok, key), tok

            (ab, tok, key), toks = jax.lax.scan(
                body,
                (ab, tokens, key),
                jnp.arange(n_steps, dtype=jnp.int32),
            )
            cache = _flush_append_buffer(cache, ab, lengths0, max_len)
            return cache, toks

        def body(carry, _):
            cache, tok, lengths, key = carry
            key, sub = jax.random.split(key)
            positions = jnp.minimum(lengths, max_len - 1)[:, None]
            hidden, cache = llama.forward(
                params,
                cfg,
                tok[:, None],
                positions,
                cache,
                jnp.minimum(lengths + 1, max_len),
                mesh=mesh,
                kv_bucket=kv_bucket,
            )
            lg = llama.logits(params, hidden)[:, 0]
            tok = sample(lg, sub, temp, top_p, top_k)
            return (cache, tok, lengths + 1, key), tok

        (cache, tok, lengths, key), toks = jax.lax.scan(
            body, (cache, tokens, lengths, key), None, length=n_steps
        )
        return cache, toks

    if not os.environ.get("GAIE_DEBUG_CHECKS"):
        return decode_chunk

    def decode_chunk_checked(
        params, cache, tokens, lengths, key, temp, top_p, top_k,
        n_steps, kv_bucket=None, live=None, carried=None, carry=None,
    ):
        """Debug-mode contract guard wrapping the compiled step.

        The step trusts its caller that every position a LIVE lane can
        read or write lies below ``kv_bucket``; a too-small bucket
        silently truncates attention (the masked softmax keeps it finite
        but wrong).  This validates the actual arguments — independent of
        how the caller derived its bucket — on the host, where lengths
        are concrete.  Lanes parked exactly at ``max_len - 1`` are the
        masked-garbage write convention (scheduler inactive slots) and
        are excluded.
        """
        if kv_bucket is not None:
            import numpy as _np

            arr = _np.asarray(lengths)
            live = arr[arr < max_len - 1]
            if live.size:
                # First step writes at position lengths, the last at
                # lengths + n_steps - 1; the window must cover positions
                # [0, lengths + n_steps) — a size, hence no extra +1.
                needed = min(int(live.max()) + int(n_steps), max_len)
                if kv_bucket < needed:
                    raise AssertionError(
                        "kv_bucket contract violated: a live lane covers "
                        f"positions up to {needed} but the attention "
                        f"window is {kv_bucket}"
                    )
        return decode_chunk(
            params, cache, tokens, lengths, key, temp, top_p, top_k,
            n_steps, kv_bucket, live, carried, carry,
        )

    return decode_chunk_checked
