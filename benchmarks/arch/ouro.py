"""The ``ouro`` family (Ouro-2.6B, the LoopLM of arXiv:2510.25741): a stack
of 48 IDENTICAL llama-shaped layers (16 query heads on 16 key-value heads of
128, SwiGLU of 5,632, a norm on each sub-layer's way in AND out) that every
token passes ``total_ut_steps`` = 4 times over the same weights, the final
norm after each pass, a K/V plane for every (pass, layer): 192 planes,
798,720 B a token in int8 with bf16 scales.

Where a family goes: a stack of identical llama-shaped layers is the
program's ``LlamaConfig`` (``models/llama.py``: the loop is one more loop
around its scan over stacked layers, and the int8 weights, the int8 cache,
the decode kernel, the append buffer, the chunked prefill and
``LlamaServing`` serve it as they serve ``mistral-7b``); a stack of
differing layer kinds is a ``HybridConfig`` (``models/hybrid.py``).

What a row of ``benchmarks/README.md``'s layout table would say (that file
is not a ``model_config`` PR's to edit): ``arch/ouro.py`` maps
``configs/ouro-2.6b.json`` to the program's ``LlamaConfig`` (``ut_steps``,
``sandwich_norm``, ``early_exit_threshold``) and holds its counts, which
are ``model_math.py``'s with the stack counted ``total_ut_steps`` times;
``ouro_reference.py`` beside ``run.py`` is the plain float32 reference (a
copy of ``generativeaiexamples_tpu/models/ouro_reference.py``);
``traffic/chat-short.json`` and ``traffic/chat-short-closed.json`` are the
cell's mix (the ``chat`` mix's shape at a slot of 768 rows: a 24-token
system line, 16-384 unique tokens, 32-320 output tokens, 24 waiting
clients on 16 slots); ``layer_metrics/decode_pass_dev_ms.py`` reads the
decode chunk's device time over the passes of the stack dispatched.

``last_logits`` below holds the program's logits, from its chunk program
and from decode steps through the cache, to the reference's full forward
pass before it hands the reference's to the harness.
``benchmarks/tests/test_arch_ouro.py`` holds the counts to the table of
the configuration worked by hand.
"""

from __future__ import annotations

import dataclasses
import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

import model_math
import ouro_reference


def llama_config(model: dict, engine: dict):
    """The public config.json keys -> the program's ``LlamaConfig``."""
    from generativeaiexamples_tpu.models.llama import LlamaConfig

    if "ut_steps" not in {f.name for f in dataclasses.fields(LlamaConfig)}:
        # The commit before the one that added the loop: fail at once.
        raise SystemExit("benchmarks/arch/ouro.py: this program has no looped stack "
                         "(models/llama.py's LlamaConfig lacks ut_steps)")
    ref = model["reference"]
    # ``last_logits`` is called without the configuration: its limits, the
    # server's chunk and the positions that go through decode steps are
    # kept from here.
    _CHECK.update(limits=dict(ref["logit_share_limits"]), decode=int(ref["decode_positions"]),
                  chunk=int(engine["prefill_chunk_tokens"]), steps=int(engine["decode_chunk_size"]),
                  slots=int(engine["max_batch"]))
    _RUN.clear()
    cfg = LlamaConfig(
        vocab_size=int(model["vocab_size"]),
        d_model=int(model["hidden_size"]),
        n_layers=int(model["num_hidden_layers"]),
        n_heads=int(model["num_attention_heads"]),
        n_kv_heads=int(model["num_key_value_heads"]),
        head_dim=int(model["head_dim"]),
        d_ff=int(model["intermediate_size"]),
        rope_theta=float(model["rope_theta"]),
        norm_eps=float(model["rms_norm_eps"]),
        max_seq_len=int(engine["max_len"]),
        dtype=str(model.get("torch_dtype", "bfloat16")),
        kv_dtype=str(engine["kv_dtype"]),
        hidden_act=str(model["hidden_act"]),
        ut_steps=int(model["total_ut_steps"]),
        sandwich_norm=True,
        early_exit_threshold=float(model["early_exit_threshold"]),
    )
    print(json.dumps({
        "bench": "state bytes", "cache_planes": cfg.cache_planes,
        "kv_bytes_per_token": int(kv_bytes_per_token(model, engine)),
        "slot_bytes": int(kv_bytes_per_token(model, engine)) * int(engine["max_batch"]) * int(engine["max_len"]),
        "stack_weight_bytes": stack_weight_bytes(model, engine),
        "head_weight_bytes": head_weight_bytes(model, engine),
    }), flush=True)
    return cfg


# -- the comparison that decides ``correct`` -------------------------------------
#
# The harness asks for the reference's logits at a prompt's last position
# and holds the server's first greedy token to them.  That token comes from
# a prefill alone; it sees neither the int8 K/V a second chunk and the decode
# steps read back, nor the decode kernel.  ``last_logits`` therefore first
# holds the program's logits to the reference's at every position of the
# prompt, through the calls the scheduler's programs make and at their
# shapes:
#
# (a) in a state of one slot of ``max_len`` rows (0.6 GB; the whole house at
#     full length is the engine's own 9.8 GB, and the chip has 4.4 GB left
#     beside it) the prompt but its last ``decode_positions`` tokens a chunk
#     at a time through ``LlamaServing.prefill_row`` (the server's
#     ``_prefill_suffix``: a first chunk, and past 256 tokens a warm one over
#     the int8 K/V of the first), then those last tokens through decode steps
#     as ``decode_chunk`` runs them (the append buffer, a chunk's steps, one
#     flush; one slot is the XLA twin of the kernel, which wants 16 rows);
# (b) in a state of all ``max_batch`` slots of ``KERNEL_ROWS`` rows (3.3 GB)
#     with EVERY slot live: slot ``r`` holds the first ``KERNEL_PREFIX +
#     KERNEL_STRIDE * r`` tokens of the prompt (even ``r``) or of the prompt
#     reversed (odd ``r``), prefilled as one chunk in place, so that
#     neighbouring rows hold different sequences and no two rows the same
#     length; all sixteen then decode their next ``decode_positions`` tokens
#     together through the same steps, which there are the Pallas kernel's at
#     a full house, over 16 KV heads of one query head each, and each row is
#     held to the reference's pass over its own sequence: a row that read
#     another's slot, or another pass's plane, reads far from it.
#
# Every position's error is taken as a share of its reference logits' root
# mean square; a prompt's four readings are the median and the ninth tenth
# over its prefilled positions and the medians over the two kinds of decoded
# positions.  A single prompt's level swings twofold with its tokens (seeded
# weights: the configuration's ``reference.why``), so one prompt cannot tell
# the precision below from a sound run; the RUN's level can: the mean of each
# reading over the prompts read so far is held to
# ``reference.logit_share_limits``, and while one stands outside its limit
# the prompt in hand is handed to the harness as one the served token cannot
# agree with, so it counts against ``min_within`` like a wrong token.  (The
# harness asks a prompt at a time and counts prompts, so the run's level is
# taken as it stands at each; the last line's ``run`` is the whole run's.)

_CHECK: dict = {}
_RUN: dict = {}  # reading -> the prompts' values so far, in the harness's order
KERNEL_ROWS = 256
KERNEL_PREFIX, KERNEL_STRIDE = 64, 9  # slot r: 64 + 9 r tokens, 64..199


@functools.lru_cache(maxsize=4)
def _programs(cfg, n_steps: int, slots: int, rows: int):
    """The serving model over a state of ``slots`` x ``rows`` and the two
    calls the scheduler's programs make of it: a chunk into one slot through
    ``prefill_row`` (returns the chunk's logits) and ``n_steps`` decode steps
    of the live slots with the tokens given (returns their logits)."""
    from generativeaiexamples_tpu.engine.decode import _flush_append_buffer
    from generativeaiexamples_tpu.engine.serving_models import LlamaServing
    from generativeaiexamples_tpu.models import llama
    from generativeaiexamples_tpu.ops.decode_attention import use_append_buffer
    from generativeaiexamples_tpu.utils.buckets import bucket_size

    model = LlamaServing(dataclasses.replace(cfg, max_seq_len=rows), None, rows)
    cfg = model.cfg

    @functools.partial(jax.jit, donate_argnums=(1,), static_argnums=(6,))
    def chunk(params, state, tokens, start, n, slot, kv_bucket):
        state, hidden, _ = model.prefill_row(params, state, tokens[None], start, n, slot, kv_bucket)
        return state, model.logits(params, hidden[0]).astype(jnp.float32)

    def chunk_at(params, state, tokens, start: int, n: int, slot: int = 0):
        """The shapes ``Scheduler._dispatch_chunk`` gives a chunk."""
        s = min(bucket_size(n, minimum=16, dense=True), rows)
        piece = np.zeros((s,), np.int32)
        piece[:n] = tokens[start : start + n]
        kv_bucket = bucket_size(start + s, maximum=rows, dense=True)
        state, logits = chunk(
            params, state, jnp.asarray(piece), jnp.int32(start), jnp.int32(n), jnp.int32(slot), kv_bucket)
        return state, logits[:n]

    @functools.partial(jax.jit, donate_argnums=(1,))
    def steps(params, state, fed, pos, live):
        """``decode_chunk``'s body with the tokens given: ``fed`` (n_steps,
        slots) from positions ``pos`` (slots,); a slot that is not ``live``
        does not decode (it attends nothing and writes to the tail zone).
        Returns the state and the logits (n_steps, slots, V)."""
        lengths = jnp.where(live, pos, rows - 1)
        attended = jnp.where(live, pos, 0)
        toks = jnp.where(live[None, :], fed, 0)
        out = []
        if use_append_buffer(
            s=1, kv_int8=len(state) == 4, batch=slots, window=rows,
            n_q=cfg.n_heads, n_kv=cfg.n_kv_heads, head_dim=cfg.head_dim, mesh=None,
        ):
            ab = llama.init_append_buffer(cfg, slots, n_steps)
            for i in range(n_steps):
                positions = jnp.minimum(lengths + i, rows - 1)[:, None]
                hidden, _, ab = llama.forward(
                    params, cfg, toks[i][:, None], positions, state, attended,
                    kv_bucket=rows, append_cache=(ab, i))
                out.append(hidden[:, 0])
            state = _flush_append_buffer(state, ab, lengths, rows)
        else:  # off the chip: the scatter path, as decode_chunk's
            for i in range(n_steps):
                positions = jnp.minimum(lengths + i, rows - 1)[:, None]
                hidden, state = llama.forward(
                    params, cfg, toks[i][:, None], positions, state,
                    jnp.minimum(lengths + i + 1, rows), kv_bucket=rows)
                out.append(hidden[:, 0])
        return state, model.logits(params, jnp.stack(out)).astype(jnp.float32)

    return model, chunk_at, steps


@jax.jit
def _shares(got, want):
    """Each position's |got - want|_rms / |want|_rms."""
    return jnp.sqrt(((got - want) ** 2).mean(-1)) / jnp.sqrt((want**2).mean(-1))


def logit_shares(params, cfg, tokens, pad_to: int):
    """({kind: shares}, (V,) reference logits at the last position): the
    program's logits against the reference's at the positions of one
    prompt, by how the program computed them."""
    n, every = len(tokens), _CHECK["steps"]
    n_decode = min(_CHECK["decode"], n - 1)
    n_decode -= n_decode % every  # whole chunks of steps
    n_prefill = n - n_decode

    def padded(seq):
        # One length for every prompt of a run: one compiled reference
        # (every layer is causal, so no position before the pad sees it).
        out = np.zeros((max(pad_to, n),), np.int32)
        out[:n] = seq
        return out

    toks = padded(tokens)
    want = ouro_reference.all_logits(params, cfg, toks)
    shares = {}

    def decoded(step_fn, state, fed, pos, live):
        """(n_decode, slots, V): each slot's next ``n_decode`` tokens,
        ``fed`` (n_decode, slots), through chunks of steps."""
        got = []
        for at in range(0, n_decode, every):
            state, logits = step_fn(
                params, state, jnp.asarray(fed[at : at + every]), jnp.asarray(pos + at), jnp.asarray(live))
            got.append(logits)
        return jnp.concatenate(got)

    # (a) the prompt through chunks and decode steps at the slot's length.
    model, chunk_at, steps = _programs(cfg, every, 1, cfg.max_seq_len)
    state = model.init_state(1, cfg.max_seq_len)
    prefilled = []
    for start in range(0, n_prefill, _CHECK["chunk"]):
        count = min(n_prefill - start, _CHECK["chunk"])
        state, logits = chunk_at(params, state, toks, start, count)
        prefilled.append(_shares(logits, want[start : start + count]))
    shares["prefill"] = np.asarray(jnp.concatenate(prefilled))
    if n_decode:
        got = decoded(steps, state, toks[n_prefill:n, None], np.array([n_prefill], np.int32), np.array([True]))
        shares["decode"] = np.asarray(_shares(got[:, 0], want[n_prefill:n]))
    del state
    # (b) every slot live at a length and (by turns) a sequence of its own:
    # the kernel's shape at a full house.
    slots = _CHECK["slots"]
    starts = KERNEL_PREFIX + KERNEL_STRIDE * np.arange(slots, dtype=np.int32)
    rows = min(KERNEL_ROWS, cfg.max_seq_len)
    if n_decode and starts[-1] + n_decode <= min(n, rows - every):
        seqs = (toks, padded(np.asarray(tokens)[::-1]))
        wants = (want, ouro_reference.all_logits(params, cfg, seqs[1]))
        model, chunk_at, steps = _programs(cfg, every, slots, rows)
        state = model.init_state(slots, rows)
        for r, p in enumerate(starts):
            state, _ = chunk_at(params, state, seqs[r % 2], 0, int(p), slot=r)
        fed = np.stack([seqs[r % 2][p : p + n_decode] for r, p in enumerate(starts)], axis=1)
        got = decoded(steps, state, fed, starts, np.ones((slots,), bool))
        del state
        shares["kernel_decode"] = np.asarray(jnp.stack(
            [_shares(got[:, r], wants[r % 2][p : p + n_decode]) for r, p in enumerate(starts)], axis=1))
    return shares, np.asarray(want[n - 1])


def share_quantiles(shares: dict) -> dict:
    """A prompt's readings: the median and the ninth tenth over its
    prefilled positions, the medians over its decoded ones."""
    out = {
        "p50": float(np.quantile(shares["prefill"], 0.5)),
        "p90": float(np.quantile(shares["prefill"], 0.9)),
    }
    for kind in ("decode", "kernel_decode"):
        if kind in shares:
            out[f"{kind}_p50"] = float(np.quantile(shares[kind], 0.5))
    if "kernel_decode" in shares:
        # The worst ROW's median: a row that read another's slot shows here
        # and not in a median over sixteen rows.
        out["kernel_row_max"] = float(np.median(shares["kernel_decode"], axis=0).max())
    return out


def last_logits(params, cfg, tokens, pad_to: int = 0):
    """The float32 reference's logits at the prompt's last position, if
    the run's level so far (the mean of each reading over the prompts
    read) lies within the limits; else logits no served token agrees with
    (one entry more than the vocabulary, and the maximum there: gap 1)."""
    shares, want_last = logit_shares(params, cfg, tokens, pad_to)
    read = share_quantiles(shares)
    for k, v in read.items():
        _RUN.setdefault(k, []).append(v)
    run = {k: float(np.mean(_RUN[k])) for k in read}
    outside = sorted(k for k, v in run.items() if not v <= _CHECK["limits"][k])
    print(json.dumps({"bench": "logit check", **read, "run": run, "outside": outside}), flush=True)
    if outside:
        return np.append(np.zeros(want_last.shape[0], np.float32), np.float32(1.0))
    return want_last


# -- the counts ------------------------------------------------------------------
#
# ``model_math.py``'s, with the stack counted once a pass.  Why the stack
# counts ``total_ut_steps`` times in a decode step's bytes: 2.47 GB of layer
# weights do not stay on the chip between passes (its fast memory holds
# 128 MB), so the algorithm ON THIS CHIP has to read each layer once a pass;
# a chip that held the stack would need one read, and this share would then
# pass 100.  The head is read once a step; the embedding is a row gather.


def stack_weight_bytes(model: dict, engine: dict) -> int:
    """Bytes of the 48 layers' projections as served."""
    return model_math.weight_bytes(model, engine) - head_weight_bytes(model, engine)


def head_weight_bytes(model: dict, engine: dict) -> int:
    w = 1 if engine["weight_dtype"] == "int8" else 2
    return int(model["hidden_size"]) * int(model["vocab_size"]) * w


def kv_bytes_per_token(model: dict, engine: dict) -> float:
    """K and V of one token in every plane: a plane a (pass, layer)."""
    return int(model["total_ut_steps"]) * model_math.kv_bytes_per_token(model, engine)


def decode_step_bytes(model: dict, engine: dict, live_kv_tokens: float) -> float:
    """One decode step over the batch: the stack's weights once a pass, the
    head once, and the K/V of every live token in all 192 planes (pass ``u``
    reads its own 48)."""
    return (
        int(model["total_ut_steps"]) * stack_weight_bytes(model, engine)
        + head_weight_bytes(model, engine)
        + live_kv_tokens * kv_bytes_per_token(model, engine)
    )


def prefill_flops(model: dict, new_tokens: float, attn_pairs: float) -> float:
    """Operations to prefill ``new_tokens``: the llama count of the stack
    (2 a parameter and token, 4 x heads x head_dim a layer for every
    (query, visible key) pair) once a pass.  The head runs once a prompt
    and, as in ``model_math.prefill_flops``, is not counted."""
    return int(model["total_ut_steps"]) * model_math.prefill_flops(model, new_tokens, attn_pairs)
