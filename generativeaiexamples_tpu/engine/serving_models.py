"""What the scheduler asks of a model: one small interface, two servers.

``Scheduler`` builds its step programs (``_prefill_some``,
``_prefill_suffix``, ``_graft_rows``, ``_graft_prefix``, ``decode_chunk``)
from these calls and knows nothing else about the model:

``prepare_params``   random or given parameters, as they are served
``init_state``       the slots' state (``max_batch`` rows of ``max_len``)
``prefill_cold``     a batch of whole prompts into fresh state
``prefill_row``      a run of one slot's prompt from a given position
``chunks_per_program``   how many slots' prefill chunks may share one
                     program (1: ``prefill_row`` is all there is), and then
``prefill_rows``     runs of several slots' prompts, each from its position
``chunk_windows``    the windows those programs are compiled for
``graft_rows``       rows of a cold batch's state into their slots
``graft_prefix``     the first n token rows of one slot into another
``save_state`` / ``restore_state``   a snapshot of what cannot be cut at a
                     token: a slot's recurrent state, as of its last token
``logits``           hidden states -> vocabulary logits
``make_decode_chunk``   the compiled multi-step decode

``cut_anywhere`` says whether a prefix of the state can be taken at any
token (K/V rows can; a recurrent state exists only where it was saved, so
the scheduler keeps snapshots at prefill-chunk boundaries and cuts a
prefix hit back to the deepest one).  ``counter_names`` names the int32
counters each step program returns beside its tokens (``aux``; None for a
model that has none).

A model that drafts its own decode step (``draft`` ``"mtp"``: a
prediction module behind the stack, ``HybridServing``) returns from its
decode chunk tokens ``(n_steps, b, 2)`` and, after them, how many of the
two each row emitted in each step ``(n_steps, b)``: 1, or 2 where the
stack agreed with the draft; then what the chunk behind it may read
without the host, each row's newest token ``(1, b)`` and its length
``(b,)`` (``carried_len`` / ``carry_len`` beside ``carried`` / ``carry``).

``LlamaServing`` is ``models/llama.py`` as the scheduler used to call it,
so the programs of every dense llama-shaped configuration compile as they
did; a llama-shaped model with experts (Mixtral) has ``prefill_rows``
besides, whose experts dispatch sorted by expert where every other program
of the model keeps ``llama._moe_mlp``'s one-hot dispatch.  ``HybridServing``
serves ``models/hybrid.py``'s layer kinds.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from generativeaiexamples_tpu.engine.decode import carry_tokens
from generativeaiexamples_tpu.engine.sampler import sample
from generativeaiexamples_tpu.models import hybrid, llama
from generativeaiexamples_tpu.ops import moe
from generativeaiexamples_tpu.ops.dispatch import one_device


def _last_counted(x, n):
    """x (b, s, ...) at each row's last position that counts: ``n - 1``
    (position 0 of a row with none, whose caller keeps what it had)."""
    at = jnp.maximum(n - 1, 0).reshape((-1,) + (1,) * (x.ndim - 1))
    return jnp.take_along_axis(x, at, axis=1)[:, 0]


def _from_nothing(row, start):
    """State as of a last token (a call's rows first) for a prompt that
    starts at ``start`` (rows,): zeros where it starts at 0, whatever the
    slot's last occupant left (rows can be stale, and a ring's are masked by
    position; a recurrent state or a tail cannot be)."""
    return jnp.where((start == 0).reshape((-1,) + (1,) * (row.ndim - 1)), 0, row)


# The most chunks one program takes, whatever the rule below allows.
MAX_CHUNKS_PER_PROGRAM = 8


def chunks_sharing_experts(chunk_tokens: int, n_experts: int, k: int) -> int:
    """How many slots' chunks may go through a model with experts as one
    program: as many as keep the rows one expert's matrices see within the
    grouped product's row tile (``moe.ROW_TILE``), below which a chunk
    pays for the whole expert stream whatever its rows.  A chunk brings an
    expert ``chunk_tokens x k / n_experts`` rows (a share held here sees
    its share of the choices): 64 at Mixtral's 2 of 8, 32 at the 64
    experts of Mellum's cut, 4 at Ling's 512."""
    rows = -(-chunk_tokens * k // n_experts)
    return max(1, min(MAX_CHUNKS_PER_PROGRAM, moe.ROW_TILE // rows))


def doubling_windows(chunk_tokens: int, max_len: int) -> tuple[int, ...]:
    """The windows a program over the slots' first ``window`` rows is
    compiled for: eight chunks, then doubling up to the slot's length."""
    windows = [min(8 * chunk_tokens, max_len)]
    while windows[-1] < max_len:
        windows.append(min(2 * windows[-1], max_len))
    return tuple(windows)


def serving_model(cfg, mesh, max_len: int):
    """The server of ``cfg``'s kind."""
    if isinstance(cfg, hybrid.HybridConfig):
        return HybridServing(cfg, mesh, max_len)
    return LlamaServing(cfg, mesh, max_len)


class LlamaServing:
    cut_anywhere = True
    counter_names: tuple = ()
    snapshot_bytes = 0
    draft = ""  # no prediction module: a decode step yields one token

    def __init__(self, cfg: llama.LlamaConfig, mesh, max_len: int) -> None:
        self.cfg, self.mesh, self.max_len = cfg, mesh, max_len

    def check_supported(self) -> None:
        """Every option of the scheduler serves a stack that a token
        passes once.  A looped one (``cfg.ut_steps`` > 1) refuses, with
        the reason, what its loop does not carry."""
        cfg = self.cfg
        if cfg.ut_steps == 1:
            return
        if cfg.early_exit_threshold < 1.0:
            raise ValueError(
                f"early_exit_threshold {cfg.early_exit_threshold} is not "
                "served: below 1 the rows of one step would take their "
                "logits from different passes (and, in any program that "
                "saved work by it, run different numbers of passes), where "
                "the scheduler's lanes yield one token a step and "
                "llama.forward has one trip count; it is never read as 1"
            )
        if self.mesh is not None and self.mesh.shape.get("pipe", 1) > 1:
            from generativeaiexamples_tpu.parallel.pipeline import LOOPED_PIPELINE

            raise ValueError(LOOPED_PIPELINE)

    def chunks_per_program(self, chunk_tokens: int) -> int:
        """A dense model's every weight matrix multiplies every token of a
        chunk, so one chunk is all the rows its weight pass has use for: 1.
        A model with experts sends its chunks through ``prefill_rows``,
        whose sorted dispatch gives an expert only the rows that chose it
        (``chunks_sharing_experts``: 2 at Mixtral's widths).  Experts
        spread over a mesh keep the one-hot dispatch, which shards."""
        cfg = self.cfg
        if cfg.n_experts <= 1 or not one_device(self.mesh):
            return 1
        return chunks_sharing_experts(
            chunk_tokens, cfg.n_experts, cfg.n_experts_per_tok
        )

    def chunk_windows(self, chunk_tokens: int) -> tuple[int, ...]:
        return doubling_windows(chunk_tokens, self.max_len)

    def prepare_params(self, params, *, quantize, matmul_kernel, seed):
        from generativeaiexamples_tpu.engine.decode import prepare_params

        return prepare_params(
            self.cfg, params, self.mesh, quantize=quantize, pack=quantize,
            matmul_kernel=matmul_kernel, seed=seed,
        )

    def init_state(self, batch: int, max_len: int):
        from generativeaiexamples_tpu.engine.decode import prepare_cache

        return prepare_cache(self.cfg, batch, max_len, self.mesh)

    def kv_planes(self, state) -> tuple[int, int]:
        """(K/V planes a token holds, the bytes of a token's rows in all of
        them), read off ``state``: what a cold batch's fresh state costs a
        prompt token, and the scheduler's two gauges."""
        batch, rows = state[0].shape[2:4]
        return self.cfg.cache_planes, sum(leaf.nbytes for leaf in state) // (batch * rows)

    def prefill_cold(self, params, tokens, lengths):
        b, s = tokens.shape
        small = llama.init_kv_cache(self.cfg, b, s)
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
        hidden, small = llama.forward(
            params, self.cfg, tokens, positions, small, lengths, mesh=self.mesh,
            cold_prefill=True,
        )
        return hidden, small, None

    def graft_rows(self, big, small, rows, slots):
        """Rows ``rows`` of a cold batch's state into slots ``slots``,
        leaf-wise over the head-major (planes, KH, B, T, ...) cache tuple
        (2 leaves for bf16 KV, 4 for int8 KV), a row at a time: each is one
        contiguous (planes, KH, 1, s, ...) block written where it lies.
        One scatter a leaf for the whole batch made XLA re-lay the leaf out
        for it and back, a copy of the leaf each way: 4.8 GB of
        temporaries at Ouro's 192 planes, more than the chip had left (my
        chip call 1, PR 51).  A row named twice (the caller's padding) is
        written twice with the same values."""
        out = []
        for bg, sm in zip(big, small):
            tail = (0,) * (bg.ndim - 3)
            row_shape = sm.shape[:2] + (1,) + sm.shape[3:]
            for r in range(rows.shape[0]):
                row = jax.lax.dynamic_slice(sm, (0, 0, rows[r]) + tail, row_shape)
                bg = jax.lax.dynamic_update_slice(bg, row, (0, 0, slots[r]) + tail)
            out.append(bg)
        return tuple(out)

    def prefill_row(self, params, cache, tokens, start, suffix_len, slot, kv_bucket):
        s = tokens.shape[1]
        row = tuple(
            jax.lax.dynamic_slice(
                bg,
                (0, 0, slot) + (0,) * (bg.ndim - 3),
                bg.shape[:2] + (1,) + bg.shape[3:],
            )
            for bg in cache
        )
        positions = start + jnp.arange(s, dtype=jnp.int32)[None, :]
        hidden, row = llama.forward(
            params,
            self.cfg,
            tokens,
            positions,
            row,
            jnp.reshape(start + suffix_len, (1,)),
            mesh=self.mesh,
            kv_bucket=kv_bucket,
        )
        with jax.named_scope("kv_write"):
            cache = tuple(
                jax.lax.dynamic_update_slice(
                    bg, r, (0, 0, slot) + (0,) * (bg.ndim - 3)
                )
                for bg, r in zip(cache, row)
            )
        return cache, hidden, None

    def prefill_rows(self, params, cache, tokens, start, suffix_len, slots, window):
        """``prefill_row`` for the chunks of several slots at once (the
        contract of ``HybridServing.prefill_rows``): tokens (B, s) of slots
        ``slots`` (B,) from positions ``start`` (B,), of which the first
        ``suffix_len`` (B,) count, over one static ``window`` of rows.
        The experts dispatch sorted (``llama._moe_mlp_sorted``), so each
        expert's matrices pass once for the rows of all B chunks that
        chose it; a row gets what it gets alone.  A pad row (``suffix_len``
        0) routes to no expert and writes to no slot, whatever ``slots``
        says of it.  Returns (cache, hidden (B, s, D), None)."""
        B, s = tokens.shape
        steps = jnp.arange(s, dtype=jnp.int32)[None, :]

        def at(bg, r):
            return (0, 0, slots[r]) + (0,) * (bg.ndim - 3)

        def row_shape(bg):
            return bg.shape[:2] + (1, window) + bg.shape[4:]

        # A slice a row: a gather by ``slots`` reads the whole leaf.
        rows = tuple(
            jnp.concatenate(
                [jax.lax.dynamic_slice(bg, at(bg, r), row_shape(bg)) for r in range(B)],
                axis=2,
            )
            for bg in cache
        )
        hidden, rows = llama.forward(
            params,
            self.cfg,
            tokens,
            start[:, None] + steps,
            rows,
            start + suffix_len,
            mesh=self.mesh,
            chunk_valid=steps < suffix_len[:, None],
        )
        with jax.named_scope("kv_write"):
            out = []
            for bg, new in zip(cache, rows):
                for r in range(B):
                    # A pad row writes back what its slot holds.
                    held = jax.lax.dynamic_slice(bg, at(bg, r), row_shape(bg))
                    row = jnp.where(suffix_len[r] > 0, new[:, :, r : r + 1], held)
                    bg = jax.lax.dynamic_update_slice(bg, row, at(bg, r))
                out.append(bg)
        return tuple(out), hidden, None

    def graft_prefix(self, cache, src, dst, n: int):
        """Leaf-generic over the head-major cache tuple like
        ``graft_rows`` (2 bf16 leaves or 4 int8+scale leaves)."""
        out = []
        for bg in cache:
            rows = jax.lax.dynamic_slice(
                bg,
                (0, 0, src, 0) + (0,) * (bg.ndim - 4),
                bg.shape[:2] + (1, min(n, bg.shape[3])) + bg.shape[4:],
            )
            out.append(
                jax.lax.dynamic_update_slice(
                    bg, rows, (0, 0, dst, 0) + (0,) * (bg.ndim - 4)
                )
            )
        return tuple(out)

    def logits(self, params, hidden):
        return llama.logits(params, hidden)

    def make_decode_chunk(self):
        from generativeaiexamples_tpu.engine.decode import make_decode_chunk_fn

        return make_decode_chunk_fn(self.cfg, self.mesh, self.max_len)


class HybridServing:
    """``models/hybrid.py`` behind the same calls.  The state is a tuple
    of one dict a layer (``hybrid.init_state``), slot axis first.  Its
    leaves are of two sorts (``hybrid.ROW_LEAVES``): rows, one a position
    (latent rows, an indexer's keys, a full layer's K/V), which a graft
    copies up to any token; and state as of the last token (a KDA layer's
    ``S`` and ``conv``, a window layer's ring of K/V or of latent rows, a
    ``cca`` layer's tails), which a prefix hit takes from a snapshot saved
    at a prefill-chunk boundary.
    The sort is a leaf's, not a layer's: a ``cca`` layer has both, its
    ``k`` and ``v`` rows grafted and its tails (5 KB a layer) restored.  A
    model whose every leaf is of the first sort (``cfg.rows_only``: latent
    attention in every layer) says ``cut_anywhere``: its prefix hits are
    cut at any row, as a llama model's are, and no snapshot is ever saved
    for it.

    A model that holds a prediction module (``cfg.draft`` ``"mtp"``) is
    served with it as the draft of every decode step.  The module's state
    lies behind the stack's: its block's rows (K/V rows of a ``full``
    block; a latent row and an index key a position of an ``mla`` block:
    ``cfg.mtp_kind``), and ``h_last``, the stack's output at the row's
    last position.  Between programs the
    module's rows are filled up to the position BEFORE the last (that one
    needs the token after it): a prefill call runs the module one
    position behind the stack, a decode chunk first catches it up with
    its input token (which gives the first draft), and every verify step
    runs it over the positions the step accepted.

    The rule for a rejected draft's rows: the step has written the draft's
    position ``p + 1`` in every layer of the stack; the row's length
    advances by one only, so what a layer keeps a position lies past the
    length until the next step writes the true token over it: a full
    layer's K/V row is masked as a pad row is; a latent row and its index
    key likewise, the index key by the indexer's ``seen`` (no query at
    ``q <= p`` scores position ``p + 1``, and the next step's queries at
    ``p + 1`` and ``p + 2`` score the keys that very step wrote), the
    latent row because only selected positions are gathered (the gather's
    ``keep``); and a window layer's ring row ``(p + 1) % R`` is taken by
    the ring's own rule to hold position ``p + 1 - R``, which no query
    from ``p + 1`` on may see.  The module writes accepted positions
    only."""

    # Counters of a drafting model's decode chunk, after ``forward``'s:
    # drafts a greedy row offered and the stack agreed with, positions the
    # stack computed in decode steps, tokens emitted, and rows that a
    # rejection left to be written again: one for every layer of the stack
    # that keeps a row a position (``full``, ``mla``: its latent row and
    # index key count as one), none for the module's block, which writes
    # accepted positions only.
    DRAFT_COUNTERS = (
        "draft_proposed", "draft_accepted", "verify_positions",
        "decode_tokens_emitted", "draft_rows_rewritten",
    )
    # Of ``moe.COUNTERS``, those exported a second time for decode steps
    # alone: their ratio is the experts a layer of one step streamed.
    DECODE_MOE = ("experts_touched", "expert_layer_steps", "choices_local")

    def __init__(self, cfg: hybrid.HybridConfig, mesh, max_len: int) -> None:
        self.cfg, self.mesh, self.max_len = cfg, mesh, max_len
        self.draft = cfg.draft
        self.cut_anywhere = cfg.rows_only
        # Latent layers alone, attended in blocks: the chunk programs read
        # and write the slots' rows in place (``_prefill_rows_in_place``),
        # and one window serves them all (``chunk_windows``: a window layer
        # of latent rows reads its ring whatever the window).  The ``full``
        # and ``cca`` kinds' rows are written and read in place too, over
        # the doubling windows; only latent rows attended whole (Ling's)
        # have their windows taken out and put back.
        blocks = [m for m, _ in cfg.layer_kinds] + ([cfg.mtp_kind[0]] if self.draft else [])
        self.one_window = bool(cfg.latent_block) and all(
            mixer in ("mla", "mla_window") for mixer in blocks
        )
        self.rows_in_place = bool(cfg.latent_block) or not cfg.layers_of("mla")
        self.snapshot_bytes = cfg.snapshot_bytes(max_len)
        # ``forward``'s counters; the experts touched and the expert
        # layers run are counted again for decode steps alone
        # (``DECODE_MOE``), and the rows its attention layers read apart
        # for decode steps and for prefill chunks.
        self.counter_names = (
            tuple(f"moe_{n}" for n in moe.COUNTERS)
            + tuple(f"moe_{n}_decode" for n in self.DECODE_MOE)
            + tuple(
                f"attn_rows_{n}_{phase}"
                for phase in ("decode", "prefill") for n in cfg.row_counters
            )
            # What only a step's form counts: no phase to tell apart.
            + tuple(f"attn_rows_{n}" for n in cfg.step_counters)
        )
        if self.draft:
            self.counter_names += self.DRAFT_COUNTERS

    def check_supported(self) -> None:
        """What is not served for a model whose state cannot be cut at a
        token, refused with the reason."""
        if self.draft and self.cfg.layers_of("kda"):
            raise ValueError(
                "speculative decoding is not served over KDA state: a "
                "rejected draft would need the recurrent state rolled back, "
                "and no step keeps the state it started from (ops/kda.py has "
                "no rollback)"
            )
        if self.draft and self.cfg.layers_of("mamba"):
            raise ValueError(
                "speculative decoding is not served over mamba state: a "
                "rejected draft has moved the state-space state and the "
                "convolution's tail, and no step keeps what it started from "
                "(ops/ssm.py has no rollback)"
            )
        if self.draft and self.cfg.layers_of("cca"):
            raise ValueError(
                "speculative decoding is not served over a cca layer's "
                "tails: a rejected draft has moved the convolutions' last "
                "inputs and the shifted value, and no step keeps the tails "
                "it started from"
            )
        if self.mesh is not None and self.mesh.size > 1:
            raise ValueError(
                "one device holds this model's share: neither the exchange "
                "between expert shares nor a sharding of the layer kinds' "
                "state is implemented"
            )
        self.cfg.state_dtype  # refuses int8 state

    def prepare_params(self, params, *, quantize, matmul_kernel, seed):
        if quantize or matmul_kernel not in (None, "xla"):
            raise ValueError(
                "int8 weights are not served for this model: "
                "ops.quant.QUANT_TARGETS covers neither the experts nor the "
                "KDA, MLA, CCA and fused GQA projections of models/hybrid.py"
            )
        if params is None:
            key = jax.random.PRNGKey(seed)
            params = hybrid.balance_router_biases(
                hybrid.init_params(self.cfg, key), self.cfg, jax.random.fold_in(key, 1)
            )
        return params

    def init_state(self, batch: int, max_len: int):
        return hybrid.init_state(self.cfg, batch, max_len)

    def state_bytes(self, batch: int) -> dict:
        """Bytes of ``batch`` slots' state by kind (``hybrid.state_bytes``)."""
        return hybrid.state_bytes(self.cfg, batch, self.max_len)

    def _aux(self, counters, decode: bool, drafted=None):
        """``forward``'s counters under ``counter_names``: ``DECODE_MOE``
        again where the program is a decode chunk (zeros from a prefill),
        the attention rows to the decode or to the prefill entries, a
        step form's own (``cfg.step_counters``) as they are; a
        drafting model's ``DRAFT_COUNTERS`` (``drafted``; a prefill has
        none) come last."""
        n = len(moe.COUNTERS)
        m = n + len(self.cfg.row_counters)
        again = jnp.stack([counters[moe.COUNTERS.index(c)] for c in self.DECODE_MOE])
        rows, none = counters[n:m], jnp.zeros_like(counters[n:m])
        parts = [
            counters[:n], again if decode else jnp.zeros_like(again),
            *((rows, none) if decode else (none, rows)), counters[m:],
        ]
        if self.draft:
            parts.append(
                jnp.zeros((len(self.DRAFT_COUNTERS),), jnp.int32)
                if drafted is None else drafted
            )
        return jnp.concatenate(parts)

    def _module_behind(self, params, hidden, tokens, start, n_valid, state, window,
                       apart: bool = False):
        """A prefill call's part for the prediction module, one position
        behind the stack: the call's tokens (b, s) from ``start`` are the
        tokens that follow positions ``start - 1 + [0, s)``, whose stack
        outputs are ``h_last`` and then ``hidden`` (b, s, D) shifted by
        one; a prompt's position -1 does not count.  Returns (state with
        the module's rows written and ``h_last`` moved to the call's last
        position that counts, the module's counters)."""
        L = self.cfg.n_layers
        rows, last = state[L], state[L + 1]["h_last"]
        steps = jnp.arange(tokens.shape[1], dtype=jnp.int32)[None, :]
        n_valid = n_valid.astype(jnp.int32)
        pos = start[:, None].astype(jnp.int32) + steps - 1
        valid = (steps < n_valid[:, None]) & (pos >= 0)
        behind = jnp.concatenate([last[:, None].astype(hidden.dtype), hidden[:, :-1]], axis=1)
        _, rows, counters = hybrid.mtp_forward(
            params, self.cfg, behind, tokens, pos, valid, rows, window=window,
            mesh=self.mesh, rows_apart=apart,
        )
        last = jnp.where((n_valid > 0)[:, None], _last_counted(hidden, n_valid).astype(last.dtype), last)
        return state[:L] + (rows, {"h_last": last}), counters

    def prefill_cold(self, params, tokens, lengths):
        b, s = tokens.shape
        hidden, state, counters = hybrid.forward(
            params, self.cfg, tokens, jnp.zeros((b,), jnp.int32), lengths,
            hybrid.init_state(self.cfg, b, s), window=s, mesh=self.mesh,
        )
        if self.draft:
            state, c = self._module_behind(
                params, hidden, tokens, jnp.zeros((b,), jnp.int32), lengths, state, s
            )
            counters = counters + c
        return hidden, state, self._aux(counters, decode=False)

    def graft_rows(self, big, small, rows, slots):
        """Rows of a cold batch into their slots: a leaf of rows (a ring
        too, which a cold batch fills from row 0) up to the batch's
        length, the rest whole."""
        rows_of = hybrid.ROW_LEAVES + hybrid.RING_LEAVES
        out = []
        for bg, sm in zip(big, small):
            layer = {}
            for name, leaf in bg.items():
                picked = jnp.take(sm[name], rows, axis=0)
                if name in rows_of:
                    layer[name] = leaf.at[slots, : picked.shape[1]].set(picked)
                else:
                    layer[name] = leaf.at[slots].set(picked)
            out.append(layer)
        return tuple(out)

    def prefill_row(self, params, cache, tokens, start, suffix_len, slot, kv_bucket):
        def take(leaf):
            return jax.lax.dynamic_slice_in_dim(leaf, slot, 1, axis=0)

        rows_of = hybrid.ROW_LEAVES + hybrid.RING_LEAVES
        row = jax.tree.map(take, cache)
        # A prompt that starts here starts from nothing, whatever the
        # slot's last occupant left (rows can be stale, and a ring's are
        # masked by position; a recurrent state cannot be).
        row = tuple(
            {n: (leaf if n in rows_of else jnp.where(start == 0, 0, leaf))
             for n, leaf in layer.items()}
            for layer in row
        )
        hidden, row, counters = hybrid.forward(
            params, self.cfg, tokens, jnp.reshape(start, (1,)),
            jnp.reshape(suffix_len, (1,)), row, window=kv_bucket, mesh=self.mesh,
        )
        if self.draft:
            row, c = self._module_behind(
                params, hidden, tokens, jnp.reshape(start, (1,)),
                jnp.reshape(suffix_len, (1,)), row, kv_bucket,
            )
            counters = counters + c
        with jax.named_scope("kv_write"):
            cache = jax.tree.map(
                lambda bg, r: jax.lax.dynamic_update_slice_in_dim(bg, r, slot, axis=0),
                cache, row,
            )
        return cache, hidden, self._aux(counters, decode=False)

    def chunks_per_program(self, chunk_tokens: int) -> int:
        """``chunks_sharing_experts`` at the model's widths: 4 for Mellum's
        cut, 8 (the cap) for Ling's.  A model with no expert layer is a
        dense one: 1."""
        cfg = self.cfg
        if not any(mlp in hybrid.EXPERT_MLPS for _, mlp in cfg.layer_kinds):
            return 1
        return chunks_sharing_experts(
            chunk_tokens, cfg.router_outputs, cfg.n_experts_per_tok
        )

    def prefill_rows(self, params, cache, tokens, start, suffix_len, slots, window):
        """``prefill_row`` for the chunks of several slots at once: tokens
        (B, s) of slots ``slots`` (B,) from positions ``start`` (B,), of
        which the first ``suffix_len`` (B,) count, over one static
        ``window`` of rows.  Each layer's weights pass once for all B x s
        token rows; a row gets what it gets alone.  A pad row
        (``suffix_len`` 0) routes to no expert and writes to no slot,
        whatever ``slots`` says of it.  Returns (cache, hidden (B, s, D),
        counters)."""
        if self.rows_in_place:
            return self._prefill_rows_in_place(
                params, cache, tokens, start, suffix_len, slots, window
            )
        live = suffix_len > 0

        def take(name, leaf):
            if name in hybrid.ROW_LEAVES:
                return leaf[slots, :window]
            row = leaf[slots]
            return row if name in hybrid.RING_LEAVES else _from_nothing(row, start)

        rows = tuple({n: take(n, leaf) for n, leaf in layer.items()} for layer in cache)
        hidden, rows, counters = hybrid.forward(
            params, self.cfg, tokens, start, suffix_len, rows, window=window,
            mesh=self.mesh, rows_apart=True,
        )
        if self.draft:
            rows, c = self._module_behind(
                params, hidden, tokens, start, suffix_len, rows, window, apart=True
            )
            counters = counters + c
        # A pad row's slot is past the last: its write is dropped.
        dest = jnp.where(live, slots, jax.tree.leaves(cache)[0].shape[0])
        with jax.named_scope("kv_write"):
            cache = tuple(
                {
                    n: (
                        leaf.at[dest, :window] if n in hybrid.ROW_LEAVES else leaf.at[dest]
                    ).set(row[n], mode="drop")
                    for n, leaf in layer.items()
                }
                for layer, row in zip(cache, rows)
            )
        return cache, hidden, self._aux(counters, decode=False)

    def _prefill_rows_in_place(self, params, cache, tokens, start, suffix_len, slots, window):
        """``prefill_rows`` for a model whose layers work on the slots'
        rows where they lie (latent rows attended in blocks; a ``full`` or
        ``cca`` layer's K/V rows, a prediction module's block among them):
        every layer is handed the slots' whole rows and which slot each row
        of the call is, writes a chunk's rows where they belong and reads
        from there (a latent layer a row's blocks; a ``full`` or ``cca``
        layer the rows its slot holds, by ``ops/gqa_decode.py``'s chunk
        kernel, or, where its gate refuses, the call's windows gathered for
        that layer alone), so no window of the state is written back, and
        none is held for more than a layer (at 8 rows of 32,768 latent rows
        that copy is 0.2 GB a layer each way; at 8 rows of 8,192 over twenty
        ``cca`` layers the windows gathered before the stack and their
        updated copies were 2.5 GB of temporaries beside 14.75 GB of weights
        and state).  What a layer keeps whatever the length (a window
        layer's ring) or as of the last token (a ``cca`` layer's tails,
        ``h_last``: small) is taken by slot and put back, as in
        ``prefill_rows``.  A pad row writes nothing (none of its tokens
        counts) and reads nothing."""
        live = suffix_len > 0

        def take(name, leaf):
            if name in hybrid.ROW_LEAVES:
                return leaf
            row = leaf[slots]
            return row if name in hybrid.RING_LEAVES else _from_nothing(row, start)

        rows = tuple(
            {**{n: take(n, leaf) for n, leaf in layer.items()}, "slot": slots} for layer in cache
        )
        hidden, rows, counters = hybrid.forward(
            params, self.cfg, tokens, start, suffix_len, rows, window=window,
            mesh=self.mesh, rows_apart=True,
        )
        if self.draft:
            rows, c = self._module_behind(
                params, hidden, tokens, start, suffix_len, rows, window, apart=True
            )
            counters = counters + c
        # A pad row's slot is past the last: its rings' and tails' write is
        # dropped.
        dest = jnp.where(live, slots, jax.tree.leaves(cache)[0].shape[0])
        cache = tuple(
            {
                n: row[n] if n in hybrid.ROW_LEAVES else leaf.at[dest].set(row[n], mode="drop")
                for n, leaf in layer.items()
            }
            for layer, row in zip(cache, rows)
        )
        return cache, hidden, self._aux(counters, decode=False)

    def chunk_windows(self, chunk_tokens: int) -> tuple[int, ...]:
        """One window, the whole slot, for a model whose chunk programs
        read a row's blocks in place up to its length (a wider window
        costs such a program nothing, and every window is a program to
        build for each size); else the doubling family."""
        if self.one_window:
            return (self.max_len,)
        return doubling_windows(chunk_tokens, self.max_len)

    def graft_prefix(self, cache, src, dst, n: int):
        """The first ``n`` rows of what holds a row a position; what exists
        only as of the last token comes from a snapshot
        (``restore_state``), since the source's has moved on."""
        out = []
        for layer in cache:
            grafted = dict(layer)
            for name in (n for n in layer if n in hybrid.ROW_LEAVES):
                leaf = layer[name]
                rows = jax.lax.dynamic_slice(
                    leaf, (src, 0, 0), (1, min(n, leaf.shape[1]), leaf.shape[2])
                )
                grafted[name] = jax.lax.dynamic_update_slice(leaf, rows, (dst, 0, 0))
            out.append(grafted)
        return tuple(out)

    @staticmethod
    def _as_of_last_token(layer) -> list:
        """The leaves of a layer's state that hold no row a position."""
        return [n for n in layer if n not in hybrid.ROW_LEAVES]

    def save_state(self, cache, slot):
        """One slot's state as of its last token, leaf by leaf: a copy of
        every leaf that holds no row a position (a KDA layer's state, a
        window layer's ring, a ``cca`` layer's tails, ``h_last``); one
        dict for each layer that has such a leaf."""
        return tuple(
            {n: jax.lax.dynamic_index_in_dim(layer[n], slot, 0, keepdims=False) for n in names}
            for layer in cache if (names := self._as_of_last_token(layer))
        )

    def restore_state(self, cache, slot, snap):
        snaps = iter(snap)
        out = []
        for layer in cache:
            if self._as_of_last_token(layer):
                layer = {**layer, **{
                    n: jax.lax.dynamic_update_index_in_dim(layer[n], leaf, slot, 0)
                    for n, leaf in next(snaps).items()
                }}
            out.append(layer)
        return tuple(out)

    def logits(self, params, hidden):
        return hybrid.logits(params, self.cfg, hidden)

    def make_decode_chunk(self):
        if self.draft:
            return self._make_verify_chunk()
        cfg, max_len, step_logits = self.cfg, self.max_len, self.decode_step

        @functools.partial(jax.jit, donate_argnums=(1,), static_argnums=(8, 9))
        def decode_chunk(
            params, cache, tokens, lengths, key, temp, top_p, top_k,
            n_steps, kv_bucket=None, live=None, carried=None, carry=None,
        ):
            """The signature of ``engine.decode``'s chunk, and a third
            result: the counters summed over the steps.  A row that does
            not decode (``live`` False) writes no latent row and leaves its
            state as it was; its tokens are finite and never emitted."""
            tokens = carry_tokens(tokens, carried, carry)
            window = min(kv_bucket, max_len) if kv_bucket else max_len
            b = tokens.shape[0]
            counts = (
                jnp.ones((b,), jnp.int32) if live is None else live.astype(jnp.int32)
            )

            def body(carry, step):
                cache, tok, key, aux = carry
                key, sub = jax.random.split(key)
                cache, lg, c = step_logits(
                    params, cache, tok, lengths + step, counts, window
                )
                tok = sample(lg, sub, temp, top_p, top_k)
                return (cache, tok, key, aux + c), tok

            (cache, _, _, aux), toks = jax.lax.scan(
                body,
                (cache, tokens, key, jnp.zeros((cfg.n_counters,), jnp.int32)),
                jnp.arange(n_steps, dtype=jnp.int32),
            )
            return cache, toks, self._aux(aux, decode=True)

        return decode_chunk

    def decode_step(self, params, cache, tokens, lengths, counts, window: int):
        """One decode step over every slot, as ``decode_chunk`` scans it:
        ``tokens`` (b,) at positions ``lengths``, of which ``counts`` (0 or
        1 a row) count.  Returns (state, logits (b, V) float32, counters)."""
        start = jnp.minimum(lengths, self.max_len - 1)
        hidden, cache, c = hybrid.forward(
            params, self.cfg, tokens[:, None], start, counts, cache,
            window=window, mesh=self.mesh,
        )
        return cache, hybrid.logits(params, self.cfg, hidden)[:, 0], c

    # -- a decode step that verifies the model's own draft ----------------------

    def _step_start(self, lengths):
        """Where a verify step's two positions start: the row's length,
        held where both fit (a row past its end decodes on until its
        chunk ends; what it writes there is never read)."""
        return jnp.minimum(lengths, self.max_len - 2).astype(jnp.int32)

    def draft_from_last(self, params, cache, tokens, lengths, counts, window: int):
        """Catch the prediction module up with a row's input token:
        ``tokens`` (b,) are the tokens at positions ``lengths`` (not yet
        through the stack), so the module's position ``lengths - 1`` has
        its next token, with ``h_last`` for the stack's output there.
        Returns (state with that row of the module written, the module's
        logits (b, V) float32: its draft of the token at ``lengths + 1``,
        counters).  A row with ``counts`` 0 writes nothing."""
        cfg, L = self.cfg, self.cfg.n_layers
        pos = self._step_start(lengths)[:, None] - 1
        valid = (counts > 0)[:, None] & (pos >= 0)
        hidden = cache[L + 1]["h_last"][:, None].astype(jnp.dtype(cfg.dtype))
        x, rows, counters = hybrid.mtp_forward(
            params, cfg, hidden, tokens[:, None], pos, valid, cache[L],
            window=window, mesh=self.mesh,
        )
        cache = cache[:L] + (rows, cache[L + 1])
        return cache, hybrid.mtp_logits(params, cfg, x)[:, 0], counters

    def verify_stack(self, params, cache, tokens, drafts, lengths, counts, window: int):
        """The stack over ``[token, draft]`` (b, 2) at positions
        ``lengths``, ``lengths + 1``; a row with ``counts`` 0 writes
        nothing.  Returns (state, hidden (b, 2, D), logits (b, 2, V)
        float32, counters): the logits at the first position are the next
        token's whatever the draft was, those at the second are the token
        after a draft that was right."""
        hidden, cache, c = hybrid.forward(
            params, self.cfg, jnp.stack([tokens, drafts], axis=1), self._step_start(lengths),
            2 * (counts > 0).astype(jnp.int32), cache, window=window, mesh=self.mesh,
        )
        return cache, hidden, hybrid.logits(params, self.cfg, hidden), c

    def verify_module(self, params, cache, hidden, next_tokens, lengths, n_emit, window: int):
        """The prediction module over the positions a verify step accepted:
        ``hidden`` (b, 2, D) the stack's at ``lengths``, ``lengths + 1``,
        ``next_tokens`` (b, 2) the tokens that follow them, of which the
        first ``n_emit`` (0, 1 or 2 a row) count.  Returns (state with
        those rows of the module written and ``h_last`` moved to the last
        of them, the module's logits (b, V) float32 at that position: the
        next step's draft, counters)."""
        cfg, L = self.cfg, self.cfg.n_layers
        steps = jnp.arange(2, dtype=jnp.int32)[None, :]
        pos = self._step_start(lengths)[:, None] + steps
        valid = steps < n_emit[:, None]
        x, rows, counters = hybrid.mtp_forward(
            params, cfg, hidden, next_tokens, pos, valid, cache[L],
            window=window, mesh=self.mesh,
        )
        last = cache[L + 1]["h_last"]
        last = jnp.where((n_emit > 0)[:, None], _last_counted(hidden, n_emit).astype(last.dtype), last)
        cache = cache[:L] + (rows, {"h_last": last})
        return cache, hybrid.mtp_logits(params, cfg, _last_counted(x, n_emit)), counters

    def _make_verify_chunk(self):
        cfg, max_len = self.cfg, self.max_len
        # Layers of the stack whose row at a rejected position is written again.
        n_rewritten = len(cfg.layers_of("full")) + len(cfg.layers_of("mla"))

        @functools.partial(jax.jit, donate_argnums=(1,), static_argnums=(8, 9))
        def decode_chunk(
            params, cache, tokens, lengths, key, temp, top_p, top_k,
            n_steps, kv_bucket=None, live=None, carried=None, carry=None,
            carried_len=None, carry_len=None,
        ):
            """The plain chunk's signature; every step verifies the
            prediction module's draft and emits one or two tokens a row.
            Returns (state, tokens (n_steps, b, 2), how many of the two a
            row emitted (n_steps, b), what the next chunk may read without
            the host: each row's newest token (1, b) and its length (b,),
            counters).  A greedy row keeps its
            draft where the stack's own choice is the same token, so its
            tokens are the plain chunk's; a sampled row emits one token a
            step, drawn by the plain sampler from the first position's
            logits.  A row that does not decode (``live`` False) emits
            nothing and writes nothing.  ``carried_len`` / ``carry_len``:
            as ``carried`` / ``carry`` for the tokens, the lengths the
            chunk before this one left for the rows ``carry_len`` marks
            (how far its drafts took a row only the device knows until
            that chunk is fetched)."""
            tokens = carry_tokens(tokens, carried, carry)
            if carried_len is not None and carry_len is not None:
                lengths = jnp.where(carry_len, carried_len, lengths)
            window = min(kv_bucket, max_len) if kv_bucket else max_len
            b = tokens.shape[0]
            on = jnp.ones((b,), bool) if live is None else live
            counts = on.astype(jnp.int32)
            greedy = on & (temp <= 0.0)
            cache, lg, c0 = self.draft_from_last(
                params, cache, tokens, lengths, counts, window
            )
            draft = jnp.argmax(lg, axis=-1).astype(jnp.int32)

            def body(carry, _):
                cache, tok, draft, lens, key, aux, drafted = carry
                # The plain chunk's keys: a sampled row's token is the
                # plain sampler's draw from the same logits.
                key, sub = jax.random.split(key)
                cache, hidden, lg, c = self.verify_stack(
                    params, cache, tok, draft, lens, counts, window
                )
                with jax.named_scope("verify/accept"):
                    t1 = sample(lg[:, 0], sub, temp, top_p, top_k)
                    # Only a greedy row keeps a draft, and its next token
                    # is then the second position's largest logit.
                    t2 = jnp.argmax(lg[:, 1], axis=-1).astype(jnp.int32)
                    accept = greedy & (draft == t1)
                    n_emit = counts + accept.astype(jnp.int32)
                with jax.named_scope("verify/emit"):
                    out = jnp.stack([t1, t2], axis=1)
                    nxt = jnp.where(accept, t2, t1)
                cache, mlg, cm = self.verify_module(
                    params, cache, hidden, out, lens, n_emit, window
                )
                proposed = greedy.sum().astype(jnp.int32)
                accepted = accept.sum().astype(jnp.int32)
                drafted = drafted + jnp.stack([
                    proposed, accepted, 2 * counts.sum(), n_emit.sum(),
                    n_rewritten * (counts.sum() - accepted),
                ]).astype(jnp.int32)
                draft = jnp.argmax(mlg, axis=-1).astype(jnp.int32)
                carry = (cache, nxt, draft, lens + n_emit, key, aux + c + cm, drafted)
                return carry, (out, n_emit)

            init = (
                cache, tokens, draft, lengths.astype(jnp.int32), key,
                c0,
                jnp.zeros((len(self.DRAFT_COUNTERS),), jnp.int32),
            )
            (cache, tok, _, lens, _, aux, drafted), (toks, n_emits) = jax.lax.scan(
                body, init, None, length=n_steps
            )
            return (
                cache, toks, n_emits, (tok[None], lens),
                self._aux(aux, decode=True, drafted=drafted),
            )

        return decode_chunk
