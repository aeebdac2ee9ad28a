"""Scheduler-integrated speculative decoding: the device-side chunk.

TRT-LLM ships draft-model speculative decoding inside its serving engine
(reference consumes it via the NIM container, SURVEY.md §2.8;
``deploy/compose/docker-compose-nim-ms.yaml:2-22`` is the engine that owns
this class of optimization); this is the TPU-native equivalent wired into
the continuous-batching scheduler; it is the repo's one speculative
engine.

One **speculation round** per live slot:

* the draft model decodes ``gamma`` greedy tokens (its own slot cache,
  same slot indexing as the target's);
* the target scores ``[tok, d_1..d_gamma]`` in ONE warm multi-token pass
  over its slot cache — one target weight pass amortized over up to
  ``gamma + 1`` emitted tokens;
* greedy rows (temperature 0) accept the longest agreeing prefix plus the
  target's own next token — output is bit-identical to the plain decode
  chunk's greedy stream;
* sampled rows (temperature > 0 with top-p/top-k filtering active) run
  true speculative SAMPLING (Leviathan et al. 2023 / Chen et al. 2023
  rejection sampling): the draft samples ``x_i ~ q_i`` from its own
  warped distribution, the target accepts ``x_i`` with probability
  ``min(1, p_i(x_i)/q_i(x_i))``, and the first rejected position emits a
  token from the residual ``max(p_i - q_i, 0)`` (all-accepted rounds emit
  a bonus token from ``p_gamma``).  The emitted-token marginal is exactly
  the warped target distribution the plain sampler draws from — both
  paths share the same candidate-pool warp (``sampler.warped_candidates``)
  — so sampled rows now gain ``1 + E[accepts]`` tokens per target pass
  at zero distribution shift (distribution-equivalence tested in
  ``tests/test_speculative.py``);
* unfiltered sampled rows (top_p >= 1 and top_k == 0) keep the old
  one-token-per-round behavior: the plain sampler draws those from the
  FULL vocab distribution, which the sparse candidate-pool rejection
  test cannot reproduce exactly, and exactness wins over speed here.

Draft sources: an independent draft model (this builder), the target's
own first-K layers (:func:`self_draft` — weight sharing, non-floor
acceptance even at random init), or no model at all
(:func:`make_ngram_spec_chunk_fn` — prompt-lookup proposals mined from
the sequence's own history, verified through the same
:func:`_verify_and_emit` back half as one-hot q distributions).

``n_rounds`` rounds run per chunk in a ``lax.scan`` so the host round-trip
cost is amortized the same way the plain decode chunk amortizes it.  Rows
advance by their own acceptance count (per-row ragged lengths); stale
draft/target KV past a row's accepted point is overwritten by the next
round's writes before any attention window can cover it — the cache
invariant shared with ``speculative.py`` and the scheduler's masked lanes.

Cache layout: with an int8 target cache on a single chip (the TPU
serving configuration), the verify pass uses the append-buffer protocol
— the gamma+1 fresh KV rides a small buffer, attention runs over
[big-cache prefix ; causal buffer] (``ops.decode_attention.
verify_gqa_attention_xla``), and one windowed flush per round lands it —
so the big cache is never scattered into inside the executable and the
spec path shares the plain decode path's memory/layout profile at
serving batch (the scatter layout's entry copies, which ran out of
memory on the chip in 2026-07, cannot occur).  On CPU/bf16 the warm multi-token scatter path
remains the semantics oracle; both are bit-identity tested.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from generativeaiexamples_tpu.engine import sampler
from generativeaiexamples_tpu.engine.sampler import sample
from generativeaiexamples_tpu.models import llama


def gamma_bucket(desired: int, gamma_max: int) -> int:
    """Round a desired lookahead UP to the next power of two, clamped to
    ``[1, gamma_max]``.

    The scheduler's adaptive controller re-picks gamma every chunk from
    per-request acceptance EWMAs; gamma is a static jit argument, so an
    unbucketed controller would compile one chunk executable per distinct
    value it ever emits.  Bucketing bounds the compile set to
    ``{1, 2, 4, ...} ∪ {gamma_max}`` — and rounding UP (never down) means
    adaptation can only over-speculate, which costs rejected draft
    tokens, never under-serve a high-acceptance request."""
    d = max(1, min(int(desired), int(gamma_max)))
    b = 1
    while b < d:
        b <<= 1
    return min(b, int(gamma_max))


# Why a looped stack has no self-draft (``self_draft`` and
# ``LlamaServing.check_supported`` both say it).
LOOPED_SELF_DRAFT = (
    "a looped stack has no early-exit self-draft: the draft is the target's "
    "first layers as a shallower model, and the first layers of a stack "
    "that every token passes ut_steps times are no model: their output "
    "goes back into the same layers, not to the final norm and the head"
)


def self_draft(
    cfg: llama.LlamaConfig, params, n_layers: int
) -> tuple[llama.LlamaConfig, dict]:
    """Early-exit self-speculation: the draft is the target's own first
    ``n_layers`` layers plus its embedding/final-norm/head.

    Layer weights are SHARED (``init_params`` stacks per-layer weights on
    a leading ``n_layers`` axis, so the draft is a leading-axis slice —
    no copy beyond XLA's view), which makes this the zero-extra-weights
    draft option: the only added HBM is the draft's own KV cache.  Works
    on quantized/packed params too — every layer leaf keeps its leading
    layer axis through ``pack_for_serving`` and quantization.
    """
    if cfg.ut_steps > 1:
        raise ValueError(LOOPED_SELF_DRAFT)
    if not 1 <= n_layers < cfg.n_layers:
        raise ValueError(
            f"self-draft depth must be in [1, {cfg.n_layers}), got {n_layers}"
        )
    import dataclasses

    dcfg = dataclasses.replace(cfg, n_layers=n_layers)
    dparams = dict(params)
    dparams["layers"] = jax.tree.map(
        lambda a: a[:n_layers], params["layers"]
    )
    return dcfg, dparams



def _verify_and_emit(
    tparams,
    tcfg,
    mesh,
    max_len,
    kv_bucket,
    use_ab,
    gamma,
    tcache,
    tok,
    lengths0,
    drafts,
    q_ids,
    q_probs,
    greedy,
    temp,
    top_p,
    top_k,
    ksub,
    kacc,
    kres,
):
    """Target verify pass + acceptance + emission — the shared back half
    of every speculation round (model drafts and n-gram drafts differ
    only in where ``drafts``/``q_ids``/``q_probs`` come from; n-gram
    proposals are one-hot q distributions, under which the rejection test
    u*q < p degenerates to u < p(x) and the residual to p minus its
    x-mass — still exactly the warped target marginal).

    Returns ``(tcache, out, n_emit, next_tok, new_lengths)``.
    """
    from generativeaiexamples_tpu.engine.decode import _flush_append_buffer

    b = tok.shape[0]
    bidx = jnp.arange(b)
    inputs = jnp.concatenate([tok[:, None], drafts], axis=1)
    offs = jnp.arange(gamma + 1, dtype=jnp.int32)[None, :]
    tpos = jnp.minimum(lengths0[:, None] + offs, max_len - 1)
    if use_ab:
        ab0 = llama.init_append_buffer(tcfg, b, gamma + 1)
        # kv_lengths = the valid BIG-CACHE prefix; the fresh block
        # attends via the buffer, then one windowed flush per round
        # lands it at [lengths0, lengths0 + gamma + 1).
        hidden, _, ab = llama.forward(
            tparams, tcfg, inputs, tpos, tcache, lengths0,
            mesh=mesh, kv_bucket=kv_bucket, append_cache=(ab0, 0),
        )
        tcache = _flush_append_buffer(tcache, ab, lengths0, max_len)
    else:
        hidden, tcache = llama.forward(
            tparams, tcfg, inputs, tpos, tcache,
            jnp.minimum(lengths0 + gamma + 1, max_len), mesh=mesh,
            kv_bucket=kv_bucket,
        )
    tlogits = llama.logits(tparams, hidden)  # (b, gamma+1, vocab)
    targets = jnp.argmax(tlogits, axis=-1).astype(jnp.int32)

    # -- greedy acceptance ---------------------------------------------
    # targets[:, i] is the target's token AFTER consuming input i; draft
    # d_{i+1} is accepted iff it equals targets[:, i].
    agree = drafts == targets[:, :gamma]
    n_accept = jnp.sum(jnp.cumprod(agree, axis=1), axis=1)

    # -- sampled (rejection-sampling) acceptance -----------------------
    # Gated like sample()'s full-vocab special case: an all-greedy batch
    # (the bit-identical serving mode, and the bench's spec throughput
    # measurement) must not pay the gamma+1 vocab warps + residual
    # arithmetic whose outputs it would discard.
    offs_row = jnp.arange(gamma + 1, dtype=jnp.int32)[None, :]

    def sampled_path():
        # Warp every verify position's target logits into the same
        # sparse candidate distribution the plain sampler uses.
        flat = tlogits.reshape(b * (gamma + 1), -1)
        rep = lambda a: jnp.repeat(a, gamma + 1, 0)  # noqa: E731
        p_ids_f, p_probs_f = sampler.warped_candidates(
            flat, rep(temp), rep(top_p), rep(top_k)
        )
        kk = p_ids_f.shape[-1]
        p_ids = p_ids_f.reshape(b, gamma + 1, kk)
        p_probs = p_probs_f.reshape(b, gamma + 1, kk)
        kq = q_ids.shape[-1]
        # q(x_i) and p_i(x_i) for each draft position (q step i is
        # conditioned identically to target position i).
        qx = sampler.prob_of(
            q_ids.reshape(gamma * b, kq),
            q_probs.reshape(gamma * b, kq),
            jnp.swapaxes(drafts, 0, 1).reshape(gamma * b),
        ).reshape(gamma, b)
        px = sampler.prob_of(
            p_ids[:, :gamma].reshape(b * gamma, kk),
            p_probs[:, :gamma].reshape(b * gamma, kk),
            drafts.reshape(b * gamma),
        ).reshape(b, gamma)
        # Accept x_i with prob min(1, p/q): u*q < p (div-free).
        u = jax.random.uniform(kacc, (b, gamma))
        accept = u * qx.T < px
        n_acc_s = jnp.sum(jnp.cumprod(accept, axis=1), axis=1)
        # Correction token at position j = n_acc_s: residual
        # max(p_j - q_j, 0) over p's candidates; for all-accepted rows
        # j == gamma where q is defined as 0, so the residual is exactly
        # p_gamma — the bonus-token rule falls out for free.
        j = n_acc_s[:, None, None]
        p_at_ids = jnp.take_along_axis(p_ids, j, axis=1)[:, 0]
        p_at = jnp.take_along_axis(p_probs, j, axis=1)[:, 0]
        q_ids_b = jnp.swapaxes(q_ids, 0, 1)  # (b, gamma, kq)
        q_probs_b = jnp.swapaxes(q_probs, 0, 1)
        pad_i = jnp.zeros((b, 1, kq), q_ids_b.dtype)
        pad_p = jnp.zeros((b, 1, kq), q_probs_b.dtype)
        q_at_ids = jnp.take_along_axis(
            jnp.concatenate([q_ids_b, pad_i], 1), j, axis=1
        )[:, 0]
        q_at = jnp.take_along_axis(
            jnp.concatenate([q_probs_b, pad_p], 1), j, axis=1
        )[:, 0]
        q_on_p = jnp.sum(
            jnp.where(
                p_at_ids[:, :, None] == q_at_ids[:, None, :],
                q_at[:, None, :],
                0.0,
            ),
            -1,
        )  # (b, kk)
        residual = jnp.maximum(p_at - q_on_p, 0.0)
        # Degenerate all-zero residual (p <= q everywhere yet a
        # rejection fired — possible only through float rounding): fall
        # back to p itself, still the correct marginal's support.
        residual = jnp.where(
            jnp.sum(residual, -1, keepdims=True) > 1e-9, residual, p_at
        )
        correction = sampler.sample_from_candidates(p_at_ids, residual, kres)
        drafts_pad = jnp.concatenate(
            [drafts, jnp.zeros((b, 1), drafts.dtype)], axis=1
        )
        out_s = jnp.where(offs_row < n_acc_s[:, None], drafts_pad, 0)
        out_s = out_s.at[bidx, n_acc_s].set(correction)
        n_emit_s = n_acc_s + 1
        # Unfiltered sampled rows (top_p >= 1, top_k == 0): the plain
        # sampler draws these from the FULL vocab distribution; keep
        # exactness by emitting one such token and skipping the
        # candidate-pool rejection test.
        sampled0 = sample(tlogits[:, 0], ksub, temp, top_p, top_k)
        unfiltered = (~greedy) & (top_p >= 1.0) & (top_k <= 0)
        out_s = jnp.where(
            unfiltered[:, None],
            jnp.where(offs_row == 0, sampled0[:, None], 0),
            out_s,
        )
        return out_s, jnp.where(unfiltered, 1, n_emit_s)

    out_s, n_emit_s = jax.lax.cond(
        jnp.any(~greedy),
        sampled_path,
        lambda: (
            jnp.zeros((b, gamma + 1), jnp.int32),
            jnp.ones((b,), jnp.int32),
        ),
    )

    out = jnp.where(greedy[:, None], targets, out_s)
    n_emit = jnp.where(greedy, n_accept + 1, n_emit_s)
    # Never advance past max_len - 1 (full rows emit garbage the host has
    # already finished or will finish on its length cap).
    room = jnp.maximum(max_len - 1 - lengths0, 0)
    n_emit = jnp.minimum(n_emit, jnp.maximum(room, 1))
    n_emit = n_emit.astype(jnp.int32)
    next_tok = out[bidx, n_emit - 1]
    new_lengths = jnp.minimum(lengths0 + n_emit, max_len - 1)
    return tcache, out, n_emit, next_tok, new_lengths


def _make_spec_round_body(
    tparams,
    dparams,
    tcfg,
    dcfg,
    mesh,
    max_len,
    kv_bucket,
    use_ab,
    gamma,
    greedy,
    temp,
    top_p,
    top_k,
):
    """One speculation round (draft gamma tokens, verify, emit) as a
    ``lax.scan`` body."""
    b = greedy.shape[0]

    def round_body(carry, _):
        tcache, dcache, tok, lengths, key = carry
        key, ksub, kdraft, kacc, kres = jax.random.split(key, 5)
        lengths0 = jnp.minimum(lengths, max_len - 1)

        # -- draft: gamma tokens, autoregressive ----------------------
        # Greedy rows take the draft argmax; sampled rows SAMPLE from
        # the draft's warped distribution q (recorded sparsely for the
        # rejection test below).
        def draft_body(dc, kstep):
            dcache, cur, pos = dc
            positions = jnp.minimum(pos, max_len - 1)[:, None]
            hidden, dcache = llama.forward(
                dparams, dcfg, cur[:, None], positions, dcache,
                jnp.minimum(pos + 1, max_len), mesh=mesh,
                kv_bucket=kv_bucket,
            )
            dlogits = llama.logits(dparams, hidden)[:, 0]
            kq = min(sampler.CANDIDATES, dcfg.vocab_size)

            def sampled_draft():
                q_ids, q_probs = sampler.warped_candidates(
                    dlogits, temp, top_p, top_k
                )
                drawn = sampler.sample_from_candidates(
                    q_ids, q_probs, kstep
                )
                return q_ids, q_probs, drawn

            # Same gate as the verify side: an all-greedy batch must
            # not pay the per-step vocab warp + categorical draw it
            # would discard.
            q_ids, q_probs, drawn = jax.lax.cond(
                jnp.any(~greedy),
                sampled_draft,
                lambda: (
                    jnp.zeros((b, kq), jnp.int32),
                    jnp.zeros((b, kq), jnp.float32),
                    jnp.zeros((b,), jnp.int32),
                ),
            )
            nxt = jnp.where(
                greedy,
                jnp.argmax(dlogits, axis=-1).astype(jnp.int32),
                drawn,
            )
            return (dcache, nxt, pos + 1), (nxt, q_ids, q_probs)

        (dcache, last_draft, _), (drafts, q_ids, q_probs) = jax.lax.scan(
            draft_body,
            (dcache, tok, lengths0),
            jax.random.split(kdraft, gamma),
        )
        drafts = jnp.swapaxes(drafts, 0, 1)  # (b, gamma)
        # Write d_gamma's K/V too: a fully-accepted round advances past
        # position lengths+gamma, and without this write the draft
        # cache would keep a permanent hole there (degrading later
        # drafts' accuracy — never correctness, which the target's
        # verification owns).
        positions = jnp.minimum(lengths0 + gamma, max_len - 1)[:, None]
        _, dcache = llama.forward(
            dparams, dcfg, last_draft[:, None], positions, dcache,
            jnp.minimum(lengths0 + gamma + 1, max_len), mesh=mesh,
            kv_bucket=kv_bucket,
        )

        tcache, out, n_emit, next_tok, new_lengths = _verify_and_emit(
            tparams, tcfg, mesh, max_len, kv_bucket, use_ab, gamma,
            tcache, tok, lengths0, drafts, q_ids, q_probs, greedy,
            temp, top_p, top_k, ksub, kacc, kres,
        )
        return (
            (tcache, dcache, next_tok, new_lengths, key),
            (out, n_emit),
        )

    return round_body


def make_spec_chunk_fn(
    tcfg: llama.LlamaConfig,
    dcfg: llama.LlamaConfig,
    mesh,
    max_len: int,
):
    """Compiled multi-round speculation chunk.

    Signature: ``fn(params_pair, tcache, dcache, tok, lengths, key, temp,
    top_p, top_k, n_rounds, gamma, kv_bucket)`` with both caches donated
    and ``n_rounds``/``gamma``/``kv_bucket`` static.  ``lengths`` is each
    row's next cache write position (the current token ``tok``'s KV is not
    yet written in either cache — the same convention the plain decode
    chunk uses).  Returns ``(tcache, dcache, outs, n_emits)`` where
    ``outs`` is (n_rounds, b, gamma+1) emitted-token candidates and
    ``n_emits`` (n_rounds, b) how many of each round's candidates are
    real; the host consumes ``outs[r, i, :n_emits[r, i]]`` per live slot.
    """

    @functools.partial(
        jax.jit, donate_argnums=(1, 2), static_argnums=(9, 10, 11)
    )
    def spec_chunk(
        params_pair,
        tcache,
        dcache,
        tok,
        lengths,
        key,
        temp,
        top_p,
        top_k,
        n_rounds,
        gamma,
        kv_bucket,
    ):
        from generativeaiexamples_tpu.ops.decode_attention import (
            use_append_buffer,
        )

        tparams, dparams = params_pair
        b = tok.shape[0]
        greedy = temp <= 0.0
        # Verify-pass dispatch (static per compilation): with an int8
        # target cache on a single chip, the gamma+1 fresh KV rides an
        # append buffer and one windowed flush per round — the big cache
        # is never scattered into inside the executable, so the verify
        # pass shares the plain decode path's memory/layout profile at
        # serving batch.  Elsewhere (CPU tests, bf16 KV) the warm
        # scatter path remains the oracle.
        use_ab = use_append_buffer(
            s=gamma + 1,
            kv_int8=len(tcache) == 4,
            batch=b,
            window=min(kv_bucket, max_len) if kv_bucket else max_len,
            n_q=tcfg.n_heads,
            n_kv=tcfg.n_kv_heads,
            head_dim=tcfg.head_dim,
            mesh=mesh,
        )

        round_body = _make_spec_round_body(
            tparams, dparams, tcfg, dcfg, mesh, max_len, kv_bucket,
            use_ab, gamma, greedy, temp, top_p, top_k,
        )

        (tcache, dcache, tok, lengths, key), (outs, n_emits) = jax.lax.scan(
            round_body,
            (tcache, dcache, tok, lengths, key),
            None,
            length=n_rounds,
        )
        return tcache, dcache, outs, n_emits

    return spec_chunk


def _make_ngram_round_body(
    tparams,
    tcfg,
    mesh,
    max_len,
    kv_bucket,
    use_ab,
    gamma,
    ngram,
    greedy,
    temp,
    top_p,
    top_k,
):
    """One prompt-lookup round (history match, verify, emit) as a
    ``lax.scan`` body."""
    b = greedy.shape[0]
    bidx = jnp.arange(b)
    p_idx = jnp.arange(max_len, dtype=jnp.int32)[None, :]

    def round_body(carry, _):
        tcache, hist, tok, lengths, key = carry
        key, ksub, kacc, kres = jax.random.split(key, 4)
        lengths0 = jnp.minimum(lengths, max_len - 1)
        # The current token is part of the matchable pattern.
        hist = hist.at[bidx, lengths0].set(tok)

        # -- draft: most recent earlier occurrence of the trailing
        # n-gram; the gamma tokens that followed it are the proposal.
        match = (p_idx >= ngram - 1) & (p_idx < lengths0[:, None])
        for k in range(ngram):
            tail = jnp.take_along_axis(
                hist, jnp.maximum(lengths0[:, None] - k, 0), axis=1
            )  # (b, 1): hist[L-k]
            # roll(hist, k)[p] == hist[p-k] for p >= k (wrap-around
            # region is masked out by p_idx >= ngram-1 above).
            match &= jnp.roll(hist, k, axis=1) == tail
        found = jnp.any(match, axis=1)
        # Prefer the most recent match whose ENTIRE gamma-token
        # continuation is already written (p + gamma <= L, where L
        # itself holds the current token): a degenerate loop's most
        # recent match sits at p = L-1 and its continuation runs into
        # unwritten zeros, collapsing acceptance in exactly the
        # repetitive workloads prompt-lookup targets.  Fall back to
        # the most recent partial match when no full one exists.
        full = match & (p_idx + gamma <= lengths0[:, None])
        score = jnp.where(full, p_idx + max_len, jnp.where(match, p_idx, -1))
        j = jnp.argmax(score, axis=1) % max_len
        gidx = jnp.clip(
            j[:, None] + 1 + jnp.arange(gamma, dtype=jnp.int32)[None],
            0,
            max_len - 1,
        )
        drafts = jnp.take_along_axis(hist, gidx, axis=1)  # (b, gamma)
        # No match: propose the current token (always verified, never
        # trusted — the target's acceptance owns correctness).
        drafts = jnp.where(found[:, None], drafts, tok[:, None])
        # One-hot q as width-1 candidate lists (_verify_and_emit is
        # width-generic): q is a point mass on the proposal, under
        # which u*q < p reduces to u < p(x) and the residual to p
        # minus its x-mass.
        drafts_t = jnp.swapaxes(drafts, 0, 1)  # (gamma, b)
        q_ids = drafts_t[..., None]  # (gamma, b, 1)
        q_probs = jnp.ones((gamma, b, 1), jnp.float32)

        tcache, out, n_emit, next_tok, new_lengths = _verify_and_emit(
            tparams, tcfg, mesh, max_len, kv_bucket, use_ab, gamma,
            tcache, tok, lengths0, drafts, q_ids, q_probs, greedy,
            temp, top_p, top_k, ksub, kacc, kres,
        )
        # Record the accepted tokens so later ROUNDS in this chunk can
        # match against them (the host rebuilds its copy from emitted
        # tokens between chunks).  Valid lanes never clip (n_emit is
        # room-clamped); invalid lanes aim out of bounds and are
        # DROPPED — clipping them to max_len-1 could collide with (and
        # nondeterministically overwrite) a valid lane's write there.
        offs = jnp.arange(gamma + 1, dtype=jnp.int32)[None, :]
        wpos = jnp.where(
            offs < n_emit[:, None], lengths0[:, None] + 1 + offs, max_len
        )
        hist = hist.at[bidx[:, None], wpos].set(out, mode="drop")
        return (
            (tcache, hist, next_tok, new_lengths, key),
            (out, n_emit),
        )

    return round_body


def make_ngram_spec_chunk_fn(
    tcfg: llama.LlamaConfig,
    mesh,
    max_len: int,
    ngram: int = 2,
):
    """Prompt-lookup speculation chunk: drafts come from the sequence's
    OWN token history instead of a draft model (vLLM's prompt-lookup /
    "assisted generation by n-gram" — no draft weights, no draft cache,
    zero extra HBM).  Made for RAG serving, where answers quote retrieved
    context verbatim: whenever the last ``ngram`` tokens reappear earlier
    in [prompt + generated-so-far], the following ``gamma`` tokens are
    proposed and the target verifies them in one pass.

    ``hist`` is the (b, max_len) token-input history (hist[p] = the token
    whose KV lands at position p; the scheduler maintains it from prompts
    + emitted tokens).  Proposals verify through the same
    :func:`_verify_and_emit` back half as model drafts — as ONE-HOT q
    distributions, so greedy rows stay bit-identical to the plain
    scheduler and sampled rows keep the exact warped-target marginal.

    Signature: ``fn(tparams, tcache, hist, tok, lengths, key, temp,
    top_p, top_k, n_rounds, gamma, kv_bucket)`` with ``tcache`` AND
    ``hist`` donated (the scheduler keeps the history device-resident —
    rows are scattered in at admission, the chunk carries it forward);
    returns ``(tcache, hist, outs, n_emits)``.
    """
    if ngram < 1:
        raise ValueError(f"ngram must be >= 1, got {ngram}")

    @functools.partial(
        jax.jit, donate_argnums=(1, 2), static_argnums=(9, 10, 11)
    )
    def ngram_chunk(
        tparams,
        tcache,
        hist,
        tok,
        lengths,
        key,
        temp,
        top_p,
        top_k,
        n_rounds,
        gamma,
        kv_bucket,
    ):
        from generativeaiexamples_tpu.ops.decode_attention import (
            use_append_buffer,
        )

        b = tok.shape[0]
        greedy = temp <= 0.0
        use_ab = use_append_buffer(
            s=gamma + 1,
            kv_int8=len(tcache) == 4,
            batch=b,
            window=min(kv_bucket, max_len) if kv_bucket else max_len,
            n_q=tcfg.n_heads,
            n_kv=tcfg.n_kv_heads,
            head_dim=tcfg.head_dim,
            mesh=mesh,
        )
        round_body = _make_ngram_round_body(
            tparams, tcfg, mesh, max_len, kv_bucket, use_ab, gamma,
            ngram, greedy, temp, top_p, top_k,
        )

        (tcache, hist, tok, lengths, key), (outs, n_emits) = jax.lax.scan(
            round_body,
            (tcache, hist, tok, lengths, key),
            None,
            length=n_rounds,
        )
        return tcache, hist, outs, n_emits

    return ngram_chunk
