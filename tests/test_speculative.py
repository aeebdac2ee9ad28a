"""Speculative decoding: greedy output must equal target-only decoding.

The whole value proposition rests on exactness — the draft may only
change how many target passes run, never a single emitted token.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from generativeaiexamples_tpu.engine.sampler import SamplingParams
from generativeaiexamples_tpu.engine.scheduler import Scheduler
from generativeaiexamples_tpu.models import llama

TARGET_CFG = llama.llama_tiny(dtype="float32", max_seq_len=128)
DRAFT_CFG = llama.llama_tiny(
    dtype="float32", max_seq_len=128, n_layers=1, d_model=64, d_ff=128,
    n_heads=2, n_kv_heads=2, head_dim=32,
)

PROMPTS = [[3, 1, 4, 1, 5], [9, 2, 6], [5, 3, 5, 8, 9, 7, 9]]


class TestSchedulerSpeculation:
    """The scheduler-integrated path (``engine/spec_decode.py``): greedy
    streams must be bit-identical to the plain continuous-batching
    scheduler, with mixed greedy/sampled batches staying correct."""

    def _plain(self, tparams, prompts, max_tokens, temperature=0.0):
        from tests.test_scheduler import _collect

        sched = Scheduler(
            TARGET_CFG, tparams, max_batch=4, max_len=128,
            decode_chunk_size=4,
        )
        sched.start()
        try:
            return [
                _collect(sched, p, max_tokens=max_tokens,
                         temperature=temperature)[0]
                for p in prompts
            ]
        finally:
            sched.stop()

    def _spec_sched(self, tparams, dparams, dcfg=DRAFT_CFG, gamma=3):
        return Scheduler(
            TARGET_CFG, tparams, max_batch=4, max_len=128,
            decode_chunk_size=4, draft_cfg=dcfg, draft_params=dparams,
            gamma=gamma,
        )

    def test_greedy_bit_identity_weak_draft(self):
        """A mostly-disagreeing draft may cost rounds, never tokens."""
        from tests.test_scheduler import _collect

        tparams = llama.init_params(TARGET_CFG, jax.random.PRNGKey(0))
        dparams = llama.init_params(DRAFT_CFG, jax.random.PRNGKey(99))
        want = self._plain(tparams, PROMPTS, 10)
        sched = self._spec_sched(tparams, dparams)
        sched.start()
        try:
            got = [_collect(sched, p, max_tokens=10)[0] for p in PROMPTS]
        finally:
            sched.stop()
        assert got == want
        snap = sched.stats.snapshot()
        assert snap["spec_rounds"] > 0
        assert snap["spec_tokens"] >= snap["spec_rounds"]

    def test_self_draft_high_acceptance(self):
        """Draft == target accepts everything: each live round must emit
        the full gamma+1 tokens."""
        from tests.test_scheduler import _collect

        tparams = llama.init_params(TARGET_CFG, jax.random.PRNGKey(1))
        want = self._plain(tparams, [PROMPTS[0]], 12)
        sched = self._spec_sched(tparams, tparams, dcfg=TARGET_CFG, gamma=3)
        sched.start()
        try:
            got = _collect(sched, PROMPTS[0], max_tokens=12)[0]
        finally:
            sched.stop()
        assert got == want[0]
        snap = sched.stats.snapshot()
        # Full acceptance: tokens/round == gamma + 1 on every round that
        # wasn't truncated by max_tokens.
        assert snap["spec_tokens"] / snap["spec_rounds"] > 2.0

    def test_concurrent_greedy_matches_solo(self):
        """Rows joining the running batch mid-flight (continuous batching)
        keep bit-identity — admission prefills BOTH caches."""
        import threading

        from tests.test_scheduler import _collect

        tparams = llama.init_params(TARGET_CFG, jax.random.PRNGKey(0))
        dparams = llama.init_params(DRAFT_CFG, jax.random.PRNGKey(98))
        sched = self._spec_sched(tparams, dparams)
        sched.start()
        try:
            solo = [
                _collect(sched, p, max_tokens=8)[0] for p in PROMPTS
            ]
            results = {}
            threads = []
            for i, p in enumerate(PROMPTS):
                t = threading.Thread(
                    target=lambda i=i, p=p: results.update(
                        {i: _collect(sched, p, max_tokens=8)[0]}
                    )
                )
                t.start()
                threads.append(t)
            for t in threads:
                t.join(timeout=60)
        finally:
            sched.stop()
        assert [results[i] for i in range(len(PROMPTS))] == solo

    def test_mixed_sampled_rows(self):
        """temperature > 0 rows ride the spec chunk (one target-sampled
        token per round) while greedy rows stay exact."""
        import threading

        from tests.test_scheduler import _collect

        tparams = llama.init_params(TARGET_CFG, jax.random.PRNGKey(0))
        dparams = llama.init_params(DRAFT_CFG, jax.random.PRNGKey(97))
        want = self._plain(tparams, [PROMPTS[0]], 8)[0]
        sched = self._spec_sched(tparams, dparams)
        sched.start()
        try:
            out = {}

            def sampled():
                out["s"] = _collect(
                    sched, PROMPTS[1], max_tokens=8, temperature=0.9
                )
            t = threading.Thread(target=sampled)
            t.start()
            out["g"] = _collect(sched, PROMPTS[0], max_tokens=8)
            t.join(timeout=60)
        finally:
            sched.stop()
        assert out["g"][0] == want
        tokens, reason = out["s"]
        assert len(tokens) == 8 and reason == "length"
        assert all(0 <= t < TARGET_CFG.vocab_size for t in tokens)

    def test_eos_stops(self):
        from tests.test_scheduler import _collect

        tparams = llama.init_params(TARGET_CFG, jax.random.PRNGKey(2))
        ref = self._plain(tparams, [PROMPTS[0]], 12)[0]
        eos = ref[5]
        dparams = llama.init_params(DRAFT_CFG, jax.random.PRNGKey(96))
        sched = self._spec_sched(tparams, dparams)
        sched.start()
        try:
            tokens: list[int] = []
            import queue as _q

            done: "_q.Queue[str]" = _q.Queue()
            from generativeaiexamples_tpu.engine.scheduler import Request

            sched.submit(
                Request(
                    token_ids=list(PROMPTS[0]),
                    sampling=SamplingParams(temperature=0.0, max_tokens=12),
                    on_token=tokens.append,
                    on_done=done.put,
                    eos_id=eos,
                )
            )
            reason = done.get(timeout=60)
        finally:
            sched.stop()
        assert reason == "stop"
        assert tokens == ref[:5]

    def test_vocab_mismatch_rejected(self):
        with pytest.raises(ValueError):
            Scheduler(
                TARGET_CFG, max_batch=2, max_len=128,
                draft_cfg=llama.llama_tiny(vocab_size=77),
            )

    def test_append_verify_near_length_cap(self, monkeypatch):
        """Rows approaching max_len must finish BEFORE the append-buffer
        flush-clip zone: a clipped per-round flush would overwrite real
        history that the next round's verify re-reads.  The spec
        scheduler trades gamma+1 tokens of capacity for that margin; its
        stream must equal the plain scheduler's PREFIX, uncorrupted."""
        from tests.test_scheduler import _collect

        monkeypatch.setenv("GAIE_FORCE_APPEND_BUFFER", "1")
        cfg8 = llama.llama_tiny(
            dtype="float32", max_seq_len=64, kv_dtype="int8",
            n_heads=4, n_kv_heads=2,
        )
        tparams = llama.init_params(cfg8, jax.random.PRNGKey(3))
        gamma = 3
        prompt = PROMPTS[2]  # 7 tokens; decode to the cap
        plain = Scheduler(
            cfg8, tparams, max_batch=2, max_len=64, decode_chunk_size=4
        )
        plain.start()
        try:
            want, want_reason = _collect(plain, prompt, max_tokens=100)
        finally:
            plain.stop()
        assert want_reason == "length"
        dparams = llama.init_params(DRAFT_CFG, jax.random.PRNGKey(94))
        spec = Scheduler(
            cfg8, tparams, max_batch=2, max_len=64, decode_chunk_size=4,
            draft_cfg=DRAFT_CFG, draft_params=dparams, gamma=gamma,
        )
        assert spec.effective_max_len == 64 - (gamma + 1)
        spec.start()
        try:
            got, got_reason = _collect(spec, prompt, max_tokens=100)
        finally:
            spec.stop()
        assert got_reason == "length"
        # Margin costs exactly gamma+1 tokens of capacity; everything
        # emitted must be bit-identical to the plain stream's prefix —
        # any flush corruption would diverge the tail.
        assert len(got) == len(want) - (gamma + 1)
        assert got == want[: len(got)]

    def test_int8_append_verify_bit_identity(self, monkeypatch):
        """The TPU-serving spec configuration — int8 target KV with the
        append-buffer verify pass (no big-cache scatters) — must stay
        bit-identical to the plain int8 scheduler's greedy stream."""
        from tests.test_scheduler import _collect

        monkeypatch.setenv("GAIE_FORCE_APPEND_BUFFER", "1")
        cfg8 = llama.llama_tiny(
            dtype="float32", max_seq_len=128, kv_dtype="int8",
            n_heads=4, n_kv_heads=2,
        )
        tparams = llama.init_params(cfg8, jax.random.PRNGKey(0))
        plain = Scheduler(
            cfg8, tparams, max_batch=4, max_len=128, decode_chunk_size=4
        )
        plain.start()
        try:
            want = [
                _collect(plain, p, max_tokens=10)[0] for p in PROMPTS
            ]
        finally:
            plain.stop()
        dparams = llama.init_params(DRAFT_CFG, jax.random.PRNGKey(95))
        spec = Scheduler(
            cfg8, tparams, max_batch=4, max_len=128, decode_chunk_size=4,
            draft_cfg=DRAFT_CFG, draft_params=dparams, gamma=3,
        )
        spec.start()
        try:
            got = [_collect(spec, p, max_tokens=10)[0] for p in PROMPTS]
        finally:
            spec.stop()
        assert got == want


class TestRejectionSampling:
    """True speculative sampling (Leviathan/Chen rejection acceptance):
    sampled rows' emitted-token marginal must equal the warped target
    distribution the plain sampler draws from, at any draft quality."""

    MAX_LEN = 64
    GAMMA = 2
    PROMPT = PROMPTS[0]

    def _chunk_fn(self, dcfg):
        from generativeaiexamples_tpu.engine.spec_decode import (
            make_spec_chunk_fn,
        )

        return make_spec_chunk_fn(TARGET_CFG, dcfg, None, self.MAX_LEN)

    def _prefill(self, cfg, params, b):
        """Caches holding the prompt minus its last token (the chunk's
        ``tok`` input, whose KV is not yet written — the scheduler's
        convention), replicated over b identical rows."""
        import jax.numpy as jnp

        toks = np.tile(np.array(self.PROMPT[:-1])[None], (b, 1))
        cache = llama.init_kv_cache(cfg, b, self.MAX_LEN)
        positions = jnp.broadcast_to(
            jnp.arange(toks.shape[1], dtype=jnp.int32), toks.shape
        )
        _, cache = llama.forward(
            params, cfg, jnp.asarray(toks), positions, cache,
            jnp.full((b,), toks.shape[1], jnp.int32), cold_prefill=True,
        )
        return jax.tree.map(np.asarray, cache)

    def _expected_dist(self, tparams, temp, top_p, top_k):
        """Analytic warped target distribution for the first emitted
        token (conditioned on the full prompt)."""
        from generativeaiexamples_tpu.engine import sampler as S

        toks = np.array(self.PROMPT)[None]
        positions = np.arange(len(self.PROMPT))[None]
        hidden, _ = llama.forward(
            tparams, TARGET_CFG, jnp.asarray(toks), jnp.asarray(positions)
        )
        logits = llama.logits(tparams, hidden)[:, -1]
        ids, probs = S.warped_candidates(
            logits,
            jnp.array([temp]), jnp.array([top_p]), jnp.array([top_k]),
        )
        return np.asarray(ids[0]), np.asarray(probs[0])

    def _collect_first_tokens(
        self, tparams, dparams, dcfg, temp, top_p, top_k, n_calls=64, b=16
    ):
        fn = self._chunk_fn(dcfg)
        tcache0 = self._prefill(TARGET_CFG, tparams, b)
        dcache0 = self._prefill(dcfg, dparams, b)
        tok = jnp.full((b,), self.PROMPT[-1], jnp.int32)
        lengths = jnp.full((b,), len(self.PROMPT) - 1, jnp.int32)
        temp_a = jnp.full((b,), temp, jnp.float32)
        topp_a = jnp.full((b,), top_p, jnp.float32)
        topk_a = jnp.full((b,), top_k, jnp.int32)
        firsts, emits = [], []
        for i in range(n_calls):
            _, _, outs, n_emits = fn(
                (tparams, dparams),
                jax.tree.map(jnp.asarray, tcache0),
                jax.tree.map(jnp.asarray, dcache0),
                tok, lengths, jax.random.PRNGKey(1000 + i),
                temp_a, topp_a, topk_a, 1, self.GAMMA, self.MAX_LEN,
            )
            firsts.extend(np.asarray(outs)[0, :, 0].tolist())
            emits.extend(np.asarray(n_emits)[0].tolist())
        return np.array(firsts), np.array(emits)

    def _tv_distance(self, firsts, ids, probs):
        emp = np.zeros_like(probs)
        other = 0.0
        for t in firsts:
            where = np.nonzero(ids == t)[0]
            if len(where):
                emp[where[0]] += 1.0 / len(firsts)
            else:
                other += 1.0 / len(firsts)
        return 0.5 * (np.abs(emp - probs).sum() + other)

    def test_selfdraft_sampled_full_acceptance(self):
        """q == p: every draft accepted (u*q < p never fails), so every
        round emits gamma+1 tokens for sampled rows."""
        tparams = llama.init_params(TARGET_CFG, jax.random.PRNGKey(4))
        firsts, emits = self._collect_first_tokens(
            tparams, tparams, TARGET_CFG, temp=1.0, top_p=0.95, top_k=8,
            n_calls=8, b=4,
        )
        assert (emits == self.GAMMA + 1).all()
        ids, probs = self._expected_dist(tparams, 1.0, 0.95, 8)
        support = set(ids[probs > 0].tolist())
        assert set(firsts.tolist()) <= support

    def test_distribution_equivalence_perturbed_draft(self):
        """A near-target draft: acceptance is partial (both accept and
        reject paths run) and the first-token marginal still equals the
        warped target distribution."""
        tparams = llama.init_params(TARGET_CFG, jax.random.PRNGKey(4))
        dparams = dict(tparams)
        dparams["lm_head"] = tparams["lm_head"] + 0.015 * jax.random.normal(
            jax.random.PRNGKey(7), tparams["lm_head"].shape
        )
        firsts, emits = self._collect_first_tokens(
            tparams, dparams, TARGET_CFG, temp=1.2, top_p=0.98, top_k=4,
        )
        ids, probs = self._expected_dist(tparams, 1.2, 0.98, 4)
        tv = self._tv_distance(firsts, ids, probs)
        assert tv < 0.08, f"TV distance {tv:.3f} (n={len(firsts)})"
        # Both branches exercised: some rounds accept >= 1 draft, some
        # reject at position 0.
        assert (emits > 1).any() and (emits == 1).any()

    def test_distribution_equivalence_weak_draft(self):
        """A random (mostly-rejected) draft: the residual/correction path
        dominates and the marginal must STILL be the warped target."""
        tparams = llama.init_params(TARGET_CFG, jax.random.PRNGKey(4))
        dparams = llama.init_params(DRAFT_CFG, jax.random.PRNGKey(93))
        firsts, _ = self._collect_first_tokens(
            tparams, dparams, DRAFT_CFG, temp=1.2, top_p=0.98, top_k=4,
        )
        ids, probs = self._expected_dist(tparams, 1.2, 0.98, 4)
        tv = self._tv_distance(firsts, ids, probs)
        assert tv < 0.08, f"TV distance {tv:.3f} (n={len(firsts)})"

    def test_unfiltered_rows_single_token(self):
        """top_p >= 1 and top_k == 0 rows keep the exact full-vocab
        sampler: one token per round."""
        tparams = llama.init_params(TARGET_CFG, jax.random.PRNGKey(4))
        firsts, emits = self._collect_first_tokens(
            tparams, tparams, TARGET_CFG, temp=1.0, top_p=1.0, top_k=0,
            n_calls=8, b=4,
        )
        assert (emits == 1).all()
        assert ((0 <= firsts) & (firsts < TARGET_CFG.vocab_size)).all()


class TestTrainedPairAcceptance:
    """A target/draft pair TRAINED on the same structured corpus reaches
    non-floor acceptance for sampled requests through the scheduler —
    the hermetic stand-in for a production llama 8B/1B pair;
    random-weight pairs can only measure the overhead floor."""

    @pytest.fixture(scope="class")
    def trained_pair(self):
        import optax

        from generativeaiexamples_tpu.engine import training

        tcfg = llama.llama_tiny(dtype="float32", max_seq_len=64)
        dcfg = llama.llama_tiny(
            dtype="float32", max_seq_len=64, n_layers=1
        )
        # Deterministic cyclic corpus with a few interleaved cycles: both
        # models learn "next token in cycle" to near-certainty.
        rng = np.random.default_rng(0)
        period = 7
        base = np.arange(10, 10 + period)

        def batch(bsz=32, seq=33):
            phase = rng.integers(0, period, bsz)
            rows = np.stack(
                [np.tile(base, 6)[p : p + seq] for p in phase]
            )
            return {
                "tokens": jnp.asarray(rows[:, :-1]),
                "targets": jnp.asarray(rows[:, 1:]),
                "mask": jnp.ones((bsz, seq - 1), jnp.float32),
            }

        pair = []
        for cfg, seed in ((tcfg, 0), (dcfg, 1)):
            opt = optax.adam(3e-3)
            state = training.init_train_state(
                cfg, opt, jax.random.PRNGKey(seed)
            )
            step = jax.jit(training.make_train_step(cfg, opt))
            for _ in range(120):
                state, metrics = step(state, batch())
            assert float(metrics["loss"]) < 0.2, float(metrics["loss"])
            pair.append(state.params)
        return tcfg, dcfg, pair[0], pair[1]

    def test_sampled_acceptance_above_floor(self, trained_pair):
        from tests.test_scheduler import _collect

        tcfg, dcfg, tparams, dparams = trained_pair
        gamma = 3
        sched = Scheduler(
            tcfg, tparams, max_batch=2, max_len=64, decode_chunk_size=4,
            draft_cfg=dcfg, draft_params=dparams, gamma=gamma,
        )
        sched.start()
        try:
            prompt = [10, 11, 12, 13, 14, 15, 16, 10, 11, 12]
            tokens, reason = _collect(
                sched, prompt, max_tokens=24, temperature=0.7
            )
        finally:
            sched.stop()
        assert reason == "length" and len(tokens) == 24
        snap = sched.stats.snapshot()
        accept = (snap["spec_tokens"] / snap["spec_rounds"] - 1.0) / gamma
        # Trained pair on a learned-deterministic continuation: well
        # above the random-pair floor (~0).
        assert accept > 0.5, f"acceptance {accept:.2f}"
        assert all(0 <= t < tcfg.vocab_size for t in tokens)

    def test_greedy_bit_identity_trained_pair(self, trained_pair):
        from tests.test_scheduler import _collect

        tcfg, dcfg, tparams, dparams = trained_pair
        plain = Scheduler(
            tcfg, tparams, max_batch=2, max_len=64, decode_chunk_size=4
        )
        plain.start()
        try:
            want = _collect(plain, [10, 11, 12], max_tokens=20)[0]
        finally:
            plain.stop()
        spec = Scheduler(
            tcfg, tparams, max_batch=2, max_len=64, decode_chunk_size=4,
            draft_cfg=dcfg, draft_params=dparams, gamma=3,
        )
        spec.start()
        try:
            got = _collect(spec, [10, 11, 12], max_tokens=20)[0]
        finally:
            spec.stop()
        assert got == want


class TestSelfDraft:
    def test_layer_slice_shares_weights(self):
        from generativeaiexamples_tpu.engine.spec_decode import self_draft

        cfg = llama.llama_tiny(dtype="float32", max_seq_len=64, n_layers=4)
        params = llama.init_params(cfg, jax.random.PRNGKey(0))
        dcfg, dparams = self_draft(cfg, params, 2)
        assert dcfg.n_layers == 2
        assert dparams["layers"]["wq"].shape[0] == 2
        np.testing.assert_array_equal(
            np.asarray(dparams["layers"]["wq"]),
            np.asarray(params["layers"]["wq"][:2]),
        )
        assert dparams["embed"] is params["embed"]
        with pytest.raises(ValueError):
            self_draft(cfg, params, 4)

    def test_scheduler_runs_with_self_draft(self):
        from tests.test_scheduler import _collect

        from generativeaiexamples_tpu.engine.spec_decode import self_draft

        cfg = llama.llama_tiny(dtype="float32", max_seq_len=128, n_layers=4)
        params = llama.init_params(cfg, jax.random.PRNGKey(0))
        plain = Scheduler(cfg, params, max_batch=2, max_len=128,
                          decode_chunk_size=4)
        plain.start()
        try:
            want = _collect(plain, PROMPTS[0], max_tokens=10)[0]
        finally:
            plain.stop()
        dcfg, dparams = self_draft(cfg, params, 2)
        spec = Scheduler(
            cfg, params, max_batch=2, max_len=128, decode_chunk_size=4,
            draft_cfg=dcfg, draft_params=dparams, gamma=3,
        )
        spec.start()
        try:
            got = _collect(spec, PROMPTS[0], max_tokens=10)[0]
        finally:
            spec.stop()
        assert got == want


class TestNgramSpeculation:
    """Prompt-lookup speculation: drafts from the sequence's own history
    (no draft model).  Greedy streams stay bit-identical at ANY match
    quality; repetitive continuations (the RAG quote-the-context case)
    reach high acceptance."""

    def _plain_stream(self, cfg, params, prompt, max_tokens, temperature=0.0):
        from tests.test_scheduler import _collect

        sched = Scheduler(
            cfg, params, max_batch=2, max_len=128, decode_chunk_size=4
        )
        sched.start()
        try:
            return _collect(
                sched, prompt, max_tokens=max_tokens, temperature=temperature
            )
        finally:
            sched.stop()

    def _ngram_sched(self, cfg, params, gamma=3):
        return Scheduler(
            cfg, params, max_batch=2, max_len=128, decode_chunk_size=4,
            spec_mode="ngram", gamma=gamma,
        )

    def test_greedy_bit_identity(self):
        from tests.test_scheduler import _collect

        params = llama.init_params(TARGET_CFG, jax.random.PRNGKey(0))
        prompts = [
            [3, 1, 4, 1, 5],
            [7, 8, 9, 7, 8, 9, 7, 8],  # repeating: matcher fires
            [2, 2, 2, 2, 2, 2],        # degenerate unigram repetition
        ]
        want = [self._plain_stream(TARGET_CFG, params, p, 12)[0] for p in prompts]
        sched = self._ngram_sched(TARGET_CFG, params)
        sched.start()
        try:
            got = [_collect(sched, p, max_tokens=12)[0] for p in prompts]
        finally:
            sched.stop()
        assert got == want
        snap = sched.stats.snapshot()
        assert snap["spec_rounds"] > 0

    def test_repetitive_continuation_high_acceptance(self):
        """A target trained to continue a cycle + a prompt containing the
        cycle: lookup proposals are right, acceptance is high."""
        import optax

        from tests.test_scheduler import _collect

        from generativeaiexamples_tpu.engine import training

        cfg = llama.llama_tiny(dtype="float32", max_seq_len=128)
        rng = np.random.default_rng(0)
        period = 7
        base = np.arange(10, 10 + period)

        def batch(bsz=32, seq=33):
            phase = rng.integers(0, period, bsz)
            rows = np.stack([np.tile(base, 6)[p : p + seq] for p in phase])
            return {
                "tokens": jnp.asarray(rows[:, :-1]),
                "targets": jnp.asarray(rows[:, 1:]),
                "mask": jnp.ones((bsz, seq - 1), jnp.float32),
            }

        opt = optax.adam(3e-3)
        state = training.init_train_state(cfg, opt, jax.random.PRNGKey(0))
        step = jax.jit(training.make_train_step(cfg, opt))
        for _ in range(120):
            state, metrics = step(state, batch())
        assert float(metrics["loss"]) < 0.2

        prompt = list(np.tile(base, 2)[:10])  # cycle appears twice
        gamma = 3
        want, _ = self._plain_stream(cfg, state.params, prompt, 21)
        sched = self._ngram_sched(cfg, state.params, gamma=gamma)
        sched.start()
        try:
            from tests.test_scheduler import _collect

            got, reason = _collect(sched, prompt, max_tokens=21)
        finally:
            sched.stop()
        assert got == want and reason == "length"
        snap = sched.stats.snapshot()
        accept = (snap["spec_tokens"] / snap["spec_rounds"] - 1.0) / gamma
        assert accept > 0.5, f"acceptance {accept:.2f}"

    def test_sampled_distribution_equivalence(self):
        """The one-hot-q rejection test keeps the warped-target marginal
        for sampled rows regardless of what the matcher proposes."""
        from generativeaiexamples_tpu.engine.spec_decode import (
            make_ngram_spec_chunk_fn,
        )

        max_len, gamma, b = 64, 2, 16
        tparams = llama.init_params(TARGET_CFG, jax.random.PRNGKey(4))
        fn = make_ngram_spec_chunk_fn(TARGET_CFG, None, max_len)
        prompt = [7, 8, 9, 7, 8]  # trailing bigram (7,8) recurs at p=1
        toks = np.tile(np.array(prompt[:-1])[None], (b, 1))
        cache = llama.init_kv_cache(TARGET_CFG, b, max_len)
        positions = jnp.broadcast_to(
            jnp.arange(toks.shape[1], dtype=jnp.int32), toks.shape
        )
        _, cache = llama.forward(
            tparams, TARGET_CFG, jnp.asarray(toks), positions, cache,
            jnp.full((b,), toks.shape[1], jnp.int32), cold_prefill=True,
        )
        cache0 = jax.tree.map(np.asarray, cache)
        hist0 = np.zeros((b, max_len), np.int32)
        hist0[:, : len(prompt)] = prompt
        tok = jnp.full((b,), prompt[-1], jnp.int32)
        lengths = jnp.full((b,), len(prompt) - 1, jnp.int32)
        temp = jnp.full((b,), 1.2, jnp.float32)
        top_p = jnp.full((b,), 0.98, jnp.float32)
        top_k = jnp.full((b,), 4, jnp.int32)
        firsts = []
        for i in range(64):
            _, _, outs, n_emits = fn(
                tparams, jax.tree.map(jnp.asarray, cache0),
                jnp.asarray(hist0), tok, lengths,
                jax.random.PRNGKey(2000 + i), temp, top_p, top_k,
                1, gamma, max_len,
            )
            firsts.extend(np.asarray(outs)[0, :, 0].tolist())
        # Analytic warped target distribution after the full prompt.
        from generativeaiexamples_tpu.engine import sampler as S

        full = np.array(prompt)[None]
        hidden, _ = llama.forward(
            tparams, TARGET_CFG, jnp.asarray(full),
            jnp.arange(len(prompt))[None],
        )
        logits = llama.logits(tparams, hidden)[:, -1]
        ids, probs = S.warped_candidates(
            logits, jnp.array([1.2]), jnp.array([0.98]), jnp.array([4])
        )
        ids, probs = np.asarray(ids[0]), np.asarray(probs[0])
        emp = np.zeros_like(probs)
        other = 0.0
        for t in firsts:
            where = np.nonzero(ids == t)[0]
            if len(where):
                emp[where[0]] += 1.0 / len(firsts)
            else:
                other += 1.0 / len(firsts)
        tv = 0.5 * (np.abs(emp - probs).sum() + other)
        assert tv < 0.08, f"TV distance {tv:.3f} (n={len(firsts)})"

    def test_mutual_exclusion_and_validation(self):
        params = llama.init_params(TARGET_CFG, jax.random.PRNGKey(0))
        with pytest.raises(ValueError, match="excludes a draft model"):
            Scheduler(
                TARGET_CFG, params, max_batch=2, max_len=128,
                spec_mode="ngram", draft_cfg=DRAFT_CFG,
            )
        with pytest.raises(ValueError, match="unknown spec_mode"):
            Scheduler(
                TARGET_CFG, params, max_batch=2, max_len=128,
                spec_mode="medusa",
            )
