"""The one speculative engine, through ``Scheduler``: every decode step of
a model that holds its own prediction module verifies that module's draft
(``HybridServing._make_verify_chunk``, ``Scheduler._emit_verified``).

Exactness is the contract: the draft may only change how many passes of
the stack run per emitted token, never which tokens are emitted.  For each
of the two families that draft, one scheduler with the draft off and one
with it on serve the same cases, and the greedy streams have to be equal
on every admission path and wherever a row ends.  The module's own
drafts (random weights) are nearly always rejected, so the drafts are
told (``_Told``): right, from the draft-off streams, and wrong at every
third position, so that a step emits one token or two and a row ends on
either.
"""

import asyncio
import dataclasses
import inspect
import sys
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from aiohttp.test_utils import TestClient, TestServer

from generativeaiexamples_tpu.engine.sampler import SamplingParams
from generativeaiexamples_tpu.engine.scheduler import Request, Scheduler
from generativeaiexamples_tpu.engine.serving_models import HybridServing
from generativeaiexamples_tpu.engine.tokenizer import ByteTokenizer
from generativeaiexamples_tpu.models import hybrid
from tests import test_scheduler

T = 128
CHUNK = 32  # of a prefill; MIN_PREFIX is 32 too, and a snapshot lies at every 32
STEPS = 4  # of a decode chunk
FAMILIES = ("exaone_moe-tiny", "deepseek_v32-tiny")


class _Told(HybridServing):
    """The serving model with the module's drafts replaced: the state is
    moved exactly as it is, the draft of the token at position ``p + 1``
    of a row whose token at ``p`` is ``t`` is ``told[p, t]``."""

    def __init__(self, cfg, max_len, told):
        super().__init__(cfg, None, max_len)
        self.told = jnp.asarray(told, jnp.int32)

    def _said(self, at, tok):
        at = jnp.clip(at, 0, self.told.shape[0] - 1)
        return 50.0 * jax.nn.one_hot(self.told[at, tok], self.cfg.vocab_size)

    def draft_from_last(self, params, cache, tokens, lengths, counts, window):
        cache, _, c = super().draft_from_last(params, cache, tokens, lengths, counts, window)
        return cache, self._said(lengths, tokens), c

    def verify_module(self, params, cache, hidden, next_tokens, lengths, n_emit, window):
        cache, _, c = super().verify_module(params, cache, hidden, next_tokens, lengths, n_emit, window)
        last = jnp.take_along_axis(next_tokens, jnp.maximum(n_emit - 1, 0)[:, None], axis=1)[:, 0]
        return cache, self._said(lengths + n_emit, last), c


def _prompt(seed, n):
    return np.random.RandomState(seed).randint(3, 512, size=n).tolist()


@dataclasses.dataclass
class Ask:
    prompt: list
    n: int
    temperature: float = 0.0
    session: str = ""
    eos: int = None


class Side:
    """One scheduler and what it has served: ``run`` submits a burst while
    the tick thread is stopped, so that its requests wait together, and
    returns each one's (tokens, reason)."""

    def __init__(self, cfg, told=None):
        self.s = Scheduler(cfg, None, max_batch=3, max_len=T, decode_chunk_size=STEPS, seed=3,
                           prefill_chunk_tokens=CHUNK, prefix_cache="shared")
        if told is not None:
            self.s._decode_chunk = _Told(cfg, T, told).make_decode_chunk()
        self.served = []  # (prompt, greedy stream)
        self.n = 0
        self._outs, self._reasons = {}, {}
        # How each request's last verify step went: (tokens the step gave
        # the row, how many of them the row consumed: an EOS is consumed).
        self.last_step = {}
        emit = self.s._emit_verified

        def spy(toks, n_emits, mine):
            live = [(i, req) for i, req in mine
                    if self.s._slots[i].request is req and req.id in self._outs]
            before = {req.id: len(self._outs[req.id]) for _, req in live}
            emit(toks, n_emits, mine)
            for i, req in live:
                if self.s._slots[i].request is req:
                    continue  # goes on
                took = len(self._outs[req.id]) - before[req.id] + (self._reasons[req.id] == ["stop"])
                for step in np.asarray(n_emits)[:, i]:
                    if took <= step:
                        self.last_step[req.id] = (int(step), int(took))
                        break
                    took -= step

        self.s._emit_verified = spy

    def run(self, asks):
        self.s.stop()
        ids, done = [], []
        for ask in asks:
            self.n += 1
            rid, ev = f"q{self.n}", threading.Event()
            out, reason = self._outs.setdefault(rid, []), self._reasons.setdefault(rid, [])
            assert self.s.submit(Request(
                token_ids=list(ask.prompt),
                sampling=SamplingParams(temperature=ask.temperature, top_p=1.0, max_tokens=ask.n),
                on_token=out.append, on_done=lambda r, reason=reason, ev=ev: (reason.append(r), ev.set()),
                eos_id=ask.eos, id=rid, session_id=ask.session,
            ))
            ids.append(rid), done.append(ev)
        self.s.start()
        assert all(ev.wait(300) for ev in done)
        for ask, rid in zip(asks, ids):
            if ask.temperature <= 0.0:
                self.served.append((list(ask.prompt), list(self._outs[rid])))
        return [(self._outs[rid], self._reasons[rid][0]) for rid in ids]

    def forget(self):
        """Every parked slot given up: the next prompt is cold."""
        self.s.stop()
        for i, slot in enumerate(self.s._slots):
            if slot.cached:
                self.s._unpark(i)

    def counted(self):
        return self.s.stats.snapshot()


# -- the cases: each runs on both sides, and returns what is compared -------------------------------


def cold_batch_of_five(run):
    return run([Ask(_prompt(10 + i, n), 7 + i) for i, n in enumerate((9, 14, 20, 25, 31))])


def lone_cold_prompt(run):
    return run([Ask(_prompt(20, 18), 9)])


def prompt_of_several_chunks(run):
    return run([Ask(_prompt(21, 2 * CHUNK + 16), 9)])


def shared_hit(run):
    first = _prompt(22, 70)
    return run([Ask(first, 6)]) + run([Ask(first[:66] + _prompt(23, 6), 10)])


def session_hit(run):
    turn = _prompt(24, 40)
    (out, reason), = run([Ask(turn, 7, session="s-a")])
    return [(out, reason)] + run([Ask(turn + out + _prompt(25, 10), 9, session="s-a")])


def hit_inside_a_chunk(run):
    first = _prompt(26, 50)
    return run([Ask(first, 6)]) + run([Ask(first[:45] + _prompt(27, 40), 10)])


def ends_by_max_tokens(run):
    return [run([Ask(_prompt(30 + n, 12 + n), n)])[0] for n in (6, 7, 8, 9)]


def ends_by_max_len(run):
    return [run([Ask(_prompt(40 + n, n), 40)])[0] for n in (100, 101, 102, 103)]


def ends_by_eos(run):
    """The same prompts again, each with a token of its own stream for its
    EOS: the stream is cut in front of that token's first occurrence."""
    got = []
    for k, seed in enumerate((50, 51, 52, 53)):
        prompt = _prompt(seed, 10 + k)
        (free, _), = run([Ask(prompt, 14)])
        at = next(i for i in range(5 + k, len(free)) if free[i] not in free[:i])
        got.append(run([Ask(prompt, 14, eos=free[at])])[0] + (at,))
    return got


def rows_side_by_side_and_alone(run):
    asks = [Ask(_prompt(60 + i, n), 12) for i, n in enumerate((11, 19, 27))]
    return run(asks) + [run([ask])[0] for ask in asks]


def a_session_parks(run):
    turn = _prompt(70, 44)
    (out, reason), = run([Ask(turn, 9, session="s-b")])
    return turn, out, reason


ADMISSIONS = {
    "cold_batch_of_five": (cold_batch_of_five, "admits_batched"),
    "lone_cold_prompt": (lone_cold_prompt, "admits_lone"),
    "prompt_of_several_chunks": (prompt_of_several_chunks, "prefill_chunks"),
    "shared_hit": (shared_hit, "shared_prefix_hits"),
    "session_hit": (session_hit, "prefix_hits"),
    "hit_inside_a_chunk": (hit_inside_a_chunk, "shared_prefix_hits"),
}
ENDS = {"eos": ends_by_eos, "max_tokens": ends_by_max_tokens, "max_len": ends_by_max_len}
CASES = [fn for fn, _ in ADMISSIONS.values()] + list(ENDS.values()) + [
    rows_side_by_side_and_alone, a_session_parks]


class Pair:
    """The draft off, every case served once; then the draft on, told from
    those streams."""

    def __init__(self, family):
        self.cfg = hybrid.PRESETS[family]()
        off = Side(dataclasses.replace(self.cfg, mtp_layers=0))
        self.want = {fn.__name__: fn(off.run) for fn in CASES}
        # The turn after ``a_session_parks``'s, cold: nothing parked.
        turn, out, _ = self.want["a_session_parks"]
        self.next_turn = turn + out + _prompt(71, 12)
        off.forget()
        (self.next_turn_cold, _), = off.run([Ask(self.next_turn, 9)])
        off.s.stop()
        assert off.counted()["spec_rounds"] == 0 and not off.s.model.draft
        told = np.zeros((T + 2, self.cfg.vocab_size), np.int32)
        for prompt, out in off.served:
            row = prompt + out
            for p in range(len(prompt) - 1, len(row) - 1):
                told[p, row[p]] = row[p + 1]
        # Wrong where the drafted position is a multiple of 3 under 100: behind
        # a first token, steps of 2, 1, 2, 1 tokens.  From 100 on every draft
        # is right, so that a row whose prompt is even ends on ``max_len``
        # with the first of a step's two tokens and an odd one with the second.
        drafted = np.arange(1, T + 3)[:, None]
        wrong = (drafted % 3 == 0) & (drafted < 100)
        self.on = Side(self.cfg, np.where(wrong, (told + 1) % self.cfg.vocab_size, told))

    def serve(self, fn):
        """``fn`` with the draft on: (what it returned, what the draft-off
        side returned, the counters that moved)."""
        before = self.on.counted()
        got = fn(self.on.run)
        after = self.on.counted()
        moved = {k: after[k] - before[k] for k in after if isinstance(after[k], int)}
        return got, self.want[fn.__name__], moved


@pytest.fixture(scope="module", params=FAMILIES)
def pair(request):
    p = Pair(request.param)
    yield p
    p.on.s.stop()


# -- (a) the admission paths ----------------------------------------------------------------------


@pytest.mark.parametrize("case", sorted(ADMISSIONS))
def test_greedy_streams_with_the_draft_on_are_the_streams_with_it_off(pair, case):
    fn, counter = ADMISSIONS[case]
    got, want, moved = pair.serve(fn)
    assert got == want and all(reason == "length" for _, reason in got)
    assert moved[counter] >= (3 if counter == "prefill_chunks" else 1)
    if case == "hit_inside_a_chunk":
        # The hit is cut back to the snapshot at 32 and the rest, more than
        # a chunk, warms chunk by chunk from inside the prompt.
        assert moved["prefill_chunks"] >= 2 and moved["prefix_tokens_reused"] == CHUNK
    # Drafts were kept, every greedy step proposed one, and a kept one is a second token.
    assert 0 < moved["spec_accepted"] <= moved["spec_proposed"] == moved["spec_rounds"]
    assert moved["spec_rounds"] < moved["spec_tokens"] <= moved["spec_rounds"] + moved["spec_accepted"]


# -- (b) a row that ends inside a step -------------------------------------------------------------


@pytest.mark.parametrize("end", sorted(ENDS))
def test_a_row_ends_on_the_first_and_on_the_second_token_of_a_step(pair, end):
    """By EOS, by ``max_tokens`` and by ``max_len`` (``tests/
    test_exaone_moe_model.py``'s case of rows that end inside a step,
    folded in here): the streams are the draft-off ones, the second token
    of a step whose first ended the row is dropped, and both ends occur."""
    first = pair.on.n + 1
    got, want, moved = pair.serve(ENDS[end])
    assert got == want
    for row in got:
        if end == "eos":
            stream, reason, at = row
            assert reason == "stop" and len(stream) == at
        else:
            assert row[1] == "length"
    if end == "max_len":
        assert [len(out) for out, _ in got] == [28, 27, 26, 25]
    ids = [f"q{k}" for k in range(first, pair.on.n + 1)]
    ended = "stop" if end == "eos" else "length"
    lasts = {pair.on.last_step[i] for i in ids if pair.on._reasons[i] == [ended] and i in pair.on.last_step}
    assert {(2, 1), (2, 2)} <= lasts, lasts  # of a step's two tokens, the first / both
    if end != "eos":
        # A row that ended on the first of two had its second dropped with it.
        assert moved["spec_tokens"] < moved["spec_rounds"] + moved["spec_accepted"]


# -- (c) rows side by side --------------------------------------------------------------------------


def test_concurrent_greedy_rows_are_each_row_alone(pair):
    got, want, moved = pair.serve(rows_side_by_side_and_alone)
    assert got == want and got[:3] == got[3:]
    assert 0 < moved["spec_accepted"] < moved["spec_proposed"]


# -- (d) greedy and sampled rows ---------------------------------------------------------------------


def test_a_sampled_row_beside_greedy_rows_takes_one_token_a_step_and_counts_in_no_spec_stat(pair):
    greedy = [Ask(_prompt(80, 13), 12), Ask(_prompt(81, 21), 12)]
    want = [run[0] for run in (pair.on.run([ask]) for ask in greedy)]
    before = pair.on.counted()
    got = pair.on.run(greedy + [Ask(_prompt(82, 17), 12, temperature=0.9)])
    after = pair.on.counted()
    assert got[:2] == want and len(got[2][0]) == 12
    moved = {k: after[k] - before[k] for k in ("spec_rounds", "spec_proposed", "spec_accepted",
                                               "spec_tokens", "draft_proposed", "decode_tokens_emitted")}
    assert moved["spec_accepted"] <= moved["spec_proposed"] == moved["spec_rounds"]
    # The two greedy rows' 11 tokens each behind their first: nothing of the third row's.
    assert moved["spec_tokens"] == 22


def test_a_sampled_row_alone_moves_no_spec_stat(pair):
    before = pair.on.counted()
    (out, reason), = pair.on.run([Ask(_prompt(83, 15), 10, temperature=0.9)])
    after = pair.on.counted()
    assert len(out) == 10 and reason == "length"
    for k in ("spec_rounds", "spec_proposed", "spec_accepted", "spec_tokens", "draft_proposed",
              "draft_accepted"):
        assert after[k] == before[k], k
    assert after["spec_acceptance_ewma"] == before["spec_acceptance_ewma"]
    # One token a step, and the steps ran: two positions a step through the stack.
    assert after["verify_positions"] > before["verify_positions"]
    assert after["decode_chunks"] - before["decode_chunks"] >= -(-9 // STEPS)


# -- (e) what a finished session parks --------------------------------------------------------------


def test_a_parked_history_holds_the_emitted_tokens_and_no_rejected_draft(pair):
    (turn, out, reason), want, moved = pair.serve(a_session_parks)
    assert (turn, out, reason) == want and reason == "length"
    assert moved["spec_accepted"] < moved["spec_proposed"]  # drafts were rejected
    s = pair.on.s
    s.stop()
    slot, = [sl for sl in s._slots if sl.session_id == "s-b"]
    # A length finish never fed its last token back: its row was not written.
    assert slot.cached and slot.history == (turn + out)[:-1] and slot.length == len(turn) + len(out) - 1
    seg, common = s._prefix_index.match(turn + out + [7])
    assert s._slots[seg] is slot and common == slot.length
    # The next turn hits, and streams what the whole prompt streams cold.
    before = pair.on.counted()
    (hit, _), = pair.on.run([Ask(pair.next_turn, 9, session="s-b")])
    assert pair.on.counted()["prefix_hits"] == before["prefix_hits"] + 1
    assert hit == pair.next_turn_cold


# -- (f) churn --------------------------------------------------------------------------------------


def test_churn_with_cancels_in_flight_under_the_draft(pair):
    before = pair.on.counted()
    pair.on.s.stop()
    test_scheduler.TestSchedulerStress.churn(pair.on.s)
    after = pair.on.counted()
    assert after["spec_rounds"] > before["spec_rounds"]
    assert all(sl.request is None for sl in pair.on.s._slots) and after["queued"] == 0


# -- (g) over the engine app ------------------------------------------------------------------------


def test_metrics_count_the_rounds_and_no_removed_series(pair):
    from generativeaiexamples_tpu.engine.server import create_engine_app

    s = pair.on.s
    s.start()
    loop = asyncio.new_event_loop()
    client = TestClient(TestServer(create_engine_app(s, ByteTokenizer(), model_name="tiny")), loop=loop)
    try:
        loop.run_until_complete(client.start_server())

        async def go():
            resp = await client.post("/v1/completions", json={
                "model": "tiny", "prompt": "ab ab ab ab", "max_tokens": 8, "temperature": 0})
            assert resp.status == 200
            assert (await resp.json())["usage"]["completion_tokens"] == 8
            return await (await client.get("/metrics")).text()

        text = loop.run_until_complete(go())
    finally:
        loop.run_until_complete(client.close())
        loop.close()
    series = {ln.split()[0].split("{")[0]: ln.split()[-1] for ln in text.splitlines()
              if ln and not ln.startswith("#")}
    snap = s.stats.snapshot()
    assert float(series["engine_spec_rounds_total"]) > 0
    assert float(series["engine_spec_proposed_total"]) == float(series["engine_spec_rounds_total"])
    assert float(series["engine_draft_proposed_total"]) >= snap["spec_proposed"] > 0
    for name in ("engine_spec_tokens_total", "engine_spec_accepted_total", "engine_spec_acceptance_ewma",
                 "engine_verify_positions_total", "engine_state_bytes_draft"):
        assert name in series, name
    assert "engine_spec_gamma" not in text and "engine_spec_fallbacks_total" not in text


# -- (h) the names that went ------------------------------------------------------------------------

# The second engine's switches (PR 55): a draft model, a layer-slice
# self-draft and prompt-lookup n-grams went with engine/spec_decode.py.
REMOVED = {
    "keywords": ("draft_cfg", "draft_params", "gamma", "draft_quantize", "adaptive_gamma",
                 "spec_mode", "ngram"),
    "flags": ("--draft-model", "--draft-checkpoint", "--spec-decode", "--spec-ngram", "--gamma",
              "--spec-gamma"),
    "fields": ("spec_decode", "draft_model", "spec_gamma"),
}


@pytest.mark.parametrize("surface", sorted(REMOVED))
def test_no_surface_knows_a_removed_name(surface, monkeypatch, capsys):
    if surface == "keywords":
        known = set(inspect.signature(Scheduler.__init__).parameters)
        assert not known & set(REMOVED[surface])
        with pytest.raises(TypeError, match="spec_mode"):
            Scheduler(hybrid.PRESETS["exaone_moe-tiny"](), None, spec_mode="ngram")
        from generativeaiexamples_tpu.engine.serving_models import LlamaServing

        for model in (HybridServing, LlamaServing):
            assert list(inspect.signature(model.check_supported).parameters) == ["self"]
    elif surface == "flags":
        from generativeaiexamples_tpu.engine import server

        for flag in REMOVED[surface]:
            monkeypatch.setattr(sys, "argv", ["engine-server", flag, "1"])
            with pytest.raises(SystemExit) as exit_:
                server.main()
            assert exit_.value.code == 2
            assert f"unrecognized arguments: {flag}" in capsys.readouterr().err
        source = inspect.getsource(server)
        assert "GAIE_SPEC_" not in source and "GAIE_DRAFT_" not in source
    else:
        from generativeaiexamples_tpu.core.configuration import LLMConfig

        assert not {f.name for f in dataclasses.fields(LLMConfig)} & set(REMOVED[surface])
        from generativeaiexamples_tpu.engine.scheduler import Stats

        assert not {"spec_gamma", "spec_fallbacks"} & set(Stats().snapshot())
