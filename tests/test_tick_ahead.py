"""Phase 1 of tick N+1 is dispatched in tick N, behind the decode chunk
and before the host blocks on its tokens.

The ticks are driven from the test thread (``_run_tick``), one at a time,
with a spy on the step programs and on the two fetches, at the tiny size
of both model kinds: a runner decodes in slot 0 while three prompts of
4, 5 and 4 chunks warm beside it.
"""

import numpy as np
import pytest

from generativeaiexamples_tpu.engine.sampler import SamplingParams
from generativeaiexamples_tpu.engine.scheduler import Request, Scheduler
from generativeaiexamples_tpu.engine.weights import resolve_model_preset
from generativeaiexamples_tpu.models import hybrid, llama

CHUNK = 8
LENGTHS = (30, 40, 27)  # 4, 5 and 4 chunks of 8


def _chunks(n):
    return -(-n // CHUNK)


class Driven:
    """A scheduler whose ticks the test runs, with what each tick
    dispatched and fetched: ("chunk", slot, pos), ("decode", live slots),
    ("fetch",) for the decode chunk's tokens and ("first", slot) for a
    prompt's first token, each with the tick's number in front."""

    def __init__(self, kind):
        if kind == "llama":
            cfg = llama.llama_tiny(dtype="float32", max_seq_len=128)
        else:
            cfg = hybrid.PRESETS[resolve_model_preset("ling-tiny")]()
        self.s = s = Scheduler(
            cfg, None, max_batch=4, max_len=128, decode_chunk_size=4,
            seed=5, prefill_chunk_tokens=CHUNK, prefix_cache="off",
        )
        self.events = []
        self.tick = 0
        chunk, decode = s._prefill_suffix, s._decode_dispatch
        fetch, first = s._decode_finalize, s._suffix_finalize

        def spy_chunk(params, cache, tokens, pos, n, slot, *rest):
            self.events.append((self.tick, "chunk", int(slot), int(pos)))
            return chunk(params, cache, tokens, pos, n, slot, *rest)

        def spy_decode(active=None):
            self.events.append((self.tick, "decode", tuple(active)))
            return decode(active)

        def spy_fetch(*a):
            self.events.append((self.tick, "fetch"))
            return fetch(*a)

        def spy_first(req, slot, *rest):
            self.events.append((self.tick, "first", slot))
            return first(req, slot, *rest)

        s._prefill_suffix, s._decode_dispatch = spy_chunk, spy_decode
        s._decode_finalize, s._suffix_finalize = spy_fetch, spy_first
        s._clock.start("plan")

    def run_tick(self):
        self.tick += 1
        self.s._run_tick()

    def submit(self, prompt, n, rid):
        out, done = [], []
        assert self.s.submit(Request(
            token_ids=list(prompt),
            sampling=SamplingParams(temperature=0.0, max_tokens=n),
            on_token=out.append, on_done=done.append, id=rid,
        ))
        return out, done

    def drain(self, limit=200):
        """Tick until no slot holds a request and nothing is queued."""
        for _ in range(limit):
            if self.s.stats.queued == 0 and all(
                sl.request is None for sl in self.s._slots
            ):
                return
            self.run_tick()
        raise AssertionError("the scheduler did not drain")

    def start_runner(self, tokens=64):
        """Submit the runner and tick until it decodes; returns its done
        list."""
        _, done = self.submit([5, 6], tokens, "runner")
        while not any(e[1] == "decode" for e in self.events):
            self.run_tick()
        return done

    def three_beside_a_runner(self, runner_tokens=64, n=6):
        """Start the runner, tick until it decodes, then submit the three
        prompts before one tick: returns (first tick, prompts, outs,
        dones, the runner's done list)."""
        runner_done = self.start_runner(runner_tokens)
        prompts = [_prompt(i, n_) for i, n_ in enumerate(LENGTHS)]
        subs = [self.submit(p, n, f"p{i}") for i, p in enumerate(prompts)]
        t0 = self.tick + 1
        return t0, prompts, [o for o, _ in subs], [d for _, d in subs], runner_done


def _prompt(seed, n):
    return np.random.RandomState(100 + seed).randint(3, 250, size=n).tolist()


@pytest.fixture(scope="module", params=["llama", "hybrid"])
def driven(request):
    d = Driven(request.param)
    yield d
    d.s.cancel("runner")
    d.drain()


@pytest.fixture
def fresh(driven):
    """Each test starts on empty slots and reads only its own events."""
    driven.s.cancel("runner")
    driven.drain()
    with driven.s._cancel_lock:
        driven.s._cancelled.clear()
    driven.events.clear()
    return driven


def _slot_of(d, t0):
    """Prompt index -> slot, from the first chunks of tick ``t0`` (the
    admission claims slots in the order of submission)."""
    firsts = [e for e in d.events if e[0] == t0 and e[1] == "chunk" and e[3] == 0]
    assert len(firsts) == 3
    return [e[2] for e in firsts]


def test_next_chunks_go_out_behind_the_decode_chunk_and_before_its_fetch(fresh):
    d = fresh
    t0, *_ = d.three_beside_a_runner()
    for _ in range(3):
        d.run_tick()
    slots = _slot_of(d, t0)
    for k, t in enumerate((t0, t0 + 1, t0 + 2)):
        ev = [e[1:] for e in d.events if e[0] == t]
        kinds = [e[0] for e in ev]
        assert kinds.count("decode") == 1 and kinds.count("fetch") == 1
        dec, fet = kinds.index("decode"), kinds.index("fetch")
        # Behind the decode chunk and before its tokens are fetched: the
        # next chunk of every slot that is still warming, in slot order,
        # one each; before the decode chunk only the tick's admissions.
        between = [e for e in ev[dec + 1 : fet] if e[0] == "chunk"]
        assert between == [("chunk", s, (k + 1) * CHUNK) for s in sorted(slots)]
        before = [e for e in ev[:dec] if e[0] == "chunk"]
        assert before == ([("chunk", s, 0) for s in slots] if k == 0 else [])
        assert not [e for e in ev[fet + 1 :] if e[0] == "chunk"]
    d.drain()
    chunks = [e[2:] for e in d.events if e[1] == "chunk"]
    assert len(chunks) == len(set(chunks)) == sum(map(_chunks, LENGTHS))


def test_greedy_streams_equal_one_at_a_time(fresh):
    d = fresh
    _, prompts, outs, dones, _ = d.three_beside_a_runner()
    d.s.cancel("runner")
    d.drain()
    assert all(len(o) == 6 for o in outs) and dones == [["length"]] * 3
    for i, p in enumerate(prompts):
        alone, done = d.submit(p, 6, f"alone{i}")
        d.drain()
        assert done == ["length"] and alone == outs[i]


def test_a_final_chunk_sent_ahead_joins_where_it_would_have(fresh):
    d = fresh
    t0, _, outs, _, _ = d.three_beside_a_runner()
    first_seen = {}
    for _ in range(8):
        d.run_tick()
        for i, o in enumerate(outs):
            if o and i not in first_seen:
                first_seen[i] = d.tick
    slots = _slot_of(d, t0)
    for i, slot in enumerate(slots):
        k = _chunks(LENGTHS[i])
        # One chunk a tick from t0: chunk j belongs to tick t0 + j, sent
        # in tick t0 + j - 1; the last one's first token is fetched in
        # its own tick, and the slot decodes from the next.
        final = [e for e in d.events if e[1:] == ("chunk", slot, (k - 1) * CHUNK)]
        assert [e[0] for e in final] == [t0 + k - 2]
        assert [e[0] for e in d.events if e[1:] == ("first", slot)] == [t0 + k - 1]
        assert first_seen[i] == t0 + k - 1
        joins = [e[0] for e in d.events if e[1] == "decode" and slot in e[2]]
        assert min(joins) == t0 + k
        # In its own tick the first token is handled behind the decode
        # chunk's dispatch, whose snapshot the slot is not in.
        tick = [e[1:] for e in d.events if e[0] == t0 + k - 1]
        dec = [e[0] for e in tick].index("decode")
        assert slot not in tick[dec][1] and ("first", slot) in tick[dec + 1 :]


@pytest.mark.parametrize("how", ["cancel", "tick_failure"])
def test_nothing_is_left_behind_a_chunk_that_was_ahead(fresh, how):
    d = fresh
    t0, _, outs, dones, runner_done = d.three_beside_a_runner()
    d.run_tick()  # t0: first chunks, decode, second chunks ahead
    ahead = [i for i, sl in enumerate(d.s._slots) if sl.ahead_tokens]
    assert len(ahead) == len(d.s._ahead_toks) == 3
    if how == "cancel":
        for i in range(3):
            d.s.cancel(f"p{i}")
        d.run_tick()
        assert dones == [["cancelled"]] * 3 and not runner_done
        assert not any(e[1] == "chunk" for e in d.events if e[0] == d.tick)
    else:
        def boom(*a, **k):
            raise RuntimeError("injected")
        # The fetch fails: by then this tick's chunks are ahead as well.
        spy, d.s._decode_finalize = d.s._decode_finalize, boom
        d.run_tick()
        d.s._decode_finalize = spy
        assert dones == [["error"]] * 3 and runner_done == ["error"]
    for i in ahead:
        sl = d.s._slots[i]
        assert sl.request is None and not sl.ahead_tokens
        assert sl.first_token is None and sl.warm_pos is None
    assert d.s._ahead_toks == []
    assert all(o == [] for o in outs)
    # The slots serve again, and streams are what they are alone.
    again, done = d.submit(_prompt(0, LENGTHS[0]), 4, "again")
    d.s.cancel("runner")
    d.drain()
    assert done == ["length"] and len(again) == 4
    alone, _ = d.submit(_prompt(0, LENGTHS[0]), 4, "alone")
    d.drain()
    assert alone == again


def test_a_final_chunk_ahead_is_dropped_with_its_cancelled_request(fresh):
    d = fresh
    t0, _, outs, dones, _ = d.three_beside_a_runner()
    k = _chunks(LENGTHS[0])
    while d.tick < t0 + k - 2:
        d.run_tick()
    slot = _slot_of(d, t0)[0]
    assert d.s._slots[slot].first_token is not None  # the last chunk is ahead
    d.s.cancel("p0")
    d.run_tick()
    assert dones[0] == ["cancelled"] and outs[0] == []
    assert d.s._slots[slot].request is None and d.s._slots[slot].first_token is None
    assert not [e for e in d.events if e[1:] == ("first", slot)]


@pytest.mark.parametrize("lengths, ahead_expected", [
    ((3, CHUNK, 5), 0),          # under a chunk: batched cold admission
    (LENGTHS, sum(map(_chunks, LENGTHS)) - 3),  # all but each prompt's first
])
def test_the_counter_counts_chunks_sent_ahead(fresh, lengths, ahead_expected):
    d = fresh
    before = d.s.stats.snapshot()
    d.start_runner()
    # Prompts no other test sends: their state snapshots are new.
    subs = [d.submit(_prompt(20 + i, n), 3, f"c{i}") for i, n in enumerate(lengths)]
    for _ in range(10):
        d.run_tick()
    after = d.s.stats.snapshot()
    assert all(done == ["length"] for _, done in subs)
    chunks = after["prefill_chunks"] - before["prefill_chunks"]
    sent_ahead = after["prefill_chunks_ahead"] - before["prefill_chunks_ahead"]
    assert sent_ahead == ahead_expected <= chunks
    assert chunks == sum(_chunks(n) for n in lengths if n > CHUNK)
    # Counted once each, sent ahead or not: the real tokens (the runner's
    # two among them) and a state snapshot at every whole chunk's end.
    assert after["prefill_tokens_dispatched"] - before["prefill_tokens_dispatched"] == sum(lengths) + 2
    if d.s._snapshots is not None and chunks:
        saved = after["state_snapshots_saved"] - before["state_snapshots_saved"]
        assert saved == sum(n // CHUNK for n in lengths)


def test_no_decode_chunk_nothing_ahead(fresh):
    """Nothing decodes: the host blocks on nothing, ticks follow each
    other at once, and every chunk is phase 1's."""
    d = fresh
    before = d.s.stats.snapshot()
    out, done = d.submit(_prompt(7, 30), 2, "lonely")
    for _ in range(4):
        d.run_tick()
    after = d.s.stats.snapshot()
    assert len(out) >= 1
    assert after["prefill_chunks"] - before["prefill_chunks"] == 4
    assert after["prefill_chunks_ahead"] == before["prefill_chunks_ahead"]
    d.drain()
    assert done == ["length"]
