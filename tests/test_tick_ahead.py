"""Phase 1 of tick N+1 is dispatched in tick N, behind the decode chunk
and before the host blocks on its tokens; and with a full house decode
chunk N+1 is, too.

The ticks are driven from the test thread (``_run_tick``), one at a time,
with a spy on the step programs and on the two fetches, at the tiny size
of both model kinds.  The warming cases: a runner decodes in slot 0 while
three prompts of 4, 5 and 4 chunks warm beside it, with a fifth slot left
free, so that no decode chunk goes ahead.  The decode cases (the second
half): four slots, all taken.
"""

import numpy as np
import pytest

from generativeaiexamples_tpu.engine.sampler import SamplingParams
from generativeaiexamples_tpu.engine.scheduler import Request, Scheduler
from generativeaiexamples_tpu.engine.weights import resolve_model_preset
from generativeaiexamples_tpu.models import hybrid, llama

CHUNK = 8
STEPS = 4  # of a decode chunk
LENGTHS = (30, 40, 27)  # 4, 5 and 4 chunks of 8


def _chunks(n):
    return -(-n // CHUNK)


class Driven:
    """A scheduler whose ticks the test runs, with what each tick
    dispatched and fetched: ("chunk", slot, pos), ("decode", live slots),
    ("fetch",) for the decode chunk's tokens and ("first", slot) for a
    prompt's first token, each with the tick's number in front."""

    def __init__(self, kind, max_batch=5, kv_dtype="bfloat16", **options):
        if kind == "llama":
            cfg = llama.llama_tiny(
                dtype="float32", max_seq_len=128, kv_dtype=kv_dtype
            )
        else:
            cfg = hybrid.PRESETS[resolve_model_preset("ling-tiny")]()
        self.s = s = Scheduler(
            cfg, None, max_batch=max_batch, max_len=128, decode_chunk_size=STEPS,
            seed=5, prefill_chunk_tokens=CHUNK,
            **{"prefix_cache": "off", **options},
        )
        self.events = []
        self.groups = []  # (tick, live rows, rows, window) of each group program
        self.chunks = []  # the plain decode chunks' tokens, as dispatched
        self.buckets = []  # and their kv_buckets
        self.tick = 0
        chunk, decode = s._prefill_suffix, s._decode_dispatch
        fetch, first = s._decode_finalize, s._suffix_finalize

        def spy_chunk(params, cache, tokens, pos, n, slot, *rest):
            self.events.append((self.tick, "chunk", int(slot), int(pos)))
            return chunk(params, cache, tokens, pos, n, slot, *rest)

        def spy_rows(program, rows, window):
            # The chunks of several slots in one program: a "chunk" event
            # for each row that counts, in the rows' order.
            def call(params, cache, tokens, start, lens, slots, *rest):
                live = np.asarray(lens) > 0
                for sl, pos in zip(np.asarray(slots)[live], np.asarray(start)[live]):
                    self.events.append((self.tick, "chunk", int(sl), int(pos)))
                self.groups.append((self.tick, int(live.sum()), rows, window))
                return program(params, cache, tokens, start, lens, slots, *rest)
            return call

        def spy_decode(active=None):
            self.events.append((self.tick, "decode", tuple(active)))
            out = decode(active)
            self.chunks.append(out[0])
            self.buckets.append(s._tick_kv_bucket)
            return out

        def spy_fetch(toks, *a, **k):
            # Which chunk, by the order of dispatch (plain chunks only).
            n = [i for i, c in enumerate(self.chunks) if c is toks]
            self.events.append((self.tick, "fetch", *n))
            return fetch(toks, *a, **k)

        def spy_first(req, slot, *rest):
            self.events.append((self.tick, "first", slot))
            return first(req, slot, *rest)

        # The step programs themselves, for their caches' sizes.
        self.programs = {
            "decode_chunk": s._decode_chunk, "_prefill_some": s._prefill_some,
            "_graft_rows": s._graft_rows, "_prefill_suffix": chunk,
        }
        s._prefill_suffix, s._decode_dispatch = spy_chunk, spy_decode
        s._chunk_programs = {k: spy_rows(p, *k) for k, p in s._chunk_programs.items()}
        s._decode_finalize, s._suffix_finalize = spy_fetch, spy_first
        s._clock.start("plan")

    def run_tick(self):
        self.tick += 1
        self.s._run_tick()

    def submit(self, prompt, n, rid, eos_id=None):
        out, done = [], []
        assert self.s.submit(Request(
            token_ids=list(prompt),
            sampling=SamplingParams(temperature=0.0, max_tokens=n),
            on_token=out.append, on_done=done.append, id=rid, eos_id=eos_id,
        ))
        return out, done

    def slot_of(self, rid):
        return next(
            i for i, sl in enumerate(self.s._slots)
            if sl.request is not None and sl.request.id == rid
        )

    def alone(self, prompt, n, rid="alone"):
        """The greedy stream of one request with the slots to itself."""
        out, done = self.submit(prompt, n, rid)
        self.drain()
        assert done == ["length"] and len(out) == n
        return out

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
    driven.groups.clear()
    driven.chunks.clear()
    driven.buckets.clear()
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
        assert d.s._flight is None  # a slot is free: no decode chunk ahead
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
    # (Before t0: the runner's admission, a chunk of its own.)
    chunks = [e[2:] for e in d.events if e[1] == "chunk" and e[0] >= t0]
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
    # One token future a program: the hybrid model's three chunks share one.
    assert len(ahead) == 3 and len(d.s._ahead_toks) == (1 if d.s._chunk_rows > 1 else 3)
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
    ((3, CHUNK, 5), 0),          # under a chunk, three of five slots: a cold batch
    (LENGTHS, sum(map(_chunks, LENGTHS)) - 3),  # all but each prompt's first
])
def test_the_counter_counts_chunks_sent_ahead(fresh, lengths, ahead_expected):
    d = fresh
    before = d.s.stats.snapshot()
    d.start_runner()  # admitted alone: a chunk that is first and last
    # Prompts no other test sends: their state snapshots are new.
    subs = [d.submit(_prompt(20 + i, n), 3, f"c{i}") for i, n in enumerate(lengths)]
    for _ in range(10):
        d.run_tick()
    after = d.s.stats.snapshot()
    assert all(done == ["length"] for _, done in subs)
    chunks = after["prefill_chunks"] - before["prefill_chunks"]
    sent_ahead = after["prefill_chunks_ahead"] - before["prefill_chunks_ahead"]
    assert sent_ahead == ahead_expected <= chunks
    assert chunks == 1 + sum(_chunks(n) for n in lengths if n > CHUNK)
    lone = after["admits_lone"] - before["admits_lone"]
    batched = after["admits_batched"] - before["admits_batched"]
    assert (lone, batched) == ((1, 3) if max(lengths) <= CHUNK else (1, 0))
    # Counted once each, sent ahead or not: the real tokens (the runner's
    # two among them) and a state snapshot at every whole chunk's end.
    assert after["prefill_tokens_dispatched"] - before["prefill_tokens_dispatched"] == sum(lengths) + 2
    if d.s._snapshots is not None and ahead_expected:
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


def test_one_ticks_chunks_share_a_program_where_a_chunk_is_a_weight_stream(fresh):
    """The hybrid model's experts see a few rows a chunk, so the chunks
    that one tick sends go out as one program (three rows padded to four);
    llama's projections see every token of a chunk, so each goes alone, as
    ever.  Either way a chunk is a chunk: the counters, the snapshots and
    the streams are what the other tests hold them to."""
    d = fresh
    t0, *_ = d.three_beside_a_runner(n=3)
    before, seen = d.s.stats.snapshot(), len(d.s.tick_records(4096))
    for _ in range(8):
        d.run_tick()
    after, records = d.s.stats.snapshot(), d.s.tick_records(4096)[seen:]
    chunks = after["prefill_chunks"] - before["prefill_chunks"]
    programs = after["prefill_chunk_programs"] - before["prefill_chunk_programs"]
    assert chunks == sum(map(_chunks, LENGTHS)) == 13
    # The tick's record counts both where they are dispatched.
    assert sum(r["prefill_chunks"] for r in records) == chunks
    assert sum(r["prefill_chunk_programs"] for r in records) == programs
    if d.s._chunk_rows == 1:
        assert programs == chunks and not d.groups and not d.s._chunk_programs
        assert d.s._prefill_suffix_rows._cache_size() == 0
        return
    # A prompt's first chunk is its admission's, alone; then the three
    # slots' next chunks together, tick by tick, until two prompts have
    # ended; the fifth chunk of the longest goes alone again.
    assert programs == 3 + 3 + 1
    assert [g[:3] for g in d.groups if g[1] > 1] == [(t0 + k, 3, 4) for k in range(3)]
    # Alone too a chunk is a program of the family, of one row.
    assert [g[1:3] for g in d.groups if g[1] == 1 and g[0] >= t0] == [(1, 1)] * 4
    assert [r["prefill_chunk_programs"] for r in records[:4]] == [3 + 1, 1, 1, 1]
    assert [r["prefill_chunks"] for r in records[:4]] == [3 + 3, 3, 3, 1]


def test_every_group_program_is_compiled_when_the_scheduler_is_built(fresh):
    """1, 2 and 4 rows (five slots) over the windows 64 and 128: the
    family is closed, every chunk, alone or in a group, reaches the device
    through its compiled executables, and neither the jitted function
    behind them nor ``_prefill_suffix`` is ever called for one, so a chunk
    can compile nothing inside a request."""
    d = fresh
    if d.s._chunk_rows == 1:  # llama: no family, nothing to compile
        assert not d.s._chunk_programs and not d.s._chunk_windows
        return
    assert d.s._chunk_rows == 4 and d.s._chunk_windows == (64, 128)
    assert set(d.s._chunk_programs) == {(r, w) for r in (1, 2, 4) for w in (64, 128)}
    lone = d.programs["_prefill_suffix"]._cache_size()
    d.start_runner()
    subs = [d.submit(_prompt(70 + i, n), 2, f"g{i}") for i, n in enumerate((100, 90))]
    for _ in range(16):
        d.run_tick()
    assert all(done == ["length"] for _, done in subs)
    subs = [d.submit(_prompt(80 + i, n), 2, f"h{i}") for i, n in enumerate((30, 20, 25, 28))]
    for _ in range(8):
        d.run_tick()
    assert all(done == ["length"] for _, done in subs)
    # Two long prompts side by side pass 64 rows; four short ones fill a group.
    assert {(rows, window) for *_, rows, window in d.groups} >= {
        (1, 64), (2, 64), (2, 128), (4, 64)}
    assert d.s._prefill_suffix_rows._cache_size() == 0
    assert d.programs["_prefill_suffix"]._cache_size() == lone


# -- decode chunks ahead: a full house ------------------------------------
#
# Four slots, all taken: chunk N+1 is dispatched before chunk N's tokens
# are fetched, its rows' input tokens never leave the device, and a cold
# admission's first token reaches the chunk dispatched behind it there.

SHORT = (3, 5, 7, 4, 6, 5)  # under a chunk: batched cold admission


@pytest.fixture(scope="module", params=["llama", "hybrid"])
def house(request):
    return Driven(request.param, max_batch=4)


@pytest.fixture
def full(house):
    """Empty slots, no chunk in flight, only this test's events."""
    house.drain()
    assert house.s._flight is None
    with house.s._cancel_lock:
        house.s._cancelled.clear()
    house.events.clear()
    house.chunks.clear()
    house.buckets.clear()
    return house


def _fill(d, tokens=(40, 40, 40, 40), base=40, prompts=None):
    """Submit one short prompt a slot and run the tick that admits all
    four, dispatches two chunks and fetches the first; returns (prompts,
    outs, dones)."""
    prompts = prompts or [_prompt(base + i, n) for i, n in enumerate(SHORT[:4])]
    subs = [d.submit(p, n, f"h{i}") for i, (p, n) in enumerate(zip(prompts, tokens))]
    d.run_tick()
    return prompts, [o for o, _ in subs], [x for _, x in subs]


def _counters(d):
    snap = d.s.stats.snapshot()
    return snap["decode_chunks"], snap["decode_chunks_ahead"], snap["decode_tokens_dropped"]


def test_a_full_house_streams_what_each_request_streams_alone(full):
    """Rows that end at different steps, a queue that refills the slots
    (cold batches and a chunked prompt), chunks ahead all the while."""
    d = full
    before = _counters(d)
    lengths = SHORT + (27, 4)
    tokens = (9, 14, 21, 30, 12, 17, 11, 1)
    prompts = [_prompt(60 + i, n) for i, n in enumerate(lengths)]
    subs = [d.submit(p, n, f"f{i}") for i, (p, n) in enumerate(zip(prompts, tokens))]
    d.drain()
    chunks, ahead, dropped = (b - a for a, b in zip(before, _counters(d)))
    assert ahead > chunks // 2 and dropped == 0
    for i, (out, done) in enumerate(subs):
        assert done == ["length"] and len(out) == tokens[i]
    for i, (p, n) in enumerate(zip(prompts, tokens)):
        assert d.alone(p, n) == subs[i][0], i


def test_chunk_n_plus_1_goes_out_before_chunk_n_is_fetched(full):
    d = full
    _fill(d)
    t0 = d.tick  # admits all four, dispatches chunks 0 and 1, fetches 0
    for _ in range(4):
        d.run_tick()
    for t in range(t0, d.tick + 1):
        ev = [e[1:] for e in d.events if e[0] == t and e[1] in ("decode", "fetch")]
        n = t - t0 + 1  # the chunk this tick sends ahead
        want = [("decode", (0, 1, 2, 3)), ("fetch", n - 1)]
        assert ev == ([("decode", (0, 1, 2, 3))] if t == t0 else []) + want
        # The record describes the chunk fetched: chunk 0 had not gone
        # ahead; the window is the one that chunk was dispatched with.
        rec = d.s.tick_records(d.tick - t + 1)[0]
        assert rec["decode_ahead"] == (t > t0)
        assert rec["kv_bucket"] == d.buckets[n - 1], (t, d.buckets)
    assert d.s._flight is not None
    assert len(set(d.buckets)) > 1  # the window grew meanwhile
    # Four tokens a chunk and the first: nothing waited for the host.
    assert sorted(len(sl.history) for sl in d.s._slots) == sorted(
        n + 1 + STEPS * (d.tick - t0 + 1) for n in SHORT[:4]
    )


def test_a_chunk_goes_ahead_only_with_every_slot_taken(full):
    """Three of four slots decode: each tick fetches the chunk it sent."""
    d = full
    subs = [d.submit(_prompt(70 + i, n), 14, f"t{i}") for i, n in enumerate(SHORT[:3])]
    before = _counters(d)
    for _ in range(4):
        d.run_tick()
        assert d.s._flight is None
        ev = [e[1:] for e in d.events if e[0] == d.tick and e[1] in ("decode", "fetch")]
        if ev:
            assert [e[0] for e in ev] == ["decode", "fetch"]
            assert ev[1][1] == len(d.chunks) - 1
        assert d.s.tick_records(1)[0]["decode_ahead"] == 0
    d.drain()
    chunks, ahead, dropped = (b - a for a, b in zip(before, _counters(d)))
    assert chunks >= 4 and ahead == 0 and dropped == 0
    assert all(done == ["length"] and len(out) == 14 for out, done in subs)


def test_a_row_that_ends_by_length_is_absent_from_the_next_chunk(full):
    d = full
    before = _counters(d)
    # Row 1 ends after its first token and ten more: in its third chunk.
    prompts, outs, dones = _fill(d, tokens=(40, 11, 40, 40))
    waiting, waited = d.submit(_prompt(80, 5), 6, "next")
    row = d.slot_of("h1")
    while not dones[1]:
        d.run_tick()
    with_row = [e for e in d.events if e[1] == "decode" and row in e[2]]
    assert len(with_row) == 3 == -(-(11 - 1) // STEPS)
    # The chunk dispatched before its last tokens were fetched left it out.
    last = max(e[0] for e in with_row)
    assert [e[2] for e in d.events if e[0] == last + 1 and e[1] == "decode"] == [
        tuple(i for i in range(4) if i != row)
    ]
    assert _counters(d)[2] == before[2]  # no lane computed a token for nobody
    for i in (0, 2, 3):
        d.s.cancel(f"h{i}")
    d.drain()
    assert waited == ["length"] and outs[1] == d.alone(prompts[1], 11)


def _stops_inside(d):
    """A prompt, its greedy stream alone, and a step k of it (past the
    first decode chunk) whose token occurs there first: as ``eos_id`` it
    stops the request at step k."""
    for seed in range(90, 120):
        p = _prompt(seed, 6)
        out = d.alone(p, 14, f"probe{seed}")
        for k in range(STEPS + 2, 12):
            if out[k] not in out[:k]:
                return p, out, k
    raise AssertionError("no prompt's stream has a token that is new at a late step")


@pytest.mark.parametrize("how", ["eos", "cancel"])
def test_a_row_that_stops_inside_n_has_n_plus_1_dropped(full, how):
    d = full
    prompt, alone, k = _stops_inside(d)
    d.events.clear()
    d.chunks.clear()
    d.buckets.clear()
    before = _counters(d)
    others = [_prompt(85 + i, n) for i, n in enumerate(SHORT[1:4])]
    subs = [d.submit(p, 40, f"o{i}") for i, p in enumerate(others)]
    out, done = d.submit(prompt, 40, "stopper", eos_id=alone[k] if how == "eos" else None)
    heir_prompt = _prompt(99, 6)
    heir, heir_done = d.submit(heir_prompt, 9, "heir")
    d.run_tick()
    slot = d.slot_of("stopper")
    if how == "eos":
        while not done:
            d.run_tick()
        assert done == ["stop"] and out == alone[:k]
    else:
        d.run_tick()
        d.s.cancel("stopper")
        d.run_tick()
        assert done == ["cancelled"] and out == alone[: len(out)]
    stopped = d.tick
    # The chunk in flight when it stopped had the row live: all of that
    # row's tokens are dropped, and the slot's next request sees none.
    in_flight = [e for e in d.events if e[0] == stopped and e[1] == "decode"][-1]
    assert slot in in_flight[2]
    while not heir_done:
        d.run_tick()
    assert _counters(d)[2] - before[2] == STEPS
    assert d.s._slots[slot].request is None or d.s._slots[slot].request.id != "stopper"
    for i in range(3):
        d.s.cancel(f"o{i}")
    d.drain()
    assert heir_done == ["length"] and heir == d.alone(heir_prompt, 9)
    # The four were a cold batch; the heir took the stopper's slot alone,
    # as its replay took a slot: chunks of one row, fetched as such.
    firsts = [e[2] for e in d.events if e[1] == "first"]
    assert len(firsts) == 2 and firsts[0] == slot


@pytest.mark.parametrize("late_n", [3, 1])
def test_a_cold_admission_decodes_in_the_chunk_dispatched_behind_it(full, late_n):
    """A batch's first tokens reach that chunk on the device: the second
    token follows one chunk after the first, as with a free slot.  (Three
    of four slots: ``_prefill_some``.)  One prompt goes alone, as a chunk
    of one row whose token the host fetches: it joins the chunk after."""
    d = full
    tokens = (40,) + (6,) * late_n + (40,) * (3 - late_n)
    _, outs, dones = _fill(d, tokens=tokens)
    before = d.s.stats.snapshot()
    lates = [d.submit(_prompt(81 + i, 5), 20, f"late{i}") for i in range(late_n)]
    rows = {d.slot_of(f"h{i}") for i in range(1, 1 + late_n)}
    while not dones[late_n]:
        d.run_tick()
    assert all(out == [] for out, _ in lates)
    d.run_tick()  # the freed slots are taken: prefill, graft, chunk ahead
    assert {d.slot_of(f"late{i}") for i in range(late_n)} == rows
    assert all(len(out) == 1 for out, _ in lates)
    after = d.s.stats.snapshot()
    lone, batched = (after[k] - before[k] for k in ("admits_lone", "admits_batched"))
    behind = set([e for e in d.events if e[0] == d.tick and e[1] == "decode"][-1][2])
    if late_n == 1:
        assert (lone, batched) == (1, 0) and not rows & behind
        d.run_tick()  # its row is in the chunk this tick sends ahead
        assert rows <= set([e for e in d.events if e[0] == d.tick and e[1] == "decode"][-1][2])
        assert len(lates[0][0]) == 1
    else:
        assert (lone, batched) == (0, 3) and rows <= behind
    d.run_tick()
    assert all(len(out) == 1 + STEPS for out, _ in lates)
    for i in range(4):
        d.s.cancel(f"h{i}")
    d.drain()
    for i, (out, done) in enumerate(lates):
        assert done == ["length"] and out == d.alone(_prompt(81 + i, 5), 20)


def test_a_tick_that_raises_with_a_chunk_in_flight_recovers(full):
    d = full
    prompts, outs, dones = _fill(d)
    d.run_tick()
    assert d.s._flight is not None

    def boom(*a, **k):
        raise RuntimeError("injected")

    spy, d.s._decode_finalize = d.s._decode_finalize, boom
    d.run_tick()
    d.s._decode_finalize = spy
    assert dones == [["error"]] * 4 and d.s._flight is None
    assert all(sl.request is None and not sl.unfetched for sl in d.s._slots)
    # The same requests again: the streams they have alone.
    _, again, dones = _fill(d, tokens=(9, 9, 9, 9), prompts=prompts)
    d.drain()
    assert dones == [["length"]] * 4
    for p, out in zip(prompts, again):
        assert out == d.alone(p, 9)


def test_an_ahead_tick_runs_no_program_a_plain_tick_has_not(full):
    """Three rows at a time reach every attention window and both batch
    buckets; a full house of the same requests then adds no entry to any
    step program's cache: chunks go ahead inside a measured window whose
    warm-up never filled the house."""
    d = full
    programs = d.programs
    lengths, tokens = (3, 5, 7, 27, 6), (100, 30, 12, 9, 6)
    prompts = [_prompt(130 + i, n) for i, n in enumerate(lengths)]

    def serve(width):
        for lo in range(0, len(prompts), width):
            for p, n in zip(prompts[lo : lo + width], tokens[lo : lo + width]):
                d.submit(p, n, "w")
            d.run_tick()
        d.drain()

    serve(3)
    serve(3)  # the second pass finds ``_carried`` a program's result
    sizes = {k: f._cache_size() for k, f in programs.items()}
    before = _counters(d)
    serve(5)
    assert _counters(d)[1] > before[1]
    assert {k: f._cache_size() for k, f in programs.items()} == sizes
