"""A few short cold prompts are prefilled alone: with chunked prefill on,
the admissions of one tick, up to half the smallest batch bucket of
``_prefill_some`` (8 at eight slots or more, so one to four), go out as
chunks of one row each at each prompt's own bucket, as a long prompt's
chunks do; five or more are one ``_prefill_some`` batch.  The tick and
the idle path decide through one helper, ``Scheduler._admit_cold``.

The ticks are driven from the test thread (``_run_tick``), with a spy on
the facts of every dispatch (``_TickClock.enter``), at the tiny size of
both model kinds: llama, whose lone chunk is ``_prefill_suffix``, and the
hybrid model, whose chunk programs are the compiled family of
``_prefill_suffix_rows``.
"""

import numpy as np
import pytest

from generativeaiexamples_tpu.engine.sampler import SamplingParams
from generativeaiexamples_tpu.engine.scheduler import Request, Scheduler
from generativeaiexamples_tpu.engine.weights import resolve_model_preset
from generativeaiexamples_tpu.models import hybrid, llama

CHUNK = 16
STEPS = 4  # of a decode chunk
ADMISSIONS = ("_prefill_some", "_prefill_suffix", "_prefill_suffix_rows")


def _prompt(seed, n):
    return np.random.RandomState(300 + seed).randint(3, 250, size=n).tolist()


class Driven:
    """A scheduler whose ticks the test runs, and the facts of each
    prefill dispatch: (program, rows, bucket)."""

    def __init__(self, kind, **options):
        if kind == "llama":
            cfg = llama.llama_tiny(dtype="float32", max_seq_len=128, kv_dtype="int8")
        else:
            cfg = hybrid.PRESETS[resolve_model_preset("ling-tiny")]()
        self.s = s = Scheduler(
            cfg, None, seed=7,
            **{"max_batch": 8, "max_len": 128, "decode_chunk_size": STEPS,
               "prefill_chunk_tokens": CHUNK, "prefix_cache": "off", **options},
        )
        self.lone_program = "_prefill_suffix_rows" if s._chunk_programs else "_prefill_suffix"
        self.dispatches = []
        enter = s._clock.enter

        def spy_enter(phase, **facts):
            if facts.get("program") in ADMISSIONS:
                self.dispatches.append((facts["program"], facts["rows"], facts["bucket"]))
            return enter(phase, **facts)

        s._clock.enter = spy_enter
        s._clock.start("plan")

    def submit(self, prompt, n, rid=""):
        out, done = [], []
        assert self.s.submit(Request(
            token_ids=list(prompt),
            sampling=SamplingParams(temperature=0.0, max_tokens=n),
            on_token=out.append, on_done=done.append, id=rid,
        ))
        return out, done

    def drain(self, limit=200):
        for _ in range(limit):
            if self.s.stats.queued == 0 and all(sl.request is None for sl in self.s._slots):
                return
            self.s._run_tick()
        raise AssertionError("the scheduler did not drain")

    def admits(self):
        snap = self.s.stats.snapshot()
        return snap["admits_lone"], snap["admits_batched"]


@pytest.fixture(scope="module", params=["llama", "hybrid"])
def driven(request):
    return Driven(request.param)


@pytest.fixture
def empty(driven):
    driven.drain()
    driven.dispatches.clear()
    return driven


@pytest.mark.parametrize("n", [1, 2, 4, 5])
def test_up_to_four_of_a_tick_go_alone_and_five_are_one_batch(empty, n):
    d = empty
    lengths = (3, CHUNK, 9, 5, 12)[:n]
    before = d.admits()
    subs = [d.submit(_prompt(i, m), 3) for i, m in enumerate(lengths)]
    d.s._run_tick()
    lone, batched = (b - a for a, b in zip(before, d.admits()))
    if n <= 4:
        # One row each, in the order of submission, at the prompt's own
        # bucket (llama) or the family's one bucket, a whole chunk's.
        buckets = [16] * n
        assert d.dispatches == [(d.lone_program, 1, b) for b in buckets]
        assert (lone, batched) == (n, 0)
    else:
        assert d.dispatches == [("_prefill_some", 8, 16)]
        assert (lone, batched) == (0, n)
    assert all(sl.request is not None for sl in d.s._slots[-n:])
    d.drain()
    assert all(done == ["length"] and len(out) == 3 for out, done in subs)


def test_a_prompt_streams_the_same_alone_and_as_one_of_five(empty):
    """The first token and the twenty after, greedy, over int8 KV for
    llama: the standard ``TestAStreamDoesNotDependOnItsAdmission`` holds
    chunked prefill to against a whole one."""
    d = empty
    prompts = [_prompt(10 + i, m) for i, m in enumerate((11, 4, CHUNK, 7, 13))]
    subs = [d.submit(p, 21) for p in prompts]
    d.drain()
    assert d.dispatches == [("_prefill_some", 8, 16)]
    d.dispatches.clear()
    for p, (out, done) in zip(prompts, subs):
        alone, alone_done = d.submit(p, 21)
        d.drain()
        assert done == alone_done == ["length"] and len(out) == 21
        assert alone == out
    assert d.dispatches == [(d.lone_program, 1, 16)] * 5


@pytest.mark.parametrize("n", [1, 5])
def test_without_chunked_prefill_every_admission_is_a_batch(n):
    d = Driven("llama", prefill_chunk_tokens=None)
    subs = [d.submit(_prompt(20 + i, 6 + i), 2) for i in range(n)]
    d.s._run_tick()
    # No program of one row to send: the buckets start at 4, as ever.
    assert d.dispatches == [("_prefill_some", 4 if n == 1 else 8, 16)]
    assert d.admits() == (0, n)
    d.drain()
    assert all(done == ["length"] for _, done in subs)


def test_the_idle_path_takes_the_route_of_the_tick(empty):
    """``_admit_request_now`` admits the one request an idle scheduler is
    handed: the same program, the same counter and the same stream as the
    tick's admission of one."""
    d = empty
    prompt = _prompt(30, 9)
    ticked, _ = d.submit(prompt, 6)
    d.drain()
    assert d.dispatches == [(d.lone_program, 1, 16)]
    d.dispatches.clear()
    before = d.admits()
    out, done = [], []
    req = Request(
        token_ids=list(prompt), sampling=SamplingParams(temperature=0.0, max_tokens=6),
        on_token=out.append, on_done=done.append,
    )
    with d.s.stats.lock:
        d.s.stats.queued += 1  # as ``submit`` counts it
    assert d.s._admit_request_now(req)
    assert d.dispatches == [(d.lone_program, 1, 16)]
    assert tuple(b - a for a, b in zip(before, d.admits())) == (1, 0)
    assert len(out) == 1  # finalized at once: its first token is out
    d.drain()
    assert done == ["length"] and out == ticked


def test_a_small_house_sends_half_its_smallest_bucket_alone():
    """Four slots: the smallest batch bucket is 4, so two go alone and
    three are a batch of four rows."""
    d = Driven("llama", max_batch=4)
    for n, expected in ((2, [("_prefill_suffix", 1, 16)] * 2), (3, [("_prefill_some", 4, 16)])):
        d.dispatches.clear()
        subs = [d.submit(_prompt(40 + i, 5 + i), 2) for i in range(n)]
        d.s._run_tick()
        assert d.dispatches == expected
        d.drain()
        assert all(done == ["length"] for _, done in subs)
