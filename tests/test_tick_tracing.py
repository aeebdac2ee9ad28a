"""The scheduler's own account of its time: exclusive tick phases, the
starved-device counter, request-lifecycle counters, the tick record and
the request stages on the engine's front.  CPU, tiny model, no profiler:
the annotations are no-ops while no trace runs."""

import asyncio
import logging
import queue
import time

import pytest
from aiohttp.test_utils import TestClient, TestServer

from generativeaiexamples_tpu.engine.replica import EnginePool
from generativeaiexamples_tpu.engine.sampler import SamplingParams
from generativeaiexamples_tpu.engine import scheduler as scheduler_module
from generativeaiexamples_tpu.engine.scheduler import (
    DISPATCH_STAGES,
    STARVED_PHASES,
    TICK_PHASES,
    TICK_RECORD_FIELDS,
    Request,
    Scheduler,
    Stats,
    _TickClock,
)
from generativeaiexamples_tpu.engine.tokenizer import ByteTokenizer
from generativeaiexamples_tpu.models import llama
from generativeaiexamples_tpu.utils.buckets import bucket_size

CFG = llama.llama_tiny(dtype="float32", max_seq_len=128)


def _run(scheduler, prompts, max_tokens=5, timeout=120):
    """Submit ``prompts`` together and wait for every one to finish."""
    done: "queue.Queue[str]" = queue.Queue()
    for i, prompt in enumerate(prompts):
        ok = scheduler.submit(
            Request(
                token_ids=list(prompt),
                sampling=SamplingParams(temperature=0.0, max_tokens=max_tokens),
                on_token=lambda t: None,
                on_done=done.put,
                id=f"r{i}",
            )
        )
        assert ok
    return [done.get(timeout=timeout) for _ in prompts]


def _delta(after, before, keys):
    return {k: after[k] - before[k] for k in keys}


@pytest.fixture
def running():
    """A started scheduler; ``make(**kw)`` builds it, teardown stops it."""
    made = []

    def make(**kw):
        base = dict(max_batch=4, max_len=128, decode_chunk_size=4)
        base.update(kw)
        s = Scheduler(CFG, **base)
        s.start()
        made.append(s)
        return s

    yield make
    for s in made:
        s.stop()


def test_phase_sums_partition_the_tick_threads_time(running):
    s = running(prefill_chunk_tokens=8)
    _run(s, [[1, 2, 3]])  # compile outside the measured burst
    keys = [f"tick_phase_{p}_s" for p in TICK_PHASES]
    t0, before = time.perf_counter(), s.stats.snapshot()
    _run(s, [[i + 1] * (6 + 5 * i) for i in range(6)], max_tokens=9)
    time.sleep(0.12)  # two idle polls, so that idle is in the sums too
    t1, after = time.perf_counter(), s.stats.snapshot()
    spent = _delta(after, before, keys)
    assert all(v >= 0.0 for v in spent.values())
    assert sum(spent.values()) == pytest.approx(t1 - t0, rel=0.02)
    for phase in ("idle", "plan", "dispatch", "wait_device", "emit", "telemetry"):
        assert spent[f"tick_phase_{phase}_s"] > 0.0, phase
    assert after["busy_ticks"] - before["busy_ticks"] >= 3
    assert after["busy_ticks"] < after["tick_count"]  # idle polls are not busy


def test_starved_parts_add_up_to_the_whole_exactly(running):
    s = running()
    _run(s, [[1, 2, 3], [4, 5, 6, 7]], max_tokens=12)
    snap = s.stats.snapshot()
    parts = [snap[f"device_starved_{p}_s"] for p in STARVED_PHASES]
    assert snap["device_starved_s"] == sum(parts)
    # Between the fetch of one decode chunk and the next dispatch the
    # thread emits, feeds telemetry and plans: all three are in there.
    assert all(v > 0.0 for p, v in zip(STARVED_PHASES, parts) if p != "dispatch")
    busy = sum(snap[f"tick_phase_{p}_s"] for p in STARVED_PHASES)
    assert 0.0 < snap["device_starved_s"] <= busy


class _Sentinel:
    """A program's output as the clock sees it: ready when told."""

    def __init__(self, ready=False, donated=False):
        self.ready, self.donated, self.asked = ready, donated, 0

    def is_ready(self):
        self.asked += 1
        if self.donated:
            raise RuntimeError("Array has been deleted.")
        return self.ready


@pytest.fixture
def clock(monkeypatch):
    """A clock on a made-up time: ``clock.at(t)`` sets what the next
    reads of ``time.perf_counter`` in the scheduler's module give."""

    class Time:
        now = 0.0

        @classmethod
        def perf_counter(cls):
            return cls.now

    monkeypatch.setattr(scheduler_module, "time", Time)
    c = _TickClock(Stats())
    c.at = lambda t: setattr(Time, "now", t)
    c.stats = c._stats
    c.start("plan")
    yield c
    c.stop()


def _starved(c):
    return dict(c.stats.device_starved_s)


def test_an_interval_opens_at_the_first_poll_after_the_sentinel_is_ready(clock):
    """The program finishes at 2.3, in the middle of ``emit``: the poll at
    3.0 is the first to see it, so 3.0-4.0 of emit, the plan behind it
    and the dispatch up to ``dispatched`` are starved, and nothing before."""
    program = _Sentinel()
    clock.enter("dispatch", program="decode_chunk")
    clock.at(0.5)
    clock.dispatched(program)
    clock.enter("plan")
    clock.at(1.0)
    clock.enter("emit")
    clock.at(2.0)
    clock.poll()
    assert not clock.stats.device_starved
    program.ready = True  # at 2.3, say
    clock.at(3.0)
    clock.poll()
    assert clock.stats.device_starved
    asked = program.asked
    clock.at(3.5)
    clock.poll()  # nothing is asked again until the next dispatched()
    assert program.asked == asked
    clock.at(4.0)
    clock.enter("plan")
    clock.at(4.25)
    clock.enter("dispatch", program="decode_chunk")
    clock.at(4.75)
    clock.dispatched(_Sentinel())
    assert not clock.stats.device_starved
    clock.at(5.0)
    clock.enter("plan")
    assert _starved(clock) == {"plan": 0.25, "dispatch": 0.5, "emit": 1.0, "telemetry": 0.0}
    assert clock.stats.tick_phase_s["emit"] == 3.0
    assert clock.stats.tick_phase_s["dispatch"] == 0.5 + 0.75


@pytest.mark.parametrize("sentinel", [_Sentinel(), _Sentinel(donated=True)],
                         ids=["never_ready", "donated_since"])
def test_a_sentinel_that_is_never_found_ready_opens_nothing(clock, sentinel):
    """A device that never runs out, and an output that a later program
    took (its consumer is queued behind it): no interval, no raise."""
    clock.enter("dispatch")
    clock.dispatched(sentinel)
    for t, phase in enumerate(["plan", "wait_device", "emit", "telemetry", "plan"], 1):
        clock.at(float(t))
        clock.poll()
        clock.enter(phase)
    clock.at(9.0)
    clock.sums()
    assert not clock.stats.device_starved
    assert sum(_starved(clock).values()) == 0.0
    assert sentinel.asked == (1 if sentinel.donated else 11)


def test_a_fetch_of_the_newest_output_is_one_way_to_learn_it_is_ready(clock):
    """The finalizer fetches, then enters ``emit``: that lap finds the
    sentinel ready.  The fetch of an older output opens nothing."""
    older, newest = _Sentinel(), _Sentinel()
    for program in (older, newest):
        clock.enter("dispatch")
        clock.dispatched(program)
    clock.enter("wait_device")
    clock.at(1.0)
    older.ready = True  # np.asarray(older) returns
    clock.enter("emit")
    assert not clock.stats.device_starved
    clock.at(2.0)
    clock.enter("wait_device")
    clock.at(3.0)
    newest.ready = True
    clock.enter("emit")
    assert clock.stats.device_starved
    clock.at(4.5)
    clock.enter("idle")  # no work to give: not the host's doing
    clock.at(6.0)
    clock.enter("plan")
    assert _starved(clock) == {"plan": 0.0, "dispatch": 0.0, "emit": 1.5, "telemetry": 0.0}


def test_a_dispatch_is_split_into_its_stages(clock):
    """``dispatch`` opens in ``h2d``, the site marks ``call``,
    ``dispatched`` ends it and the rest belongs to neither; a stage mark
    is a poll point."""
    program = _Sentinel()
    clock.enter("dispatch", program="a")
    clock.dispatched(program)
    clock.enter("plan")
    clock.at(1.0)
    clock.enter("dispatch", program="b")
    program.ready = True
    clock.at(1.5)
    clock.stage("call")
    assert clock.stats.device_starved  # found at the stage mark
    clock.at(3.5)
    clock.dispatched(_Sentinel())
    clock.at(3.75)  # the site's counters
    clock.enter("plan")
    st = clock.stats
    assert st.dispatch_stage_s == {"h2d": 0.5, "call": 2.0}
    assert st.tick_phase_s["dispatch"] == 2.75
    assert st.device_starved_s["dispatch"] == 2.0
    snap = st.snapshot()
    assert snap["dispatch_sites"] == 2
    assert snap["dispatch_h2d_s"] == 0.5 and snap["dispatch_call_s"] == 2.0
    assert tuple(st.dispatch_stage_s) == DISPATCH_STAGES


def test_a_chunk_sent_ahead_that_runs_out_during_emit_is_seen():
    """The blind spot of the ticket rule, as a regression test: a tick
    whose last program is a warming chunk sent behind the decode chunk
    fetches nothing of it, and the client's ``on_token`` takes far longer
    than the chunk runs.  The device is idle for most of ``emit``; the
    clock that opened an interval only at the fetch of the newest program
    booked 0.0 there."""
    s = Scheduler(
        CFG, max_batch=2, max_len=128, decode_chunk_size=4,
        prefix_cache="off", prefill_chunk_tokens=8,
    )
    done = []

    def submit(prompt, n, on_token):
        assert s.submit(Request(
            token_ids=list(prompt),
            sampling=SamplingParams(temperature=0.0, max_tokens=n),
            on_token=on_token, on_done=done.append,
        ))

    s._clock.start("plan")
    try:
        submit([5, 6], 100, lambda t: time.sleep(0.05))
        for _ in range(3):
            s._run_tick()
        submit(range(1, 41), 2, lambda t: None)  # 40 tokens: five chunks
        seen = []
        for _ in range(6):
            before = s.stats.snapshot()
            s._run_tick()
            d = _delta(
                s.stats.snapshot(), before,
                ["prefill_chunks_ahead", "decode_chunks", "device_starved_emit_s",
                 "tick_phase_emit_s", "ttft_count"],
            )
            # A tick that sends a chunk ahead, fetches its decode chunk
            # and no first token: the chunk is its last program.
            if d["prefill_chunks_ahead"] and d["decode_chunks"] and not d["ttft_count"]:
                seen.append(d)
    finally:
        s._clock.stop()
    assert len(seen) >= 2
    for d in seen:
        assert d["tick_phase_emit_s"] > 0.15  # four tokens of 50 ms
        assert d["device_starved_emit_s"] > 0.5 * d["tick_phase_emit_s"]


def test_every_host_to_device_array_of_a_dispatch_is_a_poll_point():
    """The h2d stage is a transfer after another, a third of a millisecond
    each on the chip's host: the clock is asked in front of every one and
    of the key's split, not once for the lot."""
    import numpy as np

    s = Scheduler(CFG, max_batch=2, max_len=128, decode_chunk_size=4)
    program = _Sentinel()
    s._clock.start("plan")
    try:
        s._clock.enter("dispatch")
        s._clock.dispatched(program)
        s._clock.enter("dispatch")
        asked = program.asked
        ints, floats, scalar = s._h2d(
            np.arange(3, dtype=np.int32), np.float32([0.5]), np.int32(7)
        )
        assert program.asked == asked + 3
        s._next_key()
        assert program.asked == asked + 4
    finally:
        s._clock.stop()
    assert ints.dtype == "int32" and ints.tolist() == [0, 1, 2]
    assert floats.dtype == "float32" and scalar.dtype == "int32"
    assert scalar.shape == () and not scalar.weak_type and int(scalar) == 7


@pytest.mark.parametrize("path", ["cold", "chunked", "graft"])
def test_dispatch_sites_are_the_programs_sites_of_a_scripted_run(running, path):
    """One request on an empty house: every site is a decode chunk, a
    prefill program (with what it enqueues behind it) or a graft, and the
    two stages stay inside the dispatch phase."""
    s = running(
        prefill_chunk_tokens=8 if path == "chunked" else None,
        prefix_cache="shared" if path == "graft" else "off",
    )
    prompt = [3 + (i % 11) for i in range(40)]
    grafts = 0
    if path == "graft":
        _run(s, [prompt], max_tokens=4)  # parks the prompt's rows
        prompt, grafts = prompt + [7, 8, 9, 10, 11], 1
    keys = ["dispatch_sites", "decode_chunks", "prefill_chunk_programs",
            "prefill_rows", "shared_prefix_hits", "dispatch_h2d_s",
            "dispatch_call_s", "tick_phase_dispatch_s"]
    before = s.stats.snapshot()
    _run(s, [prompt], max_tokens=9)
    time.sleep(0.1)  # the last chunk's lanes had all ended: still a site
    d = _delta(s.stats.snapshot(), before, keys)
    assert d["shared_prefix_hits"] == grafts
    prefills = d["prefill_chunk_programs"] if path == "chunked" else d["prefill_rows"]
    assert prefills == (5 if path == "chunked" else 1)
    assert d["dispatch_sites"] == prefills + grafts + d["decode_chunks"] == prefills + grafts + 2
    assert d["dispatch_h2d_s"] > 0.0 and d["dispatch_call_s"] > 0.0
    assert d["dispatch_h2d_s"] + d["dispatch_call_s"] <= d["tick_phase_dispatch_s"]


def test_lifecycle_counts_follow_admissions_and_first_tokens(running):
    s = running(max_batch=2)  # four requests on two slots: two must wait
    before = s.stats.snapshot()
    prompts = [[i + 1] * 5 for i in range(4)]
    assert _run(s, prompts, max_tokens=6) == ["length"] * 4
    after = s.stats.snapshot()
    d = _delta(
        after, before,
        ["queue_wait_count", "warm_count", "ttft_count", "requests_total",
         "queue_wait_s_sum", "warm_s_sum", "prompt_tokens_admitted"],
    )
    assert d["queue_wait_count"] == d["requests_total"] == 4
    assert d["warm_count"] == d["ttft_count"] == 4
    assert d["prompt_tokens_admitted"] == sum(len(p) for p in prompts)
    assert d["queue_wait_s_sum"] > 0.0 and d["warm_s_sum"] > 0.0
    # Submit -> claim -> first token: the two parts are the whole TTFT.
    ttft_s = (
        after["ttft_avg_ms"] * after["ttft_count"]
        - before["ttft_avg_ms"] * before["ttft_count"]
    ) / 1000.0
    assert d["queue_wait_s_sum"] + d["warm_s_sum"] == pytest.approx(ttft_s, rel=1e-6)


@pytest.mark.parametrize("path", ["cold", "chunked", "suffix"])
def test_prefill_tokens_dispatched_is_prompt_less_reuse(running, path):
    """Every prompt token is either handed to a prefill program or
    supplied by the prefix cache, on each admission path."""
    chunk = 8 if path == "chunked" else None
    s = running(
        prefill_chunk_tokens=chunk,
        prefix_cache="shared" if path == "suffix" else "off",
    )
    prompt = [3 + (i % 11) for i in range(40)]
    if path == "suffix":
        _run(s, [prompt], max_tokens=4)  # parks the prompt's KV
        prompt = prompt + [7, 8, 9, 10, 11]  # the replay extends it
    before = s.stats.snapshot()
    _run(s, [prompt], max_tokens=4)
    d = _delta(
        s.stats.snapshot(), before,
        ["prefill_tokens_dispatched", "prefill_tokens_padded",
         "prefix_tokens_reused", "prefill_chunks"],
    )
    assert d["prefill_tokens_dispatched"] == len(prompt) - d["prefix_tokens_reused"]
    if path == "cold":
        rows = bucket_size(1, minimum=4)
        width = bucket_size(len(prompt), dense=True)
        assert d["prefix_tokens_reused"] == 0 and d["prefill_chunks"] == 0
        assert d["prefill_tokens_padded"] == rows * width - len(prompt)
    elif path == "chunked":
        assert d["prefix_tokens_reused"] == 0 and d["prefill_chunks"] == 5
        assert d["prefill_tokens_padded"] == 5 * (16 - 8)  # 8 tokens in a bucket of 16
    else:
        assert d["prefix_tokens_reused"] >= 32  # MIN_PREFIX
        new = len(prompt) - d["prefix_tokens_reused"]
        assert d["prefill_tokens_padded"] == bucket_size(new, minimum=16, dense=True) - new


def test_decode_kv_counters_count_the_snapshots_rows_in_blocks():
    """One decode dispatch with a known snapshot: the decoding rows'
    lengths in whole kernel blocks beside max_batch x kv_bucket, and the
    chunk is told which rows decode while every row keeps its write
    position."""
    import jax.numpy as jnp
    import numpy as np

    from generativeaiexamples_tpu.ops.decode_attention import kv_tokens_read

    s = Scheduler(CFG, max_batch=8, max_len=1024, decode_chunk_size=4)
    req = Request(
        token_ids=[1], sampling=SamplingParams(temperature=0.0, max_tokens=4),
        on_token=lambda t: None, on_done=lambda r: None,
    )
    # Slots 0, 2, 5 decode (next write positions 300, 256, 40); slot 1 is
    # warming, 3 parked, 4 admitted after the snapshot, 6 and 7 empty.
    for i, (length, emitted) in {0: (300, 1), 2: (250, 7), 5: (40, 1), 4: (90, 1)}.items():
        s._slots[i].request, s._slots[i].length, s._slots[i].emitted = req, length, emitted
    s._slots[1].request, s._slots[1].warm_pos = req, 16
    s._slots[3].cached = True
    seen = {}

    def chunk(params, cache, tokens, lengths, key, temp, top_p, top_k, n, kv_bucket, live,
              carried, carry):
        seen.update(lengths=np.asarray(lengths), live=np.asarray(live), kv_bucket=kv_bucket,
                    carry=np.asarray(carry))
        return cache, jnp.zeros((n, 8), jnp.int32)  # the clock asks it is_ready()

    s._decode_chunk = chunk
    before = s.stats.snapshot()
    s._decode_dispatch([0, 2, 5])
    d = _delta(s.stats.snapshot(), before, ["decode_kv_tokens_read", "decode_kv_tokens_dense"])
    assert seen["live"].tolist() == [True, False, True, False, False, True, False, False]
    assert not seen["carry"].any()  # every row's newest token is the host's
    assert seen["lengths"].tolist() == [300, 1023, 256, 1023, 1023, 40, 1023, 1023]
    assert seen["kv_bucket"] == bucket_size(300 + 4 + 1, maximum=1024) == 512
    # Blocks of 512 in a cache of 1,024: each of the three rows reads one.
    assert d["decode_kv_tokens_read"] == 1536 == kv_tokens_read([300, 256, 40], 1024, 512)
    assert kv_tokens_read([513, 1023, 0], 1024, 1024) == 2048
    # A block is no wider than the window, down to 128.
    assert kv_tokens_read([100, 40, 0], 1024, 128) == 256
    assert kv_tokens_read([200, 40, 0], 1024, 256) == 512
    assert d["decode_kv_tokens_dense"] == 8 * 512


def test_clipped_prompt_is_counted_and_logged_once(running):
    s = running(max_len=64)
    limit = s._admit_limit
    prompt = [1 + (i % 50) for i in range(limit + 20)]
    records: list[logging.LogRecord] = []
    handler = logging.Handler()
    handler.emit = records.append
    sched_logger = logging.getLogger("generativeaiexamples_tpu.engine.scheduler")
    sched_logger.addHandler(handler)
    try:
        before = s.stats.snapshot()
        _run(s, [prompt, [1, 2, 3]], max_tokens=2)
        d = _delta(
            s.stats.snapshot(), before,
            ["prompts_clipped", "prompt_tokens_clipped", "prompt_tokens_admitted"],
        )
    finally:
        sched_logger.removeHandler(handler)
    assert d["prompts_clipped"] == 1
    assert d["prompt_tokens_clipped"] == len(prompt) - (limit - 1)
    assert d["prompt_tokens_admitted"] == (limit - 1) + 3
    clipped = [r.getMessage() for r in records if "clipped" in r.getMessage()]
    assert len(clipped) == 1
    assert "r0" in clipped[0] and str(len(prompt)) in clipped[0]
    assert str(limit - 1) in clipped[0]


def _pool_run(prompts, policy):
    """Two replicas serve ``prompts``: the schedulers and the pool's sums."""
    scheds = [
        Scheduler(CFG, max_batch=2, max_len=128, decode_chunk_size=4)
        for _ in range(2)
    ]
    pool = EnginePool(scheds, policy=policy, health_interval=None)
    pool.start()
    try:
        _run(pool, prompts, max_tokens=3)
        return scheds, pool.snapshot()
    finally:
        pool.stop()


def test_old_overlapping_sums_are_gone_from_scheduler_and_pool():
    scheds, agg = _pool_run([[1, 2, 3], [4, 5, 6], [7, 8, 9]], "least_loaded")
    for snap in [scheds[0].stats.snapshot(), agg, *agg["replicas"]]:
        assert "prefill_s" not in snap and "decode_s" not in snap
    # The pool sums the new counters like the others.
    for key in ("busy_ticks", "queue_wait_count", "warm_count",
                "prefill_tokens_dispatched", "tick_phase_dispatch_s",
                "device_starved_s"):
        assert agg[key] == pytest.approx(sum(r[key] for r in agg["replicas"]))
    assert agg["queue_wait_count"] == agg["warm_count"] == 3
    assert agg["prefill_tokens_dispatched"] == 9


def test_the_pool_sums_the_dispatch_sites_and_stages():
    _, agg = _pool_run([[1, 2, 3], [4, 5, 6]], "round_robin")
    keys = ["dispatch_sites", *(f"dispatch_{stage}_s" for stage in DISPATCH_STAGES)]
    for key in keys:
        parts = [r[key] for r in agg["replicas"]]
        assert all(v > 0 for v in parts), key
        assert agg[key] == pytest.approx(sum(parts))
    assert agg["dispatch_h2d_s"] + agg["dispatch_call_s"] <= agg["tick_phase_dispatch_s"]


@pytest.fixture
def engine_client():
    from generativeaiexamples_tpu.engine.server import create_engine_app

    scheduler = Scheduler(CFG, max_batch=2, max_len=128, decode_chunk_size=4)
    scheduler.start()
    app = create_engine_app(scheduler, ByteTokenizer(), model_name="llama-tiny")
    loop = asyncio.new_event_loop()
    client = TestClient(TestServer(app), loop=loop)
    loop.run_until_complete(client.start_server())
    yield client, loop
    loop.run_until_complete(client.close())
    loop.close()
    scheduler.stop()


def _complete(client, loop, request_id="", **body):
    async def go():
        resp = await client.post(
            "/v1/completions",
            json={"prompt": "hello there", "temperature": 0.0, **body},
            headers={"X-Request-Id": request_id} if request_id else {},
        )
        assert resp.status == 200
        await resp.read()

    loop.run_until_complete(go())


def _metrics(client, loop):
    """``GET /metrics``, parsed."""
    from generativeaiexamples_tpu.obs.exposition import parse_exposition

    async def go():
        resp = await client.get("/metrics")
        return await resp.text()

    return parse_exposition(loop.run_until_complete(go()))


def _get_json(client, loop, path):
    async def go():
        resp = await client.get(path)
        return resp.status, await resp.json()

    return loop.run_until_complete(go())


def test_debug_ticks_returns_the_newest_tick_records(engine_client):
    client, loop = engine_client
    _complete(client, loop, max_tokens=24)
    status, body = _get_json(client, loop, "/debug/ticks?limit=4")
    assert status == 200 and body["count"] == len(body["ticks"]) == 4
    ticks = body["ticks"]
    assert all(set(t) == set(TICK_RECORD_FIELDS) for t in ticks)
    numbers = [t["tick"] for t in ticks]
    assert numbers == sorted(set(numbers))  # rising, none twice
    starts = [t["t_start"] for t in ticks]
    assert starts == sorted(starts)
    for t in ticks:
        assert all(t[f"{p}_s"] >= 0.0 for p in TICK_PHASES)
        assert t["starved_s"] >= 0.0
        # Only the idle path's admission waits on the queue first.
        assert t["idle_s"] == 0.0 or t["admitted"]
        assert t["decode_lanes"] in (0, 1) and t["queued"] >= 0
    assert sum(t["tokens"] for t in ticks) > 0
    assert any(t["kv_bucket"] > 0 for t in ticks)
    status, everything = _get_json(client, loop, "/debug/ticks")
    assert sum(t["admitted"] for t in everything["ticks"]) == 1
    status, _ = _get_json(client, loop, "/debug/ticks?limit=x")
    assert status == 422


@pytest.mark.parametrize("stream", [False, True])
def test_engine_request_record_has_lifecycle_stages(engine_client, stream):
    client, loop = engine_client
    request_id = f"tick-tracing-{int(stream)}"
    _complete(client, loop, request_id=request_id, max_tokens=8, stream=stream)
    status, body = _get_json(client, loop, "/debug/requests")
    assert status == 200
    record = next(r for r in body["requests"] if r["request_id"] == request_id)
    assert record["route"] == "/v1/completions"
    stages = {s["stage"]: s for s in record["stages"]}
    assert list(stages) == ["queue_wait", "prefill", "decode"]
    assert all(s["duration_ms"] >= 0.0 for s in stages.values())
    # One after the other, and inside the request's total.
    assert stages["queue_wait"]["start_ms"] <= stages["prefill"]["start_ms"]
    assert stages["prefill"]["start_ms"] <= stages["decode"]["start_ms"]
    assert sum(s["duration_ms"] for s in stages.values()) <= record["total_ms"] + 1.0


def test_metrics_export_the_new_counters(engine_client):
    client, loop = engine_client
    _complete(client, loop, max_tokens=6)
    exp = _metrics(client, loop)
    assert exp.types["engine_tick_phase_seconds_total"] == "counter"
    for phase in TICK_PHASES:
        assert exp.value("engine_tick_phase_seconds_total", phase=phase) >= 0.0
    assert exp.value("engine_tick_phase_seconds_total", phase="dispatch") > 0.0
    for phase in STARVED_PHASES:
        assert exp.value("engine_device_starved_seconds_total", phase=phase) >= 0.0
    assert exp.value("engine_busy_ticks_total") >= 2
    assert exp.value("engine_queue_wait_count_total") == 1
    assert exp.value("engine_warm_count_total") == 1
    assert exp.value("engine_prompt_tokens_admitted_total") == len("hello there") + 1
    assert exp.value("engine_prefill_tokens_dispatched_total") == len("hello there") + 1
    assert exp.types["engine_prompts_clipped_total"] == "counter"
    assert exp.value("engine_prompts_clipped_total") == 0
    assert exp.types["engine_decode_kv_tokens_read_total"] == "counter"
    assert exp.value("engine_decode_kv_tokens_read_total") > 0
    assert exp.value("engine_decode_kv_tokens_dense_total") > 0


def test_metrics_export_the_stack_passes_and_the_planes(engine_client):
    """A stack that a token passes once counts one pass a decode step and
    a prefill program; its planes are its layers (float32 K/V here)."""
    client, loop = engine_client
    _complete(client, loop, max_tokens=6)
    exp = _metrics(client, loop)
    assert exp.types["engine_stack_passes_total"] == "counter"
    assert exp.value("engine_stack_passes_total", phase="prefill") == 1
    decode = exp.value("engine_stack_passes_total", phase="decode")
    assert decode >= 4 and decode % 4 == 0  # chunks of four steps
    assert exp.types["engine_cache_planes"] == exp.types["engine_kv_bytes_per_token"] == "gauge"
    assert exp.value("engine_cache_planes") == CFG.n_layers
    assert exp.value("engine_kv_bytes_per_token") == (
        2 * CFG.n_layers * CFG.n_kv_heads * CFG.head_dim * CFG.compute_dtype.itemsize
    )


def test_metrics_export_the_dispatch_sites_and_stages(engine_client):
    client, loop = engine_client
    _complete(client, loop, max_tokens=6)
    exp = _metrics(client, loop)
    assert exp.types["engine_dispatch_sites_total"] == "counter"
    assert exp.types["engine_dispatch_stage_seconds_total"] == "counter"
    # The admission and two decode chunks of four steps.
    assert exp.value("engine_dispatch_sites_total") >= 3
    stages = [
        exp.value("engine_dispatch_stage_seconds_total", stage=stage)
        for stage in DISPATCH_STAGES
    ]
    assert all(v > 0.0 for v in stages)
    assert sum(stages) <= exp.value("engine_tick_phase_seconds_total", phase="dispatch")
