"""The layer-kind model on the normal serving path at the tiny size:
``Scheduler`` + ``create_engine_app`` built as ``engine.server`` builds
them, a streamed ``/v1/completions`` request, a cold batch, a chunked
prompt and a prefix hit restored from a state snapshot, each held to the
plain reference's logits.

The path returns tokens, so a greedy token is held to the reference's
logits: the reference logit of the served token must lie within ``GAP`` of
the reference maximum.  Both sides are float32 at the highest precision
(conftest.py) and agree to 2e-4 in every logit (test_hybrid_model.py), so
a served token that is not the reference's argmax is a near-tie; 1e-3 is
five times that agreement, and a wrong state or a missed restore moves
logits by 1e-2 and more.
"""

import asyncio
import json
import threading

import numpy as np
import pytest
from aiohttp.test_utils import TestClient, TestServer

from generativeaiexamples_tpu.engine.prefix_cache import StateSnapshots
from generativeaiexamples_tpu.engine.sampler import SamplingParams
from generativeaiexamples_tpu.engine.scheduler import Request, Scheduler
from generativeaiexamples_tpu.engine.weights import resolve_model_preset
from generativeaiexamples_tpu.models import hybrid
from generativeaiexamples_tpu.models import hybrid_reference as ref

GAP = 1e-3
CHUNK = 32


@pytest.fixture(scope="module")
def scheduler():
    # What engine.server.main() builds for --model ling-tiny.
    cfg = hybrid.PRESETS[resolve_model_preset("ling-tiny")]()
    s = Scheduler(
        cfg, None, max_batch=4, max_len=256, decode_chunk_size=4, seed=3,
        prefill_chunk_tokens=CHUNK, prefix_cache="shared",
    )
    s.start()
    yield s
    s.stop()


def _generate(scheduler, prompts, n=6):
    """Submit while the tick loop is stopped, so that the prompts are
    admitted together; returns each one's tokens."""
    scheduler.stop()
    outs = [[] for _ in prompts]
    done = [threading.Event() for _ in prompts]
    for i, p in enumerate(prompts):
        assert scheduler.submit(Request(
            token_ids=list(p),
            sampling=SamplingParams(temperature=0.0, top_p=1.0, max_tokens=n),
            on_token=outs[i].append, on_done=lambda _r, i=i: done[i].set(),
            eos_id=None, id=f"t{i}-{len(p)}",
        ))
    scheduler.start()
    assert all(ev.wait(300) for ev in done)
    return outs


def _worst_gap(scheduler, prompt, out):
    """Largest (reference max - reference logit of the served token) over
    the positions: prefill's first token, then decoding through the state."""
    seq, worst = list(prompt), 0.0
    for tok in out:
        # Padded to one length: one compiled reference for every position.
        lg = np.asarray(ref.last_logits(scheduler.params, scheduler.cfg, seq, pad_to=128))
        worst = max(worst, float(lg.max() - lg[tok]))
        seq.append(tok)
    return worst


def _prompt(seed, n):
    return np.random.RandomState(seed).randint(0, 512, size=n).tolist()


def test_a_cold_batch_of_unequal_prompts(scheduler):
    prompts = [_prompt(1, 20), _prompt(2, 31), _prompt(3, 9)]  # all <= one chunk
    before = scheduler.stats.snapshot()
    outs = _generate(scheduler, prompts)
    after = scheduler.stats.snapshot()
    assert after["prefill_rows"] - before["prefill_rows"] == 3
    assert after["prefill_chunks"] == before["prefill_chunks"]  # one batched program
    for p, o in zip(prompts, outs):
        assert len(o) == 6 and _worst_gap(scheduler, p, o) <= GAP


def test_a_chunked_prompt_and_a_hit_restored_from_its_snapshot(scheduler):
    first = _prompt(4, 100)  # chunks of 32: snapshots at 32, 64, 96
    before = scheduler.stats.snapshot()
    (out,) = _generate(scheduler, [first])
    mid = scheduler.stats.snapshot()
    assert mid["prefill_chunks"] - before["prefill_chunks"] == 4
    assert mid["state_snapshots_saved"] - before["state_snapshots_saved"] == 3
    assert mid["state_snapshot_bytes"] == len(scheduler._snapshots) * scheduler.cfg.snapshot_bytes()
    assert _worst_gap(scheduler, first, out) <= GAP
    # Shares 70 tokens: rows could be grafted to 70, the state exists at 64.
    again = first[:70] + _prompt(5, 25)
    (hit,) = _generate(scheduler, [again])
    after = scheduler.stats.snapshot()
    assert after["shared_prefix_hits"] - mid["shared_prefix_hits"] == 1
    assert after["state_snapshots_restored"] - mid["state_snapshots_restored"] == 1
    assert after["prefix_tokens_matched"] - mid["prefix_tokens_matched"] == 70
    assert after["prefix_tokens_reused"] - mid["prefix_tokens_reused"] == 64
    # Against a cold prefill of the same prompt: the reference's forward.
    assert _worst_gap(scheduler, again, hit) <= GAP
    # The step programs' counters came out with the tokens.
    routed = after["moe_choices_routed"] - mid["moe_choices_routed"]
    assert routed % (scheduler.cfg.n_experts_per_tok * 6) == 0 and routed > 0
    assert 0 < after["moe_choices_local"] < after["moe_choices_routed"]
    assert after["moe_experts_touched"] > 0 and after["moe_expert_rows_max"] > 0


def test_a_slots_next_occupant_starts_from_nothing(scheduler):
    """Chunked cold prefill into a slot whose last occupant left state."""
    for seed in (6, 7, 8, 9, 10):  # more prompts than slots: every slot is reused
        p = _prompt(seed, 70)
        (o,) = _generate(scheduler, [p], n=3)
        assert _worst_gap(scheduler, p, o) <= GAP


def test_streamed_completion_through_the_http_front(scheduler):
    from generativeaiexamples_tpu.engine.server import create_engine_app
    from generativeaiexamples_tpu.engine.tokenizer import ByteTokenizer

    app = create_engine_app(scheduler, ByteTokenizer(), model_name="ling-tiny")
    loop = asyncio.new_event_loop()
    client = TestClient(TestServer(app), loop=loop)
    loop.run_until_complete(client.start_server())
    prompt = [int(t) for t in np.random.RandomState(11).randint(3, 250, size=40)]

    async def go():
        resp = await client.post("/v1/completions", json={
            "prompt": prompt, "max_tokens": 5, "temperature": 0.0, "stream": True})
        assert resp.status == 200
        body = (await resp.read()).decode()
        metrics = await (await client.get("/metrics")).text()
        return body, metrics

    try:
        body, metrics = loop.run_until_complete(go())
    finally:
        loop.run_until_complete(client.close())
        loop.close()
    events = [json.loads(l[6:]) for l in body.splitlines()
              if l.startswith("data: ") and l != "data: [DONE]"]
    assert events and events[-1]["choices"][0]["finish_reason"] == "length"
    for name in ("engine_moe_choices_routed_total", "engine_moe_experts_touched_total",
                 "engine_prefix_tokens_matched_total", "engine_state_snapshots_saved_total",
                 "engine_state_snapshot_bytes", "engine_prefill_chunk_programs_total"):
        assert f"\n{name} " in metrics, name


def test_state_snapshots_keep_a_byte_budget_and_cut_to_the_deepest_boundary():
    snaps = StateSnapshots(every=4, bytes_each=10, budget_bytes=35)  # room for 3
    toks = list(range(100, 120))
    assert snaps.deepest(toks, 19) == 0
    for depth in (4, 8, 12):
        assert snaps.put(snaps.key(toks, depth), f"s{depth}") == 0
    assert snaps.bytes == 30 and len(snaps) == 3
    assert snaps.deepest(toks, 11) == 8 and snaps.deepest(toks, 12) == 12
    assert snaps.deepest(toks[:6] + [0] * 10, 16) == 4  # diverges after 6 tokens
    assert snaps.get(snaps.key(toks, 4)) == "s4"  # now the freshest
    assert snaps.put(snaps.key(toks, 16), "s16") == 1  # pushes out the oldest: 8
    assert snaps.deepest(toks, 11) == 4 and snaps.deepest(toks, 19) == 16
    none = StateSnapshots(every=4, bytes_each=10, budget_bytes=0)
    assert none.put(none.key(toks, 4), "x") == 0 and len(none) == 0


def test_a_llama_scheduler_reports_the_new_counters_as_zero_or_equal():
    """matched == reused where the state can be cut at any token, no
    snapshot is ever taken, and no model counter appears."""
    from generativeaiexamples_tpu.models import llama

    s = Scheduler(llama.PRESETS["llama-tiny"](), None, max_batch=2, max_len=128,
                  decode_chunk_size=4, prefill_chunk_tokens=32, prefix_cache="shared")
    assert s._snapshots is None and s.model.cut_anywhere
    s.start()
    try:
        base = _prompt(12, 60)
        _generate(s, [[t % 250 for t in base]], n=2)
        _generate(s, [[t % 250 for t in base[:50]] + [7] * 20], n=2)
    finally:
        s.stop()
    snap = s.stats.snapshot()
    assert snap["prefix_tokens_matched"] == snap["prefix_tokens_reused"] == 50
    assert snap["state_snapshots_saved"] == snap["state_snapshot_bytes"] == 0
    assert not any(k.startswith("moe_") for k in snap)


# -- the ``mellum`` family: window rings beside full rows ---------------------------


@pytest.fixture(scope="module")
def mellum():
    # What engine.server.main() builds for --model mellum-tiny.
    cfg = hybrid.PRESETS[resolve_model_preset("mellum-tiny")]()
    s = Scheduler(
        cfg, None, max_batch=4, max_len=256, decode_chunk_size=4, seed=5,
        prefill_chunk_tokens=CHUNK, prefix_cache="shared",
    )
    s.start()
    yield s
    s.stop()


def _mellum_gap(scheduler, prompt, out, pad_to=192):
    """``_worst_gap`` against ``mellum_reference``: the whole (padded)
    sequence at once, one compiled reference; every layer is causal, so no
    position before the pad sees it."""
    from generativeaiexamples_tpu.models import mellum_reference

    seq = list(prompt) + list(out)
    lg = np.asarray(mellum_reference.all_logits(
        scheduler.params, scheduler.cfg, seq + [0] * (pad_to - len(seq))))
    rows = lg[len(prompt) - 1 : len(seq) - 1]
    return float((rows.max(-1) - rows[np.arange(len(out)), out]).max())


def test_mellum_cold_prompts_a_snapshot_hit_and_a_prompt_over_half_of_max_len(mellum):
    """A window of 16 under chunks of 32: every prompt wraps its rings.
    Greedy tokens equal the reference's (to a near-tie) for a cold batch,
    a chunked prompt, a prefix hit cut back to a snapshot, and a prompt of
    150 tokens in slots of 256."""
    cfg = mellum.cfg
    assert mellum._snapshots.bytes_each == cfg.snapshot_bytes(256) == 6 * 2 * 2 * 16 * 16 * 4
    snap0 = mellum.stats.snapshot()
    assert snap0["state_bytes_window"] == 4 * cfg.snapshot_bytes(256)
    assert snap0["state_bytes_full"] == 4 * 2 * 2 * 2 * 256 * 16 * 4
    cold = [_prompt(21, 20), _prompt(22, 31)]
    for p, o in zip(cold, _generate(mellum, cold)):
        assert len(o) == 6 and _mellum_gap(mellum, p, o) <= GAP
    first = _prompt(23, 100)  # chunks of 32: snapshots at 32, 64, 96
    before = mellum.stats.snapshot()
    (out,) = _generate(mellum, [first])
    mid = mellum.stats.snapshot()
    assert mid["prefill_chunks"] - before["prefill_chunks"] == 4
    assert mid["state_snapshots_saved"] - before["state_snapshots_saved"] == 3
    assert _mellum_gap(mellum, first, out) <= GAP
    again = first[:70] + _prompt(24, 25)  # rows match to 70, the rings exist at 64
    (hit,) = _generate(mellum, [again])
    after = mellum.stats.snapshot()
    assert after["shared_prefix_hits"] - mid["shared_prefix_hits"] == 1
    assert after["state_snapshots_restored"] - mid["state_snapshots_restored"] == 1
    assert after["prefix_tokens_matched"] - mid["prefix_tokens_matched"] == 70
    assert after["prefix_tokens_reused"] - mid["prefix_tokens_reused"] == 64
    assert _mellum_gap(mellum, again, hit) <= GAP
    long = _prompt(25, 150)
    (o,) = _generate(mellum, [long])
    assert len(o) == 6 and _mellum_gap(mellum, long, o) <= GAP
    # The rows the attention layers read, by kind and phase, came out with
    # the tokens: a window layer reads its ring where a full one reads the
    # bucket, so it reads less than it would as a full layer.
    end = mellum.stats.snapshot()
    for phase in ("decode", "prefill"):
        read, dense = (end[f"attn_rows_{n}_window_{phase}"] for n in ("read", "dense"))
        assert 0 < read < dense and end[f"attn_rows_read_full_{phase}"] * 3 == dense
    assert end["moe_choices_local"] == end["moe_choices_routed"] > 0  # every expert is here


def test_mellum_next_occupants_start_from_nothing_and_metrics_are_exported(mellum):
    for seed in (26, 27, 28, 29, 30):  # more prompts than slots: every slot is reused
        p = _prompt(seed, 70)
        (o,) = _generate(mellum, [p], n=3)
        assert _mellum_gap(mellum, p, o) <= GAP
    from generativeaiexamples_tpu.engine.server import create_engine_app
    from generativeaiexamples_tpu.engine.tokenizer import ByteTokenizer

    app = create_engine_app(mellum, ByteTokenizer(), model_name="mellum-tiny")
    loop = asyncio.new_event_loop()
    client = TestClient(TestServer(app), loop=loop)
    loop.run_until_complete(client.start_server())

    async def go():
        return await (await client.get("/metrics")).text()

    try:
        metrics = loop.run_until_complete(go())
    finally:
        loop.run_until_complete(client.close())
        loop.close()
    for name in ("engine_attn_rows_read_window_decode_total", "engine_attn_rows_read_full_decode_total",
                 "engine_attn_rows_dense_window_decode_total", "engine_attn_rows_read_window_prefill_total",
                 "engine_attn_rows_read_full_prefill_total", "engine_attn_rows_dense_window_prefill_total",
                 "engine_state_bytes_full", "engine_state_bytes_window", "engine_state_snapshot_bytes",
                 "engine_moe_experts_touched_total"):
        assert f"\n{name} " in metrics, name


# -- the ``zaya`` family: rows and tails in every layer ---------------------------------


@pytest.fixture(scope="module")
def zaya():
    # What engine.server.main() builds for --model zaya-tiny.
    cfg = hybrid.PRESETS[resolve_model_preset("zaya-tiny")]()
    s = Scheduler(
        cfg, None, max_batch=4, max_len=256, decode_chunk_size=4, seed=9,
        prefill_chunk_tokens=CHUNK, prefix_cache="shared",
    )
    s.start()
    yield s
    s.stop()


def _zaya_gap(scheduler, prompt, out, pad_to=192):
    """``_mellum_gap`` against ``zaya_reference``."""
    from generativeaiexamples_tpu.models import zaya_reference

    seq = list(prompt) + list(out)
    lg = np.asarray(zaya_reference.all_logits(
        scheduler.params, scheduler.cfg, seq + [0] * (pad_to - len(seq))))
    rows = lg[len(prompt) - 1 : len(seq) - 1]
    return float((rows.max(-1) - rows[np.arange(len(out)), out]).max())


def test_zaya_cold_prompts_a_hit_restored_from_a_snapshot_of_tails_and_reused_slots(zaya):
    """A layer with both sorts of state on the serving path: greedy tokens
    equal the reference's (to a near-tie) for a cold batch, a chunked
    prompt, a prefix hit whose rows are grafted and whose tails come from
    a snapshot of tails alone, and slots whose last occupant left tails."""
    cfg = zaya.cfg
    tails = 3 * (2 * 96 + 16) * 4  # three layers: u, a (96 channels each) and the late values (16)
    assert not zaya.model.cut_anywhere
    assert zaya._snapshots.bytes_each == cfg.snapshot_bytes(256) == tails
    snap0 = zaya.stats.snapshot()
    assert snap0["state_bytes_full"] == 4 * 3 * 2 * 256 * 32 * 4 and snap0["state_bytes_window"] == 0
    cold = [_prompt(61, 20), _prompt(62, 31)]
    for p, o in zip(cold, _generate(zaya, cold)):
        assert len(o) == 6 and _zaya_gap(zaya, p, o) <= GAP
    first = _prompt(63, 100)  # chunks of 32: snapshots at 32, 64, 96
    before = zaya.stats.snapshot()
    (out,) = _generate(zaya, [first])
    mid = zaya.stats.snapshot()
    assert mid["prefill_chunks"] - before["prefill_chunks"] == 4
    assert mid["state_snapshots_saved"] - before["state_snapshots_saved"] == 3
    assert mid["state_snapshot_bytes"] == len(zaya._snapshots) * tails
    assert _zaya_gap(zaya, first, out) <= GAP
    again = first[:70] + _prompt(64, 25)  # rows match to 70, the tails exist at 64
    (hit,) = _generate(zaya, [again])
    after = zaya.stats.snapshot()
    assert after["shared_prefix_hits"] - mid["shared_prefix_hits"] == 1
    assert after["state_snapshots_restored"] - mid["state_snapshots_restored"] == 1
    assert after["prefix_tokens_matched"] - mid["prefix_tokens_matched"] == 70
    assert after["prefix_tokens_reused"] - mid["prefix_tokens_reused"] == 64
    assert _zaya_gap(zaya, again, hit) <= GAP
    for seed in (65, 66, 67, 68, 69):  # more prompts than slots: every slot is reused
        p = _prompt(seed, 70)
        (o,) = _generate(zaya, [p], n=3)
        assert _zaya_gap(zaya, p, o) <= GAP
    end = zaya.stats.snapshot()
    # The mixer's rows are counted as a full layer's; one choice a token,
    # every expert here; the decode-only pair says what a step streamed.
    for phase in ("decode", "prefill"):
        assert 0 < end[f"attn_rows_read_full_{phase}"] <= end[f"attn_rows_dense_full_{phase}"]
        assert end[f"attn_rows_read_window_{phase}"] == 0
    assert end["moe_choices_local"] == end["moe_choices_routed"] > 0
    steps = end["moe_expert_layer_steps_decode"]
    assert 0 < steps < end["moe_expert_layer_steps"] and steps % 3 == 0
    assert steps <= end["moe_experts_touched_decode"] <= 4 * steps  # 1 to 4 live rows a step


def test_zaya_metrics_are_exported(zaya):
    from generativeaiexamples_tpu.engine.server import create_engine_app
    from generativeaiexamples_tpu.engine.tokenizer import ByteTokenizer

    (o,) = _generate(zaya, [_prompt(70, 40)], n=3)
    app = create_engine_app(zaya, ByteTokenizer(), model_name="zaya-tiny")
    loop = asyncio.new_event_loop()
    client = TestClient(TestServer(app), loop=loop)
    loop.run_until_complete(client.start_server())

    async def go():
        return await (await client.get("/metrics")).text(), await (await client.get("/health")).json()

    try:
        metrics, health = loop.run_until_complete(go())
    finally:
        loop.run_until_complete(client.close())
        loop.close()
    for name in ("engine_moe_experts_touched_decode_total", "engine_moe_expert_layer_steps_decode_total",
                 "engine_moe_expert_layer_steps_total", "engine_attn_rows_read_full_decode_total",
                 "engine_attn_rows_dense_full_prefill_total", "engine_state_bytes_full",
                 "engine_state_snapshot_bytes", "engine_state_snapshots_saved_total"):
        assert f"\n{name} " in metrics, name
    paths = health["runtime"]["kernel_paths"]
    assert any(site.startswith("attn_cca b=4 s=1 ") for site in paths), sorted(paths)
    assert all(taken == "xla" for site, taken in paths.items() if site.startswith("attn_cca"))


# -- the ``nemotron_h`` family: state-space layers between decode chunks ----------------


@pytest.fixture(scope="module")
def nemotron():
    # What engine.server.main() builds for --model nemotron_h-tiny.
    cfg = hybrid.PRESETS[resolve_model_preset("nemotron_h-tiny")]()
    s = Scheduler(
        cfg, None, max_batch=4, max_len=256, decode_chunk_size=4, seed=9,
        prefill_chunk_tokens=CHUNK, prefix_cache="shared",
    )
    s.start()
    yield s
    s.stop()


def _nemotron_gap(scheduler, prompt, out, pad_to=192):
    """``_mellum_gap`` against ``nemotron_h_reference``."""
    from generativeaiexamples_tpu.models import nemotron_h_reference

    seq = list(prompt) + list(out)
    lg = np.asarray(nemotron_h_reference.all_logits(
        scheduler.params, scheduler.cfg, seq + [0] * (pad_to - len(seq))))
    rows = lg[len(prompt) - 1 : len(seq) - 1]
    return float((rows.max(-1) - rows[np.arange(len(out)), out]).max())


def test_nemotron_cold_prompts_a_hit_restored_from_a_snapshot_of_state_and_reused_slots(nemotron):
    """State-space layers on the serving path with no change to the batcher:
    greedy tokens equal the reference's (to a near-tie) for a cold batch, a
    prompt in chunks of 32 (four scan blocks of 8 each, ``S`` and the tail
    carried from program to program between decode chunks), a prefix hit
    whose K/V rows are grafted and whose ``S`` and tails come from a
    by-leaf snapshot, and slots whose last occupant left state."""
    cfg = nemotron.cfg
    assert cfg.layer_kinds == (("mamba", "experts"), ("mamba", "none"), ("full", "experts"), ("mamba", "none"))
    one = 3 * (8 * 16 * 16 * 4 + 3 * 192 * 4)  # three mamba layers: S in float32 and a tail of 3 x 192
    assert not nemotron.model.cut_anywhere and not nemotron.model.draft
    assert nemotron._snapshots.bytes_each == cfg.snapshot_bytes(256) == one
    snap0 = nemotron.stats.snapshot()
    assert snap0["state_bytes_full"] == 4 * 2 * 256 * 32 * 4 and snap0["state_bytes_window"] == 0
    cold = [_prompt(61, 20), _prompt(62, 31)]
    for p, o in zip(cold, _generate(nemotron, cold)):
        assert len(o) == 6 and _nemotron_gap(nemotron, p, o) <= GAP
    first = _prompt(63, 100)  # chunks of 32: snapshots at 32, 64, 96
    before = nemotron.stats.snapshot()
    (out,) = _generate(nemotron, [first])
    mid = nemotron.stats.snapshot()
    assert mid["prefill_chunks"] - before["prefill_chunks"] == 4
    assert mid["state_snapshots_saved"] - before["state_snapshots_saved"] == 3
    assert mid["state_snapshot_bytes"] == len(nemotron._snapshots) * one
    assert _nemotron_gap(nemotron, first, out) <= GAP
    again = first[:70] + _prompt(64, 25)  # rows match to 70, the state exists at 64
    (hit,) = _generate(nemotron, [again])
    after = nemotron.stats.snapshot()
    assert after["shared_prefix_hits"] - mid["shared_prefix_hits"] == 1
    assert after["state_snapshots_restored"] - mid["state_snapshots_restored"] == 1
    assert after["prefix_tokens_matched"] - mid["prefix_tokens_matched"] == 70
    assert after["prefix_tokens_reused"] - mid["prefix_tokens_reused"] == 64
    assert _nemotron_gap(nemotron, again, hit) <= GAP
    for seed in (65, 66, 67, 68, 69):  # more prompts than slots: every slot is reused
        p = _prompt(seed, 70)
        (o,) = _generate(nemotron, [p], n=3)
        assert _nemotron_gap(nemotron, p, o) <= GAP
    end = nemotron.stats.snapshot()
    # K/V rows in one layer and recurrent state in the next: both sets of
    # counters, and the scan's; half of the router's outputs are held here.
    for phase in ("decode", "prefill"):
        assert 0 < end[f"attn_rows_read_full_{phase}"] <= end[f"attn_rows_dense_full_{phase}"]
    assert end["attn_rows_read_state_decode"] == end["attn_rows_dense_state_decode"] > 0  # XLA's step
    assert end["attn_rows_read_state_prefill"] == end["attn_rows_ssm_blocks_decode"] == 0
    assert 0 < end["attn_rows_ssm_tokens_prefill"] <= 8 * end["attn_rows_ssm_blocks_prefill"]
    assert 0 < end["moe_choices_local"] < end["moe_choices_routed"]
    steps = end["moe_expert_layer_steps_decode"]
    assert 0 < steps < end["moe_expert_layer_steps"] and steps % 2 == 0  # two expert layers
    assert 0 < end["moe_experts_touched_decode"] <= end["moe_choices_local_decode"] < end["moe_choices_local"]


def test_nemotron_metrics_are_exported(nemotron):
    from generativeaiexamples_tpu.engine.server import create_engine_app
    from generativeaiexamples_tpu.engine.tokenizer import ByteTokenizer

    (o,) = _generate(nemotron, [_prompt(70, 40)], n=3)
    app = create_engine_app(nemotron, ByteTokenizer(), model_name="nemotron_h-tiny")
    loop = asyncio.new_event_loop()
    client = TestClient(TestServer(app), loop=loop)
    loop.run_until_complete(client.start_server())

    async def go():
        return await (await client.get("/metrics")).text(), await (await client.get("/health")).json()

    try:
        metrics, health = loop.run_until_complete(go())
    finally:
        loop.run_until_complete(client.close())
        loop.close()
    for name in ("engine_moe_choices_local_decode_total", "engine_moe_experts_touched_decode_total",
                 "engine_attn_rows_ssm_tokens_prefill_total", "engine_attn_rows_ssm_blocks_prefill_total",
                 "engine_attn_rows_read_state_decode_total", "engine_attn_rows_dense_state_decode_total",
                 "engine_attn_rows_read_full_decode_total", "engine_state_bytes_full",
                 "engine_state_snapshot_bytes", "engine_state_snapshots_saved_total"):
        assert f"\n{name} " in metrics, name
    paths = health["runtime"]["kernel_paths"]
    assert paths["ssm_step b=4 h=8"] == "xla" and paths["ssm_scan b=1 s=32"] == "xla"
    assert any(site.startswith("attn_full b=4 s=1 ") for site in paths), sorted(paths)


# -- the ``dots3_note`` family: an indexer's keys beside latent rows, rings of latent rows ------


@pytest.fixture(scope="module")
def dots3():
    # What engine.server.main() builds for --model dots3_note-tiny.
    cfg = hybrid.PRESETS[resolve_model_preset("dots3_note-tiny")]()
    s = Scheduler(
        cfg, None, max_batch=4, max_len=256, decode_chunk_size=4, seed=11,
        prefill_chunk_tokens=CHUNK, prefix_cache="shared",
    )
    s.start()
    yield s
    s.stop()


def _dots3_gap(scheduler, prompt, out, pad_to=192):
    """``_mellum_gap`` against ``dots3_note_reference``."""
    from generativeaiexamples_tpu.models import dots3_note_reference

    seq = list(prompt) + list(out)
    lg = np.asarray(dots3_note_reference.all_logits(
        scheduler.params, scheduler.cfg, seq + [0] * (pad_to - len(seq))))
    rows = lg[len(prompt) - 1 : len(seq) - 1]
    return float((rows.max(-1) - rows[np.arange(len(out)), out]).max())


def test_dots3_cold_prompts_a_hit_with_index_rows_grafted_and_rings_restored_and_reused_slots(dots3):
    """Selected latent attention and rings of latent rows on the serving
    path with no change to the batcher: greedy tokens equal the
    reference's (to a near-tie) for a cold batch, a prompt in chunks of 32
    (past ``index_topk`` 24 from its first chunk on, the rings of 13 turned
    over seven times), a prefix hit whose latent AND index-key rows are
    grafted and whose three rings come from a by-leaf snapshot, and slots
    whose last occupant left rows, index keys and rings."""
    cfg = dots3.cfg
    assert cfg.layers_of("mla") == [0, 1, 5] and cfg.layers_of("mla_window") == [2, 3, 4]
    one = 3 * 13 * 128 * 4  # three rings of 13 rows of 128 float32 values
    assert not dots3.model.cut_anywhere and not dots3.model.draft
    assert dots3._snapshots.bytes_each == cfg.snapshot_bytes(256) == one
    assert dots3._chunk_windows == (256,)  # rows read in place, rings whatever the window
    snap0 = dots3.stats.snapshot()
    assert snap0["state_bytes_full"] == 4 * 3 * 256 * (128 + 16) * 4
    assert snap0["state_bytes_window"] == 4 * one
    cold = [_prompt(81, 20), _prompt(82, 31)]
    for p, o in zip(cold, _generate(dots3, cold)):
        assert len(o) == 6 and _dots3_gap(dots3, p, o) <= GAP
    first = _prompt(83, 100)  # chunks of 32: snapshots at 32, 64, 96
    before = dots3.stats.snapshot()
    (out,) = _generate(dots3, [first])
    mid = dots3.stats.snapshot()
    assert mid["prefill_chunks"] - before["prefill_chunks"] == 4
    assert mid["state_snapshots_saved"] - before["state_snapshots_saved"] == 3
    assert mid["state_snapshot_bytes"] == len(dots3._snapshots) * one
    assert _dots3_gap(dots3, first, out) <= GAP
    again = first[:70] + _prompt(84, 25)  # rows match to 70, the rings exist at 64
    (hit,) = _generate(dots3, [again])
    after = dots3.stats.snapshot()
    assert after["shared_prefix_hits"] - mid["shared_prefix_hits"] == 1
    assert after["state_snapshots_restored"] - mid["state_snapshots_restored"] == 1
    assert after["prefix_tokens_matched"] - mid["prefix_tokens_matched"] == 70
    assert after["prefix_tokens_reused"] - mid["prefix_tokens_reused"] == 64
    assert _dots3_gap(dots3, again, hit) <= GAP
    for seed in (85, 86, 87, 88, 89):  # more prompts than slots: every slot is reused
        p = _prompt(seed, 70)
        (o,) = _generate(dots3, [p], n=3)
        assert _dots3_gap(dots3, p, o) <= GAP
    end = dots3.stats.snapshot()
    # Prefill: the walk scored every row of its whole blocks for every
    # query, which is no fewer pairs than the queries saw, the indexer every
    # pair of the rows that select; the block walk read less than the
    # windows.  Decode: 24 rows a slot gathered.
    assert 0 < end["attn_rows_seen_latent_prefill"] <= end["attn_rows_read_selected_prefill"]
    assert 0 < end["attn_rows_index_pairs_prefill"] and 0 < end["attn_rows_read_index_prefill"]
    assert 0 < end["attn_rows_read_latent_prefill"] < end["attn_rows_dense_latent_prefill"]
    assert end["attn_rows_read_selected_decode"] == end["attn_rows_read_latent_decode"] > 0
    assert end["attn_rows_read_selected_decode"] % (3 * 4 * 24) == 0  # three layers, four slots
    assert 0 < end["attn_rows_seen_latent_decode"] and 0 < end["attn_rows_read_index_decode"]
    for phase in ("decode", "prefill"):
        assert 0 < end[f"attn_rows_read_window_{phase}"] < end[f"attn_rows_dense_window_{phase}"]
    assert 0 < end["moe_choices_local"] < end["moe_choices_routed"]  # 2 of 16 experts


def test_dots3_metrics_and_kernel_paths_are_exported(dots3):
    from generativeaiexamples_tpu.engine.server import create_engine_app
    from generativeaiexamples_tpu.engine.tokenizer import ByteTokenizer

    (o,) = _generate(dots3, [_prompt(90, 40)], n=3)
    app = create_engine_app(dots3, ByteTokenizer(), model_name="dots3_note-tiny")
    loop = asyncio.new_event_loop()
    client = TestClient(TestServer(app), loop=loop)
    loop.run_until_complete(client.start_server())

    async def go():
        return await (await client.get("/metrics")).text(), await (await client.get("/health")).json()

    try:
        metrics, health = loop.run_until_complete(go())
    finally:
        loop.run_until_complete(client.close())
        loop.close()
    for name in ("engine_attn_rows_index_pairs_prefill_total", "engine_attn_rows_read_selected_prefill_total",
                 "engine_attn_rows_read_index_decode_total", "engine_attn_rows_seen_latent_decode_total",
                 "engine_attn_rows_read_latent_decode_total", "engine_attn_rows_dense_latent_prefill_total",
                 "engine_attn_rows_read_window_prefill_total", "engine_attn_rows_dense_window_decode_total",
                 "engine_attn_rows_kernel_latent_prefill_total",
                 "engine_state_bytes_full", "engine_state_bytes_window",
                 "engine_state_snapshot_bytes", "engine_state_snapshots_saved_total"):
        assert f"\n{name} " in metrics, name
    assert "engine_attn_rows_read_full_decode_total" not in metrics  # the GQA kinds' are theirs
    paths = health["runtime"]["kernel_paths"]
    for site in ("index_scores b=1 s=32 t=256", "attn_latent_chunk b=1 s=32 t=256 k=24",
                 "attn_latent_ring b=1 s=32 t=13", "attn_latent_ring b=4 s=1 t=13"):
        assert paths[site] == "xla", sorted(paths)
    assert any(site.startswith("attn_latent_sparse_decode b=4 t=") and site.endswith(" k=24") for site in paths)
    assert any(site.startswith("index_scores b=4 s=1 t=") for site in paths)


# -- the chunks of several slots in one program ---------------------------------------


@pytest.mark.parametrize("family", ["ling", "mellum", "zaya", "nemotron", "dots3"])
def test_prompts_that_warm_side_by_side_are_held_to_the_reference(family, request):
    """Three chunked prompts admitted together: from their second chunk on
    a tick sends their chunks as one program (three rows padded to four,
    then two), and each greedy stream is the reference's."""
    s = request.getfixturevalue({"ling": "scheduler"}.get(family, family))
    prompts = [_prompt(50 + i, n) for i, n in enumerate((100, 120, 70))]
    before = s.stats.snapshot()
    outs = _generate(s, prompts, n=4)
    after = s.stats.snapshot()
    chunks = after["prefill_chunks"] - before["prefill_chunks"]
    assert chunks == 4 + 4 + 3
    # Alone: each prompt's first chunk and the longest's fourth; together: two of three, one of two.
    assert after["prefill_chunk_programs"] - before["prefill_chunk_programs"] < chunks
    gap = {"ling": _worst_gap, "mellum": _mellum_gap, "zaya": _zaya_gap, "nemotron": _nemotron_gap,
           "dots3": _dots3_gap}[family]
    for p, o in zip(prompts, outs):
        assert len(o) == 4 and gap(s, p, o) <= GAP


# -- the chunks of several slots in one program: ``prefill_rows`` ---------------------

SLOTS, MAX_LEN, WINDOW, S = 6, 128, 64, 8
# (slot, tokens of the prompt before this chunk, tokens of the chunk that
# count): a prompt's first chunk into a slot whose last occupant left
# state, a chunk far past the window layers' ring of 16, and a prompt's
# last chunk, shorter than the bucket of 8.
ROWS = ((4, 0, 8), (1, 40, 8), (3, 19, 5))


@pytest.fixture(scope="module", params=[
    "ling-tiny", "mellum-tiny", "zaya-tiny", "exaone_moe-tiny", "nemotron_h-tiny", "dots3_note-tiny"])
def rows_case(request):
    """A serving model, its parameters, and slots whose state is what
    ``prefill_row`` left of each row's prompt so far; every slot that is
    no row's holds noise, which no call may touch.  Ling's latent rows have
    their windows taken out and put back; the others' rows (Mellum's full
    layers, ZAYA's ``cca`` layers, K-EXAONE's full layer and its prediction
    module's block, the draft on) are written and read in place."""
    import jax
    import jax.numpy as jnp

    from generativeaiexamples_tpu.engine.serving_models import HybridServing

    cfg = hybrid.PRESETS[resolve_model_preset(request.param)]()
    model = HybridServing(cfg, None, MAX_LEN)
    params = model.prepare_params(None, quantize=False, matmul_kernel=None, seed=7)
    keys = iter(jax.random.split(jax.random.PRNGKey(11), 64))
    state = jax.tree.map(
        lambda x: jax.random.normal(next(keys), x.shape, jnp.float32).astype(x.dtype),
        model.init_state(SLOTS, MAX_LEN),
    )
    prompts = {slot: _prompt(40 + slot, before + n) for slot, before, n in ROWS}
    one = jax.jit(model.prefill_row, static_argnums=(6,))
    for slot, before, _ in ROWS:
        if before:
            toks = jnp.zeros((1, 64), jnp.int32).at[0, :before].set(jnp.asarray(prompts[slot][:before]))
            state, _, _ = one(
                params, state, toks, jnp.int32(0), jnp.int32(before), jnp.int32(slot), 64
            )
    return model, params, state, prompts, one


def _chunk_of(prompts, slot, before, n):
    return np.pad(prompts[slot][before : before + n], (0, S - n))


@pytest.mark.parametrize("n_rows", [2, 3])
def test_rows_of_a_group_get_what_each_gets_alone(rows_case, n_rows):
    """2 rows, and 3 padded to 4, at different starts and lengths over one
    shared window: the hidden states and the state ``prefill_row`` gives
    each row alone; every other slot is as it was, the slot that the pad
    row names too."""
    import jax
    import jax.numpy as jnp

    model, params, state, prompts, one = rows_case
    rows = ROWS[:n_rows]
    alone, want = state, {}
    for slot, before, n in rows:
        alone, hidden, _ = one(
            params, alone, jnp.asarray(_chunk_of(prompts, slot, before, n))[None],
            jnp.int32(before), jnp.int32(n), jnp.int32(slot), WINDOW,
        )
        want[slot] = np.asarray(hidden[0, :n])
    pad = [(2, 7, 0)] * (-n_rows % 2)  # names slot 2, counts no token
    tokens = np.stack([_chunk_of(prompts, *r) if r[2] else np.zeros(S, int) for r in (*rows, *pad)])
    slots, start, lens = (jnp.asarray(c, jnp.int32) for c in zip(*rows, *pad))
    together, hidden, counters = jax.jit(model.prefill_rows, static_argnums=(6,))(
        params, state, jnp.asarray(tokens, jnp.int32), start, lens, slots, WINDOW
    )
    for r, (slot, _, n) in enumerate(rows):
        np.testing.assert_allclose(np.asarray(hidden[r, :n]), want[slot], rtol=2e-5, atol=2e-5)
    touched = {slot for slot, _, _ in rows}
    for got, each, was in zip(*(jax.tree.leaves(t) for t in (together, alone, state))):
        np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(each, np.float32), rtol=2e-5, atol=2e-5)
        others = [i for i in range(SLOTS) if i not in touched]
        np.testing.assert_array_equal(np.asarray(got)[others], np.asarray(was)[others])
    # A pad row routes to no expert: the choices counted are the rows'
    # (and the module's expert layer's, one position behind them).
    experts = sum(mlp == "experts" for _, mlp in model.cfg.layer_kinds)
    tokens_routed = sum(n for _, _, n in rows)
    behind = sum(n - (before == 0) for _, before, n in rows) if model.draft else 0
    assert int(counters[0]) == (tokens_routed * experts + behind) * model.cfg.n_experts_per_tok
    # Latent rows attended whole (Ling's) are the ones taken out and put back.
    assert model.rows_in_place == (bool(model.cfg.latent_block) or not model.cfg.layers_of("mla"))
    named = dict(zip(model.counter_names, np.asarray(counters).tolist()))
    if "attn_rows_dense_full_prefill" in named:
        # XLA's twin (float32 state): every row's window read whole, the pad
        # row's counted too (the chunk kernel reads less:
        # tests/test_gqa_chunk_kernel.py).
        layers = len(model.cfg.layers_of("full")) + len(model.cfg.layers_of("cca")) + bool(model.draft)
        assert named["attn_rows_dense_full_prefill"] == layers * len(tokens) * WINDOW
        assert named["attn_rows_read_full_prefill"] == named["attn_rows_dense_full_prefill"]
        assert named["attn_rows_read_full_decode"] == named["attn_rows_dense_full_decode"] == 0


@pytest.mark.parametrize("config, chunks", [
    ("mellum2-12b-a2.5b-l12", 4), ("ling-3.0-flash-vl-l7e128", 8),
    ("mistral-7b", 1), ("mixtral-8x7b-l4", 2), ("mistral-small-4-119b-l6e32", 8),
    ("zaya1-8b-l20", 8), ("nemotron-3-super-120b-a12b-l11e128", 8),
])
def test_how_many_chunks_share_a_program_follows_from_the_rows_an_expert_sees(config, chunks):
    """256 tokens x 8 choices over 64 experts are 32 rows an expert: 4
    chunks fill ``gmm``'s row tile of 128; over 512 experts they are 4
    rows: the cap of 8; Mixtral's 2 choices over 8 experts are 64 rows:
    2 chunks; ZAYA's one choice over 16 experts is 16 rows: 8 chunks; a
    dense projection sees every token: a chunk goes alone."""
    import importlib
    from pathlib import Path

    from generativeaiexamples_tpu.engine.serving_models import serving_model

    bench = Path(__file__).resolve().parents[1] / "benchmarks"
    model = json.loads((bench / "configs" / f"{config}.json").read_text())
    spec = importlib.util.spec_from_file_location(
        "arch", bench / "arch" / f"{model.get('arch', 'llama')}.py")
    import sys
    sys.path.append(str(bench))
    try:
        arch = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(arch)
        cfg = arch.llama_config(model, model["engine"])
    finally:
        sys.path.remove(str(bench))
    engine = model["engine"]
    serving = serving_model(cfg, None, int(engine["max_len"]))
    assert serving.chunks_per_program(int(engine["prefill_chunk_tokens"])) == chunks


# -- the ``exaone_moe`` family: the model's own prediction module drafts every step ------


@pytest.fixture(scope="module")
def exaone():
    # What engine.server.main() builds for --model exaone_moe-tiny: the draft on.
    cfg = hybrid.PRESETS[resolve_model_preset("exaone_moe-tiny")]()
    assert cfg.draft == "mtp"
    s = Scheduler(
        cfg, None, max_batch=4, max_len=256, decode_chunk_size=4, seed=5,
        prefill_chunk_tokens=CHUNK, prefix_cache="shared",
    )
    s.start()
    yield s
    s.stop()


def _exaone_gap(scheduler, prompt, out, pad_to=192):
    """``_mellum_gap`` against ``exaone_moe_reference``'s stack."""
    from generativeaiexamples_tpu.models import exaone_moe_reference

    seq = list(prompt) + list(out)
    x = exaone_moe_reference.hidden_states(
        scheduler.params, scheduler.cfg, seq + [0] * (pad_to - len(seq)))
    lg = np.asarray(exaone_moe_reference.head(scheduler.params, scheduler.cfg, x))
    rows = lg[len(prompt) - 1 : len(seq) - 1]
    return float((rows.max(-1) - rows[np.arange(len(out)), out]).max())


def test_exaone_with_the_draft_on_cold_prompts_prefix_hits_and_a_full_house(exaone):
    """A window of 8 under chunks of 32 (four windows a chunk), every
    decode step a verify step: greedy tokens equal the reference's (to a
    near-tie) for a cold batch, a chunked prompt, a prefix hit cut back
    to a snapshot (rings and the module's ``h_last``), and a full house of
    four rows decoding side by side; the ``spec_*`` stats count one draft
    a row a step."""
    cfg = exaone.cfg
    snap0 = exaone.stats.snapshot()
    assert exaone._snapshots.bytes_each == cfg.snapshot_bytes(256) == 6 * 2 * 8 * 2 * 16 * 4 + 64 * 4
    assert snap0["state_bytes_draft"] == 4 * (2 * 256 * 2 * 16 * 4 + 64 * 4)
    assert snap0["state_bytes_window"] == 4 * 6 * 2 * 8 * 2 * 16 * 4
    assert not exaone._goes_ahead()  # an empty house: an arrival's prefill would lead the queue
    cold = [_prompt(21, 20), _prompt(22, 31)]
    for p, o in zip(cold, _generate(exaone, cold)):
        assert len(o) == 6 and _exaone_gap(exaone, p, o) <= GAP
    first = _prompt(23, 100)  # chunks of 32: snapshots at 32, 64, 96
    before = exaone.stats.snapshot()
    (out,) = _generate(exaone, [first])
    mid = exaone.stats.snapshot()
    assert mid["prefill_chunks"] - before["prefill_chunks"] == 4
    assert mid["state_snapshots_saved"] - before["state_snapshots_saved"] == 3
    assert _exaone_gap(exaone, first, out) <= GAP
    again = first[:70] + _prompt(24, 25)  # rows match to 70, the state exists at 64
    (hit,) = _generate(exaone, [again], n=9)
    after = exaone.stats.snapshot()
    assert after["shared_prefix_hits"] - mid["shared_prefix_hits"] == 1
    assert after["state_snapshots_restored"] - mid["state_snapshots_restored"] == 1
    assert after["prefix_tokens_reused"] - mid["prefix_tokens_reused"] == 64
    assert len(hit) == 9 and _exaone_gap(exaone, again, hit) <= GAP
    # A full house: four rows of unequal lengths decode together.
    house = [_prompt(30 + i, n) for i, n in enumerate((40, 75, 12, 120))]
    start = exaone.stats.snapshot()
    for p, o in zip(house, _generate(exaone, house, n=10)):
        assert len(o) == 10 and _exaone_gap(exaone, p, o) <= GAP
    end = exaone.stats.snapshot()
    # One draft a row a step; a row's first token comes from its prefill.
    assert end["spec_proposed"] == end["spec_rounds"] > 0
    tokens = end["spec_tokens"] - start["spec_tokens"]
    assert tokens == 4 * 9 == (end["spec_rounds"] - start["spec_rounds"]) + (
        end["spec_accepted"] - start["spec_accepted"])
    # The device's own count: two positions a row a step, the module's
    # rows as one more full layer.
    assert end["verify_positions"] == 2 * end["draft_proposed"] > 0
    assert end["draft_proposed"] >= end["spec_proposed"]  # a step past a row's end counts there
    assert end["decode_tokens_emitted"] == end["draft_proposed"] + end["draft_accepted"]
    assert end["draft_rows_rewritten"] == 2 * (end["draft_proposed"] - end["draft_accepted"])
    # Every slot holds a request: a chunk goes out before the one in front is fetched.
    assert end["decode_chunks_ahead"] > start["decode_chunks_ahead"]
    read, dense = end["attn_rows_read_window_decode"], end["attn_rows_dense_window_decode"]
    # A chunk of 4 steps: the 2 full layers and the module's read the bucket
    # in every step, the module's once more to catch up; 6 window layers.
    assert 0 < read < dense and end["attn_rows_read_full_decode"] * 6 * 4 == dense * (3 * 4 + 1)


def test_exaone_metrics_export_the_drafts_counters(exaone):
    from generativeaiexamples_tpu.engine.server import create_engine_app
    from generativeaiexamples_tpu.engine.tokenizer import ByteTokenizer

    (o,) = _generate(exaone, [_prompt(41, 50)], n=5)
    assert len(o) == 5
    app = create_engine_app(exaone, ByteTokenizer(), model_name="exaone_moe-tiny")
    loop = asyncio.new_event_loop()
    client = TestClient(TestServer(app), loop=loop)
    loop.run_until_complete(client.start_server())

    async def go():
        return await (await client.get("/metrics")).text()

    try:
        metrics = loop.run_until_complete(go())
    finally:
        loop.run_until_complete(client.close())
        loop.close()
    for name in ("engine_draft_proposed_total", "engine_draft_accepted_total",
                 "engine_verify_positions_total", "engine_decode_tokens_emitted_total",
                 "engine_draft_rows_rewritten_total", "engine_state_bytes_draft",
                 "engine_state_bytes_window", "engine_attn_rows_read_window_decode_total"):
        assert f"\n{name} " in metrics, name
    paths = runtime_kernel_paths()
    assert any(site.startswith("mtp_attn_full") for site in paths)
    assert any(site.startswith("attn_window") for site in paths)


def runtime_kernel_paths() -> dict:
    from generativeaiexamples_tpu.utils.jax_runtime import runtime_report

    return runtime_report()["kernel_paths"]


# -- the ``mistral4`` family: latent rows are the whole state ------------------------------


@pytest.fixture(scope="module")
def mistral4():
    # What engine.server.main() builds for --model mistral4-tiny.
    cfg = hybrid.PRESETS[resolve_model_preset("mistral4-tiny")]()
    s = Scheduler(
        cfg, None, max_batch=4, max_len=256, decode_chunk_size=4, seed=7,
        prefill_chunk_tokens=CHUNK, prefix_cache="shared",
    )
    s.start()
    yield s
    s.stop()


def _mistral4_gap(scheduler, prompt, out, pad_to=192):
    """``_mellum_gap`` against ``mistral4_reference``."""
    from generativeaiexamples_tpu.models import mistral4_reference

    seq = list(prompt) + list(out)
    lg = np.asarray(mistral4_reference.all_logits(
        scheduler.params, scheduler.cfg, seq + [0] * (pad_to - len(seq))))
    rows = lg[len(prompt) - 1 : len(seq) - 1]
    return float((rows.max(-1) - rows[np.arange(len(out)), out]).max())


def test_mistral4_is_served_with_no_snapshot_and_its_hits_are_cut_at_any_row(mistral4):
    """A model whose state is rows alone takes the path the llama models'
    hits take: no ``StateSnapshots``, a hit cut at row 70 of chunks of 32
    (no multiple of the chunk), its suffix prefilled from there; one
    window for the chunk programs of every size."""
    assert mistral4.model.cut_anywhere and mistral4._snapshots is None
    assert mistral4._chunk_rows == 4 and mistral4._chunk_windows == (256,)
    snap0 = mistral4.stats.snapshot()
    assert snap0["state_bytes_full"] == 4 * 3 * 256 * 128 * 4 and snap0["state_bytes_window"] == 0
    cold = [_prompt(41, 20), _prompt(42, 31)]  # within a chunk: one cold batch
    for p, o in zip(cold, _generate(mistral4, cold)):
        assert len(o) == 6 and _mistral4_gap(mistral4, p, o) <= GAP
    first = _prompt(43, 100)  # past the tiny original context of 32 three times
    before = mistral4.stats.snapshot()
    (out,) = _generate(mistral4, [first])
    mid = mistral4.stats.snapshot()
    assert mid["prefill_chunks"] - before["prefill_chunks"] == 4
    assert _mistral4_gap(mistral4, first, out) <= GAP
    again = first[:70] + _prompt(44, 45)
    (hit,) = _generate(mistral4, [again])
    after = mistral4.stats.snapshot()
    assert after["shared_prefix_hits"] - mid["shared_prefix_hits"] == 1
    assert after["prefix_tokens_matched"] - mid["prefix_tokens_matched"] == 70
    assert after["prefix_tokens_reused"] - mid["prefix_tokens_reused"] == 70  # not 64
    assert after["prefill_chunks"] - mid["prefill_chunks"] == 2  # 45 tokens from row 70
    assert _mistral4_gap(mistral4, again, hit) <= GAP
    for key in ("state_snapshots_saved", "state_snapshots_restored", "state_snapshots_evicted",
                "state_snapshot_bytes"):
        assert after[key] == 0, key
    # The rows the latent layers read came out with the tokens: a chunk
    # and a decode step read whole blocks up to what a row holds, and a
    # slot that does not decode is not read at all.
    for phase in ("prefill", "decode"):
        assert 0 < after[f"attn_rows_read_latent_{phase}"] < after[f"attn_rows_dense_latent_{phase}"]
    assert 0 < after["moe_choices_local"] < after["moe_choices_routed"]  # 8 of 32 experts


def test_mistral4_prompts_warm_side_by_side_and_metrics_are_exported(mistral4):
    prompts = [_prompt(45, 120), _prompt(46, 90), _prompt(47, 60), _prompt(48, 150)]
    before = mistral4.stats.snapshot()
    outs = _generate(mistral4, prompts, n=4)
    after = mistral4.stats.snapshot()
    for p, o in zip(prompts, outs):
        assert _mistral4_gap(mistral4, p, o) <= GAP
    chunks = after["prefill_chunks"] - before["prefill_chunks"]
    programs = after["prefill_chunk_programs"] - before["prefill_chunk_programs"]
    assert chunks == 4 + 3 + 2 + 5 and programs < chunks  # some went out together
    for seed in (49, 50, 51):  # every slot is reused: stale rows past a length are never read
        p = _prompt(seed, 70)
        (o,) = _generate(mistral4, [p], n=3)
        assert _mistral4_gap(mistral4, p, o) <= GAP
    from generativeaiexamples_tpu.engine.server import create_engine_app
    from generativeaiexamples_tpu.engine.tokenizer import ByteTokenizer
    from generativeaiexamples_tpu.utils.jax_runtime import runtime_report

    app = create_engine_app(mistral4, ByteTokenizer(), model_name="mistral4-tiny")
    loop = asyncio.new_event_loop()
    client = TestClient(TestServer(app), loop=loop)
    loop.run_until_complete(client.start_server())

    async def go():
        return await (await client.get("/metrics")).text()

    try:
        metrics = loop.run_until_complete(go())
    finally:
        loop.run_until_complete(client.close())
        loop.close()
    for name in ("engine_attn_rows_read_latent_decode_total", "engine_attn_rows_dense_latent_decode_total",
                 "engine_attn_rows_read_latent_prefill_total", "engine_attn_rows_dense_latent_prefill_total",
                 "engine_attn_rows_kernel_latent_prefill_total", "engine_attn_rows_kernel_latent_decode_total",
                 "engine_state_bytes_full", "engine_moe_experts_touched_total"):
        assert f"\n{name} " in metrics, name
    assert "engine_attn_rows_read_full_decode_total" not in metrics  # the GQA kinds' four are theirs
    paths = runtime_report()["kernel_paths"]
    assert any(site.startswith("attn_latent_chunk b=") for site in paths)
    assert any(site.startswith("attn_latent_decode b=4") for site in paths)
    ticks = mistral4.tick_records(64)
    assert ticks and all("kv_bucket" in r for r in ticks)


def test_check_supported_keeps_refusing_what_it_refuses_for_a_rows_only_model():
    from generativeaiexamples_tpu.engine.serving_models import serving_model

    model = serving_model(hybrid.PRESETS["mistral4-tiny"](), None, 128)
    model.check_supported()
    with pytest.raises(ValueError, match="int8 weights"):
        model.prepare_params(None, quantize=True, matmul_kernel="xla", seed=0)
    with pytest.raises(ValueError, match="int8 state"):
        serving_model(hybrid.from_hf_config(hybrid.MISTRAL4_TINY, max_len=128, kv_dtype="int8"), None, 128)
    # The three families with state as of the last token keep their snapshots.
    for preset in ("ling-tiny", "mellum-tiny", "exaone_moe-tiny"):
        assert not serving_model(hybrid.PRESETS[preset](), None, 128).cut_anywhere, preset
