"""Continuous-batching scheduler.

The in-flight-batching core TensorRT-LLM provides inside NIM (reference
consumes it as a container, ``docs/architecture.md:57-66``; SURVEY.md §2.8),
rebuilt TPU-first:

* **Slot model** — the KV cache holds ``max_batch`` fixed slots; requests
  occupy a slot from prefill to finish and release it immediately, so new
  requests join the running batch between decode chunks instead of waiting
  for the batch to drain.
* **Disaggregated batched prefill** — all waiting prompts prefill together
  into a private bucketed cache (one MXU-bound pass instead of per-request
  dispatches — the difference between admission keeping up with decode or
  becoming the throughput ceiling under load), then jitted
  ``dynamic_update_slice`` grafts copy each row into its slot.  Decode
  latency of running requests is bounded by one prefill + one chunk.
* **Chunked decode** — all slots advance together through a device-side
  ``lax.scan`` chunk (small, for streaming latency); finished or empty
  slots compute masked garbage that is never emitted — the XLA program is
  shape-stable regardless of occupancy.
* **Chunked prefill** — cold prompts longer than ``prefill_chunk_tokens``
  claim a slot and prefill in fixed-size chunks, one chunk per tick per
  warming slot, interleaved with the decode chunk on the same stream —
  one ISL-1500 admission no longer stalls every running lane behind a
  monolithic prefill, and running lanes' inter-token latency stays
  bounded by one prefill chunk + one decode chunk.
* **Cross-request shared-prefix KV cache** — finished slots park their KV
  as content-addressed segments in a host-side radix index
  (``engine.prefix_cache``); an admission whose prompt shares a long
  token prefix with any segment grafts the cached rows into its slot and
  prefills only the suffix (the paged-KV prefix reuse the reference
  delegates to TRT-LLM; vLLM/SGLang prove the technique).  Segments are
  evicted LRU under slot pressure, pinned while a graft reads them.
* **Callbacks, not queues** — the scheduler thread emits tokens via
  ``on_token``/``on_done`` callbacks; the HTTP front bridges them onto its
  event loop.
"""

from __future__ import annotations

import collections
import dataclasses
import functools
import queue
import threading
import time
from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from generativeaiexamples_tpu.core.logging import get_logger
from generativeaiexamples_tpu.engine.prefix_cache import (
    PrefixCacheIndex,
    StateSnapshots,
)
from generativeaiexamples_tpu.obs.metrics import observe_stage
from generativeaiexamples_tpu.engine.sampler import SamplingParams, sample
from generativeaiexamples_tpu.models import llama
from generativeaiexamples_tpu.ops.decode_attention import (
    flush_clip_start,
    kv_tokens_read,
)
from generativeaiexamples_tpu.resilience.faults import inject_replica
from generativeaiexamples_tpu.utils.buckets import bucket_size
from generativeaiexamples_tpu.utils.jax_runtime import EXECUTABLES

logger = get_logger(__name__)


@dataclasses.dataclass
class Request:
    token_ids: list[int]
    sampling: SamplingParams
    on_token: Callable[[int], None]
    on_done: Callable[[str], None]  # finish_reason
    eos_id: Optional[int] = None
    id: str = ""
    # Conversation key for KV prefix reuse: a finished request parks its
    # slot under this id, and the next turn whose prompt extends the
    # parked tokens prefills only the new suffix (see _admit_hit).
    session_id: str = ""
    submitted_at: float = 0.0
    # Stamped by the scheduler where a slot is claimed for the request
    # (same perf_counter clock as submitted_at / first_token_at):
    # submit -> claim is queue wait, claim -> first token is prefill.
    claimed_at: Optional[float] = None
    first_token_at: Optional[float] = None
    # Set by the HTTP front for short non-streaming requests: the pool
    # may fire a duplicate copy to a second replica if this one is slow
    # (first response wins; see EnginePool hedging).
    hedgeable: bool = False
    # Prompt tokens the prefix index matched at the last lookup; what was
    # reused of them may be less (Stats.prefix_tokens_matched).
    prefix_matched: int = 0


@dataclasses.dataclass
class _Slot:
    request: Optional[Request] = None
    length: int = 0  # valid cache entries
    emitted: int = 0
    # Parked state (prefix cache): ``cached`` marks a slot whose cache
    # rows still hold reusable KV for ``history`` — either a conversation
    # turn (``session_id`` set; reused via session match) or an anonymous
    # cross-request segment (session_id empty; reused via the shared
    # radix index).  ``parked_at`` orders LRU reclaim.
    session_id: str = ""
    cached: bool = False
    history: list[int] = dataclasses.field(default_factory=list)
    parked_at: float = 0.0
    # Chunked prefill: next prompt position to prefill.  ``None`` = not
    # warming; while set, the slot owns a request but is excluded from
    # decode (its lanes pin to the tail garbage zone like parked slots).
    warm_pos: Optional[int] = None
    # Chunked prefill, a tick ahead: tokens of the chunk that the last
    # tick dispatched behind its decode chunk on this tick's account
    # (0 = none; this tick's phase 1 then books it and dispatches
    # nothing), and, where that chunk was the prompt's last, the
    # finalizer that fetches its first token.  While ``first_token`` is
    # set the token is a device future and the slot is in no decode
    # snapshot (``_active``), although ``warm_pos`` is already None.
    ahead_tokens: int = 0
    first_token: Optional[Callable[[], None]] = None
    # Tokens of this request that are on the device and not fetched yet:
    # its first token from the dispatch of its last prefill program to
    # that token's fetch, and ``decode_chunk_size`` for every decode chunk
    # dispatched with the row live until that chunk's fetch.  The row's
    # next write position is ``length + emitted + unfetched - 1``.
    # ``on_device``: the newest of them is the slot's entry in the last row
    # of ``Scheduler._carried``, where the next decode chunk can read it
    # without the host (the last decode chunk computed it, or an
    # admission's graft landed it there since).
    unfetched: int = 0
    on_device: bool = False
    # Decode chunks that are on the device, unfetched, with this row live
    # (0, or 1 while a full house goes ahead).  Where every step verifies
    # the model's own draft such a chunk advances the row by one or two a
    # step: until its fetch the row's length is the device's to know
    # (``Scheduler._carried_len``) and ``unfetched`` counts the fewest.
    chunks_out: int = 0


@dataclasses.dataclass(frozen=True)
class _Chunk:
    """A warming slot's next prefill chunk, about to be dispatched: ``n``
    tokens of its prompt from position ``pos``."""

    slot_idx: int
    pos: int
    n: int


# What the tick thread can be doing; it is in exactly one at any instant.
#   idle         blocked on the empty queue: no work anywhere
#   plan         host-only bookkeeping: slot scans, prefix lookups, prompt
#                clipping, slot claims, lane state
#   dispatch     from the first host-to-device array of a program to the
#                return of its jitted call
#   wait_device  blocked fetching a result
#   emit         token callbacks, finishing and parking slots
#   telemetry    _note_tick and the tick record
TICK_PHASES = ("idle", "plan", "dispatch", "wait_device", "emit", "telemetry")
# The phases starved-device time is split by: there is no work to give
# in ``idle``, and in ``wait_device`` the device is busy but for the copy
# to the host behind a ready output (``_TickClock``).
STARVED_PHASES = ("plan", "dispatch", "emit", "telemetry")
# What a dispatch is split into, in order; the counters a site books under
# ``Stats.lock`` behind its jitted calls are the rest of the phase.
#   h2d   numpy staging, the host-to-device arrays, ``_next_key``
#   call  the site's jitted calls: the step program and what is enqueued
#         behind it (a boundary's snapshot, a graft)
DISPATCH_STAGES = ("h2d", "call")
# Device bytes the snapshots of recurrent state may hold (StateSnapshots),
# beside the slots: a tenth of a 16 GB chip, about 120 snapshots of six
# KDA layers at the published widths.
STATE_SNAPSHOT_BUDGET = 1_600_000_000
# One record a busy tick (Scheduler.tick_records, GET /debug/ticks).
TICK_RECORD_FIELDS = (
    ("tick", "t_start", "wall_start")
    + tuple(f"{p}_s" for p in TICK_PHASES)
    + ("starved_s", "prefill_chunks", "admitted", "decode_lanes",
       "kv_bucket", "tokens", "queued", "decode_ahead",
       "prefill_chunk_programs", "executables")
)


class _TickClock:
    """The tick thread's phase clock.

    ``enter(phase)`` reads the clock once, adds the elapsed time to the
    phase that ends and starts the next, so ``Stats.tick_phase_s``
    partitions the thread's wall time.  Each phase is also a
    ``jax.profiler.TraceAnnotation("tick/<phase>")`` on the thread's line
    of the profiler's host plane (the device plane's clock), which costs
    a check of an atomic while no trace runs.  ``dispatch`` opens in
    stage ``h2d``, the site marks ``stage("call")`` in front of its
    jitted calls and ``dispatched`` ends it (``DISPATCH_STAGES``): sums
    in ``Stats.dispatch_stage_s``, annotations ``tick/dispatch/<stage>``.

    Starved-device time, the drain rule: ``dispatched(sentinel)`` keeps
    an output of the site's LAST program, and the device is starved from
    the moment the newest sentinel is ready until the next
    ``dispatched``; each phase's time in between goes to
    ``Stats.device_starved_s`` too (``STARVED_PHASES``).  The moment is
    learnt by ``is_ready()`` at every phase change, stage mark and
    ``poll()`` (in front of each host-to-device array, once a lane while
    emitting), so an interval opens late by at most one poll interval:
    a lower bound of the device's idle time.  A fetch of that output is
    one more way to learn it (the ``enter`` behind it); its tail, the
    copy to the host behind a ready sentinel, is booked nowhere
    (0.4 ms a fetch on a v5e's host, in the one tick of five that
    fetches its newest program: under 0.1 ms a tick, so ``wait_device``
    is no starved phase).  Unseen: a gap between two programs of one site.
    """

    def __init__(self, stats: "Stats") -> None:
        self._stats = stats
        self._span: Optional[jax.profiler.TraceAnnotation] = None
        self._stage: Optional[str] = None
        self._stage_span: Optional[jax.profiler.TraceAnnotation] = None
        # The newest program's output, until a poll finds it ready.
        self._sentinel = None
        # What the running phase was entered with: an executable that JAX
        # makes inside it is recorded with them (``Scheduler._asked``).
        self.facts: dict = {}

    def _lap(self) -> None:
        """Book the time since the last lap to the running phase and
        stage (under the lock ``Stats.snapshot`` reads them with)."""
        st = self._stats
        now = time.perf_counter()
        with st.lock:
            if st.tick_phase is not None:
                dt = now - st.tick_phase_since
                st.tick_phase_s[st.tick_phase] += dt
                if st.device_starved and st.tick_phase in st.device_starved_s:
                    st.device_starved_s[st.tick_phase] += dt
                if self._stage is not None:
                    st.dispatch_stage_s[self._stage] += dt
            st.tick_phase_since = now

    def poll(self) -> None:
        """Has the device run out of work?  The time up to the poll that
        first finds the sentinel ready is booked as before, what follows
        as starved; nothing is asked again until the next ``dispatched``."""
        sentinel = self._sentinel
        if sentinel is None:
            return
        try:
            if not sentinel.is_ready():
                return
        except RuntimeError:
            # Donated since (a state restored in ``plan``, a fault's
            # recovery): the program that took it is queued behind it.
            self._sentinel = None
            return
        self._sentinel = None
        self._lap()
        self._stats.device_starved = True

    def _mark(self) -> None:
        """A phase or stage boundary: a poll point, then the lap."""
        self.poll()
        self._lap()

    def enter(self, phase: str, **facts) -> None:
        """End the current phase and start ``phase``; ``facts`` (a
        dispatch's program and shapes) ride on the trace annotation."""
        self._mark()
        self._stats.tick_phase = phase
        self.facts = facts
        self.end_span()
        self._span = jax.profiler.TraceAnnotation(_SPAN_NAMES[phase], **facts)
        self._span.__enter__()
        if phase == "dispatch":
            self._open_stage("h2d")

    def stage(self, name: str) -> None:
        """Inside ``dispatch``: the stage before ends, ``name`` starts."""
        self._mark()
        self._close_stage()
        self._open_stage(name)

    def _open_stage(self, name: str) -> None:
        self._stage = name
        self._stage_span = jax.profiler.TraceAnnotation(
            _STAGE_SPAN_NAMES[name], **self.facts
        )
        self._stage_span.__enter__()

    def _close_stage(self) -> None:
        self._stage = None
        if self._stage_span is not None:
            self._stage_span.__exit__(None, None, None)
            self._stage_span = None

    def end_span(self) -> None:
        """Close the open annotations (the phase itself runs on), so that
        they nest inside the loop's step annotation."""
        self._close_stage()
        if self._span is not None:
            self._span.__exit__(None, None, None)
            self._span = None

    def start(self, phase: str) -> None:
        """The tick thread starts: the time since the last one stopped
        belongs to no phase."""
        self._stats.tick_phase = None
        self._stats.device_starved = False
        self._sentinel = None
        self.enter(phase)

    def stop(self) -> None:
        """The tick thread ends: no phase runs on."""
        self._lap()
        self._stats.tick_phase = None
        self.end_span()

    def dispatched(self, sentinel) -> None:
        """A dispatch site's jitted calls have returned: the device has
        work until ``sentinel``, an output of the last of them that
        answers ``is_ready()``, is ready."""
        self._lap()
        self._close_stage()
        self._sentinel = sentinel
        self._stats.device_starved = False
        self._stats.dispatch_sites += 1

    def sums(self) -> tuple:
        """(phase sums..., starved) up to now, for a tick's record."""
        self._mark()
        st = self._stats
        return tuple(st.tick_phase_s.values()) + (
            sum(st.device_starved_s.values()),
        )


_SPAN_NAMES = {p: f"tick/{p}" for p in TICK_PHASES}
_STAGE_SPAN_NAMES = {s: f"tick/dispatch/{s}" for s in DISPATCH_STAGES}


# The most a cold admission batch's own state may take
# (``Scheduler._cold_pieces``).
COLD_BATCH_STATE_BYTES = 1 << 30


def make_prefill_suffix_rows(model):
    """The step program for the prefill chunks of several slots at once,
    for a serving model that has ``prefill_rows`` (``Scheduler`` compiles
    it for every group size and window it will dispatch;
    ``tests/test_chip_compile.py`` for the described chip)."""

    @functools.partial(jax.jit, donate_argnums=(1,), static_argnums=(8,))
    def _prefill_suffix_rows(
        params, cache, tokens, start, suffix_len, slots, key, sampling, window
    ):
        """``_prefill_suffix`` for the chunks of several slots in one
        program: tokens (B, s) of slots ``slots`` from positions
        ``start``, each row's first ``suffix_len`` counting; a row of
        length 0 is padding.  One sampled token a row."""
        temp, top_p, top_k = sampling
        cache, hidden, aux = model.prefill_rows(
            params, cache, tokens, start, suffix_len, slots, window
        )
        last = hidden[jnp.arange(tokens.shape[0]), jnp.maximum(suffix_len - 1, 0)]
        lg = model.logits(params, last[:, None, :])[:, 0]
        return cache, sample(lg, key, temp, top_p, top_k), aux

    return _prefill_suffix_rows


class Stats:
    """Served-token counters surfaced by /metrics."""

    def __init__(self) -> None:
        self.lock = threading.Lock()
        self.requests_total = 0
        self.tokens_total = 0
        self.ttft_sum = 0.0
        self.ttft_count = 0
        self.active_slots = 0
        self.queued = 0
        self.rejected_total = 0
        self.prefix_hits = 0
        self.prefix_tokens_reused = 0
        # Prompt tokens the prefix index matched for admitted requests;
        # reused is less where a hit was cut back to a state snapshot's
        # boundary (models whose state cannot be cut at a token).
        self.prefix_tokens_matched = 0
        # Snapshots of recurrent state (engine.prefix_cache.StateSnapshots):
        # saved at prefill-chunk boundaries, restored by prefix hits,
        # pushed out by their byte budget; bytes held now (a gauge).
        self.state_snapshots_saved = 0
        self.state_snapshots_restored = 0
        self.state_snapshots_evicted = 0
        self.state_snapshot_bytes = 0
        # Counters the model's step programs return beside their tokens
        # (serving_models: ``counter_names``); empty for a model with none.
        self.model_counters: dict[str, int] = {}
        # Bytes of the slots' state by kind (gauges, fixed when the state
        # is made): rows that grow with the tokens, and window layers'
        # rings, which do not grow with ``max_len``.  Zero for a model
        # that does not say (``serving_models``: ``state_bytes``).
        self.state_bytes_full = 0
        self.state_bytes_window = 0
        self.state_bytes_draft = 0  # a prediction module's rows
        # Cross-request shared-prefix cache hits (content match through
        # the radix index; session matches count under prefix_hits) and
        # chunked-prefill chunk dispatches.  prefix_tokens_reused pools
        # BOTH hit kinds — it measures prefill FLOPs avoided either way.
        self.shared_prefix_hits = 0
        self.prefill_chunks = 0
        # Of those, chunks dispatched behind a decode chunk and before
        # the host blocked on its tokens (``_advance_warming(ahead=True)``).
        self.prefill_chunks_ahead = 0
        # Programs dispatched for those chunks: a chunk alone is a program
        # of one, the chunks of several slots may share one
        # (``Scheduler._send_chunks``), so chunks / programs is the mean
        # number of chunks a weight pass served.
        self.prefill_chunk_programs = 0
        # Decode chunks dispatched while the one before was still
        # unfetched (a full house: ``Scheduler._goes_ahead``), counted at
        # their fetch like ``decode_chunks``; and the tokens a chunk
        # computed for a row whose request had ended before the chunk's
        # fetch (it stopped on EOS or was cancelled in the chunk before).
        self.decode_chunks_ahead = 0
        self.decode_tokens_dropped = 0
        # Steps that verified the model's own draft (``_emit_verified``):
        # rounds = live (greedy row, step) pairs, one draft each, and
        # tokens = what those steps emitted (one or two).  A sampled row
        # takes one token a step and counts in none of them.  proposed
        # counts the drafts put in front of the stack, accepted the ones
        # it kept (the stack's own next token behind an accepted draft is
        # NOT an accepted draft: accepted / proposed stays in [0, 1]);
        # spec_acceptance_ewma smooths the per-chunk rate.
        self.spec_rounds = 0
        self.spec_tokens = 0
        self.spec_proposed = 0
        self.spec_accepted = 0
        self.spec_acceptance_ewma = 0.0
        # tick_count counts every pass of the tick loop, idle polls
        # included; busy_ticks only those that dispatched or fetched a
        # program.
        self.tick_count = 0
        self.busy_ticks = 0
        self.prefill_rows = 0
        self.decode_chunks = 0
        # Exclusive tick phases (see _TickClock): the tick thread is in
        # exactly one phase at any instant, so the six sums partition its
        # wall time.  tick_phase is the phase it is in now (None while no
        # tick thread runs) and tick_phase_since when it entered it:
        # snapshot() adds the running phase's time, so the sums are exact
        # at any instant and not only at a phase change.
        self.tick_phase_s = dict.fromkeys(TICK_PHASES, 0.0)
        self.tick_phase: Optional[str] = None
        self.tick_phase_since = 0.0
        # Host time during which nothing was queued on the device, by the
        # phase the tick thread was in (_TickClock says what opens and
        # closes an interval; device_starved is whether one is open);
        # snapshot() sums the parts into device_starved_s.
        self.device_starved_s = dict.fromkeys(STARVED_PHASES, 0.0)
        self.device_starved = False
        # Dispatch sites returned from (``_TickClock.dispatched``), and
        # the ``dispatch`` phase's seconds by stage; their rest is the
        # sites' counters.
        self.dispatch_sites = 0
        self.dispatch_stage_s = dict.fromkeys(DISPATCH_STAGES, 0.0)
        # Request lifecycle: submit -> slot claim (queue wait, counted at
        # the claim) and claim -> first token fetched (counted at the
        # first token); the two add up to ttft_sum request by request.
        self.queue_wait_s_sum = 0.0
        self.queue_wait_count = 0
        self.warm_s_sum = 0.0
        self.warm_count = 0
        # Prompt tokens of claimed requests (after clipping), and what
        # _clip_prompt cut off prompts over the admissible bound.
        self.prompt_tokens_admitted = 0
        self.prompts_clipped = 0
        self.prompt_tokens_clipped = 0
        # Real prompt tokens handed to the prefill programs, counted at
        # the dispatch, and the padding that rode along (bucketed shape
        # minus real tokens, padded batch rows included).
        self.prefill_tokens_dispatched = 0
        self.prefill_tokens_padded = 0
        # Cold prompts of at most one prefill chunk, by the program their
        # admission went out in (``Scheduler._admit_cold``): a chunk of
        # one row of its own, or a row of a ``_prefill_some`` batch.
        self.admits_lone = 0
        self.admits_batched = 0
        # KV positions a contiguous decode chunk's attention reads a
        # step, counted at the dispatch: each decoding row's length in
        # whole kernel blocks, beside what a dense walk of the window
        # would read for every slot (max_batch x kv_bucket).
        self.decode_kv_tokens_read = 0
        self.decode_kv_tokens_dense = 0
        # Passes of the stack dispatched: a decode step or a prefill
        # program is one, and ``ut_steps`` of a looped stack
        # (``llama.LlamaConfig``), so decode time over ``decode_stack_passes``
        # is what one pass of the layers costs whatever the model loops.
        self.decode_stack_passes = 0
        self.prefill_stack_passes = 0
        # K/V planes a token holds and their bytes (gauges, fixed when the
        # state is made; zero for a model whose state is not K/V planes).
        self.cache_planes = 0
        self.kv_bytes_per_token = 0
        # EWMA of tick wall time, updated lock-free from the tick loop
        # (single-writer; readers tolerate a torn-in-time value).  The
        # 429 Retry-After hint derives queue-drain time from it without
        # a TSDB window scan on the shed path.
        self.tick_ms_ewma = 0.0
        # Token-normalized tick time: raw tick wall time scaled down by
        # emitted-tokens / baseline-chunk-tokens when a tick emits MORE
        # than one decode chunk's worth (a step that verifies the model's
        # own draft emits up to two tokens a row).  Every latency signal
        # derived from tick time — autoscaler tick_high_ms, replica
        # brownout scoring, the 429 Retry-After drain estimate — compares
        # against a one-token-per-slot-per-chunk-step cost model; feeding
        # it the raw wall time of a tick that emitted 2x the tokens would
        # read "2x slower" when the engine is actually 2x FASTER per
        # token.  A tick without drafts emits at most the baseline, so
        # there norm == raw and nothing changes.
        self.tick_ms_norm_ewma = 0.0
        # Executables this scheduler's tick thread asked JAX for (a step
        # program's first call at a new shape; ``utils.jax_runtime``'s
        # record has each by name): all of them, those the persistent
        # cache had and those it was asked for in vain (the rest ran with
        # no cache), and their seconds by stage.  After warm-up every one
        # is a request that waited for a compile.
        self.executables_requested = 0
        self.executables_hit = 0
        self.executables_missed = 0
        self.executable_trace_s = 0.0
        self.executable_lower_s = 0.0
        self.executable_backend_s = 0.0

    def snapshot(self) -> dict:
        with self.lock:
            phase_s = dict(self.tick_phase_s)
            starved_s = dict(self.device_starved_s)
            if self.tick_phase is not None:
                running = time.perf_counter() - self.tick_phase_since
                phase_s[self.tick_phase] += running
                if self.device_starved and self.tick_phase in starved_s:
                    starved_s[self.tick_phase] += running
            return {
                "requests_total": self.requests_total,
                "tokens_total": self.tokens_total,
                "tick_count": self.tick_count,
                "busy_ticks": self.busy_ticks,
                "prefill_rows": self.prefill_rows,
                "decode_chunks": self.decode_chunks,
                # Unrounded: a reader takes deltas over a few seconds.
                **{f"tick_phase_{p}_s": v for p, v in phase_s.items()},
                "device_starved_s": sum(starved_s.values()),
                **{f"device_starved_{p}_s": v for p, v in starved_s.items()},
                "dispatch_sites": self.dispatch_sites,
                **{f"dispatch_{k}_s": v for k, v in self.dispatch_stage_s.items()},
                "queue_wait_s_sum": self.queue_wait_s_sum,
                "queue_wait_count": self.queue_wait_count,
                "warm_s_sum": self.warm_s_sum,
                "warm_count": self.warm_count,
                "prompt_tokens_admitted": self.prompt_tokens_admitted,
                "prompts_clipped": self.prompts_clipped,
                "prompt_tokens_clipped": self.prompt_tokens_clipped,
                "prefill_tokens_dispatched": self.prefill_tokens_dispatched,
                "prefill_tokens_padded": self.prefill_tokens_padded,
                "admits_lone": self.admits_lone,
                "admits_batched": self.admits_batched,
                "decode_kv_tokens_read": self.decode_kv_tokens_read,
                "decode_kv_tokens_dense": self.decode_kv_tokens_dense,
                "decode_stack_passes": self.decode_stack_passes,
                "prefill_stack_passes": self.prefill_stack_passes,
                "cache_planes": self.cache_planes,
                "kv_bytes_per_token": self.kv_bytes_per_token,
                "ttft_avg_ms": (
                    self.ttft_sum / self.ttft_count * 1000 if self.ttft_count else 0.0
                ),
                "ttft_count": self.ttft_count,
                "active_slots": self.active_slots,
                "queued": self.queued,
                "rejected_total": self.rejected_total,
                "prefix_hits": self.prefix_hits,
                "prefix_tokens_reused": self.prefix_tokens_reused,
                "prefix_tokens_matched": self.prefix_tokens_matched,
                "state_snapshots_saved": self.state_snapshots_saved,
                "state_snapshots_restored": self.state_snapshots_restored,
                "state_snapshots_evicted": self.state_snapshots_evicted,
                "state_snapshot_bytes": self.state_snapshot_bytes,
                "state_bytes_full": self.state_bytes_full,
                "state_bytes_window": self.state_bytes_window,
                "state_bytes_draft": self.state_bytes_draft,
                **self.model_counters,
                "shared_prefix_hits": self.shared_prefix_hits,
                "prefill_chunks": self.prefill_chunks,
                "prefill_chunks_ahead": self.prefill_chunks_ahead,
                "prefill_chunk_programs": self.prefill_chunk_programs,
                "decode_chunks_ahead": self.decode_chunks_ahead,
                "decode_tokens_dropped": self.decode_tokens_dropped,
                "spec_rounds": self.spec_rounds,
                "spec_tokens": self.spec_tokens,
                "spec_proposed": self.spec_proposed,
                "spec_accepted": self.spec_accepted,
                "spec_acceptance_ewma": round(self.spec_acceptance_ewma, 4),
                "tick_ms_ewma": round(self.tick_ms_ewma, 3),
                "tick_ms_norm_ewma": round(self.tick_ms_norm_ewma, 3),
                "executables_requested": self.executables_requested,
                "executables_hit": self.executables_hit,
                "executables_missed": self.executables_missed,
                "executable_trace_s": self.executable_trace_s,
                "executable_lower_s": self.executable_lower_s,
                "executable_backend_s": self.executable_backend_s,
            }


def _as_build(init):
    """``Scheduler.__init__`` as a build of the executable record: what it
    compiles reads ``asked_by`` ``build``, and its seconds go to
    ``runtime_report()["setup"]`` whether it returns or raises."""

    @functools.wraps(init)
    def timed(self, *args, **kwargs) -> None:
        with EXECUTABLES.building():
            init(self, *args, **kwargs)

    return timed


class Scheduler:
    """Continuous batching over a fixed-slot KV cache."""

    @_as_build
    def __init__(
        self,
        cfg: llama.LlamaConfig,
        params=None,
        *,
        mesh=None,
        max_batch: int = 8,
        max_len: Optional[int] = None,
        decode_chunk_size: int = 8,
        seed: int = 0,
        max_queue: Optional[int] = None,
        admit_cap: Optional[int] = None,
        admit_token_budget: Optional[int] = None,
        prefill_chunk_tokens: Optional[int] = 256,
        prefix_cache: str = "shared",
        quantize: bool = False,
        matmul_kernel: Optional[str] = None,
        kv_layout: str = "contiguous",
    ) -> None:
        # Set-up in three stages (``setup/params``, ``setup/state``,
        # ``setup/programs``: spans, and gauges of ``runtime_report()``):
        # the parameters made, quantized and placed.
        EXECUTABLES.enter_stage("params")
        self.cfg = cfg
        self.mesh = mesh
        # Pool index when owned by an EnginePool (tags the `replica`
        # fault site); None for a standalone scheduler.
        self.replica_index: Optional[int] = None
        self.max_batch = max_batch
        self.max_len = max_len or cfg.max_seq_len
        self.decode_chunk_size = decode_chunk_size
        # Admission control: with a FIFO queue and sustained overload the
        # queue (and therefore TTFT) grows without bound — a
        # bounded-latency serving engine must shed load instead (the
        # reference's NIM/Triton containers bound their request queues
        # the same way).  None = unbounded (offline/batch callers).
        self.max_queue = max_queue
        if admit_cap is not None:
            if admit_cap < 1:
                raise ValueError(f"admit_cap must be >= 1, got {admit_cap}")
            if admit_cap & (admit_cap - 1):
                # _admit_dispatch buckets each prefill batch to the next power
                # of two, so a non-pow2 cap pads every saturated admission
                # batch (e.g. cap 96 -> 128 rows) and wastes prefill FLOPs
                # — measured as a ~10% serving-throughput regression.
                rounded = 1 << (admit_cap.bit_length() - 1)
                logger.warning(
                    "admit_cap %d is not a power of two; rounding down to "
                    "%d (bucketed prefill would pad it back up)",
                    admit_cap, rounded,
                )
                admit_cap = rounded
            self.ADMIT_CAP = admit_cap
        if admit_token_budget is not None:
            if admit_token_budget < 1:
                raise ValueError(
                    f"admit_token_budget must be >= 1, got {admit_token_budget}"
                )
            self.ADMIT_TOKEN_BUDGET = admit_token_budget
        self.stats = Stats()
        self._key = jax.random.PRNGKey(seed)
        from generativeaiexamples_tpu.engine.serving_models import (
            serving_model,
        )

        # The model behind the step programs (engine/serving_models.py):
        # everything below that touches parameters or slot state goes
        # through it.  It refuses, with the reason, what it does not serve.
        self.model = model = serving_model(cfg, mesh, self.max_len)
        # Positions a decode step writes a row: two where every step
        # verifies the model's own draft (``serving_models``: ``draft``).
        self._step_width = 2 if model.draft else 1
        model.check_supported()
        # ``quantize`` is the int8-weights serving configuration: float
        # (or absent, hence random) params become int8 projections with
        # qkv and gate/up packed — what ``chip_smoke.py`` hands over pre-built.
        self.params = model.prepare_params(
            params, quantize=quantize, matmul_kernel=matmul_kernel, seed=seed
        )
        # The layout the params are in (prepare_params refuses a
        # pallas_w8a8 request it cannot honour; params may also arrive
        # pre-blocked).  /metrics exports this.
        from generativeaiexamples_tpu.ops.qmm import BlockedQuantizedMatrix

        self.matmul_kernel = (
            "pallas_w8a8"
            if any(
                isinstance(leaf, BlockedQuantizedMatrix)
                for leaf in jax.tree.leaves(
                    self.params,
                    is_leaf=lambda x: isinstance(x, BlockedQuantizedMatrix),
                )
            )
            else "xla"
        )
        # The slots' state and the host's books.
        EXECUTABLES.enter_stage("state")
        # One KV layout; the keyword stays because benchmarks/run.py
        # passes it (ROADMAP.md queue 3 item 15).
        if kv_layout != "contiguous":
            raise ValueError(f"unknown kv_layout mode {kv_layout!r}")
        self._cache = model.init_state(max_batch, self.max_len)
        # Passes of the stack a step or a prefill program runs.
        self._stack_passes = getattr(cfg, "ut_steps", 1)
        # Bytes of fresh state a cold batch's prompt token costs; zero for
        # a model that does not say (``_cold_batch_rows`` then cuts nothing).
        self._kv_bytes_per_token = 0
        if hasattr(model, "kv_planes"):
            planes, per_token = model.kv_planes(self._cache)
            self.stats.cache_planes = planes
            self.stats.kv_bytes_per_token = self._kv_bytes_per_token = per_token
            logger.info(
                "slot state: %d K/V planes (%d passes of the stack), %d B a "
                "token, %d slots of %d rows",
                planes, self._stack_passes, per_token, max_batch, self.max_len,
            )
        self._decode_chunk = model.make_decode_chunk()
        # Prefix cache mode: "shared" (cross-request content matching via
        # the radix index + per-session parking), "session" (conversation
        # parking only — the pre-shared behavior), "off".
        if prefix_cache not in ("shared", "session", "off"):
            raise ValueError(f"unknown prefix_cache mode {prefix_cache!r}")
        self.prefix_cache = prefix_cache
        self._prefix_index = PrefixCacheIndex()
        # Chunked prefill: cold prompts (and cache-hit suffixes) longer
        # than this claim a slot and prefill one chunk per tick,
        # interleaved with decode.  None/0 disables (monolithic batched
        # admission for everything).
        if prefill_chunk_tokens is not None and prefill_chunk_tokens <= 0:
            prefill_chunk_tokens = None
        self.prefill_chunk_tokens = prefill_chunk_tokens
        # The smallest batch bucket of ``_prefill_some``.  With chunked
        # prefill a batch of at most half of it goes out as chunks of one
        # row each (``_admit_cold``), so the buckets start at 8; without,
        # a lone admission has no other program, and they start at 4.
        self._admit_rows_min = min(8 if prefill_chunk_tokens else 4, max_batch)
        # A model whose state cannot be cut at a token is reused only from
        # where chunked prefill saved it: at every chunk boundary, under a
        # byte budget of its own (StateSnapshots).  Without chunking there
        # is no boundary, and every prefix hit is cut back to nothing.
        self._snapshots: Optional[StateSnapshots] = None
        if not model.cut_anywhere:
            self._snapshots = StateSnapshots(
                prefill_chunk_tokens or self.max_len,
                model.snapshot_bytes,
                STATE_SNAPSHOT_BUDGET if prefill_chunk_tokens else 0,
            )
        self.stats.model_counters = dict.fromkeys(model.counter_names, 0)
        if hasattr(model, "state_bytes"):
            by_kind = model.state_bytes(max_batch)
            self.stats.state_bytes_full = by_kind["full"]
            self.stats.state_bytes_window = by_kind["window"]
            self.stats.state_bytes_draft = by_kind.get("draft", 0)
        # Counters the step programs return beside their tokens, not yet
        # fetched: drained once ready, after a token fetch, so that they
        # cost no synchronisation of their own.
        self._aux_pending: list = []
        # Token futures of the chunk programs the last tick sent ahead, in
        # the device's order (``_tick`` waits for the first of several).
        self._ahead_toks: list = []
        # The decode chunk dispatched ahead, while the one before it was
        # unfetched (``_goes_ahead``): ``_decode_finalize``'s arguments and
        # the chunk's kv_bucket (for the record of the tick that fetches
        # it), kept for the next tick; None between ticks otherwise.
        self._flight: Optional[tuple] = None
        # The newest decode chunk's tokens, (decode_chunk_size, max_batch)
        # on the device: the next chunk reads the rows that go on in its
        # last row (``decode.carry_tokens``), and a cold admission's graft
        # lands its first tokens there (``_graft_rows``).  Never donated:
        # the chunk's finalizer fetches the same buffer.
        self._carried = self._no_tokens()
        # A drafting model's newest chunk also leaves each row's length
        # (max_batch,) there: a step advanced it by one or two.
        self._carried_len = self._no_lengths()
        # Pipelined ticks dispatch the decode chunk in the same tick as
        # admissions, pinning not-yet-decoding lanes to max_len - 1 —
        # whose append-buffer flush garbage-writes [max_len - w, max_len)
        # where w is the chunk's flush width, decode_chunk_size.  Admitted
        # prompt KV must stay strictly below flush_clip_start of that
        # flush, so admissions truncate to one less (ADVICE r5: longer
        # same-tick prompts had their tail KV overwritten and decoded
        # garbage from then on).
        self._admit_limit = flush_clip_start(
            self.max_len, self.decode_chunk_size
        )
        if self._admit_limit < 2:
            raise ValueError(
                f"max_len {self.max_len} leaves no admissible prompt room "
                f"beside decode_chunk_size {self.decode_chunk_size}"
            )
        self._slots = [_Slot() for _ in range(max_batch)]
        self._cancelled: set[str] = set()
        self._cancel_lock = threading.Lock()
        self._cur_tok = np.zeros((max_batch,), dtype=np.int32)
        self._tok_count = 0  # tokens emitted since the last stats flush
        # Per-tick emission accounting for the token-normalized tick
        # latency (Stats.tick_ms_norm_ewma): tokens emitted this tick and
        # the number of lanes the tick's decode chunk actually advanced.
        # Scheduler-thread only; _note_tick reads them after each tick.
        self._tick_tokens = 0
        self._tick_decoded = 0
        # The rest of a busy tick's record (``_ticks``, newest last):
        # warming chunks dispatched, requests claimed, the decode chunk's
        # attention window, and whether the tick touched the device.
        self._tick_chunks = 0
        self._tick_chunk_programs = 0
        self._tick_admitted = 0
        self._tick_kv_bucket = 0
        self._tick_ahead = 0
        self._tick_executables = 0
        self._tick_busy = False
        self._tick_no = 0
        self._ticks: "collections.deque[tuple]" = collections.deque(
            maxlen=4096
        )
        self._clock = _TickClock(self.stats)
        self._pending: "queue.Queue[Request]" = queue.Queue()
        # Requests popped but not yet placeable (all slots busy) wait here,
        # at the FRONT, so admission stays FIFO under overload.  Scheduler-
        # thread only.
        self._backlog: "collections.deque[Request]" = collections.deque()
        self._running = False
        self._thread: Optional[threading.Thread] = None
        # Fleet telemetry (obs/tsdb.py): tick durations feed every tick;
        # snapshot-derived gauges/counter-deltas at most every interval.
        self._tsdb_feed_interval_s = 0.25
        self._last_tsdb_feed = 0.0
        self._tsdb_prev: dict = {}
        # The step programs: closures that compile at their first call,
        # and the family compiled here (``_compile_chunk_programs``).
        EXECUTABLES.enter_stage("programs")

        @jax.jit
        def _prefill_some(params, tokens, lengths, key, temp, top_p, top_k):
            """Prefill a (bucketed) batch of sequences into a fresh cache.

            Batched admission: a burst of waiting requests prefills as one
            weight pass and then grafts row-by-row into its slots.  The
            time follows the token positions computed, pad rows included:
            on one v5e, mistral-7b in int8, a row of 64 / 128 / 256 tokens
            alone takes 12 / 14 / 25 ms (the weight stream's floor, then
            the MXU), four rows 21 / 40 / 89 and eight 41 / 79 / 177
            (PERF.md section 6, PR 46).  So a batch pays for its padding,
            and ``_admit_cold`` sends a tick's one to four short prompts
            as chunks of one row each instead.
            """
            b = tokens.shape[0]
            hidden, small, aux = model.prefill_cold(params, tokens, lengths)
            last = hidden[jnp.arange(b), jnp.maximum(lengths - 1, 0)]
            lg = model.logits(params, last[:, None, :])[:, 0]
            tok = sample(lg, key, temp, top_p, top_k)
            return small, tok, aux

        @functools.partial(jax.jit, donate_argnums=(0,))
        @jax.named_scope("kv_write")
        def _graft_rows(big, small, rows, slots, carried=None, first=None):
            """Copy prefilled KV rows of the small cache into their slots
            of the big cache — one scatter per leaf for the whole
            admission batch (per-row dispatches were a measurable slice of
            the serving cycle at tens of admissions per tick).

            ``rows``/``slots`` are equal-length int32 vectors, padded by
            the caller with duplicates of index 0 (duplicate scatters of
            the same source row are harmless).

            ``carried``/``first``: the batch's sampled first tokens land
            in their slots' entries of ``carried``'s last row, where a
            decode chunk dispatched behind this graft reads them
            (``Scheduler._carried``); returned second, not donated."""
            big = model.graft_rows(big, small, rows, slots)
            if carried is None:
                return big
            return big, carried.at[-1, slots].set(first[rows])

        @functools.partial(
            jax.jit, donate_argnums=(1,), static_argnums=(8,)
        )
        def _prefill_suffix(
            params, cache, tokens, start, suffix_len, slot,
            key, sampling, kv_bucket,
        ):
            """Warm-prefill a prompt suffix into a parked slot's cache rows.

            The prefix-cache hit path (reference gap: TRT-LLM paged-KV
            prefix reuse, SURVEY.md §2.8): the slot already holds KV for
            ``start`` tokens of this conversation, so only the suffix
            (tokens, (1, s) bucketed) runs the model — attention reads
            back the slot's cached prefix via the warm (non-cold) path.
            """
            temp, top_p, top_k = sampling
            cache, hidden, aux = model.prefill_row(
                params, cache, tokens, start, suffix_len, slot, kv_bucket
            )
            last = hidden[0, jnp.maximum(suffix_len - 1, 0)]
            lg = model.logits(params, last[None, None, :])[:, 0]
            tok = sample(lg, key, temp, top_p, top_k)
            return cache, tok, aux

        @functools.partial(jax.jit, donate_argnums=(0,), static_argnums=(3,))
        @jax.named_scope("kv_write")
        def _graft_prefix(cache, src, dst, n):
            """Copy the first ``n`` cache rows of slot ``src`` into slot
            ``dst`` — the shared-prefix cache hit's device op.

            ``n`` is static (bucketed by the caller); copying a few rows
            beyond the actual common prefix is harmless — positions past
            the destination's live length are rewritten by its own
            suffix prefill/decode before any attention mask exposes
            them."""
            return model.graft_prefix(cache, src, dst, n)

        if self._snapshots is not None:
            self._save_state = jax.jit(
                jax.named_scope("state_snapshot")(model.save_state)
            )
            self._restore_state = jax.jit(
                jax.named_scope("state_snapshot")(model.restore_state),
                donate_argnums=(0,),
            )

        self._prefill_some = _prefill_some
        self._prefill_suffix = _prefill_suffix
        self._prefill_suffix_rows = make_prefill_suffix_rows(model)
        self._graft_rows = _graft_rows
        self._graft_prefix = _graft_prefix
        # The chunks that one tick sends for several warming slots go out
        # as one program where the model says its chunk is a weight stream
        # that more token rows could share (``chunks_per_program``): the
        # largest group, 1 where every chunk goes alone through
        # ``_prefill_suffix``.  The chunk programs of such a model are a
        # closed family, compiled here, so that no traffic can ask for one
        # inside a request.
        self._chunk_rows, self._chunk_windows = 1, ()
        self._chunk_programs: dict[tuple[int, int], Callable] = {}
        if prefill_chunk_tokens:
            self._compile_chunk_programs(
                model.chunks_per_program(prefill_chunk_tokens)
            )

    # -- public API --------------------------------------------------------

    def submit(self, request: Request) -> bool:
        """Enqueue a request; returns False (and touches nothing) when
        the admission queue is full — the HTTP front maps that to 429 so
        TTFT of accepted requests stays bounded under overload."""
        request.submitted_at = time.perf_counter()
        with self.stats.lock:
            if (
                self.max_queue is not None
                and self.stats.queued >= self.max_queue
            ):
                self.stats.rejected_total += 1
                return False
            self.stats.queued += 1
        self._pending.put(request)
        return True

    def cancel(self, request_id: str) -> None:
        """Stop generating for a request (client disconnect / stop-string
        satisfied).  The slot is released at the next chunk boundary and
        ``on_done("cancelled")`` fires."""
        if not request_id:
            return
        with self._cancel_lock:
            self._cancelled.add(request_id)

    def start(self) -> None:
        if self._running:
            return
        self._running = True
        self._thread = threading.Thread(target=self._loop, daemon=True)
        self._thread.start()

    def stop(self) -> None:
        self._running = False
        if self._thread is not None:
            self._thread.join(timeout=10)
            self._thread = None

    def request_stop(self) -> None:
        """Ask the tick loop to exit without joining it — safe to call
        from a health monitor that must not block on a wedged thread."""
        self._running = False

    def healthy(self) -> bool:
        """False iff the tick thread died while the scheduler was meant
        to be running (the /health liveness signal; a never-started or
        cleanly stopped scheduler is not 'dead')."""
        return (
            self._thread is None
            or not self._running
            or self._thread.is_alive()
        )

    def tick_records(self, limit: int = 100) -> list[dict]:
        """The newest ``limit`` busy ticks, oldest first: tick number,
        start (perf_counter and wall clock), seconds in each phase,
        starved seconds, warming chunks dispatched, requests claimed,
        decode lanes and their attention window, tokens emitted, the
        queue depth at the tick's end and, last, the executables the tick
        asked JAX for (``utils.jax_runtime.EXECUTABLES`` has each by
        name).  What a stall is read from."""
        if limit <= 0:
            return []
        return [
            dict(zip(TICK_RECORD_FIELDS, r)) for r in list(self._ticks)[-limit:]
        ]

    # -- internals ---------------------------------------------------------

    def _no_tokens(self) -> jax.Array:
        """``_carried`` before any chunk has run (an upload, no program):
        the shape a chunk leaves, which for a drafting model is each
        row's newest token alone."""
        steps = 1 if self.model.draft else self.decode_chunk_size
        return jax.device_put(np.zeros((steps, self.max_batch), np.int32))

    def _no_lengths(self) -> Optional[jax.Array]:
        """``_carried_len`` before any chunk has run; a model that does
        not draft has none (the host knows every length)."""
        if not self.model.draft:
            return None
        return jax.device_put(np.zeros((self.max_batch,), np.int32))

    def _next_key(self) -> jax.Array:
        self._clock.poll()
        self._key, sub = jax.random.split(self._key)
        return sub

    def _h2d(self, *host) -> list:
        """A dispatch's host arrays (or scalars) on the device, the
        clock's poll point in front of each: a transfer is a third of a
        millisecond of host time and more beside the server's other
        threads (PERF.md section 5), and a device that runs out of work
        while its next program's arrays go over should not wait for the
        last of them to be seen."""
        out = []
        for a in host:
            self._clock.poll()
            out.append(jnp.asarray(a))
        return out

    def _note_aux(self, aux=None) -> None:
        """Keep a step program's counters until they can be fetched
        without waiting (``_drain_aux``)."""
        if aux is not None:
            self._aux_pending.append(aux)

    def _drain_aux(self) -> None:
        """Add up the counters of every step program that has finished.
        Called right after a token fetch: the device runs its programs
        in order, so whatever was dispatched before the fetched one is
        ready and nothing here waits."""
        if not self._aux_pending:
            return
        ready, waiting = [], []
        for a in self._aux_pending:
            (ready if a.is_ready() else waiting).append(a)
        if not ready:
            return
        self._aux_pending = waiting
        total = np.sum([np.asarray(a, dtype=np.int64) for a in ready], axis=0)
        with self.stats.lock:
            for name, n in zip(self.model.counter_names, total):
                self.stats.model_counters[name] += int(n)

    def _state_depth(self, req: Request, common: int) -> int:
        """How much of a ``common``-token prefix match can be reused: all
        of it where the state can be cut at any token, else up to the
        deepest boundary at which a snapshot of the state is held."""
        req.prefix_matched = common
        if self._snapshots is None:
            return common
        return self._snapshots.deepest(req.token_ids, common)

    def _save_boundary(
        self, slot: _Slot, slot_idx: int, depth: int
    ) -> Optional[jax.Array]:
        """After a prefill chunk that ended at ``depth``: keep the slot's
        recurrent state if ``depth`` is a snapshot boundary.  Returns an
        array of the snapshot it enqueued (the clock's sentinel), or None."""
        snaps = self._snapshots
        if snaps is None or depth % snaps.every or not snaps.capacity:
            return None
        key = snaps.key(slot.history, depth)
        if key in snaps:
            snaps.get(key)  # fresh again
            return None
        snapshot = self._save_state(self._cache, jnp.int32(slot_idx))
        evicted = snaps.put(key, snapshot)
        with self.stats.lock:
            self.stats.state_snapshots_saved += 1
            self.stats.state_snapshots_evicted += evicted
            self.stats.state_snapshot_bytes = snaps.bytes
        return jax.tree_util.tree_leaves(snapshot)[0]

    def _restore_boundary(self, req: Request, slot_idx: int, depth: int) -> None:
        """Before a prefix hit's suffix runs: put the state saved at
        ``depth`` tokens of ``req``'s prompt into the slot."""
        snaps = self._snapshots
        if snaps is None or depth <= 0:
            return
        snap = snaps.get(snaps.key(req.token_ids, depth))
        self._cache = self._restore_state(self._cache, jnp.int32(slot_idx), snap)
        with self.stats.lock:
            self.stats.state_snapshots_restored += 1

    def _is_cancelled(self, request_id: str) -> bool:
        with self._cancel_lock:
            if request_id in self._cancelled:
                self._cancelled.discard(request_id)
                return True
            return False

    def _drop_if_cancelled(self, req: Request) -> bool:
        """Drop a still-queued request that was cancelled before admission;
        returns True if dropped.  on_done is guarded like _finish's — a
        raising callback (e.g. a bridge whose event loop died at server
        shutdown) must not escape into _tick and trigger the loop's
        catastrophic cache-reallocation recovery."""
        if not (req.id and self._is_cancelled(req.id)):
            return False
        with self.stats.lock:
            self.stats.queued -= 1
        try:
            req.on_done("cancelled")
        except Exception:
            logger.exception("on_done callback failed")
        return True

    def _next_pending(self) -> Optional[Request]:
        """Next request to consider: the FIFO backlog first, then the
        cross-thread queue."""
        if self._backlog:
            return self._backlog.popleft()
        try:
            return self._pending.get_nowait()
        except queue.Empty:
            return None

    def _flush_tokens(self) -> None:
        if self._tok_count:
            with self.stats.lock:
                self.stats.tokens_total += self._tok_count
                self._tok_count = 0

    def _free_slots(self) -> list[int]:
        """Slots with neither a live request nor parked prefix KV."""
        return [
            i
            for i, s in enumerate(self._slots)
            if s.request is None and not s.cached
        ]

    def _reclaim_parked(self, n: int) -> list[int]:
        """Evict up to ``n`` slot-parked prefix segments, oldest first.
        Segments pinned by an in-flight graft are never taken."""
        parked = sorted(
            (
                i
                for i, s in enumerate(self._slots)
                if s.request is None
                and s.cached
                and not self._prefix_index.pinned(i)
            ),
            key=lambda i: self._slots[i].parked_at,
        )
        out = []
        for i in parked[:n]:
            self._unpark(i)
            out.append(i)
        return out

    def _unpark(self, slot_idx: int) -> None:
        slot = self._slots[slot_idx]
        self._prefix_index.remove(slot_idx)
        slot.session_id = ""
        slot.cached = False
        slot.history = []
        slot.parked_at = 0.0
        slot.length = 0
        slot.warm_pos = None

    def _active(self) -> list[int]:
        """Slots decoding this tick: live request, prefill complete and
        its first token on the host (a final chunk dispatched a tick
        ahead leaves it a device future until ``first_token`` has run)."""
        return [
            i
            for i, s in enumerate(self._slots)
            if s.request is not None
            and s.warm_pos is None
            and s.first_token is None
        ]

    def _warming(self) -> list[int]:
        """Slots phase 1 has a chunk to book for: mid chunked-prefill
        (live request, KV still building), or the prompt's last chunk
        went out a tick ahead and is still to be booked."""
        return [
            i
            for i, s in enumerate(self._slots)
            if s.request is not None
            and (s.warm_pos is not None or s.ahead_tokens)
        ]

    def _clip_prompt(self, req: Request) -> None:
        """Truncate an over-long prompt to the admissible bound (keeps the
        TAIL — recency matters for chat/RAG prompts).  The bound keeps
        prompt KV clear of the append-buffer flush-clip zone a pipelined
        tick can garbage-write for lanes admitted the same tick."""
        n = len(req.token_ids)
        if n >= self._admit_limit:
            req.token_ids = req.token_ids[-(self._admit_limit - 1) :]
            with self.stats.lock:
                self.stats.prompts_clipped += 1
                self.stats.prompt_tokens_clipped += n - len(req.token_ids)
            logger.warning(
                "request %s: prompt of %d tokens clipped to its last %d",
                req.id or "<no id>", n, len(req.token_ids),
            )

    def _note_claim(self, reqs: Sequence[Request]) -> None:
        """A slot was claimed for each of ``reqs``: stamp it and count
        its queue wait.  Every admission path claims through
        ``_admit_dispatch``, ``_admit_hit`` or ``_claim_warm_cold``."""
        now = time.perf_counter()
        self._tick_admitted += len(reqs)
        with self.stats.lock:
            for req in reqs:
                req.claimed_at = now
                self.stats.queue_wait_s_sum += now - req.submitted_at
                self.stats.queue_wait_count += 1
                self.stats.prompt_tokens_admitted += len(req.token_ids)
                self.stats.prefix_tokens_matched += req.prefix_matched

    def _note_first_token(self, req: Request) -> None:
        """``req``'s first token was fetched: TTFT and its prefill part.
        Caller holds the stats lock."""
        self.stats.requests_total += 1
        self.stats.ttft_sum += req.first_token_at - req.submitted_at
        self.stats.ttft_count += 1
        self.stats.warm_s_sum += req.first_token_at - req.claimed_at
        self.stats.warm_count += 1

    def _finish(self, slot_idx: int, reason: str) -> None:
        # Publish deferred token counts before on_done fires: a caller
        # reading stats right after completion must see its own tokens.
        self._flush_tokens()
        slot = self._slots[slot_idx]
        req = slot.request
        slot.request = None
        # A chunk dispatched ahead for this request wrote only this
        # slot's rows; nothing of it is booked or fetched any more.
        slot.ahead_tokens = 0
        slot.first_token = None
        # Nor of a decode chunk that is still on the device with this row
        # live: its finalizer finds another request here, or none, and
        # drops the row's tokens (``_decode_finalize``).
        slot.unfetched, slot.on_device, slot.chunks_out = 0, False, 0
        if (
            req is not None
            and reason in ("stop", "length")
            # Park session turns in "session"/"shared" mode; in "shared"
            # mode ALSO park sessionless finishes as anonymous segments
            # for cross-request prefix grafting — but only when the
            # history is long enough to ever hit (MIN_PREFIX), so trivial
            # requests don't churn slots.
            and (
                (req.session_id and self.prefix_cache != "off")
                or (
                    self.prefix_cache == "shared"
                    and slot.length + slot.emitted > self.MIN_PREFIX
                )
            )
            # Parked history must stay clear of the cache tail: inactive
            # lanes' garbage lands at [max_len - 1] (scatter path) or in
            # the append-buffer flush zone [flush_clip_start, max_len)
            # (kernel path), decode_chunk_size wide.
            and slot.length + slot.emitted
            < min(
                flush_clip_start(self.max_len, self.decode_chunk_size),
                self.max_len - max(16, self.decode_chunk_size + 1),
            )
        ):
            # Park the slot: its cache rows hold KV for the prompt plus
            # every emitted token except, on length finishes, the last one
            # (the final sampled token is never fed back, so its KV was
            # never written).  On EOS stops the step that sampled the EOS
            # consumed — and wrote KV for — the last history token, so the
            # full history is reusable.  The next turn of this
            # conversation reuses the common prefix.
            if reason == "stop" or not slot.emitted:
                history = list(slot.history)
            else:
                history = slot.history[:-1]
            if req.session_id:
                for i, s in enumerate(self._slots):
                    if (
                        s.session_id == req.session_id
                        and s.request is None
                    ):
                        # stale earlier turn of this session
                        self._unpark(i)
            slot.session_id = req.session_id
            slot.cached = True
            slot.history = history
            slot.length = len(history)
            slot.parked_at = time.monotonic()
            if self.prefix_cache == "shared":
                # Register for cross-request content matching (session
                # turns included: many sessions share one system
                # prompt).
                self._prefix_index.insert(slot_idx, history)
        else:
            self._unpark(slot_idx)
        slot.emitted = 0
        if req is not None and req.id:
            # Late cancels (e.g. the handler's disconnect guard) must not
            # accumulate for ids that already finished.
            with self._cancel_lock:
                self._cancelled.discard(req.id)
        if req is not None:
            try:
                req.on_done(reason)
            except Exception:
                logger.exception("on_done callback failed")

    def _admit_cold(
        self, reqs: Sequence[Request], slot_idxs: Sequence[int]
    ) -> list[Callable[[], None]]:
        """Dispatch the admission of cold prompts of at most one prefill
        chunk, the tick's or the idle path's, without blocking; returns
        their finalizers in the device's order.

        Prefill time follows the token positions computed, pad rows
        included (``_prefill_some``), so a few prompts are cheaper alone:
        where chunked prefill is on, a batch of at most half the smallest
        batch bucket goes out as a long prompt's chunks do, each a first
        chunk that is also the last (``_advance_warm``: a program of one
        row at the prompt's own bucket).  A burst stays one weight pass
        through ``_admit_dispatch``, cut into as many as its own state has
        room for (``_cold_batch_rows``).  A slot admitted alone joins decode a
        tick later than a batch's row, whose first token the graft lands
        on the device (``_carried``)."""
        rows = self._cold_batch_rows(reqs)
        alone = bool(
            self.prefill_chunk_tokens
            and (not rows or 2 * len(reqs) <= self._admit_rows_min)
        )
        if not alone and rows < len(reqs):
            return [
                fin
                for at in range(0, len(reqs), rows)
                for fin in self._admit_cold(
                    reqs[at : at + rows], slot_idxs[at : at + rows]
                )
            ]
        with self.stats.lock:
            if alone:
                self.stats.admits_lone += len(reqs)
            else:
                self.stats.admits_batched += len(reqs)
        if not alone:
            t = self._admit_dispatch(reqs, slot_idxs)
            return [lambda: self._admit_finalize(*t)]
        fins = []
        for req, slot_idx in zip(reqs, slot_idxs):
            self._claim_warm_cold(req, slot_idx)
            fin, _ = self._advance_warm(slot_idx)
            if fin is not None:  # None: cancelled since it was polled
                fins.append(fin)
        return fins

    def _cold_batch_rows(self, reqs: Sequence[Request]) -> int:
        """How many of ``reqs`` (clipped) may prefill as one batch, so that
        the batch's own state stays within ``COLD_BATCH_STATE_BYTES``.

        A batch prefills into fresh state beside the slots and the weights
        before its rows are grafted (``_prefill_some``): batch bucket x
        prompt bucket x the bytes a token.  At 66,560 B a token (Mistral,
        llama3-8b in int8) 32 rows of 256 tokens are 0.5 GB and nothing is
        cut; at a looped stack's 798,720 B, 16 rows of 256 were 3.3 GB that
        the chip did not have (my chip call 1, PR 51): there 16 rows of 64
        tokens or 8 of 128 stay one batch, and prompts of bucket 256 go out
        alone, as chunks of one row written in place.

        A power of two (pieces fill their batch bucket).  0 where not even
        the smallest batch bucket fits and chunked prefill has the program
        of one row; without it that bucket is the smallest program there
        is, and is returned."""
        if not self._kv_bytes_per_token:
            return len(reqs)
        s = min(
            bucket_size(max(len(r.token_ids) for r in reqs), dense=True),
            self.max_len,
        )
        rows = COLD_BATCH_STATE_BYTES // (s * self._kv_bytes_per_token)
        if rows >= bucket_size(len(reqs), minimum=self._admit_rows_min):
            return len(reqs)
        if rows < self._admit_rows_min:
            return 0 if self.prefill_chunk_tokens else self._admit_rows_min
        return 1 << (int(rows).bit_length() - 1)

    def _admit_dispatch(
        self, reqs: Sequence[Request], slot_idxs: Sequence[int]
    ) -> tuple:
        """Dispatch an admission batch — prefill forward + cache graft —
        WITHOUT blocking on the device result.

        Slot metadata is claimed here so later admission batches and the
        next decode dispatch see these slots as occupied; token emission
        and TTFT accounting happen in :meth:`_admit_finalize` once the
        sampled tokens are fetched.  The split exists for the pipelined
        tick: admission batches dispatch FIRST and the decode chunk is
        dispatched behind them on the device stream, so the per-dispatch
        latency overlaps decode compute instead of extending the tick,
        and the batch's first tokens are fetchable ~RTT+prefill into the tick —
        ahead of the decode chunk — which keeps the decode chunk off
        every request's TTFT critical path."""
        plens = []
        for req in reqs:
            self._clip_prompt(req)
            plens.append(len(req.token_ids))
        self._note_claim(reqs)
        pb = bucket_size(len(reqs), minimum=self._admit_rows_min)
        s = min(bucket_size(max(plens), dense=True), self.max_len)
        with self.stats.lock:
            self.stats.prefill_tokens_dispatched += sum(plens)
            self.stats.prefill_tokens_padded += pb * s - sum(plens)
            self.stats.prefill_stack_passes += self._stack_passes
        tokens = np.zeros((pb, s), dtype=np.int32)
        lengths = np.zeros((pb,), dtype=np.int32)
        temp = np.zeros((pb,), dtype=np.float32)
        top_p = np.ones((pb,), dtype=np.float32)
        top_k = np.zeros((pb,), dtype=np.int32)
        for r, req in enumerate(reqs):
            tokens[r, : plens[r]] = req.token_ids
            lengths[r] = plens[r]
            temp[r] = req.sampling.temperature
            top_p[r] = req.sampling.top_p
            top_k[r] = req.sampling.top_k
        self._clock.enter(
            "dispatch", program="_prefill_some", tokens=sum(plens),
            rows=pb, bucket=s,
        )
        k = len(reqs)
        kb = bucket_size(k, minimum=min(4, pb))
        rows = np.zeros((kb,), dtype=np.int32)
        slots_arr = np.full((kb,), slot_idxs[0], dtype=np.int32)
        rows[:k] = np.arange(k)
        slots_arr[:k] = slot_idxs
        tokens_dev, lengths_dev, rows_dev, slots_dev, *sampling_dev = self._h2d(
            tokens, lengths, rows, slots_arr, temp, top_p, top_k
        )
        key = self._next_key()
        self._clock.stage("call")
        small, tok, aux = self._prefill_some(
            self.params, tokens_dev, lengths_dev, key, *sampling_dev
        )
        self._cache, self._carried = self._graft_rows(
            self._cache, small, rows_dev, slots_dev, self._carried, tok
        )
        self._clock.dispatched(self._carried)
        self._note_aux(aux)
        self._clock.enter("plan")
        for r, (req, slot_idx) in enumerate(zip(reqs, slot_idxs)):
            slot = self._slots[slot_idx]
            slot.request = req
            slot.length = plens[r]
            slot.emitted = 0
            slot.history = list(req.token_ids)
            slot.unfetched = 1
            slot.on_device = True
        return reqs, slot_idxs, tok

    def _admit_finalize(
        self,
        reqs: Sequence[Request],
        slot_idxs: Sequence[int],
        tok,
    ) -> None:
        """Fetch a dispatched admission batch's first tokens and emit them."""
        self._clock.enter("wait_device")
        tok_host = np.asarray(tok)
        self._clock.enter("emit")
        self._drain_aux()
        now = time.perf_counter()
        for r, (req, slot_idx) in enumerate(zip(reqs, slot_idxs)):
            self._clock.poll()
            req.first_token_at = now
            with self.stats.lock:
                self.stats.queued -= 1
                self._note_first_token(req)
            observe_stage(
                "llm_ttft", (req.first_token_at - req.submitted_at) * 1000.0
            )
            self._first_token(slot_idx, req, int(tok_host[r]))
        with self.stats.lock:
            self.stats.prefill_rows += len(reqs)

    # Minimum shared-prefix length for the suffix-prefill path; below this
    # a full prefill in the admission batch is cheaper than a dedicated
    # single-row dispatch.
    MIN_PREFIX = 32

    def _find_parked(self, req: Request) -> tuple[int, int]:
        """Locate this session's parked slot, whose cached history is a
        long-enough prefix of the new prompt; returns (slot, prefix_len)
        or (-1, 0)."""
        req.prefix_matched = 0
        if not req.session_id:
            return -1, 0
        for i, s in enumerate(self._slots):
            if s.request is None and s.session_id == req.session_id:
                n = 0
                for a, b in zip(s.history, req.token_ids):
                    if a != b:
                        break
                    n += 1
                n = self._state_depth(req, n)
                if n >= self.MIN_PREFIX:
                    return i, n
                return -1, 0
        return -1, 0

    def _find_shared(self, req: Request) -> tuple[int, int]:
        """Locate a parked segment (any session) sharing the longest token
        prefix with the prompt via the radix index; returns
        (slot, prefix_len) or (-1, 0)."""
        if self.prefix_cache != "shared":
            return -1, 0
        seg, common = self._prefix_index.match(req.token_ids)
        if seg is None:
            return -1, 0
        common = min(common, len(req.token_ids) - 1)
        if common >= self.MIN_PREFIX:
            common = self._state_depth(req, common)
        if common < self.MIN_PREFIX:
            return -1, 0
        slot = self._slots[seg]
        if slot.request is not None or not slot.cached:
            # Defensive: the index and slot state are maintained
            # together, but a stale entry must never graft live rows.
            self._prefix_index.remove(seg)
            return -1, 0
        return seg, common

    def _suffix_dispatch(self, req: Request, slot_idx: int, common: int):
        """Dispatch a suffix prefill into ``slot_idx`` (whose cache rows
        already hold KV for ``common`` prompt tokens) without blocking;
        claims the slot.  Returns args for :meth:`_suffix_finalize`."""
        plen = len(req.token_ids)
        suffix = req.token_ids[common:]
        s = min(bucket_size(len(suffix), minimum=16, dense=True), self.max_len)
        tokens = np.zeros((1, s), dtype=np.int32)
        tokens[0, : len(suffix)] = suffix
        kv_bucket = bucket_size(common + s, maximum=self.max_len, dense=True)
        sp = req.sampling
        self._prefill_suffix_begin(len(suffix), s, kv_bucket)
        tok = self._call_prefill_suffix(
            tokens, common, len(suffix), slot_idx, sp, kv_bucket
        )
        self._clock.enter("plan")
        slot = self._slots[slot_idx]
        slot.request = req
        slot.length = plen
        slot.emitted = 0
        slot.history = list(req.token_ids)
        slot.warm_pos = None
        slot.unfetched = 1
        return req, slot_idx, tok

    def _prefill_suffix_begin(
        self, n: int, s: int, kv_bucket: int, rows: int = 1,
        program: str = "_prefill_suffix",
    ) -> None:
        """Count a ``_prefill_suffix`` dispatch of ``n`` real tokens in
        ``rows`` buckets of ``s`` (a group's padding rows are padding too)
        and enter the dispatch phase."""
        with self.stats.lock:
            self.stats.prefill_tokens_dispatched += n
            self.stats.prefill_tokens_padded += rows * s - n
            self.stats.prefill_stack_passes += self._stack_passes
        self._clock.enter(
            "dispatch", program=program, tokens=n, bucket=s,
            kv_bucket=kv_bucket, rows=rows,
        )

    def _suffix_finalize(self, req, slot_idx, tok, row=0) -> None:
        """Fetch a suffix prefill's first token (``row`` of its program's)
        and emit it."""
        self._clock.enter("wait_device")
        tok_host = int(np.asarray(tok)[row])
        self._clock.enter("emit")
        self._drain_aux()
        self._clock.poll()
        req.first_token_at = time.perf_counter()
        with self.stats.lock:
            self._note_first_token(req)
            self.stats.prefill_rows += 1
        observe_stage(
            "llm_ttft", (req.first_token_at - req.submitted_at) * 1000.0
        )
        self._first_token(slot_idx, req, tok_host)

    def _admit_hit(
        self, req: Request, slot_idx: int, common: int, *, shared: bool
    ) -> Optional[Callable[[], None]]:
        """Admit a prefix-cache hit into ``slot_idx`` — the slot's rows
        already hold the first ``common`` tokens' KV (a parked session
        turn taken over, or a freshly grafted shared segment).  Prefills
        only the suffix: directly when it is small, via chunked warming
        when it exceeds ``prefill_chunk_tokens`` (turn-2 / shared-hit
        TTFT scales with the new text, not the whole context).

        Returns the finalize callable for the pipelined tick (None when
        the slot enters warming — its first token comes from the final
        chunk in a later tick)."""
        plen = len(req.token_ids)
        common = min(common, plen - 1, self._admit_limit - 2)
        if self._snapshots is not None:
            common = self._snapshots.deepest(req.token_ids, common)
        self._note_claim([req])
        with self.stats.lock:
            self.stats.queued -= 1
            if shared:
                self.stats.shared_prefix_hits += 1
            else:
                self.stats.prefix_hits += 1
            self.stats.prefix_tokens_reused += common
        self._unpark(slot_idx)  # consumed: off the index, cached cleared
        self._restore_boundary(req, slot_idx, common)
        if (
            self.prefill_chunk_tokens
            and plen - common > self.prefill_chunk_tokens
        ):
            self._claim_warm(req, slot_idx, common)
            fin, _ = self._advance_warm(slot_idx)
            return fin
        t = self._suffix_dispatch(req, slot_idx, common)
        return lambda: self._suffix_finalize(*t)

    def _graft_into(self, src: int, dst: int, common: int) -> None:
        """Copy the shared segment's first ``common`` rows from slot
        ``src`` into slot ``dst`` (bucketed; over-copy is harmless, see
        ``_graft_prefix``).
        The source stays parked and indexed — serving one cached
        prefill to many requests is the point."""
        n = min(
            bucket_size(common, minimum=16, dense=True), self.max_len
        )
        self._clock.enter("dispatch", program="_graft_prefix", rows=n)
        src_dev, dst_dev = self._h2d(np.int32(src), np.int32(dst))
        self._clock.stage("call")
        self._cache = self._graft_prefix(self._cache, src_dev, dst_dev, n)
        # The graft returns the state alone, which the next program takes
        # (donated): the clock drops a sentinel that has gone that way.
        self._clock.dispatched(jax.tree_util.tree_leaves(self._cache)[0])
        self._clock.enter("plan")
        self._prefix_index.touch(src)

    def _claim_warm(self, req: Request, slot_idx: int, start: int) -> None:
        """Claim a slot for chunked prefill: KV for ``start`` prompt
        tokens is already in place; the rest arrives one chunk per tick
        via :meth:`_advance_warm`."""
        slot = self._slots[slot_idx]
        slot.request = req
        slot.length = len(req.token_ids)
        slot.emitted = 0
        slot.history = list(req.token_ids)
        slot.session_id = ""
        slot.cached = False
        slot.parked_at = 0.0
        slot.warm_pos = start

    def _claim_warm_cold(self, req: Request, slot_idx: int) -> None:
        """Cold chunked admission: claim + account (no cached prefix)."""
        self._note_claim([req])
        with self.stats.lock:
            self.stats.queued -= 1
        self._claim_warm(req, slot_idx, 0)

    def _advance_warm(
        self, slot_idx: int
    ) -> tuple[Optional[Callable[[], None]], int]:
        """Dispatch a prompt's first chunk for the slot its admission has
        just claimed (:meth:`_advance_warming` for one slot)."""
        (out,) = self._advance_warming([slot_idx])
        return out

    def _advance_warming(
        self, slot_idxs: Sequence[int], ahead: bool = False
    ) -> list[tuple[Optional[Callable[[], None]], int]]:
        """Dispatch one prefill chunk for each warming slot of
        ``slot_idxs``; the chunks of several slots go out as one program
        where the model's chunk is a weight stream that their token rows
        can share (:meth:`_chunk_groups`).

        Intermediate chunks need no host sync at all — the sampled token
        future is dropped and the cache future flows on.  The FINAL chunk
        returns a finalize callable that fetches the prompt's first
        token; the pipelined tick runs it after the decode dispatch so
        the chunk rides the device stream ahead of the decode like every
        other admission.  Returns (finalize_or_None, chunk_tokens) a
        slot, in the device's order.

        ``ahead``: the tick calls this behind its decode dispatch, before
        it blocks on anything, for the chunks the NEXT tick's phase 1
        would send — their tokens were known when the last chunks went
        out, so the device runs them while the host emits and plans.  A
        slot keeps its chunk's size and finalizer (``ahead_tokens``,
        ``first_token``) and gets (None, 0) here; the next tick's phase-1
        call dispatches nothing for it and returns them, so the chunk is
        charged to that tick's budget and its first token joins where it
        would have.  Every counter is counted here, at the dispatch."""
        out, chunks = [], []
        for i in slot_idxs:
            nxt = self._next_chunk(i, ahead)
            (chunks if isinstance(nxt, _Chunk) else out).append(nxt)
        for group in self._chunk_groups(chunks):
            out.extend(self._send_chunks(group, ahead))
        return out

    def _next_chunk(self, slot_idx: int, ahead: bool):
        """A warming slot's part of this call: the ``_Chunk`` to dispatch
        for it, or what it gets with nothing dispatched, as
        (finalize_or_None, chunk_tokens)."""
        slot = self._slots[slot_idx]
        req = slot.request
        if req is None or (slot.warm_pos is None and not slot.ahead_tokens):
            return None, 0
        if req.id and self._is_cancelled(req.id):
            self._clock.enter("emit")
            self._finish(slot_idx, "cancelled")
            self._clock.enter("plan")
            return None, 0
        if slot.ahead_tokens:
            # This tick's chunk is on the device already.
            fin, n = slot.first_token, slot.ahead_tokens
            slot.first_token, slot.ahead_tokens = None, 0
            return fin, n
        pos = slot.warm_pos
        return _Chunk(
            slot_idx, pos, min(self.prefill_chunk_tokens, slot.length - pos)
        )

    def _chunk_window(self, need: int) -> int:
        """The attention window of a group's program whose widest row
        reaches ``need`` rows (the widest there is, if it pads past it)."""
        return next((w for w in self._chunk_windows if w >= need), self.max_len)

    def _compile_chunk_programs(self, most: int) -> None:
        """Build the family of chunk programs: ``_prefill_suffix_rows``
        for 1, 2, 4, ... up to ``most`` rows (a group between two sizes
        is padded) at windows that double from eight chunks up to
        ``max_len``: far coarser than ``_prefill_suffix``'s dense
        ``kv_bucket``s, since a row pays a wider window in its attention
        alone and every window is a program to build for each size.  Each
        is lowered against the shapes its dispatch will have and compiled
        now (the persistent cache serves a second run).  Every chunk,
        grouped or alone, is dispatched through these executables, so
        nothing the traffic does can compile one later: a lone chunk
        through ``_prefill_suffix`` would lean on a warm-up to have sent
        its position alone, which a warm-up whose chunks went out in
        groups has not."""
        most = min(most, self.max_batch)
        if most < 2:
            return
        sizes = [1 << k for k in range(most.bit_length())]
        s = self._chunk_bucket()
        windows = list(self.model.chunk_windows(self.prefill_chunk_tokens))
        t0 = time.perf_counter()
        for rows in sizes:
            ints = jax.ShapeDtypeStruct((rows,), jnp.int32)
            floats = jax.ShapeDtypeStruct((rows,), jnp.float32)
            for window in windows:
                self._chunk_programs[rows, window] = self._prefill_suffix_rows.lower(
                    self.params, self._cache,
                    jax.ShapeDtypeStruct((rows, s), jnp.int32), ints, ints, ints,
                    self._key, (floats, floats, ints), window,
                ).compile()
        self._chunk_rows, self._chunk_windows = sizes[-1], tuple(windows)
        logger.info(
            "chunk programs: %s rows x windows %s compiled in %.1f s",
            sizes, windows, time.perf_counter() - t0,
        )

    def _chunk_bucket(self) -> int:
        """Token columns of a group's program: a whole chunk's bucket (a
        prompt's shorter last chunk is padded to it)."""
        return min(
            bucket_size(self.prefill_chunk_tokens, minimum=16, dense=True),
            self.max_len,
        )

    def _chunk_groups(self, chunks: list[_Chunk]) -> list[list[_Chunk]]:
        """Partition one tick's chunks into programs: alone where the
        model's chunk shares nothing (``_chunk_rows`` 1), else in groups
        of at most ``_chunk_rows``, rows of alike reach together so that
        a short prompt seldom pays a long one's window."""
        most = self._chunk_rows
        chunks = sorted(chunks, key=lambda c: c.pos) if most > 1 else chunks
        return [chunks[i : i + most] for i in range(0, len(chunks), most)]

    def _send_chunks(
        self, group: list[_Chunk], ahead: bool
    ) -> list[tuple[Optional[Callable[[], None]], int]]:
        """Dispatch one program for ``group`` (of the family where the
        model has one, else the lone chunk's ``_prefill_suffix``) and book
        each of its rows."""
        if self._chunk_programs:
            tok = self._dispatch_chunk_rows(group)
        else:
            (lone,) = group
            tok = self._dispatch_chunk(lone)
        self._clock.enter("plan")
        self._tick_chunks += len(group)
        self._tick_chunk_programs += 1
        with self.stats.lock:
            self.stats.prefill_chunks += len(group)
            self.stats.prefill_chunks_ahead += len(group) * ahead
            self.stats.prefill_chunk_programs += 1
        if ahead:
            self._ahead_toks.append(tok)
        out = []
        for row, c in enumerate(group):
            slot = self._slots[c.slot_idx]
            fin = None
            if c.pos + c.n < slot.length:
                slot.warm_pos = c.pos + c.n
            else:
                # Final chunk: prefill complete — the slot joins decode in
                # the tick after the one its first token is fetched in.
                slot.warm_pos = None
                slot.unfetched = 1
                fin = functools.partial(
                    self._suffix_finalize, slot.request, c.slot_idx, tok, row
                )
            if ahead:
                slot.ahead_tokens, slot.first_token = c.n, fin
                out.append((None, 0))
            else:
                out.append((fin, c.n))
        return out

    def _dispatch_chunk(self, c: _Chunk):
        """One slot's chunk as a program of its own; returns the token
        future (1,)."""
        slot_idx, pos, n = c.slot_idx, c.pos, c.n
        slot = self._slots[slot_idx]
        s = min(bucket_size(n, minimum=16, dense=True), self.max_len)
        tokens = np.zeros((1, s), dtype=np.int32)
        tokens[0, :n] = slot.history[pos : pos + n]
        kv_bucket = bucket_size(pos + s, maximum=self.max_len, dense=True)
        self._prefill_suffix_begin(n, s, kv_bucket)
        return self._call_prefill_suffix(
            tokens, pos, n, slot_idx, slot.request.sampling, kv_bucket,
            boundary=True,
        )

    def _call_prefill_suffix(
        self, tokens: np.ndarray, start: int, n: int, slot_idx: int,
        sp: SamplingParams, kv_bucket: int, boundary: bool = False,
    ):
        """The rest of a ``_prefill_suffix`` dispatch, from its arrays to
        ``dispatched``: ``n`` of ``tokens`` (1, s) into slot ``slot_idx``
        from position ``start``, and with ``boundary`` the slot's snapshot
        where the chunk ends on one.  Returns the token future (1,)."""
        tokens_dev, *where = self._h2d(
            tokens, np.int32(start), np.int32(n), np.int32(slot_idx)
        )
        sampling_dev = tuple(self._h2d(
            np.float32([sp.temperature]), np.float32([sp.top_p]),
            np.int32([sp.top_k]),
        ))
        key = self._next_key()
        self._clock.stage("call")
        self._cache, tok, aux = self._prefill_suffix(
            self.params, self._cache, tokens_dev, *where, key, sampling_dev,
            kv_bucket,
        )
        last = tok
        if boundary:
            snapshot = self._save_boundary(
                self._slots[slot_idx], slot_idx, start + n
            )
            last = tok if snapshot is None else snapshot
        self._clock.dispatched(last)
        self._note_aux(aux)
        return tok

    def _dispatch_chunk_rows(self, group: list[_Chunk]):
        """The chunks of one or several slots as one program of the family
        (:meth:`_compile_chunk_programs`): the group padded to the next
        size with rows of no tokens, which write to no slot, over the
        window its widest row needs.  Returns the token future, one
        entry a row."""
        rows = bucket_size(len(group), minimum=1)
        s = self._chunk_bucket()
        tokens = np.zeros((rows, s), np.int32)
        start, lens, slots = (np.zeros((rows,), np.int32) for _ in range(3))
        temp, top_p = np.zeros((rows,), np.float32), np.ones((rows,), np.float32)
        top_k = np.zeros((rows,), np.int32)
        for r, c in enumerate(group):
            slot = self._slots[c.slot_idx]
            tokens[r, : c.n] = slot.history[c.pos : c.pos + c.n]
            start[r], lens[r], slots[r] = c.pos, c.n, c.slot_idx
            sp = slot.request.sampling
            temp[r], top_p[r], top_k[r] = sp.temperature, sp.top_p, sp.top_k
        window = self._chunk_window(max(c.pos for c in group) + s)
        self._prefill_suffix_begin(
            int(lens.sum()), s, window, rows=rows, program="_prefill_suffix_rows"
        )
        *rows_dev, temp_dev, top_p_dev, top_k_dev = self._h2d(
            tokens, start, lens, slots, temp, top_p, top_k
        )
        key = self._next_key()
        self._clock.stage("call")
        self._cache, tok, aux = self._chunk_programs[rows, window](
            self.params, self._cache, *rows_dev, key,
            (temp_dev, top_p_dev, top_k_dev),
        )
        last = tok
        for c in group:
            snapshot = self._save_boundary(
                self._slots[c.slot_idx], c.slot_idx, c.pos + c.n
            )
            last = last if snapshot is None else snapshot
        self._clock.dispatched(last)
        self._note_aux(aux)
        return tok

    def _first_token(self, slot_idx: int, req: Request, tid: int) -> None:
        """A prompt's first token has been fetched: it is on the device
        no longer.  ``req`` still holds the slot (no tick ends a request
        between a prefill's dispatch and its fetch but by failing)."""
        slot = self._slots[slot_idx]
        if slot.request is req:
            slot.unfetched -= 1
            self._handle_token(slot_idx, tid)

    def _poll_lane(self, step: int, lane: int, steps: int) -> None:
        """The clock's poll point of an emit loop over ``steps`` rows of
        tokens, lanes inside: asked once a row and once a lane (not once
        a token), spread evenly over the loop."""
        if lane == 0 or lane % steps == step:
            self._clock.poll()

    def _handle_token(self, slot_idx: int, tid: int) -> None:
        """Process one sampled token for a slot; may finish the slot."""
        slot = self._slots[slot_idx]
        req = slot.request
        if req is None:
            return
        if req.id and self._is_cancelled(req.id):
            self._finish(slot_idx, "cancelled")
            return
        # This token is the slot's next decode input.
        self._cur_tok[slot_idx] = tid
        if req.eos_id is not None and tid == req.eos_id and req.sampling.stop_on_eos:
            self._finish(slot_idx, "stop")
            return
        try:
            req.on_token(tid)
        except Exception:
            logger.exception("on_token callback failed; cancelling request")
            self._finish(slot_idx, "error")
            return
        slot.emitted += 1
        slot.history.append(tid)
        # Deferred stats: one lock acquisition per decode chunk instead of
        # per token (GIL makes the bare increment safe; _flush_tokens
        # publishes).  At 320 slots x 16-step chunks the per-token lock
        # was a measurable slice of the serving gap.
        self._tok_count += 1
        self._tick_tokens += 1
        if slot.emitted >= req.sampling.max_tokens:
            self._finish(slot_idx, "length")
        elif slot.length + slot.emitted >= self.max_len:
            self._finish(slot_idx, "length")

    def _loop(self) -> None:
        logger.info(
            "scheduler started: %d slots, chunk %d",
            self.max_batch,
            self.decode_chunk_size,
        )
        clock = self._clock
        clock.start("plan")
        with EXECUTABLES.asking("tick", self._asked, self._note_executable):
            while self._running:
                self._tick_no += 1
                # One step on the tick thread's line of the profiler's
                # host plane; the phase annotations nest inside it.
                with jax.profiler.StepTraceAnnotation(
                    "tick", step_num=self._tick_no
                ):
                    clock.enter("plan")
                    self._run_tick()
                    clock.end_span()
        clock.stop()
        logger.info("scheduler stopped")

    def _asked(self) -> dict:
        """What the executable record notes of one this thread asked for:
        the tick, its phase, the facts of the running dispatch and, in a
        pool, the replica."""
        asked = {
            "tick": self._tick_no,
            "phase": self.stats.tick_phase,
            **self._clock.facts,
        }
        if self.replica_index is not None:
            asked["replica"] = self.replica_index
        return asked

    def _note_executable(self, entry: dict) -> None:
        """The tick thread asked JAX for an executable (``entry`` of
        ``utils.jax_runtime.EXECUTABLES``): count it, and mark the tick."""
        self._tick_executables += 1
        stats = self.stats
        with stats.lock:
            stats.executables_requested += 1
            stats.executables_hit += entry["cache"] == "hit"
            stats.executables_missed += entry["cache"] == "miss"
            stats.executable_trace_s += entry["trace_s"]
            stats.executable_lower_s += entry["lower_s"]
            stats.executable_backend_s += entry["backend_s"]

    def _run_tick(self) -> None:
        """One pass of the tick loop: the tick, recovery if it raised,
        telemetry, and the tick's record if it touched the device."""
        clock = self._clock
        tick_t0 = time.perf_counter()
        wall_t0 = time.time()
        before = clock.sums()
        # Gray-failure chaos hook: `replica:latency=ms,index=i`
        # slows exactly this scheduler's ticks.  Inside the timed
        # region so the injected latency lands in tick_ms and the
        # brownout scorer can see the straggler it creates.
        inject_replica(self.replica_index)
        try:
            self._tick()
        except Exception:
            clock.enter("emit")
            # A failing request must not take the serving loop down:
            # fail every in-flight request, keep serving new ones.
            logger.exception("scheduler tick failed; failing active slots")
            # Every slot with a live request — warming (mid chunked
            # prefill) included: a warming slot left behind would hold
            # its slot forever with no tick ever advancing it.
            for i, s in enumerate(self._slots):
                if s.request is not None:
                    self._finish(i, "error")
            # A fault mid-step can leave the donated cache deleted;
            # reallocate so the next tick starts from clean buffers.
            # Parked prefix caches died with the old buffers — unpark
            # them all, or the next prefix hit would suffix-prefill on
            # zeroed KV and stream silently wrong tokens.
            for i, s in enumerate(self._slots):
                if s.cached:
                    self._unpark(i)
            self._cache = self.model.init_state(
                self.max_batch, self.max_len
            )
            self._aux_pending.clear()
            self._ahead_toks.clear()
            # A decode chunk sent ahead dies with the buffers it wrote,
            # and no row's token is on the device any more.
            self._flight = None
            self._carried = self._no_tokens()
            self._carried_len = self._no_lengths()
            if self._snapshots is not None:
                self._snapshots.clear()
                with self.stats.lock:
                    self.stats.state_snapshot_bytes = 0
        clock.enter("telemetry")
        self._note_tick((time.perf_counter() - tick_t0) * 1000.0)
        if self._tick_busy:
            spent = tuple(
                b - a for a, b in zip(before, clock.sums())
            )
            self._ticks.append(
                (self._tick_no, tick_t0, wall_t0) + spent + (
                    self._tick_chunks, self._tick_admitted,
                    self._tick_decoded, self._tick_kv_bucket,
                    self._tick_tokens, self.stats.queued,
                    self._tick_ahead, self._tick_chunk_programs,
                    self._tick_executables,
                )
            )

    # Snapshot counters mirrored into the TSDB as per-interval deltas, so
    # /debug/timeseries shows their history (rates at read time) instead
    # of only the monotonic totals /metrics scrapes.
    _TSDB_COUNTER_KEYS = (
        "requests_total",
        "tokens_total",
        "rejected_total",
        "prefix_hits",
        "shared_prefix_hits",
        "prefill_chunks",
        "spec_accepted",
    )
    # Snapshot keys whose TSDB series name predates the generic
    # ``engine.<key>`` convention (dashboards already reference it).
    _TSDB_SERIES_NAMES = {"spec_accepted": "engine.spec.accepted"}

    def _note_tick(self, dt_ms: float) -> None:
        """Feed fleet telemetry from the tick loop.

        Per tick: one histogram observe + one TSDB pending append (idle
        ticks are throttled by the 50 ms queue wait in ``_tick``).  The
        snapshot-derived gauges and counter deltas run at most every
        ``_tsdb_feed_interval_s`` — ``Stats.snapshot`` takes the stats
        lock, which must not ride the per-tick hot path."""
        try:
            from generativeaiexamples_tpu.obs.metrics import observe_engine_tick
            from generativeaiexamples_tpu.obs.tsdb import get_tsdb

            observe_engine_tick(dt_ms)
            stats = self.stats
            stats.tick_ms_ewma += 0.1 * (dt_ms - stats.tick_ms_ewma)
            # Token-normalized tick time: scale the wall time back to a
            # one-chunk-per-lane cost model when accepted drafts emitted
            # more than the baseline chunk would have.  Every downstream
            # consumer of "tick latency" (autoscaler tick_high_ms, the
            # pool's brownout scorer, 429 Retry-After) was calibrated
            # against that model; feeding them the raw wall time of a
            # tick that emitted 2x the tokens reads as congestion when
            # the engine is 2x FASTER per token.  A tick without drafts
            # emits at most the baseline, so norm == raw there.
            emitted = self._tick_tokens
            baseline = self._tick_decoded * self.decode_chunk_size
            norm_ms = dt_ms
            if emitted > baseline > 0:
                norm_ms = dt_ms * baseline / emitted
            stats.tick_ms_norm_ewma += 0.1 * (
                norm_ms - stats.tick_ms_norm_ewma
            )
            db = get_tsdb()
            db.record("engine.tick_ms", norm_ms)
            now = time.time()
            if now - self._last_tsdb_feed < self._tsdb_feed_interval_s:
                return
            self._last_tsdb_feed = now
            snap = self.stats.snapshot()
            db.record("engine.queued", snap["queued"])
            db.record("engine.active_slots", snap["active_slots"])
            # Parked = free slots still holding a reusable prefix cache.
            parked = sum(
                1
                for s in self._slots
                if s.cached and s.request is None
            )
            db.record("engine.parked_slots", parked)
            prev = self._tsdb_prev
            for key in self._TSDB_COUNTER_KEYS:
                value = snap.get(key, 0)
                delta = value - prev.get(key, 0)
                prev[key] = value
                if delta > 0:
                    name = self._TSDB_SERIES_NAMES.get(key, f"engine.{key}")
                    db.record(name, delta, kind="counter")
        except Exception:  # telemetry must never take the loop down
            logger.exception("tick telemetry feed failed")

    # Per-batch admission cap: bounds the prefill-bucket compile set and
    # the largest prefill activation transient.  64 rows keeps admission
    # prefill near its MXU-efficient regime under saturation (smaller
    # batches pay the per-dispatch floor once per handful of requests).
    # Must be a power of two: _admit_dispatch buckets the batch to the next
    # power of two, so a 96-cap pads 65-96 requests to 128 rows and
    # wastes a third of the prefill FLOPs (measured as a ~10% serving
    # throughput regression).
    ADMIT_CAP = 64

    # Per-TICK admission cap in prompt TOKENS: prefill cost scales with
    # total tokens, so a burst of long RAG prompts (e.g. 64 x 1536) would
    # otherwise prefill for multiple seconds in one tick while every
    # RUNNING request's decode stalls.  Bounding the tick's admission
    # tokens interleaves prefill and decode chunks — waiting requests
    # still make progress every tick, and running requests' inter-token
    # latency stays bounded by (budget-sized prefill + one chunk).
    # 32k tokens ~ one 64 x 512 admission batch.
    ADMIT_TOKEN_BUDGET = 32768

    def _tick(self) -> None:
        with self.stats.lock:
            self.stats.tick_count += 1
        progressed = False
        self._tick_tokens = 0
        self._tick_decoded = 0
        self._tick_chunks = 0
        self._tick_chunk_programs = 0
        self._tick_admitted = 0
        self._tick_kv_bucket = 0
        self._tick_ahead = 0
        self._tick_executables = 0
        self._tick_busy = False
        # Every decode path runs the tick PIPELINED: admission
        # prefill+graft batches are dispatched first (async), the decode
        # chunk for the previously-active slots is dispatched behind them
        # on the device stream, and only then does the host block.  Two
        # wins over the synchronous tick: per-dispatch latency overlaps
        # device compute instead of landing serially once per phase, and
        # — because the
        # prefill executes FIRST on the stream — the admission batch's
        # first tokens are fetchable ~RTT+prefill into the tick, not
        # after the decode chunk, which removes the decode chunk from
        # every request's TTFT critical path.
        #
        # Newly admitted slots join decode at the NEXT tick (this tick's
        # chunk keeps the pre-admission active snapshot: their host-side
        # _cur_tok is still a device future when the chunk is dispatched);
        # with a full house a cold batch's rows join the chunk dispatched
        # behind them, reading that future on the device (``_carried``).
        # The chunk's shape-stable garbage writes into those lanes are
        # harmless BECAUSE admissions are length-bounded: non-snapshot
        # lanes pin to max_len - 1, whose append-buffer flush clips into
        # [flush_clip_start, max_len) — _clip_prompt keeps every
        # admitted prompt's KV strictly below that zone (on the XLA
        # scatter path the garbage lands at max_len - 1 only, which the
        # row's own decode rewrites before its mask exposes it).
        decode_active: list[int] = self._active()
        admits: list[Callable[[], None]] = []

        def settle(fin: Optional[Callable[[], None]]) -> None:
            """Queue a finalize behind the decode dispatch."""
            if fin is not None:
                admits.append(fin)

        budget = self.ADMIT_TOKEN_BUDGET
        # Phase 1 — warming slots advance exactly one prefill chunk each,
        # BEFORE new admissions: they already own slots, and their
        # per-tick chunk is what bounds running lanes' latency to one
        # prefill chunk + one decode chunk during a long cold admission.
        # A slot whose chunk the last tick sent ahead (below) is only
        # booked here: its chunk leads this tick's programs on the device
        # as it would have, dispatched before the host's gap, not after.
        warming = self._warming()
        progressed = bool(warming)
        for fin, n in self._advance_warming(warming):
            budget -= n
            settle(fin)
        # The admissions below are read where they used to be: phase 1's
        # dispatches took 20-30 ms between the last tick's emit and this
        # poll, long enough for a client that sends its next request when
        # its reply ends to be in the queue; with nothing left to
        # dispatch the poll would come at once and that request would
        # wait a whole tick.  So where several chunk programs went ahead,
        # wait for the first: the device has the others to run meanwhile.
        # (Chunks that share one program leave no first to wait for: the
        # poll then comes at once, as it does behind a single chunk.)
        # (Not with a decode chunk in flight from the last tick: the house
        # was full then, and its tokens are the next thing to fetch.)
        sent, self._ahead_toks = self._ahead_toks, []
        if len(sent) > 1 and self._flight is None:
            self._clock.enter("wait_device")
            sent[0].block_until_ready()
            self._clock.enter("plan")
        # Phase 2 — admit pending requests into free slots (batched
        # prefill phase).  Keep draining in ADMIT_CAP-sized prefill
        # batches until slots, the queue, or this tick's token budget run
        # out: admission throughput must scale with backlog, not with
        # tick frequency, or it becomes the serving ceiling.
        free = self._free_slots()
        stalled = False
        while not stalled and budget > 0:
            batch: list[tuple[Request, int]] = []
            batch_tokens = 0
            while len(batch) < self.ADMIT_CAP:
                req = self._next_pending()
                if req is None:
                    stalled = True
                    break
                if self._drop_if_cancelled(req):
                    continue
                self._clip_prompt(req)
                plen = len(req.token_ids)
                # Budget accounting charges what prefill will actually
                # COST THIS TICK: the full prompt for cold monolithic
                # admissions, only the suffix for prefix-cache hits, and
                # only the first chunk for chunked admissions (later
                # chunks bill their own ticks in phase 1).
                parked, common = self._find_parked(req)
                shared_src, shared_common = (-1, 0)
                if parked < 0:
                    shared_src, shared_common = self._find_shared(req)
                reuse = common if parked >= 0 else shared_common
                cost = plen - reuse
                if self.prefill_chunk_tokens and cost > self.prefill_chunk_tokens:
                    cost = self.prefill_chunk_tokens
                if batch_tokens + cost > budget and (
                    batch or budget < self.ADMIT_TOKEN_BUDGET
                ):
                    # Over this TICK's budget: keep FIFO order and resume
                    # after the next decode chunk.  The exemption — a
                    # request admitted alone against an untouched full
                    # budget — exists because an over-budget prompt must
                    # run sometime; a merely over-REMAINDER one must not.
                    self._backlog.appendleft(req)
                    budget = 0
                    break
                if parked >= 0:
                    # Session hit: take over the conversation's own
                    # parked slot.
                    settle(
                        self._admit_hit(req, parked, common, shared=False)
                    )
                    budget -= cost
                    progressed = True
                    continue
                if shared_src >= 0:
                    # Shared-prefix hit: graft the segment's rows into a
                    # spare slot so the segment keeps serving other
                    # requests.  The source is pinned so the one-slot
                    # reclaim can never evict the rows it is about to
                    # copy.
                    self._prefix_index.pin(shared_src)
                    try:
                        if not free:
                            free = self._reclaim_parked(1)
                    finally:
                        self._prefix_index.unpin(shared_src)
                    if free:
                        dst = free.pop()
                        self._graft_into(shared_src, dst, shared_common)
                        settle(
                            self._admit_hit(
                                req, dst, shared_common, shared=True
                            )
                        )
                    else:
                        # No spare slot anywhere: consume the segment
                        # itself (destructive takeover, like a session
                        # hit) — the TTFT win beats keeping it parked.
                        settle(
                            self._admit_hit(
                                req, shared_src, shared_common, shared=True
                            )
                        )
                    budget -= cost
                    progressed = True
                    continue
                if not free:
                    # Evict exactly one parked prefix cache per request
                    # that actually needs a slot — never in bulk: every
                    # eviction costs a cached prefix its KV.
                    free = self._reclaim_parked(1)
                    if not free:
                        # Back to the FRONT: admission stays FIFO.
                        self._backlog.appendleft(req)
                        stalled = True
                        break
                chunked_cold = bool(
                    self.prefill_chunk_tokens
                    and plen > self.prefill_chunk_tokens
                )
                if chunked_cold:
                    # Cold chunked admission: claim the slot and dispatch
                    # the first chunk; the rest interleaves with decode
                    # over the following ticks.
                    slot_idx = free.pop()
                    self._claim_warm_cold(req, slot_idx)
                    fin, _ = self._advance_warm(slot_idx)
                    settle(fin)
                    budget -= cost
                    progressed = True
                    continue
                batch.append((req, free.pop()))
                batch_tokens += plen
            if not batch:
                break
            batch_reqs = [r for r, _ in batch]
            batch_slots = [i for _, i in batch]
            admits.extend(self._admit_cold(batch_reqs, batch_slots))
            budget -= batch_tokens
            progressed = True

        # Published occupancy includes this tick's admissions (``/metrics``
        # and the benchmark's window log read it) — the DECODE snapshot
        # stays pre-admission.
        with self.stats.lock:
            self.stats.active_slots = len(self._active())
        # A full house decodes without a pause: the chunk the last tick
        # sent ahead is on the device, and the next goes out behind it
        # before the host blocks on anything, with its rows' input tokens
        # read where the chunk in flight leaves them.
        ahead = self._goes_ahead()
        flight, self._flight = self._flight, None
        in_flight = flight is not None
        decode_pending = None
        if in_flight:
            pending, self._tick_kv_bucket = flight
            finalize = functools.partial(self._decode_finalize, ahead=True)
            decode_pending, lanes = (finalize, pending), pending[1]
        else:
            lanes = self._decode_lanes() if ahead else decode_active
            if lanes:
                decode_pending = (
                    self._decode_finalize, self._decode_dispatch(lanes)
                )
        if decode_pending is not None:
            progressed = True
            # The tick's record describes the chunk it fetches: its
            # lanes, its window, its tokens, whether it had gone ahead.
            self._tick_decoded, self._tick_ahead = len(lanes), int(in_flight)
            fetched_bucket = self._tick_kv_bucket
            lanes = self._decode_lanes() if ahead else None
            if lanes:
                pending = self._decode_dispatch(lanes)
                self._flight = pending, self._tick_kv_bucket
                self._tick_kv_bucket = fetched_bucket
            # The next tick's phase 1, now: a continuing slot's next
            # chunk depends on nothing the finalizers below fetch, so it
            # goes out behind the decode chunk and the device has work
            # while the host emits this tick's tokens, books the tick and
            # plans the next.  Same programs in the same order.
            self._advance_warming(self._warming(), ahead=True)
        # Fetches follow the device's order: a chunk that was in flight
        # when this tick began ran before this tick's admissions.
        fetch = []
        if decode_pending is not None:
            finalize, pending = decode_pending
            fetch = [lambda: finalize(*pending)]
        for fin in fetch + admits if in_flight else admits + fetch:
            fin()
        if not progressed:
            # Idle: block briefly on the queue (backlogged requests first).
            # This path deliberately bypasses ADMIT_TOKEN_BUDGET — it only
            # runs when nothing is active, so there is no running request
            # whose latency the budget would protect.
            req = self._next_pending()
            if req is None:
                self._clock.enter("idle")
                try:
                    req = self._pending.get(timeout=0.05)
                except queue.Empty:
                    return
                finally:
                    self._clock.enter("plan")
            if self._drop_if_cancelled(req):
                return
            if self._admit_request_now(req):
                progressed = True
            else:
                # Every slot parked/busy and none reclaimable this tick:
                # keep the request waiting at the front, not dropped.
                self._backlog.appendleft(req)
        if progressed:
            self._tick_busy = True
            with self.stats.lock:
                self.stats.busy_ticks += 1

    def _admit_request_now(self, req: Request) -> bool:
        """Idle-path admission: route one request through the same
        decision tree as the busy tick (session hit, shared-prefix graft,
        chunked warm claim, ``_admit_cold`` of one), finalizing synchronously.
        Returns False when no slot could be claimed."""
        self._clip_prompt(req)
        parked, common = self._find_parked(req)
        if parked >= 0:
            fin = self._admit_hit(req, parked, common, shared=False)
            if fin is not None:
                fin()
            return True
        shared_src, shared_common = self._find_shared(req)
        if shared_src >= 0:
            self._prefix_index.pin(shared_src)
            try:
                free = self._free_slots() or self._reclaim_parked(1)
            finally:
                self._prefix_index.unpin(shared_src)
            if free:
                dst = free[0]
                self._graft_into(shared_src, dst, shared_common)
                fin = self._admit_hit(req, dst, shared_common, shared=True)
            else:
                fin = self._admit_hit(
                    req, shared_src, shared_common, shared=True
                )
            if fin is not None:
                fin()
            return True
        free = self._free_slots() or self._reclaim_parked(1)
        if not free:
            return False
        if (
            self.prefill_chunk_tokens
            and len(req.token_ids) > self.prefill_chunk_tokens
        ):
            self._claim_warm_cold(req, free[0])
            fin, _ = self._advance_warm(free[0])
            if fin is not None:
                fin()
            return True
        for fin in self._admit_cold([req], [free[0]]):
            fin()
        return True

    def _goes_ahead(self) -> bool:
        """Whether this tick dispatches its decode chunk AND the next
        before it fetches anything: after its admissions every slot holds
        a live request, decoding or warming.  While a slot is free the
        next arrival's prefill should lead the device's queue, not wait
        behind a decode chunk; with a full house nothing can be admitted
        before a row ends anyway.  (A chunk that verifies the model's own
        draft leaves its rows' lengths on the device beside their tokens,
        ``_carried_len``, and goes ahead as a plain one.)"""
        return all(s.request is not None for s in self._slots)

    def _decode_lanes(self) -> list[int]:
        """A full house's rows for the next decode chunk: every row whose
        newest token the host has (as ``_active``'s) or the device holds
        for it (``_Slot.on_device``: the chunk in flight computes it, or
        this tick's admission landed it), left out if the tokens in
        flight already bring it to its end: no lane computes a token
        that the host knows will be thrown away.  A row that may stop on
        EOS or be cancelled is taken to go on (``_decode_finalize``), and
        so is one that the drafts kept in the chunk in flight may have
        brought to its end: ``unfetched`` counts one token a step."""
        lanes = []
        for i, s in enumerate(self._slots):
            if s.request is None or s.warm_pos is not None:
                continue
            if s.unfetched and not s.on_device:
                continue
            done = s.emitted + s.unfetched
            if (
                done < s.request.sampling.max_tokens
                and s.length + done < self.max_len
            ):
                lanes.append(i)
        return lanes

    def _lane_state(self) -> tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray, int]:
        """Per-slot decode-chunk inputs: (lengths, temp, top_p, top_k,
        max_active_length).

        Next write position per slot: the prompt plus all emitted tokens
        except the latest one, which is the decode input and gets written
        by the first scan step of this chunk.
        Inactive slots still get garbage K/V written by the shape-stable
        decode scan.  Parked slots — and warming slots whose chunked
        prefill is still building real KV — point at the last cache
        position: always safely overwritable (its flush clips into the
        tail garbage zone that _clip_prompt and the parking margin keep
        clear of live KV); position 0 would corrupt their prefixes.
        Plain empty slots keep 0 (they hold nothing), and the attention
        window is computed over ACTIVE lanes only, so parked/warming
        lanes' max_len-1 write position does not inflate every chunk's
        kv read window.
        """
        b = self.max_batch
        active_lengths = [
            s.length + s.emitted - 1
            for s in self._slots
            if s.request is not None and s.warm_pos is None
        ]
        lengths = np.array(
            [
                (s.length + s.emitted - 1)
                if s.request is not None and s.warm_pos is None
                else (
                    self.max_len - 1
                    if s.cached or s.request is not None
                    else 0
                )
                for s in self._slots
            ],
            dtype=np.int32,
        )
        temp = np.zeros((b,), dtype=np.float32)
        top_p = np.ones((b,), dtype=np.float32)
        top_k = np.zeros((b,), dtype=np.int32)
        for i, s in enumerate(self._slots):
            if s.request is not None:
                temp[i] = s.request.sampling.temperature
                top_p[i] = s.request.sampling.top_p
                top_k[i] = s.request.sampling.top_k
        return (
            lengths, temp, top_p, top_k,
            max(active_lengths) if active_lengths else 0,
        )

    def _emit_verified(self, toks: np.ndarray, n_emits: np.ndarray, mine: list) -> None:
        """Emit a chunk whose steps verified the model's own draft: step
        ``r`` gave row ``i`` its first ``n_emits[r, i]`` of ``toks[r, i]``
        (two where the stack agreed with the draft).  A row may end on
        the first of two (``max_tokens``, ``max_len``, EOS): its second,
        and its later steps, are dropped with it: a rejected draft never
        reaches ``_handle_token``, so it enters neither ``slot.history``
        nor what a finish parks.  Feeds the ``spec_*`` stats: one draft a
        greedy row a step."""
        rounds = accepted = tokens = 0
        for step, (row, counts) in enumerate(zip(toks, n_emits)):
            for k, (i, req) in enumerate(mine):
                self._poll_lane(step, k, len(toks))
                if self._slots[i].request is not req:
                    continue
                n = int(counts[i])
                drafted = req.sampling.temperature <= 0.0
                rounds += drafted
                accepted += drafted and n > 1
                for j in range(n):
                    self._handle_token(i, int(row[i, j]))
                    tokens += drafted
                    if self._slots[i].request is not req:
                        break
        with self.stats.lock:
            self.stats.spec_rounds += rounds
            self.stats.spec_proposed += rounds
            self.stats.spec_accepted += accepted
            self.stats.spec_tokens += tokens
            if rounds:
                self.stats.spec_acceptance_ewma += 0.2 * (
                    accepted / rounds - self.stats.spec_acceptance_ewma
                )

    def _decode_dispatch(
        self, active: Optional[list[int]] = None
    ) -> tuple:
        """Dispatch one plain decode chunk asynchronously; the host does
        not block until :meth:`_decode_finalize` fetches the tokens.

        ``active`` optionally pins the emission snapshot to a set taken
        BEFORE this tick's admissions (pipelined tick): rows admitted
        after that snapshot still hold a device-future first token, so
        this chunk must neither read their ``_cur_tok`` nor emit their
        lanes.  A full house's snapshot (``_decode_lanes``) also holds
        rows with tokens still on the device (``_Slot.unfetched``): each
        writes that many positions further on, and reads its input token
        in ``_carried`` instead of ``_cur_tok``."""
        lengths, temp, top_p, top_k, max_active = self._lane_state()
        pinned = active is not None
        if not pinned:
            active = self._active()
        # The rows that decode: the only ones whose cache attention
        # reads (decode_chunk's ``live``), and the only ones emitted.
        snap = np.zeros((self.max_batch,), dtype=bool)
        snap[active] = True
        carry = np.zeros((self.max_batch,), dtype=bool)
        carry_len = np.zeros((self.max_batch,), dtype=bool)
        for i in active:
            n = self._slots[i].unfetched
            if n:
                carry[i] = True
                lengths[i] += n
                # What a drafting model's chunk in flight may have added
                # to the fewest: the row's length is read on the device,
                # and the window covers the most it can be.
                adrift = self._slots[i].chunks_out and self.model.draft
                carry_len[i] = bool(adrift)
                most = int(lengths[i]) + (self.decode_chunk_size if adrift else 0)
                max_active = max(max_active, most)
        if pinned:
            # Lanes outside the emission snapshot (freshly admitted this
            # tick, emitted still 0) would garbage-write at length-1 —
            # INSIDE the prompt KV the graft just landed.  Pin their
            # write positions to the cache tail instead: any row that
            # eventually reaches those positions rewrites them with its
            # own K/V before its attention mask exposes them.
            lengths = np.where(snap, lengths, self.max_len - 1)
        # Attention window: smallest power-of-two bucket covering every
        # position this chunk can write for a LIVE sequence — per-step KV
        # reads then track the longest live sequence instead of always
        # paying max_len.  (Garbage writes by inactive lanes may land
        # beyond the window; writes are not gated by kv_bucket.)
        # (A step that verifies the model's own draft writes two.)
        kv_bucket = bucket_size(
            max_active + self._step_width * self.decode_chunk_size + 1,
            maximum=self.max_len,
        )
        self._tick_kv_bucket = kv_bucket
        self._clock.enter(
            "dispatch", program="decode_chunk", lanes=len(active),
            kv_bucket=kv_bucket,
        )
        lengths = np.minimum(lengths, self.max_len - 1)
        cur_dev, lengths_dev, live_dev, carry_dev, *sampling_dev = self._h2d(
            self._cur_tok, lengths, snap, carry, temp, top_p, top_k
        )
        carried_len = (
            (self._carried_len, *self._h2d(carry_len))
            if self.model.draft else ()
        )
        key = self._next_key()
        self._clock.stage("call")
        self._cache, toks, *aux = self._decode_chunk(
            self.params, self._cache, cur_dev, lengths_dev, key,
            *sampling_dev, self.decode_chunk_size, kv_bucket, live_dev,
            self._carried, carry_dev, *carried_len,
        )
        self._clock.dispatched(toks)
        if self.model.draft:
            # Tokens (steps, b, 2), how many of the two count, and
            # what the chunk leaves for the next: each row's newest
            # token (wherever its counts put it) and its length.
            n_emits, (self._carried, self._carried_len), *aux = aux
            toks = toks, n_emits
        self._note_aux(*aux)
        with self.stats.lock:
            self.stats.decode_kv_tokens_read += kv_tokens_read(
                lengths[snap], self.max_len, kv_bucket
            )
            self.stats.decode_kv_tokens_dense += (
                self.max_batch * kv_bucket
            )
            self.stats.decode_stack_passes += (
                self.decode_chunk_size * self._stack_passes
            )
        self._clock.enter("plan")
        # The chunk's last tokens stay where the next chunk can read them;
        # a row that sat this chunk out has nothing there any more.
        if not self.model.draft:
            self._carried = toks
        for i, s in enumerate(self._slots):
            s.on_device = bool(snap[i])
            if s.on_device:
                s.unfetched += self.decode_chunk_size
                s.chunks_out += 1
        lanes = [(i, self._slots[i].request) for i in active]
        return toks, lanes

    def _decode_finalize(self, toks, lanes: list, ahead: bool = False) -> None:
        """Fetch a dispatched decode chunk's tokens and emit them.

        ``lanes`` is the snapshot taken at dispatch, each slot with the
        request it held then: slots admitted after the dispatch
        (pipelined tick) were not decoded by this chunk, and a slot whose
        request ended while the chunk was in flight (it stopped on EOS or
        was cancelled in the chunk before: a full house dispatches a
        chunk before it has fetched the last) may hold another by now.
        Such a row's tokens are dropped and counted; what the chunk wrote
        for it lies beyond the history that was kept, in the slot's own
        rows, before any later admission's writes on the device.
        ``ahead``: the chunk was dispatched while the one before it was
        unfetched (``_tick`` says so of the chunk it kept in flight)."""
        self._clock.enter("wait_device")
        n_host = None
        if self.model.draft:
            toks, n_emits = toks
            n_host = np.asarray(n_emits)  # (chunk, b): 1 or 2 a live row
        toks_host = np.asarray(toks)  # (chunk, b), or (chunk, b, 2)
        self._clock.enter("emit")
        self._drain_aux()
        mine = []
        for i, req in lanes:
            slot = self._slots[i]
            if slot.request is req and req is not None:
                slot.unfetched -= self.decode_chunk_size
                slot.chunks_out -= 1
                mine.append((i, req))
        if n_host is None:
            for step, row in enumerate(toks_host):
                for k, (i, req) in enumerate(mine):
                    self._poll_lane(step, k, len(toks_host))
                    if self._slots[i].request is req:
                        self._handle_token(i, int(row[i]))
        else:
            self._emit_verified(toks_host, n_host, mine)
        self._flush_tokens()
        with self.stats.lock:
            self.stats.decode_chunks += 1
            self.stats.decode_chunks_ahead += ahead
            self.stats.decode_tokens_dropped += (
                (len(lanes) - len(mine)) * len(toks_host)
            )
