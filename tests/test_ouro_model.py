"""The looped llama-shaped stack (Ouro: ``LlamaConfig.ut_steps`` passes over
the same layers, sandwich norms, a K/V plane a (pass, layer)) against the
plain reference, ``models/ouro_reference.py``, at ``ouro-tiny``'s size: two
layers, four passes, 4 query heads on 2 key-value heads of 16, a vocabulary
of 512.  Seeded random weights; logits are compared, never sampled tokens.

Tolerances.  In float32 both sides run at the highest matmul precision
(conftest.py) and differ by the order of their sums: logits are O(0.5), the
program reads 6e-7 from the reference, and ``ATOL`` 1e-5 leaves 17x; every
part left out (the mutations below) moves a logit by 1e-2 or more.  With
int8 K/V a warm chunk and the decode steps read back rows of 127 levels a
head: 2.5e-3 is measured, ``ATOL_INT8`` 1.5e-2 leaves 6x and is still under
what a mutation moves.
"""

import dataclasses
import functools
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from generativeaiexamples_tpu.engine.decode import _flush_append_buffer, make_decode_chunk_fn
from generativeaiexamples_tpu.engine.serving_models import LlamaServing
from generativeaiexamples_tpu.models import llama
from generativeaiexamples_tpu.models import ouro_reference as ref

ATOL = 1e-5
ATOL_INT8 = 1.5e-2
CFG = llama.ouro_tiny(dtype="float32", kv_dtype="float32")
PLANES = CFG.n_layers * CFG.ut_steps
SLOTS, ROWS = 4, 64
COLD, WARM, STEPS = 24, 8, 12  # a cold batch's bucket, a warm chunk, decode steps
REPO = Path(__file__).resolve().parents[1]


def _seeded(cfg):
    """Seeded weights with gains that are not 1, so that a norm left out
    or taken twice is seen."""
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    key = jax.random.PRNGKey(1)
    for i, name in enumerate(n for n in sorted(params["layers"]) if n.endswith("norm")):
        gain = params["layers"][name]
        params["layers"][name] = gain + 0.3 * jax.random.normal(jax.random.fold_in(key, i), gain.shape)
    params["final_norm"] = params["final_norm"] + 0.3 * jax.random.normal(key, params["final_norm"].shape)
    return params


@pytest.fixture(scope="module")
def params():
    return _seeded(CFG)


@pytest.fixture(scope="module")
def tokens():
    return np.random.RandomState(0).randint(0, CFG.vocab_size, size=(2, COLD + WARM)).astype(np.int32)


def _cacheless(params, cfg, row):
    pos = jnp.arange(len(row), dtype=jnp.int32)[None]
    hidden, _ = llama.forward(params, cfg, jnp.asarray(row)[None], pos)
    return np.asarray(llama.logits(params, hidden)[0])


def _held(got, want, atol=ATOL):
    """THE comparison: every logit within ``atol`` of the reference's."""
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=atol, rtol=0)


# -- the cacheless path: training, scoring, the tests' oracle ------------------


@pytest.mark.parametrize(
    "ut_steps,sandwich", [(4, True), (2, True), (4, False), (1, True)],
    ids=["ouro", "two_passes", "loop_without_sandwich", "sandwich_without_loop"],
)
def test_the_cacheless_forward_is_the_reference(tokens, ut_steps, sandwich):
    cfg = dataclasses.replace(CFG, ut_steps=ut_steps, sandwich_norm=sandwich)
    params = _seeded(cfg)
    _held(_cacheless(params, cfg, tokens[0]), ref.all_logits(params, cfg, tokens[0]))


def _without_renorm(params, cfg, row):
    """The reference's own parts with the norm BETWEEN passes left out."""
    with jax.default_matmul_precision("highest"):
        x = ref.embed(params, row)
        for _ in range(cfg.ut_steps):
            for l in range(cfg.n_layers):
                x = ref.layer(params, cfg, x, l)
        return ref.head(params, ref.final_norm(params, cfg, x))


MUTATED_REFERENCES = {
    "one_pass_fewer": lambda p, cfg, row: ref.all_logits(
        p, dataclasses.replace(cfg, ut_steps=cfg.ut_steps - 1), row),
    "no_norm_between_passes": _without_renorm,
    "no_output_norm": lambda p, cfg, row: ref.all_logits(
        p, dataclasses.replace(cfg, sandwich_norm=False), row),
}


@pytest.mark.parametrize("mutation", sorted(MUTATED_REFERENCES))
def test_a_part_left_out_fails_the_comparison(params, tokens, mutation):
    got = _cacheless(params, CFG, tokens[0])
    wrong = np.asarray(MUTATED_REFERENCES[mutation](params, CFG, tokens[0]))
    with pytest.raises(AssertionError):
        _held(got, wrong)
    assert np.abs(got - wrong).max() > 1000 * ATOL


# -- through the cache: a cold batch, a warm chunk, decode steps ---------------


@functools.lru_cache(maxsize=None)
def _serving(kv_dtype: str):
    cfg = dataclasses.replace(CFG, kv_dtype=kv_dtype)
    serving = LlamaServing(cfg, None, ROWS)
    cold = jax.jit(serving.prefill_cold)
    graft = jax.jit(serving.graft_rows)
    warm = jax.jit(serving.prefill_row, static_argnums=(6,))

    @jax.jit
    def steps(params, cache, fed, lengths):
        """``decode_chunk``'s steps with the tokens given (``fed`` (n, b))
        and the logits handed back: the append buffer where the chunk has
        one (int8 K/V), one flush at the end."""
        if len(cache) == 2:
            out = []
            for i in range(fed.shape[0]):
                hidden, cache = llama.forward(
                    params, cfg, fed[i][:, None], (lengths + i)[:, None], cache, lengths + i + 1)
                out.append(llama.logits(params, hidden)[:, 0])
            return cache, jnp.stack(out)
        n, b = fed.shape
        ab = llama.init_append_buffer(cfg, b, n)
        out = []
        for i in range(n):
            hidden, _, ab = llama.forward(
                params, cfg, fed[i][:, None], (lengths + i)[:, None], cache, lengths,
                append_cache=(ab, i))
            out.append(llama.logits(params, hidden)[:, 0])
        return _flush_append_buffer(cache, ab, lengths, ROWS), jnp.stack(out)

    return cfg, serving, cold, graft, warm, steps, make_decode_chunk_fn(cfg, None, ROWS)


def _through_the_cache(params, tokens, kv_dtype, spoil=None):
    """Rows 0 and 1 of ``tokens`` in slots 3 and 0: ``COLD`` tokens as a
    cold batch, ``WARM`` as a warm chunk each, then ``STEPS`` greedy decode
    steps of ``decode_chunk`` and the same steps again with the logits
    kept.  Returns (logits (2, COLD + WARM + STEPS, V) at every position,
    the sequences (2, COLD + WARM + STEPS) they belong to).  ``spoil``
    changes the slots' state between the cold batch and the warm chunk."""
    cfg, serving, cold, graft, warm, steps, chunk = _serving(kv_dtype)
    slots = np.array([3, 0], np.int32)
    state = serving.init_state(SLOTS, ROWS)
    assert all(leaf.shape[0] == PLANES for leaf in state)
    toks = jnp.asarray(tokens)
    hidden, small, _ = cold(params, toks[:, :COLD], jnp.full((2,), COLD, jnp.int32))
    logits = [llama.logits(params, hidden)]
    state = graft(state, small, jnp.arange(2, dtype=jnp.int32), jnp.asarray(slots))
    if spoil is not None:
        state = spoil(state)
    chunks = []
    for row, slot in enumerate(slots):
        state, hidden, _ = warm(
            params, state, toks[row : row + 1, COLD:], jnp.int32(COLD), jnp.int32(WARM),
            jnp.int32(slot), ROWS)
        chunks.append(llama.logits(params, hidden))
    logits.append(jnp.concatenate(chunks))
    # The token each row decodes from is the argmax at its last position.
    first = np.zeros((SLOTS,), np.int32)
    first[slots] = np.asarray(jnp.argmax(logits[-1][:, -1], axis=-1))
    lengths = np.full((SLOTS,), ROWS - 1, np.int32)
    lengths[slots] = COLD + WARM
    live = np.zeros((SLOTS,), bool)
    live[slots] = True
    before = jax.tree.map(jnp.copy, state)
    zeros = jnp.zeros((SLOTS,), jnp.float32)
    _, out = chunk(params, state, jnp.asarray(first), jnp.asarray(lengths), jax.random.PRNGKey(0),
                   zeros, zeros + 1.0, jnp.zeros((SLOTS,), jnp.int32), STEPS, ROWS, jnp.asarray(live))
    out = np.asarray(out)  # (STEPS, SLOTS): step i's output is step i + 1's input
    fed = np.concatenate([first[None], out[:-1]])
    _, stepped = steps(params, before, jnp.asarray(fed), jnp.asarray(lengths))
    stepped = np.asarray(stepped)[:, slots]  # (STEPS, 2, V)
    # What ``decode_chunk`` emitted is the argmax of those logits.
    np.testing.assert_array_equal(stepped.argmax(-1), out[:, slots])
    logits.append(jnp.swapaxes(jnp.asarray(stepped), 0, 1))
    seqs = np.concatenate([tokens, fed[:, slots].T], axis=1)
    return np.asarray(jnp.concatenate(logits, axis=1)), seqs


@pytest.mark.parametrize("kv_dtype,atol", [("float32", ATOL), ("int8", ATOL_INT8)])
def test_a_cold_batch_a_warm_chunk_and_decode_steps_are_the_reference(
    params, tokens, kv_dtype, atol, monkeypatch
):
    """Every position's logits, through the cache (and, with int8 K/V, the
    append buffer and its flush), against the reference's full forward
    pass over the sequence the steps produced."""
    monkeypatch.setenv("GAIE_FORCE_APPEND_BUFFER", "1")
    got, seqs = _through_the_cache(params, tokens, kv_dtype)
    for row in range(2):
        _held(got[row], ref.all_logits(params, CFG, seqs[row]), atol)


def test_a_pass_that_reads_the_pass_befores_planes_fails_the_comparison(params, tokens):
    """Pass ``u``, layer ``l`` attends plane ``u * L + l`` alone: with the
    planes of a slot rolled by one pass after the cold batch, the warm
    chunk's pass ``u`` reads what pass ``u - 1`` wrote."""

    def roll(state):
        return tuple(jnp.roll(leaf, CFG.n_layers, axis=0) for leaf in state)

    got, seqs = _through_the_cache(params, tokens, "float32", spoil=roll)
    want = np.asarray(ref.all_logits(params, CFG, seqs[0]))
    _held(got[0][:COLD], want[:COLD])  # the cold batch ran before the roll
    with pytest.raises(AssertionError):
        _held(got[0][COLD:], want[COLD:])
    assert np.abs(got[0][COLD:] - want[COLD:]).max() > 1000 * ATOL


def test_a_prefix_graft_and_a_grouped_call_carry_every_plane(params, tokens):
    """``graft_prefix`` copies a slot's first rows in all 8 planes, and the
    warm chunks of two slots as one ``prefill_rows`` call write what each
    writes alone through ``prefill_row``."""
    cfg, serving, cold, graft, warm, _, _ = _serving("float32")
    toks = jnp.asarray(tokens)
    state = serving.init_state(SLOTS, ROWS)
    _, small, _ = cold(params, toks[:, :COLD], jnp.full((2,), COLD, jnp.int32))
    state = graft(state, small, jnp.arange(2, dtype=jnp.int32), jnp.asarray([3, 0], jnp.int32))
    grafted = jax.jit(serving.graft_prefix, static_argnums=(3,))(state, jnp.int32(3), jnp.int32(1), 16)
    for leaf in grafted:
        assert leaf.shape[0] == PLANES
        np.testing.assert_array_equal(np.asarray(leaf[:, :, 1, :16]), np.asarray(leaf[:, :, 3, :16]))
        assert all(np.abs(np.asarray(leaf[p, :, 1, :16])).max() > 0 for p in range(PLANES))
    alone = state
    for row, slot in enumerate((3, 0)):
        alone, _, _ = warm(params, alone, toks[row : row + 1, COLD:], jnp.int32(COLD),
                           jnp.int32(WARM), jnp.int32(slot), ROWS)
    start = jnp.full((2,), COLD, jnp.int32)
    grouped, hidden, _ = jax.jit(serving.prefill_rows, static_argnums=(6,))(
        params, state, toks[:, COLD:], start, jnp.full((2,), WARM, jnp.int32),
        jnp.asarray([3, 0], jnp.int32), ROWS)
    assert hidden.shape == (2, WARM, cfg.d_model)
    for a, g in zip(alone, grouped):
        np.testing.assert_allclose(np.asarray(a), np.asarray(g), atol=1e-6, rtol=0)


# -- one body, ut_steps trips; the plain models' programs as they were --------


def _inner(eqn):
    """The jaxprs an equation holds (a scan's body, a jit's, a branch)."""
    for value in eqn.params.values():
        for sub in value if isinstance(value, (list, tuple)) else [value]:
            inner = getattr(sub, "jaxpr", sub)
            inner = getattr(inner, "jaxpr", inner)
            if hasattr(inner, "eqns"):
                yield inner


def _scans(jaxpr, inside=()):
    """(trip count, the trip counts of the scans around it) of every scan."""
    found = []
    for eqn in jaxpr.eqns:
        here = inside
        if eqn.primitive.name == "scan":
            found.append((eqn.params["length"], inside))
            here = inside + (eqn.params["length"],)
        for inner in _inner(eqn):
            found += _scans(inner, here)
    return found


def _n_eqns(jaxpr) -> int:
    return sum(1 + sum(_n_eqns(inner) for inner in _inner(eqn)) for eqn in jaxpr.eqns)


def _modes(cfg, params):
    """``forward``'s three modes as (function, arguments)."""
    toks = jnp.asarray(np.random.RandomState(0).randint(0, 512, (2, 12)), jnp.int32)
    pos = jnp.broadcast_to(jnp.arange(12), (2, 12))
    cache = llama.init_kv_cache(cfg, 2, 32)
    n, n1 = jnp.array([12, 12]), jnp.array([13, 13])
    modes = {
        "cacheless": (lambda p, t: llama.forward(p, cfg, t, pos)[0], (params, toks)),
        "cold_batch": (lambda p, t, c: llama.forward(p, cfg, t, pos, c, n, cold_prefill=True),
                       (params, toks, cache)),
        "decode_step": (lambda p, t, c: llama.forward(p, cfg, t[:, :1], pos[:, :1] + 12, c, n1),
                        (params, toks, cache)),
    }
    if cfg.kv_dtype == "int8":
        ab = llama.init_append_buffer(cfg, 2, 4)
        modes["append_buffer"] = (
            lambda p, t, c, ab: llama.forward(p, cfg, t[:, :1], pos[:, :1] + 12, c, n,
                                              append_cache=(ab, 1)),
            (params, toks, cache, ab))
    return modes


@pytest.mark.parametrize("mode", ["cacheless", "cold_batch", "decode_step", "append_buffer"])
def test_the_loop_is_one_body_of_ut_steps_trips(mode, monkeypatch):
    """The scan over the layers stands ONCE in the program, inside one scan
    of ``ut_steps`` trips: not 8 (192 at the published depth) unrolled
    layers, not a scan a pass."""
    monkeypatch.setenv("GAIE_FORCE_APPEND_BUFFER", "1")
    cfg = dataclasses.replace(CFG, kv_dtype="int8", n_layers=3)
    fn, args = _modes(cfg, llama.init_params(cfg, jax.random.PRNGKey(0)))[mode]
    scans = _scans(jax.make_jaxpr(fn)(*args).jaxpr)
    assert [s for s in scans if s[0] == cfg.n_layers] == [(cfg.n_layers, (cfg.ut_steps,))]
    assert [s for s in scans if s[0] == cfg.ut_steps] == [(cfg.ut_steps, ())]


# What ``forward`` traced to and returned at the commit before the loop
# (fb5005c; cacheless, a cold batch, a decode step): equations of the
# jaxpr, and of the cacheless hidden states their sum, the sum of their
# magnitudes and three values.
BEFORE_THE_LOOP = {
    ("llama-tiny", "bfloat16"): ([118, 141, 172], 92.63371276855469, 1213.50634765625,
                                 [0.07083497941493988, -0.4921216070652008, 0.5895833969116211]),
    ("llama-tiny", "int8"): ([118, 189, 256], 92.63371276855469, 1213.50634765625,
                             [0.07083497941493988, -0.4921216070652008, 0.5895833969116211]),
    ("llama-moe-tiny", "bfloat16"): ([192, 215, 246], 143.03123474121094, 1221.654541015625,
                                     [0.5687502026557922, -0.2795359790325165, 1.1075236797332764]),
}


@pytest.mark.parametrize("preset,kv_dtype", sorted(BEFORE_THE_LOOP))
def test_a_stack_passed_once_is_the_program_it_was(preset, kv_dtype):
    """``ut_steps`` 1 without sandwich norms (Mistral's and Mixtral's
    configurations): the loop is a Python ``if`` at trace time, so the
    jaxprs have the size they had and no scan around the layers' scan, and
    the outputs are the parent commit's."""
    cfg = llama.PRESETS[preset](dtype="float32", kv_dtype=kv_dtype)
    assert cfg.ut_steps == 1 and not cfg.sandwich_norm and cfg.cache_planes == cfg.n_layers
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    assert "exit_gate" not in params and "attn_post_norm" not in params["layers"]
    modes = _modes(cfg, params)
    sizes, total, magnitude, values = BEFORE_THE_LOOP[preset, kv_dtype]
    jaxprs = [jax.make_jaxpr(modes[m][0])(*modes[m][1]).jaxpr for m in ("cacheless", "cold_batch", "decode_step")]
    assert [_n_eqns(j) for j in jaxprs] == sizes
    assert all(s == (cfg.n_layers, ()) for j in jaxprs for s in _scans(j) if s[0] == cfg.n_layers)
    hidden = np.asarray(modes["cacheless"][0](*modes["cacheless"][1]))
    np.testing.assert_allclose(
        [hidden.sum(), np.abs(hidden).sum(), *hidden[1, 5, :3]], [total, magnitude, *values], rtol=2e-6)


def test_the_lowered_programs_carry_the_loops_scopes(monkeypatch):
    monkeypatch.setenv("GAIE_FORCE_APPEND_BUFFER", "1")
    cfg = dataclasses.replace(CFG, kv_dtype="int8")
    fn, args = _modes(cfg, llama.init_params(cfg, jax.random.PRNGKey(0)))["append_buffer"]
    text = jax.jit(fn).lower(*args).as_text(debug_info=True)
    for scope in ("loop/pass", "loop/renorm", "layer/post_norm", "layer/attn", "kv_write"):
        assert scope in text, scope
    assert "/final_norm/" not in text  # the last trip's norm is the final one


def test_the_looped_stack_trains_through_its_passes(params, tokens):
    """The cacheless path under ``jax.grad`` with the layers rematerialised
    (``engine/training.py``'s call): every leaf the four passes read gets a
    gradient, the exit gate (unused at threshold 1) none."""

    def loss(p):
        toks = jnp.asarray(tokens[:, :16])
        pos = jnp.broadcast_to(jnp.arange(16), toks.shape)
        hidden, _ = llama.forward(p, CFG, toks, pos, remat=True)
        return jnp.mean(llama.logits(p, hidden) ** 2)

    grads = jax.grad(loss)(params)
    assert all(bool(jnp.isfinite(g).all()) for g in jax.tree.leaves(grads))
    for name in ("wq", "w_down", "attn_post_norm", "mlp_post_norm", "attn_norm"):
        assert float(jnp.abs(grads["layers"][name]).max()) > 0, name
    assert float(jnp.abs(grads["final_norm"]).max()) > 0
    assert float(jnp.abs(grads["exit_gate"]["w"]).max()) == 0


# -- the exit rule -------------------------------------------------------------


def test_the_exit_rule_at_the_published_threshold_and_below(params, tokens):
    """At 1 every position leaves after the last pass, which is what the
    served program computes (no gate); below 1 the reference's answer is
    another, which is why ``check_supported`` refuses it."""
    row = tokens[0]
    pdf = np.asarray(ref.exit_pdf(params, CFG, row))
    assert pdf.shape == (CFG.ut_steps, len(row)) and (pdf > 0).all()
    np.testing.assert_allclose(pdf.sum(0), 1.0, atol=1e-6)
    passes = ref.hidden_passes(params, CFG, row)
    lam = 1 / (1 + np.exp(-(np.asarray(passes) @ np.asarray(params["exit_gate"]["w"])
                            + float(params["exit_gate"]["b"]))))
    np.testing.assert_allclose(pdf[1], lam[1] * (1 - lam[0]), atol=1e-6)
    np.testing.assert_allclose(pdf[-1], np.prod(1 - lam[:-1], axis=0), atol=1e-6)
    assert (np.asarray(ref.exit_steps(pdf, 1.0)) == CFG.ut_steps - 1).all()
    at_one = np.asarray(ref.all_logits(params, CFG, row))
    _held(at_one, ref.head(params, passes[-1]))
    _held(_cacheless(params, CFG, row), at_one)
    early = np.asarray(ref.exit_steps(pdf, 0.5))
    assert (early < CFG.ut_steps - 1).any()
    below = np.asarray(ref.all_logits(params, CFG, row, threshold=0.5))
    assert np.abs(below - at_one).max() > 1000 * ATOL


# -- what is refused, and what is served --------------------------------------


def test_what_a_looped_stack_refuses_is_refused_with_its_sentence(params):
    from jax.sharding import Mesh

    from generativeaiexamples_tpu.parallel import pipeline

    LlamaServing(CFG, None, ROWS).check_supported()
    with pytest.raises(ValueError, match="early_exit_threshold 0.9 is not served.*never read as 1"):
        LlamaServing(dataclasses.replace(CFG, early_exit_threshold=0.9), None, ROWS).check_supported()
    mesh = Mesh(np.array(jax.devices()[:2]).reshape(2, 1), ("pipe", "data"))
    with pytest.raises(ValueError, match="not served as a pipeline"):
        LlamaServing(CFG, mesh, ROWS).check_supported()
    with pytest.raises(NotImplementedError, match="not served as a pipeline"):
        pipeline.pipeline_forward(
            params, CFG, jnp.zeros((2, 8), jnp.int32), jnp.zeros((2, 8), jnp.int32), mesh)
    # A stack passed once is served over that mesh, as before.
    LlamaServing(llama.llama_tiny(), mesh, ROWS).check_supported()


@pytest.fixture(scope="module")
def served(params):
    """What ``engine.server --model ouro-tiny`` builds, and the greedy
    stream of one prompt through it."""
    from generativeaiexamples_tpu.engine.scheduler import Scheduler
    from tests.test_scheduler import _collect

    prompt = [7, 8, 9, 7, 8, 9, 7, 8]
    sched = Scheduler(CFG, params, max_batch=2, max_len=128, decode_chunk_size=4)
    sched.start()
    try:
        stream, reason = _collect(sched, prompt, max_tokens=9)
    finally:
        sched.stop()
    assert reason == "length"
    return sched, prompt, stream


def test_the_scheduler_serves_the_reference_and_counts_its_passes(params, served):
    sched, prompt, stream = served
    want = np.asarray(ref.all_logits(params, CFG, prompt + stream[:-1]))
    assert stream == want[len(prompt) - 1 :].argmax(-1).tolist()
    snap = sched.stats.snapshot()
    assert snap["cache_planes"] == PLANES == 8
    # K and V of 2 heads of 16 float32 values in each of 8 planes.
    assert snap["kv_bytes_per_token"] == 2 * PLANES * CFG.n_kv_heads * CFG.head_dim * 4
    assert snap["prefill_stack_passes"] == CFG.ut_steps * 1  # one cold program
    # Counted at the dispatch, a chunk of four steps at a time (/metrics'
    # names: tests/test_tick_tracing.py).
    assert snap["decode_stack_passes"] >= CFG.ut_steps * 4 * snap["decode_chunks"] > 0
    assert snap["decode_stack_passes"] % (CFG.ut_steps * 4) == 0


def test_a_plain_stack_counts_one_pass_a_step():
    from generativeaiexamples_tpu.engine.scheduler import Scheduler
    from tests.test_scheduler import _collect

    cfg = llama.llama_tiny(dtype="float32")
    sched = Scheduler(cfg, llama.init_params(cfg, jax.random.PRNGKey(0)), max_batch=2,
                      max_len=64, decode_chunk_size=4)
    sched.start()
    try:
        _collect(sched, [3, 1, 4, 1, 5], max_tokens=6)
    finally:
        sched.stop()
    snap = sched.stats.snapshot()
    assert snap["cache_planes"] == cfg.n_layers
    assert snap["prefill_stack_passes"] == 1
    assert snap["decode_stack_passes"] >= 4 * snap["decode_chunks"] > 0 and snap["decode_stack_passes"] % 4 == 0


def _requests(plens, done=None):
    from generativeaiexamples_tpu.engine.sampler import SamplingParams
    from generativeaiexamples_tpu.engine.scheduler import Request

    return [
        Request(token_ids=[5 + i % 7] * n, sampling=SamplingParams(temperature=0.0, max_tokens=3),
                on_token=lambda _t: None, on_done=(lambda _r, i=i: done[i].set()) if done else (lambda _r: None),
                eos_id=None, id=f"r{i}")
        for i, n in enumerate(plens)
    ]


# (bytes a token, slot rows, chunk tokens, prompt lengths) -> rows of one batch.
# 1 GiB holds 1,344 padded tokens at Ouro's 798,720 B and 16,131 at Mistral's 66,560.
@pytest.mark.parametrize("per_token, max_len, chunk, plens, rows", [
    (798_720, 768, 256, [60] * 16, 16),        # 16 x 64: one batch
    (798_720, 768, 256, [120] * 16, 8),        # 16 x 128 is 1.6 GB: two batches of 8
    (798_720, 768, 256, [120] * 7 + [40], 8),  # the batch's longest prompt decides
    (798_720, 768, 256, [250] * 16, 0),        # 8 x 256 does not fit: each alone, in place
    (66_560, 2048, 256, [250] * 32, 32),       # Mistral's cells: nothing is cut
    (166_400, 2048, 256, [250] * 32, 16),      # llama3-70b's planes: 32 x 256 is 1.4 GB
    (66_560, 8192, None, [40] * 8, 8),         # unchunked, a long slot: short prompts are one batch
    (66_560, 8192, None, [8000] * 8, 4),       # ... long ones the smallest program there is (4 rows)
    (0, 8192, None, [8000] * 8, 8),            # a model that gives no bytes: nothing is cut
])
def test_a_cold_batch_is_cut_by_the_bytes_of_its_own_state(params, per_token, max_len, chunk, plens, rows):
    """A cold batch prefills into fresh state of its own (batch bucket x
    the batch's prompt bucket x the bytes a token) beside the slots: the
    cut follows those bytes as the batch has them, not a worst case, and
    leaves ``ADMIT_CAP`` and a given ``admit_cap`` alone."""
    from generativeaiexamples_tpu.engine.scheduler import Scheduler

    sched = Scheduler(CFG, params, max_batch=8, max_len=128, prefill_chunk_tokens=chunk, admit_cap=32)
    assert sched.ADMIT_CAP == 32 and sched._kv_bytes_per_token == sched.stats.kv_bytes_per_token > 0
    sched._kv_bytes_per_token, sched.max_len = per_token, max_len
    assert sched._cold_batch_rows(_requests(plens)) == rows


@pytest.mark.parametrize("budget_rows, lone, batched", [(16, 0, 11), (8, 3, 8), (4, 11, 0)])
def test_a_burst_goes_out_in_pieces_that_fit(params, monkeypatch, budget_rows, lone, batched):
    """Eleven short prompts at once on 16 slots, the smallest batch bucket
    8: a budget of 16 rows of their bucket sends one batch, one of 8 rows a
    batch of eight and the other three alone (a piece under half the
    smallest bucket), one of 4 rows all of them alone, as chunks of one row
    in place; every request is served whichever way it went."""
    from generativeaiexamples_tpu.engine import scheduler
    from generativeaiexamples_tpu.engine.scheduler import Scheduler
    import threading

    per_token = 2 * PLANES * CFG.n_kv_heads * CFG.head_dim * 4
    monkeypatch.setattr(scheduler, "COLD_BATCH_STATE_BYTES", budget_rows * 16 * per_token)
    sched = Scheduler(CFG, params, max_batch=16, max_len=128, prefill_chunk_tokens=64)
    assert sched.stats.kv_bytes_per_token == per_token and sched.ADMIT_CAP == Scheduler.ADMIT_CAP
    done = [threading.Event() for _ in range(11)]
    for req in _requests([9] * 11, done):
        assert sched.submit(req)
    sched.start()
    try:
        assert all(ev.wait(120) for ev in done)
    finally:
        sched.stop()
    snap = sched.stats.snapshot()
    assert (snap["admits_lone"], snap["admits_batched"]) == (lone, batched)


# -- the benchmark's comparison (benchmarks/arch/ouro.py) -----------------------


@pytest.mark.parametrize("fault", [None, "neighbours_slot"])
def test_the_benchmarks_full_house_rows_catch_a_wrong_slot(params, monkeypatch, fault):
    """``arch/ouro.py::logit_shares``: a chunked prompt and decode steps in
    a slot of its own, then every slot live at its own length with
    neighbours holding different sequences.  In float32 every reading is
    rounding; with each chunk written to the neighbour's slot the rows
    decode over another sequence's keys, and their readings leave the
    reference's by far while the lone slot's stay."""
    import chip_smoke
    from generativeaiexamples_tpu.engine.serving_models import LlamaServing

    arch = chip_smoke._bench_arch("ouro")
    arch._CHECK.update(decode=8, chunk=32, steps=4, slots=4)
    monkeypatch.setattr(arch, "KERNEL_ROWS", 64)
    monkeypatch.setattr(arch, "KERNEL_PREFIX", 16)
    monkeypatch.setattr(arch, "KERNEL_STRIDE", 5)
    arch._programs.cache_clear()
    if fault == "neighbours_slot":
        real = LlamaServing.prefill_row
        monkeypatch.setattr(
            LlamaServing, "prefill_row",
            lambda self, p, cache, toks, start, n, slot, kv: real(
                self, p, cache, toks, start, n, slot ^ 1 if cache[0].shape[2] > 1 else slot, kv))
    cfg = dataclasses.replace(CFG, max_seq_len=ROWS)
    row = np.random.RandomState(3).randint(0, CFG.vocab_size, size=(48,)).astype(np.int32)
    shares, last = arch.logit_shares(params, cfg, row, 48)
    arch._programs.cache_clear()
    read = arch.share_quantiles(shares)
    assert shares["prefill"].shape == (40,) and shares["decode"].shape == (8,) and shares["kernel_decode"].shape == (8, 4)
    assert last.shape == (CFG.vocab_size,)
    if fault is None:
        assert max(read.values()) < 1e-4, read
    else:  # (a)'s one slot is where it should be
        assert max(read["p50"], read["decode_p50"]) < 1e-4 < 0.3 < read["kernel_decode_p50"] <= read["kernel_row_max"], read


# -- the checkpoint mapping and the reference's copy ---------------------------


def test_a_written_ouro_checkpoint_maps_to_the_looped_configuration(params, tokens, tmp_path):
    """``llama_config_from_hf`` on a ``config.json`` of ``model_type:
    ouro`` (no checkpoint is in the repository: the test writes one, with
    ``modeling_ouro.py``'s tensor names), and ``load_hf_llama`` brings the
    two further norms a layer and the gate."""
    from generativeaiexamples_tpu.engine.weights import (
        llama_config_from_hf, load_hf_llama, save_safetensors)

    published = json.loads((REPO / "benchmarks" / "configs" / "ouro-2.6b.json").read_text())
    hf = {k: published[k] for k in (
        "model_type", "vocab_size", "hidden_size", "num_hidden_layers", "num_attention_heads",
        "num_key_value_heads", "head_dim", "intermediate_size", "rope_theta", "rms_norm_eps",
        "max_position_embeddings", "total_ut_steps", "early_exit_threshold")}
    hf["architectures"] = ["OuroForCausalLM"]
    (tmp_path / "config.json").write_text(json.dumps(hf))
    assert llama_config_from_hf(str(tmp_path), max_seq_len=65536) == llama.ouro_2_6b()
    (tmp_path / "config.json").write_text(json.dumps({**hf, "model_type": "gemma"}))
    with pytest.raises(ValueError, match="looped ouro family"):
        llama_config_from_hf(str(tmp_path))

    lay = {k: np.asarray(v) for k, v in params["layers"].items()}
    names = {
        "wq": "self_attn.q_proj", "wk": "self_attn.k_proj", "wv": "self_attn.v_proj",
        "wo": "self_attn.o_proj", "w_gate": "mlp.gate_proj", "w_up": "mlp.up_proj",
        "w_down": "mlp.down_proj", "attn_norm": "input_layernorm",
        "attn_post_norm": "input_layernorm_2", "mlp_norm": "post_attention_layernorm",
        "mlp_post_norm": "post_attention_layernorm_2",
    }
    tensors = {
        f"model.layers.{i}.{hf_name}.weight": (lay[ours][i].T if lay[ours].ndim == 3 else lay[ours][i])
        for ours, hf_name in names.items() for i in range(CFG.n_layers)
    }
    tensors |= {
        "model.embed_tokens.weight": np.asarray(params["embed"]),
        "model.norm.weight": np.asarray(params["final_norm"]),
        "lm_head.weight": np.asarray(params["lm_head"]).T,
        "model.early_exit_gate.weight": np.asarray(params["exit_gate"]["w"])[None],
        "model.early_exit_gate.bias": np.asarray(params["exit_gate"]["b"])[None],
    }
    save_safetensors(tensors, str(tmp_path / "model.safetensors"))
    loaded = load_hf_llama(CFG, str(tmp_path))
    assert jax.tree.structure(loaded) == jax.tree.structure(params)
    for a, b in zip(jax.tree.leaves(loaded), jax.tree.leaves(params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_the_benchmarks_reference_is_this_reference_byte_for_byte():
    ours = REPO / "generativeaiexamples_tpu" / "models" / "ouro_reference.py"
    assert (REPO / "benchmarks" / "ouro_reference.py").read_bytes() == ours.read_bytes()


def test_the_presets_serve_what_the_cell_serves():
    cfg = llama.PRESETS["ouro-2.6b"]()
    assert (cfg.n_layers, cfg.ut_steps, cfg.cache_planes) == (48, 4, 192)
    assert cfg.sandwich_norm and cfg.early_exit_threshold == 1.0
    # K and V of 16 heads of 128 int8 values and a bf16 scale, 192 planes.
    assert 2 * cfg.cache_planes * cfg.n_kv_heads * (cfg.head_dim + 2) == 798_720
    shapes = jax.eval_shape(lambda: llama.init_params(cfg, jax.random.PRNGKey(0)))
    layer = sum(x.size for x in jax.tree.leaves(shapes["layers"])) // cfg.n_layers
    assert layer == 4 * 2048**2 + 3 * 2048 * 5632 + 4 * 2048 == 51_388_416
    assert sum(x.size for x in jax.tree.leaves(shapes)) == 48 * layer + 2 * 49152 * 2048 + 2048 + 2049
    assert llama.PRESETS["ouro-tiny"]() == llama.ouro_tiny()
