"""The expert llama model's prefill chunks on the sorted dispatch:
``LlamaServing.prefill_rows`` (the chunks of several slots as one program)
against ``prefill_row`` (each alone, through the one-hot dispatch), the
sorted expert MLP against ``llama._moe_mlp`` dropless, the rule that says
how many chunks share a program, and the scheduler's family for it.

Float32 at the highest precision (conftest.py): the two dispatches sum an
expert's rows in another order and agree to a few 1e-7; a wrong route, a
dropped row or a pad row that writes moves values by 1e-2 and more.
"""

import dataclasses
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from generativeaiexamples_tpu.engine.sampler import SamplingParams
from generativeaiexamples_tpu.engine.scheduler import Request, Scheduler
from generativeaiexamples_tpu.engine.serving_models import LlamaServing
from generativeaiexamples_tpu.models import llama

SLOTS, MAX_LEN, WINDOW, S = 5, 64, 64, 16
# (slot, tokens of the prompt before this chunk, tokens of the chunk that
# count): a second chunk, a prompt's first chunk, and a prompt's last
# chunk, shorter than the bucket of 16, at another start.
ROWS = ((3, 16, 16), (0, 0, 16), (1, 32, 9))
TOL = dict(rtol=2e-5, atol=2e-5)


def _tiny(**overrides):
    return llama.llama_moe_tiny(dtype="float32", moe_dropless=True, **overrides)


def _prompt(seed, n):
    return np.random.RandomState(seed).randint(0, 512, size=n)


@pytest.fixture(scope="module", params=["bfloat16", "int8"])
def rows_case(request):
    """A serving model, its parameters, and slots that hold what
    ``prefill_row`` left of each row's prompt so far; every other slot
    holds noise, which no call may touch."""
    model = LlamaServing(_tiny(kv_dtype=request.param), None, MAX_LEN)
    params = model.prepare_params(None, quantize=False, matmul_kernel=None, seed=7)
    keys = iter(jax.random.split(jax.random.PRNGKey(11), 8))
    state = tuple(
        (jax.random.normal(next(keys), x.shape, jnp.float32) * 20).astype(x.dtype)
        for x in model.init_state(SLOTS, MAX_LEN)
    )
    prompts = {slot: _prompt(40 + slot, before + n) for slot, before, n in ROWS}
    one = jax.jit(model.prefill_row, static_argnums=(6,))
    for slot, before, _ in ROWS:
        for at in range(0, before, S):
            state, _, _ = one(
                params, state, jnp.asarray(prompts[slot][at : at + S], jnp.int32)[None],
                jnp.int32(at), jnp.int32(S), jnp.int32(slot), WINDOW,
            )
    return model, params, state, prompts, one


def _chunk_of(prompts, slot, before, n):
    return np.pad(prompts[slot][before : before + n], (0, S - n))


@pytest.mark.parametrize("n_rows", [1, 2, 3])
def test_rows_of_a_group_get_what_each_gets_alone(rows_case, n_rows):
    """1 row, 2 rows, and 3 padded to 4 (the pad row names a slot and
    counts no token), at different starts and lengths over one window:
    the hidden states and the cache rows ``prefill_row`` gives each row
    alone; every other slot is as it was, the pad row's too."""
    model, params, state, prompts, one = rows_case
    rows = ROWS[:n_rows]
    alone, want = state, {}
    for slot, before, n in rows:
        alone, hidden, _ = one(
            params, alone, jnp.asarray(_chunk_of(prompts, slot, before, n), jnp.int32)[None],
            jnp.int32(before), jnp.int32(n), jnp.int32(slot), WINDOW,
        )
        want[slot] = np.asarray(hidden[0, :n])
    pad = [(2, 7, 0)] * (n_rows == 3)
    tokens = np.stack([_chunk_of(prompts, *r) if r[2] else np.zeros(S, int) for r in (*rows, *pad)])
    slots, start, lens = (jnp.asarray(c, jnp.int32) for c in zip(*rows, *pad))
    together, hidden, aux = jax.jit(model.prefill_rows, static_argnums=(6,))(
        params, state, jnp.asarray(tokens, jnp.int32), start, lens, slots, WINDOW
    )
    assert aux is None
    for r, (slot, _, n) in enumerate(rows):
        np.testing.assert_allclose(np.asarray(hidden[r, :n]), want[slot], **TOL)
    others = [i for i in range(SLOTS) if i not in {slot for slot, _, _ in rows}]
    for got, each, was in zip(together, alone, state):
        got, each, was = (np.asarray(x, np.float32) for x in (got, each, was))
        # A row's cache up to its length: what lies past it is rewritten
        # before any mask shows it (a short chunk's padded positions).
        for slot, before, n in rows:
            np.testing.assert_allclose(got[:, :, slot, : before + n], each[:, :, slot, : before + n], **TOL)
        np.testing.assert_array_equal(got[:, :, others], was[:, :, others])


def _layer(cfg, seed, skew):
    """One layer's router and experts viewed as a stack of three layers'
    (the layer in question in the middle), and the layer's own slices."""
    E, D, F = cfg.n_experts, cfg.d_model, cfg.d_ff
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    router = jax.random.normal(keys[0], (D, E), jnp.float32) * 0.05
    if skew:
        # Inputs are positive: expert 0 is every token's first choice and
        # expert 3 no token's.
        router = router.at[:, 0].set(1.0).at[:, 3].set(-1.0)
    stack = {
        name: jax.random.normal(k, (3, E) + shape, jnp.float32) * 0.1
        for name, k, shape in zip(llama.EXPERT_LEAVES, keys[1:], ((D, F), (D, F), (F, D)))
    }
    lp = {"router": router, **{n: w[1] for n, w in stack.items()}}
    experts = {n: w.reshape((-1,) + w.shape[2:]) for n, w in stack.items()}
    return lp, experts


@pytest.mark.parametrize("kernel", ["stand-in", "gmm-interpreted"])
@pytest.mark.parametrize("skew", [False, True], ids=["random", "one-expert-all-another-none"])
@pytest.mark.parametrize("b,s", [(1, 16), (2, 200)])
def test_the_sorted_dispatch_is_the_one_hot_dispatch_dropless(b, s, skew, kernel, monkeypatch):
    """``_moe_mlp_sorted`` over the whole stack's experts, reading layer
    1's groups, against ``_moe_mlp`` on that layer's slices: random
    routing, and one expert that receives a choice of every token beside
    one that receives none (200 positions: the one-hot side blocks them
    in groups of 128 and pads).  Positions that do not count give zero.
    Through the dense stand-in, and through megablox's ``gmm`` itself in
    interpret mode: its groups are the stack's 12, of which 8 are empty."""
    if kernel == "gmm-interpreted":
        monkeypatch.setenv("GAIE_MOE_KERNEL_INTERPRET", "1")
    cfg = _tiny()
    lp, experts = _layer(cfg, 3, skew)
    h = jnp.abs(jax.random.normal(jax.random.PRNGKey(5), (b, s, cfg.d_model), jnp.float32))
    want, _ = llama._moe_mlp(h, lp, cfg, None)
    if skew:
        probs = jax.nn.softmax(jnp.einsum("bsd,de->bse", h, lp["router"]), -1)
        top = np.asarray(jax.lax.top_k(probs, cfg.n_experts_per_tok)[1])
        assert (top == 0).any(-1).all() and not (top == 3).any()
    valid = jnp.ones((b, s), bool).at[-1, s // 2 :].set(False)
    got = jax.jit(
        lambda h, li: llama._moe_mlp_sorted(h, lp, experts, li, valid, cfg, None)
    )(h, jnp.int32(1))
    keep = np.asarray(valid)
    np.testing.assert_allclose(np.asarray(got)[keep], np.asarray(want)[keep], **TOL)
    assert not np.asarray(got)[~keep].any()


def test_experts_spread_over_a_mesh_keep_their_chunks_alone():
    """The one-hot dispatch shards over the ``expert`` axis; the sorted
    one runs where one device holds the experts (16 tokens x 2 choices
    over 4 experts are 8 rows an expert: the cap of 8 chunks; the cells'
    widths: test_hybrid_serving.py)."""
    from generativeaiexamples_tpu.parallel.mesh import MeshSpec, make_mesh

    if len(jax.devices()) < 2:
        pytest.skip("needs two devices")
    mesh = make_mesh(MeshSpec(tensor=1, expert=2), devices=jax.devices()[:2])
    assert LlamaServing(_tiny(), mesh, MAX_LEN).chunks_per_program(16) == 1
    assert LlamaServing(_tiny(), None, MAX_LEN).chunks_per_program(16) == 8


@pytest.fixture(scope="module", params=["llama-moe-tiny", "llama-tiny"])
def scheduler(request):
    cfg = llama.PRESETS[request.param](dtype="float32")
    if cfg.n_experts > 1:
        cfg = dataclasses.replace(cfg, moe_dropless=True)  # as engine.server serves it
    s = Scheduler(
        cfg, None, max_batch=4, max_len=128, decode_chunk_size=4, seed=3,
        prefill_chunk_tokens=32, prefix_cache="off",
    )
    yield s
    s.stop()


def _generate(s, prompts, n=4):
    outs = [[] for _ in prompts]
    done = [threading.Event() for _ in prompts]
    for i, p in enumerate(prompts):
        assert s.submit(Request(
            token_ids=list(p),
            sampling=SamplingParams(temperature=0.0, top_p=1.0, max_tokens=n),
            on_token=outs[i].append, on_done=lambda _r, i=i: done[i].set(),
            eos_id=None, id=f"t{i}-{len(p)}",
        ))
    s.start()
    assert all(ev.wait(300) for ev in done)
    s.stop()
    return outs


def test_the_expert_model_has_a_family_and_the_dense_one_none(scheduler):
    """32 tokens x 2 choices over 4 experts are 16 rows an expert: up to
    8 chunks a program, held to the 4 slots there are; the family is
    compiled when the scheduler is built.  The dense model compiles
    none, and every chunk of its goes alone through ``_prefill_suffix``."""
    s = scheduler
    if s.cfg.n_experts > 1:
        assert s._chunk_rows == 4
        assert set(s._chunk_programs) == {(r, 128) for r in (1, 2, 4)}
    else:
        assert s._chunk_rows == 1 and not s._chunk_programs and not s._chunk_windows


def test_prompts_that_warm_side_by_side_stream_what_the_reference_says(scheduler):
    """Three chunked prompts admitted together: the expert model's chunks
    of one tick go out as one program of the family (the dense model's
    alone), and every greedy token is the float32 reference's choice or a
    near-tie of it, through the whole-prompt forward with the one-hot
    dispatch."""
    s = scheduler
    prompts = [_prompt(50 + i, n) for i, n in enumerate((100, 120, 70))]
    before = s.stats.snapshot()
    outs = _generate(s, prompts)
    after = s.stats.snapshot()
    chunks = after["prefill_chunks"] - before["prefill_chunks"]
    programs = after["prefill_chunk_programs"] - before["prefill_chunk_programs"]
    assert chunks == 4 + 4 + 3
    if s.cfg.n_experts > 1:
        assert programs < chunks and s._prefill_suffix_rows._cache_size() == 0
    else:
        assert programs == chunks

    @jax.jit
    def last_logits(tokens, n):
        pos = jnp.arange(tokens.shape[1], dtype=jnp.int32)[None]
        hidden, _ = llama.forward(s.params, s.cfg, tokens, pos, kv_lengths=n[None])
        return llama.logits(s.params, hidden)[0, n - 1]

    for p, out in zip(prompts, outs):
        seq = list(p)
        assert len(out) == 4
        for tok in out:
            lg = np.asarray(last_logits(
                jnp.asarray(np.pad(seq, (0, 128 - len(seq))), jnp.int32)[None], jnp.int32(len(seq))
            ))
            assert lg.max() - lg[tok] <= 1e-3
            seq.append(tok)
