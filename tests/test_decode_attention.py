"""Pallas decode-attention kernel vs the XLA reference (interpret mode).

The kernel (``ops.decode_attention``) is the TPU serving hot path; its
contract is gqa_attention specialized to s == 1 over the head-major int8
cache.  Interpret mode runs the same kernel logic on CPU so the
equivalence is checked hermetically (SURVEY.md §4 test strategy).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from generativeaiexamples_tpu.ops.attention import gqa_attention
from generativeaiexamples_tpu.ops.decode_attention import (
    decode_gqa_attention,
    decode_gqa_attention_xla,
    use_decode_kernel,
)

L, KH, B, T, HD, QH = 3, 2, 16, 128, 128, 4
WINDOW = 128


def _cache(key):
    kk = jax.random.split(key, 4)
    k8 = jax.random.randint(kk[0], (L, KH, B, T, HD), -127, 128, jnp.int8)
    v8 = jax.random.randint(kk[1], (L, KH, B, T, HD), -127, 128, jnp.int8)
    ks = (
        jnp.abs(jax.random.normal(kk[2], (L, KH, B, T), jnp.float32)) * 0.02
        + 0.01
    ).astype(jnp.bfloat16)
    vs = (
        jnp.abs(jax.random.normal(kk[3], (L, KH, B, T), jnp.float32)) * 0.02
        + 0.01
    ).astype(jnp.bfloat16)
    return k8, v8, ks, vs


@pytest.mark.parametrize("layer", [0, 2])
def test_matches_gqa_attention(layer):
    key = jax.random.PRNGKey(0)
    k8, v8, ks, vs = _cache(key)
    q = jax.random.normal(key, (B, QH, HD), jnp.bfloat16)
    # Varied lengths including empty (0) and full-window rows.
    lengths = jnp.asarray(
        [0, 1, 5, 17, 40, 64, 100, 127, 128, 3, 9, 77, 50, 2, 128, 31],
        jnp.int32,
    )

    got = decode_gqa_attention(
        q, k8, v8, ks, vs, jnp.int32(layer), lengths,
        window=WINDOW, interpret=True,
    )

    # Reference: slice the layer, transpose to gqa_attention's
    # (b, t, kh, ...) layout.  Decode q position = lengths - 1 with
    # kv_len = lengths (t <= pos === t < kv_len for s == 1).
    kl = jnp.transpose(k8[layer, :, :, :WINDOW], (1, 2, 0, 3))
    vl = jnp.transpose(v8[layer, :, :, :WINDOW], (1, 2, 0, 3))
    ksl = jnp.transpose(ks[layer, :, :, :WINDOW], (1, 2, 0))
    vsl = jnp.transpose(vs[layer, :, :, :WINDOW], (1, 2, 0))
    want = gqa_attention(
        q[:, None],
        kl,
        vl,
        jnp.maximum(lengths - 1, 0)[:, None],
        lengths,
        k_scale=ksl,
        v_scale=vsl,
    )[:, 0]

    g = np.asarray(got, np.float32)
    w = np.asarray(want, np.float32)
    np.testing.assert_allclose(g, w, rtol=0.05, atol=0.02)
    # Empty rows are exactly zero in both.
    np.testing.assert_array_equal(g[0], np.zeros_like(g[0]))


def _append_cfg():
    from generativeaiexamples_tpu.models import llama

    return llama.LlamaConfig(
        vocab_size=256,
        d_model=256,
        n_layers=2,
        n_heads=2,
        n_kv_heads=2,
        head_dim=128,
        d_ff=256,
        max_seq_len=256,
        rope_theta=10000.0,
        kv_dtype="int8",
    )


@pytest.mark.parametrize("mode", ["kernel-interpret", "xla-fallback"])
def test_append_buffer_path_matches_scatter_path(monkeypatch, mode):
    """forward(append_cache=...) + flush == the warm-scatter decode path.

    Runs the real append-buffer protocol (ab writes, chunk flush) for two
    steps against the XLA scatter path on the same cache and inputs —
    once through the Pallas kernel in interpret mode, once through the
    ``decode_gqa_attention_xla`` full-batch fallback (the path a TPU with
    the kernel disabled serves on).
    """
    from generativeaiexamples_tpu.engine.decode import _flush_append_buffer
    from generativeaiexamples_tpu.models import llama

    cfg = _append_cfg()
    b, plen, steps = 16, 8, 2
    key = jax.random.PRNGKey(1)
    params = llama.init_params(cfg, key)
    tokens = jax.random.randint(key, (b, plen), 0, cfg.vocab_size)
    lengths = jnp.full((b,), plen, jnp.int32)
    positions = jnp.broadcast_to(jnp.arange(plen), (b, plen))

    # Cold prefill fills both caches identically.
    cache = llama.init_kv_cache(cfg, b, 128)
    _, cache = llama.forward(
        params, cfg, tokens, positions, cache, lengths, cold_prefill=True
    )
    cache_ref = jax.tree.map(jnp.copy, cache)

    step_tok = jax.random.randint(key, (b, 1), 0, cfg.vocab_size)
    hid_ab = []
    hid_ref = []

    # Reference: warm scatter path, one token at a time.
    cur_len = lengths
    for i in range(steps):
        pos = cur_len[:, None]
        h, cache_ref = llama.forward(
            params, cfg, step_tok + i, pos, cache_ref, cur_len + 1,
            kv_bucket=128,
        )
        hid_ref.append(h)
        cur_len = cur_len + 1

    if mode == "kernel-interpret":
        monkeypatch.setenv("GAIE_DECODE_KERNEL_INTERPRET", "1")
    else:
        monkeypatch.setenv("GAIE_DISABLE_DECODE_KERNEL", "1")
        monkeypatch.setenv("GAIE_FORCE_APPEND_BUFFER", "1")
    ab_shape = (cfg.n_layers, cfg.n_kv_heads, b, steps, cfg.head_dim)
    ab = (
        jnp.zeros(ab_shape, jnp.int8),
        jnp.zeros(ab_shape, jnp.int8),
        jnp.zeros(ab_shape[:-1], jnp.bfloat16),
        jnp.zeros(ab_shape[:-1], jnp.bfloat16),
    )
    for i in range(steps):
        pos = (lengths + i)[:, None]
        h, _, ab = llama.forward(
            params, cfg, step_tok + i, pos, cache, lengths,
            kv_bucket=128, append_cache=(ab, i),
        )
        hid_ab.append(h)
    cache_flushed = _flush_append_buffer(cache, ab, lengths, 128)

    for h_ab, h_ref in zip(hid_ab, hid_ref):
        np.testing.assert_allclose(
            np.asarray(h_ab, np.float32),
            np.asarray(h_ref, np.float32),
            rtol=0.08,
            atol=0.08,
        )
    # The flushed cache matches the scatter-path cache.  Layer 0's fresh
    # KV depends only on the (identical) embeddings, so it is bit-exact;
    # deeper layers see numerically slightly different attention inputs
    # (online vs full softmax), so their int8 codes may differ by ±1.
    for leaf_f, leaf_r in zip(cache_flushed, cache_ref):
        f = np.asarray(leaf_f).astype(np.float32)
        r = np.asarray(leaf_r).astype(np.float32)
        np.testing.assert_array_equal(f[0], r[0])
        np.testing.assert_allclose(f, r, atol=3.0)


def test_a_block_of_several_tokens_over_the_append_buffer_is_refused(monkeypatch):
    """The append buffer takes a decode step's one token a row: the gate
    says no to a block, and ``forward`` raises rather than write one (its
    verify block went with the engine of a second model's drafts, PR 55;
    a model's own draft is verified by ``HybridServing``, two positions
    through ``hybrid.forward``)."""
    from generativeaiexamples_tpu.models import llama
    from generativeaiexamples_tpu.ops.decode_attention import use_append_buffer

    monkeypatch.setenv("GAIE_FORCE_APPEND_BUFFER", "1")
    cfg = _append_cfg()
    gate = dict(kv_int8=True, batch=8, window=128, n_q=cfg.n_heads, n_kv=cfg.n_kv_heads,
                head_dim=cfg.head_dim)
    assert use_append_buffer(s=1, **gate) and not use_append_buffer(s=4, **gate)
    params = llama.init_params(cfg, jax.random.PRNGKey(5))
    cache = llama.init_kv_cache(cfg, 8, 128)
    lengths = jnp.full((8,), 8, jnp.int32)
    fresh = jnp.zeros((8, 4), jnp.int32)
    with pytest.raises(ValueError, match="one token a row"):
        llama.forward(
            params, cfg, fresh, lengths[:, None] + jnp.arange(4)[None, :], cache, lengths,
            kv_bucket=128, append_cache=(llama.init_append_buffer(cfg, 8, 4), 0),
        )


def test_flush_clip_boundary_confines_damage_to_tail_zone():
    """A lane entering a chunk at start > max_len - chunk clips its flush
    to [max_len - chunk, max_len) — the tail garbage zone.

    This pins the cross-module invariant the clip relies on (ADVICE r3):
    such lanes always FINISH within that chunk (scheduler length cap),
    and the scheduler's parking margin ``max_len - max(16, chunk+1)``
    keeps parked history strictly below the zone — so the overwrite can
    only ever hit positions no live or parked sequence will read.  The
    test asserts the damage is confined: every slot below the zone, and
    every other lane, is untouched.
    """
    from generativeaiexamples_tpu.engine.decode import _flush_append_buffer

    L, KH, B, T, HD, C = 2, 2, 3, 32, 8, 4
    rng = np.random.default_rng(0)
    cache_np = rng.integers(-100, 100, (L, KH, B, T, HD), dtype=np.int8)
    cache = (
        jnp.asarray(cache_np),
        jnp.asarray(cache_np + 1),
        jnp.asarray(rng.random((L, KH, B, T), np.float32), jnp.bfloat16),
        jnp.asarray(rng.random((L, KH, B, T), np.float32), jnp.bfloat16),
    )
    ab_np = rng.integers(-100, 100, (L, KH, B, C, HD), dtype=np.int8)
    ab = (
        jnp.asarray(ab_np),
        jnp.asarray(ab_np - 1),
        jnp.asarray(rng.random((L, KH, B, C), np.float32), jnp.bfloat16),
        jnp.asarray(rng.random((L, KH, B, C), np.float32), jnp.bfloat16),
    )
    # Row 0: normal mid-cache flush.  Row 1: start = T - 2 > T - C — the
    # boundary case, clipped to T - C.  Row 2: parked-lane convention
    # (max_len - 1), also clipped to T - C.
    starts = jnp.asarray([5, T - 2, T - 1], jnp.int32)
    out = _flush_append_buffer(cache, ab, starts, T)

    for big, small, new in zip(cache, ab, out):
        big_h, small_h, new_h = map(np.asarray, (big, small, new))
        # Row 0: exact placement at [5, 5+C), rest intact.
        np.testing.assert_array_equal(new_h[:, :, 0, 5 : 5 + C], small_h[:, :, 0])
        np.testing.assert_array_equal(new_h[:, :, 0, :5], big_h[:, :, 0, :5])
        np.testing.assert_array_equal(
            new_h[:, :, 0, 5 + C :], big_h[:, :, 0, 5 + C :]
        )
        # Rows 1 and 2: clip to the tail zone; EVERYTHING below T - C is
        # untouched (the invariant that protects real history).
        for r in (1, 2):
            np.testing.assert_array_equal(
                new_h[:, :, r, : T - C], big_h[:, :, r, : T - C]
            )
            np.testing.assert_array_equal(
                new_h[:, :, r, T - C :], small_h[:, :, r]
            )


# A cache of three 512-slot blocks and two row groups of 16, so a walk
# takes 0 to 3 turns, the ping-pong slot is carried from row to row and
# group to group in either parity, and a walked row follows empty ones.
WALK_B, WALK_T = 32, 1536
_RAGGED = [1400, 3, 513, 0, 1023, 1024, 1025, 64, 1536, 1, 511, 512, 600, 0, 0, 1280] * 2
WALK_LENGTHS = {
    # every row another number of blocks, some rows empty
    "ragged": _RAGGED,
    # empty rows first, last, and a whole group of 16
    "zero-rows": [0] * 17 + [600, 0, 1536, 0, 0, 5, 0, 0, 0, 1025, 0, 0, 0, 0, 0],
    # every row ends inside a block
    "mid-block": [1 + (37 * i) % 511 + 512 * (i % 3) for i in range(WALK_B)],
    # every row is exactly one block
    "one-block": [512] * WALK_B,
    # every row fills the cache
    "full": [WALK_T] * WALK_B,
}


@pytest.fixture(scope="module")
def walk_inputs():
    kk = jax.random.split(jax.random.PRNGKey(25), 8)
    shape = (L, KH, WALK_B, WALK_T, HD)
    c = 8

    def scales(key, n):
        return (
            jnp.abs(jax.random.normal(key, shape[:3] + (n,), jnp.float32)) * 0.02
            + 0.01
        ).astype(jnp.bfloat16)

    cache = (
        jax.random.randint(kk[0], shape, -127, 128, jnp.int8),
        jax.random.randint(kk[1], shape, -127, 128, jnp.int8),
        scales(kk[2], WALK_T),
        scales(kk[3], WALK_T),
    )
    leaves = (
        jax.random.randint(kk[4], shape[:3] + (c, HD), -127, 128, jnp.int8),
        jax.random.randint(kk[5], shape[:3] + (c, HD), -127, 128, jnp.int8),
        scales(kk[6], c),
        scales(kk[7], c),
    )
    # The step's fresh rows, (B, KH, ...): slot 4's of the last layer's
    # planes will do for values.
    fresh = tuple(jnp.swapaxes(leaf[-1, :, :, 4], 0, 1) for leaf in leaves)
    q = jax.random.normal(kk[7], (WALK_B, QH, HD), jnp.float32)
    return q, cache, (leaves, fresh, jnp.int32(4))


@pytest.mark.parametrize("layer", [0, 2])
@pytest.mark.parametrize("with_append", [False, True], ids=["cache", "append"])
@pytest.mark.parametrize("case", sorted(WALK_LENGTHS))
def test_row_walk_matches_xla_twin(walk_inputs, case, with_append, layer):
    """The kernel walks each row's own blocks — none for a row of length
    0, the last masked to the length — and equals the XLA twin, which
    slices the whole window and masks.  With an append buffer both put
    the step's fresh rows into slot 4 of the layer and attend slots
    [0, 4]: the leaves they hand back are the same bits."""
    q, cache, append = walk_inputs
    lengths = jnp.asarray(WALK_LENGTHS[case], jnp.int32)
    kw = dict(append=append if with_append else None, window=WALK_T)
    want = decode_gqa_attention_xla(
        q, *cache, jnp.int32(layer), lengths, **kw
    )
    got = decode_gqa_attention(
        q, *cache, jnp.int32(layer), lengths, interpret=True, **kw
    )
    if with_append:
        (got, got_leaves), (want, want_leaves) = got, want
        for mine, twins in zip(got_leaves, want_leaves):
            np.testing.assert_array_equal(np.asarray(mine), np.asarray(twins))
    g, w = np.asarray(got), np.asarray(want)
    np.testing.assert_allclose(g, w, rtol=1e-3, atol=1e-4)
    if not with_append:
        # A row of length 0 reads nothing and is exactly zero.
        empty = np.asarray(lengths) == 0
        np.testing.assert_array_equal(g[empty], np.zeros_like(g[empty]))


@pytest.mark.parametrize("c", [1, 8])
def test_kernel_writes_the_append_buffer_as_the_twin_does(c):
    """A chunk of ``c`` steps over two of three layers, from an empty
    buffer: after every call the four leaves the kernel hands back are,
    bit for bit, the twin's (``write_append_rows``' ``dynamic_update_slice``)
    and what placing the fresh rows by hand gives; a row that does not
    decode (``kv_lengths`` 0, a whole group of them among the rows) has its
    slot written like any other, because the chunk's flush reads every
    row; slots not yet written and the layer no call names stay zero."""
    from generativeaiexamples_tpu.models.llama import _quantize_kv

    b, t = 32, 128
    kk = jax.random.split(jax.random.PRNGKey(54), 3)
    cache = _cache(kk[0])
    cache = tuple(jnp.concatenate([leaf, leaf], axis=2) for leaf in cache)
    lengths = jnp.asarray([0] * 16 + [t, 0, 1, 0, 77] + [5] * 10 + [0], jnp.int32)
    q = jax.random.normal(kk[1], (b, QH, HD), jnp.float32)
    shape = (L, KH, b, c, HD)
    empty = (
        jnp.zeros(shape, jnp.int8), jnp.zeros(shape, jnp.int8),
        jnp.zeros(shape[:-1], jnp.bfloat16), jnp.zeros(shape[:-1], jnp.bfloat16),
    )
    mine, twins, by_hand = empty, empty, [np.asarray(leaf).copy() for leaf in empty]
    for step in range(c):
        for layer in (2, 0):
            kv = jax.random.normal(
                jax.random.fold_in(kk[2], 8 * step + layer), (2, b, 1, KH, HD), jnp.bfloat16)
            (k8, ks), (v8, vs) = _quantize_kv(kv[0]), _quantize_kv(kv[1])
            fresh = (k8[:, 0], v8[:, 0], ks[:, 0], vs[:, 0])
            got, mine = decode_gqa_attention(
                q, *cache, jnp.int32(layer), lengths,
                append=(mine, fresh, jnp.int32(step)), window=t, interpret=True)
            want, twins = decode_gqa_attention_xla(
                q, *cache, jnp.int32(layer), lengths,
                append=(twins, fresh, jnp.int32(step)), window=t)
            np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-3, atol=1e-4)
            for leaf, new in zip(by_hand, fresh):
                leaf[layer, :, :, step] = np.swapaxes(np.asarray(new), 0, 1)
            for a, x, h in zip(mine, twins, by_hand):
                a, x = np.asarray(a), np.asarray(x)
                assert a.dtype == x.dtype == h.dtype
                np.testing.assert_array_equal(a.view(np.uint8), x.view(np.uint8))
                np.testing.assert_array_equal(a.view(np.uint8), h.view(np.uint8))
                assert not a[:, :, :, step + 1 :].any() and not a[1].any()
    assert all(np.asarray(leaf)[0].any() and np.asarray(leaf)[2].any() for leaf in mine)


def test_decode_chunk_dead_rows_change_nothing_that_is_kept(monkeypatch):
    """``live`` only takes the dead rows' cache out of attention: the
    live rows' tokens are those of a chunk that attends every row, and
    the flush lands where it did — the cache is bit-identical on the live
    rows and, on the dead ones, everywhere but the tail zone their
    (never emitted) garbage is written to."""
    from generativeaiexamples_tpu.engine.decode import make_decode_chunk_fn
    from generativeaiexamples_tpu.models import llama
    from generativeaiexamples_tpu.ops.decode_attention import flush_clip_start

    monkeypatch.setenv("GAIE_DECODE_KERNEL_INTERPRET", "1")
    cfg = _append_cfg()
    b, plen, max_len, steps = 16, 8, 128, 4
    key = jax.random.PRNGKey(3)
    params = llama.init_params(cfg, key)
    tokens = jax.random.randint(key, (b, plen), 0, cfg.vocab_size)
    positions = jnp.broadcast_to(jnp.arange(plen), (b, plen))
    cache = llama.init_kv_cache(cfg, b, max_len)
    _, cache = llama.forward(
        params, cfg, tokens, positions, cache,
        jnp.full((b,), plen, jnp.int32), cold_prefill=True,
    )
    live = np.zeros((b,), bool)
    live[[0, 3, 4, 9, 15]] = True
    # Dead rows sit where the scheduler pins them: the cache's last slot.
    lengths = jnp.asarray(np.where(live, plen, max_len - 1), jnp.int32)
    decode_chunk = make_decode_chunk_fn(cfg, None, max_len)
    args = (
        tokens[:, -1], lengths, key, jnp.zeros((b,), jnp.float32),
        jnp.ones((b,), jnp.float32), jnp.zeros((b,), jnp.int32), steps, 32,
    )
    copy = lambda c: tuple(jnp.array(leaf) for leaf in c)  # the cache is donated
    cache_all, toks_all = decode_chunk(params, copy(cache), *args)
    cache_live, toks_live = decode_chunk(
        params, copy(cache), *args, jnp.asarray(live)
    )
    np.testing.assert_array_equal(
        np.asarray(toks_live)[:, live], np.asarray(toks_all)[:, live]
    )
    zone = flush_clip_start(max_len, steps)
    for got, want, before in zip(cache_live, cache_all, cache):
        g, w, b0 = (np.asarray(x).astype(np.float32) for x in (got, want, before))
        np.testing.assert_array_equal(g[:, :, live], w[:, :, live])
        np.testing.assert_array_equal(g[:, :, ~live, :zone], w[:, :, ~live, :zone])
        # ... and the live rows did write their chunk: [plen, plen + steps).
        assert (g[:, :, live, plen : plen + steps] != b0[:, :, live, plen : plen + steps]).any()


# (what differs from a llama3-8b decode step on one TPU chip, whether
# the kernel takes it).  ``devices``: the mesh spans that many; a mesh of
# one is a replica's slice of a multi-chip host, 0 is no mesh at all: the
# gate reads the mesh, not the process, so no mesh means the default
# device, whatever else the host holds (8 virtual devices here).
GATE_CASES = {
    "tpu": ({}, True),
    "cpu": ({"backend": "cpu"}, False),
    "two_queries": ({"s": 2}, False),
    "bf16_kv": ({"kv_int8": False}, False),
    "batch_321": ({"batch": 321}, False),
    # Small pow2 buckets run as a single window-deep tile (sublane
    # quantum 32 divides them); only sub-sublane windows fall back.
    "window_64": ({"window": 64}, True),
    "window_32": ({"window": 32}, True),
    "window_16": ({"window": 16}, False),
    "no_mesh": ({"devices": 0}, True),
    "mesh_of_two": ({"devices": 2}, False),
    # Ouro-2.6B's step: 16 slots of 768 rows, 16 KV heads with ONE query
    # head each (the kernel's block pairs it with a zero one), a chunk's
    # append buffer of 8, at the narrowest and the widest decode window.
    "lone_query_heads": (
        {"batch": 16, "window": 768, "cache_len": 768, "n_q": 16, "n_kv": 16, "append_width": 8}, True),
    "lone_query_heads_window_64": (
        {"batch": 16, "window": 64, "cache_len": 768, "n_q": 16, "n_kv": 16, "append_width": 8}, True),
}


@pytest.mark.parametrize("case", sorted(GATE_CASES))
def test_use_decode_kernel_gating(case):
    changed, taken = GATE_CASES[case]
    asked = dict(
        s=1, kv_int8=True, batch=320, window=256, n_q=32, n_kv=8,
        head_dim=128, backend="tpu", devices=1,
    )
    asked.update(changed)
    n = asked.pop("devices")
    mesh = None
    if n:
        mesh = jax.sharding.Mesh(np.asarray(jax.devices()[:n]), ("data",))
    assert use_decode_kernel(mesh=mesh, **asked) is taken


# Every window ``bucket_size(..., dense=True)`` can produce for a context
# of up to 2,048 tokens (``test_reachable_windows_are_these`` holds the
# list to the function).
REACHABLE_WINDOWS = [16, 32, 64, 128, 256, 384, 512, 768, 1024, 1536, 2048]


def test_reachable_windows_are_these():
    from generativeaiexamples_tpu.utils.buckets import bucket_size

    assert REACHABLE_WINDOWS == sorted(
        {bucket_size(n, minimum=16, dense=True) for n in range(1, 2049)}
    )


@pytest.mark.parametrize("window", REACHABLE_WINDOWS)
def test_decode_kernel_gate_covers_every_reachable_window(monkeypatch, window):
    """Regression for the ``window % 128 == 0`` gate bug that silently
    sent the small pow2 kv buckets (32, 64) — reachable from any
    short-context decode — to the scatter path.  Every window
    ``bucket_size(..., dense=True)`` can actually produce must engage
    the kernel, except the 16 floor (below the int8 sublane quantum's
    single-tile minimum of 32)."""
    monkeypatch.setenv("GAIE_DECODE_KERNEL_INTERPRET", "1")
    assert use_decode_kernel(
        s=1, kv_int8=True, batch=16, window=window, n_q=4, n_kv=2,
        head_dim=128,
    ) is (window >= 32)


@pytest.mark.parametrize("window", [32, 64])
def test_decode_kernel_numeric_at_small_windows(monkeypatch, window):
    """The newly-admitted small windows actually run the kernel and
    match the XLA twin (interpret mode) — the gate fix is not just a
    predicate change."""
    monkeypatch.setenv("GAIE_DECODE_KERNEL_INTERPRET", "1")
    lcl, kh, b, hd, qh = 1, 2, 16, 128, 4
    key = jax.random.PRNGKey(window)
    kk = jax.random.split(key, 6)
    k8 = jax.random.randint(kk[0], (lcl, kh, b, window, hd), -127, 128, jnp.int8)
    v8 = jax.random.randint(kk[1], (lcl, kh, b, window, hd), -127, 128, jnp.int8)
    ks = (
        jnp.abs(jax.random.normal(kk[2], (lcl, kh, b, window))) * 0.02 + 0.01
    ).astype(jnp.bfloat16)
    vs = (
        jnp.abs(jax.random.normal(kk[3], (lcl, kh, b, window))) * 0.02 + 0.01
    ).astype(jnp.bfloat16)
    lengths = jax.random.randint(kk[4], (b,), 1, window + 1, jnp.int32)
    q = jax.random.normal(kk[5], (b, qh, hd), jnp.float32)
    assert use_decode_kernel(
        s=1, kv_int8=True, batch=b, window=window,
        n_q=qh, n_kv=kh, head_dim=hd,
    )
    ref = decode_gqa_attention_xla(
        q, k8, v8, ks, vs, jnp.int32(0), lengths, window=window
    )
    got = decode_gqa_attention(
        q, k8, v8, ks, vs, jnp.int32(0), lengths,
        window=window, interpret=True,
    )
    np.testing.assert_allclose(
        np.asarray(got, np.float32),
        np.asarray(ref, np.float32),
        rtol=1e-3,
        atol=1e-4,
    )


def test_a_lone_query_head_a_kv_head_walks_as_its_twin():
    """Plain multi-head attention at Ouro-2.6B's step shape (16 slots of
    768 rows, 16 KV heads of 128 with one query head each, an append buffer
    of 8): the group of one rides the kernel's block beside a zero query
    head (``_MIN_GROUP``), whose output is dropped, and equals the XLA twin
    over ragged rows; the block's VMEM stays a quarter of the budget."""
    from generativeaiexamples_tpu.ops import decode_attention as da

    b, kh, t, hd, c = 16, 16, 768, 128, 8
    assert da._block_t(t, t) == 256
    for window in (64, t):
        held = da._decode_kernel_vmem_bytes(
            da._block_t(t, window), da._scale_width(window, t), kh, da._MIN_GROUP, hd, c)
        assert held <= da._VMEM_BUDGET_BYTES // 4, (window, held)
    kk = jax.random.split(jax.random.PRNGKey(51), 9)

    def scales(key, n):
        return (jnp.abs(jax.random.normal(key, (1, kh, b, n))) * 0.02 + 0.01).astype(jnp.bfloat16)

    cache = (
        jax.random.randint(kk[0], (1, kh, b, t, hd), -127, 128, jnp.int8),
        jax.random.randint(kk[1], (1, kh, b, t, hd), -127, 128, jnp.int8),
        scales(kk[2], t), scales(kk[3], t),
    )
    leaves = (
        jax.random.randint(kk[4], (1, kh, b, c, hd), -127, 128, jnp.int8),
        jax.random.randint(kk[5], (1, kh, b, c, hd), -127, 128, jnp.int8),
        scales(kk[6], c), scales(kk[7], c),
    )
    fresh = tuple(jnp.swapaxes(leaf[0, :, :, 5], 0, 1) for leaf in leaves)
    append = (leaves, fresh, jnp.int32(2))
    q = jax.random.normal(kk[8], (b, kh, hd), jnp.float32)
    lengths = jnp.asarray([768, 0, 1, 255, 256, 257, 40, 511, 512, 513, 700, 0, 64, 350, 767, 128], jnp.int32)
    kw = dict(append=append, window=t)
    want, want_leaves = decode_gqa_attention_xla(q, *cache, jnp.int32(0), lengths, **kw)
    got, got_leaves = decode_gqa_attention(q, *cache, jnp.int32(0), lengths, interpret=True, **kw)
    assert got.shape == (b, kh, hd)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-3, atol=1e-4)
    for mine, twins in zip(got_leaves, want_leaves):
        np.testing.assert_array_equal(np.asarray(mine), np.asarray(twins))
