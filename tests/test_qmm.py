"""W8A8 quantized-matmul kernel (ops/qmm.py) + fused serving path tests.

The fused decode path's whole contract is bit-exactness: the Pallas
kernel (interpret mode on CPU) and its XLA twin consume identical
quantized operands and must agree to the bit, all the way up through
greedy decode in the serving scheduler on every admission path (cold,
chunked prefill, shared-prefix graft).  Tile blocking
happens ONCE at load — ``BLOCK_EVENTS`` proves no decode step re-tiles.
"""

import queue

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from generativeaiexamples_tpu.engine.decode import (
    init_random_int8_params,
    prepare_params,
)
from generativeaiexamples_tpu.engine.sampler import SamplingParams
from generativeaiexamples_tpu.engine.scheduler import Request, Scheduler
from generativeaiexamples_tpu.models import llama
from generativeaiexamples_tpu.ops import qmm
from generativeaiexamples_tpu.ops.quant import (
    QuantizedMatrix,
    dequantize,
    q_dot,
    quantize_matrix,
)

CFG = llama.llama_tiny(dtype="float32", max_seq_len=128)


def _random_blocked(key, k, n, block_n=None):
    w = jax.random.normal(key, (k, n), jnp.float32)
    return w, qmm.block_matrix(quantize_matrix(w), block_n=block_n)


# ---------------------------------------------------------------------------
# Kernel exactness: interpret-mode Pallas vs the XLA twin
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "m,k,n",
    [
        (1, 64, 96),  # decode batch 1, ragged everything
        (5, 200, 300),  # ragged K and N edges
        (8, 128, 384),  # decode_chunk-sized batch, aligned K
        (32, 256, 512),  # fully aligned
    ],
)
def test_kernel_bit_exact_vs_xla_twin(monkeypatch, m, k, n):
    _, bw = _random_blocked(jax.random.PRNGKey(0), k, n)
    x = jax.random.normal(jax.random.PRNGKey(1), (m, k), jnp.float32)
    monkeypatch.setenv("GAIE_DISABLE_QMM_KERNEL", "1")
    ref = qmm.q_matmul(x, bw)
    monkeypatch.delenv("GAIE_DISABLE_QMM_KERNEL")
    monkeypatch.setenv("GAIE_QMM_INTERPRET", "1")
    out = qmm.q_matmul(x, bw)
    assert out.shape == (m, n)
    assert (np.asarray(out) == np.asarray(ref)).all()


def test_kernel_bit_exact_narrow_block(monkeypatch):
    """Non-default BN (the GAIE_QMM_BN tuning knob) stays bit-exact."""
    _, bw = _random_blocked(jax.random.PRNGKey(2), 192, 640, block_n=128)
    assert bw.tiles.shape == (5, 256, 128)  # K 192 pads to the 128 quantum
    x = jax.random.normal(jax.random.PRNGKey(3), (4, 192), jnp.float32)
    monkeypatch.setenv("GAIE_DISABLE_QMM_KERNEL", "1")
    ref = qmm.q_matmul(x, bw)
    monkeypatch.delenv("GAIE_DISABLE_QMM_KERNEL")
    monkeypatch.setenv("GAIE_QMM_INTERPRET", "1")
    assert (np.asarray(qmm.q_matmul(x, bw)) == np.asarray(ref)).all()


def test_scale_folding_matches_dequantized_reference():
    """W8A8 ~= the f32 matmul against the dequantized weight.

    Not bit-exact (activations are quantized too); the folded per-token
    x per-channel scales must land within the expected int8 rounding
    envelope of the full-precision product.
    """
    w, bw = _random_blocked(jax.random.PRNGKey(4), 256, 320)
    x = jax.random.normal(jax.random.PRNGKey(5), (16, 256), jnp.float32)
    out = qmm.q_matmul(x, bw)
    ref = x @ dequantize(quantize_matrix(w), jnp.float32)
    err = np.abs(np.asarray(out) - np.asarray(ref))
    scale = np.abs(np.asarray(ref)).mean()
    assert err.mean() / scale < 0.02


def test_quantize_activations_round_trip():
    x = jax.random.normal(jax.random.PRNGKey(6), (4, 64), jnp.float32) * 3.0
    xq, a_scale = qmm.quantize_activations(x)
    assert xq.dtype == jnp.int8 and a_scale.shape == (4, 1)
    back = np.asarray(xq, np.float32) * np.asarray(a_scale)
    assert np.abs(back - np.asarray(x)).max() <= np.asarray(a_scale).max()


# ---------------------------------------------------------------------------
# Blocking: layout, idempotence, tile-once accounting
# ---------------------------------------------------------------------------


def test_block_matrix_layout_and_logical_shape():
    qm = quantize_matrix(
        jax.random.normal(jax.random.PRNGKey(7), (200, 300), jnp.float32)
    )
    bw = qmm.block_matrix(qm, block_n=256)
    assert bw.tiles.shape == (2, 256, 256)  # K 200->256, N 300->2x256
    assert bw.scale.shape == (2, 1, 256)
    assert bw.shape == (200, 300) and bw.ndim == 2
    # Padding columns carry scale 0 so they cannot leak into the output.
    assert np.asarray(bw.scale)[1, 0, 300 - 256 :].max() == 0.0


def test_block_matrix_stacked_layers():
    qm = quantize_matrix(
        jax.random.normal(jax.random.PRNGKey(8), (3, 64, 96), jnp.float32)
    )
    bw = qmm.block_matrix(qm, block_n=128)
    assert bw.tiles.shape == (3, 1, 128, 128)
    assert bw.shape == (3, 64, 96)
    # lax.scan slices the layer axis like any other stacked leaf.
    sliced = jax.tree.map(lambda a: a[1], bw)
    assert sliced.tiles.shape == (1, 128, 128) and sliced.n == 96


def test_block_matrix_idempotent_and_typed():
    qm = quantize_matrix(
        jax.random.normal(jax.random.PRNGKey(9), (64, 64), jnp.float32)
    )
    bw = qmm.block_matrix(qm)
    before = qmm.BLOCK_EVENTS["count"]
    assert qmm.block_matrix(bw) is bw  # already blocked: no re-tiling
    assert qmm.BLOCK_EVENTS["count"] == before
    with pytest.raises(TypeError, match="QuantizedMatrix"):
        qmm.block_matrix(jnp.zeros((4, 4)))


# ---------------------------------------------------------------------------
# q_dot validation + dequantize default dtype (satellite)
# ---------------------------------------------------------------------------


def test_q_dot_names_projection_on_shape_mismatch():
    qm = quantize_matrix(
        jax.random.normal(jax.random.PRNGKey(10), (64, 96), jnp.float32)
    )
    x = jnp.zeros((2, 48), jnp.float32)
    with pytest.raises(ValueError, match="projection 'wqkv'"):
        q_dot(x, qm, "wqkv")
    with pytest.raises(ValueError, match="projection 'w_gu'"):
        q_dot(x, qmm.block_matrix(qm), "w_gu")
    with pytest.raises(ValueError, match="floating point"):
        q_dot(jnp.zeros((2, 64), jnp.int32), qm, "wo")


def test_q_dot_dispatches_blocked(monkeypatch):
    w, bw = _random_blocked(jax.random.PRNGKey(11), 64, 96)
    x = jax.random.normal(jax.random.PRNGKey(12), (3, 64), jnp.float32)
    monkeypatch.setenv("GAIE_DISABLE_QMM_KERNEL", "1")
    assert (
        np.asarray(q_dot(x, bw, "wo")) == np.asarray(qmm.q_matmul(x, bw))
    ).all()


def test_dequantize_defaults_to_compute_dtype():
    qm = quantize_matrix(jnp.ones((4, 4), jnp.float32))
    assert dequantize(qm).dtype == jnp.bfloat16  # serving default
    assert dequantize(qm, cfg=CFG).dtype == jnp.float32  # cfg wins
    assert dequantize(qm, jnp.float16).dtype == jnp.float16  # explicit wins


# ---------------------------------------------------------------------------
# Load-time blocking through prepare_params (tentpole plumbing)
# ---------------------------------------------------------------------------


def _blocked_leaf_names(params):
    return sorted(
        name
        for name, leaf in params["layers"].items()
        if isinstance(leaf, qmm.BlockedQuantizedMatrix)
    )


def test_prepare_params_blocks_once_at_load():
    raw = init_random_int8_params(CFG, jax.random.PRNGKey(0))
    packed = prepare_params(CFG, raw, None, pack=True)
    before = qmm.BLOCK_EVENTS["count"]
    blocked = prepare_params(CFG, packed, None, matmul_kernel="pallas_w8a8")
    # One blocking event per projection (packed layout: 4), none after.
    assert qmm.BLOCK_EVENTS["count"] - before == 4
    assert _blocked_leaf_names(blocked) == ["w_down", "w_gu", "wo", "wqkv"]
    # Idempotent: re-preparing an already-blocked tree re-tiles nothing.
    again = prepare_params(CFG, blocked, None, matmul_kernel="pallas_w8a8")
    assert qmm.BLOCK_EVENTS["count"] - before == 4
    assert _blocked_leaf_names(again) == ["w_down", "w_gu", "wo", "wqkv"]


def test_prepare_params_xla_path_untouched():
    raw = init_random_int8_params(CFG, jax.random.PRNGKey(0))
    packed = prepare_params(CFG, raw, None, pack=True, matmul_kernel="xla")
    assert _blocked_leaf_names(packed) == []
    with pytest.raises(ValueError, match="matmul_kernel"):
        prepare_params(CFG, packed, None, matmul_kernel="mxu9000")


def test_pallas_w8a8_refuses_float_params():
    """Blocking only applies to int8 serving weights: asking for the
    kernel over float projections is an error, not a silent stay on the
    XLA path — and ``quantize=True`` is what makes it apply."""
    with pytest.raises(ValueError, match="needs int8 projections"):
        prepare_params(CFG, None, None, matmul_kernel="pallas_w8a8")
    params = prepare_params(
        CFG, None, None, quantize=True, pack=True,
        matmul_kernel="pallas_w8a8",
    )
    assert _blocked_leaf_names(params) == ["w_down", "w_gu", "wo", "wqkv"]


# ---------------------------------------------------------------------------
# Greedy decode parity through the FULL scheduler, all admission paths
# ---------------------------------------------------------------------------


def _collect(scheduler, prompt, max_tokens=6, timeout=120, session_id=""):
    tokens: list[int] = []
    done: "queue.Queue[str]" = queue.Queue()
    scheduler.submit(
        Request(
            token_ids=list(prompt),
            sampling=SamplingParams(temperature=0.0, max_tokens=max_tokens),
            on_token=tokens.append,
            on_done=done.put,
            session_id=session_id,
        )
    )
    reason = done.get(timeout=timeout)
    return tokens, reason


@pytest.fixture(scope="module")
def int8_packed_params():
    raw = init_random_int8_params(CFG, jax.random.PRNGKey(0))
    return prepare_params(CFG, raw, None, pack=True)


def _run_paths(params, sched_kw):
    """Drive every admission path greedily; returns the token streams."""
    out = {}
    sched = Scheduler(
        CFG,
        params,
        max_batch=4,
        max_len=128,
        decode_chunk_size=2,
        matmul_kernel="pallas_w8a8",
        **sched_kw,
    )
    assert sched.matmul_kernel == "pallas_w8a8"
    sched.start()
    try:
        out["cold"] = _collect(sched, [1, 2, 3, 4], max_tokens=5)
        # Long prompt vs prefill_chunk_tokens=8 -> chunked prefill.
        out["chunked"] = _collect(sched, list(range(2, 26)), max_tokens=5)
        # Same session prefix again -> parked-prefix / graft path.
        out["graft_warm"] = _collect(
            sched, [7, 8, 9], max_tokens=4, session_id="s1"
        )
        out["graft"] = _collect(
            sched, [7, 8, 9, 10, 11], max_tokens=4, session_id="s1"
        )
    finally:
        sched.stop()
    return out


def test_greedy_parity_fused_vs_xla_all_paths(monkeypatch, int8_packed_params):
    sched_kw = dict(prefill_chunk_tokens=8, prefix_cache="shared")
    monkeypatch.setenv("GAIE_DISABLE_QMM_KERNEL", "1")
    ref = _run_paths(int8_packed_params, sched_kw)
    monkeypatch.delenv("GAIE_DISABLE_QMM_KERNEL")
    monkeypatch.setenv("GAIE_QMM_INTERPRET", "1")
    fused = _run_paths(int8_packed_params, sched_kw)
    assert fused == ref
    assert ref["cold"][0] and ref["chunked"][0]  # non-degenerate streams


def test_no_per_step_retiling_through_scheduler(int8_packed_params):
    """Dispatch-count gate: decoding never re-tiles weights.

    Blocking happens inside Scheduler construction (prepare_params);
    after start, an arbitrary number of requests/steps must leave
    BLOCK_EVENTS flat.
    """
    before = qmm.BLOCK_EVENTS["count"]
    sched = Scheduler(
        CFG,
        int8_packed_params,
        max_batch=2,
        max_len=128,
        decode_chunk_size=2,
        matmul_kernel="pallas_w8a8",
    )
    after_load = qmm.BLOCK_EVENTS["count"]
    assert after_load - before == 4  # wqkv, w_gu, w_down, wo — once each
    sched.start()
    try:
        _collect(sched, [1, 2, 3], max_tokens=6)
        _collect(sched, [4, 5], max_tokens=6)
    finally:
        sched.stop()
    assert qmm.BLOCK_EVENTS["count"] == after_load


def test_scheduler_factory_replicas_get_blocked_layout(int8_packed_params):
    """EnginePool.scheduler_factory twin: autoscale-grown replicas are
    built by the same closure, so they inherit the blocked layout."""
    from generativeaiexamples_tpu.engine.replica import EnginePool

    def factory():
        return Scheduler(
            CFG,
            int8_packed_params,
            max_batch=2,
            max_len=128,
            decode_chunk_size=2,
            matmul_kernel="pallas_w8a8",
        )

    pool = EnginePool([factory()], scheduler_factory=factory)
    pool.start()
    try:
        pool.scale_to(2)
        for rep in pool.replicas:
            assert rep.scheduler.matmul_kernel == "pallas_w8a8"
            assert _blocked_leaf_names(rep.scheduler.params) == [
                "w_down", "w_gu", "wo", "wqkv",
            ]
    finally:
        pool.stop()


def test_scheduler_reports_xla_for_unblocked_params():
    sched = Scheduler(CFG, max_batch=2, max_len=128)
    assert sched.matmul_kernel == "xla"
