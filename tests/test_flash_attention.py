"""Parity tests: Pallas flash attention vs the XLA reference implementation.

Runs the kernel in interpreter mode so the identical code path is validated
hermetically on the CPU test mesh; on a real TPU the same kernel compiles
via Mosaic.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from generativeaiexamples_tpu.ops.attention import gqa_attention
from generativeaiexamples_tpu.ops.flash_attention import (
    flash_gqa_attention,
    use_flash,
)


def _rand_qkv(key, b, s, t, n_q, n_kv, d, dtype=jnp.float32):
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (b, s, n_q, d), dtype)
    k = jax.random.normal(kk, (b, t, n_kv, d), dtype)
    v = jax.random.normal(kv, (b, t, n_kv, d), dtype)
    return q, k, v


@pytest.mark.parametrize("block_k", [128, 256])  # 256 = production default
@pytest.mark.parametrize(
    "b,s,t,n_q,n_kv,d",
    [
        (2, 128, 256, 4, 2, 128),  # prefill-shaped, GQA group 2
        (1, 256, 256, 2, 2, 128),  # MHA (group 1)
        (2, 200, 300, 4, 1, 128),  # ragged: needs padding on s and t
    ],
)
def test_flash_matches_xla_reference(b, s, t, n_q, n_kv, d, block_k):
    key = jax.random.PRNGKey(0)
    q, k, v = _rand_qkv(key, b, s, t, n_q, n_kv, d)
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    kv_lengths = jnp.asarray(
        np.linspace(s // 2, t, b).astype(np.int32)
    )

    ref = gqa_attention(q, k, v, positions, kv_lengths)
    got = flash_gqa_attention(
        q, k, v, positions, kv_lengths, block_q=128, block_k=block_k,
        interpret=True,
    )
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=2e-5)


def test_flash_offset_positions_decode_style():
    """Queries at arbitrary absolute positions (chunked decode)."""
    b, s, t, n_q, n_kv, d = 2, 128, 512, 4, 2, 128
    q, k, v = _rand_qkv(jax.random.PRNGKey(1), b, s, t, n_q, n_kv, d)
    starts = jnp.asarray([100, 37], dtype=jnp.int32)
    positions = starts[:, None] + jnp.arange(s, dtype=jnp.int32)[None, :]
    kv_lengths = starts + s

    ref = gqa_attention(q, k, v, positions, kv_lengths)
    got = flash_gqa_attention(
        q, k, v, positions, kv_lengths, block_q=128, block_k=128,
        interpret=True,
    )
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=2e-5)


def test_flash_fully_masked_rows_are_zero():
    """Padded query rows (position -1) must come out exactly zero."""
    b, s, t, n_q, n_kv, d = 1, 128, 128, 2, 1, 128
    q, k, v = _rand_qkv(jax.random.PRNGKey(2), b, s, t, n_q, n_kv, d)
    positions = jnp.full((b, s), -1, dtype=jnp.int32)
    got = flash_gqa_attention(
        q, k, v, positions, jnp.asarray([t], jnp.int32), interpret=True
    )
    assert not np.isnan(np.asarray(got)).any()
    np.testing.assert_array_equal(np.asarray(got), 0.0)


def test_flash_bf16_storage_dtype():
    b, s, t, n_q, n_kv, d = 1, 128, 256, 4, 2, 128
    q, k, v = _rand_qkv(
        jax.random.PRNGKey(3), b, s, t, n_q, n_kv, d, dtype=jnp.bfloat16
    )
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    ref = gqa_attention(q, k, v, positions, None)
    got = flash_gqa_attention(q, k, v, positions, None, interpret=True)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(got, dtype=np.float32),
        np.asarray(ref, dtype=np.float32),
        atol=0.05,
    )


def test_use_flash_dispatch_predicate():
    from generativeaiexamples_tpu.parallel.mesh import MeshSpec, make_mesh

    one = make_mesh(MeshSpec(tensor=1), devices=jax.devices()[:1])
    assert not use_flash(1, 128, backend="tpu", mesh=one)  # decode: XLA
    assert not use_flash(512, 64, backend="tpu", mesh=one)  # unaligned dim
    assert not use_flash(512, 128, backend="cpu", mesh=one)  # hermetic
    assert use_flash(512, 128, backend="tpu", mesh=one)

    # Multi-device meshes stay on the partitionable XLA path; no mesh
    # means the default device, whatever else the process can see.
    mesh = make_mesh()  # all local (virtual CPU) devices
    assert mesh.size > 1
    assert not use_flash(512, 128, backend="tpu", mesh=mesh)
    assert use_flash(512, 128, backend="tpu", mesh=None)
