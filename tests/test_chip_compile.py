"""The serving path's Pallas kernels, asked of the v5e compiler, each at the
widths of the cells it serves (``tests/chip_compile_lib.py`` says what
the files ``test_chip_compile*.py`` share and why they are several).
"""

import re

import jax
import jax.numpy as jnp
import pytest

from chip_compile_lib import (  # noqa: F401 — ``one_chip`` is the file's fixture
    HD,
    KDA_STATE,
    KH,
    L,
    NQ,
    _compile,
    _spec,
    _state_is_the_kernels_alone,
    one_chip,
)
from generativeaiexamples_tpu.ops import decode_attention as da
from generativeaiexamples_tpu.ops import flash_attention as fa
from generativeaiexamples_tpu.ops import qmm

LLAMA3_8B = (L, KH, NQ)  # planes, KV heads, query heads
PROJECTIONS = {
    "wqkv": (4096, 6144),
    "wo": (4096, 4096),
    "w_gu": (4096, 28672),
    "w_down": (14336, 4096),
}


@pytest.mark.parametrize(
    "batch,window,cache_len,chunk,heads",
    [
        (320, 256, 256, 64, LLAMA3_8B),
        (48, 2048, 2048, 8, LLAMA3_8B),
        # A short context in a longer cache: the first chip run's
        # scheduler tick died here (a 64-wide bf16 scale block).
        (16, 64, 256, 8, LLAMA3_8B),
        # The row walk's other block sizes (lengths are run-time values,
        # ragged or not: what the compiler sees of them is the block):
        # 128 in a cache that whole 256-slot blocks do not tile, and 128
        # under a window no wider, in the serving cache.
        (32, 384, 384, 8, LLAMA3_8B),
        (32, 128, 2048, 8, LLAMA3_8B),
        # Ouro-2.6B's step: 192 planes of 16 KV heads with ONE query head
        # each, which Mosaic refused (``LLO_CHECK ... lhs->ProducesVreg()``)
        # until the block paired it with a zero one (``_MIN_GROUP``); 16
        # slots of 768 rows, the widest and the narrowest decode window.
        (16, 768, 768, 8, (192, 16, 16)),
        (16, 64, 768, 8, (192, 16, 16)),
        # Mistral-7B's step (llama3-8b's heads): 32 slots of 2,048 rows in
        # two groups of 16, 8 KV heads, 8 slots a row in the append buffer.
        (32, 2048, 2048, 8, LLAMA3_8B),
    ],
)
def test_decode_kernel_compiles(one_chip, batch, window, cache_len, chunk, heads):
    """The kernel with the step's fresh rows beside the append buffer: it
    writes them into the buffer itself and hands the four leaves back
    aliased onto their operands, so a caller that gives the leaves away
    gets them back in place (what a whole decode chunk makes of that:
    ``test_chip_compile_llama.py``)."""
    L, KH, NQ = heads
    S = _spec(one_chip)
    cache = S((L, KH, batch, cache_len, HD), jnp.int8)
    scales = S((L, KH, batch, cache_len), jnp.bfloat16)
    ab = S((L, KH, batch, chunk, HD), jnp.int8)
    ab_scales = S((L, KH, batch, chunk), jnp.bfloat16)
    row, row_scales = S((batch, KH, HD), jnp.int8), S((batch, KH), jnp.bfloat16)

    def attn(q, k, v, ks, vs, li, lens, leaves, fresh, slot):
        return da.decode_gqa_attention(
            q, k, v, ks, vs, li, lens,
            append=(leaves, fresh, slot),
            window=window, interpret=False,
        )

    compiled = jax.jit(attn, donate_argnums=(7,)).lower(
        S((batch, NQ, HD), jnp.bfloat16), cache, cache, scales, scales,
        S((), jnp.int32), S((batch,), jnp.int32),
        (ab, ab, ab_scales, ab_scales), (row, row, row_scales, row_scales),
        S((), jnp.int32),
    ).compile()
    call = re.search(r"[^\n]*custom_call_target=\"tpu_custom_call\"[^\n]*", compiled.as_text())[0]
    # Outputs 1-4 are operands 8-11 (after three scalars, q and the cache).
    aliased = re.findall(r"\{(\d)\}: \((\d+), \{\}\)", call.split("output_to_operand_aliasing=")[1])
    assert aliased == [("1", "8"), ("2", "9"), ("3", "10"), ("4", "11")], aliased
    # As tiled the scales' slots are padded to a lane tile: at least this.
    leaf_bytes = 2 * L * KH * batch * chunk * (HD + 2)
    assert compiled.memory_analysis().alias_size_in_bytes >= leaf_bytes


# The layer-kind models' full GQA layers (``ops/gqa_decode.py``): 32 slots
# of 8,192 rows; K-EXAONE's rows of 8 KV heads under a step that verifies
# a draft, Mellum's of 4 under a plain one, at the narrowest and the
# widest decode window (blocks of 512 either way).
@pytest.mark.parametrize("window", [512, 8192])
@pytest.mark.parametrize("s,n_q,n_kv", [(2, 64, 8), (1, 32, 4)], ids=["k-exaone", "mellum"])
def test_row_walk_kernel_compiles_at_both_cells_widths(one_chip, s, n_q, n_kv, window, monkeypatch):
    from generativeaiexamples_tpu.ops import gqa_decode

    S = _spec(one_chip)
    batch, rows = 32, 8192
    row = S((batch, rows, n_kv * HD), jnp.bfloat16)
    monkeypatch.setattr(gqa_decode, "platform_of", lambda mesh: "tpu")
    assert gqa_decode.use_row_walk(
        s=s, q_dtype=jnp.bfloat16, rows_dtype=row.dtype, width=n_kv * HD, head_dim=HD,
        rows=rows, window=window, batch=batch, n_q=n_q,
    )
    block = da._block_t(rows, window)
    held = gqa_decode._walk_vmem_bytes(block, n_kv * HD, 16, s * n_q, HD)
    assert block == 512 and held <= qmm._VMEM_BUDGET_BYTES // 4

    def attn(q, k, v, pos, lens):
        return gqa_decode.attend_rows_walk(
            q, k, v, pos, lens, n_kv=n_kv, window=window, interpret=False
        )

    # The scoped limit handed to Mosaic is the shared budget: a kernel
    # that held more would be refused here.
    _compile(
        attn, S((batch, s, n_q, HD), jnp.bfloat16), row, row,
        S((batch, s), jnp.int32), S((batch,), jnp.int32),
    )


# A decode step over latent rows (``ops/mla_decode.py``), 16 slots:
# Mistral-Small-4's rows of 384 columns over a latent of 256 under one
# query a row, dots3-note-prev's of 640 over 512 under a token and its
# draft (what a verify step would hand it), LongCat-Flash's of 640 over 512
# under one query of 64 heads a row (no indexer in front: the step walks a
# row's every block), at the narrowest and the widest decode window (blocks
# of 2,048 either way).
@pytest.mark.parametrize("window", [2048, 32768])
@pytest.mark.parametrize(
    "s,heads,rank,nope,width,rows",
    [(1, 32, 256, 64, 384, 32768), (2, 128, 512, 128, 640, 16384), (1, 64, 512, 128, 640, 16384)],
    ids=["mistral-small-4", "dots3", "longcat-flash"],
)
def test_latent_decode_kernel_compiles_at_both_families_widths(
    one_chip, s, heads, rank, nope, width, rows, window, monkeypatch
):
    from generativeaiexamples_tpu.ops import gqa_decode, mla_decode

    S = _spec(one_chip)
    batch, rope, v_dim, window = 16, 64, 128, min(window, rows)
    leaf = S((batch, rows, width), jnp.bfloat16)
    monkeypatch.setattr(gqa_decode, "platform_of", lambda mesh: "tpu")
    assert mla_decode.use_latent_decode(
        s=s, q_dtype=jnp.bfloat16, rows_dtype=leaf.dtype, width=width, rank=rank, heads=heads,
        rows=rows, window=window, block=2048,
    )
    assert mla_decode._vmem_bytes(s * heads, 2048, width, rank) <= qmm._VMEM_BUDGET_BYTES // 2

    def attn(q_nope, q_rope, latent, w_kvb, pos, lens, slot):
        return mla_decode.attend_latent_decode(
            q_nope, q_rope, latent, w_kvb, pos, lens, slot, rank=rank, nope=nope, v_dim=v_dim,
            window=window, block=2048, interpret=False,
        )

    compiled = _compile(
        attn, S((batch, s, heads, nope), jnp.bfloat16), S((batch, s, heads, rope), jnp.bfloat16),
        leaf, S((rank, heads * (nope + v_dim)), jnp.bfloat16), S((batch, s), jnp.int32),
        S((batch,), jnp.int32), S((batch,), jnp.int32),
    )
    text = compiled.as_text()
    assert len(re.findall(r"custom_call_target=\"tpu_custom_call\"[^\n]*latent_decode_attention", text)) == 1
    # The leaf stays where it lies: nothing of its size is made.
    assert compiled.memory_analysis().temp_size_in_bytes < 4_000_000


def test_latent_chunk_kernel_compiles_at_longcat_flashs_widths(one_chip, monkeypatch):
    """A prefill chunk's walk over latent rows (``ops/mla_chunk.py``) at the
    one set of widths no chunk PROGRAM of these files holds: LongCat-Flash's
    64 heads over rows of 640 and a latent of 512, 8 rows of 256 queries on
    16 slots of 16,384, no indexer's mask, 8 heads a grid step (the whole
    8-row program, eight such walks beside two dense MLPs a layer, compiled
    here with 1.19 GB of temporaries: PERF.md, PR 57)."""
    from generativeaiexamples_tpu.ops import gqa_decode, mla_chunk

    S = _spec(one_chip)
    b, s, heads, rank, nope, rope, v_dim, width, rows = 8, 256, 64, 512, 128, 64, 128, 640, 16384
    leaf = S((16, rows, width), jnp.bfloat16)
    monkeypatch.setattr(gqa_decode, "platform_of", lambda mesh: "tpu")
    assert mla_chunk.use_latent_chunk(
        s=s, q_dtype=jnp.bfloat16, rows_dtype=leaf.dtype, width=width, rank=rank, nope=nope,
        v_dim=v_dim, heads=heads, rows=rows, window=rows, block=1024, masked=False,
    )

    def attn(q_nope, q_rope, latent, w_kvb, pos, lens, slot):
        return mla_chunk.attend_latent_chunk(
            q_nope, q_rope, latent, w_kvb, pos, lens, slot, rank=rank, nope=nope, v_dim=v_dim,
            window=rows, block=1024, interpret=False,
        )

    compiled = _compile(
        attn, S((b, s, heads, nope), jnp.bfloat16), S((b, s, heads, rope), jnp.bfloat16), leaf,
        S((rank, heads * (nope + v_dim)), jnp.bfloat16), S((b, s), jnp.int32), S((b,), jnp.int32),
        S((b,), jnp.int32),
    )
    assert len(re.findall(r"custom_call_target=\"tpu_custom_call\"[^\n]*latent_chunk_attention", compiled.as_text())) == 1


@pytest.mark.parametrize("batch,s,t", [(8, 1536, 1536), (16, 256, 2048)])
def test_flash_kernel_compiles(one_chip, batch, s, t):
    S = _spec(one_chip)

    def attn(q, k, v, pos, lens):
        return fa.flash_gqa_attention(q, k, v, pos, lens, interpret=False)

    _compile(
        attn,
        S((batch, s, NQ, HD), jnp.bfloat16),
        S((batch, t, KH, HD), jnp.bfloat16),
        S((batch, t, KH, HD), jnp.bfloat16),
        S((batch, s), jnp.int32),
        S((batch,), jnp.int32),
    )


@pytest.mark.parametrize("m", [32, 320])
@pytest.mark.parametrize("name", sorted(PROJECTIONS))
def test_w8a8_kernel_compiles_where_the_gate_admits(one_chip, name, m):
    """What ``use_qmm_kernel`` admits is what Mosaic accepts: at decode
    batches up to 320 rows that is all four projections."""
    S = _spec(one_chip)
    k, n = PROJECTIONS[name]
    bn = qmm.DEFAULT_BLOCK_N
    assert (
        qmm._kernel_vmem_bytes(m, k, n, bn, 2) <= qmm._VMEM_BUDGET_BYTES
    ), "the gate sends this shape to the XLA twin"

    def matmul(xq, a_scale, tiles, w_scale):
        return qmm._qmm_pallas(
            xq, a_scale, tiles, w_scale, jnp.bfloat16, False
        )

    _compile(
        matmul,
        S((m, k), jnp.int8),
        S((m, 1), jnp.float32),
        S((n // bn, k, bn), jnp.int8),
        S((n // bn, 1, bn), jnp.float32),
    )


# (hidden, expert width, router outputs, experts held, choices a token,
# group limit, score, the expert's form, the tiles ``ops.moe._tiles`` must
# pick for the first product (gate-up, or ``relu2``'s up) and for down)
EXPERT_WIDTHS = {
    "ling": (2560, 768, 512, 128, 8, (8, 4), "sigmoid", "swiglu", (128, 2560, 768), (128, 768, 2560)),
    "mellum": (2304, 896, 64, 64, 8, (1, 1), "softmax", "swiglu", (128, 2304, 896), (128, 896, 2304)),
    "exaone": (6144, 2048, 128, 16, 8, (1, 1), "sigmoid", "swiglu", (128, 6144, 256), (128, 2048, 1024)),
    "zaya": (2048, 2048, 16, 16, 1, (1, 1), "softmax", "swiglu", (128, 2048, 1024), (128, 2048, 1024)),
    "mistral4": (4096, 2048, 128, 32, 4, (1, 1), "softmax", "swiglu", (128, 4096, 512), (128, 2048, 1024)),
    "nemotron": (1024, 2688, 512, 128, 22, (1, 1), "sigmoid", "relu2", (128, 1024, 2688), (128, 2688, 1024)),
}


@pytest.mark.parametrize("family", sorted(EXPERT_WIDTHS))
@pytest.mark.parametrize("tokens", [32, 256])
def test_grouped_expert_products_compile_at_ling_widths(one_chip, tokens, family, monkeypatch):
    """``ops/moe.py``'s sorted dispatch at the published widths of the
    expert families served: ling-3.0-flash-vl-l7e128 (128 experts held of
    512, hidden 2,560, expert width 768, sigmoid scores, 4 of 8 groups),
    mellum2-12b-a2.5b-l12 (all 64, hidden 2,304, width 896 = 7 x 128,
    softmax, no groups), k-exaone-236b-a23b-l5e16 (16 held of 128,
    hidden 6,144, width 2,048, sigmoid scores with a bias, one group),
    8 a token each; zaya1-8b-l20 (all 16, 2,048 / 2,048, softmax, one a
    token), mistral-small-4-119b-l6e32 (32 held of 128, 4,096 / 2,048,
    4 a token) and nemotron-3-super-120b-a12b-l11e128 (128 held of 512,
    ``relu2`` experts of 2,688 in a latent of 1,024, 22 a token); a decode
    step's 32 rows and a prefill chunk's 256.  The grouped products are
    megablox's ``gmm``, which asks Mosaic for no more VMEM than its
    default: this holds the tiles ``ops.moe._tiles`` picks (whole K, a
    weight tile of 3-5.5 MB) to what Mosaic accepts, and to what the
    sweep on the chip chose (PERF.md section 6, PR 45)."""
    from generativeaiexamples_tpu.ops import moe

    # The gate asks the default backend, which is the CPU here.
    monkeypatch.setattr(moe, "platform_of", lambda mesh: "tpu")
    spec = _spec(one_chip)
    D, F, E, held, k, (n_group, topk_group), score, act, first, down = EXPERT_WIDTHS[family]
    wide = F if act == "relu2" else 2 * F
    assert moe._tiles(D, wide, moe.ROW_TILE, 2) == first and moe._tiles(F, D, moe.ROW_TILE, 2) == down

    def layer(x, w_router, bias, w_first, w_down_e, valid):
        idx, w = moe.route(
            x, w_router, bias if score == "sigmoid" else None, k=k, n_group=n_group,
            topk_group=topk_group, norm_topk=k > 1, scale=2.5, score=score,
        )
        lp = {"w_up_e" if act == "relu2" else "w_gu_e": w_first, "w_down_e": w_down_e}
        return moe.expert_mlp(x, idx, w, valid, lp, offset=0, held=held, act=act)

    _compile(
        layer,
        spec((tokens, D), jnp.bfloat16),
        spec((D, E), jnp.bfloat16),
        spec((E,), jnp.float32),
        spec((held, D, wide), jnp.bfloat16),
        spec((held, F, D), jnp.bfloat16),
        spec((tokens,), jnp.bool_),
    )


def test_kda_step_kernel_compiles_at_the_cells_widths(one_chip, monkeypatch):
    from generativeaiexamples_tpu.ops import kda

    S = _spec(one_chip)
    b, H, K, V = KDA_STATE
    monkeypatch.setattr(kda, "platform_of", lambda mesh: "tpu")
    assert kda.use_step_kernel(state_dtype=jnp.float32, k_dim=K, v_dim=V, heads=H)
    # A whole row a grid step: in and out, each double, a quarter of the
    # scoped limit handed to Mosaic.
    hb = kda._heads_a_step(H, K, V)
    assert hb == H and 4 * hb * K * V * 4 <= qmm._VMEM_BUDGET_BYTES // 4

    def step(q, k, v, g, beta, state, live):
        return kda.kda_step_rows(q, k, v, g, beta, state, live, interpret=False)

    vec = S((b, H, K), jnp.float32)
    compiled = jax.jit(step, donate_argnums=(5,)).lower(
        vec, vec, vec, vec, S((b, H), jnp.float32), S(KDA_STATE, jnp.float32), S((b,), jnp.bool_)
    ).compile()
    _state_is_the_kernels_alone(compiled.as_text(), calls=1)
    # The leaf goes in and comes out as one buffer.
    assert compiled.memory_analysis().alias_size_in_bytes == 4 * b * H * K * V
    assert compiled.memory_analysis().temp_size_in_bytes < 4_000_000


@pytest.mark.parametrize("rows", [1, 2, 8])
def test_kda_chunk_kernel_compiles_at_the_cells_widths(one_chip, rows, monkeypatch):
    """``ops/kda.py::kda_chunk_rows`` for a chunk program's 1, 2 and 8 rows
    of 256 tokens: a sublane tile of heads a grid step, cut from q, k, v
    and o where they lie, inside half the VMEM budget, and the rows' state
    in and out of the call as one buffer."""
    from generativeaiexamples_tpu.ops import kda

    S = _spec(one_chip)
    _, H, K, V = KDA_STATE
    s = 256
    monkeypatch.setattr(kda, "platform_of", lambda mesh: "tpu")
    assert kda.use_chunk_kernel(state_dtype=jnp.float32, k_dim=K, v_dim=V, heads=H, s=s)
    hb = kda._heads_a_chunk(H)
    assert hb == 8 and kda._chunk_vmem_bytes(hb, s, K, V, H) <= qmm._VMEM_BUDGET_BYTES // 2

    def chunk(q, k, v, g, beta, state, n_valid):
        return kda.kda_chunk_rows(q, k, v, g, beta, state, n_valid, interpret=False)

    wide = S((rows, s, H, K), jnp.float32)
    compiled = jax.jit(chunk, donate_argnums=(5,)).lower(
        wide, wide, wide, wide, S((rows, s, H), jnp.float32), S((rows, H, K, V), jnp.float32),
        S((rows,), jnp.int32),
    ).compile()
    assert len(re.findall(r"custom_call_target=\"tpu_custom_call\"[^\n]*kda_chunk_rows", compiled.as_text())) == 1
    assert compiled.memory_analysis().alias_size_in_bytes == 4 * rows * H * K * V
