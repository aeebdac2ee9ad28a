"""The serving path's Pallas kernels, asked of the v5e compiler.

Interpret mode cannot see what Mosaic refuses — a block shape off the
(8, 128) tiling, a dynamic index on a packed sublane, more scoped VMEM
than the limit — so every kernel of the main path is compiled here at
llama3-8b widths for a chip that is described, not attached
(``on-chip-measurement`` guide, section 2).  Nothing runs; a compile
that passes is not a chip run.

All of it lives in this one file: the worker that is handed the file is
the only process that loads the TPU's library, and it does so inside the
module fixture — never while a module is imported.
"""

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from generativeaiexamples_tpu.ops import decode_attention as da
from generativeaiexamples_tpu.ops import flash_attention as fa
from generativeaiexamples_tpu.ops import qmm

# llama3-8b: 32 layers, 32 q / 8 kv heads, head_dim 128.
L, KH, NQ, HD = 32, 8, 32, 128
PROJECTIONS = {
    "wqkv": (4096, 6144),
    "wo": (4096, 4096),
    "w_gu": (4096, 28672),
    "w_down": (14336, 4096),
}


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — any failure to describe skips
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache
    # but cannot be read back without the chip: keep it off here.
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    # conftest forces "highest" matmul precision for the CPU's sake; the
    # chip runs the default, and Mosaic refuses an fp32-precision bf16 dot.
    precision_was = jax.config.jax_default_matmul_precision
    jax.config.update("jax_default_matmul_precision", None)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_default_matmul_precision", precision_was)
    jax.config.update("jax_enable_compilation_cache", cache_was)
    compilation_cache.reset_cache()


def _compile(fn, *shapes):
    """Compile for the described chip; the kernel must be in the program."""
    compiled = jax.jit(fn).lower(*shapes).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _spec(sharding):
    return lambda shape, dtype: jax.ShapeDtypeStruct(
        shape, dtype, sharding=sharding
    )


@pytest.mark.parametrize(
    "batch,window,cache_len,chunk",
    [
        (320, 256, 256, 64),
        (48, 2048, 2048, 8),
        # A short context in a longer cache: the first chip run's
        # scheduler tick died here (a 64-wide bf16 scale block).
        (16, 64, 256, 8),
        # The row walk's other block sizes (lengths are run-time values,
        # ragged or not: what the compiler sees of them is the block):
        # 128 in a cache that whole 256-slot blocks do not tile, and 128
        # under a window no wider, in the serving cache.
        (32, 384, 384, 8),
        (32, 128, 2048, 8),
    ],
)
def test_decode_kernel_compiles(one_chip, batch, window, cache_len, chunk):
    S = _spec(one_chip)
    cache = S((L, KH, batch, cache_len, HD), jnp.int8)
    scales = S((L, KH, batch, cache_len), jnp.bfloat16)
    ab = S((L, KH, batch, chunk, HD), jnp.int8)
    ab_scales = S((L, KH, batch, chunk), jnp.bfloat16)

    def attn(q, k, v, ks, vs, li, lens, kab, vab, ksab, vsab, count):
        return da.decode_gqa_attention(
            q, k, v, ks, vs, li, lens,
            append=(kab, vab, ksab, vsab, count),
            window=window, interpret=False,
        )

    _compile(
        attn,
        S((batch, NQ, HD), jnp.bfloat16), cache, cache, scales, scales,
        S((), jnp.int32), S((batch,), jnp.int32),
        ab, ab, ab_scales, ab_scales, S((), jnp.int32),
    )


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


# (configuration under benchmarks/configs/, rows of the largest group its
# rule allows, the widest window of its slots)
GROUP_PROGRAMS = {
    "mellum": ("mellum2-12b-a2.5b-l12", 4, 8192),
    "ling": ("ling-3.0-flash-vl-l7e128", 8, 2048),
    "exaone": ("k-exaone-236b-a23b-l5e16", 8, 8192),
    "mistral4": ("mistral-small-4-119b-l6e32", 8, 32768),
    "zaya": ("zaya1-8b-l20", 8, 8192),
    "dots3_note": ("dots3-note-prev-l6e32", 8, 16384),
}
# What Mellum's cell has to spare beside its weights, slots and snapshots
# (peak 15.19 of the 16.91 GB the build sees, less the reference check's
# blocks: PERF.md section 4).  K-EXAONE's cut holds 9.09 GB of weights and
# 2.2 GB of state: 5 GB to spare, of which its group of 8 rows of 6,144
# (a full layer's scores of 64 heads are 537 MB a row) may take half.
SPARE_BYTES = 1_400_000_000
# Mistral-Small-4's cut holds 10.85 GB of weights and 2.42 GB of latent
# rows: 3.6 GB to spare.  Its group of 8 rows reads each row's blocks from
# the state in place (no window is gathered), a block of 1,024 keys at a
# time: the largest temporaries are a block's float32 scores (32 heads x
# 256 x 1,024: 33.6 MB) and the experts' combine.
# ZAYA1's cut holds 9.38 GB of weights and 5.37 GB of K/V rows: 2.1 GB to
# spare.  Its group of 8 rows gathers each row's window of 256-wide rows (8
# query heads' scores are 67 MB a row at 8,192); the compiler here counts
# 0.26 GB for the chunks alone.
# dots3-note-prev's cut holds 10.02 GB of weights and 1.27 GB of state:
# 5.6 GB to spare.  Its group of 8 rows reads each row's latent rows and
# index keys in place, a block of 1,024 at a time (a block's float32 scores
# of 128 heads x 256 queries are 134 MB, its expansion 67 MB; a row's
# index scores and their ordered bits 16.8 MB each); the compiler here
# counts 0.63 GB.
SPARE_BY_FAMILY = {
    "exaone": 2_500_000_000, "mistral4": 400_000_000, "zaya": 800_000_000,
    "dots3_note": 1_000_000_000,
}


_CHUNK_PROGRAMS: dict = {}  # what ``_chunk_program`` compiled, by what it was asked


def _chunk_program(one_chip, config: str, rows: int, window: int):
    """``_prefill_suffix_rows`` of a layer-kind configuration under
    benchmarks/configs/, compiled for ``rows`` chunks under ``window``
    against the cell's own slot state.  Returns (compiled, serving, engine).
    A program is compiled once for the tests that read it (half a minute
    each), apart by what the attention gates believe of the platform."""
    from generativeaiexamples_tpu.ops import gqa_decode, kda

    key = (config, rows, window, gqa_decode.platform_of(None), kda.platform_of(None))
    if key not in _CHUNK_PROGRAMS:
        _CHUNK_PROGRAMS[key] = _compile_chunk_program(one_chip, config, rows, window)
    return _CHUNK_PROGRAMS[key]


def _compile_chunk_program(one_chip, config: str, rows: int, window: int):
    import json
    from pathlib import Path

    from generativeaiexamples_tpu.engine.scheduler import make_prefill_suffix_rows
    from generativeaiexamples_tpu.engine.serving_models import HybridServing
    from generativeaiexamples_tpu.models import hybrid

    configs = Path(__file__).resolve().parents[1] / "benchmarks" / "configs"
    model = json.loads((configs / f"{config}.json").read_text())
    engine = model["engine"]
    max_len, chunk = int(engine["max_len"]), int(engine["prefill_chunk_tokens"])
    cfg = hybrid.from_hf_config(
        model, max_len=max_len, kv_dtype=engine["kv_dtype"], draft=engine.get("draft", ""),
    )
    serving = HybridServing(cfg, None, max_len)

    def described(make):
        return jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
            jax.eval_shape(make),
        )

    spec = _spec(one_chip)
    ints, floats = spec((rows,), jnp.int32), spec((rows,), jnp.float32)
    compiled = make_prefill_suffix_rows(serving).lower(
        described(lambda: hybrid.init_params(cfg, jax.random.PRNGKey(0))),
        described(lambda: hybrid.init_state(cfg, int(engine["max_batch"]), max_len)),
        spec((rows, chunk), jnp.int32), ints, ints, ints,
        spec((2,), jnp.uint32), (floats, floats, ints), window,
    ).compile()
    return compiled, serving, engine


@pytest.mark.parametrize("family", sorted(GROUP_PROGRAMS))
def test_the_chunks_of_several_slots_compile_as_one_program(one_chip, family, monkeypatch):
    """``_prefill_suffix_rows`` (the scheduler's program for the prefill
    chunks of several slots) at the published widths, for the largest
    group and the widest window each cell's family holds, against the
    cell's own slot state (32 slots of 8,192 and of 2,048 rows): the
    grouped products are in it, and its temporaries (a full layer's
    float32 scores are 268 MB a row at 8,192 where the chunk kernel's
    gate refuses, as it does here, which is why the rows then attend one
    after the other) stay under what Mellum's cell has to spare."""
    from generativeaiexamples_tpu.ops import kda, moe

    monkeypatch.setattr(moe, "platform_of", lambda mesh: "tpu")
    monkeypatch.setattr(kda, "platform_of", lambda mesh: "tpu")  # Ling's scan: the chunk kernel
    config, rows, window = GROUP_PROGRAMS[family]
    compiled, serving, engine = _chunk_program(one_chip, config, rows, window)
    max_len, chunk = int(engine["max_len"]), int(engine["prefill_chunk_tokens"])
    assert serving.chunks_per_program(chunk) == rows and window == max_len
    if family in ("mistral4", "dots3_note"):
        assert serving.chunk_windows(chunk) == (max_len,)  # the one window it is built for
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    spare = SPARE_BY_FAMILY.get(family, SPARE_BYTES)
    assert compiled.memory_analysis().temp_size_in_bytes < spare
    if family == "mistral4":
        _no_window_sized_temporaries(text, slots=int(engine["max_batch"]), rows=rows, window=window)


# (layers that attend over rows a position: ``full`` or ``cca``, a
# prediction module's block among them; the width of a K/V row)
ROW_LAYERS = {"mellum": (3, 4 * HD), "exaone": (2, 8 * HD), "zaya": (20, 2 * HD)}


@pytest.mark.parametrize("rows", ["one_row", "largest_group"])
@pytest.mark.parametrize("family", sorted(ROW_LAYERS))
def test_a_chunk_program_attends_over_its_slots_rows_where_they_lie(one_chip, family, rows, monkeypatch):
    """The one-row and the largest chunk program of the three cells whose
    layers hold K/V rows a position, at the widest window, with
    ``ops/gqa_decode.py``'s gates believing they are on the chip: every
    ``full`` / ``cca`` layer's attention (K-EXAONE's module's block too) is
    the chunk kernel over the slots' leaves as they lie.  No leaf is
    copied (the one-row program of the ``cca`` family re-laid every V leaf
    out, ``copy(bf16[32,8192,256])`` twenty times: PERF.md, PR 40), no
    window of one is gathered or written back, and no float32 scores of
    (heads, 256, 8,192) are made."""
    from generativeaiexamples_tpu.ops import gqa_decode, moe

    monkeypatch.setattr(moe, "platform_of", lambda mesh: "tpu")
    monkeypatch.setattr(gqa_decode, "platform_of", lambda mesh: "tpu")
    config, largest, window = GROUP_PROGRAMS[family]
    n = 1 if rows == "one_row" else largest
    compiled, serving, engine = _chunk_program(one_chip, config, n, window)
    assert serving.rows_in_place
    layers, width = ROW_LAYERS[family]
    slots, chunk = int(engine["max_batch"]), int(engine["prefill_chunk_tokens"])
    text = compiled.as_text()
    assert text.count("gqa_rows_chunk_attention") >= layers
    assert not re.search(rf"= bf16\[{slots},{window},{width}\]\S* copy\(", text)
    assert not re.search(rf"bf16\[{n},{window},{width}\]", text)  # no group's windows
    assert not re.search(rf"f32\[(?:\d+,)*{chunk},{window}\]", text)  # no layer's scores
    memory = compiled.memory_analysis()
    print(family, rows, "chunk program temporaries", memory.temp_size_in_bytes)
    # 0.09-0.32 GB here, 0.51 for K-EXAONE's eight rows of 6,144 (the
    # experts' dispatch and combine): no window-sized buffer is among them.
    assert memory.temp_size_in_bytes < 640_000_000
    # The slots' rows go through in place.
    assert memory.alias_size_in_bytes >= 2 * layers * slots * window * width * 2 * 0.99


# (``mla`` layers whose chunk walks blocks of latent rows, query heads)
LATENT_LAYERS = {"mistral4": (6, 32), "dots3_note": (3, 128)}


@pytest.mark.parametrize("family", sorted(LATENT_LAYERS))
def test_a_latent_chunk_program_keeps_a_blocks_scores_on_the_chip(one_chip, family, monkeypatch):
    """The largest chunk program of the two latent families (8 rows; H 32
    over slots of 32,768, H 128 over slots of 16,384 with the indexer's
    selection as the mask), with ``ops/mla_chunk.py``'s gate believing it
    is on the chip: every ``mla`` layer's walk is the kernel over the
    slots' leaf as it lies, and nothing of heads x queries x block (a
    block's float32 scores, 33.6 and 134 MB, which XLA's form wrote and
    read back: PERF.md, PR 48) nor a block's expansion is made outside it."""
    from generativeaiexamples_tpu.ops import dispatch, gqa_decode, moe

    monkeypatch.setattr(moe, "platform_of", lambda mesh: "tpu")
    monkeypatch.setattr(gqa_decode, "platform_of", lambda mesh: "tpu")
    config, rows, window = GROUP_PROGRAMS[family]
    dispatch.TAKEN.clear()
    compiled, serving, engine = _chunk_program(one_chip, config, rows, window)
    layers, heads = LATENT_LAYERS[family]
    slots, chunk = int(engine["max_batch"]), int(engine["prefill_chunk_tokens"])
    sites = {s: p for s, p in dispatch.TAKEN.items() if s.startswith("attn_latent_chunk")}
    assert sites and set(sites.values()) == {"pallas"}, sites
    text = compiled.as_text()
    assert text.count("latent_chunk_attention") >= layers
    block = serving.cfg.latent_block
    assert not re.search(rf"(?:f32|bf16)\[(?:\d+,)?{heads},{chunk},{block}\]", text)  # a block's scores
    sz = serving.cfg.latent_sizes("mla")
    kv = sz.qk_nope_head_dim + sz.v_head_dim  # a block's expansion
    assert not re.search(rf"bf16\[(?:\d+,)?{block},(?:{heads},{kv}|{heads * kv})\]", text)
    _no_window_sized_temporaries(
        text, slots=slots, rows=rows, window=window, H=heads, width=serving.cfg.latent_width
    )
    memory = compiled.memory_analysis()
    print(family, "latent chunk program temporaries", memory.temp_size_in_bytes)
    assert memory.temp_size_in_bytes < SPARE_BY_FAMILY[family]


# (window layers, query heads, rows of a ring, a chunk's tokens)
RING_LAYERS = {"mellum": (9, 32, 1024, 256), "exaone": (4, 64, 128, 256)}


@pytest.mark.parametrize("rows", ["one_row", "largest_group"])
@pytest.mark.parametrize("family", sorted(RING_LAYERS))
def test_a_chunk_program_attends_over_its_rings_in_vmem(one_chip, family, rows, monkeypatch):
    """The same programs (Mellum's and K-EXAONE's one-row and largest chunk
    program at the widest window, the gates believing they are on the
    chip): every ``window`` layer's attention is the ring kernel, and no
    float32 scores of a chunk's queries against a ring (Mellum:
    ``[.,32,256,1024]``; K-EXAONE ``[.,64,256,128]``), against the ring and
    its own rows side by side (``[.,256,1280]``, ``[.,256,384]``) or
    against its own rows (``[.,256,256]``) are left in the compiled text."""
    from generativeaiexamples_tpu.ops import gqa_decode, moe

    monkeypatch.setattr(moe, "platform_of", lambda mesh: "tpu")
    monkeypatch.setattr(gqa_decode, "platform_of", lambda mesh: "tpu")
    config, largest, window = GROUP_PROGRAMS[family]
    n = 1 if rows == "one_row" else largest
    compiled, serving, engine = _chunk_program(one_chip, config, n, window)
    layers, heads, ring, chunk = RING_LAYERS[family]
    assert len(serving.cfg.layers_of("window")) == layers
    assert serving.cfg.ring_rows(window) == ring and int(engine["prefill_chunk_tokens"]) == chunk
    text = compiled.as_text()
    assert text.count("gqa_ring_chunk_attention") >= layers
    kh = serving.cfg.n_kv_heads
    for keys in (ring, ring + chunk, chunk):  # XLA's form has them by head and by KV head
        assert not re.search(rf"f32\[(?:\d+,)*{heads},{chunk},{keys}\]", text), keys
        assert not re.search(rf"f32\[(?:\d+,)*{kh},{heads // kh},{chunk},{keys}\]", text), keys


@pytest.mark.parametrize("family", sorted(RING_LAYERS))
def test_a_decode_chunk_keeps_the_rings_wide_form(one_chip, family, monkeypatch):
    """Mellum's and K-EXAONE's decode chunk (8 steps over 32 slots, one
    query a row or a token and its draft), lowered with the gates believing
    they are on the chip: the full layers walk their rows, and a window
    layer is ``gqa.attend_ring``'s wide form, as before the ring kernel:
    its name is nowhere in a decode step."""
    import json
    from pathlib import Path

    from generativeaiexamples_tpu.engine.serving_models import HybridServing
    from generativeaiexamples_tpu.models import hybrid
    from generativeaiexamples_tpu.ops import dispatch, gqa_decode, moe

    monkeypatch.setattr(moe, "platform_of", lambda mesh: "tpu")
    monkeypatch.setattr(gqa_decode, "platform_of", lambda mesh: "tpu")
    configs = Path(__file__).resolve().parents[1] / "benchmarks" / "configs"
    model = json.loads((configs / f"{GROUP_PROGRAMS[family][0]}.json").read_text())
    engine = model["engine"]
    max_len, b, steps = int(engine["max_len"]), int(engine["max_batch"]), int(engine["decode_chunk_size"])
    cfg = hybrid.from_hf_config(
        model, max_len=max_len, kv_dtype=engine["kv_dtype"], draft=engine.get("draft", "")
    )
    serving = HybridServing(cfg, None, max_len)

    def described(make):
        return jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
            jax.eval_shape(make),
        )

    spec = _spec(one_chip)
    ints, floats, flags = spec((b,), jnp.int32), spec((b,), jnp.float32), spec((b,), jnp.bool_)
    drafting = (spec((1, b), jnp.int32), flags, ints, flags) if cfg.draft else ()
    dispatch.TAKEN.clear()
    text = serving.make_decode_chunk().lower(
        described(lambda: hybrid.init_params(cfg, jax.random.PRNGKey(0))),
        described(lambda: hybrid.init_state(cfg, b, max_len)),
        ints, ints, spec((2,), jnp.uint32), floats, floats, ints, steps, max_len, flags,
        *drafting,
    ).as_text()
    assert "gqa_rows_decode_attention" in text and "gqa_ring_chunk_attention" not in text
    ring = RING_LAYERS[family][2]
    s = 2 if cfg.draft else 1
    assert dispatch.TAKEN[f"attn_window b={b} s={s} t={ring}"] == "xla"
    assert not any("attn_window_chunk" in site for site in dispatch.TAKEN)


def _no_window_sized_temporaries(
    text: str, *, slots: int, rows: int, window: int, H: int = 32, width: int = 384
) -> None:
    """Nothing of a window's size is made in a program of the latent
    family: no scores of heads x queries x window, no expansion of a
    window through ``W_kvb`` (window x heads x 192), and the slots' state
    (slots, window, 384) is a parameter, scattered into and handed on, but
    never copied, nor is a group's window of it gathered."""
    s = 256
    assert not re.search(rf"(?:f32|bf16)\[(?:\d+,)?{H},{s},{window}\]", text)
    assert not re.search(rf"bf16\[(?:\d+,)?{window},{H},(?:192|64|128)\]", text)
    assert not re.search(rf"bf16\[(?:\d+,)?{window},{H * 192}\]", text)
    assert not re.search(rf"= bf16\[{slots},{window},{width}\]\S* copy\(", text)
    assert not re.search(rf"= bf16\[{rows},{window},{width}\]", text)


@pytest.mark.parametrize("rows", [1, 2])
def test_mixtrals_chunk_program_holds_no_copy_of_an_expert_leaf(one_chip, rows, monkeypatch):
    """``_prefill_suffix_rows`` of ``LlamaServing`` at mixtral-8x7b-l4's
    published widths (4 layers of 8 experts 4,096 x 14,336 in bf16, int8
    dense projections and K/V, 32 slots of 2,048), the two programs its
    family holds (1 and 2 rows x window 2,048): the sorted dispatch's
    grouped products lower through Mosaic, three a layer; and the layer
    loop hands them the expert stacks whole (a bitcast of the parameter,
    (4, 8, ...) viewed as (32, ...)): no copy, slice or fusion result has
    an expert leaf's size or a layer's share of it, 2.8 GB that a chunk
    of ~17 ms cannot pay for."""
    import json
    import sys
    from pathlib import Path

    from generativeaiexamples_tpu.engine.scheduler import make_prefill_suffix_rows
    from generativeaiexamples_tpu.engine.serving_models import LlamaServing
    from generativeaiexamples_tpu.models import llama
    from generativeaiexamples_tpu.ops import moe

    monkeypatch.setattr(moe, "platform_of", lambda mesh: "tpu")
    configs = Path(__file__).resolve().parents[1] / "benchmarks" / "configs"
    model = json.loads((configs / "mixtral-8x7b-l4.json").read_text())
    engine = model["engine"]
    max_len, chunk = int(engine["max_len"]), int(engine["prefill_chunk_tokens"])
    cfg = llama.LlamaConfig(
        vocab_size=model["vocab_size"], d_model=model["hidden_size"],
        n_layers=model["num_hidden_layers"], n_heads=model["num_attention_heads"],
        n_kv_heads=model["num_key_value_heads"], head_dim=model["head_dim"],
        d_ff=model["intermediate_size"], rope_theta=model["rope_theta"],
        norm_eps=model["rms_norm_eps"], max_seq_len=max_len, dtype="bfloat16",
        kv_dtype=engine["kv_dtype"], n_experts=model["num_local_experts"],
        n_experts_per_tok=model["num_experts_per_tok"], moe_dropless=True,
    )
    serving = LlamaServing(cfg, None, max_len)
    assert serving.chunks_per_program(chunk) == 2

    def described(make):
        return jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
            jax.eval_shape(make),
        )

    spec = _spec(one_chip)
    ints, floats = spec((rows,), jnp.int32), spec((rows,), jnp.float32)
    compiled = make_prefill_suffix_rows(serving).lower(
        described(lambda: serving.prepare_params(
            None, quantize=True, matmul_kernel=engine["matmul_kernel"], seed=0)),
        described(lambda: serving.init_state(int(engine["max_batch"]), max_len)),
        spec((rows, chunk), jnp.int32), ints, ints, ints,
        spec((2,), jnp.uint32), (floats, floats, ints), max_len,
    ).compile()
    text = compiled.as_text()
    assert len(re.findall(r"custom_call_target=\"tpu_custom_call\"", text)) == 3
    # Whatever yields an expert-sized buffer is the parameter itself, its
    # way into the layer loop, or a view of it.
    L, E, D, F = cfg.n_layers, cfg.n_experts, cfg.d_model, cfg.d_ff
    sized = re.findall(
        rf"= bf16\[(?:{L},{E}|{L * E}|{E}|1,{E}),(?:{D},{F}|{F},{D})\]\S* ([\w-]+)\(", text
    )
    assert sized and set(sized) <= {"parameter", "get-tuple-element", "bitcast"}, sized
    assert compiled.memory_analysis().temp_size_in_bytes < 600_000_000


def test_the_verify_chunk_compiles_at_the_published_widths(one_chip, monkeypatch):
    """The decode chunk of a model that drafts its own step
    (``HybridServing._make_verify_chunk``: the prediction module's
    catch-up, then 8 steps of the stack over [token, draft], acceptance,
    the module over the accepted positions) for k-exaone-236b-a23b-l5e16's
    32 slots of 8,192 at the widest decode window: the grouped products
    and the full layers' row walk are in it, it returns tokens (8, 32, 2)
    with a count a row and each row's newest token and length for the
    chunk behind it, and its
    temporaries stay under what the cell has to spare beside 9.09 GB of
    weights and 2.2 GB of state."""
    import json
    from pathlib import Path

    from generativeaiexamples_tpu.engine.serving_models import HybridServing
    from generativeaiexamples_tpu.models import hybrid
    from generativeaiexamples_tpu.ops import gqa_decode, moe

    monkeypatch.setattr(moe, "platform_of", lambda mesh: "tpu")
    monkeypatch.setattr(gqa_decode, "platform_of", lambda mesh: "tpu")
    configs = Path(__file__).resolve().parents[1] / "benchmarks" / "configs"
    model = json.loads((configs / "k-exaone-236b-a23b-l5e16.json").read_text())
    engine = model["engine"]
    max_len, b, steps = int(engine["max_len"]), int(engine["max_batch"]), int(engine["decode_chunk_size"])
    cfg = hybrid.from_hf_config(model, max_len=max_len, kv_dtype=engine["kv_dtype"], draft=engine["draft"])
    assert cfg.draft == "mtp" and cfg.qk_norm and cfg.rope_full.rope_type == "none"
    serving = HybridServing(cfg, None, max_len)

    def described(make):
        return jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
            jax.eval_shape(make),
        )

    spec = _spec(one_chip)
    ints, floats = spec((b,), jnp.int32), spec((b,), jnp.float32)
    compiled = serving.make_decode_chunk().lower(
        described(lambda: hybrid.init_params(cfg, jax.random.PRNGKey(0))),
        described(lambda: hybrid.init_state(cfg, b, max_len)),
        ints, ints, spec((2,), jnp.uint32), floats, floats, ints, steps, max_len,
        spec((b,), jnp.bool_), spec((1, b), jnp.int32), spec((b,), jnp.bool_),
        ints, spec((b,), jnp.bool_),
    ).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    # The full layers' attention is the row walk (the stack's and the
    # module's, with one query a row in the catch-up), and the scatter that
    # writes a step's rows feeds it in place: no K or V leaf is copied.
    assert text.count("gqa_rows_decode_attention") >= 3
    assert not re.search(r"= bf16\[32,8192,1024\]\S* copy\(", text)
    _, toks, counts, (newest, lengths), aux = compiled.out_info
    assert toks.shape == (steps, b, 2) and counts.shape == (steps, b)
    assert newest.shape == (1, b) and lengths.shape == (b,)
    assert aux.shape == (len(serving.counter_names),)
    print("verify chunk temporaries", compiled.memory_analysis().temp_size_in_bytes)
    assert compiled.memory_analysis().temp_size_in_bytes < SPARE_BY_FAMILY["exaone"]


def test_the_cca_models_decode_chunk_walks_its_rows_in_place(one_chip, monkeypatch):
    """The decode chunk of zaya1-8b-l20 (8 steps over 32 slots of 8,192
    rows, twenty ``cca`` layers): every layer's attention is the row walk
    of ``ops/gqa_decode.py`` at 8 query heads on 2 key-value heads over
    rows 256 wide, the scatter that writes a step's rows feeds it in place
    (no K or V leaf is copied), and beside 14.75 GB of weights and state
    the program's temporaries are the float32 logits of 32 rows x 262,272
    and little else."""
    import json
    from pathlib import Path

    from generativeaiexamples_tpu.engine.serving_models import HybridServing
    from generativeaiexamples_tpu.models import hybrid
    from generativeaiexamples_tpu.ops import gqa_decode, moe

    monkeypatch.setattr(moe, "platform_of", lambda mesh: "tpu")
    monkeypatch.setattr(gqa_decode, "platform_of", lambda mesh: "tpu")
    configs = Path(__file__).resolve().parents[1] / "benchmarks" / "configs"
    model = json.loads((configs / "zaya1-8b-l20.json").read_text())
    engine = model["engine"]
    max_len, b, steps = int(engine["max_len"]), int(engine["max_batch"]), int(engine["decode_chunk_size"])
    cfg = hybrid.from_hf_config(model, max_len=max_len, kv_dtype=engine["kv_dtype"])
    assert isinstance(cfg, hybrid.CcaConfig) and cfg.n_layers == 20
    serving = HybridServing(cfg, None, max_len)

    def described(make):
        return jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
            jax.eval_shape(make),
        )

    spec = _spec(one_chip)
    ints, floats = spec((b,), jnp.int32), spec((b,), jnp.float32)
    compiled = serving.make_decode_chunk().lower(
        described(lambda: hybrid.init_params(cfg, jax.random.PRNGKey(0))),
        described(lambda: hybrid.init_state(cfg, b, max_len)),
        ints, ints, spec((2,), jnp.uint32), floats, floats, ints, steps, max_len,
        spec((b,), jnp.bool_),
    ).compile()
    text = compiled.as_text()
    assert text.count("gqa_rows_decode_attention") >= 20  # a walk a layer
    assert not re.search(rf"= bf16\[{b},{max_len},256\]\S* copy\(", text)
    _, toks, aux = compiled.out_info
    assert toks.shape == (steps, b) and aux.shape == (len(serving.counter_names),)
    memory = compiled.memory_analysis()
    print("cca decode chunk temporaries", memory.temp_size_in_bytes)
    assert memory.temp_size_in_bytes < 200_000_000
    assert memory.alias_size_in_bytes >= 5_368_709_120  # the slots' state goes through in place


def test_the_mamba_models_decode_chunk_updates_its_state_in_place(one_chip, monkeypatch):
    """The decode chunk of nemotron-3-super-120b-a12b-l11e128 (8 steps over
    32 slots: five ``mamba`` layers, five expert layers in a latent, one
    ``full`` layer): the slots' 0.95 GB of state goes through in place (no
    copy of a layer's ``S``, 537 MB of float32), the one attention layer is
    the row walk of ``ops/gqa_decode.py`` at 32 query heads on 2 key-value
    heads, the experts the grouped products at tiles that divide 1,024 and
    2,688, and the program's temporaries stay small beside 10.25 GB of
    weights and state."""
    import json
    from pathlib import Path

    from generativeaiexamples_tpu.engine.serving_models import HybridServing
    from generativeaiexamples_tpu.models import hybrid
    from generativeaiexamples_tpu.ops import gqa_decode, moe

    monkeypatch.setattr(moe, "platform_of", lambda mesh: "tpu")
    monkeypatch.setattr(gqa_decode, "platform_of", lambda mesh: "tpu")
    configs = Path(__file__).resolve().parents[1] / "benchmarks" / "configs"
    model = json.loads((configs / "nemotron-3-super-120b-a12b-l11e128.json").read_text())
    engine = model["engine"]
    max_len, b, steps = int(engine["max_len"]), int(engine["max_batch"]), int(engine["decode_chunk_size"])
    cfg = hybrid.from_hf_config(model, max_len=max_len, kv_dtype=engine["kv_dtype"])
    assert isinstance(cfg, hybrid.MambaConfig) and cfg.n_layers == 6
    serving = HybridServing(cfg, None, max_len)

    def described(make):
        return jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
            jax.eval_shape(make),
        )

    spec = _spec(one_chip)
    ints, floats = spec((b,), jnp.int32), spec((b,), jnp.float32)
    compiled = serving.make_decode_chunk().lower(
        described(lambda: hybrid.init_params(cfg, jax.random.PRNGKey(0))),
        described(lambda: hybrid.init_state(cfg, b, max_len)),
        ints, ints, spec((2,), jnp.uint32), floats, floats, ints, steps, max_len,
        spec((b,), jnp.bool_),
    ).compile()
    text = compiled.as_text()
    assert "gqa_rows_decode_attention" in text and "gmm" in text
    assert not re.search(rf"= f32\[{b},128,64,128\]\S* copy\(", text)
    _, toks, aux = compiled.out_info
    assert toks.shape == (steps, b) and aux.shape == (len(serving.counter_names),)
    memory = compiled.memory_analysis()
    print("mamba decode chunk temporaries", memory.temp_size_in_bytes)
    assert memory.temp_size_in_bytes < 200_000_000
    assert memory.alias_size_in_bytes >= 949_354_496  # S, the tails and the K/V rows, in place


def test_the_latent_models_decode_chunk_keeps_its_state_in_place(one_chip, monkeypatch):
    """The decode chunk of mistral-small-4-119b-l6e32 (8 absorbed steps
    over 16 slots) at the widest decode window, 32,768: the slots' latent
    rows go in and come out in the layout they are stored in.  A row of
    320 columns is no whole number of lanes, the chip's default layout of
    such a leaf puts the POSITIONS minor, and the program then copied every
    layer's 0.34 GB in and out (2.5 GB of temporaries); rows of 384
    columns keep the layout the steps work in, and the whole-row
    contraction never cuts a row into its latent and its rope key."""
    import json
    from pathlib import Path

    from generativeaiexamples_tpu.engine.serving_models import HybridServing
    from generativeaiexamples_tpu.models import hybrid
    from generativeaiexamples_tpu.ops import moe

    monkeypatch.setattr(moe, "platform_of", lambda mesh: "tpu")
    configs = Path(__file__).resolve().parents[1] / "benchmarks" / "configs"
    model = json.loads((configs / "mistral-small-4-119b-l6e32.json").read_text())
    engine = model["engine"]
    max_len, b, steps = int(engine["max_len"]), int(engine["max_batch"]), int(engine["decode_chunk_size"])
    cfg = hybrid.from_hf_config(model, max_len=max_len, kv_dtype=engine["kv_dtype"])
    assert cfg.latent_width == 384 and cfg.latent_width % 128 == 0
    serving = HybridServing(cfg, None, max_len)

    def described(make):
        return jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
            jax.eval_shape(make),
        )

    spec = _spec(one_chip)
    ints, floats = spec((b,), jnp.int32), spec((b,), jnp.float32)
    compiled = serving.make_decode_chunk().lower(
        described(lambda: hybrid.init_params(cfg, jax.random.PRNGKey(0))),
        described(lambda: hybrid.init_state(cfg, b, max_len)),
        ints, ints, spec((2,), jnp.uint32), floats, floats, ints, steps, max_len,
        spec((b,), jnp.bool_),
    ).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text  # the grouped expert products
    assert not re.search(rf"= bf16\[{b},{max_len},384\]\S* copy\(", text)
    assert not re.search(rf"= bf16\[{b},{max_len},(?:256|64|320)\]", text)  # no row cut in two
    _, toks, aux = compiled.out_info
    assert toks.shape == (steps, b) and aux.shape == (len(serving.counter_names),)
    print("latent decode chunk temporaries", compiled.memory_analysis().temp_size_in_bytes)
    assert compiled.memory_analysis().temp_size_in_bytes < 400_000_000



# Ling's KDA layers (``ops/kda.py::kda_step_rows``): 32 slots of 32 heads,
# K = V 128, a float32 state of 67.1 MB a layer.
KDA_STATE = (32, 32, 128, 128)


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


def _state_is_the_kernels_alone(text: str, *, calls: int) -> None:
    """Every operation of a compiled program that makes an array of the
    KDA state's shape is the step kernel, a parameter or a renaming of one:
    no copy, and no XLA fusion that walks the leaf."""
    shape = ",".join(map(str, KDA_STATE))
    made = re.findall(rf"= (?:\([^=]*)?f32\[{shape}\]\S*(?:, [^=]*\))? ([\w-]+)\(", text)
    assert made.count("custom-call") >= calls, made
    assert set(made) <= {"custom-call", "parameter", "get-tuple-element", "bitcast", "tuple", "while"}, made
    assert len(re.findall(r"custom_call_target=\"tpu_custom_call\"[^\n]*kda_step_rows", text)) >= calls


def test_lings_decode_chunk_touches_the_kda_state_by_the_kernel_alone(one_chip, monkeypatch):
    """The decode chunk of ling-3.0-flash-vl-l7e128 (8 steps over 32
    slots) at the widest decode window: each of the six KDA layers' state
    leaves ``f32[32,32,128,128]`` is read and written by the step kernel
    in place; XLA's twin made two fusions over it and three passes, every
    slot's (PERF.md, PR 39)."""
    import json
    from pathlib import Path

    from generativeaiexamples_tpu.engine.serving_models import HybridServing
    from generativeaiexamples_tpu.models import hybrid
    from generativeaiexamples_tpu.ops import kda, moe

    monkeypatch.setattr(moe, "platform_of", lambda mesh: "tpu")
    monkeypatch.setattr(kda, "platform_of", lambda mesh: "tpu")
    configs = Path(__file__).resolve().parents[1] / "benchmarks" / "configs"
    model = json.loads((configs / "ling-3.0-flash-vl-l7e128.json").read_text())
    engine = model["engine"]
    max_len, b, steps = int(engine["max_len"]), int(engine["max_batch"]), int(engine["decode_chunk_size"])
    cfg = hybrid.from_hf_config(model, max_len=max_len, kv_dtype=engine["kv_dtype"])
    layers = len(cfg.layers_of("kda"))
    assert (b, cfg.n_heads, cfg.kda_head_dim, cfg.kda_head_dim) == KDA_STATE and layers == 6
    serving = HybridServing(cfg, None, max_len)

    def described(make):
        return jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
            jax.eval_shape(make),
        )

    spec = _spec(one_chip)
    ints, floats = spec((b,), jnp.int32), spec((b,), jnp.float32)
    compiled = serving.make_decode_chunk().lower(
        described(lambda: hybrid.init_params(cfg, jax.random.PRNGKey(0))),
        described(lambda: hybrid.init_state(cfg, b, max_len)),
        ints, ints, spec((2,), jnp.uint32), floats, floats, ints, steps, max_len,
        spec((b,), jnp.bool_),
    ).compile()
    text = compiled.as_text()
    _state_is_the_kernels_alone(text, calls=layers)
    _, toks, aux = compiled.out_info
    assert toks.shape == (steps, b) and aux.shape == (len(serving.counter_names),)
    print("ling decode chunk temporaries", compiled.memory_analysis().temp_size_in_bytes)
    assert compiled.memory_analysis().temp_size_in_bytes < 600_000_000


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


@pytest.mark.parametrize("rows", [2, 8])
def test_lings_chunk_program_scans_its_kda_layers_by_the_kernel_alone(one_chip, rows, monkeypatch):
    """The chunk program of ling-3.0-flash-vl-l7e128 for 2 and for 8 rows
    at the widest window: each of the six KDA layers' scans is one
    ``kda_chunk_rows`` call that takes the rows' state and returns it in
    the same buffer; what XLA made of ``kda_chunked`` (a ``while`` of 16
    trips with a triangular inverse in its body, and q, k, v, g copied to
    ``f32[16, rows, 32, 16, 128]`` around it: PERF.md, PR 50) is gone."""
    from generativeaiexamples_tpu.ops import kda, moe

    monkeypatch.setattr(moe, "platform_of", lambda mesh: "tpu")
    monkeypatch.setattr(kda, "platform_of", lambda mesh: "tpu")
    config, _, window = GROUP_PROGRAMS["ling"]
    compiled, serving, _ = _chunk_program(one_chip, config, rows, window)
    layers = len(serving.cfg.layers_of("kda"))
    text = compiled.as_text()
    lines = text.splitlines()
    calls = [ln for ln in lines if 'custom_call_target="tpu_custom_call"' in ln and "kda_chunk_rows" in ln]
    assert len(calls) == layers == 6
    assert "InvertDiagBlocksLowerTriangular" not in text
    assert not [ln for ln in lines if " while(" in ln and "layer/kda/scan" in ln]
    assert f"f32[16,{rows},32,16,128]" not in text
    # The rows' state (operand 7, behind the two prefetched and q, k, v,
    # g, beta) is the call's second output.
    assert all("output_to_operand_aliasing={{1}: (7, {})}" in call for call in calls), calls[0][-600:]
