"""The layer-kind families' chunk programs with the attention gates
believing they are on the chip: the kernels over the slots' rows, latent
rows and rings, compiled for the described v5e
(``tests/chip_compile_lib.py``).
"""

import re

import pytest

from chip_compile_lib import (  # noqa: F401 — ``one_chip`` is the file's fixture
    GROUP_PROGRAMS,
    HD,
    SPARE_BY_FAMILY,
    _chunk_program,
    _no_window_sized_temporaries,
    one_chip,
)


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
