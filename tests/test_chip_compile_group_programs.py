"""The layer-kind families' largest chunk program (the prefill chunks of
several slots as one program), compiled for the described v5e at the
cells' widths and slot state (``tests/chip_compile_lib.py``).
"""

import pytest

from chip_compile_lib import (  # noqa: F401 — ``one_chip`` is the file's fixture
    GROUP_PROGRAMS,
    SPARE_BYTES,
    SPARE_BY_FAMILY,
    _chunk_program,
    _no_window_sized_temporaries,
    one_chip,
)


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
