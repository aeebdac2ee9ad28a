"""Share of the K/V rows the full layers' prefill chunks would read with
every chunk's window read whole that they read:
d ``attn_rows_read_full_prefill`` / d ``attn_rows_dense_full_prefill``
(``models/hybrid.py::_full_rows`` counts both in every prefill program:
what the chunk kernel of ``ops/gqa_decode.py`` copies, each live chunk's
length in whole blocks and nothing for a group's padding, against the
program's rows x its window).  The twin of ``decode_full_rows_pct``.
Lower is better; 100 where XLA's path reads the window whole (the commit
before the kernel), and nothing to read from a program without the second
counter."""

from counter_lib import ratio


def read(ctx):
    return ratio(
        ctx, ["attn_rows_read_full_prefill"], ["attn_rows_dense_full_prefill"], 100.0
    )
