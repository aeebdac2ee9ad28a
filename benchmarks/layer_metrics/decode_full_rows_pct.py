"""Share of the K/V rows the full layers' decode attention would read
with every slot's window read whole that it reads:
d ``attn_rows_read_full_decode`` / d ``attn_rows_dense_full_decode``
(``models/hybrid.py::_gqa_mixer`` counts both in the decode step: what
the row walk of ``ops/gqa_decode.py`` copies, each live row's length in
whole blocks by the device's own lengths, against every slot's first
``kv_bucket`` rows).  Lower is better; 100 where XLA's path reads the
window whole, and nothing to read from a program without the second
counter."""

from counter_lib import ratio


def read(ctx):
    return ratio(
        ctx, ["attn_rows_read_full_decode"], ["attn_rows_dense_full_decode"], 100.0
    )
