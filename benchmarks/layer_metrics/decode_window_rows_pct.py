"""Share of the K/V rows the window layers' decode attention would read
as full layers that it reads from its rings:
d ``attn_rows_read_window_decode`` / d ``attn_rows_dense_window_decode``
(``models/hybrid.py::_gqa_mixer`` counts both in the decode step: every
slot's ring of ``sliding_window`` rows, against every slot's first
``kv_bucket`` rows, which is what the full layers beside them read).
Lower is better; 100 where the window is no shorter than the rows."""

from counter_lib import ratio


def read(ctx):
    return ratio(
        ctx, ["attn_rows_read_window_decode"], ["attn_rows_dense_window_decode"], 100.0
    )
