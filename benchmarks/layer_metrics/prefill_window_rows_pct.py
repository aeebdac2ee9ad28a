"""Share of the K/V rows the window layers' prefill chunks would read as
full layers that they read from their rings:
d ``attn_rows_read_window_prefill`` / d ``attn_rows_dense_window_prefill``
(``models/hybrid.py::_gqa_mixer`` counts both in every prefill program:
the ring of ``sliding_window`` rows, against the chunk's ``kv_bucket``
rows, which is what the full layers beside them read).  Lower is better;
over 100 in a prompt's first chunks, whose bucket is shorter than the ring."""

from counter_lib import ratio


def read(ctx):
    return ratio(
        ctx, ["attn_rows_read_window_prefill"], ["attn_rows_dense_window_prefill"], 100.0
    )
