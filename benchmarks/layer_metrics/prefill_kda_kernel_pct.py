"""Share of the tokens the KDA layers' prefill chunks scanned whose scan
was ``ops/kda.py::kda_chunk_rows``' Pallas kernel:
d ``attn_rows_read_state_prefill`` / d ``attn_rows_dense_state_prefill``
(``models/hybrid.py::_kda_mixer`` counts both in a chunk program: the
tokens that count where ``use_chunk_kernel`` admitted the call, 0 where
XLA's ``kda_chunked`` scanned them, against tokens that count x KDA
layers).  100 where every chunk program's scan is the kernel's, 0 on XLA's
path.  Higher is better; nothing to read from a program whose prefill
calls add zeros to both (the commits before the kernel)."""

from counter_lib import ratio


def read(ctx):
    return ratio(
        ctx, ["attn_rows_read_state_prefill"], ["attn_rows_dense_state_prefill"], 100.0
    )
