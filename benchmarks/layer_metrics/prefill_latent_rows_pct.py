"""Share of the latent rows the MLA layers' prefill chunks would read
with every row's whole window read that they read:
d ``attn_rows_read_latent_prefill`` / d ``attn_rows_dense_latent_prefill``
(``models/hybrid.py::_mla_mixer`` counts both in a chunk program of a
``LatentConfig`` model: the whole blocks of ``latent_block`` rows up to
each row's length, which ``ops/mla.py::attend_blocks`` expands and scores,
against the program's static window for every row of the group, its
padding too).  Lower is better; nothing to read from a program without
the counters."""

from counter_lib import ratio


def read(ctx):
    return ratio(
        ctx, ["attn_rows_read_latent_prefill"], ["attn_rows_dense_latent_prefill"], 100.0
    )
