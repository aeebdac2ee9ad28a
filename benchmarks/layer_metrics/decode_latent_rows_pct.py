"""Share of the latent rows the MLA layers' decode attention would read
with every slot's window read whole that it reads:
d ``attn_rows_read_latent_decode`` / d ``attn_rows_dense_latent_decode``
(``models/hybrid.py::_mla_mixer`` counts both in the decode step of a
``LatentConfig`` model: the whole blocks of ``latent_decode_block`` rows
up to each decoding row's length, which the absorbed form walks a row at
a time, against every slot's first ``kv_bucket`` rows).  Lower is better;
100 where a step reads every slot's window whole
(``latent_decode_block`` 0), and nothing to read from a program without
the counters."""

from counter_lib import ratio


def read(ctx):
    return ratio(
        ctx, ["attn_rows_read_latent_decode"], ["attn_rows_dense_latent_decode"], 100.0
    )
