"""Share of the latent rows the MLA layers' decode steps read that
``ops/mla_decode.py``'s Pallas kernel walked:
d ``attn_rows_kernel_latent_decode`` / d ``attn_rows_read_latent_decode``
(``models/hybrid.py::_mla_mixer`` counts both in a decode step of a
``LatentConfig`` model: the whole blocks of ``latent_decode_block`` rows up
to each decoding row's length; the first where ``use_latent_decode``
admitted the call, 0 where ``ops/mla.py::attend_absorbed_blocks`` walked
them a row after the other).  100 where every decode step's walk is the
kernel's.  Higher is better; nothing to read from a program without the
counter."""

from counter_lib import ratio


def read(ctx):
    return ratio(
        ctx, ["attn_rows_kernel_latent_decode"], ["attn_rows_read_latent_decode"], 100.0
    )
