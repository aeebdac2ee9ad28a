"""Share of the latent rows the MLA layers' prefill chunks read that
``ops/mla_chunk.py``'s Pallas kernel walked:
d ``attn_rows_kernel_latent_prefill`` / d ``attn_rows_read_latent_prefill``
(``models/hybrid.py::_mla_mixer`` counts both in a chunk program of a
``LatentConfig`` model: the whole blocks of ``latent_block`` rows up to
each row's length; the first where ``use_latent_chunk`` admitted the call,
0 where ``ops/mla.py::attend_blocks`` walked them).  100 where every chunk
program's walk is the kernel's.  Higher is better; nothing to read from a
program without the counter."""

from counter_lib import ratio


def read(ctx):
    return ratio(
        ctx, ["attn_rows_kernel_latent_prefill"], ["attn_rows_read_latent_prefill"], 100.0
    )
