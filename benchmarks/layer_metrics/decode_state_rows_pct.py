"""Share of the slots' recurrent state that the KDA layers' decode steps
read: d ``attn_rows_read_state_decode`` / d ``attn_rows_dense_state_decode``
(``models/hybrid.py::_kda_mixer`` counts both in the decode step: the rows
that decode where the step is the kernel of ``ops/kda.py::kda_step_rows``,
every slot where it is XLA's ``kda_step``, against slots x KDA layers).
Lower is better; 100 on XLA's path, and nothing to read from a program
without the second counter."""

from counter_lib import ratio


def read(ctx):
    return ratio(
        ctx, ["attn_rows_read_state_decode"], ["attn_rows_dense_state_decode"], 100.0
    )
