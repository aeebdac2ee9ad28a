"""Latent rows the step forms of the indexed latent layers gathered, over
the rows the decoding slots needed:
100 x d ``attn_rows_gathered_verify`` / d ``attn_rows_needed_verify``
(``models/hybrid.py::_mla_mixer`` counts both in a step of one or two
queries a slot: every row gathered, for every slot and each of the step's
positions, whoever decodes; and, for the slots that decode, the rows in
the UNION of the sets their positions that count keep, which is what one
shared gather would fetch: two adjacent positions share most of theirs).
100 is a step that gathers each needed row once; a verify step that
gathers a set a position reads near 200 at a full house, more where slots
that do not decode are computed beside the others.  Lower is better;
nothing to read from a program without the counters."""

from counter_lib import ratio


def read(ctx):
    return ratio(ctx, ["attn_rows_gathered_verify"], ["attn_rows_needed_verify"], 100.0)
