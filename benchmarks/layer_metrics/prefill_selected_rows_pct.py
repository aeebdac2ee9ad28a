"""Of the (query, latent row) pairs the full layers' prefill chunks would
attend unselected, the share the program's attention scored:
d ``attn_rows_read_selected_prefill`` / d ``attn_rows_seen_latent_prefill``
(``models/hybrid.py::_mla_mixer`` counts both in a chunk program of an
``IndexedLatentConfig`` model: for every query that counts the rows it
sees, and the rows the program attends for it).  It reads what the PROGRAM
does, not what the selection would allow: while a chunk expands and scores
every whole block up to a row's length and masks the pairs not kept, it
reads over 100 (whole blocks reach past a query's own position); the
selection itself leaves ``min(t + 1, index_topk)`` rows a query, which a
program that touches only the kept rows would read here.  Lower is better;
nothing to read from a program without the counters."""

from counter_lib import ratio


def read(ctx):
    return ratio(
        ctx, ["attn_rows_read_selected_prefill"], ["attn_rows_seen_latent_prefill"], 100.0
    )
