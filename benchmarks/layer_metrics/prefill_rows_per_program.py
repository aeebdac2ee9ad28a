"""Prefill chunks per program dispatched for them: where a chunk is a
weight stream (the grouped-expert models), the chunks that one tick sends
for several warming slots go out as one program whose token rows share
each layer's weight pass; a chunk that goes alone is a program of one, so
1.0 says every chunk paid for its own pass."""

from counter_lib import ratio


def read(ctx):
    return ratio(ctx, ["prefill_chunks"], ["prefill_chunk_programs"])
