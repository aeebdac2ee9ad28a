"""The tokenizer the benchmark hands to ``create_engine_app``.

A random model emits ids the byte tokenizer turns into nothing, so the
SSE stream would carry no chunk.  This one gives every id a non-empty
piece, so one streamed chunk is one token, and has no end-of-sequence
id, so every request runs to its ``max_tokens`` and ends ``length``:
output lengths are exactly what the traffic asked for.
"""

from __future__ import annotations


class BenchTokenizer:
    eos_id = None
    bos_id = None
    pad_id = 0

    def __init__(self, vocab_size: int) -> None:
        self.vocab_size = int(vocab_size)

    def encode(self, text: str, add_bos: bool = True) -> list[int]:
        """The inverse of :meth:`decode`; other text is an error (the
        benchmark sends token ids, never text)."""
        return [int(p[1:]) for p in text.split()]

    def decode(self, ids) -> str:
        return "".join(f"t{int(i)} " for i in ids)


def piece_ids(text: str) -> list[int]:
    """Token ids back out of streamed or aggregated text."""
    return [int(p[1:]) for p in text.split()]
