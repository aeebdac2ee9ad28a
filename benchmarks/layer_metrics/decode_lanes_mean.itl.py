"""``decode_lanes_mean`` for cells that do not judge ``out_tok_s``: the
same reading, listed beside ``itl_p95_ms``, which it qualifies: a tick
that serves fewer lanes is shorter without being better."""

from metrics_lib import load_reader

read = load_reader("decode_lanes_mean")
