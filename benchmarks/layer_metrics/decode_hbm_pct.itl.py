"""``decode_hbm_pct`` for cells that do not judge ``out_tok_s``: the
same reading, listed as moving ``itl_p95_ms`` (a step nearer the roofline is a shorter tick)."""

from metrics_lib import load_reader

read = load_reader("decode_hbm_pct")
