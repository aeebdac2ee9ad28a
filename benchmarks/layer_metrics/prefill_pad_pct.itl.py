"""``prefill_pad_pct`` for cells that do not judge ``ttft_p50_ms``: the
same reading, listed as moving ``itl_p95_ms``: where every lane decodes
and the device never idles, a padded row or position of a prefill program
is device time that a tick's decode steps wait behind."""

from metrics_lib import load_reader

read = load_reader("prefill_pad_pct")
