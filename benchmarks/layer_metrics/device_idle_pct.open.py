"""Share of the traced window in which no operation ran on the device,
in the open-loop cells (BENCHMARK.json says which end-to-end metric it
moves there)."""

from metrics_lib import idle_pct as read  # noqa: F401
