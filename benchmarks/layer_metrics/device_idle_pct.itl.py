"""The device's idle share in a closed-loop cell that does not judge
``out_tok_s``: the host's gap is part of every tick, so it moves
``itl_p95_ms``."""

from metrics_lib import idle_pct as read  # noqa: F401
