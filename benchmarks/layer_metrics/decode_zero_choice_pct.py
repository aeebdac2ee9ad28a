"""Share of the routed choices that fell on identity experts:
100 x d ``moe_choices_zero`` / d ``moe_choices_routed``
(``ops/moe.py::expert_mlp``'s counters, over every expert layer and
program of the traced window; LongCat-Flash's router has 256 such outputs
past its 512 real experts, and a choice there adds ``w x`` and computes
nothing).  33.3 where the selection bias spreads a token's 12 choices
evenly over the 768 outputs, which leaves a token 8 real experts on
average; a drift moves the expert rows a token and with them the experts a
decode step streams.  Lower is more compute a token, higher less: neither
is better in itself; listed as higher, the side on which a step is
cheaper.  Nothing to read from a program without the counter."""

from counter_lib import ratio


def read(ctx):
    return ratio(ctx, ["moe_choices_zero"], ["moe_choices_routed"], 100.0)
