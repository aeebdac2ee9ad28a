"""Share of the routed choices that landed on the experts held here:
d ``moe_choices_local`` / d ``moe_choices_routed``.  The share's fair part
is held / published (25 for 128 of 512); more means this chip's experts
are popular, less that its work went elsewhere."""

from counter_lib import ratio


def read(ctx):
    return ratio(ctx, ["moe_choices_local"], ["moe_choices_routed"], 100.0)
