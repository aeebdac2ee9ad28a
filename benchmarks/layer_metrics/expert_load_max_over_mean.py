"""The fullest expert against the mean, over every expert layer and step
of the traced window: ``moe_expert_rows_max`` (the largest count of rows
one expert held received, summed over layers and steps) over
the mean, ``moe_choices_local`` (every row an expert held received) /
experts held.  1 is even; the grouped products take as long as their
fullest group's tiles."""

from counter_lib import ratio


def read(ctx):
    return ratio(
        ctx, ["moe_expert_rows_max"], ["moe_choices_local"],
        float(ctx["model"]["num_experts"]),
    )
