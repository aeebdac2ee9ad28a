"""Times a grouped product streamed an expert's matrices, for each expert
that received a row: d ``moe_expert_streams`` / d ``moe_experts_touched``
(``ops/moe.py::expert_mlp``'s counters, over every expert layer and
program of the traced window).  megablox's ``gmm`` visits a group once
for each row tile its rows touch; where a weight tile holds the whole of
K the visits of one group ask for the same block and the pipeline fetches
it once, so the ratio reads 1.0; with two or more k tiles every visit
streams the expert again (1.2-1.7 in Mellum's chunk programs before
PR 45).  Lower is better; nothing to read from a program without the
counter."""

from counter_lib import ratio


def read(ctx):
    return ratio(ctx, ["moe_expert_streams"], ["moe_experts_touched"])
