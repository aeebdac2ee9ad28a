"""Share of the experts held that a decode step's expert layer streams:
d ``moe_experts_touched_decode`` / (experts held x d
``moe_expert_layer_steps_decode``)
(``engine/serving_models.py::HybridServing._aux`` keeps both for decode
chunks alone, from ``ops/moe.py::expert_mlp``'s counters: the experts that
received a row, and one a call).  It says how much of the expert stream a
step pays for the rows it decodes: with one expert a token of 16, about
86 % at 30 rows and 28 % at 5.  Lower is better; nothing to read from a
program without the decode-only counters."""

from counter_lib import ratio


def read(ctx):
    return ratio(
        ctx, ["moe_experts_touched_decode"], ["moe_expert_layer_steps_decode"],
        100.0 / float(ctx["model"]["num_experts"]),
    )
