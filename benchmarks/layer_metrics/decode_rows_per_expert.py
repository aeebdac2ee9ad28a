"""Rows an expert that a decode step touched was handed: d
``moe_choices_local_decode`` / d ``moe_experts_touched_decode``
(``engine/serving_models.py::HybridServing._aux`` keeps both for decode
chunks alone, from ``ops/moe.py::expert_mlp``'s counters: the choices that
landed on the experts held, and the experts that received a row).  It says
how thin the groups of the grouped products are: with 22 choices of 512 a
token and a quarter of the experts here, about 1.8 at 30 rows, where each
group still pays a whole row tile of 128 and its own stream of weights.
Higher is better; nothing to read from a program without the decode-only
count of local choices."""

from counter_lib import ratio


def read(ctx):
    return ratio(ctx, ["moe_choices_local_decode"], ["moe_experts_touched_decode"])
