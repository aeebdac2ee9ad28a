"""Share of the drafts the model's own prediction module offered that the
stack agreed with: d ``draft_accepted`` / d ``draft_proposed``
(``serving_models.HybridServing``'s verify chunk counts both on the
device: one draft a greedy row a step).  With seeded random weights the
module's guess and the stack's token are unrelated, so this reads about
100 / vocabulary; a trained model's reads what speculation buys."""

from counter_lib import ratio


def read(ctx):
    return ratio(ctx, ["draft_accepted"], ["draft_proposed"], 100.0)
