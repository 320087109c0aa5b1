"""Device milliseconds per step on the way back from the grouped products'
output to the tokens' sum, every MoE layer together: the phases
``experts.layout`` (the rows cast and laid out as the combine wants them,
their place in the span's results, the zero fills where the compiler leaves
them their name) and ``experts.combine`` (``moe_combine``'s kernel, or a gather
and multiply-add per place; in the top-1 family also the merge after it)
inside ``moe.experts``. 0.0 where the program names no phase."""

from benchmark import phase_times


def read(ctx):
    return phase_times.ms(ctx, "experts.layout", "experts.combine")
