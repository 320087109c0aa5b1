"""Device milliseconds per step under the phase ``experts.gather`` inside
``moe.experts``: a chunk's index arithmetic (which expert's tile, which pair)
and the gather of its rows' tokens into the first product's left operand,
every MoE layer together. 0.0 where the program names no phase."""

from benchmark import phase_times


def read(ctx):
    return phase_times.ms(ctx, "experts.gather")
