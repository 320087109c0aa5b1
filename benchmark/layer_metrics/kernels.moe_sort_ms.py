"""Device milliseconds per step under the phase ``route.sort`` inside
``moe.route``: the stable sort of the (token, expert) pairs by expert, the
held experts' counts, both cumulative sums and every pair's padded row, apart
from the router's own arithmetic (``route.score``), every MoE layer together.
0.0 where the program names no phase."""

from benchmark import phase_times


def read(ctx):
    return phase_times.ms(ctx, "route.sort")
