"""The share of their roofline of the kernels that hold FC6, FC7 and FC8, taken
together, in percent: see ``layer_times.roofline_pct``. Their weights are
counted as the kernels read them, in the compute type; the cast from float32
is ``kernels.cast_in_ms``."""

from benchmark import layer_times


def read(ctx):
    return layer_times.roofline_pct(ctx, "fc6", "fc7", "fc8")
