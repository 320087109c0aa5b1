"""The share of its roofline of the kernel that holds conv1 (with its bias and
ReLU), in percent: see ``layer_times.roofline_pct``."""

from benchmark import layer_times


def read(ctx):
    return layer_times.roofline_pct(ctx, "conv1")
