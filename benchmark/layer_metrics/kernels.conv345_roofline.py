"""The share of their roofline of the kernels that hold conv3, conv4 and conv5
(13x13 maps, 3x3 filters), taken together, in percent: see
``layer_times.roofline_pct``."""

from benchmark import layer_times


def read(ctx):
    return layer_times.roofline_pct(ctx, "conv3", "conv4", "conv5")
