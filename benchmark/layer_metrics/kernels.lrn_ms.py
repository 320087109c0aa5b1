"""Device milliseconds per step in the kernels that hold the LRN (its window
sum and its scale; where the compiler fuses the scale into a convolution it
counts there, and the fusion is listed on an earlier line)."""

from benchmark import layer_times


def read(ctx):
    return layer_times.ms(ctx, layer_times.covers("lrn2"))
