"""Device milliseconds per step in the kernels that hold a max-pool (pool1,
pool2 and, in the full model, pool5), together."""

from benchmark import layer_times


def read(ctx):
    return layer_times.ms(ctx, layer_times.covers("pool1", "pool2", "pool5"))
