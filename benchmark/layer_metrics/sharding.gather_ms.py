"""Device milliseconds per step and chip under ``gather``: the slice after the
``shard_map`` and the all-gather the compiler puts in for the output."""

from benchmark import layer_times


def read(ctx):
    return layer_times.ms(ctx, layer_times.exactly(layer_times.GATHER))
