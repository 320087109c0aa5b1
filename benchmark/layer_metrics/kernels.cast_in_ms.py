"""Device milliseconds per step under the scope ``cast_in``: the input batch and
the parameters cast from float32 to the compute type inside the forward."""

from benchmark import layer_times


def read(ctx):
    return layer_times.ms(ctx, layer_times.exactly(layer_times.CAST_IN))
