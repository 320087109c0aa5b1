"""Device milliseconds per step under the scope ``kda.mix``: the short
convolution, silu, l2norm, the decay's softplus and the write strength's
sigmoid between the linear layers' projections and their scan (elementwise,
memory-bound), every linear layer together."""

from benchmark import layer_times


def read(ctx):
    return layer_times.ms(ctx, layer_times.exactly("kda.mix"))
