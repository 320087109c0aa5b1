"""Device milliseconds per step under the scope ``mamba.mix``: the short causal
convolution with its bias, silu, the step's softplus and ``A`` between the
state-space layers' projections and their scan (elementwise, memory-bound),
every Mamba layer together."""

from benchmark import layer_times


def read(ctx):
    return layer_times.ms(ctx, layer_times.exactly("mamba.mix"))
