"""Device milliseconds per step under the scope ``cca.mix``: the two causal
convolutions over the latent (the per-head 128 x 128 products inside it), the
q-k mean, the value shift, the q/k normalisation with ``tau`` and the rotary
embedding between the attention sublayers' projections and their flash kernel
(elementwise and memory-bound), every layer together."""

from benchmark import layer_times


def read(ctx):
    return layer_times.ms(ctx, layer_times.exactly("cca.mix"))
