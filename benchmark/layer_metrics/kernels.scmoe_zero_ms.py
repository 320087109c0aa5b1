"""Device milliseconds per step under the scope ``moe.zero``: the identity
experts' term (the chosen identity experts' weights summed over a token's
places, times the token's normed input) and its sum with the routed experts'
part, every layer together.

It times NO KERNEL OF ITS OWN and is expected to read about nothing: the
compiler fuses the term into the fusion that ends the layer, which counts
under ``dense_mlp`` (most of its instructions), and what is left here is a
slice of a fraction of a microsecond (0.00017 ms a step; my chip runs, PR 37).
The entry says that the identity experts cost no device time of their own as
the program stands; it would rise only if a later change gave the term an
operation of its own. Nothing here can move ``images_per_s`` until then."""

from benchmark import layer_times


def read(ctx):
    return layer_times.ms(ctx, layer_times.exactly("moe.zero"))
