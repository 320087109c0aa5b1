"""Device milliseconds per step under the scope ``moe.route`` in a cell whose
router is a softmax over the experts and the identity experts: the norm after
a layer's first attention, the router's matmul and softmax, the top-k over
every output, then the sort of the pairs by expert and the index arithmetic,
every layer together."""

from benchmark import layer_times


def read(ctx):
    return layer_times.ms(ctx, layer_times.exactly("moe.route"))
