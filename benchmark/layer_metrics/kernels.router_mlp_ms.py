"""Device milliseconds per step under the scope ``moe.route`` in a cell whose
router is an MLP over a state carried down the depth: the norm before the
experts, the down-projection, the carried state's addition, the MLP, the
softmax and the top-1 over the experts and the skip output, then the sort of
the pairs by expert and the index arithmetic, every layer together."""

from benchmark import layer_times


def read(ctx):
    return layer_times.ms(ctx, layer_times.exactly("moe.route"))
