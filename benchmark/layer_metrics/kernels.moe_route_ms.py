"""Device milliseconds per step under the scope ``moe.route``: the norm before
the experts, the router's matmul and sigmoid, the group-limited top-k, the sort
of the pairs by expert and the index arithmetic, every MoE layer together."""

from benchmark import layer_times


def read(ctx):
    return layer_times.ms(ctx, layer_times.exactly("moe.route"))
