"""Device milliseconds per step under the phase ``route.sort`` inside
``moe.route`` (the stable sort of the pairs by expert, the held experts'
counts, the cumulative sums, every pair's padded row) at twelve places a token
over 768 outputs: ``kernels.moe_sort_ms`` itself, by import of its ``read``,
under a name whose ``workloads`` may list this family's cell. 0.0 where the
program names no phase."""

from benchmark import harness


def read(ctx):
    return harness.load_plugin("layer_metrics", "kernels.moe_sort_ms").read(ctx)
