"""Device milliseconds per step on the way back from the grouped products'
output to the branch's sum (phases ``experts.layout`` and ``experts.combine``
inside ``moe.experts``) in a cell whose router also chooses identity experts,
at twelve places a token: ``kernels.moe_combine_ms`` itself, by import of its
``read``, under a name whose ``workloads`` may list this family's cell. 0.0
where the program names no phase."""

from benchmark import harness


def read(ctx):
    return harness.load_plugin("layer_metrics", "kernels.moe_combine_ms").read(ctx)
