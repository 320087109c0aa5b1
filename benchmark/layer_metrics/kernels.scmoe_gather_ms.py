"""Device milliseconds per step under the phase ``experts.gather`` inside
``moe.experts`` (a chunk's index arithmetic and the gather of its rows'
tokens) in a cell whose router also chooses identity experts:
``kernels.moe_gather_ms`` itself, by import of its ``read``, under a name whose
``workloads`` may list this family's cell. 0.0 where the program names no
phase."""

from benchmark import harness


def read(ctx):
    return harness.load_plugin("layer_metrics", "kernels.moe_gather_ms").read(ctx)
