"""The share of their roofline of the routed experts' grouped products alone
(phase ``experts.products`` inside ``moe.experts``) where a token has
``moe_topk`` places of which most are other chips' experts or identity experts:
``kernels.moe_products_roofline`` itself, by import of its ``read``, under a
name whose ``workloads`` may list this family's cell. The family's counts are
``shapes/scmoe_mla.py``'s ``experts_flops`` and ``experts_bytes``. Nothing
without a device plane, 0.0 where the program names no phase."""

from benchmark import harness


def read(ctx):
    return harness.load_plugin("layer_metrics", "kernels.moe_products_roofline").read(ctx)
