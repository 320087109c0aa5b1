"""The share of their roofline of the routed experts held here where a token
has ``moe_topk`` places of which most are other chips' experts or identity
experts (scope ``moe.experts``: the gather, the grouped products, the layout
and the combine), every layer of the step together, in percent. The work is
reckoned as ``kernels.moe_experts_roofline`` reckons it (by import of its
``_work``): the pairs the plain REFERENCE routed to the held experts on the
sampled sequence, scaled to the step's tokens; every held expert read once, a
row gathered and a float32 row added per pair (``shapes/scmoe_mla.py``). The
scope's phases have readers of their own (``kernels.scmoe_products_roofline``,
``kernels.scmoe_gather_ms``, ``kernels.scmoe_combine_ms``). See
``scope_roofline.pct``."""

from benchmark import harness, scope_roofline


def read(ctx):
    work = harness.load_plugin("layer_metrics", "kernels.moe_experts_roofline")._work
    return scope_roofline.pct(ctx, "moe.experts", work)
