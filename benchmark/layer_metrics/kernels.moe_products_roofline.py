"""The share of their roofline of the routed experts' grouped products alone
(phase ``experts.products`` inside ``moe.experts``: the three ``grouped_matmul``
calls, silu and the multiply), every MoE layer of the step together, in
percent. The work is the MODEL's, whatever tile, chunk or kernel runs it: the
family's ``experts_flops`` for the pairs the plain REFERENCE routed to the held
experts, scaled to the step (as ``kernels.moe_experts_roofline`` takes them);
bytes: every held expert of every MoE layer read once, and per pair one row
read and one written in the compute type. Weights read once per tile and not
once per expert, and padded rows, show here as a lower share. See
``phase_times.pct``, which logs the bound that binds."""

from benchmark import phase_times


def read(ctx):
    return phase_times.pct(ctx, "experts.products", phase_times.products_work)
