"""The share of their roofline of the gated memory units (scope ``gmu``: the
norm, both products and the gate by the memory one earlier layer's scan
made), every unit of the step together, in percent. Operations: 2 x tokens x
the two matrices; bytes: the matrices, the memory, the float32 residual
(``shapes/sambay.py``). See ``scope_roofline.pct``."""

from benchmark import scope_roofline


def _work(ctx, batch):
    cfg, shapes = ctx.config, ctx.shapes
    layers = shapes.n_gmu_layers(cfg)
    return layers * shapes.gmu_flops(cfg, batch), layers * shapes.gmu_bytes(cfg, batch)


def read(ctx):
    return scope_roofline.pct(ctx, "gmu", _work)
