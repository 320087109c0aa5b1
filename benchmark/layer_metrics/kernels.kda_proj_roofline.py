"""The share of its roofline of the linear layers' projections (scope
``kda.proj``: the norm, q/k/v, both low-rank pairs, the write strength, the
output norm, gate and projection), every linear layer of the step together, in
percent. Operations: the matrices' products; bytes: the residual read and
written, the matrices read, ``q, k, v, o`` in the stored type and ``g``,
``beta`` in float32 once (``shapes/kda_moe.py``). See ``scope_roofline.pct``."""

from benchmark import scope_roofline


def _work(ctx, batch):
    cfg, shapes = ctx.config, ctx.shapes
    layers = shapes.n_kda_layers(cfg)
    return layers * shapes.kda_proj_flops(cfg, batch), layers * shapes.kda_proj_bytes(cfg, batch)


def read(ctx):
    return scope_roofline.pct(ctx, "kda.proj", _work)
