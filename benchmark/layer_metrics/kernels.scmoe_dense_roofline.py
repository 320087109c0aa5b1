"""The share of their roofline of the dense SwiGLUs of a shortcut-connected
layer (scope ``dense_mlp``: both of a layer's, with the residual additions and
the branch's at the layer's end), every layer of the step together, in
percent. Operations: 2 x tokens x the three matrices, twice a layer; bytes:
the matrices and the float32 residual read and written
(``shapes/scmoe_mla.py``). See ``scope_roofline.pct``."""

from benchmark import scope_roofline


def _work(ctx, batch):
    cfg, shapes = ctx.config, ctx.shapes
    n = shapes.n_attentions(cfg)
    return n * shapes.dense_flops(cfg, batch), n * shapes.dense_bytes(cfg, batch)


def read(ctx):
    return scope_roofline.pct(ctx, "dense_mlp", _work)
