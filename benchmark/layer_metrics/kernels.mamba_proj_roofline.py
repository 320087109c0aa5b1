"""The share of their roofline of the state-space layers' projections (scope
``mamba.proj``: the norm, ``W_in``, ``W_x``, ``W_dt``, the gate and ``W_out``),
every Mamba layer of the step together, in percent. Operations: 2 x tokens x
the four matrices; bytes: the matrices, the float32 residual, the activations
between them and the scan (``shapes/sambay.py``). See ``scope_roofline.pct``."""

from benchmark import scope_roofline


def _work(ctx, batch):
    cfg, shapes = ctx.config, ctx.shapes
    layers = shapes.n_mamba_layers(cfg)
    return layers * shapes.mamba_proj_flops(cfg, batch), layers * shapes.mamba_proj_bytes(cfg, batch)


def read(ctx):
    return scope_roofline.pct(ctx, "mamba.proj", _work)
