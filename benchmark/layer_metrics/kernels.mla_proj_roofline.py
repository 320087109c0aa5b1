"""The share of their roofline of the latent attention's five projections with
their norms and the rotary embedding (scope ``mla.proj``), every layer of the
step together, in percent. Operations: 2 x tokens x the five matrices; bytes:
the matrices, the float32 residual read and written, queries, keys and values
written and the attention's output read (``shapes/mla_moe.py``). See
``scope_roofline.pct``."""

from benchmark import scope_roofline


def _work(ctx, batch):
    cfg, shapes = ctx.config, ctx.shapes
    return cfg["num_layers"] * shapes.proj_flops(cfg, batch), cfg["num_layers"] * shapes.proj_bytes(cfg, batch)


def read(ctx):
    return scope_roofline.pct(ctx, "mla.proj", _work)
