"""The share of its roofline of the attention sublayers' projections (scope
``cca.proj``: the norm, the projections of queries, keys and the two value
heads into the latent, the output projection, the merge), every layer of the
step together, in percent. Operations: the five matrices' products; bytes: the
float32 residual read and written, the matrices read, the latent ``q~, k~, v``
written and the attention's output read in the stored type
(``shapes/cca_moe.py``). See ``scope_roofline.pct``."""

from benchmark import scope_roofline


def _work(ctx, batch):
    cfg, shapes = ctx.config, ctx.shapes
    layers = cfg["num_layers"]
    return layers * shapes.cca_proj_flops(cfg, batch), layers * shapes.cca_proj_bytes(cfg, batch)


def read(ctx):
    return scope_roofline.pct(ctx, "cca.proj", _work)
