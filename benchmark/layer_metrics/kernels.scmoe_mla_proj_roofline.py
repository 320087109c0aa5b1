"""The share of their roofline of the latent attentions' projections with
their norms, the two latent scales and the rotary embedding (scope
``mla.proj``) where a layer has TWO attentions, every layer of the step
together, in percent. The work of one attention is reckoned as
``kernels.mla_proj_roofline`` reckons it (``shapes/mla_moe.py``'s counts, which
``shapes/scmoe_mla.py`` hands on). See ``scope_roofline.pct``."""

from benchmark import scope_roofline


def _work(ctx, batch):
    cfg, shapes = ctx.config, ctx.shapes
    n = shapes.n_attentions(cfg)
    return n * shapes.proj_flops(cfg, batch), n * shapes.proj_bytes(cfg, batch)


def read(ctx):
    return scope_roofline.pct(ctx, "mla.proj", _work)
