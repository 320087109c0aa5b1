"""The share of its roofline of the latent attention's scores, softmax and
values (scope ``mla.attn``: the flash kernel), every layer of the step
together, in percent. Operations: the half of the scores and values the causal
mask leaves; bytes: queries, keys and values read and the output written once
(``shapes/mla_moe.py``). See ``scope_roofline.pct``."""

from benchmark import scope_roofline


def _work(ctx, batch):
    cfg, shapes = ctx.config, ctx.shapes
    return cfg["num_layers"] * shapes.attn_flops(cfg, batch), cfg["num_layers"] * shapes.attn_bytes(cfg, batch)


def read(ctx):
    return scope_roofline.pct(ctx, "mla.attn", _work)
