"""The share of its roofline of the attention sublayers' scores, softmax and
values (scope ``cca.attn``: the flash kernel on the latent's heads, 8 query
heads over 2 key/value heads), every layer of the step together, in percent.
Operations: the half of the scores and values the causal mask leaves, every
query head at ``head_dim`` wide; bytes: queries read and output written per
query head, keys and values of the key/value heads read once
(``shapes/cca_moe.py``). See ``scope_roofline.pct``."""

from benchmark import scope_roofline


def _work(ctx, batch):
    cfg, shapes = ctx.config, ctx.shapes
    layers = cfg["num_layers"]
    return layers * shapes.cca_attn_flops(cfg, batch), layers * shapes.cca_attn_bytes(cfg, batch)


def read(ctx):
    return scope_roofline.pct(ctx, "cca.attn", _work)
