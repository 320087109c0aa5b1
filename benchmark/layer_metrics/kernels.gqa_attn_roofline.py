"""The share of its roofline of the softmax layers' scores, softmax and values
(scope ``gqa.attn``: the flash kernel with grouped key/value heads), every
softmax layer of the step together, in percent. Operations: the half of the
scores and values the causal mask leaves, every query head; bytes: queries
read and output written per query head, keys and values of the key/value heads
read once (``shapes/kda_moe.py``). See ``scope_roofline.pct``."""

from benchmark import scope_roofline


def _work(ctx, batch):
    cfg, shapes = ctx.config, ctx.shapes
    layers = shapes.n_gqa_layers(cfg)
    return layers * shapes.gqa_attn_flops(cfg, batch), layers * shapes.gqa_attn_bytes(cfg, batch)


def read(ctx):
    return scope_roofline.pct(ctx, "gqa.attn", _work)
