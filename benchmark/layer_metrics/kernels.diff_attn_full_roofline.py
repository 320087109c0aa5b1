"""The share of its roofline of the causal differential attention (scope
``diff.attn_full``: the flash kernel of the layer that keeps its keys and
values and of the cross layers that read them), those layers of the step
together, in percent. Operations: the causal half of the scores, both
softmaxes of a differential head, keys a head wide and values two; bytes:
queries and outputs per query head, keys and values of a pair once
(``shapes/sambay.py``). See ``scope_roofline.pct``."""

from benchmark import scope_roofline


def _work(ctx, batch):
    cfg, shapes = ctx.config, ctx.shapes
    layers = shapes.n_full_layers(cfg)
    return layers * shapes.diff_attn_flops(cfg, batch), layers * shapes.diff_attn_bytes(cfg, batch)


def read(ctx):
    return scope_roofline.pct(ctx, "diff.attn_full", _work)
