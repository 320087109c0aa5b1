"""The share of their roofline of the differential attention layers'
projections (scope ``diff.proj``: the norm, ``W_qkv`` or a cross layer's
``W_q``, the heads laid out for the kernel, ``lambda``, the difference, the
per-head norm and ``W_o``), every attention layer of the step together, in
percent. Operations: 2 x tokens x the matrices; bytes: the matrices, the
float32 residual, queries, keys and values out and both softmaxes' outputs in
(``shapes/sambay.py``). See ``scope_roofline.pct``."""

from benchmark import scope_roofline


def _work(ctx, batch):
    cfg, shapes = ctx.config, ctx.shapes
    own, cross = shapes.n_window_layers(cfg) + 1, shapes.n_cross_layers(cfg)
    return (
        own * shapes.diff_proj_flops(cfg, batch) + cross * shapes.diff_proj_flops(cfg, batch, True),
        own * shapes.diff_proj_bytes(cfg, batch) + cross * shapes.diff_proj_bytes(cfg, batch, True),
    )


def read(ctx):
    return scope_roofline.pct(ctx, "diff.proj", _work)
