"""The share of its roofline of the windowed differential attention (scope
``diff.attn_window``: the flash kernel over the band), every windowed layer of
the step together, in percent. Operations: the scores the window leaves
(``sum_q min(q + 1, window)`` a head), both softmaxes of a differential head,
keys a head wide and values two; bytes: queries and outputs per query head,
keys and values of a pair once (``shapes/sambay.py``). See
``scope_roofline.pct``."""

from benchmark import scope_roofline


def _work(ctx, batch):
    cfg, shapes = ctx.config, ctx.shapes
    layers = shapes.n_window_layers(cfg)
    return (
        layers * shapes.diff_attn_flops(cfg, batch, cfg["sliding_window"]),
        layers * shapes.diff_attn_bytes(cfg, batch),
    )


def read(ctx):
    return scope_roofline.pct(ctx, "diff.attn_window", _work)
