"""The share of its roofline of the linear layers' scan (scope ``kda.scan``:
the chunked gated-delta-rule kernel), every linear layer of the step together,
in percent. The work is the RECURRENCE's own, whatever algorithm runs it:
``7 d^2`` operations a token and head, ``q, k, v, o`` in the stored type and
``g``, ``beta`` in float32 moved once (``shapes/kda_moe.py``). See
``scope_roofline.pct``, which logs the bound that binds."""

from benchmark import scope_roofline


def _work(ctx, batch):
    cfg, shapes = ctx.config, ctx.shapes
    layers = shapes.n_kda_layers(cfg)
    return layers * shapes.kda_scan_flops(cfg, batch), layers * shapes.kda_scan_bytes(cfg, batch)


def read(ctx):
    return scope_roofline.pct(ctx, "kda.scan", _work)
