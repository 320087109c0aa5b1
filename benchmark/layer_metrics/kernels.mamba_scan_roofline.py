"""The share of its roofline of the state-space layers' scan (scope
``mamba.scan``: the selective scan's kernel), every Mamba layer of the step
together, in percent. The work is the RECURRENCE's own, whatever algorithm
runs it: 6 operations a token, channel and state; ``x''`` and ``y`` in the
stored type, ``Delta`` in float32, ``B`` and ``C`` moved once
(``shapes/sambay.py``). A memory-bound yardstick that a scan on the vector
units reads low against: the peaks are the MXU's and the HBM's, and a token
loop is bound by neither. See ``scope_roofline.pct``, which logs the bound
that binds."""

from benchmark import scope_roofline


def _work(ctx, batch):
    cfg, shapes = ctx.config, ctx.shapes
    layers = shapes.n_mamba_layers(cfg)
    return layers * shapes.mamba_scan_flops(cfg, batch), layers * shapes.mamba_scan_bytes(cfg, batch)


def read(ctx):
    return scope_roofline.pct(ctx, "mamba.scan", _work)
