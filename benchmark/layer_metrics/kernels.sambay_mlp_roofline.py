"""The share of their roofline of the MLPs of the decoder-hybrid-decoder (scope
``dense_mlp``: the norm, ``W_1``, the gate, ``W_2`` and the residual addition,
one after every mixer), every layer of the step together, in percent.
Operations: 2 x tokens x the two matrices; bytes: the matrices and the
float32 residual read and written (``shapes/sambay.py``). See
``scope_roofline.pct``."""

from benchmark import scope_roofline


def _work(ctx, batch):
    cfg, shapes = ctx.config, ctx.shapes
    layers = cfg["num_hidden_layers"]
    return layers * shapes.mlp_flops(cfg, batch), layers * shapes.mlp_bytes(cfg, batch)


def read(ctx):
    return scope_roofline.pct(ctx, "dense_mlp", _work)
