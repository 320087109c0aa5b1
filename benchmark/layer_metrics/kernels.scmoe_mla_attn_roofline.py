"""The share of its roofline of the latent attentions' scores, softmax and
values (scope ``mla.attn``: the flash kernel at 64 heads of 128 + 64 and 128)
where a layer has TWO attentions, every layer of the step together, in
percent. Operations: the half of the scores and values the causal mask leaves;
bytes: queries, keys and values read and the output written once
(``shapes/mla_moe.py``'s counts, which ``shapes/scmoe_mla.py`` hands on). See
``scope_roofline.pct``."""

from benchmark import scope_roofline


def _work(ctx, batch):
    cfg, shapes = ctx.config, ctx.shapes
    n = shapes.n_attentions(cfg)
    return n * shapes.attn_flops(cfg, batch), n * shapes.attn_bytes(cfg, batch)


def read(ctx):
    return scope_roofline.pct(ctx, "mla.attn", _work)
