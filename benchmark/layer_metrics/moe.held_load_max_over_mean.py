"""The fullest held expert's pairs over the held experts' mean, over every
layer of one batch, in a cell whose router also chooses identity experts: the
program's routing gauge ``moe.expert_load_max_over_mean``
(``models.scmoe_mla.routing_statistics`` fills it outside any window; the
driver calls it after a traced run), read as ``moe.expert_load_max_over_mean``
reads it (by import of its ``read``). 1 is an even load; the grouped product's
padding grows with it."""

from benchmark import harness


def read(ctx):
    return harness.load_plugin("layer_metrics", "moe.expert_load_max_over_mean").read(ctx)
