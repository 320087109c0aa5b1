"""Of the rows the grouped products run, the share that are pairs and not
padding, in percent, in a cell whose router also chooses identity experts (a
held expert sees about 128 pairs a layer in one tile of 256 rows):
``moe.tile_fill_share`` itself, by import of its ``read``, under a name whose
``workloads`` may list this family's cell. Nothing where the program has no
registry, 0.0 where it has no such gauge."""

from benchmark import harness


def read(ctx):
    return harness.load_plugin("layer_metrics", "moe.tile_fill_share").read(ctx)
