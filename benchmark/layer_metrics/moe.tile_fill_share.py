"""Of the rows the grouped products run, the share that are pairs and not
padding, in percent: ``moe.pairs_held / moe.rows_padded`` of the program's
routing gauges (each held expert's pairs rounded up to whole tiles, every MoE
layer of one batch; ``models.moe_share.set_routing_gauges`` fills them outside
any window, the driver calls it after a traced run). It follows the routing
and the tile. 0.0 where the program has no such gauge."""


def read(ctx):
    adapter = getattr(ctx, "adapter", None)
    if adapter is None or not hasattr(adapter, "registry_summary"):
        return None
    program = adapter.registry_summary().summary()
    padded = program.get("moe.rows_padded")
    return 100.0 * program.get("moe.pairs_held", 0.0) / padded if padded else 0.0
