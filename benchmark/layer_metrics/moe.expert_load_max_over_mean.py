"""The fullest held expert's pairs over the held experts' mean, over every MoE
layer of one batch: the program's routing gauge (``models.mla_moe.routing_statistics``
fills it outside any window; the driver calls it after a traced run). 1 is an
even load; the grouped product's padding and the chunks' count grow with it."""


def read(ctx):
    adapter = getattr(ctx, "adapter", None)
    if adapter is None or not hasattr(adapter, "registry_summary"):
        return None
    return adapter.registry_summary().summary().get("moe.expert_load_max_over_mean")
