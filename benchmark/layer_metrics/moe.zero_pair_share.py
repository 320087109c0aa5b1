"""The chosen places that are identity experts over all places, every layer of
one batch, in percent: the program's gauge
(``models.scmoe_mla.routing_statistics`` fills it outside any window; the
driver calls it after a traced run). An identity expert runs no product and
sends no row, so the routed experts' work falls as this rises. Nothing where
the program has no such gauge."""


def read(ctx):
    adapter = getattr(ctx, "adapter", None)
    if adapter is None or not hasattr(adapter, "registry_summary"):
        return None
    share = adapter.registry_summary().summary().get("moe.zero_pair_share")
    return None if share is None else 100.0 * share
