"""The tokens whose top-1 is the router's skip output over all routed tokens,
every layer of one batch, in percent: the program's gauge
(``models.cca_moe.layer_statistics`` fills it outside any window; the driver
calls it after a traced run). A skipped token dispatches no pair, so the
experts' rows fall with it. Nothing where the program has no such gauge."""


def read(ctx):
    adapter = getattr(ctx, "adapter", None)
    if adapter is None or not hasattr(adapter, "registry_summary"):
        return None
    share = adapter.registry_summary().summary().get("moe.skip_share")
    return None if share is None else 100.0 * share
