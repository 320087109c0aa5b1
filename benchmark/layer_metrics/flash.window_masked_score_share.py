"""Of the scores the windowed layers' flash kernel computes for one head, the
share its two masks throw away, in percent: the program's gauge
(``models.sambay.layer_statistics`` fills it outside any window from
``flash_attention.causal_plan`` with the window; the driver calls it after a
traced run). A matter of the sequence length, the window and the block alone.
Nothing where the program has no such gauge."""


def read(ctx):
    adapter = getattr(ctx, "adapter", None)
    if adapter is None or not hasattr(adapter, "registry_summary"):
        return None
    share = adapter.registry_summary().summary().get("flash.window_masked_score_share")
    return None if share is None else 100.0 * share
