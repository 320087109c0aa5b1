"""99th percentile (nearest rank) of send time minus due time: how late the
benchmark's own generator ran. Small against request_p50_ms, or the
generator was measured and not the server."""

from benchmark import loadgen


def read(ctx):
    return loadgen.percentile(ctx.samples.get("loadgen.late_ms", []), 99)
