"""The sharded configuration's images per second over its one-chip
baseline's (``baseline_config`` in the configuration file), same batches,
same process, both outside the profiler: what the extra chips buy."""

from benchmark import loadgen


def read(ctx):
    sharded = loadgen.median(ctx.samples.get("offline.rate_img_s", []))
    base = loadgen.median(ctx.samples.get("offline.baseline_rate_img_s", []))
    return sharded / base if sharded and base else None
