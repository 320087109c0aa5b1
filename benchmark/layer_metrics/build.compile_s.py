"""Seconds in the first call of each shape this cell uses (the benchmark's
span round it): compilation, or the load from the compile cache. Offline it
is the one batch shape; served, the server's start, which warms every
bucket."""


def read(ctx):
    return ctx.span_seconds("build.compile") if "build.compile" in ctx.spans else None
