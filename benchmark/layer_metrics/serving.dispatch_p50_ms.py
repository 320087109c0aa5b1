"""Median of the program's ``serve.batch_ms`` histogram: one dispatch from
the copy in, through the forward, to the fence. Padding is before it and
the copy out after it."""


def read(ctx):
    return ctx.adapter.registry_summary().histogram("serve.batch_ms").percentile(50)
