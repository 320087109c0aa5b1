"""Images answered per dispatched batch (the program's counters): how much
the batcher packs together at this rate."""


def read(ctx):
    batches = ctx.counters.get("serve.batches")
    return ctx.counters["serve.images"] / batches if batches else None
