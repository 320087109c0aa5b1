"""Time of the collective and cross-device operations (the trace's
``hlo_category``) over the time of all device operations, in percent."""

from benchmark import trace_reduce


def read(ctx):
    if ctx.trace is None:
        return None
    share = ctx.trace.share_of(trace_reduce.COLLECTIVE_CATEGORIES)
    return None if share is None else 100.0 * share
