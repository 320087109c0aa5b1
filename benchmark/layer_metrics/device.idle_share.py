"""Share of the traced window in which no operation ran on the chip: 1 -
union of the operations' intervals over the window, in percent; with
several chips, the mean over them. A chip on which the profiler lost events
is left out of the mean, as it is out of ``device.busy_s``, and named on an
earlier line (the harness logs each whole chip's busy time beside it)."""


def read(ctx):
    if ctx.trace is None or ctx.trace.idle_share() is None:
        return None
    return 100.0 * ctx.trace.idle_share()
