"""Device time of one run of the forward program (the jitted program that
took most device time in the traced window): median over the traced runs,
over every chip that ran it."""

from benchmark import loadgen


def read(ctx):
    if ctx.trace is None:
        return None
    return loadgen.median(ctx.trace.step_durations_ms())
