"""Images over the whole of the traced run's untraced window (half of
``--seconds``) over the median of its chains' rates, in percent. The chains'
readings cover that window with nothing between them, so it is under 100 by
what the chains that ran slow cost: ``images_per_s`` is the median and cannot
see them while they are under half of all chains. Host clock."""

from benchmark import loadgen


def read(ctx):
    window = loadgen.median(ctx.samples.get("offline.window_rate_img_s", []))
    chains = loadgen.median(ctx.samples.get("offline.rate_img_s", []))
    return 100.0 * window / chains if window and chains else None
