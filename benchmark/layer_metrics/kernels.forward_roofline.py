"""The forward program's share of its roofline, in percent: the least time
the peaks of the chips the cell uses allow one step (the larger of operations
over peak FLOP/s and bytes over peak bytes/s, both from the benchmark's shape
functions and ``peaks.json``, times the number of chips) over the step's
device time from the trace. The bound that binds is logged. Only where the
driver says how many images a step holds (``offline.batch``)."""

from benchmark import loadgen


def read(ctx):
    if ctx.trace is None or ctx.peaks is None:
        return None
    step_ms = loadgen.median(ctx.trace.step_durations_ms())
    batch = ctx.counters.get("offline.batch")
    if not step_ms or not batch:
        return None
    chips = len(ctx.devices)
    peak = ctx.peaks[f"{ctx.config['compute']}_tflops"] * 1e12 * chips
    flops = ctx.shapes.matmul_flops_per_image(ctx.config) * batch
    bytes_ = ctx.shapes.min_bytes_per_step(ctx.config, int(batch))
    t_flops = flops / peak
    t_bytes = bytes_ / (ctx.peaks["hbm_gbps"] * 1e9 * chips)
    least = max(t_flops, t_bytes)
    ctx.log(
        f"roofline on {chips} chip(s): {flops / 1e9:.1f} GFLOP -> {t_flops * 1e3:.4f} ms at peak, "
        f"{bytes_ / 1e6:.1f} MB -> {t_bytes * 1e3:.4f} ms at peak; "
        f"{'compute' if t_flops >= t_bytes else 'memory'}-bound; "
        f"step {step_ms:.4f} ms on the device"
    )
    return 100.0 * least * 1e3 / step_ms
