"""A reader's whole body for the roofline share of one scope whose work is not
a run of AlexNet layers: the least time the chip's peaks allow the scope's
step (the larger of operations over the compute type's peak and bytes over the
HBM peak) over the device time the trace shows under that scope, in percent.
The bound that binds is logged. As ``layer_times.roofline_pct``: nothing
without a device plane, 0 where no operation carries the scope, and only then
are the family's own shape functions asked for the work.
"""

from __future__ import annotations

from typing import Callable, Optional, Tuple

from benchmark import layer_times

# Operations whose own duration covers their bodies', which are in the trace too
CONTAINERS = ("while", "conditional", "call")


def body_ms(ctx, lt, scope: str) -> float:
    """Milliseconds per step under ``scope``, as ``LayerTimes.step_ms`` takes
    them (the operations that start inside a run of the step program on a whole
    chip, mean over runs and chips), but leaving out the control-flow
    operations: a ``while`` lasts as long as its body, whose operations the
    trace lists too, so the per-scope table counts a loop twice."""
    program = ctx.trace.step_program()
    lo, hi = ctx.trace.window_ns
    total = steps = 0
    for d in ctx.trace.devices.values():
        runs = sorted(
            (start, start + dur) for name, start, dur in d["modules"]
            if name.split("(")[0] == program and start >= lo and start + dur <= hi
        )
        steps += len(runs)
        for label, opcode, start, dur in d["ops"]:
            if opcode in CONTAINERS or lt.scopes.get(label.split(" ")[0]) != scope:
                continue
            if any(a <= start < b for a, b in runs):
                total += dur
    return total / 1e6 / steps if steps else 0.0


def pct(ctx, scope: str, work: Callable[[object, int], Tuple[float, float]]) -> Optional[float]:
    """``work(ctx, batch) -> (operations, bytes)`` of one whole step in ``scope``."""
    lt = layer_times.of(ctx)
    if lt is None:
        return None
    batch = ctx.counters.get("offline.batch")
    if ctx.peaks is None or not batch:
        return None
    if lt.step_ms(layer_times.exactly(scope)) <= 0:
        return 0.0
    step_ms = body_ms(ctx, lt, scope)
    chips = len(ctx.devices)
    flops, bytes_ = work(ctx, int(batch))
    t_flops = flops / (ctx.peaks[f"{ctx.config['compute']}_tflops"] * 1e12 * chips)
    t_bytes = bytes_ / (ctx.peaks["hbm_gbps"] * 1e9 * chips)
    ctx.log(
        f"roofline of {scope} on {chips} chip(s): {flops / 1e9:.1f} GFLOP -> {t_flops * 1e3:.4f} ms "
        f"at peak, {bytes_ / 1e6:.1f} MB -> {t_bytes * 1e3:.4f} ms at peak; "
        f"{'compute' if t_flops >= t_bytes else 'memory'}-bound; {step_ms:.4f} ms on the device"
    )
    return 100.0 * max(t_flops, t_bytes) * 1e3 / step_ms
