"""Offline traffic: a closed loop of fenced chains over a pool of distinct
seeded batches that wait on the device.

Traffic file: ``batch``, ``pool_batches``, ``chain_len`` (forward calls
between two fences: long enough that one fence's cost is small beside the
chain, after the program's ``utils/timing.amortized_stats``), ``sample_images``
(how many images of one batch are compared with the plain reference) and
``trace_seconds`` (the profiled window of a ``--trace 1`` run).

Every chain ends in ``block_until_ready``, and a chain's reading runs from
the end of the chain before it to its own fence's return, so the readings
cover the window with nothing between them. ``images_per_s`` is the median of
the per-chain rates: on a one-chip machine, whose host is shared, images over
the whole window spread by up to 2% between runs of the same code (PERF.md
section 2), too widely to be held to a bound. The whole window's rate is
printed beside it, and a traced run sets it against the median over an
untraced window of half ``--seconds`` (``step.window_rate_share``), so that
chains that run slow, too few to move the median, show in the record. A chain
that raises is counted under ``failed``.

The batches are float32, made on the default device and left uncommitted,
exactly as the program's ``run.py`` hands them over; the driver places and
reshards nothing, so a sharded configuration pays for its own scatter and
gather inside the measured chain.
"""

from __future__ import annotations

import time
from typing import Dict, List

from benchmark import loadgen
from benchmark.harness import load_config


def make_pool(adapter, cfg: Dict, batch: int, n: int, seed: int) -> list:
    """``n`` distinct uniform [0, 1) float32 batches from the seed, each made
    on the device by one run of one small jitted program."""
    import jax
    import jax.numpy as jnp

    shape = adapter.input_shape(cfg, batch)
    draw = jax.jit(lambda key: jax.random.uniform(key, shape, jnp.float32))
    keys = jax.random.split(jax.random.fold_in(jax.random.key(seed), 2), n)
    pool = [draw(k) for k in keys]
    jax.block_until_ready(pool)
    return pool


def run_chains(ctx, fwd, params, pool, batch: int, chain_len: int,
               seconds: float, rates: List[float]) -> Dict[str, int]:
    """Chains until ``seconds`` have passed, at least one; appends each
    chain's images/s, timed from the end of the chain before it."""
    import jax

    done = {"attempted": 0, "failed": 0, "images": 0}
    start = t0 = time.perf_counter()
    i = 0
    while done["attempted"] == 0 or t0 - start < seconds:
        done["attempted"] += 1
        try:
            with ctx.span("bench.chain"):
                out = None
                for _ in range(chain_len):
                    out = fwd(params, pool[i % len(pool)])
                    i += 1
            with ctx.span("bench.fence"):
                jax.block_until_ready(out)
        except Exception as e:  # noqa: BLE001 — a failed chain is counted, not fatal
            ctx.log(f"chain {done['attempted']} failed: {e!r}")
            done["failed"] += 1
            t0 = time.perf_counter()
            continue
        t1 = time.perf_counter()
        rates.append(chain_len * batch / (t1 - t0))
        t0 = t1
        done["images"] += chain_len * batch
    done["seconds"] = t0 - start
    return done


def log_window(ctx, what: str, done: Dict, rates: List[float],
               chain_len: int, batch: int) -> None:
    median = loadgen.median(rates)
    ctx.log(
        f"{what} window: {done['attempted']} chains of {chain_len} x {batch} "
        f"images, {done['failed']} failed; median chain rate "
        f"{median if median is None else round(median, 1)} img/s; whole window "
        f"{done['images'] / done['seconds']:.1f} img/s over {done['seconds']:.2f} s; "
        f"{sum(r < 0.99 * median for r in rates)} chains more than 1% under the median"
    )


def check(ctx, fwd, params, batch_x, n_sample: int) -> bool:
    """The first ``n_sample`` images of one batch against the plain
    reference, outside any window."""
    import jax
    import numpy as np

    cfg = ctx.config
    got = np.asarray(fwd(params, batch_x)[:n_sample])
    ref_fn = jax.jit(lambda p, x: ctx.reference.forward(cfg, p, x))
    want = np.asarray(ref_fn(params, batch_x[:n_sample]))
    return ctx.check(got, want, f"{n_sample} images")


def run(ctx) -> Dict:
    import jax

    cfg, traffic, adapter = ctx.config, ctx.traffic, ctx.adapter
    batch, chain_len = int(traffic["batch"]), int(traffic["chain_len"])

    params = adapter.make_params(cfg, ctx.seed)
    with ctx.span("setup.build"):
        fwd = adapter.build_forward(cfg)
    with ctx.span("setup.pool"):
        pool = make_pool(adapter, cfg, batch, int(traffic["pool_batches"]), ctx.seed)
    with ctx.span("build.compile", shape=str(pool[0].shape)):
        jax.block_until_ready(fwd(params, pool[0]))
    with ctx.span("setup.warm"):
        run_chains(ctx, fwd, params, pool, batch, chain_len, 0.0, [])
        ctx.spans.pop("bench.chain", None), ctx.spans.pop("bench.fence", None)
    ctx.setup_done()

    rates: List[float] = []
    if ctx.trace_on:
        # A window outside the profiler first, for the rates that the
        # per-layer readers set beside the traced one and beside each other.
        with ctx.measured():
            plain = run_chains(ctx, fwd, params, pool, batch, chain_len, ctx.seconds / 2, rates)
        log_window(ctx, "untraced", plain, rates, chain_len, batch)
        ctx.samples["offline.rate_img_s"] = list(rates)
        ctx.samples["offline.window_rate_img_s"] = [plain["images"] / plain["seconds"]]
        traced: List[float] = []
        with ctx.profile(), ctx.measured():
            done = run_chains(
                ctx, fwd, params, pool, batch, chain_len,
                float(traffic["trace_seconds"]), traced,
            )
        log_window(ctx, "traced", done, traced, chain_len, batch)
        for key in ("attempted", "failed"):
            done[key] += plain[key]
        ctx.name_fusions(fwd, params, pool[0])
        baseline = cfg.get("baseline_config")
        if baseline:
            measure_baseline(ctx, baseline, batch, chain_len, pool)
    else:
        with ctx.measured():
            done = run_chains(ctx, fwd, params, pool, batch, chain_len, ctx.seconds, rates)
        log_window(ctx, "measured", done, rates, chain_len, batch)
    ctx.counters["offline.batch"] = batch
    ok = check(ctx, fwd, params, pool[0], int(traffic["sample_images"]))
    if ctx.trace_on and ctx.trace is not None and ctx.devices[0].platform == "tpu":
        planes = ctx.trace.planes_with_work
        if planes < ctx.cell["chips"]:
            ctx.log(
                f"NOT CORRECT: work on {planes} device planes, the cell "
                f"asks for {ctx.cell['chips']} chips"
            )
            ok = False
    return {
        "attempted": done["attempted"],
        "failed": done["failed"],
        "correct": ok,
        "values": {"images_per_s": loadgen.median(rates)},
    }


def measure_baseline(ctx, baseline: str, batch: int, chain_len: int, pool) -> None:
    """The same batches through the configuration named as this one's
    one-chip baseline, in this process and outside the profiler, for
    ``sharding.speedup_vs_1chip``."""
    import jax

    base_cfg = load_config(ctx.manifest, baseline)
    params = ctx.adapter.make_params(base_cfg, ctx.seed)
    fwd = ctx.adapter.build_forward(base_cfg)
    jax.block_until_ready(fwd(params, pool[0]))
    rates: List[float] = []
    run_chains(ctx, fwd, params, pool, batch, chain_len, 1.0, rates)
    ctx.samples["offline.baseline_rate_img_s"] = rates
    ctx.log(f"baseline {baseline}: median chain rate {loadgen.median(rates):.1f} img/s")
