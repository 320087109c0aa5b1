"""Wall-clock timing of jitted callables.

The reference times with ``std::chrono`` around the whole pass
(v1_serial/src/alexnet_serial.cpp:74,174-176; v3_cuda_only/src/main_cuda.cpp:30-36)
and its printed ``... completed in X ms`` line is the de-facto profiling API
consumed by the harness regex (scripts/common_test_utils.sh:296-297). Here
timing is explicit: warmup iterations absorb XLA compilation (the analogue of
the reference's "cold first session" 2.349 s V3 outlier, README.md:188), and
``block_until_ready`` pins async dispatch.
"""

from __future__ import annotations

import dataclasses
import statistics
import time
from typing import Any, Callable, List, Optional

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class TimingResult:
    times_ms: List[float]
    compile_ms: float

    @property
    def best_ms(self) -> float:
        return min(self.times_ms)

    @property
    def mean_ms(self) -> float:
        return statistics.fmean(self.times_ms)

    @property
    def stdev_ms(self) -> float:
        return statistics.stdev(self.times_ms) if len(self.times_ms) > 1 else 0.0


def _block(out: Any) -> None:
    for leaf in jax.tree_util.tree_leaves(out):
        if hasattr(leaf, "block_until_ready"):
            leaf.block_until_ready()


def time_fn_ms(fn: Callable, *args: Any, repeats: int = 10, warmup: int = 1) -> TimingResult:
    """Time ``fn(*args)`` end to end, one fenced call per sample (each
    sample therefore carries the host's per-dispatch latency; use
    :func:`amortized_stats` for device throughput). First call is measured
    as compile time.
    """
    t0 = time.perf_counter()
    _block(fn(*args))
    compile_ms = (time.perf_counter() - t0) * 1e3
    for _ in range(max(0, warmup - 1)):
        _block(fn(*args))
    times = []
    for _ in range(max(1, repeats)):
        t0 = time.perf_counter()
        _block(fn(*args))
        times.append((time.perf_counter() - t0) * 1e3)
    return TimingResult(times_ms=times, compile_ms=compile_ms)


def _fetch_scalar(out: Any) -> float:
    """Device->host fetch of one element — a completion fence that consumes
    the result (single-stream ordering implies everything enqueued before
    it has finished)."""
    leaf = jax.tree_util.tree_leaves(out)[0]
    return float(jnp.ravel(leaf)[0])


def sync_fence(fn: Callable, *args: Any) -> None:
    """Run once and force true completion via a D2H scalar fetch."""
    _fetch_scalar(fn(*args))


@dataclasses.dataclass(frozen=True)
class AmortizedStats:
    """Result of :func:`amortized_stats` — per-call estimate plus enough
    metadata (sample list, chain length, accumulated measured time) for a
    consumer to report n and a confidence interval instead of a bare point."""

    samples_ms: List[float]   # independent per-call estimates, one per repeat
    n_chain: int              # chain length the estimates were taken at
    shadowed: bool            # True = fence-shadow fallback (upper bound, not a difference)
    total_measured_s: float   # wall time accumulated across all measurement runs
    # True = the resample loop exhausted its attempt budget discarding
    # hiccup pairs and ended below min_samples — the ci95 then reflects too
    # few samples, NOT a passed convergence gate. Distinct from `shadowed`.
    underconverged: bool = False

    @property
    def per_call_ms(self) -> float:
        # Median, not mean: a single host hiccup inflates one sample by
        # milliseconds and the mean with it.
        return max(1e-3, statistics.median(self.samples_ms))

    @property
    def n_samples(self) -> int:
        return len(self.samples_ms)

    @property
    def stdev_ms(self) -> float:
        return statistics.stdev(self.samples_ms) if len(self.samples_ms) > 1 else 0.0

    @property
    def ci95_ms(self) -> float:
        """Half-width of a 95% CI on the MEDIAN (the reported estimator) —
        MAD-based so it stays coherent with per_call_ms: one surviving
        hiccup sample must not blow the interval up (a mean/stdev CI on
        [1,1,1,1,8] reads "1.0 ± 2.7 ms" for a median the hiccup barely
        moved). sigma ≈ 1.4826·MAD; Var(median) ≈ (π/2)·σ²/n."""
        if len(self.samples_ms) < 2:
            return 0.0
        med = statistics.median(self.samples_ms)
        mad = statistics.median([abs(s - med) for s in self.samples_ms])
        sigma = 1.4826 * mad
        return 1.96 * sigma * (1.5707963267948966 / len(self.samples_ms)) ** 0.5


def amortized_stats(
    fn: Callable, *args: Any, n_small: int = 10, n_large: int = 110,
    max_chain: int = 4096, work_floor_ms: Optional[float] = None,
    min_samples: int = 3, max_samples: int = 15,
) -> AmortizedStats:
    """Per-call wall time of a pipelined chain: enqueue N calls, fence on
    the last output, and difference two queue lengths so the fixed cost of
    a fence cancels:

        per_call = (T(n_large) - T(n_small)) / (n_large - n_small)

    Rationale: JAX dispatch is asynchronous, so a single fenced call times
    one dispatch plus one device->host round trip on top of the device
    work; for passes of a millisecond or less that host cost is comparable
    to the work. A long enqueued chain between two fences keeps the device
    queue full, so the difference measures sustained per-call time
    (conservatively: any host overhead that does not pipeline is charged
    to compute).

    Validity guard: when the per-pass compute is tiny, the extra chain work
    finishes inside the fence's own latency and T(n_large) ~= T(n_small) —
    the difference is pure noise (observed on TPU: fabricated "0.001 ms"
    passes = 64M img/s). The chain is therefore grown until the long run
    clearly dominates the short one; if even ``max_chain`` calls can't
    escape the fence's shadow, the CONSERVATIVE bound T(n)/n (fixed costs
    charged to compute) is returned instead of the noise difference.

    Work floor (sub-3 ms rows carried ~40% run-to-run variance when the
    fence latency dominated a short chain): the chain is also grown until
    one long run accumulates >= ``work_floor_ms`` of measured wall time,
    and the (T_small, T_large) pair is then re-measured
    ``min_samples``..``max_samples`` times — stopping once the spread is
    resolved (ci95 < 5% of the median) — so the result carries n and a CI
    instead of a single noisy point.

    ``work_floor_ms=None`` (the default) resolves per platform: 100 ms on
    accelerators, 0 on the CPU backend, whose passes are long enough not to
    need it — and XLA's CPU collective thunks ABORT
    (CollectivePermuteThunk SIGABRT, observed with the sharded configs on a
    virtual mesh) when a work-floor-grown chain queues tens of unfenced
    multi-device programs. Explicit values are always honored.
    """
    if n_large <= n_small:
        raise ValueError(f"n_large ({n_large}) must exceed n_small ({n_small})")
    if work_floor_ms is None:
        work_floor_ms = 0.0 if jax.default_backend() == "cpu" else 100.0
    if min_samples < 1 or max_samples < min_samples:
        raise ValueError(f"need 1 <= min_samples <= max_samples, got {min_samples}/{max_samples}")
    _block(fn(*args))  # compile
    sync_fence(fn, *args)  # warm the fetch path the fences below use

    total = 0.0

    def run(n: int) -> float:
        nonlocal total
        t0 = time.perf_counter()
        out = None
        for _ in range(n):
            out = fn(*args)
        _fetch_scalar(out)
        dt = time.perf_counter() - t0
        total += dt
        return dt

    t_small = run(n_small)
    n = n_large
    t_large = run(n)
    while (t_large < 1.5 * t_small or t_large * 1e3 < work_floor_ms) and n < max_chain:
        n = min(max_chain, n * 2)
        t_large = run(n)
    if t_large < 1.5 * t_small:
        # Still inside the fence's shadow: report the upper bound rather
        # than noise.
        return AmortizedStats(
            samples_ms=[t_large / n * 1e3], n_chain=n, shadowed=True,
            total_measured_s=total,
        )

    samples = [max(1e-3, (t_large - t_small) / (n - n_small) * 1e3)]
    attempts = 1
    while len(samples) < max_samples and attempts < 2 * max_samples:
        stats = AmortizedStats(samples, n, False, total)
        if len(samples) >= min_samples and stats.ci95_ms < 0.05 * stats.per_call_ms:
            break
        ts, tl = run(n_small), run(n)
        attempts += 1
        # A host hiccup landing on the SHORT run makes tl - ts tiny or
        # negative; clamping such a pair would inject a fabricated ~0 ms
        # sample (the "64M img/s" failure mode) into the median. Keep the
        # same dominance criterion the first pair had to pass, and discard
        # pairs that fail it rather than record them.
        if tl < 1.5 * ts:
            continue
        samples.append((tl - ts) / (n - n_small) * 1e3)
    return AmortizedStats(
        samples_ms=samples, n_chain=n, shadowed=False, total_measured_s=total,
        underconverged=len(samples) < min_samples,
    )


def amortized_ms(
    fn: Callable, *args: Any, n_small: int = 10, n_large: int = 110,
    max_chain: int = 4096,
) -> float:
    """Back-compat scalar form of :func:`amortized_stats` (single sample, no
    work floor) — existing sweep callers keep their exact cost profile."""
    return amortized_stats(
        fn, *args, n_small=n_small, n_large=n_large, max_chain=max_chain,
        work_floor_ms=0.0, min_samples=1, max_samples=1,
    ).per_call_ms
