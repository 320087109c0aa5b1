"""Served traffic: an open loop against the program's in-process server.

Traffic file: ``rate_rps`` and optional ``bursts`` (see ``loadgen.arrivals``),
``classes`` (a class sets a request's size and nothing else), ``server``
(the server's own settings, here ``max_batch``), ``pool_images`` (distinct
float32 images the requests are cut from), ``sample_images`` (how many
images' answers are compared with the plain reference), ``trace_seconds``
and ``drain_timeout_s``.

Requests are due at seeded Poisson times and are sent by one thread, which
sleeps until each is due. A request's latency runs FROM THE TIME IT WAS DUE
to the time its answer was complete, so a stall of the server (or of the
generator) is charged to every request that it delays; how late the
generator sent each request is kept beside it (``loadgen.late_ms``). A
request that is rejected, shed, failed or unanswered when the drain time
ends counts under ``failed`` and has no latency.

The pool is made on the device from the seed in one jitted call, copied to
the host once (requests are host arrays, as a caller's are), and kept on
the device as the input of the reference. It is large on purpose: requests
that re-send a handful of images would be served from the host's caches,
and the host path is what this traffic measures.
"""

from __future__ import annotations

import collections
import contextlib
import random
import time
from typing import Dict, List, Tuple

from benchmark import loadgen


def make_pool(adapter, cfg: Dict, n_images: int, seed: int):
    """``n_images`` distinct uniform [0, 1) float32 images from the seed:
    drawn on the device in one jitted call, and the same on the host."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    shape = adapter.input_shape(cfg, n_images)
    draw = jax.jit(lambda key: jax.random.uniform(key, shape, jnp.float32))
    pool_dev = draw(jax.random.fold_in(jax.random.key(seed), 2))
    return pool_dev, np.asarray(pool_dev)


def plan_requests(traffic: Dict, seconds: float, seed: int) -> List[Tuple[float, str, int, int]]:
    """``(due_s, class, images, pool offset)`` for every request of the
    window: the same for one seed, another for another."""
    due = loadgen.arrivals(traffic, seconds, seed)
    sizes = loadgen.assign_sizes(traffic["classes"], len(due), seed)
    rng = random.Random(f"benchmark.offsets:{seed}")
    pool = int(traffic["pool_images"])
    return [
        (t, cls, n, rng.randrange(0, pool - n + 1))
        for t, (cls, n) in zip(due, sizes)
    ]


def pick_sample(plan, budget: int, seed: int) -> List[int]:
    """Indices of requests whose answers are checked: a seeded walk that
    takes every request that still fits the budget of images."""
    order = list(range(len(plan)))
    random.Random(f"benchmark.sample:{seed}").shuffle(order)
    picked, left = [], budget
    for i in order:
        n = plan[i][2]
        if n <= left:
            picked.append(i)
            left -= n
        if left == 0 or len(picked) >= 16:
            break
    return sorted(picked)


def drive(ctx, server, plan, pool_host, sample: List[int], drain_timeout_s: float) -> Dict:
    """Send every request of ``plan`` when it is due and collect the
    outcome. Answers are dropped as they are read, except the sampled
    ones, so the host does not fill with outputs."""
    latencies: List[float] = []
    answered_due: List[float] = []  # when each answered request was due, from t0
    late: List[float] = []
    kept: Dict[int, object] = {}
    wanted = set(sample)
    outcome = collections.Counter()
    pending = collections.deque()  # (index, handle, due time on time.monotonic)

    def reap(block_until: float = 0.0) -> None:
        while pending:
            i, handle, due_at = pending[0]
            if not handle.done:
                left = block_until - time.monotonic()
                if left <= 0 or not handle.wait(left):
                    return
            pending.popleft()
            outcome[handle.status] += 1
            if handle.status == "OK":
                latencies.append((handle.completed_at - due_at) * 1e3)
                answered_due.append(due_at - t0)
                if i in wanted:
                    kept[i] = handle.result.copy()
            handle.result = None

    t0 = time.monotonic()
    for i, (due, cls, n, off) in enumerate(plan):
        due_at = t0 + due
        wait = due_at - time.monotonic()
        if wait > 0:
            time.sleep(wait)
        with ctx.span("bench.submit"):
            sent_at = time.monotonic()
            try:
                handle = server.submit(pool_host[off : off + n], cls=cls)
            except (RuntimeError, ValueError) as e:  # QueueFull, too wide
                outcome[f"rejected:{type(e).__name__}"] += 1
                continue
        late.append((sent_at - due_at) * 1e3)
        pending.append((i, handle, due_at))
        reap()
    sent_s = time.monotonic() - t0
    with ctx.span("bench.drain"):
        reap(block_until=time.monotonic() + drain_timeout_s)
    outcome["unanswered"] += len(pending)
    return {
        "latencies_ms": latencies, "answered_due_s": answered_due,
        "late_ms": late, "kept": kept,
        "outcome": outcome, "sent_s": sent_s,
        "wall_s": time.monotonic() - t0,
    }


def check(ctx, params, pool_dev, plan, kept: Dict[int, object], budget: int) -> bool:
    """The sampled requests' answers against the plain reference on the
    images each sent, in one reference batch of a fixed size."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    cfg = ctx.config
    if not kept:
        ctx.log("check: NOT CORRECT, no sampled request was answered")
        return False
    rows = np.concatenate(
        [np.arange(plan[i][3], plan[i][3] + plan[i][2]) for i in sorted(kept)]
    )
    padded = np.zeros(budget, np.int32)
    padded[: len(rows)] = rows
    ref_fn = jax.jit(lambda p, pool, idx: ctx.reference.forward(cfg, p, pool[idx]))
    want = np.asarray(ref_fn(params, pool_dev, jnp.asarray(padded)))[: len(rows)]
    got = np.concatenate([kept[i] for i in sorted(kept)])
    return ctx.check(got, want, f"{len(kept)} requests ({len(rows)} images)")


def run(ctx) -> Dict:
    cfg, traffic, adapter = ctx.config, ctx.traffic, ctx.adapter
    window_s = float(traffic["trace_seconds"]) if ctx.trace_on else ctx.seconds
    budget = int(traffic["sample_images"])

    params = adapter.make_params(cfg, ctx.seed)
    with ctx.span("setup.pool"):
        pool_dev, pool_host = make_pool(adapter, cfg, int(traffic["pool_images"]), ctx.seed)
    plan = plan_requests(traffic, window_s, ctx.seed)
    sample = pick_sample(plan, budget, ctx.seed)
    server = adapter.build_server(cfg, params, traffic["server"])
    try:
        # start() builds the forward and warms every bucket shape: the
        # first call of each shape, which is where compilation happens.
        with ctx.span("build.compile", buckets=str(server.buckets)):
            server.start()
        ctx.setup_done()
        profiled = ctx.profile() if ctx.trace_on else contextlib.nullcontext()
        with profiled, ctx.measured():
            res = drive(ctx, server, plan, pool_host, sample,
                        float(traffic["drain_timeout_s"]))
    finally:
        server.stop(drain=False, timeout_s=30.0)

    lat, outcome = res["latencies_ms"], res["outcome"]
    ctx.samples["loadgen.late_ms"] = res["late_ms"]
    ctx.samples["request_ms"] = lat
    attempted = len(plan)
    failed = attempted - outcome["OK"]
    images = sum(p[2] for p in plan)
    p50, p99 = loadgen.percentile(lat, 50), loadgen.percentile(lat, 99)
    stats = server.stats
    ctx.counters["serve.cache_misses"] = stats.cache_misses
    ctx.counters["serve.batches"] = stats.n_batches
    ctx.counters["serve.images"] = stats.n_images
    ctx.log(
        f"{attempted} requests ({images} images) due over {window_s:g} s "
        f"at {traffic['rate_rps']:g}/s; outcome {dict(outcome)}; sent in "
        f"{res['sent_s']:.2f} s, all answered after {res['wall_s']:.2f} s"
    )
    ctx.log(
        f"latency from due time: p50 {p50} ms, p99 {p99} ms over {len(lat)} "
        f"answered; generator late p50 "
        f"{loadgen.percentile(res['late_ms'], 50)} ms, p99 "
        f"{loadgen.percentile(res['late_ms'], 99)} ms; server: {stats.summary()}"
    )
    # Drift and the choice of a window: a backlog that grows shows as a rise
    # from quarter to quarter, and the percentiles over the first quarter,
    # half and three quarters say what a shorter window would have read.
    quarters = [[], [], [], []]
    for due, ms in zip(res["answered_due_s"], lat):
        quarters[min(3, int(4 * due / window_s))].append(ms)
    ctx.log("p50/p99 ms by quarter of the window: " + ", ".join(
        f"{loadgen.percentile(q, 50) or 0:.2f}/{loadgen.percentile(q, 99) or 0:.2f}"
        for q in quarters
    ))
    for k in (1, 2, 3, 4):
        part = [ms for q in quarters[:k] for ms in q]
        ctx.log(f"first {k}/4 of the window, {len(part)} answered: " + ", ".join(
            f"p{q} {loadgen.percentile(part, q) or 0:.3f}" for q in (50, 90, 95, 99)
        ) + " ms")
    ok = check(ctx, params, pool_dev, plan, res["kept"], budget)
    if stats.cache_misses:
        ctx.log(f"NOT CORRECT: serve.cache_misses = {stats.cache_misses}")
        ok = False
    return {
        "attempted": attempted,
        "failed": failed,
        "correct": ok,
        "values": {
            "request_p50_ms": p50,
            "request_p99_ms": p99,
            "images_per_s": stats.n_images / res["wall_s"],  # completed, over the whole window
        },
    }
