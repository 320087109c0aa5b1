"""The continuous-batching inference server: queue -> bucket -> dispatch
-> (degrade) lifecycle around ``configs.build_forward``.

Dispatch discipline (docs/SERVING.md):

- **Warmup compiles everything, dispatch compiles nothing.** At
  :meth:`InferenceServer.start` every bucket shape is compiled once (the
  PR 2 persistent compile cache makes that cheap across restarts); a
  dispatched batch whose bucket shape is not warmed on the current rung is
  counted as a ``cache_miss`` — the acceptance number that must be zero
  after warmup.
- **Every batch is journaled** (``serve_batch`` records with per-request
  latencies; ``serve_shed``/``serve_fail`` for the explicit loss paths) via
  PR 3's fsync'd ``Journal``, so the load reports' p50/p99 come from the same
  crash-consistent trail every other artifact uses.
- **Degradation, not 500s.** With ``supervise=True`` the forward is the
  PR 5 elastic :class:`~..resilience.supervisor.Supervisor`: an SDC trip or
  device loss mid-batch re-plans down the ladder, re-warms every bucket on
  the new rung (``on_rebuild``), and REPLAYS the in-flight batch — callers
  get answers, late, instead of errors.
- **Deadline-aware shedding.** Expired requests complete with status
  ``SHED`` at assembly time and are journaled — never silently dropped.
- **Every run is replayable.** The journal carries the full arrival
  schedule, not just the outcomes: one ``serve_config`` record at build
  time (config / shards / buckets / SLO policy / model geometry) and one
  ``serve_submit`` record per admission attempt (arrival offset, request
  shape, class, resolved deadline, admitted-or-rejected). Together with
  the ``sup_*``/``mesh_*`` incident records they are exactly what
  ``observability.replay`` needs to re-drive the run — same arrivals,
  same chaos schedule — on a live server (docs/OBSERVABILITY.md
  "Replay").

The dispatch loop keeps host syncs out of its body (staticcheck's
``host-sync-in-hot-loop`` rule now covers this file): the timed region
lives in ``_dispatch``, and result slicing/journal writes run in
``@off_timed_path`` completion helpers, the same contract the supervisor's
screening uses.
"""

from __future__ import annotations

import dataclasses
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from ..observability.metrics import registry as metrics_registry
from ..observability.trace import current_ids, get_tracer, span
from ..resilience.journal import Journal
from ..resilience.sentinel import off_timed_path
from .batcher import AssembledBatch, Batcher, power_of_two_buckets
from .queue import FAILED, OK, AdmissionQueue, QueueFull, Request, RequestHandle


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    """How to build and run the service (CLI/bench surface in one place)."""

    config: str = "v1_jit"  # configs.REGISTRY key (blocks12 family)
    n_shards: int = 1
    # The precision policy the service runs (and warms, and derives its
    # tuned bucket set at): a policy name — fp32 | bf16 | int8w
    # (docs/PRECISION.md). ``policy`` records HOW it was chosen
    # (compute|dtype|policy|tuned — the run CLI's Precision source token)
    # so journals stay attributable.
    compute: str = "fp32"
    policy: str = ""
    max_batch: int = 8
    # None = powers of two up to max_batch, or the TunePlan-derived set
    # when plan_path names a plan covering this point (tuning.plan_batches).
    buckets: Optional[Tuple[int, ...]] = None
    plan_path: str = ""
    supervise: bool = False
    journal_path: str = ""
    max_pending: int = 1024
    poll_s: float = 0.02
    default_deadline_s: Optional[float] = None
    model_cfg: Any = None  # Blocks12Config override (tests use 63x63)
    # Optional serving.slo.SLOPolicy: per-class SLO targets with pop-time
    # shed-by-class (docs/SERVING.md "Network front end & SLOs"). None =
    # the PR 6 behavior (hard deadlines only).
    slo: Any = None
    # Live resource telemetry cadence (docs/OBSERVABILITY.md "Roofline
    # attribution"): every ``mem_snapshot_s`` seconds the dispatch loop
    # journals one ``serve_gauges`` (queue depth / pending images /
    # oldest wait) and one ``mem_snapshot`` (device memory_stats, RSS
    # fallback) record — strictly off the timed path, exported as
    # Perfetto counter tracks. 0 disables.
    mem_snapshot_s: float = 1.0
    # Optional serving.controller.ControllerConfig: the autopilot — a
    # journaled closed-loop controller evaluated from the observation
    # cadence that trades admission, bucket width, precision, and
    # capacity for the protected class's SLO under pressure
    # (docs/SERVING.md "Autopilot"). None = every knob stays fixed at
    # build time (the pre-PR 18 behavior).
    controller: Any = None


@dataclasses.dataclass
class ServeStats:
    """Steady-state counters the CLI line surfaces."""

    n_batches: int = 0
    n_images: int = 0
    n_ok: int = 0
    n_shed: int = 0
    n_failed: int = 0
    warmup_compiles: int = 0
    cache_misses: int = 0  # post-warmup dispatches at an un-warmed shape
    rewarm_ms: float = 0.0  # wall ms spent re-compiling buckets on degrades
    promotions: int = 0  # supervised grow-back climbs committed mid-serve
    batch_ms: List[float] = dataclasses.field(default_factory=list)

    def summary(self) -> str:
        return (
            f"batches={self.n_batches} images={self.n_images} ok={self.n_ok} "
            f"shed={self.n_shed} failed={self.n_failed} "
            f"cache_misses={self.cache_misses} warmups={self.warmup_compiles}"
        )


class InferenceServer:
    """Continuous-batching service over one execution config.

    Two run modes: :meth:`start`/:meth:`stop` spin the dispatch loop on a
    background thread (the load-generator path), while
    :meth:`run_until_drained` runs it inline until the queue empties — the
    deterministic path the chaos drills and tests use (batch assembly then
    depends only on submission order, never on thread timing).
    """

    def __init__(self, cfg: ServeConfig, params=None, plan=None, ladder=None):
        # ``ladder``: explicit supervisor LadderEntry list (supervise mode
        # only) — the chaos drills pin a clean comparison server to the
        # exact rung a faulted run degraded to.
        self.cfg = cfg
        self._ladder = ladder
        self.queue = AdmissionQueue(max_pending=cfg.max_pending, slo=cfg.slo)
        self.stats = ServeStats()
        self.journal = Journal(cfg.journal_path) if cfg.journal_path else None
        self._plan = plan
        self._params = params
        self._fwd = None
        self.sup = None  # the Supervisor in supervise mode (drill surface)
        self._warmed: set = set()  # bucket sizes compiled on the current rung
        self._thread: Optional[threading.Thread] = None
        self._stop = threading.Event()
        self._started = False
        # The journal epoch every serve_submit arrival offset is relative
        # to; replay only needs the offsets' relative spacing, so the
        # construction instant is as good an epoch as any.
        self._epoch = time.monotonic()
        self._seq_submit = 0
        self._seq_snapshot = 0
        self._last_snapshot = 0.0  # monotonic: first _step snapshots
        self._submit_lock = threading.Lock()  # submit() is thread-safe
        self._compute_override: Optional[str] = None  # live dtype downshift
        self.buckets = self._resolve_buckets()
        self._batcher = Batcher(self.queue, self.buckets)
        self.controller = None
        if cfg.controller is not None:
            from .controller import AutopilotController, ControllerConfig

            ctl_cfg = (
                cfg.controller
                if isinstance(cfg.controller, ControllerConfig)
                else ControllerConfig.from_obj(cfg.controller)
            )
            self.controller = AutopilotController(self, ctl_cfg)

    # ------------------------------------------------------------- building

    def _resolve_buckets(self) -> Tuple[int, ...]:
        cfg = self.cfg
        if cfg.buckets:
            return tuple(sorted(set(int(b) for b in cfg.buckets)))
        if cfg.plan_path:
            import jax

            from ..models.alexnet import BLOCKS12
            from ..tuning.plan import plan_batches

            tuned = plan_batches(
                cfg.plan_path,
                device_kind=jax.devices()[0].device_kind,
                model_cfg=cfg.model_cfg or BLOCKS12,
                dtype=cfg.compute,
            )
            tuned = [b for b in tuned if b <= cfg.max_batch]
            if tuned:
                return tuple(tuned)
        return power_of_two_buckets(cfg.max_batch)

    def _model_cfg(self):
        from ..models.alexnet import BLOCKS12

        return self.cfg.model_cfg if self.cfg.model_cfg is not None else BLOCKS12

    @property
    def current_compute(self) -> str:
        """The precision the service is running RIGHT NOW — the build
        compute unless the autopilot has a live dtype override installed
        (``apply_compute``)."""
        return self._compute_override or self.cfg.compute

    def _build(self) -> None:
        from ..configs import REGISTRY, build_forward
        from ..models.init import init_params_deterministic

        cfg = self.cfg
        exec_cfg = REGISTRY[cfg.config]
        if exec_cfg.model != "blocks12":
            raise ValueError(
                f"serving supports the Blocks 1-2 configs only, got {cfg.config!r}"
            )
        model_cfg = self._model_cfg()
        if self._params is None:
            self._params = init_params_deterministic(model_cfg)
        if cfg.supervise:
            from ..resilience.supervisor import Supervisor, default_ladder

            self.sup = Supervisor(
                model_cfg,
                self._ladder
                or default_ladder(exec_cfg.strategy, exec_cfg.tier, cfg.n_shards),
                plan=self._plan,
                journal=self.journal,
                on_rebuild=self._rewarm,
                site="serve",
            )
        else:
            self._fwd = build_forward(
                exec_cfg,
                model_cfg,
                n_shards=cfg.n_shards,
                compute=cfg.compute,
                plan=self._plan,
            )

    def _warm_input(self, bucket: int) -> np.ndarray:
        m = self._model_cfg()
        return np.zeros(
            (bucket, m.in_height, m.in_width, m.in_channels), np.float32
        )

    @off_timed_path
    def _note_compile(self, xb: np.ndarray, ms: float, *, hit: bool) -> None:
        """Journal one ``compile_event`` for an UNSUPERVISED warmup compile
        (the supervised path journals through the supervisor's ledger) —
        observability.health folds these into compile-cost attribution."""
        if self.journal is None:
            return
        from ..configs import REGISTRY
        from ..observability.health import compile_event, journal_compile_event

        strategy = REGISTRY[self.cfg.config].strategy
        journal_compile_event(
            self.journal,
            compile_event(
                site="serve",
                entry=self.cfg.config,
                shape=xb.shape,
                dtype=self.current_compute,
                ms=ms,
                cache_hit=hit,
                n_shards=(self.cfg.n_shards if strategy != "single" else 1),
                fn=None if hit else self._fwd,
                args=(self._params, xb),
            ),
        )

    @off_timed_path
    def warmup(self) -> None:
        """Compile every bucket shape now, before any request is waiting.
        After this, a dispatch that compiles is a counted cache miss.
        Off the timed path by contract: warmup fences are setup cost, not
        serving latency."""
        with span("serve.warmup", buckets=list(self.buckets)):
            for bucket in self.buckets:
                self._warm_bucket(bucket)

    @off_timed_path
    def _warm_bucket(self, bucket: int) -> float:
        """Compile ONE bucket shape on the current rung/precision and
        journal it — warmup's unit, shared with the autopilot's actuation
        paths (bucket widening and dtype shifts re-warm through here, so
        post-actuation dispatch stays a compile-cache hit)."""
        import jax

        xb = self._warm_input(bucket)
        if self.sup is not None:
            # compile_event journaling rides the supervisor's
            # per-(rung, shape) ledger inside warm().
            ms = self.sup.warm(self._params, xb)
        else:
            t0 = time.perf_counter()
            jax.block_until_ready(self._fwd(self._params, xb))
            ms = (time.perf_counter() - t0) * 1e3
            self._note_compile(xb, ms, hit=bucket in self._warmed)
        self.stats.warmup_compiles += 1
        self._warmed.add(bucket)
        self._journal(
            "serve_warm", key=f"warm:b{bucket}", bucket=bucket,
            ms=round(ms, 3), dtype=self.current_compute,
        )
        return ms

    def _rewarm(self, entry) -> None:
        """Supervisor on_rebuild hook: a degrade landed on a fresh rung, so
        every bucket must compile again BEFORE the failed batch replays —
        re-warming here keeps the replay itself a cache hit and the
        steady-state miss count at zero across degradations. The params are
        live-resharded onto the rung's surviving-device mesh FIRST, so the
        warm compiles land on exactly the placement the replay (which the
        supervisor reshards the same way) will dispatch with — after a
        mesh shrink nothing here touches a lost device."""
        with span("serve.rewarm", entry=entry.key):
            self._warmed.clear()
            self._params = self.sup.reshard(self._params)
            ms = 0.0
            for bucket in self.buckets:
                ms += self.sup.warm(self._params, self._warm_input(bucket))
                self.stats.warmup_compiles += 1
                self._warmed.add(bucket)
            self.stats.rewarm_ms += ms
            metrics_registry().counter("serve.rewarms").inc()
            self._journal(
                "serve_rewarm", key=f"rewarm:{entry.key}", entry=entry.key,
                buckets=list(self.buckets), ms=round(ms, 3),
                devices=self.sup.pool.n_alive,
            )

    # ------------------------------------------------------------ lifecycle

    def start(self) -> "InferenceServer":
        """Build, warm every bucket, then serve on a background thread."""
        if self._started:
            raise RuntimeError("server already started")
        self._ensure_built()
        self._started = True
        self._stop.clear()
        self._thread = threading.Thread(
            target=self._loop, name="serve-dispatch", daemon=True
        )
        self._thread.start()
        return self

    def _ensure_built(self) -> None:
        if self._fwd is None and self.sup is None:
            self._build()
            self._journal_config()
            self.warmup()

    @off_timed_path
    def _journal_config(self) -> None:
        """One ``serve_config`` record per built server: the exact
        conditions this run serves under, so a replay
        (observability.replay) can rebuild an equivalent server from the
        journal ALONE — config, topology, bucket set, SLO policy, model
        geometry. Written before warmup so even a run killed mid-warm
        leaves a replayable header."""
        m = self._model_cfg()
        cfg = self.cfg
        self._journal(
            "serve_config",
            key="config",
            config=cfg.config,
            n_shards=cfg.n_shards,
            compute=cfg.compute,
            max_batch=cfg.max_batch,
            buckets=list(self.buckets),
            max_pending=cfg.max_pending,
            poll_s=cfg.poll_s,
            default_deadline_s=cfg.default_deadline_s,
            supervise=cfg.supervise,
            height=m.in_height,
            width=m.in_width,
            channels=m.in_channels,
            slo=cfg.slo.to_obj() if cfg.slo is not None else None,
            devices=self.sup.pool.n_alive if self.sup is not None else 1,
            # The autopilot's knobs (None = uncontrolled): a replay
            # rebuilds the exact controller from this, and the
            # --controller on|off A/B overrides it (observability.replay).
            controller=(
                self.controller.cfg.to_obj()
                if self.controller is not None
                else None
            ),
        )

    def stop(self, drain: bool = True, timeout_s: float = 60.0) -> None:
        """Stop the dispatch thread; with ``drain`` (default) the loop
        first finishes everything already admitted."""
        if self._thread is None:
            return
        if drain:
            deadline = time.monotonic() + timeout_s
            while len(self.queue) and time.monotonic() < deadline:
                time.sleep(0.005)
        self._stop.set()
        self._thread.join(timeout_s)
        self._thread = None

    def _loop(self) -> None:
        while not self._stop.is_set():
            self._step()

    def run_until_drained(self) -> None:
        """Inline dispatch until the queue is empty — the deterministic
        mode: with all requests pre-submitted, batch assembly depends only
        on FIFO order and the bucket set (chaos drills compare two such
        runs bit-for-bit)."""
        self._ensure_built()
        while len(self.queue):
            self._step()

    # ------------------------------------------------------------- dispatch

    def _step(self) -> None:
        # Grow-back check FIRST, strictly between batches (off the dispatch
        # timed region): a healed+graduated pool promotes the supervisor —
        # and re-warms every bucket at the higher rung — before the next
        # batch is even assembled, so in-flight requests are never dropped
        # and no post-promotion dispatch can miss the compile cache.
        self._maybe_promote()
        self._observe_queue()
        self._observe_resources()
        self._observe_controller()
        batch, shed = self._batcher.next_batch(self.cfg.poll_s)
        if shed:
            self._record_shed(shed)
        if batch is not None:
            self._dispatch(batch)

    @off_timed_path
    def _observe_queue(self) -> None:
        """Mirror the queue's saturation gauges into the metrics registry
        between batches — ``serve.queue_oldest_wait_ms`` climbs toward the
        tightest class SLO while every request is still servable, so
        saturation is observable BEFORE the first shed (docs/SERVING.md).
        O(1) per step; strictly off the dispatch timed region."""
        qs = self.queue.stats()
        reg = metrics_registry()
        reg.gauge("serve.queue_depth").set(qs.depth)
        reg.gauge("serve.queue_pending_images").set(qs.pending_images)
        reg.gauge("serve.queue_oldest_wait_ms").set(qs.oldest_wait_ms)

    @off_timed_path
    def _observe_resources(self) -> None:
        """Live resource telemetry, throttled to ``cfg.mem_snapshot_s``
        (docs/OBSERVABILITY.md "Roofline attribution"): one
        ``serve_gauges`` journal record (the queue saturation trio) and
        one ``mem_snapshot`` record (device ``memory_stats()`` summed
        over local devices, process-RSS fallback with ``source`` named)
        per interval, plus the ``mem.*`` registry gauges. Strictly off
        the dispatch timed region; journal-less servers keep the gauges
        and skip the records."""
        if self.cfg.mem_snapshot_s <= 0:
            return
        now = time.monotonic()
        if now - self._last_snapshot < self.cfg.mem_snapshot_s:
            return
        self._last_snapshot = now
        from ..observability.specs import device_memory_stats

        snap = device_memory_stats()
        reg = metrics_registry()
        for field in ("bytes_in_use", "peak_bytes_in_use"):
            if isinstance(snap.get(field), (int, float)):
                reg.gauge(f"mem.{field}").set(snap[field])
        if self.journal is None:
            return
        qs = self.queue.stats()
        self._seq_snapshot += 1
        t_ms = round((now - self._epoch) * 1e3, 3)
        self._journal(
            "serve_gauges",
            key=f"gauges:{self._seq_snapshot}",
            t_ms=t_ms,
            depth=qs.depth,
            pending_images=qs.pending_images,
            oldest_wait_ms=qs.oldest_wait_ms,
            # Controller ladder depth rides the gauge record (ISSUE 20)
            # so the degrade trajectory is a counter series beside the
            # queue trio; absent without an Autopilot — pre-20 journals
            # export unchanged.
            **(
                {"ctl_level": self.controller.level}
                if self.controller is not None
                else {}
            ),
        )
        self._journal(
            "mem_snapshot", key=f"mem:{self._seq_snapshot}", t_ms=t_ms, **snap
        )

    @off_timed_path
    def _maybe_promote(self) -> None:
        """Between-batches grow-back (docs/RESILIENCE.md "Grow-back &
        hysteresis"): retry pending heals against a fresh device re-query
        and, when the eligible pool satisfies a higher rung, run the
        supervised promotion. The supervisor's ``on_rebuild`` hook fires
        ``_rewarm`` inside the promotion, so every bucket is compiled at
        the higher rung before this returns — the cutover costs zero
        cache misses on the post-promotion dispatch path."""
        if self.sup is None:
            return
        state = self.sup.maybe_promote(self._params)
        if state is not None:
            self._params = state
            self.stats.promotions += 1
            metrics_registry().counter("serve.promotions").inc()

    @off_timed_path
    def _observe_controller(self) -> None:
        """Autopilot evaluation (docs/SERVING.md "Autopilot"), on the
        same between-batches observation cadence as the queue/resource
        gauges — the controller folds signals and (rarely) actuates, all
        strictly off the dispatch timed region."""
        if self.controller is not None:
            self.controller.evaluate(time.monotonic())

    # --------------------------------------------------- controller hooks
    #
    # The autopilot's actuation surface: each method swaps ONE live knob
    # in place, reversibly, between batches. The controller journals the
    # decision (``controller_action`` with evidence); these journal only
    # what the equivalent build-time path already journals (warm/rewarm
    # records), so the trail stays one vocabulary.

    @off_timed_path
    def apply_slo_policy(self, policy) -> None:
        """Swap the queue's pop-time admission policy. The queue reads
        ``self.slo`` per pop under its own lock, so an atomic attribute
        swap is the whole cutover — in-flight requests see the new cuts
        on their next pop, admitted work is never dropped retroactively."""
        self.queue.slo = policy

    @off_timed_path
    def apply_buckets(self, buckets) -> float:
        """Swap the active bucket set (narrow under pressure, widen on
        recovery). Any bucket not compiled on the current rung is warmed
        FIRST (a widen after a mid-narrow rewarm would otherwise compile
        on the request path), then the batcher is rebuilt over the new
        set — its dispatch seq carries over so journal keys stay unique.
        Returns the wall ms spent warming (0 = pure resize)."""
        buckets = tuple(sorted(set(int(b) for b in buckets)))
        if not buckets:
            raise ValueError("bucket set cannot be empty")
        ms = 0.0
        for bucket in buckets:
            if bucket not in self._warmed:
                ms += self._warm_bucket(bucket)
        seq = self._batcher._seq
        self.buckets = buckets
        self._batcher = Batcher(self.queue, buckets)
        self._batcher._seq = seq
        return ms

    @off_timed_path
    def apply_compute(self, compute: str) -> float:
        """Rebuild the UNSUPERVISED forward at a new precision policy and
        re-warm every bucket before the next dispatch — the autopilot's
        dtype downshift/upshift, ToleranceGate-screened by the caller
        (no silent adoption; the supervisor's rungs carry no dtype axis,
        so supervised servers degrade capacity instead). Journals one
        ``serve_rewarm`` (the same record a ladder rebuild writes) and
        returns its wall ms."""
        if self.sup is not None:
            raise RuntimeError(
                "dtype actuation is unsupervised-only — supervised "
                "servers degrade through the ladder"
            )
        from ..configs import REGISTRY, build_forward

        self._fwd = build_forward(
            REGISTRY[self.cfg.config],
            self._model_cfg(),
            n_shards=self.cfg.n_shards,
            compute=compute,
            plan=self._plan,
        )
        self._compute_override = (
            compute if compute != self.cfg.compute else None
        )
        self._warmed.clear()
        ms = 0.0
        for bucket in self.buckets:
            ms += self._warm_bucket(bucket)
        self.stats.rewarm_ms += ms
        metrics_registry().counter("serve.rewarms").inc()
        self._journal(
            "serve_rewarm", key=f"rewarm:dtype:{compute}",
            entry=self.cfg.config, buckets=list(self.buckets),
            ms=round(ms, 3), dtype=compute, devices=1,
        )
        return ms

    @off_timed_path
    def request_degrade(self, cause: str) -> bool:
        """Ask the supervisor DOWN one rung as a capacity decision (the
        autopilot's load-pressure rung) — same degrade walk, re-warm, and
        journal trail as a fault trip, but with a ``requested:`` cause.
        False when unsupervised or already at the floor."""
        if self.sup is None:
            return False
        return self.sup.request_degrade(cause)

    @off_timed_path
    def request_promote(self) -> bool:
        """The explicit grow-back half: climb one rung, sentinel-verified
        like any promotion (a refusal journals ``sup_promote_refused``
        and leaves the rung as-is). False when nothing was adopted."""
        if self.sup is None:
            return False
        state = self.sup.request_promote(self._params)
        if state is None:
            return False
        self._params = state
        self.stats.promotions += 1
        metrics_registry().counter("serve.promotions").inc()
        return True

    def _dispatch(self, batch: AssembledBatch) -> None:
        """One timed region: pad -> run -> fence. Completion (slicing,
        handle wakeups, journal append) happens off the timed path."""
        import jax

        if batch.bucket not in self._warmed:
            # Post-warmup compile on the request path — the exact failure
            # the bucket discipline exists to prevent. Counted AND
            # journaled, then warmed so it can only fire once per shape.
            self.stats.cache_misses += 1
            metrics_registry().counter("serve.cache_misses").inc()
            self._journal(
                "serve_miss", key=f"miss:b{batch.bucket}", bucket=batch.bucket
            )
        xb = batch.padded_input()
        t0 = time.perf_counter()
        try:
            if self.sup is not None:
                out = self.sup.execute(self._params, xb)
            else:
                out = self._fwd(self._params, xb)
                jax.block_until_ready(out)
        except Exception as e:  # noqa — terminal failure: ladder exhausted
            # (supervise) or the bare forward raised. Every in-flight
            # request completes FAILED with the cause — no hung handles.
            self._record_failed(batch, e)
            return
        batch_ms = (time.perf_counter() - t0) * 1e3
        self._warmed.add(batch.bucket)
        self._complete(batch, out, batch_ms)

    @off_timed_path
    def _complete(self, batch: AssembledBatch, out, batch_ms: float) -> None:
        """Slice the padded output back per request and wake the handles —
        one host transfer per batch, contractually between timed regions.
        Tracing happens HERE, after the timed region: the dispatch span is
        emitted from its measured bounds and each request gets a
        queue-wait span (submit -> dispatch start), so the trace carries
        the queue-wait vs dispatch attribution without a single host sync
        on the dispatch path."""
        arr = np.asarray(out)
        lat_ms: Dict[str, float] = {}
        req_cls: Dict[str, str] = {}
        reg = metrics_registry()
        for req, off in batch.offsets():
            req.handle._complete(OK, arr[off : off + req.n_images])
            lat_ms[req.rid] = round(req.handle.latency_ms, 3)
            req_cls[req.rid] = req.cls
            # Per-request latency histogram: the SAME nearest-rank
            # estimator and population as the journal-derived serve
            # percentiles, so bench (registry) and journal p99s agree.
            reg.histogram("serve.request_ms").observe(req.handle.latency_ms)
        if self.controller is not None:
            # Feed the autopilot's sliding burn windows from the same
            # per-request outcomes the journal records — the live half
            # of the PR 15 attainment fold.
            for req in batch.requests:
                self.controller.note_ok(req.cls, lat_ms[req.rid])
        self.stats.n_batches += 1
        self.stats.n_images += batch.n_images
        self.stats.n_ok += len(batch.requests)
        self.stats.batch_ms.append(batch_ms)
        reg.counter("serve.ok").inc(len(batch.requests))
        reg.counter("serve.images").inc(batch.n_images)
        reg.histogram("serve.batch_ms").observe(batch_ms)
        trace_fields: Dict[str, str] = {}
        tr = get_tracer()
        if tr is not None:
            # Monotonic bounds reconstructed from the measured region so
            # the span write costs the timed path nothing.
            t1 = tr.clock()
            t0 = t1 - batch_ms / 1e3
            dsid = tr.emit(
                "serve.dispatch", t0, t1, track="dispatch",
                bucket=batch.bucket, seq=batch.seq,
                n_requests=len(batch.requests),
                entry=(
                    self.sup.entry.key if self.sup is not None
                    else self.cfg.config
                ),
            )
            trace_fields = {"trace_id": tr.trace_id, "span_id": dsid}
            for req in batch.requests:
                wait_ms = (t0 - req.handle.submitted_at) * 1e3
                reg.histogram("serve.queue_wait_ms").observe(max(0.0, wait_ms))
                tr.emit(
                    "serve.queue_wait", req.handle.submitted_at, t0,
                    parent_id="", track="queue", rid=req.rid,
                )
        else:
            for req in batch.requests:
                reg.histogram("serve.queue_wait_ms").observe(
                    max(0.0, req.handle.latency_ms - batch_ms)
                )
        self._journal(
            "serve_batch",
            key=f"batch:{batch.seq}",
            bucket=batch.bucket,
            n_requests=len(batch.requests),
            n_images=batch.n_images,
            pad=batch.pad,
            batch_ms=round(batch_ms, 3),
            req_lat_ms=lat_ms,
            req_cls=req_cls,
            entry=self.sup.entry.key if self.sup is not None else self.cfg.config,
            **trace_fields,
        )

    @off_timed_path
    def _record_shed(self, shed: List[Request]) -> None:
        self.stats.n_shed += len(shed)
        reg = metrics_registry()
        reg.counter("serve.shed").inc(len(shed))
        if self.controller is not None:
            for req in shed:
                self.controller.note_shed(req.cls)
        for req in shed:
            reason = req.shed_reason or "deadline"
            if reason == "slo":
                # SLO sheds counted separately: "capacity protected the
                # SLO" vs "a caller's own deadline lapsed" are different
                # operational stories (docs/SERVING.md).
                reg.counter("serve.shed_slo").inc()
            self._journal(
                "serve_shed", key=f"shed:{req.rid}", rid=req.rid,
                n_images=req.n_images, cls=req.cls, reason=reason,
                waited_ms=round(req.handle.latency_ms or 0.0, 3),
            )

    @off_timed_path
    def _record_failed(self, batch: AssembledBatch, e: BaseException) -> None:
        cause = f"{type(e).__name__}: {e}"[:200]
        for req in batch.requests:
            req.handle._complete(FAILED, error=cause)
        if self.controller is not None:
            for req in batch.requests:
                self.controller.note_fail(req.cls)
        self.stats.n_failed += len(batch.requests)
        metrics_registry().counter("serve.failed").inc(len(batch.requests))
        self._journal(
            "serve_fail",
            key=f"fail:{batch.seq}",
            bucket=batch.bucket,
            n_requests=len(batch.requests),
            # Per-request class attribution, same shape as serve_batch's
            # req_cls: a failed bulk batch and a failed interactive batch
            # are different stories, and replay accounting closes per class.
            req_cls={req.rid: req.cls for req in batch.requests},
            cause=cause,
        )

    # ------------------------------------------------------------- frontend

    def submit(
        self,
        x,
        *,
        deadline_s: Optional[float] = None,
        rid: Optional[str] = None,
        cls: str = "",
    ) -> RequestHandle:
        """Admit one request (thread-safe). Requests wider than the largest
        bucket are rejected at the door — they could never dispatch.
        Deadline resolution: explicit ``deadline_s`` > the class's default
        (SLO policy) > the server default."""
        x = np.asarray(x)
        n = 1 if x.ndim == 3 else int(x.shape[0])
        if n > self.buckets[-1]:
            self._journal_submit(rid or "", n, cls, None, "too_wide")
            raise ValueError(
                f"request of {n} images exceeds the largest bucket "
                f"{self.buckets[-1]} — split it client-side"
            )
        if deadline_s is None and self.cfg.slo is not None:
            deadline_s = self.cfg.slo.deadline_for(cls)
        if deadline_s is None:
            deadline_s = self.cfg.default_deadline_s
        try:
            handle = self.queue.submit(x, deadline_s=deadline_s, rid=rid, cls=cls)
        except QueueFull:
            self._journal_submit(rid or "", n, cls, deadline_s, "queue_full")
            raise
        self._journal_submit(
            handle.rid, n, cls, deadline_s, "", t=handle.submitted_at
        )
        return handle

    def _journal_submit(
        self,
        rid: str,
        n: int,
        cls: str,
        deadline_s: Optional[float],
        reason: str,
        t: Optional[float] = None,
    ) -> None:
        """One ``serve_submit`` record per admission attempt — the arrival
        schedule half of the replay contract (``serve_config`` is the
        conditions half). ``t_ms`` is the arrival offset from the server
        epoch; rejected attempts (``admitted=False`` with their reason)
        are recorded too, because a replayed load must OFFER them again
        for per-class accounting to close identically. Runs on the
        submitting thread, never the dispatch loop."""
        if self.journal is None:
            return
        with self._submit_lock:  # HTTP handler threads submit concurrently
            self._seq_submit += 1
            self._journal(
                "serve_submit",
                key=f"sub:{self._seq_submit}",
                rid=rid,
                t_ms=round(
                    ((t if t is not None else time.monotonic()) - self._epoch)
                    * 1e3,
                    3,
                ),
                n=n,
                cls=cls,
                deadline_s=deadline_s,
                admitted=not reason,
                reason=reason,
            )

    def _journal(self, kind: str, key: str, **payload) -> None:
        if self.journal is not None:
            # Correlation fields ride along when a tracer is active (and a
            # call site's explicit span_id wins over the ambient one);
            # schemas keep their shape for pre-observability tooling.
            self.journal.append(kind, key=key, **{**current_ids(), **payload})

    def summary(self) -> str:
        """One machine-parseable line ('Serve: ...' — run CLI contract)."""
        s = self.stats.summary()
        buckets = ",".join(str(b) for b in self.buckets)
        tail = (
            f" entry={self.sup.entry.key} trips={len(self.sup.trips)}"
            f" promotions={self.sup.promotions}"
            if self.sup
            else ""
        )
        return f"{s} buckets={buckets}{tail}"


def request_latencies_from_journal(path) -> List[float]:
    """All per-request latencies (ms) journaled by ``serve_batch`` records —
    the crash-consistent source p50/p99 are computed from (a
    killed run's percentiles cover exactly the requests that completed)."""
    return latencies_from_records(Journal.load(path))


def latencies_from_records(records: List[dict]) -> List[float]:
    """Per-request latencies out of an already-loaded record list (the
    saturation sweep slices ONE journal into per-rate windows)."""
    lats: List[float] = []
    for rec in records:
        if rec.get("kind") == "serve_batch":
            req_lat = rec.get("req_lat_ms")
            if isinstance(req_lat, dict):
                lats.extend(
                    float(v) for v in req_lat.values()
                    if isinstance(v, (int, float))
                )
    return lats


def class_latencies_from_records(records: List[dict]) -> Dict[str, List[float]]:
    """{class name: [latency ms, ...]} from ``serve_batch`` records — the
    per-class p99 source (``req_cls`` maps each rid to its class; rids
    journaled before the class field existed land under ``""``)."""
    out: Dict[str, List[float]] = {}
    for rec in records:
        if rec.get("kind") != "serve_batch":
            continue
        req_lat = rec.get("req_lat_ms")
        req_cls = rec.get("req_cls") or {}
        if not isinstance(req_lat, dict):
            continue
        for rid, v in req_lat.items():
            if isinstance(v, (int, float)):
                out.setdefault(str(req_cls.get(rid, "")), []).append(float(v))
    return out


def class_latencies_from_journal(path) -> Dict[str, List[float]]:
    """Journal-file form of :func:`class_latencies_from_records`."""
    return class_latencies_from_records(Journal.load(path))
