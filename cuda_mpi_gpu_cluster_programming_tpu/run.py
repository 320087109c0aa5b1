"""CLI entry point: run one execution config and print the stdout contract.

The reference has one hard-coded ``main()`` per version (L3 layer,
SURVEY §1); this runner replaces all of them with a real flag system (a
capability upgrade the reference lacked — SURVEY §5.6) while keeping the
exact machine-parseable stdout contract its harness greps
(scripts/common_test_utils.sh:296-317):

    Final Output Shape: 13x13x256
    Final Output (first 10 values): 29.2932 25.9153 ...
    AlexNet TPU Forward Pass completed in X ms

Usage (run from the repo root so cwd is importable):

    python -m cuda_mpi_gpu_cluster_programming_tpu.run --config v1_jit --batch 1
"""

from __future__ import annotations

import argparse
import dataclasses
import sys
import time

import jax
import numpy as np


def make_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(prog="cuda_mpi_gpu_cluster_programming_tpu.run")
    p.add_argument("--config", default="v1_jit", help="execution config key (see configs.REGISTRY)")
    p.add_argument("--batch", type=int, default=1, help="batch size (reference is strictly batch-1)")
    p.add_argument("--shards", type=int, default=1, help="row-shard count (mpirun -np analogue)")
    p.add_argument("--init", choices=["deterministic", "random"], default="deterministic")
    p.add_argument(
        "--input",
        choices=["jax", "native"],
        default="jax",
        help="input source: jax = on-device init, native = C++ data pipeline",
    )
    p.add_argument("--seed", type=int, default=0)
    p.add_argument(
        "--preset",
        choices=["small", "ep16_share", "solar_ep8", "zaya1_ep2", "longcat_ep32", "phi4_mini_flash"],
        default="small",
        help="language-model configs only (the PRESETS of the config's model module, "
        "models.<ExecConfig.model>): small = the CPU tests' size; the published widths "
        "as one expert-parallel chip holds them, at the benchmark's shape: ep16_share "
        "(v8_mla_moe: one of 16 chips, 2 x 4,096 tokens), solar_ep8 (v9_kda_moe: one of "
        "8 chips, 2 x 8,192 tokens), zaya1_ep2 (v10_cca_moe: one of 2 chips, 1 x 4,096 tokens), "
        "longcat_ep32 (v11_scmoe_mla: one of 32 chips, 2 x 4,096 tokens); phi4_mini_flash "
        "(v12_sambay: the dense model whole on one chip, 1 x 4,096 tokens)",
    )
    p.add_argument("--repeats", type=int, default=10, help="fenced passes for amortized timing")
    p.add_argument(
        "--warmup", type=int, default=5, help="short-queue passes subtracted by the fence protocol"
    )
    p.add_argument(
        "--compute",
        choices=["fp32", "bf16"],
        default="fp32",
        help="fp32 = exact reference-parity numerics; bf16 = MXU fast path "
        "(legacy spelling; --dtype/--policy supersede it when given)",
    )
    p.add_argument(
        "--dtype",
        choices=["", "fp32", "bf16", "int8w"],
        default="",
        help="force a precision policy for this run (docs/PRECISION.md): "
        "fp32 = reference floor, bf16 = MXU fast path, int8w = per-channel "
        "int8 weights with dequant-free bf16-accumulate compute. With "
        "--tune, pins the dtype sweep to this single dtype",
    )
    p.add_argument(
        "--policy",
        choices=["", "tuned", "fp32", "bf16", "int8w"],
        default="",
        help="named precision-policy selection: 'tuned' runs the winning "
        "dtype of the persisted dtype sweep (the plan file's policy "
        "record; falls back to --compute with a visible note when none "
        "matches); a preset name behaves like --dtype. Mutually exclusive "
        "with --dtype",
    )
    p.add_argument(
        "--gate-journal",
        default="",
        help="with --tune: journal every tolerance-gate verdict "
        "(gate_pass/gate_fail records) to this jsonl path; default: "
        "<plan>_gate.jsonl next to the plan file (docs/PRECISION.md)",
    )
    p.add_argument(
        "--lrn-form",
        choices=["cuda", "cpu"],
        default="cuda",
        help="LRN scale: cuda = k+a*sum (golden 29.2932...), cpu = k+a*sum/N (44.4152...)",
    )
    p.add_argument("--height", type=int, default=227)
    p.add_argument("--width", type=int, default=227)
    p.add_argument("--params", help="load weights from this .npz checkpoint instead of --init")
    p.add_argument("--save-params", help="save the weights used to this .npz checkpoint")
    p.add_argument("--list-configs", action="store_true")
    p.add_argument(
        "--breakdown",
        action="store_true",
        help="also print a fenced per-layer timing breakdown (XLA-op tier)",
    )
    p.add_argument(
        "--profile",
        metavar="DIR",
        help="capture a jax.profiler trace of the timed passes into DIR",
    )
    p.add_argument(
        "--max-retries",
        type=int,
        default=0,
        help="retry the build+compile step on transient faults with "
        "exponential backoff (0 = fail immediately, the historical behavior)",
    )
    p.add_argument(
        "--fallback-chain",
        default="",
        help="comma-separated config keys to degrade to when the requested "
        "config cannot build/compile (e.g. 'v4_hybrid,v2.2_sharded,v1_jit'), "
        "or 'auto' for the canonical tier ladder; each step prints a "
        "structured DEGRADED(from -> to) event",
    )
    p.add_argument(
        "--deadline-s",
        type=float,
        default=0.0,
        help="wall-clock budget for build+compile retries (0 = unbounded); "
        "with --tune it also bounds the sweep, which degrades to the "
        "default plan instead of wedging",
    )
    p.add_argument(
        "--tune",
        action="store_true",
        help="autotune the Pallas kernel-variant plan for this geometry/"
        "dtype/batch and run with it; the plan is cached in --plan (a "
        "fresh matching entry skips the sweep entirely — docs/TUNING.md)",
    )
    p.add_argument(
        "--tune-force",
        action="store_true",
        help="with --tune: re-sweep even when the plan cache has a fresh entry",
    )
    p.add_argument(
        "--tune-repeats", type=int, default=5,
        help="timed chain length per tuning candidate (amortized_stats n_large - n_small)",
    )
    p.add_argument(
        "--tune-warmup", type=int, default=2,
        help="warmup chain length per tuning candidate",
    )
    p.add_argument(
        "--plan",
        default="",
        help="TunePlan JSON path: with --tune the cache target (default "
        "perf/tune_plan.json), otherwise load per-layer kernel variants "
        "from it; layers it does not cover, or a stale plan, run the "
        "defaults (docs/TUNING.md)",
    )
    p.add_argument(
        "--supervise",
        action="store_true",
        help="run under the elastic supervisor: the forward compiles with "
        "in-graph per-stage digest taps, every batch is screened off the "
        "timed path, and a trip (stage_digest / shard_divergence / "
        "device_loss) degrades down the shard ladder and replays the batch "
        "(docs/RESILIENCE.md). Blocks 1-2 configs only; prints one "
        "machine-parsed 'Supervisor: ...' line",
    )
    p.add_argument(
        "--supervisor-journal",
        default="",
        help="with --supervise: journal every build/trip/degrade/ok "
        "transition to this jsonl path (resilience.journal format)",
    )
    p.add_argument(
        "--serve",
        action="store_true",
        help="run the continuous-batching inference service under a seeded "
        "Poisson load instead of the one-shot forward: admission queue with "
        "per-request deadlines, bucketed batch assembly (compile-cache-"
        "safe padded shapes), journaled dispatch; with --supervise the "
        "PR 5 elastic ladder degrades in-service instead of failing "
        "requests (docs/SERVING.md). Blocks 1-2 configs only; prints "
        "machine-parsed 'Serve load:' and 'Serve:' lines",
    )
    p.add_argument("--serve-rate", type=float, default=20.0,
                   help="with --serve: Poisson arrival rate (requests/s)")
    p.add_argument("--serve-duration", type=float, default=2.0,
                   help="with --serve: load-generation window (s)")
    p.add_argument("--serve-max-batch", type=int, default=8,
                   help="with --serve: largest dispatch bucket (powers of "
                   "two below it form the default bucket set)")
    p.add_argument("--serve-deadline-s", type=float, default=0.0,
                   help="with --serve: per-request deadline (0 = none); "
                   "expired requests are shed explicitly, never dropped")
    p.add_argument("--serve-journal", default="",
                   help="with --serve: journal every warm/batch/shed/"
                   "degrade record to this jsonl path (the p50/p99 "
                   "source)")
    p.add_argument("--serve-buckets", default="",
                   help="with --serve: comma-separated explicit bucket "
                   "sizes (overrides the powers-of-two/TunePlan-derived "
                   "set)")
    p.add_argument(
        "--serve-controller",
        action="store_true",
        help="with --serve: run the Autopilot closed-loop controller on "
        "the dispatch loop (docs/SERVING.md 'Autopilot') — journaled, "
        "hysteresis-bounded degrade/restore off live error-budget burn "
        "and queue-knee signals (shed bulk -> narrow buckets -> int8w "
        "downshift -> supervisor degrade; reversed in LIFO order on "
        "recovery). Pairs with --traffic-shape: the class mix's SLO "
        "policy is the controller's signal source; without one it is "
        "inert by design. Prints a machine-parsed 'Serve controller:' "
        "line",
    )
    p.add_argument(
        "--serve-frontend",
        type=int,
        default=None,
        metavar="PORT",
        help="with --serve: expose the service over HTTP on 127.0.0.1:PORT "
        "(0 = ephemeral) and drive the load through a threaded HTTP client "
        "fleet over real sockets instead of in-process submits "
        "(docs/SERVING.md 'Network front end & SLOs'). Backpressure is "
        "429, sheds are 504 with their reason, every exchange journals a "
        "serve.transport span. Prints a machine-parsed 'Serve frontend:' "
        "line",
    )
    p.add_argument(
        "--route",
        type=int,
        default=0,
        metavar="N",
        help="with --serve: fleet mode — spawn N backend serving PROCESSES "
        "(serving.fleet) behind a FleetRouter and drive the HTTP client "
        "fleet through the router instead of one in-process server "
        "(docs/SERVING.md 'Fleet router'). Deterministic crc32-of-rid "
        "routing, probe-driven up/probation/quarantine hysteresis, "
        "journaled retry-with-redirect; prints machine-parsed 'Route "
        "fleet:'/'Route load:'/'Route class:'/'Route:' lines. Ignores "
        "--config et al. (backends build their own v1_jit servers)",
    )
    p.add_argument(
        "--route-dir",
        default="logs/route",
        help="with --route: journal directory — one backend_<i>.jsonl per "
        "backend plus router.jsonl, exportable as ONE stitched timeline "
        "via 'observability export --journal DIR'",
    )
    p.add_argument(
        "--traffic-shape",
        default="",
        help="with --serve: traffic-shaped load instead of plain Poisson — "
        "steady | diurnal | burst | flash, composable with '+' (e.g. "
        "'diurnal+burst'), params as key=value ('diurnal:amp=0.8,period=2"
        "+burst:every=1,mult=5'). Requests draw a seeded heavy-tailed "
        "class mix (interactive/batch/bulk) with per-class deadlines and "
        "SLO-aware shed-by-class; prints per-class 'Serve class:' lines",
    )
    p.add_argument(
        "--serve-replay",
        default="",
        metavar="JOURNAL",
        help="re-drive a recorded serve journal through a live server on "
        "this mesh (docs/OBSERVABILITY.md 'Replay'): "
        "same arrivals, request shapes/classes/deadlines, and chaos "
        "schedule, reconstructed from the journal alone (--config et al. "
        "are ignored — the journal's serve_config record is the truth). "
        "Prints machine-parsed 'Replay:' and 'Replay class:' lines; rc 3 "
        "when a neutral replay diverges from the recorded accounting, "
        "rc 2 on an unreplayable (pre-replay-schema) journal",
    )
    p.add_argument(
        "--replay-mult",
        type=float,
        default=1.0,
        help="with --serve-replay: offer the recorded schedule at this "
        "traffic multiple (what-if knob; non-neutral replays never rc 3)",
    )
    p.add_argument(
        "--replay-devices",
        type=int,
        default=None,
        help="with --serve-replay: rebuild the server at this shard "
        "width instead of the recorded one",
    )
    p.add_argument(
        "--replay-slo-scale",
        type=float,
        default=1.0,
        help="with --serve-replay: scale every class SLO budget and "
        "per-request deadline (0.5 = twice as tight)",
    )
    p.add_argument(
        "--replay-journal",
        default="",
        help="with --serve-replay: journal the replay run here (itself "
        "replayable; default: a temp file)",
    )
    p.add_argument(
        "--trace",
        default="",
        help="journal spans (observability.trace) to this jsonl path: "
        "build/tune/measure phases, supervisor trip->degrade->reshard->"
        "replay parents, per-request serve queue-wait vs dispatch; export "
        "with 'python -m cuda_mpi_gpu_cluster_programming_tpu."
        "observability export --journal PATH' (docs/OBSERVABILITY.md). "
        "With --serve and --serve-journal, spans default into the serve "
        "journal so one file carries the whole correlated timeline",
    )
    return p


def _chaos_build_faults(exec_cfg) -> None:
    """Fault-injection hook for the build+compile step (CHAOS_SPEC; no-op
    when chaos is off). Sites map onto the real failure modes each config
    class is exposed to: collectives for the sharded strategies, Mosaic
    lowering for the Pallas tier, device loss for anything needing a mesh."""
    from .resilience import chaos

    ch = chaos.active()
    if ch is None:
        return
    if exec_cfg.strategy != "single":
        ch.maybe_raise("collective", f"{exec_cfg.key} halo/collective transport")
        if ch.draw("device_loss"):
            # Mesh shrink: mimic the exact message the mesh-size guard
            # raises, so triage (MESH_WARN patterns) sees the real signature.
            raise RuntimeError(
                f"chaos: injected device_loss fault: config {exec_cfg.key!r} "
                f"needs 2 devices, have 1"
            )
    if exec_cfg.tier == "pallas":
        ch.maybe_raise("kernel_compile", f"{exec_cfg.key} Mosaic lowering")


def _run_route(args, blocks_cfg) -> int:
    """Fleet mode (--serve --route N): N backend serving processes behind
    a FleetRouter, the HTTP client fleet driven through the router, and
    the journals (one per backend + the router's) stitched from one
    directory. With host_loss chaos armed, the seeded backend is
    SIGKILLed mid-load, restarted after the load window, and must
    re-admit through probation — the CLI face of the acceptance drill
    (docs/SERVING.md 'Fleet router')."""
    import threading
    import time as _time
    from pathlib import Path

    from .resilience.policy import RetryPolicy
    from .serving.batcher import power_of_two_buckets
    from .serving.fleet import BackendFleet, FleetError, maybe_host_loss
    from .serving.frontend import http_fleet_load
    from .serving.router import UP, FleetRouter, RouterConfig
    from .serving.traffic import default_class_mix

    route_dir = Path(args.route_dir)
    route_dir.mkdir(parents=True, exist_ok=True)
    fleet = BackendFleet(
        args.route,
        route_dir,
        height=blocks_cfg.in_height,
        width=blocks_cfg.in_width,
        max_batch=args.serve_max_batch,
    )
    router = None
    killed = [None]
    try:
        fleet.start()
        router = FleetRouter(
            fleet.urls(),
            RouterConfig(
                probe_interval_s=0.1,
                probe_timeout_s=2.0,
                fail_k=2,
                readmit_m=2,
                retry=RetryPolicy(
                    max_retries=3, base_delay_s=0.02, max_delay_s=0.25,
                    jitter=0.1,
                ),
                default_deadline_s=args.serve_deadline_s or None,
                journal_path=str(route_dir / "router.jsonl"),
            ),
        ).start()
        print(
            f"Route fleet: n={args.route} url={router.url} dir={route_dir}"
        )
        mix = list(
            default_class_mix(power_of_two_buckets(args.serve_max_batch))
        )
        # host_loss chaos fires mid-window from a timer — the load keeps
        # offering while the victim dies, which is the point.
        timer = threading.Timer(
            max(0.05, args.serve_duration / 2),
            lambda: killed.__setitem__(0, maybe_host_loss(fleet)),
        )
        timer.start()
        t_kill = _time.monotonic()
        report = http_fleet_load(
            router.url,
            (
                blocks_cfg.in_height,
                blocks_cfg.in_width,
                blocks_cfg.in_channels,
            ),
            shape=args.traffic_shape or "steady",
            rate_rps=args.serve_rate,
            duration_s=args.serve_duration,
            classes=mix,
            seed=args.seed,
        )
        timer.cancel()
        recovery_ms = None
        if killed[0] is not None:
            idx = killed[0]
            print(f"Route host loss: killed=b{idx} (chaos host_loss)")
            router.replace_backend(idx, fleet.restart(idx))
            deadline = _time.monotonic() + 60.0
            while (
                _time.monotonic() < deadline
                and router.backend_states()[f"b{idx}"] != UP
            ):
                _time.sleep(0.05)
            if router.backend_states()[f"b{idx}"] == UP:
                recovery_ms = (_time.monotonic() - t_kill) * 1e3
        print(f"Route load: {report.summary()}")
        rrep = router.report()
        for line in rrep.class_lines():
            print(line)
        if recovery_ms is not None:
            print(f"Route recovery: killed=b{killed[0]} ms={recovery_ms:.0f}")
        print(f"Route: {rrep.summary()}")
    except FleetError as e:
        # The launcher's refusals (more backends than chips, a parent that
        # holds the chips) and spawn failures: the reason, at once.
        print(f"--route: {e}", file=sys.stderr)
        return 2
    finally:
        if router is not None:
            router.stop()
        fleet.stop()
    from .observability.health import health_from_journal

    try:
        print(f"Health: {health_from_journal(route_dir).summary_line()}")
    except Exception as e:  # noqa — the fold is evidence, not the verdict
        print(f"Health: unavailable ({type(e).__name__}: {e})")
    return 0


def _run_language_model(args, exec_cfg) -> int:
    """One-shot run of a token-driven config: seeded parameters stored in the
    compute type, seeded ids over the vocabulary slice, fenced passes."""
    import jax.numpy as jnp

    from .configs import build_forward, language_model

    model = language_model(exec_cfg)
    if args.preset not in model.PRESETS:
        print(f"--preset {args.preset} is not one of {exec_cfg.key}'s ({', '.join(model.PRESETS)})",
              file=sys.stderr)
        return 2
    model_cfg, batch, seq = model.PRESETS[args.preset]
    compute = args.dtype or args.compute
    print(f"--- Language model {exec_cfg.version_name} [{exec_cfg.key}] "
          f"(preset={args.preset}, batch={batch}, seq={seq}) ---")
    print(f"Devices: {jax.device_count()} x {jax.devices()[0].device_kind} "
          f"({jax.default_backend()})")
    print(f"Precision: dtype={compute} source={'dtype' if args.dtype else 'compute'} gate=none")
    # the chip's own bit generator: its draw compiles in seconds at any size
    kp, kx = jax.random.split(jax.random.key(args.seed, impl="rbg"))
    params = model.init(kp, model_cfg, jnp.bfloat16 if compute == "bf16" else jnp.float32)
    ids = jax.random.randint(kx, (batch, seq), 0, model_cfg.vocab_size, jnp.int32)
    fwd = build_forward(exec_cfg, model_cfg, compute=compute)
    out = jax.block_until_ready(fwd(params, ids))  # compile, and the values printed below
    t0 = time.perf_counter()
    for _ in range(max(1, args.repeats)):  # one fenced chain
        last = fwd(params, ids)
    jax.block_until_ready(last)
    per_pass_ms = (time.perf_counter() - t0) * 1e3 / max(1, args.repeats)
    held = (
        f"experts [{model_cfg.experts_first}, {model_cfg.experts_first + model_cfg.experts_held}) "
        f"of {model_cfg.n_routed_experts}" if hasattr(model_cfg, "experts_held") else "a dense model, whole"
    )
    print(f"Parameters: {model.param_count(model_cfg)} held here ({held})")
    print(f"Final Output Shape: {'x'.join(str(d) for d in out.shape[1:])}")
    print("Final Output (first 10 values): "
          + " ".join(f"{v:.4f}" for v in np.asarray(out[0, -1, :10])))
    print(f"Forward pass completed in {per_pass_ms:.3f} ms (one fenced chain of {max(1, args.repeats)} "
          f"passes; {batch * seq / (per_pass_ms / 1e3):.1f} tokens/s)")
    return 0


def main(argv=None) -> int:
    args = make_parser().parse_args(argv)

    # Join a multi-host cluster when launched by parallel.distributed's
    # launch_plan/launch_local (no-op otherwise) — must happen before any
    # backend use.
    from .parallel.distributed import maybe_initialize_from_env

    maybe_initialize_from_env()

    # Persistent compile cache (the prebuilt-binaries analogue,
    # build_local_binaries.sh:8-10) — before the first jit.
    from .utils.compile_cache import enable_persistent_cache

    enable_persistent_cache()

    from .configs import LANGUAGE_MODELS, REGISTRY, build_forward
    from .models.alexnet import BLOCKS12
    from .models.init import (
        deterministic_input,
        init_params_deterministic,
        init_params_random,
        random_input,
    )
    from .observability.trace import Tracer, set_tracer, span as obs_span
    from .utils.timing import amortized_stats

    if args.trace:
        # Journal-backed span tracing (docs/OBSERVABILITY.md): every
        # wired subsystem below (tuner, supervisor, serving) records into
        # this trail; the "Trace:" line is the machine-parseable pointer.
        from .resilience.journal import Journal as _Journal

        tracer = Tracer(journal=_Journal(args.trace))
        set_tracer(tracer)
        print(f"Trace: id={tracer.trace_id} journal={args.trace}")

    if args.list_configs:
        for c in REGISTRY.values():
            print(f"{c.key:18s} {c.version_name:22s} {c.description}")
        return 0

    if args.serve_replay:
        # Journal-replay mode: the recorded serve_config record carries
        # the run's conditions, so every CLI build knob below is moot —
        # rebuild from the journal, re-drive, judge.
        from .observability.replay import (
            ReplayKnobs,
            load_recorded_run,
            replay_recorded,
        )

        if args.replay_mult <= 0 or args.replay_slo_scale <= 0:
            print(
                "--replay-mult/--replay-slo-scale must be > 0",
                file=sys.stderr,
            )
            return 2
        try:
            recorded = load_recorded_run(args.serve_replay)
        except ValueError as e:
            print(f"--serve-replay: {e}", file=sys.stderr)
            return 2
        report = replay_recorded(
            recorded,
            ReplayKnobs(
                traffic_mult=args.replay_mult,
                devices=args.replay_devices,
                slo_scale=args.replay_slo_scale,
                journal_path=args.replay_journal,
            ),
        )
        print(f"Replay source: {args.serve_replay}")
        print(f"Replay journal: {report.journal_path}")
        print(f"Replay: {report.summary()}")
        for line in report.class_lines():
            print(line)
        if report.diverged:
            print(
                "replay divergence: neutral replay broke the recorded "
                "accounting/percentile contract (docs/OBSERVABILITY.md)",
                file=sys.stderr,
            )
            return 3
        return 0

    if args.config not in REGISTRY:
        print(f"unknown config {args.config!r}; try --list-configs", file=sys.stderr)
        return 2
    exec_cfg = REGISTRY[args.config]
    if exec_cfg.model in LANGUAGE_MODELS:
        if args.serve:
            print("--serve supports the Blocks 1-2 configs only", file=sys.stderr)
            return 2
        return _run_language_model(args, exec_cfg)

    blocks_cfg = dataclasses.replace(
        BLOCKS12,
        in_height=args.height,
        in_width=args.width,
        lrn2=dataclasses.replace(BLOCKS12.lrn2, alpha_over_size=(args.lrn_form == "cpu")),
    )
    if exec_cfg.model == "alexnet_full":
        from .models.alexnet_full import AlexNetConfig

        model_cfg = AlexNetConfig(blocks12=blocks_cfg)
    else:
        model_cfg = blocks_cfg

    if args.serve and args.route:
        # Fleet mode: N backend PROCESSES behind the router, one chip
        # each. A chip belongs to one process at a time, so this branch
        # comes before anything below initialises the backend — the
        # parent stays off JAX and each backend owns its server (the
        # router owns the accounting).
        if exec_cfg.model != "blocks12":
            print("--serve supports the Blocks 1-2 configs only", file=sys.stderr)
            return 2
        return _run_route(args, blocks_cfg)

    print(f"--- AlexNet TPU {exec_cfg.version_name} [{exec_cfg.key}] "
          f"(shards={args.shards}, batch={args.batch}) ---")
    print(f"Devices: {jax.device_count()} x {jax.devices()[0].device_kind} "
          f"({jax.default_backend()})")

    # Precision-policy resolution (docs/PRECISION.md): an explicit --dtype
    # (or preset --policy) pins the run; --policy tuned reads the persisted
    # dtype-sweep winner; plain --tune adopts the sweep winner; otherwise
    # the legacy --compute flag stands. The "Precision:" line below is
    # machine-parsed (harness._RE_PRECISION) into the CSV's Dtype column.
    if args.dtype and args.policy:
        print("--dtype and --policy are mutually exclusive", file=sys.stderr)
        return 2
    pinned = args.dtype or (args.policy if args.policy not in ("", "tuned") else "")
    run_dtype = pinned or args.compute
    dtype_source = "dtype" if args.dtype else ("policy" if pinned else "compute")
    gate_info = None

    # Kernel-variant tuning plan: --tune sweeps (or loads the cached sweep),
    # --plan alone loads; either way the resolved plan rides into
    # build_forward and its hash is printed for the harness CSV. The
    # "Tune plan:" line is part of the machine-parsed stdout contract
    # (harness._RE_PLAN).
    plan = None
    if args.tune or args.plan or args.policy == "tuned":
        from pathlib import Path

        from .resilience.policy import Deadline as _Deadline
        from .tuning.autotune import DTYPES, autotune, autotune_precision
        from .tuning.plan import load_plan, load_policy

        plan_path = args.plan or str(
            Path(__file__).resolve().parent.parent / "perf" / "tune_plan.json"
        )
        device_kind = jax.devices()[0].device_kind
        if args.policy == "tuned" and not args.tune:
            rec = load_policy(
                plan_path, device_kind=device_kind, model_cfg=model_cfg,
                batch=args.batch,
            )
            if rec is None:
                print(
                    f"Policy: no tuned dtype record in {plan_path} "
                    f"(falling back to --compute {args.compute}; "
                    "run --tune to sweep)"
                )
            else:
                run_dtype = rec["dtype"]
                dtype_source = "tuned"
                gate_info = rec.get("gates", {}).get(run_dtype)
        if args.tune:
            if exec_cfg.model == "blocks12":
                # ONE sweep covers {fp32, bf16, int8w} x kernel variants per
                # conv layer; gate-failed dtypes are pruned attributably and
                # the winner's policy record is persisted (docs/PRECISION.md).
                res = None
                try:
                    with obs_span("run.tune", config=args.config, batch=args.batch):
                        res = autotune_precision(
                            plan_path,
                            model_cfg,
                            batch=args.batch,
                            dtypes=(run_dtype,) if pinned else DTYPES,
                            force=args.tune_force,
                            deadline=_Deadline.after(args.deadline_s or None),
                            repeats=args.tune_repeats,
                            warmup=args.tune_warmup,
                            device_kind=device_kind,
                            gate_journal=args.gate_journal,
                            seed=args.seed,
                        )
                except RuntimeError as e:
                    # Every requested dtype gate-pruned (possible only for a
                    # pinned sweep, or a broken fp32 oracle): say so and run
                    # the forced dtype untuned — the gate blocks PERSISTED
                    # winners, not explicitly forced runs.
                    print(f"Gate pruned: {e}")
                if res is not None:
                    for dt, why in sorted(res.pruned.items()):
                        print(f"Gate pruned: {dt} ({why})")
                    if not pinned:
                        run_dtype = res.winner
                        dtype_source = "tuned"
                    gate_info = res.gates.get(run_dtype)
                    plan = res.plans.get(run_dtype)
                if plan is not None:
                    print(
                        f"Tune plan: {'cache' if res.cached else 'swept'} "
                        f"hash={plan.plan_hash()} key={plan.key} path={plan_path}"
                        + (f" DEGRADED({plan.degraded})" if plan.degraded else "")
                    )
                else:
                    print(
                        f"Tune plan: none for dtype {run_dtype} "
                        "(gate-pruned; untuned defaults)"
                    )
            else:
                plan, cached = autotune(
                    plan_path,
                    model_cfg,
                    dtype=run_dtype,
                    batch=args.batch,
                    force=args.tune_force,
                    deadline=_Deadline.after(args.deadline_s or None),
                    repeats=args.tune_repeats,
                    warmup=args.tune_warmup,
                    device_kind=device_kind,
                )
                print(
                    f"Tune plan: {'cache' if cached else 'swept'} "
                    f"hash={plan.plan_hash()} key={plan.key} path={plan_path}"
                    + (f" DEGRADED({plan.degraded})" if plan.degraded else "")
                )
        else:  # --plan and/or --policy tuned: load, never sweep
            plan = load_plan(
                plan_path, device_kind=device_kind, model_cfg=model_cfg,
                dtype=run_dtype, batch=args.batch,
            )
            if plan is None:
                print(
                    f"Tune plan: none matching in {plan_path} "
                    "(untuned defaults; run --tune to sweep)"
                )
            else:
                print(f"Tune plan: loaded hash={plan.plan_hash()} key={plan.key}")
            if gate_info is None:
                rec = load_policy(
                    plan_path, device_kind=device_kind, model_cfg=model_cfg,
                    batch=args.batch,
                )
                if rec is not None:
                    gate_info = rec.get("gates", {}).get(run_dtype)

    if run_dtype == "fp32":
        gate_str = "ref"  # fp32 IS the oracle: nothing to gate against
    elif isinstance(gate_info, dict):
        margin = gate_info.get("margin")
        gate_str = ("pass" if gate_info.get("passed") else "fail") + (
            f" margin={margin:.4f}" if isinstance(margin, (int, float)) else ""
        )
    else:
        gate_str = "none"
    print(f"Precision: dtype={run_dtype} source={dtype_source} gate={gate_str}")

    if exec_cfg.model == "alexnet_full":
        from .models.alexnet_full import init_full_deterministic, init_full_random

        init_det, init_rnd = init_full_deterministic, init_full_random
    else:
        init_det, init_rnd = init_params_deterministic, init_params_random
    input_cfg = blocks_cfg  # inputs depend only on the Blocks 1-2 input dims
    # kp/kx derivation is shared by every branch, so --params w.npz --seed S
    # reproduces the exact inputs of the run that saved w.npz.
    kp, kx = jax.random.split(jax.random.PRNGKey(args.seed))
    if args.params:
        from .utils.checkpoint import load_params_npz

        params = load_params_npz(args.params)
        print(f"Loaded params from {args.params}")
    elif args.init == "deterministic":
        params = init_det(model_cfg)
    else:
        params = init_rnd(kp, model_cfg)

    if args.serve:
        # Continuous-batching service mode: the serving subsystem owns the
        # build (per-bucket warmup through the compile cache; with
        # --supervise the elastic ladder), so every later build/measure
        # path below is bypassed.
        if exec_cfg.model != "blocks12":
            print("--serve supports the Blocks 1-2 configs only", file=sys.stderr)
            return 2
        if args.fallback_chain:
            print(
                "--serve degrades through the supervisor ladder "
                "(--supervise); drop --fallback-chain",
                file=sys.stderr,
            )
            return 2
        from .serving.loadgen import run_load, run_shaped_load
        from .serving.server import InferenceServer, ServeConfig
        from .serving.traffic import default_class_mix, parse_shape, slo_policy

        buckets = tuple(
            int(b) for b in args.serve_buckets.split(",") if b.strip()
        )
        # Shaped traffic carries a class mix whose SLO policy rides into
        # admission (shed-by-class); plain Poisson keeps PR 6 behavior.
        mix = None
        slo = None
        if args.traffic_shape:
            try:
                parse_shape(args.traffic_shape)  # fail loudly before building
            except ValueError as e:
                print(f"--traffic-shape: {e}", file=sys.stderr)
                return 2
        scfg = ServeConfig(
            config=args.config,
            n_shards=args.shards,
            # The resolved precision policy rides into serving whole: the
            # bucket set derives from the plan at THIS dtype and every
            # warmup compile runs it (docs/SERVING.md).
            compute=run_dtype,
            policy=dtype_source,
            max_batch=args.serve_max_batch,
            buckets=buckets or None,
            plan_path=args.plan,
            supervise=args.supervise,
            journal_path=args.serve_journal,
            default_deadline_s=args.serve_deadline_s or None,
            model_cfg=blocks_cfg,
        )
        if args.traffic_shape:
            mix = list(default_class_mix(
                InferenceServer(scfg, params=params, plan=plan).buckets
            ))
            slo = slo_policy(mix)
            scfg = dataclasses.replace(scfg, slo=slo)
        if args.serve_controller:
            from .serving.controller import ControllerConfig

            scfg = dataclasses.replace(scfg, controller=ControllerConfig())
            if scfg.slo is None:
                print(
                    "Serve controller: inert (no SLO policy — pair with "
                    "--traffic-shape for the class-mix signal source)"
                )
        server = InferenceServer(scfg, params=params, plan=plan)
        # With --trace the tracer is already installed; otherwise the
        # serve journal doubles as the span trail, so ONE file exports
        # into the full correlated timeline (queue-wait vs dispatch spans
        # beside their serve_batch records — docs/OBSERVABILITY.md).
        serve_tracer = None
        if not args.trace and server.journal is not None:
            serve_tracer = Tracer(journal=server.journal)
            set_tracer(serve_tracer)
            print(f"Trace: id={serve_tracer.trace_id} journal={scfg.journal_path}")
        frontend = None
        try:
            server.start()
            try:
                if args.serve_frontend is not None:
                    # The network path: requests travel a real socket into
                    # the admission queue; the load is a threaded HTTP
                    # client fleet (docs/SERVING.md).
                    from .serving.frontend import ServingFrontend, http_fleet_load

                    frontend = ServingFrontend(
                        server, port=args.serve_frontend
                    ).start()
                    print(f"Serve frontend: url={frontend.url}")
                    with obs_span(
                        "serve.load",
                        rate_rps=args.serve_rate,
                        duration_s=args.serve_duration,
                        transport="http",
                    ):
                        report = http_fleet_load(
                            frontend.url,
                            (
                                blocks_cfg.in_height,
                                blocks_cfg.in_width,
                                blocks_cfg.in_channels,
                            ),
                            shape=args.traffic_shape or "steady",
                            rate_rps=args.serve_rate,
                            duration_s=args.serve_duration,
                            classes=mix or list(default_class_mix(server.buckets)),
                            seed=args.seed,
                        )
                elif args.traffic_shape:
                    with obs_span(
                        "serve.load",
                        rate_rps=args.serve_rate,
                        duration_s=args.serve_duration,
                        shape=args.traffic_shape,
                    ):
                        report = run_shaped_load(
                            server,
                            shape=args.traffic_shape,
                            rate_rps=args.serve_rate,
                            duration_s=args.serve_duration,
                            classes=mix,
                            seed=args.seed,
                        )
                else:
                    with obs_span(
                        "serve.load",
                        rate_rps=args.serve_rate,
                        duration_s=args.serve_duration,
                    ):
                        report = run_load(
                            server,
                            rate_rps=args.serve_rate,
                            duration_s=args.serve_duration,
                            seed=args.seed,
                        )
            finally:
                if frontend is not None:
                    frontend.stop()
                server.stop()
        finally:
            if serve_tracer is not None:
                set_tracer(None)  # in-process callers must not leak a tracer
        print(f"Serve buckets: {','.join(str(b) for b in server.buckets)}")
        print(f"Serve load: {report.summary()}")
        if hasattr(report, "class_lines"):
            for line in report.class_lines():
                print(line)
        print(f"Serve: {server.summary()}")
        if frontend is not None:
            codes = " ".join(
                f"http_{c}={n}"
                for c, n in sorted(frontend.http_codes.items())
            )
            print(f"Serve transport: {codes}")
        if server.controller is not None:
            # Machine-parsed Autopilot line: mode/level/action counts
            # (docs/SERVING.md "Autopilot").
            print(f"Serve controller: {server.controller.summary()}")
        if server.sup is not None:
            # Same machine-parsed supervisor line as the one-shot
            # --supervise path (harness._RE_SUPERVISOR).
            print(f"Supervisor: {server.sup.summary()}")
        if scfg.journal_path:
            # One-line fleet-health fold of the run's own journal
            # (observability.health; the full report via
            # `observability health --journal <path>`).
            from .observability.health import health_from_journal

            try:
                print(
                    f"Health: "
                    f"{health_from_journal(scfg.journal_path).summary_line()}"
                )
            except Exception as e:  # noqa — the fold is evidence, not
                # the serve result; degrade visibly, never fatally.
                print(f"Health: unavailable ({type(e).__name__}: {e})")
        if server.stats.n_failed or report.n_failed:
            # A dispatch exception completes its batch FAILED and the
            # service goes on (serving/server.py) — right for a service,
            # but the command must not then report success: a kernel the
            # compiler refused or an exhausted supervisor ladder ends here.
            print(
                f"serve: {max(server.stats.n_failed, report.n_failed)} "
                "request(s) failed",
                file=sys.stderr,
            )
            return 1
        return 0

    if args.input == "native":
        # C++ pipeline generates the batch host-side (the reference's C++
        # initializeData analogue); deterministic mode is bit-identical to the
        # jax path, random mode uses the native LCG stream instead of
        # jax.random (documented, seeded, reproducible).
        try:
            from . import native

            mode = "ones" if args.init == "deterministic" else "uniform"
            x = jax.device_put(
                native.fill_batch(
                    (args.batch, input_cfg.in_height, input_cfg.in_width, input_cfg.in_channels),
                    mode=mode,
                    seed=args.seed,
                )
            )
        except RuntimeError as e:  # toolchain missing / native build broke
            print(f"cannot build native input tier: {e}", file=sys.stderr)
            return 2
    elif args.init == "deterministic":
        x = deterministic_input(args.batch, input_cfg)
    else:
        x = random_input(kx, args.batch, input_cfg)
    if args.save_params:
        from .utils.checkpoint import save_params_npz

        save_params_npz(args.save_params, params)
        print(f"Saved params to {args.save_params}")

    from .resilience import chaos

    chain = [args.config]
    if args.fallback_chain:
        from .resilience.policy import tier_fallback_chain

        if args.fallback_chain.strip() == "auto":
            chain = tier_fallback_chain(args.config)
        else:
            chain += [k.strip() for k in args.fallback_chain.split(",") if k.strip()]
        chain = list(dict.fromkeys(chain))
        unknown = [k for k in chain if k not in REGISTRY]
        if unknown:
            print(f"unknown configs in --fallback-chain: {unknown}", file=sys.stderr)
            return 2
        mixed = [k for k in chain if REGISTRY[k].model != exec_cfg.model]
        if mixed:
            # Degrading across model families would run the wrong network
            # against this process's params/input — a silent lie, not a
            # graceful fallback.
            print(
                f"--fallback-chain crosses model families: {mixed} "
                f"(primary is {exec_cfg.model})",
                file=sys.stderr,
            )
            return 2

    def _build_and_compile(key: str):
        cfg = REGISTRY[key]
        _chaos_build_faults(cfg)
        f = build_forward(
            cfg, model_cfg, n_shards=args.shards, policy=run_dtype, plan=plan
        )
        t0 = time.perf_counter()
        jax.block_until_ready(f(params, x))
        return f, (time.perf_counter() - t0) * 1e3

    resilient = (
        len(chain) > 1
        or args.max_retries > 0
        or args.deadline_s > 0
        or chaos.active() is not None
    )
    sup = None
    if args.supervise:
        # Elastic supervisor: digest-tapped forward + screening + ladder
        # re-planning. It owns building (and its own chaos draws), so the
        # retry/degrader build path below is bypassed.
        if exec_cfg.model != "blocks12":
            print("--supervise supports the Blocks 1-2 configs only", file=sys.stderr)
            return 2
        if args.fallback_chain:
            print(
                "--supervise has its own degradation ladder; drop --fallback-chain",
                file=sys.stderr,
            )
            return 2
        from .resilience.journal import Journal
        from .resilience.policy import DegradationExhausted
        from .resilience.supervisor import Supervisor, default_ladder

        try:
            ladder = default_ladder(exec_cfg.strategy, exec_cfg.tier, args.shards)
        except ValueError as e:
            print(f"cannot supervise config {exec_cfg.key!r}: {e}", file=sys.stderr)
            return 2
        sup = Supervisor(
            model_cfg,
            ladder,
            plan=plan,
            journal=(
                Journal(args.supervisor_journal) if args.supervisor_journal else None
            ),
            # DEGRADED events print to stdout where the harness greps them,
            # exactly like the build-time Degrader's.
            on_event=lambda ev: print(ev, flush=True),
        )
        try:
            sup.execute(params, x)
        except DegradationExhausted as e:
            print(f"supervisor: every ladder rung failed: {e.last}", file=sys.stderr)
            return 2
        fwd = sup.fwd()  # (params, x) -> (out, digests): taps ride the timed path
        compile_ms = sup.compile_ms or 0.0
    elif not resilient:
        # Historical fast path, byte-identical stdout/stderr.
        try:
            fwd = build_forward(
                exec_cfg, model_cfg, n_shards=args.shards, policy=run_dtype,
                plan=plan,
            )
        except (ValueError, NotImplementedError, ModuleNotFoundError) as e:
            print(f"cannot build config {exec_cfg.key!r}: {e}", file=sys.stderr)
            return 2
        t0 = time.perf_counter()
        jax.block_until_ready(fwd(params, x))
        compile_ms = (time.perf_counter() - t0) * 1e3
    else:
        from .resilience.policy import (
            Deadline,
            DegradationExhausted,
            Degrader,
            RetryPolicy,
            retry_call,
        )

        policy = RetryPolicy(max_retries=max(0, args.max_retries), base_delay_s=1.0)
        deadline = Deadline.after(args.deadline_s or None)
        # DEGRADED events go to stdout: the harness greps them out of the
        # captured log and triages the row as DEGRADED rather than FAIL.
        degrader = Degrader(chain, on_event=lambda ev: print(ev, flush=True))
        try:
            ran_key, (fwd, compile_ms) = degrader.run(
                lambda key: retry_call(
                    lambda: _build_and_compile(key), policy=policy, deadline=deadline
                )
            )
        except DegradationExhausted as e:
            print(f"cannot build config {chain[-1]!r}: {e.last}", file=sys.stderr)
            return 2
        except (ValueError, NotImplementedError, ModuleNotFoundError) as e:
            print(f"cannot build config {exec_cfg.key!r}: {e}", file=sys.stderr)
            return 2
        if ran_key != args.config:
            # Downstream consumers (--breakdown tier attribution) must see
            # the tier that actually ran, not the one that was asked for.
            exec_cfg = REGISTRY[ran_key]
    n_small = max(1, args.warmup)
    if args.profile:
        from .utils.profiling import trace as profile_ctx
    else:
        import contextlib

        profile_ctx = lambda _dir: contextlib.nullcontext()  # noqa: E731
    with profile_ctx(args.profile):
        # Work-floor stats, not a single sample: the conv-variant A/B and
        # every harness row route through this line, so it must resolve
        # deltas smaller than a single sample's run-to-run noise.
        with obs_span(
            "run.measure", config=exec_cfg.key, batch=args.batch,
            dtype=run_dtype,
        ) as _msp:
            st = amortized_stats(
                fwd, params, x, n_small=n_small, n_large=n_small + max(1, args.repeats)
            )
            if _msp is not None:
                _msp.set(per_pass_ms=round(st.per_call_ms, 4))
        per_pass_ms = st.per_call_ms
    if args.profile:
        print(f"Profiler trace written to {args.profile}")
    if sup is not None:
        # Screened verification pass (digest screening off the timed path),
        # then the machine-parsed supervisor line for the harness CSV.
        out = np.asarray(sup.execute(params, x))
        print(f"Supervisor: {sup.summary()}")
    else:
        out = np.asarray(fwd(params, x))

    shape_str = "x".join(str(d) for d in out.shape[1:])
    flat = out[0].reshape(-1)
    first10 = " ".join(f"{v:.4f}" for v in flat[:10])
    print(f"Compile time: {compile_ms:.1f} ms")
    print(f"Final Output Shape: {shape_str}")
    print(f"Final Output (first 10 values): {first10}")
    print(
        f"AlexNet TPU Forward Pass completed in {per_pass_ms:.3f} ms "
        f"(amortized over {args.repeats} fenced passes; "
        f"{args.batch / (per_pass_ms / 1e3):.1f} img/s)"
    )
    # Separate line: the 'completed in' format above is the harness-regexed
    # stdout contract (common_test_utils.sh analogue) and must not change.
    print(
        f"Timing stats: n={st.n_samples} ci95={st.ci95_ms:.4f} ms "
        f"chain={st.n_chain}"
        + (" SHADOWED" if st.shadowed else "")
        + (" UNDERCONVERGED" if st.underconverged else "")
    )
    if args.breakdown and run_dtype == "int8w":
        print(
            "--breakdown does not support the int8w policy "
            "(the quantized lowering has no per-layer XLA-tier analogue); "
            "skipped"
        )
    elif args.breakdown:
        from .utils.profiling import layer_breakdown

        # Per-layer costs (the per-phase breakdown the reference lists as
        # future work, reference README.md:233) — timed on the SELECTED
        # config's op tier, so a v3_pallas breakdown attributes cost to
        # the hand-written kernels, not the XLA ops.
        for name, ms, shape in layer_breakdown(
            params,
            x,
            model_cfg,
            repeats=max(1, args.repeats),
            warmup=n_small,
            compute=run_dtype,
            tier=exec_cfg.tier,
        ):
            shape_s = "x".join(str(d) for d in shape[1:])
            print(f"Layer {name} completed in {ms:.3f} ms -> {shape_s}")
        if exec_cfg.strategy in ("halo", "staged_halo"):
            # Static comm/compute plan for the sharded strategies — the
            # per-phase breakdown the reference listed as future work
            # (reference README.md:233); exact because the halo geometry
            # is Python ints at trace time (parallel/plan.py). The same
            # numbers are asserted against the compiled jaxpr's collective
            # count in tests/test_breakdown.py.
            from .parallel.breakdown import comm_compute_breakdown, format_table

            staged = exec_cfg.strategy == "staged_halo"
            dtype_bytes = 2 if run_dtype in ("bf16", "int8w") else 4
            rows = comm_compute_breakdown(
                blocks_cfg, args.shards, batch=args.batch,
                dtype_bytes=dtype_bytes, staged=staged,
            )
            print(format_table(rows, staged=staged))
        elif exec_cfg.strategy == "tp":
            # Same static-plan guarantee for the filter-decomposition dual:
            # channel-halo ppermutes + the conv2 boundary all_gather
            # (parallel/tensor_parallel.py), asserted against the compiled
            # jaxpr per primitive in tests/test_breakdown.py.
            from .parallel.breakdown import format_table, tp_comm_compute_breakdown

            dtype_bytes = 2 if run_dtype in ("bf16", "int8w") else 4
            rows = tp_comm_compute_breakdown(
                blocks_cfg, args.shards, batch=args.batch, dtype_bytes=dtype_bytes,
            )
            print(format_table(rows, transport="all_gather + channel-halo ppermute"))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
