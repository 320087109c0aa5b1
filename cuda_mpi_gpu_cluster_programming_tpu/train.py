"""Training-loop CLI: synthetic teacher-student training on any mesh.

The reference is inference-only (SURVEY §5.4: "nothing to save"); this loop
is the framework's training tier wired end-to-end: the native C++ data
pipeline feeds batches, the distributed train step (dp and/or sp axes) fits
a randomly-initialized student to a fixed deterministic teacher's outputs,
loss is printed per step in a machine-parseable line, and weights checkpoint
to npz so runs can resume.

    python -m cuda_mpi_gpu_cluster_programming_tpu.train --steps 20 --batch 8
    python -m cuda_mpi_gpu_cluster_programming_tpu.train --sp 8 --fake-devices 8

Resilience (docs/RESILIENCE.md): the SDC sentinel screens every step's
loss/grad-norm/params for NaN/Inf and norm spikes (``--no-sentinel`` opts
out). ``--checkpoint-every N`` additionally makes the run preemption- and
corruption-tolerant: the training state (params + optimizer state + step)
is checkpointed atomically every N steps into ``--work-dir`` alongside a
crash-consistent journal, a sentinel trip rolls back to the last-good
checkpoint and re-enters (bounded by ``--max-rollbacks``), and relaunching
the same command resumes at the last checkpointed step. Batches in this
mode are derived per step index (identical stream to the prefetching
loader), so a resumed or rolled-back run replays exactly the batches the
uninterrupted run would have seen:

    python -m cuda_mpi_gpu_cluster_programming_tpu.train --steps 200 \\
        --checkpoint-every 20 --work-dir logs/train_work

``--supervise-steps`` additionally puts every step under the elastic
supervisor (docs/RESILIENCE.md "True elastic meshes"): a mid-step device/
mesh loss or sentinel trip degrades down the shard ladder, rebuilds the
step over the SURVIVING devices, live-reshards params+opt-state, and
replays the same batch — rollback only once the ladder is spent.
"""

from __future__ import annotations

import argparse
import sys
import time


def make_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(prog="cuda_mpi_gpu_cluster_programming_tpu.train")
    p.add_argument("--steps", type=int, default=10)
    p.add_argument("--batch", type=int, default=4)
    p.add_argument("--lr", type=float, default=1e-3)
    p.add_argument("--optimizer", choices=["sgd", "adam"], default="sgd")
    p.add_argument("--dp", type=int, default=1, help="data-parallel mesh axis size")
    p.add_argument("--sp", type=int, default=0, help="spatial/context-parallel shards (0 = off)")
    p.add_argument("--tp", type=int, default=0, help="tensor-parallel (K-axis) shards (0 = off)")
    p.add_argument("--remat", action="store_true", help="rematerialize activations in backward")
    p.add_argument("--height", type=int, default=63, help="input H (default small for fast demo)")
    p.add_argument("--width", type=int, default=63)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--loader-workers", type=int, default=2)
    p.add_argument("--checkpoint", help="save trained params to this .npz")
    p.add_argument("--resume", help="initialize student from this .npz")
    p.add_argument(
        "--fake-devices",
        type=int,
        default=0,
        help="run on N virtual CPU devices (mpirun --oversubscribe analogue)",
    )
    p.add_argument(
        "--checkpoint-every",
        type=int,
        default=0,
        help="atomically checkpoint the full training state every N steps "
        "into --work-dir and journal progress; enables idempotent resume "
        "(relaunch the same command) and sentinel rollback (0 = off, the "
        "historical run-once behavior)",
    )
    p.add_argument(
        "--work-dir",
        default="logs/train_work",
        help="state directory for --checkpoint-every: last-good checkpoint "
        "+ crash-consistent journal.jsonl",
    )
    p.add_argument(
        "--checkpoint-shards",
        type=int,
        default=0,
        help="with --checkpoint-every: save the training state as a "
        "crash-consistent SHARDED tree (N atomic shard files + a "
        "manifest-last commit, utils.checkpoint.save_train_state_sharded) "
        "instead of one npz; a kill mid-save always leaves the last-good "
        "generation loadable (0 = single-file npz, the historical format)",
    )
    p.add_argument(
        "--supervise-steps",
        action="store_true",
        help="supervisor-managed training steps (requires --checkpoint-every; "
        "docs/RESILIENCE.md 'True elastic meshes'): a sentinel trip or a "
        "device/mesh loss DURING a step degrades down the elastic ladder "
        "(halo@n -> halo@n/2 -> ... -> single@1), rebuilds the step over "
        "the surviving-device mesh, live-reshards params+opt-state onto it "
        "(jax.device_put, no checkpoint round-trip) and replays the SAME "
        "batch — step-level replay instead of whole-checkpoint rollback; "
        "rollback remains the floor once the ladder or --max-rollbacks is "
        "exhausted. Prints one machine-parseable 'Elastic: ...' line",
    )
    p.add_argument(
        "--max-rollbacks",
        type=int,
        default=2,
        help="consecutive sentinel-trip rollbacks tolerated before aborting "
        "(the counter resets at every successful checkpoint)",
    )
    p.add_argument(
        "--no-sentinel",
        action="store_true",
        help="disable the SDC sentinel (NaN/Inf + norm-spike screening of "
        "loss/grads/params each step)",
    )
    p.add_argument(
        "--sentinel-window",
        type=int,
        default=8,
        help="rolling history length per watched scalar for spike detection",
    )
    p.add_argument(
        "--sentinel-spike",
        type=float,
        default=1e3,
        help="trip when a watched scalar exceeds this factor times its "
        "window median",
    )
    p.add_argument(
        "--oracle-every",
        type=int,
        default=0,
        help="run the golden-oracle conv spot check (tests/oracle.py) every "
        "N-th param check; a mismatch trips the sentinel (0 = off)",
    )
    return p


def _run_resilient_loop(
    args, jr, save_state, load_state, start_step, get_batch, teacher_fwd, teacher,
    step_fn, student, opt_state, sentinel, mesh, flog, sup=None,
):
    """The quarantine-capable training loop (``--checkpoint-every`` > 0).

    Every committed step is journaled; every N-th commit atomically
    checkpoints (params, opt_state, step) as the last-good state via
    ``save_state`` (single-npz or sharded-tree, per --checkpoint-shards). A
    sentinel :class:`~..resilience.sentinel.SDC` trip rolls the loop back
    to that state (``load_state``) and re-enters (the chaos
    ``sdc``/``nan_loss`` drills exercise exactly this path on CPU);
    ``--max-rollbacks`` consecutive trips without a successful checkpoint
    abort with rc 3. Returns either an exit code (int) or
    ``(first_loss, last_loss, steps_run, final_params, final_opt_state)``
    — callers must take the trained state from the return value (a
    rebound local never propagates back through an argument).

    With ``sup`` (``--supervise-steps``, an elastic
    :class:`~.resilience.supervisor.Supervisor` in step mode) the CHEAP
    recovery comes first: any trip — in-step device/mesh loss caught by
    ``supervise_step``, or a host-side sentinel trip routed through
    ``trip_external`` — degrades the ladder, live-reshards the state onto
    the surviving-device mesh, and REPLAYS the same step-indexed batch.
    Checkpoint rollback runs only once the ladder is exhausted.
    """
    import jax

    from .observability.trace import current_ids, span as obs_span
    from .resilience import chaos
    from .resilience.policy import DegradationExhausted
    from .resilience.sentinel import SDC

    first = last = None
    last_good_step = start_step
    rollbacks = 0
    steps_run = 0
    i = start_step

    def _rollback(cause: str):
        """The floor: consume one rollback, restore last-good, rewind.
        Returns rc 3 when the budget is spent, else None."""
        nonlocal rollbacks, student, opt_state, i
        rollbacks += 1
        flog.record("retry", cause=cause[:160])
        jr.append(
            "rollback", key=f"rollback:{i + 1}", step=i + 1, cause=cause[:200],
            **current_ids(),
        )
        print(
            f"{cause} -> rollback to last-good step {last_good_step} "
            f"(rollback {rollbacks}/{args.max_rollbacks})",
            flush=True,
        )
        if rollbacks > args.max_rollbacks:
            flog.record("fail", cause="rollback budget exhausted")
            print(
                f"sentinel: {args.max_rollbacks} consecutive rollbacks "
                "exhausted without progress; aborting",
                file=sys.stderr,
            )
            return 3
        student, opt_state, _ = load_state(student, opt_state)
        i = last_good_step
        return None

    while i < args.steps:
        x = get_batch(i)
        if sup is None:
            x = jax.device_put(x)
        # Supervised mode leaves the batch UNCOMMITTED: an explicit
        # device_put would pin it to the default device, which the elastic
        # floor must not assume survives (ROADMAP item 3 leftover (d)) —
        # placement follows the supervisor-resharded params instead.
        y = teacher_fwd(teacher, x)
        try:
            # One span per training step (no-op untraced): the supervisor's
            # trip->degrade->reshard->replay spans nest under it, so an
            # incident reads as one tree in the exported timeline.
            with obs_span("train.step", step=i + 1):
                if sup is not None:
                    out = sup.supervise_step(student, opt_state, x, y, step=i)
                else:
                    out = step_fn(student, opt_state, x, y)
        except DegradationExhausted as e:
            # Ladder spent mid-step: the checkpoint rollback is the floor.
            rc = _rollback(f"elastic ladder exhausted: {str(e)[:120]}")
            if rc is not None:
                return rc
            continue
        new_student, new_opt, loss = out[0], out[1], float(out[2])
        gnorm = float(out[3]) if len(out) > 3 else None
        ch = chaos.active()
        if ch is not None:
            if ch.draw("nan_loss"):
                print(f"chaos: injected nan_loss at step {i + 1}", flush=True)
                loss = float("nan")
            if ch.draw("sdc"):
                from .resilience.sentinel import inject_bit_flip

                new_student, loc = inject_bit_flip(new_student, seed=ch.spec.seed)
                print(
                    f"chaos: injected sdc bit-flip at step {i + 1} "
                    f"(leaf/elem {loc})",
                    flush=True,
                )
        try:
            if sentinel is not None:
                sentinel.check_scalar(i, loss, "loss")
                if gnorm is not None:
                    sentinel.check_scalar(i, gnorm, "grad_norm")
                sentinel.check_tree(i, new_student, "params")
                if mesh is not None:
                    sentinel.check_divergence(i, new_student, "params")
        except SDC as e:
            if sup is not None:
                # Step-level replay first: degrade, reshard the PRE-step
                # state live, re-run the same batch — no rollback consumed,
                # no checkpoint touched. The discarded new_student carries
                # whatever tripped the screen.
                try:
                    student, opt_state = sup.trip_external(e, student, opt_state)
                    print(
                        f"{e} -> elastic replay of step {i + 1} on "
                        f"{sup.entry.key} (no rollback consumed)",
                        flush=True,
                    )
                    continue
                except DegradationExhausted:
                    pass  # ladder spent: fall through to the floor
            rc = _rollback(str(e))
            if rc is not None:
                return rc
            continue
        student, opt_state = new_student, new_opt
        if first is None:
            first = loss
        last = loss
        steps_run += 1
        print(f"Step {i + 1}/{args.steps}: loss = {loss:.6f}")
        jr.append("step", key=f"step:{i + 1}", step=i + 1, loss=loss, **current_ids())
        i += 1
        if i % args.checkpoint_every == 0 or i == args.steps:
            save_state(student, opt_state, i)
            jr.append("ckpt", key=f"ckpt:{i}", step=i, **current_ids())
            last_good_step = i
            rollbacks = 0  # progress made: reset the consecutive-trip budget
        if sup is not None:
            # Grow-back check between steps: pending heals are retried
            # against a fresh device re-query, and once a rejoined device
            # graduates probation the supervisor climbs the ladder back up
            # — mid-run, with the live state resharded onto the promoted
            # rung (no restart, no checkpoint round-trip).
            promoted = sup.maybe_promote(student, opt_state)
            if promoted is not None:
                student, opt_state = promoted
                print(
                    f"Elastic promote: climbed back to {sup.entry.key} "
                    f"(pool={sup.pool.summary()})",
                    flush=True,
                )
    flog.record("ok")
    if flog.retried:
        print(f"Sentinel fault log: {flog.summary()}")
    return first, last, steps_run, student, opt_state


def main(argv=None) -> int:
    args = make_parser().parse_args(argv)
    if args.steps < 1:
        print(f"--steps must be >= 1, got {args.steps}", file=sys.stderr)
        return 2
    if args.fake_devices:
        from .utils.env_info import force_virtual_cpu

        force_virtual_cpu(args.fake_devices)
    from .utils.compile_cache import enable_persistent_cache

    enable_persistent_cache()
    import dataclasses

    import jax
    import optax

    from . import native
    from .models.alexnet import BLOCKS12, output_shape
    from .models.init import init_params_deterministic, init_params_random
    from .parallel.mesh import make_mesh
    from .training import make_train_step

    cfg = dataclasses.replace(BLOCKS12, in_height=args.height, in_width=args.width)
    oh, ow, oc = output_shape(cfg)
    if min(oh, ow) <= 0:
        print(f"degenerate model for H={args.height} W={args.width}", file=sys.stderr)
        return 2

    if args.sp and args.tp:
        print("--sp and --tp are mutually exclusive strategies", file=sys.stderr)
        return 2
    if args.tp and args.dp > 1:
        print("--tp does not compose with --dp yet (TP builds its own 1-D mesh)", file=sys.stderr)
        return 2
    model_shards = args.sp or args.tp or 1
    n_devices_needed = max(1, args.dp) * model_shards
    if jax.device_count() < n_devices_needed:
        print(
            f"need {n_devices_needed} devices (dp={args.dp} x shards={model_shards}), "
            f"have {jax.device_count()}; use --fake-devices on CPU",
            file=sys.stderr,
        )
        return 2

    mesh = None
    if args.sp or args.dp > 1:
        mesh = make_mesh(args.sp or 1, dp=args.dp)
    sentinel = None
    if not args.no_sentinel:
        from .resilience.sentinel import SDC, Sentinel, SentinelConfig

        sentinel = Sentinel(
            SentinelConfig(
                window=args.sentinel_window,
                spike_factor=args.sentinel_spike,
                oracle_every=args.oracle_every,
            )
        )

    opt = optax.adam(args.lr) if args.optimizer == "adam" else optax.sgd(args.lr)
    opt_init, step_fn = make_train_step(
        cfg, mesh=mesh, optimizer=opt, sp_shards=args.sp, tp_shards=args.tp,
        remat=args.remat, with_grad_norm=sentinel is not None,
    )

    teacher = init_params_deterministic(cfg)
    if args.resume:
        from .utils.checkpoint import load_params_npz

        student = load_params_npz(args.resume)
        print(f"Resumed student from {args.resume}")
    else:
        student = init_params_random(jax.random.PRNGKey(args.seed), cfg)
    opt_state = opt_init(student)

    from .configs import REGISTRY, build_forward

    teacher_fwd = build_forward(REGISTRY["v1_jit"], cfg)

    print(
        f"--- Training (teacher-student, {args.optimizer}, lr={args.lr}, "
        f"batch={args.batch}, dp={args.dp}, sp={args.sp or 'off'}, "
        f"remat={args.remat}, H={args.height}) ---"
    )
    print(f"Devices: {jax.device_count()} x {jax.devices()[0].device_kind}")

    shape = (args.batch, cfg.in_height, cfg.in_width, cfg.in_channels)
    resilient = args.checkpoint_every > 0
    if args.supervise_steps and not resilient:
        print(
            "--supervise-steps requires --checkpoint-every (checkpoint "
            "rollback is the floor below the elastic ladder)",
            file=sys.stderr,
        )
        return 2

    from .resilience import chaos
    from .resilience.policy import FaultLog

    jr = None
    save_state = load_state = None
    start_step = 0
    if resilient:
        from pathlib import Path

        from .resilience.journal import Journal

        work = Path(args.work_dir)
        work.mkdir(parents=True, exist_ok=True)
        jr = Journal(work / "journal.jsonl")
        if args.checkpoint_shards > 0:
            # Crash-consistent sharded tree: N atomic shard files, manifest
            # committed last — a kill mid-save leaves the previous
            # generation loadable (docs/RESILIENCE.md).
            from .utils.checkpoint import (
                MANIFEST_NAME,
                load_train_state_sharded,
                save_train_state_sharded,
            )

            ckpt_path = work / "ckpt_last_good"
            ckpt_exists = (ckpt_path / MANIFEST_NAME).exists()

            def save_state(p, o, s):
                return save_train_state_sharded(
                    ckpt_path, p, o, s, n_shards=args.checkpoint_shards
                )

            def load_state(lp, lo):
                return load_train_state_sharded(ckpt_path, lp, lo)
        else:
            from .utils.checkpoint import load_train_state, save_train_state

            ckpt_path = work / "ckpt_last_good.npz"
            ckpt_exists = ckpt_path.exists()

            def save_state(p, o, s):
                return save_train_state(ckpt_path, p, o, s)

            def load_state(lp, lo):
                return load_train_state(ckpt_path, lp, lo)

        if ckpt_exists:
            try:
                student, opt_state, start_step = load_state(student, opt_state)
                print(f"Resumed training state from {ckpt_path} at step {start_step}")
                jr.append("resume", key=f"resume:{start_step}", step=start_step)
            except (ValueError, KeyError) as e:
                # A corrupt/mismatched checkpoint must not brick the run —
                # report it and start fresh (the atomic saver will replace it
                # at the next boundary).
                print(f"ignoring unusable checkpoint {ckpt_path}: {e}", file=sys.stderr)
        if start_step == 0:
            # The rollback target must exist BEFORE the first step so a trip
            # at step 1 has a last-good state to quarantine back to.
            save_state(student, opt_state, 0)
            jr.append("ckpt", key="ckpt:0", step=0)

    first = last = None
    t0 = time.perf_counter()
    if resilient:
        # Per-step-indexed batches (bit-identical to the loader stream:
        # batch k = fill_batch(shape, mode, batch_seed(seed, k))) so resume
        # and rollback replay exactly the batches an uninterrupted run sees.
        try:
            native.fill_batch((1, 1, 1, 1))
        except RuntimeError as e:
            print(f"cannot build native input tier: {e}", file=sys.stderr)
            return 2

        def get_batch(k: int):
            return native.fill_batch(shape, "uniform", native.batch_seed(args.seed, k))

        sup = None
        train_tracer = None
        if args.supervise_steps:
            # Spans ride the SAME work-dir journal the step/ckpt records
            # use, so one export covers the whole supervised run
            # (docs/OBSERVABILITY.md); step/rollback/ckpt records gain the
            # trace id, supervisor trips their span ids.
            from .observability.trace import Tracer, set_tracer

            train_tracer = Tracer(journal=jr)
            set_tracer(train_tracer)
            print(f"Trace: id={train_tracer.trace_id} journal={jr.path}")
            from .resilience.supervisor import Supervisor, train_ladder
            from .training import make_elastic_step_builder

            # The elastic step ladder replaces the bare step_fn: same
            # optimizer (opt-state trees stay portable across rungs), every
            # sharded rung rebuilt over the supervisor pool's SURVIVING
            # devices, trips replayed step-level before any rollback.
            sup = Supervisor(
                cfg,
                train_ladder(sp_shards=args.sp, tp_shards=args.tp),
                step_builder=make_elastic_step_builder(
                    cfg, optimizer=opt, remat=args.remat,
                    with_grad_norm=sentinel is not None,
                ),
                journal=jr,
                site="train",
            )

        try:
            rc = _run_resilient_loop(
                args, jr, save_state, load_state, start_step, get_batch, teacher_fwd,
                teacher, step_fn, student, opt_state, sentinel, mesh,
                FaultLog(site="train-sentinel"), sup=sup,
            )
        finally:
            if train_tracer is not None:
                from .observability.trace import set_tracer

                set_tracer(None)  # in-process callers must not leak a tracer
        if isinstance(rc, int):
            return rc
        # Take the TRAINED state back from the loop: --checkpoint below
        # must save what the run actually learned (the loop's locals never
        # flow back through its arguments; saving the pre-loop `student`
        # here silently exported the INITIAL params).
        first, last, steps_run, student, opt_state = rc
        if sup is not None:
            # Machine-parseable elastic summary: rung, trip kinds, replay
            # count, surviving pool.
            print(f"Elastic: {sup.summary()}")
            if jr is not None:
                # One-line fleet-health fold of the work-dir journal
                # (observability.health): incident MTTR, compile-cost
                # attribution for the supervised run.
                from .observability.health import health_from_journal

                try:
                    print(
                        f"Health: "
                        f"{health_from_journal(jr.path).summary_line()}"
                    )
                except Exception as e:  # noqa — evidence, not the result
                    print(f"Health: unavailable ({type(e).__name__}: {e})")
    else:
        try:
            loader_cm = native.NativeDataLoader(
                shape, mode="uniform", seed=args.seed, workers=args.loader_workers
            )
        except RuntimeError as e:  # toolchain missing / native build broke
            print(f"cannot build native input tier: {e}", file=sys.stderr)
            return 2
        with loader_cm as loader:
            for i in range(args.steps):
                x = jax.device_put(next(loader))
                y = teacher_fwd(teacher, x)
                out = step_fn(student, opt_state, x, y)
                student, opt_state, loss = out[0], out[1], float(out[2])
                gnorm = float(out[3]) if len(out) > 3 else None
                ch = chaos.active()
                if ch is not None and ch.draw("nan_loss"):
                    print(f"chaos: injected nan_loss at step {i + 1}", flush=True)
                    loss = float("nan")
                if sentinel is not None:
                    try:
                        sentinel.check_scalar(i, loss, "loss")
                        if gnorm is not None:
                            sentinel.check_scalar(i, gnorm, "grad_norm")
                    except SDC as e:  # no checkpoint: abort loudly
                        print(f"{e} (no checkpoint to roll back to; "
                              "run with --checkpoint-every)", file=sys.stderr)
                        return 3
                if first is None:
                    first = loss
                last = loss
                print(f"Step {i + 1}/{args.steps}: loss = {loss:.6f}")
        steps_run = args.steps
    wall = time.perf_counter() - t0
    if last is None:
        print(f"Training already complete at step {start_step}/{args.steps} (resumed)")
    else:
        print(
            f"Training completed in {wall * 1e3:.1f} ms "
            f"({steps_run} steps, loss {first:.6f} -> {last:.6f})"
        )

    if args.checkpoint:
        from .utils.checkpoint import save_params_npz

        save_params_npz(args.checkpoint, student)
        print(f"Saved params to {args.checkpoint}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
