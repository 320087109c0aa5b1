"""CLI: Perfetto export, journal replay, the BENCH regression gate,
roofline attribution, and the fleet-health report.

    python -m cuda_mpi_gpu_cluster_programming_tpu.observability \\
        export --journal logs/serve_journal.jsonl --out logs/trace.json
    python -m cuda_mpi_gpu_cluster_programming_tpu.observability \\
        replay --journal logs/serve_journal.jsonl [--traffic-mult 2] \\
        [--devices 1] [--slo-scale 0.5] [--journal-out replay.jsonl]
    python -m cuda_mpi_gpu_cluster_programming_tpu.observability \\
        report [--fail-on-regression] [--json] BENCH_r*.json
    python -m cuda_mpi_gpu_cluster_programming_tpu.observability \\
        roofline BENCH_r*.json            # committed rows, echo-aware
    python -m cuda_mpi_gpu_cluster_programming_tpu.observability \\
        roofline --live [--batch N] [--height H --width W]  # measure now
    python -m cuda_mpi_gpu_cluster_programming_tpu.observability \\
        health --journal logs/serve_journal.jsonl \\
        [--json] [--fail-on-budget-burn]

Exit codes (docs/OBSERVABILITY.md "Replay & regression gating" /
"Roofline attribution" / "Fleet health & compile attribution"):

- ``0`` — clean: trace exported / replay matched (or a what-if ran) /
  no regression / roofline rendered / health report rendered (budgets
  intact, or no gate requested).
- ``2`` — usage: missing journal, unreplayable journal (recorded before
  the replay schema), empty journal, bad arguments, no measurable
  roofline view.
- ``3`` — the gate tripped: a >10% regression with
  ``--fail-on-regression``, a NEUTRAL replay that broke the
  determinism contract (per-class accounting or percentile divergence),
  or a blown SLO error budget with ``--fail-on-budget-burn``.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path


def make_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="cuda_mpi_gpu_cluster_programming_tpu.observability"
    )
    sub = p.add_subparsers(dest="cmd", required=True)
    ex = sub.add_parser(
        "export",
        help="stitch span + journal records into a Perfetto-loadable "
        "Chrome trace-event JSON",
    )
    ex.add_argument(
        "--journal",
        required=True,
        help="a journal .jsonl file, or a directory whose *.jsonl files "
        "are stitched together",
    )
    ex.add_argument(
        "--out",
        default="",
        help="output trace path (default: <journal>.trace.json next to "
        "the input)",
    )
    rp = sub.add_parser(
        "report",
        help="cross-run report diffing BENCH_r*.json trajectories "
        "(>10% headline/stage regressions; last_good echoes excluded "
        "attributably)",
    )
    rp.add_argument("bench", nargs="+", help="BENCH_r*.json paths")
    rp.add_argument(
        "--fail-on-regression",
        action="store_true",
        help="exit 3 when any >threshold regression survives echo "
        "exclusion — the CI gate mode",
    )
    rp.add_argument(
        "--json",
        action="store_true",
        help="print the machine-readable GateVerdict object instead of "
        "the text report",
    )
    rl = sub.add_parser(
        "replay",
        help="re-drive a recorded serve journal through a live server on "
        "the CPU mesh (same arrivals/classes/deadlines, same chaos "
        "schedule) — scaling knobs turn it into a capacity what-if",
    )
    rl.add_argument(
        "--journal",
        required=True,
        help="the recorded serve journal (.jsonl file or directory)",
    )
    rl.add_argument(
        "--traffic-mult",
        type=float,
        default=1.0,
        help="offer the recorded schedule at this multiple (2 = every "
        "arrival twice; fractions select by a stable per-rid hash)",
    )
    rl.add_argument(
        "--devices",
        type=int,
        default=None,
        help="rebuild the server at this shard width instead of the "
        "recorded one ('would it hold at half the devices?')",
    )
    rl.add_argument(
        "--slo-scale",
        type=float,
        default=1.0,
        help="scale every class SLO budget and per-request deadline "
        "(0.5 = twice as tight)",
    )
    rl.add_argument(
        "--controller",
        choices=("on", "off"),
        default="",
        help="autopilot A/B dial (docs/SERVING.md \"Autopilot\"): 'on' "
        "forces the closed-loop controller onto the replay server, "
        "'off' strips a recorded one; default re-drives as recorded. "
        "Replaying one saturating trace both ways is the controller's "
        "win-quantification: interactive burn lower with it on, books "
        "closed both ways",
    )
    rl.add_argument(
        "--journal-out",
        default="",
        help="journal the replay run here (default: a temp file; the "
        "replay journal is itself replayable)",
    )
    rl.add_argument(
        "--json",
        action="store_true",
        help="print the machine-readable replay report object",
    )
    rf = sub.add_parser(
        "roofline",
        help="per-stage MFU / HBM-bandwidth attribution with "
        "compute-vs-memory-bound verdicts and the predicted fused-block "
        "ceiling, over committed BENCH_r*.json rows (echo-aware) or a "
        "live measurement",
    )
    rf.add_argument(
        "bench",
        nargs="*",
        help="BENCH_r*.json rows (driver-wrapped, bare objects, or "
        "JSONL); last_good echoes are marked via the gate's detection "
        "and never ranked as fresh",
    )
    rf.add_argument(
        "--live",
        action="store_true",
        help="measure a per-stage breakdown NOW (observability.stages on "
        "the current backend) and attribute it — TPU only: a device "
        "outside the spec table has no roof to judge against",
    )
    rf.add_argument("--batch", type=int, default=4, help="live batch size")
    rf.add_argument(
        "--height", type=int, default=227, help="live input height"
    )
    rf.add_argument("--width", type=int, default=227, help="live input width")
    rf.add_argument(
        "--dtype", default="fp32", help="live dtype policy (fp32|bf16)"
    )
    rf.add_argument(
        "--repeats", type=int, default=3, help="live per-prefix chain size"
    )
    rf.add_argument(
        "--json",
        action="store_true",
        help="print machine-readable RooflineReport objects (one JSON "
        "line per view)",
    )
    hl = sub.add_parser(
        "health",
        help="fleet-health report over any journal: incident MTTR "
        "decomposition (phases sum to wall time), availability from the "
        "device-seconds capacity timeline, per-class SLO attainment with "
        "error-budget burn, and compile-cost attribution",
    )
    hl.add_argument(
        "--journal",
        required=True,
        help="a journal .jsonl file, or a directory whose *.jsonl files "
        "are folded together",
    )
    hl.add_argument(
        "--json",
        action="store_true",
        help="print the machine-readable HealthReport object",
    )
    hl.add_argument(
        "--fail-on-budget-burn",
        action="store_true",
        help="exit 3 when any SLO class has burned through its error "
        "budget (burn > 1.0) — the gate mode",
    )
    return p


def main(argv=None) -> int:
    args = make_parser().parse_args(argv)
    if args.cmd == "export":
        from .export import export_trace

        src = Path(args.journal)
        if not src.exists():
            print(f"no journal at {src}", file=sys.stderr)
            return 2
        out = args.out or str(
            (src if src.is_dir() else src.with_suffix("")).with_suffix("")
        ) + ".trace.json"
        info = export_trace(src, out)
        print(
            f"Trace exported: {info['out']} events={info['events']} "
            f"spans={info['spans']} records={info['records']}"
        )
        if info["spans"] == 0:
            print(
                "note: no span records found — the timeline is the "
                "synthetic journal-order view (run with tracing wired, "
                "e.g. run --serve --serve-journal / --trace, for real "
                "timestamps)"
            )
        return 0
    if args.cmd == "report":
        from .gate import evaluate

        verdict = evaluate(args.bench)
        if args.json:
            print(json.dumps(verdict.to_obj()))
        else:
            print(verdict.render())
        if args.fail_on_regression and not verdict.ok:
            print(
                f"regression gate: FAIL ({len(verdict.regressions)} "
                f"regression(s) > {verdict.threshold:.0%})",
                file=sys.stderr,
            )
            return 3
        return 0
    if args.cmd == "replay":
        from .replay import ReplayKnobs, load_recorded_run, replay_recorded

        src = Path(args.journal)
        if not src.exists():
            print(f"no journal at {src}", file=sys.stderr)
            return 2
        try:
            recorded = load_recorded_run(src)
        except ValueError as e:
            print(f"unreplayable journal: {e}", file=sys.stderr)
            return 2
        if args.traffic_mult <= 0 or args.slo_scale <= 0:
            print("--traffic-mult/--slo-scale must be > 0", file=sys.stderr)
            return 2
        report = replay_recorded(
            recorded,
            ReplayKnobs(
                traffic_mult=args.traffic_mult,
                devices=args.devices,
                slo_scale=args.slo_scale,
                journal_path=args.journal_out,
                controller=args.controller,
            ),
        )
        if args.json:
            print(json.dumps(report.to_obj()))
        else:
            print(f"Replay: {report.summary()}")
            for line in report.class_lines():
                print(line)
            if report.controller_state is not None:
                st = report.controller_state
                print(
                    f"Replay controller: mode={st['mode']} "
                    f"level={st['level']} actions={st['actions'] or 'none'}"
                )
        if report.diverged:
            print(
                "replay divergence: a neutral replay must reproduce the "
                "recorded per-class accounting identically and land its "
                "percentiles within estimator resolution "
                "(docs/OBSERVABILITY.md)",
                file=sys.stderr,
            )
            return 3
        return 0
    if args.cmd == "roofline":
        return _roofline_main(args)
    if args.cmd == "health":
        from .export import load_records
        from .health import health_from_records

        src = Path(args.journal)
        if not src.exists():
            print(f"no journal at {src}", file=sys.stderr)
            return 2
        records = load_records(src)
        if not records:
            print(f"empty journal at {src}", file=sys.stderr)
            return 2
        report = health_from_records(records)
        if args.json:
            print(json.dumps(report.to_obj()))
        else:
            print(report.render())
        if args.fail_on_budget_burn and report.budget_blown:
            from .health import ERROR_BUDGET

            blown = [c.name or "(default)" for c in report.classes if c.blown]
            print(
                f"health gate: FAIL — error budget blown for class(es) "
                f"{', '.join(blown)} (burn > 1.0x of the "
                f"{ERROR_BUDGET:.0%} violation budget)",
                file=sys.stderr,
            )
            return 3
        return 0


def _roofline_main(args) -> int:
    """``roofline`` subcommand: ranked per-stage tables over committed
    BENCH rows (gate-classified, echoes marked and never ranked as
    fresh) or a live breakdown measurement."""
    rendered = 0
    # Row-per-line artifacts (perf/bench_tuned_*.jsonl — one row PER
    # config) render every row; round files go through the gate's
    # classifier so echoes are marked.
    jsonl = [p for p in args.bench if str(p).endswith(".jsonl")]
    rounds_paths = [p for p in args.bench if p not in jsonl]
    for path in jsonl:
        try:
            lines = Path(path).read_text().splitlines()
        except OSError as e:
            print(f"cannot read {path}: {e}", file=sys.stderr)
            return 2
        from .roofline import roofline_from_bench_row

        for i, line in enumerate(lines):
            line = line.strip()
            if not line.startswith("{"):
                continue
            try:
                obj = json.loads(line)
            except ValueError:
                continue
            for rep in roofline_from_bench_row(obj):
                rendered += 1
                rep.label = f"{obj.get('config', '')} {rep.label}".strip()
                if args.json:
                    print(json.dumps({"row": f"{path}:{i + 1}", **rep.to_obj()}))
                else:
                    print(f"== {path}:{i + 1}")
                    print(rep.render())
    if rounds_paths:
        from .gate import load_rounds
        from .roofline import roofline_from_bench_row

        rounds = load_rounds(rounds_paths)
        if not rounds:
            print("no parseable BENCH rows", file=sys.stderr)
            return 2
        for rr in rounds:
            print(f"== {rr.name}: {rr.provenance}")
            if rr.is_echo:
                # The gate's echo detection, reused: a wedged round
                # re-reporting an earlier round's number is marked and
                # skipped — ranking it would double-count stale evidence.
                print(
                    f"   echo of {rr.echo_of} — stale carry, not ranked"
                )
                continue
            reports = roofline_from_bench_row(rr.row)
            if not reports:
                print("   no measurable roofline view (error-only round)")
                continue
            for rep in reports:
                rendered += 1
                if args.json:
                    print(json.dumps({"round": rr.name, **rep.to_obj()}))
                else:
                    print(rep.render())
    if args.live:
        import jax

        from ..models.alexnet import BLOCKS12
        from ..models.init import deterministic_input, init_params_deterministic
        from .roofline import attribute_roofline
        from .stages import attribute_stages

        from .specs import UnknownDeviceError, spec_for

        import dataclasses as _dc

        device = jax.devices()[0]
        try:
            spec_for(device.device_kind)  # before spending the measurement
        except UnknownDeviceError as e:
            print(f"roofline --live: {e}", file=sys.stderr)
            return 2
        cfg = _dc.replace(
            BLOCKS12, in_height=args.height, in_width=args.width
        )
        att = attribute_stages(
            init_params_deterministic(cfg),
            deterministic_input(args.batch, cfg),
            cfg,
            compute=args.dtype,
            repeats=args.repeats,
            warmup=1,
        )
        rep = attribute_roofline(
            dict(att.stages),
            dtype=args.dtype,
            batch=args.batch,
            device_kind=device.device_kind,
            cfg=cfg,
            source="breakdown",
            total_ms=att.total_ms,
            label=f"live {device.platform}",
        )
        rendered += 1
        print(json.dumps(rep.to_obj()) if args.json else rep.render())
    if not args.bench and not args.live:
        print("roofline: name BENCH rows and/or pass --live", file=sys.stderr)
        return 2
    return 0 if rendered else 2


if __name__ == "__main__":
    raise SystemExit(main())
