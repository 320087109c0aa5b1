"""CLI: Perfetto export, journal replay, roofline attribution, and the
fleet-health report.

    python -m cuda_mpi_gpu_cluster_programming_tpu.observability \\
        export --journal logs/serve_journal.jsonl --out logs/trace.json
    python -m cuda_mpi_gpu_cluster_programming_tpu.observability \\
        replay --journal logs/serve_journal.jsonl [--traffic-mult 2] \\
        [--devices 1] [--slo-scale 0.5] [--journal-out replay.jsonl]
    python -m cuda_mpi_gpu_cluster_programming_tpu.observability \\
        roofline --live [--batch N] [--height H --width W]  # measure now
    python -m cuda_mpi_gpu_cluster_programming_tpu.observability \\
        health --journal logs/serve_journal.jsonl \\
        [--json] [--fail-on-budget-burn]

Exit codes (docs/OBSERVABILITY.md "Replay" / "Roofline attribution" /
"Fleet health & compile attribution"):

- ``0`` — clean: trace exported / replay matched (or a what-if ran) /
  roofline rendered / health report rendered (budgets intact, or no
  gate requested).
- ``2`` — usage: missing journal, unreplayable journal (recorded before
  the replay schema), empty journal, bad arguments, ``roofline``
  without ``--live`` or on a device outside the spec table.
- ``3`` — the gate tripped: a NEUTRAL replay that broke the
  determinism contract (per-class accounting or percentile divergence),
  or a blown SLO error budget with ``--fail-on-budget-burn``.
"""

from __future__ import annotations

import argparse
import dataclasses
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
        "ceiling, over a live host-clock measurement (--live, the only "
        "mode; it stays until ROADMAP D6 removes the host-clock layer "
        "timers)",
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
    """``roofline`` subcommand: a live per-stage breakdown measurement,
    attributed against the spec table's roofs."""
    if not args.live:
        print("roofline: pass --live", file=sys.stderr)
        return 2
    import jax

    from ..models.alexnet import BLOCKS12
    from ..models.init import deterministic_input, init_params_deterministic
    from .roofline import attribute_roofline
    from .specs import UnknownDeviceError, spec_for
    from .stages import attribute_stages

    device = jax.devices()[0]
    try:
        spec_for(device.device_kind)  # before spending the measurement
    except UnknownDeviceError as e:
        print(f"roofline --live: {e}", file=sys.stderr)
        return 2
    cfg = dataclasses.replace(BLOCKS12, in_height=args.height, in_width=args.width)
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
    print(json.dumps(rep.to_obj()) if args.json else rep.render())
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
