"""Session-to-session timing spread on the common cells of the two newest
TPU harness sessions — the acceptance check for the amortized work-floor
protocol (utils/timing.py).

Round-3 observed ~40% spread on sub-3 ms bf16 rows with naive short-chain
timing; the round-4 protocol (grow chain to >=100 ms of work, resample to
ci95 < 5%, MAD CI) claims <10%. This prints per-cell spread
|t_a - t_b| / mean(t_a, t_b) over cells present in BOTH sessions, flagging
the sub-3 ms rows the claim is about, and exits 1 if any sub-3 ms cell
exceeds SPREAD_BAR (default 0.10).

Usage: python scripts/session_spread.py [--bar 0.10] [--logs logs]
Session selection: the two newest ``logs/bench_*`` whose run logs carry a
``Devices: ... (tpu)``-style non-cpu backend banner (run.py prints it in
every case log) — a --fake-devices CPU smoke session landing in logs/
must not be compared against a TPU session. Pass
--sessions A B to pin explicitly (no backend filter then).
"""

from __future__ import annotations

import argparse
import csv
import json
from pathlib import Path

SPREAD_BAR = 0.10


def read_cells(csv_path: Path) -> dict:
    """(Variant, ConfigKey, NP, Batch) -> time_ms for OK rows."""
    cells = {}
    with open(csv_path, newline="") as f:
        for row in csv.DictReader(f):
            if row["Status"] == "OK" and row["ExecutionTime_ms"]:
                key = (row["Variant"], row["ConfigKey"], row["NP"], row["Batch"])
                cells[key] = float(row["ExecutionTime_ms"])
    return cells


def real_backend(session_dir: Path) -> bool:
    """True when any case log in the session ran on a non-cpu backend.

    run.py prints ``Devices: N x <kind> (<backend>)`` in every case log;
    the cpu backend includes every --fake-devices run. A session with no
    readable banner (all cases timed out pre-banner) is NOT real-backend —
    it has no usable rows either way.
    """
    for log in session_dir.glob("run_*.log"):
        try:
            text = log.read_text(errors="replace")
        except OSError:
            continue
        for line in text.splitlines():
            if line.startswith("Devices: "):
                if "(cpu)" not in line:
                    return True
                break  # one banner per log; cpu -> try the next log
    return False


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--bar", type=float, default=SPREAD_BAR)
    ap.add_argument("--logs", default="logs")
    ap.add_argument(
        "--sessions", nargs=2, metavar=("A", "B"),
        help="two session dirs to compare (default: the two newest bench_*)",
    )
    ap.add_argument(
        "--out", default="",
        help="persist the comparison as JSON for the analysis narrative "
        "(default: off — opt-in so test/ad-hoc invocations cannot clobber "
        "the canonical perf/session_spread_latest.json artifact)",
    )
    args = ap.parse_args(argv)
    root = Path(args.logs)
    if args.sessions:
        dirs = [Path(s) if Path(s).exists() else root / s for s in args.sessions]
    else:
        dirs = sorted(
            (
                d for d in root.glob("bench_*")
                if (d / "summary.csv").exists() and real_backend(d)
            ),
            key=lambda d: d.stat().st_mtime,
        )[-2:]
    if len(dirs) < 2:
        print(
            "session_spread: need two real-backend sessions, found fewer — "
            "nothing to compare"
        )
        return 0
    a, b = (read_cells(d / "summary.csv") for d in dirs)
    common = sorted(set(a) & set(b))
    if not common:
        print(f"session_spread: no common OK cells between {dirs[0].name} and {dirs[1].name}")
        return 0
    print(f"session_spread: {dirs[0].name} vs {dirs[1].name} ({len(common)} common cells)")
    print(f"{'cell':44s} {'t_a ms':>9s} {'t_b ms':>9s} {'spread':>7s}")
    worst_fast = 0.0
    failed = []
    rows = []
    for key in common:
        ta, tb = a[key], b[key]
        spread = abs(ta - tb) / ((ta + tb) / 2)
        cell = f"{key[0]} np={key[2]} b={key[3]}"
        fast = min(ta, tb) < 3.0
        mark = " <3ms" if fast else ""
        print(f"{cell:44s} {ta:9.3f} {tb:9.3f} {spread:6.1%}{mark}")
        rows.append(
            {
                "cell": cell, "batch": int(key[3]), "t_a_ms": ta, "t_b_ms": tb,
                "spread": round(spread, 4), "sub3ms": fast,
            }
        )
        if fast:
            worst_fast = max(worst_fast, spread)
            if spread > args.bar:
                failed.append(cell)
    if any(min(a[k], b[k]) < 3.0 for k in common):
        print(
            f"session_spread: worst sub-3ms spread {worst_fast:.1%} "
            f"(bar {args.bar:.0%}) -> {'FAIL: ' + ', '.join(failed) if failed else 'PASS'}"
        )
    if args.out:
        # Persisted so `analysis.py narrative` can quote the ACHIEVED spread
        # (round-4 verdict item 6 wants the measured number in the
        # narrative, pass or fail — not the protocol's claim).
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(
            json.dumps(
                {
                    "sessions": [dirs[0].name, dirs[1].name],
                    "bar": args.bar,
                    "worst_sub3ms_spread": round(worst_fast, 4),
                    "failed_cells": failed,
                    "cells": rows,
                },
                indent=1,
            )
            + "\n"
        )
    return 1 if failed else 0


if __name__ == "__main__":
    raise SystemExit(main())
