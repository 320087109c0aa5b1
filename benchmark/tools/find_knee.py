"""The one-off rate sweep for a served cell: the highest rate the system
sustains, found once on the chip; the cell then runs at four fifths of it
and the number is written into the traffic file.

    python3 benchmark/tools/find_knee.py --workload blocks12_served \\
        --rates 100,200,400,600,800,1000 --seconds 8

One process, one server, one window per rate. A rate is sustained when the
99th percentile from due time stays under ``--limit-ms``, nothing is
rejected or left unanswered, and the backlog does not grow: the answers
are all in within ``--drain-s`` of the last send.
"""

from __future__ import annotations

import argparse
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmark import harness, loadgen  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True, help="comma-separated requests per second")
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--limit-ms", type=float, default=100.0)
    ap.add_argument("--drain-s", type=float, default=0.25)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    args.trace = 0
    ctx = harness.open_cell(args)
    code = harness.attach_device(ctx)
    if code:
        return code
    served = harness.load_plugin("drivers", ctx.traffic["driver"])

    cfg, traffic, adapter = ctx.config, dict(ctx.traffic), ctx.adapter
    params = adapter.make_params(cfg, ctx.seed)
    _pool_dev, pool_host = served.make_pool(adapter, cfg, int(traffic["pool_images"]), ctx.seed)
    server = adapter.build_server(cfg, params, traffic["server"])
    server.start()
    rows = []
    try:
        for i, rate in enumerate(float(r) for r in args.rates.split(",")):
            traffic["rate_rps"] = rate
            plan = served.plan_requests(traffic, args.seconds, args.seed + i)
            res = served.drive(ctx, server, plan, pool_host, [], float(traffic["drain_timeout_s"]))
            lat = res["latencies_ms"]
            failed = len(plan) - res["outcome"]["OK"]
            drain = res["wall_s"] - res["sent_s"]
            tail = lat[-max(1, len(lat) // 10):]
            row = {
                "rate_rps": rate, "requests": len(plan),
                "images_per_s": sum(p[2] for p in plan) / res["wall_s"],
                "p50_ms": loadgen.percentile(lat, 50), "p99_ms": loadgen.percentile(lat, 99),
                "last_tenth_p50_ms": loadgen.percentile(tail, 50),
                "late_p99_ms": loadgen.percentile(res["late_ms"], 99),
                "failed": failed, "drain_s": drain,
            }
            row["sustained"] = bool(
                row["p99_ms"] is not None and row["p99_ms"] < args.limit_ms
                and failed == 0 and drain < args.drain_s
            )
            rows.append(row)
            print(
                "| {rate_rps:g} | {requests} | {images_per_s:.0f} | {p50_ms:.2f} | {p99_ms:.2f} | "
                "{last_tenth_p50_ms:.2f} | {late_p99_ms:.3f} | {failed} | {drain_s:.3f} | "
                "{sustained} |".format(**row),
                flush=True,
            )
    finally:
        server.stop(drain=False, timeout_s=30.0)
    knee = max((r["rate_rps"] for r in rows if r["sustained"]), default=None)
    print(f"knee: {knee} requests/s; four fifths: {None if knee is None else 0.8 * knee}")
    print(f"server: {server.stats.summary()}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
