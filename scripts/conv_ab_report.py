"""Summarize a conv-variant A/B log into the PALLAS_PERF lever table.

A chip A/B runs `run.py --config v3_pallas` across the lever grid
(conv=taps|pairs x rowblock 8|16|32 x kblock 0|128 x fp32|bf16) and
prefixes each harness-contract stdout line with the combo:

    conv=taps rb=8 kb=0 bf16 AlexNet TPU Forward Pass completed in 2.134 ms
    (amortized over 100 fenced passes; 59981.2 img/s)

This script parses those lines out of such a log, ranks combos by
throughput per compute tier, and emits the markdown table for
docs/PALLAS_PERF.md plus the adoption verdict against the round-3 bar
(v3_pallas bf16 >= 0.5x v1_jit at b=128 — VERDICT r3/r4 item 3). The
v1_jit reference rows come from perf/bench_latest.json (fresh same-session
numbers; the bar is only meaningful same-chip, same-day).

Usage:
    python scripts/conv_ab_report.py <ab_log>
"""

from __future__ import annotations

import json
import re
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent

# combo prefix added by the A/B driver, then the run.py stdout contract.
# The optional fuse= prefix carries the round-5 hpool epilogue-fusion A/B
# rows (fuse=none|hpool conv=vcol rb=64 kb=0 ...).
_LINE = re.compile(
    r"(?:fuse=(?P<fuse>\w+) )?"
    r"conv=(?P<conv>\w+) rb=(?P<rb>\d+) kb=(?P<kb>\d+) (?P<compute>fp32|bf16) "
    r"AlexNet TPU Forward Pass completed in (?P<ms>[\d.]+) ms "
    r"\(amortized over \d+ fenced passes; (?P<ips>[\d.]+) img/s\)"
)


def parse(text: str) -> list[dict]:
    rows = []
    for m in _LINE.finditer(text):
        rows.append(
            {
                "conv": m["conv"],
                "rowblock": int(m["rb"]),
                "kblock": int(m["kb"]),
                "fuse": m["fuse"] or "none",
                "compute": m["compute"],
                "ms": float(m["ms"]),
                "img_per_sec": float(m["ips"]),
            }
        )
    return rows


def v1_reference() -> dict[str, float]:
    """v1_jit img/s by compute tier from the committed fresh headline.

    The bar and the A/B grid are defined at v1_jit b=128, but the frozen
    record perf/bench_latest.json was captured at whatever config and batch
    its run named, so it is
    not guaranteed to be that capture (the round-3 headline was b=256) —
    refuse any mismatched baseline rather than judge the bar against it.
    """
    out: dict[str, float] = {}
    try:
        latest = json.loads((ROOT / "perf" / "bench_latest.json").read_text())
    except (OSError, ValueError):
        return out
    if latest.get("config") != "v1_jit" or latest.get("batch") != 128:
        return out
    if isinstance(latest.get("value"), (int, float)):
        out[latest.get("compute", "fp32")] = latest["value"]
    bf16 = latest.get("bf16")
    if isinstance(bf16, dict) and isinstance(bf16.get("value"), (int, float)):
        out["bf16"] = bf16["value"]
    return out


def report(rows: list[dict], ref: dict[str, float]) -> str:
    lines = [
        "| conv | rowblock | kblock | fuse | compute | ms/pass | img/s | vs v1_jit |",
        "|---|---|---|---|---|---|---|---|",
    ]
    for r in sorted(rows, key=lambda r: (r["compute"], -r["img_per_sec"])):
        rv = ref.get(r["compute"])
        vs = f"{r['img_per_sec'] / rv:.2f}x" if rv else "n/a"
        lines.append(
            f"| {r['conv']} | {r['rowblock']} | {r['kblock']} | {r['fuse']} "
            f"| {r['compute']} | {r['ms']:.3f} | {r['img_per_sec']:.0f} | {vs} |"
        )
    out = ["## Conv lever A/B (b=128, real chip)", "", *lines, ""]
    for tier in ("bf16", "fp32"):
        tier_rows = [r for r in rows if r["compute"] == tier]
        if not tier_rows:
            continue
        best = max(tier_rows, key=lambda r: r["img_per_sec"])
        rv = ref.get(tier)
        msg = (
            f"best {tier}: conv={best['conv']} rowblock={best['rowblock']} "
            f"kblock={best['kblock']} fuse={best['fuse']} "
            f"-> {best['img_per_sec']:.0f} img/s"
        )
        if rv:
            ratio = best["img_per_sec"] / rv
            msg += f" = {ratio:.2f}x v1_jit ({rv:.0f})"
            if tier == "bf16":
                msg += " — BAR MET (>=0.5x)" if ratio >= 0.5 else " — bar NOT met (<0.5x)"
        out.append(msg)
    return "\n".join(out)


def main(argv: list[str]) -> int:
    if len(argv) != 2:
        print(__doc__)
        return 2
    rows = parse(Path(argv[1]).read_text())
    if not rows:
        print("no A/B lines found (grep 'conv=' in the log?)")
        return 1
    print(report(rows, v1_reference()))
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv))
