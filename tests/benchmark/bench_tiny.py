"""A copy of the benchmark with tiny cells beside the real ones, for the CPU.

Everything is added the way a later PR has to add it: new files under
``benchmark/`` and new entries in ``BENCHMARK.json``, no edit to a file that
was there. So each test that runs a tiny cell also shows that a cell, a
configuration and a traffic mix are data.
"""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]

# the cell it stands for -> (tiny cell, tiny config, tiny traffic, chips);
# blocks12_served is not in BENCHMARK.json: its entries wait in
# benchmark/cells_kept_for_later.json and are added here as a later PR would
TINY = {
    "blocks12_offline": ("tiny_offline", "tiny_blocks12", "tiny_offline_b4", 1),
    "blocks12_served": ("tiny_served", "tiny_blocks12", "tiny_served", 1),
    "alexnet_full_offline": ("tiny_full_offline", "tiny_full", "tiny_offline_b4", 1),
    "blocks12_rows4_offline": ("tiny_rows4_offline", "tiny_blocks12_rows4", "tiny_offline_b4", 4),
}


def copy_benchmark(dst: Path) -> Path:
    """``BENCHMARK.json`` and ``benchmark/`` copied into ``dst``."""
    shutil.copy(REPO / "BENCHMARK.json", dst / "BENCHMARK.json")
    shutil.copytree(
        REPO / "benchmark", dst / "benchmark",
        ignore=shutil.ignore_patterns("__pycache__"),
    )
    return dst


def add_tiny_cells(root: Path) -> None:
    """63x63 inputs, batch 4, a pool of 3 batches, short chains."""
    bench = root / "benchmark"
    manifest = json.loads((root / "BENCHMARK.json").read_text())
    for real, tiny in (
        ("alexnet_blocks12", "tiny_blocks12"),
        ("alexnet_full", "tiny_full"),
        ("alexnet_blocks12_rows4", "tiny_blocks12_rows4"),
    ):
        cfg = json.loads((bench / "configs" / f"{real}.json").read_text())
        size = 99 if cfg["fc"] else 63  # 99 leaves pool5 a 2x2 map to flatten
        cfg.update(in_height=size, in_width=size, reduced=["in_height", "in_width"])
        if "baseline_config" in cfg:
            cfg["baseline_config"] = "tiny_blocks12"
        if cfg["fc"]:  # a rehearsal only: a cell on the chip may not cut a width
            cfg.update(fc=[32, 32, 10], reduced=cfg["reduced"] + ["fc"])
        (bench / "configs" / f"{tiny}.json").write_text(json.dumps(cfg))
        manifest["configs"].append({
            "name": tiny, "source": cfg["source"],
            "file": f"benchmark/configs/{tiny}.json",
            "reduced": cfg["reduced"], "why": "CPU rehearsal size",
        })
    offline = json.loads((bench / "traffic" / "offline_b128.json").read_text())
    offline.update(batch=4, pool_batches=3, chain_len=2, sample_images=2, trace_seconds=0.2)
    (bench / "traffic" / "tiny_offline_b4.json").write_text(json.dumps(offline))
    served = json.loads((bench / "traffic" / "served_56rps.json").read_text())
    served.update(rate_rps=40.0, pool_images=64, sample_images=12, trace_seconds=0.5,
                  server={"max_batch": 4})
    for c in served["classes"]:
        c["sizes"] = [min(s, 4) for s in c["sizes"]]
    (bench / "traffic" / "tiny_served.json").write_text(json.dumps(served))
    kept = json.loads((bench / "cells_kept_for_later.json").read_text())
    for group in ("end_to_end", "per_layer"):
        manifest[group] += [dict(m, bound=0.1) if "bound" in m else m for m in kept[group]]
    for real, (cell, config, traffic, chips) in TINY.items():
        manifest["workloads"].append({
            "name": cell, "config": config, "traffic": traffic, "chips": chips,
            "why": f"{real} at a CPU rehearsal size",
        })
        for m in manifest["end_to_end"] + manifest["per_layer"]:
            if real in m.get("workloads", []):
                m["workloads"].append(cell)
    have = {w["name"] for w in manifest["workloads"]}
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        if "workloads" in m:  # blocks12_served itself is not pasted in
            m["workloads"] = [c for c in m["workloads"] if c in have]
    (root / "BENCHMARK.json").write_text(json.dumps(manifest, indent=1))


def on_two_cores() -> list:
    """A command prefix that keeps a rehearsal to two cores: tier-1 runs it
    beside timing-sensitive tests of the program, so it must not take every
    core of the machine. Empty where ``taskset`` is missing."""
    if not shutil.which("taskset"):
        return []
    cores = sorted(os.sched_getaffinity(0))[-2:]
    return ["taskset", "-c", ",".join(map(str, cores))]


def run_cell(root: Path, cell: str, *extra: str, seconds: float = 0.5,
             seed: int = 0, trace: int = 0, env=None, timeout: float = 600):
    """Run one cell of the copy as the driver would, on the CPU."""
    full_env = dict(os.environ)
    full_env.update(
        JAX_PLATFORMS="cpu",
        PYTHONPATH=str(REPO),  # the program; the copy holds only the benchmark
        JAX_COMPILATION_CACHE_DIR=str(root / ".xla_cache"),
    )
    if "xla_force_host_platform_device_count" not in full_env.get("XLA_FLAGS", ""):
        full_env["XLA_FLAGS"] = (
            full_env.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=4"
        ).strip()
    full_env.update(env or {})
    return subprocess.run(
        [*on_two_cores(), sys.executable, "benchmark/run.py", "--workload", cell, "--seed", str(seed),
         "--seconds", str(seconds), "--trace", str(trace), *extra],
        cwd=root, env=full_env, capture_output=True, text=True, timeout=timeout,
    )
