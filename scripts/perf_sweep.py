"""TPU perf sweep: find the best (config, compute, batch) for the headline bench.

Run from the repo root on the real chip (ambient env untouched):

    python scripts/perf_sweep.py               # full sweep -> perf/sweep_<ts>.json
    python scripts/perf_sweep.py --quick       # 2 points per dimension

Prints one JSON line per point (machine-parseable, harness-style) and a
final ranking (a frozen record: the benchmark is benchmark/run.py).
"""

from __future__ import annotations

import argparse
import itertools
import json
import os
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--repeats", type=int, default=100)
    ap.add_argument("--out-dir", default="perf")
    args = ap.parse_args()

    import jax

    from cuda_mpi_gpu_cluster_programming_tpu.configs import REGISTRY, build_forward
    from cuda_mpi_gpu_cluster_programming_tpu.models.init import (
        deterministic_input,
        init_params_deterministic,
    )
    from cuda_mpi_gpu_cluster_programming_tpu.utils.timing import amortized_stats

    # v6_full_jit rides along: the full-AlexNet extension is a bench
    # candidate too (its matmul-heavy FC head behaves differently from
    # blocks 1-2), and the capture harness already measures it — the
    # ranking sweep should see the same family.
    configs = ["v1_jit", "v3_pallas", "v6_full_jit"]
    computes = ["fp32", "bf16"]
    batches = [64, 128, 256, 512]
    if args.quick:
        configs, computes, batches = ["v1_jit"], ["fp32", "bf16"], [128, 256]

    print(f"backend={jax.default_backend()} devices={jax.devices()}")
    from cuda_mpi_gpu_cluster_programming_tpu.models.alexnet_full import (
        init_full_deterministic,
    )

    params_b12 = init_params_deterministic()
    # Full-AlexNet params (~61M, ~230 MB fp32) only when a selected config
    # needs them — they'd otherwise sit in HBM during the blocks12 timings.
    params_full = (
        init_full_deterministic()
        if any(REGISTRY[k].model == "alexnet_full" for k in configs)
        else None
    )
    rows = []
    for key, compute, batch in itertools.product(configs, computes, batches):
        x = deterministic_input(batch=batch)
        params = params_full if REGISTRY[key].model == "alexnet_full" else params_b12
        try:
            fwd = build_forward(REGISTRY[key], compute=compute)
            t0 = time.perf_counter()
            jax.block_until_ready(fwd(params, x))
            compile_s = time.perf_counter() - t0
            # Work-floor stats (round-3 verdict: sub-3 ms bf16 rows carried
            # ~40% session spread on short chains) — each point now reports
            # its sample count and 95% CI alongside the median.
            st = amortized_stats(fwd, params, x, n_small=10, n_large=10 + args.repeats)
            ms = st.per_call_ms
            row = {
                "config": key,
                "compute": compute,
                "batch": batch,
                "ms_per_pass": round(ms, 4),
                "img_per_sec": round(batch / (ms / 1e3), 1),
                "compile_s": round(compile_s, 1),
                "timing_n": st.n_samples,
                "timing_ci95_ms": round(st.ci95_ms, 4),
                "timing_chain": st.n_chain,
                "timing_shadowed": st.shadowed,
                "timing_underconverged": st.underconverged,
            }
        except Exception as e:  # record and continue the sweep
            row = {"config": key, "compute": compute, "batch": batch,
                   "error": f"{type(e).__name__}: {e}"[:200]}
        print(json.dumps(row), flush=True)
        rows.append(row)

    ok = [r for r in rows if "img_per_sec" in r]
    ok.sort(key=lambda r: -r["img_per_sec"])
    out = {
        "ts": time.strftime("%Y%m%d_%H%M%S"),
        "backend": jax.default_backend(),
        "device": jax.devices()[0].device_kind,
        "rows": rows,
        "best": ok[0] if ok else None,
    }
    os.makedirs(args.out_dir, exist_ok=True)
    path = Path(args.out_dir) / f"sweep_{out['ts']}.json"
    path.write_text(json.dumps(out, indent=1))
    print(f"\nbest: {json.dumps(out['best'])}\nsaved: {path}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
