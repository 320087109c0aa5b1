"""Per-layer Pallas-vs-XLA A/B for the v3 tier's five ops (chip evidence).

The v3_pallas full-pass bar (bf16 >= 0.5x v1_jit at b=128) has now missed
on all three named levers (pairs, rowblock, kblock). This script attributes
the remaining gap per layer: each of the five ops in forward_blocks12_pallas
is timed in isolation against the XLA lowering of the same math, same
shapes, same dtype — so the next lever (or the documented negative) is
named from measurement, not guesswork.

Usage (real chip):
    python scripts/v3_layer_ab.py [--compute bf16] [--batch 128] [--repeats 100]
"""

from __future__ import annotations

import argparse
import functools
import statistics
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import jax
import jax.numpy as jnp
from jax import lax

from cuda_mpi_gpu_cluster_programming_tpu.configs import BLOCKS12
from cuda_mpi_gpu_cluster_programming_tpu.models.init import (
    deterministic_input,
    init_params_deterministic,
)
from cuda_mpi_gpu_cluster_programming_tpu.ops import pallas_kernels as pk
from cuda_mpi_gpu_cluster_programming_tpu.ops import reference as ref_ops


def _time(fn, *args, repeats: int) -> float:
    """Median per-call ms under the repo's work-floor protocol
    (utils/timing.py amortized_stats: two-queue-length differencing with a
    D2H fence, chain grown to the >=100 ms work floor).  ``repeats``
    seeds the small queue length; the protocol grows the chain as needed."""
    from cuda_mpi_gpu_cluster_programming_tpu.utils.timing import amortized_stats

    f = jax.jit(fn)
    jax.block_until_ready(f(*args))  # compile outside the clock
    st = amortized_stats(f, *args, n_small=10, n_large=10 + repeats)
    return statistics.median(st.samples_ms)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--compute", default="bf16", choices=["fp32", "bf16"])
    ap.add_argument("--batch", type=int, default=128)
    ap.add_argument("--repeats", type=int, default=100)
    args = ap.parse_args()
    dtype = jnp.bfloat16 if args.compute == "bf16" else jnp.float32

    v = pk.KernelVariants.resolve()
    cfg = BLOCKS12
    params = init_params_deterministic()
    x0 = deterministic_input(batch=args.batch).astype(dtype)
    w1 = params["conv1"]["w"].astype(dtype)
    b1 = params["conv1"]["b"].astype(dtype)
    w2 = params["conv2"]["w"].astype(dtype)
    b2 = params["conv2"]["b"].astype(dtype)

    c1, p1, c2, p2, n2 = cfg.conv1, cfg.pool1, cfg.conv2, cfg.pool2, cfg.lrn2

    def conv_pallas(x, w, b, spec):
        return pk.conv2d_pallas(
            x, w, b, stride=spec.stride, padding=spec.padding, relu=True,
            variant=v.conv, row_block=v.row_block, k_block=v.k_block,
        )

    def conv_xla(x, w, b, spec):
        # Precision must match the Pallas side's _mxu_precision (fp32 ->
        # HIGHEST = true fp32 via 6 bf16 MXU passes; default would round
        # operands to bf16 and make the fp32 column ~6x too fast — review
        # finding, 2026-07-31). bf16 stays DEFAULT on both sides.
        out = lax.conv_general_dilated(
            x, w, (spec.stride, spec.stride),
            [(spec.padding, spec.padding)] * 2,
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            preferred_element_type=jnp.float32,
            precision=(
                lax.Precision.HIGHEST
                if x.dtype == jnp.float32
                else lax.Precision.DEFAULT
            ),
        )
        return jnp.maximum(out + b, 0.0).astype(x.dtype)

    def pool_pallas(x, spec):
        return pk.maxpool_pallas(x, window=spec.window, stride=spec.stride, variant=v.pool)

    def pool_xla(x, spec):
        return lax.reduce_window(
            x, -jnp.inf if x.dtype == jnp.float32 else jnp.finfo(x.dtype).min,
            lax.max, (1, spec.window, spec.window, 1),
            (1, spec.stride, spec.stride, 1), "VALID",
        )

    lrn_pallas = functools.partial(
        pk.lrn_pallas, size=n2.size, alpha=n2.alpha, beta=n2.beta, k=n2.k,
        alpha_over_size=n2.alpha_over_size,
    )
    lrn_xla = functools.partial(
        ref_ops.lrn, size=n2.size, alpha=n2.alpha, beta=n2.beta, k=n2.k,
        alpha_over_size=n2.alpha_over_size,
    )

    # Chain the real intermediate activations so every stage sees its true
    # input shape/layout.
    a1 = jax.jit(lambda x: conv_xla(x, w1, b1, c1))(x0)
    a2 = jax.jit(lambda x: pool_xla(x, p1))(a1)
    a3 = jax.jit(lambda x: conv_xla(x, w2, b2, c2))(a2)
    a4 = jax.jit(lambda x: pool_xla(x, p2))(a3)

    stages = [
        ("conv1+relu", lambda x: conv_pallas(x, w1, b1, c1),
         lambda x: conv_xla(x, w1, b1, c1), x0),
        ("pool1", lambda x: pool_pallas(x, p1), lambda x: pool_xla(x, p1), a1),
        ("conv2+relu", lambda x: conv_pallas(x, w2, b2, c2),
         lambda x: conv_xla(x, w2, b2, c2), a2),
        ("pool2", lambda x: pool_pallas(x, p2), lambda x: pool_xla(x, p2), a3),
        ("lrn2", lrn_pallas, lrn_xla, a4),
    ]

    plat = jax.devices()[0].platform
    print(f"# v3 per-layer A/B  platform={plat} compute={args.compute} "
          f"batch={args.batch} conv={v.conv} rb={v.row_block} kb={v.k_block} "
          f"pool={v.pool}")
    print(f"{'layer':<12} {'pallas_ms':>10} {'xla_ms':>8} {'pallas/xla':>10}")
    tot_p = tot_x = 0.0
    for name, fp, fx, xin in stages:
        mp = _time(fp, xin, repeats=args.repeats)
        mx = _time(fx, xin, repeats=args.repeats)
        tot_p += mp
        tot_x += mx
        print(f"{name:<12} {mp:>10.3f} {mx:>8.3f} {mp / mx:>9.2f}x")
    print(f"{'TOTAL':<12} {tot_p:>10.3f} {tot_x:>8.3f} {tot_p / tot_x:>9.2f}x")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
