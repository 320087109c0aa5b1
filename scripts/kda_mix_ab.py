"""On-chip check and timing of the short convolution's kernel alone (`ops/kda_mix.py`).

`kda.mix` was a ninth of `solar_open2_prefill_s8192`'s step as three `jax.numpy`
passes a layer, and a change to the kernel is decided on the kernel first: one
layer's projection at the cell's shape (2 x 64 heads x 8,192 tokens x 128,
float32 in, bf16 out), with the l2norm (q, k) and without (v), against the
jitted `jax.numpy` form of `models/kda_moe.py` — the same numbers to one place
of bf16, and less time. PR 36 read 1.29 / 1.32 ms for the kernel and 4.98-5.02
/ 3.35-3.38 for the `jax.numpy` form (`PERF.md` section 6).

Usage: python scripts/kda_mix_ab.py [--block-elements 524288] [--rows 256] [--calls 20]
One JSON line per form. On the CPU the kernel runs interpreted at a small
shape and the time printed is the interpreter's, not a device number.
"""

from __future__ import annotations

import argparse
import functools
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import jax
import jax.numpy as jnp
import numpy as np

from cuda_mpi_gpu_cluster_programming_tpu.models.kda_moe import _conv_mix_plain
from cuda_mpi_gpu_cluster_programming_tpu.ops import kda_mix
from cuda_mpi_gpu_cluster_programming_tpu.ops.vma import interpret_mode

HBM_BYTES_PER_S = 819e9  # the v5e's published peak (benchmark/peaks.json)


def ms_a_call(form, x, taps, calls):
    """Mean host-clock time of ``calls`` calls in flight behind one fence, after a warm-up."""
    jax.block_until_ready(form(x, taps))
    start = time.perf_counter()
    for _ in range(calls):
        out = form(x, taps)
    jax.block_until_ready(out)
    return (time.perf_counter() - start) / calls * 1e3


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--block-elements", type=int, default=kda_mix.BLOCK_ELEMENTS)
    ap.add_argument("--rows", type=int, default=kda_mix.ROWS)
    ap.add_argument("--calls", type=int, default=20)
    args = ap.parse_args()
    on_chip = not interpret_mode()
    device = jax.devices()[0]
    b, h, seq, e = (2, 64, 8192, 128) if on_chip else (2, 2, 128, 128)
    kx, kt = jax.random.split(jax.random.key(0))
    x = jax.random.normal(kx, (b, h, seq, e), jnp.float32)
    taps = (0.5 * jax.random.normal(kt, (4, h, e), jnp.float32)).astype(jnp.bfloat16)
    moved = x.size * (4 + 2)
    ok = True
    sizes = dict(block_elements=args.block_elements if on_chip else 64 * e, rows=args.rows if on_chip else 16)
    kernel = functools.partial(kda_mix.short_conv_mix, out_dtype=jnp.bfloat16, **sizes)
    jitted = {
        "kernel": jax.jit(kernel, static_argnames="l2norm"),
        "jax_numpy": jax.jit(functools.partial(_conv_mix_plain, dtype=jnp.bfloat16), static_argnames="l2norm"),
    }
    for l2norm in (True, False):
        forms = {name: functools.partial(form, l2norm=l2norm) for name, form in jitted.items()}
        got, want = (np.asarray(form(x, taps), np.float32) for form in forms.values())
        # one place of bf16 at the reference's magnitude (at 2**-7 where the terms cancel to less)
        place = 2.0 ** (np.floor(np.log2(np.maximum(np.abs(want), 2.0**-7))) - 7)
        apart = np.abs(got - want) / place
        ok = ok and float(apart.max()) <= 1.0
        for name, form in forms.items():
            ms = ms_a_call(form, x, taps, args.calls)
            print(json.dumps(dict(
                form=name, l2norm=l2norm, ms_a_call=ms if on_chip else None, interpreted_ms=None if on_chip else ms,
                hbm_peak_share=moved / HBM_BYTES_PER_S / (ms * 1e-3) if on_chip else None,
                bf16_places_apart_max=float(apart.max()), elements_apart=float((apart > 0).mean()),
                shape=[b, h, seq, e], **sizes, platform=device.platform, device_kind=device.device_kind,
            )), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
