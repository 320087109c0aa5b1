"""On-chip check and timing of the expert tier's combine alone (`ops/moe_combine.py`).

The combine is a tenth of the step in the two top-8 language-model cells, and a
change to the kernel is decided on the kernel first: at the three published
shapes (tokens x places x hidden x span: dots 8,192 x 8 x 7,168 x 8,192, solar
16,384 x 8 x 4,096 x 24,576, zaya 4,096 x 1 x 2,048 x 3,840; bf16 rows, a
place held with the share of the experts a chip holds, every held place its
own row, as the dispatch lays them) it must give what the gather form gives
(`moe_combine_reference`, the form it replaced), and it is kept only where it
takes less time: `worth_a_kernel`'s one constant is fitted to these readings
(the shapes `k2` and `k4` stand between zaya's and the two top-8 ones), and
each line says which form the rule takes there. Beside each time: the rows
moved (one DMA each) and the share of the HBM's peak that the kernel's bytes
(the rows once, `y` in and out) come to. `PERF.md` section 6 has the readings.

Usage: python scripts/moe_combine_ab.py [--shapes dots,solar,zaya,k2,k4] [--slots 64]
    [--block-elements 524288] [--calls 10]
One JSON line per shape and form. On the CPU the kernel runs interpreted at a
small shape and the time printed is the interpreter's, not a device number.
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

from cuda_mpi_gpu_cluster_programming_tpu.ops.moe_combine import (
    moe_combine,
    moe_combine_reference,
    row_slab,
    worth_a_kernel,
)
from cuda_mpi_gpu_cluster_programming_tpu.ops.vma import interpret_mode

HBM_BYTES_PER_S = 819e9  # the v5e's published peak (benchmark/peaks.json)
# tokens, places a token, hidden, rows of the span, share of the places held here
SHAPES = {
    "dots": (8192, 8, 7168, 8192, 16 / 256),
    "solar": (16384, 8, 4096, 24576, 40 / 320),
    "zaya": (4096, 1, 2048, 3840, 8 / 17),
    # between them: zaya's tokens, width and span with two and four places a token, as many rows held
    "k2": (4096, 2, 2048, 3840, 0.4),
    "k4": (4096, 4, 2048, 3840, 0.2),
    "small": (64, 4, 256, 128, 0.25),
}


def operands(key, tokens, k, d, span, share, dtype=jnp.bfloat16):
    """Rows of unit normals, weights in (0, 1), and a table in which a place is
    held with probability ``share`` and every held place has a row of its own
    (the first ``span`` of them: the rest fall in a later span)."""
    k_res, k_held, k_perm, k_w = jax.random.split(key, 4)
    results = jax.random.normal(k_res, (span, d), jnp.float32).astype(dtype)
    held = jax.random.uniform(k_held, (tokens * k,)) < share
    rank = jnp.cumsum(held) - 1
    scattered = jax.random.permutation(k_perm, tokens * k)  # a pair's row is anywhere among the held
    row = jnp.where(held, scattered[rank] % jnp.maximum(held.sum(), 1), -1).astype(jnp.int32)
    weights = jax.random.uniform(k_w, (tokens, k), jnp.float32)
    return results, row.reshape(tokens, k), weights


def ms_a_call(form, results, row, weights, y, calls):
    """Mean host-clock time of ``calls`` calls chained through ``y`` (donated:
    no copy rides the call) behind one fence, after a warm-up."""
    y = jax.block_until_ready(form(results, row, weights, y))
    start = time.perf_counter()
    for _ in range(calls):
        y = form(results, row, weights, y)
    jax.block_until_ready(y)
    return (time.perf_counter() - start) / calls * 1e3


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--shapes", default="dots,solar,zaya")
    ap.add_argument("--slots", type=int, default=None)
    ap.add_argument("--block-elements", type=int, default=None)
    ap.add_argument("--calls", type=int, default=10)
    args = ap.parse_args()
    on_chip = not interpret_mode()
    device = jax.devices()[0]
    options = {name: v for name, v in (("slots", args.slots), ("block_elements", args.block_elements)) if v}
    kernel = jax.jit(functools.partial(moe_combine, base=0, **options), donate_argnums=3)
    gathers = jax.jit(functools.partial(moe_combine_reference, base=0), donate_argnums=3)
    ok = True
    for name in args.shapes.split(",") if on_chip else ["small"]:
        tokens, k, d, span, share = SHAPES[name]
        results, row, weights = operands(jax.random.key(len(name)), tokens, k, d, span, share)
        slabs, zeros = results.reshape(span, *row_slab(d)), lambda *shape: jnp.zeros(shape, jnp.float32)
        moved = int(((row >= 0) & (row < span)).sum())
        got = kernel(slabs, row, weights, zeros(tokens, *slabs.shape[1:])).reshape(tokens, d)
        want = gathers(results, row, weights, zeros(tokens, d))
        err = float(jnp.abs(got - want).max() / jnp.abs(want).max())
        bitwise = bool(np.array_equal(np.asarray(got), np.asarray(want)))
        ok = ok and err <= 1e-6
        bytes_least = moved * d * results.dtype.itemsize + 2 * tokens * d * 4
        # the third: the same kernel with nothing held, the scalar loop and y's pass alone
        for form, fn, res, table in (
            ("kernel", kernel, slabs, row), ("gathers", gathers, results, row),
            ("kernel_none_held", kernel, slabs, jnp.full_like(row, -1)),
        ):
            ms = ms_a_call(fn, res, table, weights, zeros(tokens, *res.shape[1:]), args.calls)
            print(json.dumps(dict(
                shape=name, form=form, ms_a_call=ms if on_chip else None, interpreted_ms=None if on_chip else ms,
                rows_moved=moved if form != "kernel_none_held" else 0, places=tokens * k,
                hbm_peak_share=bytes_least / HBM_BYTES_PER_S / (ms * 1e-3) if on_chip and form == "kernel" else None,
                rel_err=err, bitwise=bitwise, tokens=tokens, k=k, hidden=d, span=span, **options,
                rule_takes="kernel" if worth_a_kernel(tokens, k, d, span) else "gathers",
                platform=device.platform, device_kind=device.device_kind,
            )), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
