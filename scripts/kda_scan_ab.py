"""On-chip check and timing of the chunked delta-rule scan alone (`ops/kda.py`).

`kda.scan` is a third of `solar_open2_prefill_s8192`'s step, and a change to
the kernel is decided on the kernel first: it must still agree with the plain
recurrence on the chip (float32 and bf16 operands, decays of 0.1 and of 30 a
token, where `exp(-G)` alone overflows float32 inside a chunk), and it is kept
only if one layer's call at the cell's shape (2 x 64 heads x 8,192 tokens x
128, bf16, chunk 128, four heads a program) takes less time than before. PR 31's
kernel read 43.6-43.9 ms a layer there, PR 32's 30.5 (`PERF.md` section 6).

Usage: python scripts/kda_scan_ab.py [--chunk 128] [--head-block 4] [--calls 5]
One JSON line per check and one for the timing; exits 1 if a check is over
its limit. On the CPU the kernel runs interpreted: the checks hold at a small
shape, and the time printed is the interpreter's, not a device number.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import jax
import jax.numpy as jnp

from cuda_mpi_gpu_cluster_programming_tpu.ops.kda import kda_chunked, kda_recurrence
from cuda_mpi_gpu_cluster_programming_tpu.ops.vma import interpret_mode

# PR 31's kernel read 2.01e-5 (float32, decay 0.1; the same to every digit at chunk 64 and 128: the
# recurrence's own float32 over 1,024 slowly decaying tokens) and 5.84e-3 (bf16, decay 30) on the chip
# on these operands; the limits leave a fifth above those
LIMITS = {"float32": 2.5e-5, "bfloat16": 7e-3}


def operands(key, b, h, l, d, *, decay, dtype):
    """Unit queries and keys, normal values, `g = -decay * U(0, 1)^3`, `beta` in [0, 2)."""
    kq, kk, kv, kg, kb = jax.random.split(key, 5)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(jax.random.normal(kq, (b, h, l, d))).astype(dtype)
    k = unit(jax.random.normal(kk, (b, h, l, d))).astype(dtype)
    v = jax.random.normal(kv, (b, h, l, d)).astype(dtype)
    g = -decay * jax.random.uniform(kg, (b, h, l, d)) ** 3
    return q, k, v, g, 2.0 * jax.random.uniform(kb, (b, h, l))


def check(scan, *, length, dim, heads) -> list[dict]:
    """The kernel against the recurrence: largest error over the largest output."""
    rows = []
    for dtype in (jnp.float32, jnp.bfloat16):
        for decay in (0.1, 30.0):
            args = operands(jax.random.key(0), 1, heads, length, dim, decay=decay, dtype=dtype)
            want, _state = kda_recurrence(*args)
            got = scan(*args).astype(jnp.float32)
            err = float(jnp.abs(got - want).max() / jnp.abs(want).max())
            limit = LIMITS[jnp.dtype(dtype).name]
            rows.append(dict(check=jnp.dtype(dtype).name, decay=decay, rel_err=err, limit=limit, ok=err <= limit))
    return rows


def ms_a_layer(scan, *, batch, heads, length, dim, calls) -> float:
    """Mean host-clock time of `calls` calls in flight behind one fence, after a warm-up."""
    args = operands(jax.random.key(1), batch, heads, length, dim, decay=2.0, dtype=jnp.bfloat16)
    jax.block_until_ready(scan(*args))
    start = time.perf_counter()
    for _ in range(calls):
        out = scan(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - start) / calls * 1e3


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--chunk", type=int, default=128)
    ap.add_argument("--head-block", type=int, default=4)
    ap.add_argument("--calls", type=int, default=5)
    args = ap.parse_args()
    on_chip = not interpret_mode()
    device = jax.devices()[0]
    scan = jax.jit(lambda *a: kda_chunked(*a, chunk=args.chunk, head_block=args.head_block))
    small = dict(batch=1, heads=args.head_block, length=2 * args.chunk, dim=128)
    shape = dict(batch=2, heads=64, length=8192, dim=128) if on_chip else small
    rows = check(scan, length=1024 if on_chip else small["length"], dim=128, heads=args.head_block)
    for row in rows:
        print(json.dumps(row), flush=True)
    ms = ms_a_layer(scan, calls=args.calls, **shape)
    print(json.dumps(dict(
        ms_a_layer=ms if on_chip else None, interpreted_ms=None if on_chip else ms, chunk=args.chunk,
        head_block=args.head_block, platform=device.platform, device_kind=device.device_kind, **shape,
    )), flush=True)
    return 0 if all(row["ok"] for row in rows) else 1


if __name__ == "__main__":
    sys.exit(main())
