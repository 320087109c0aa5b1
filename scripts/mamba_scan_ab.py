"""On-chip check and timing of the selective scan's kernel alone (`ops/selective_scan.py` `mamba_scan`).

One Mamba layer's scan at the published shape (1 x 4,096 tokens, 5,120 channels
of 16 states, `x''` in bf16, `Delta` in float32 drawn log-uniform in [1e-3, 0.1],
`A[c, n] = -(n + 1)`) over the forms the kernel's arguments span: the tokens of
one step of the sequential grid axis (`chunk`), the channels whose state one
program keeps in registers across a chunk's tokens (`channel_block`: 128
channels are one (16, 128) pair of tiles and one chain of dependent
multiply-adds, so a block of 512 interleaves four chains), and the tokens one
trip of the loop writes out (`unroll`). Every form is held to the recurrence one
token at a time (`mamba_recurrence`); the program's own choice
(`models.sambay.PHI4_MINI_FLASH`) should be the fastest or near it.

What was NOT built: a log-depth scan inside a chunk. The token loop costs per
(token, channel, state) one `exp` and five multiply-adds on the vector units,
which bind it (the roofline printed is the HBM's: `x''`, `y`, `Delta`, `B`, `C`
moved once); a log-depth scan does the same products `log2(chunk)` times over,
and would have to pair every decay with care never to exponentiate a sum.

Usage: python scripts/mamba_scan_ab.py [--calls 10]
One JSON line per form. On the CPU the kernel runs interpreted at a small shape
and the time printed is the interpreter's, not a device number.
"""

from __future__ import annotations

import argparse
import json
import math
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import jax
import jax.numpy as jnp
import numpy as np

from cuda_mpi_gpu_cluster_programming_tpu.models import sambay
from cuda_mpi_gpu_cluster_programming_tpu.ops.selective_scan import mamba_recurrence, mamba_scan
from cuda_mpi_gpu_cluster_programming_tpu.ops.vma import interpret_mode

HBM_BYTES_S = 819e9  # the v5e's published peak (benchmark/peaks.json)
# (chunk, channel_block, unroll)
FORMS = [
    (256, 128, 8), (256, 256, 8), (256, 512, 8), (256, 1024, 8), (256, 1280, 8), (256, 2560, 8), (256, 512, 16),
    (256, 1024, 16), (128, 512, 8), (128, 1024, 8), (128, 2560, 8), (128, 5120, 8), (512, 512, 8), (512, 1024, 8),
    (1024, 256, 8),
]


def ms_a_call(form, calls):
    """Least of three means of ``calls`` calls in flight behind one fence, after a warm-up."""
    jax.block_until_ready(form())
    means = []
    for _ in range(3):
        start = time.perf_counter()
        for _ in range(calls):
            out = form()
        jax.block_until_ready(out)
        means.append((time.perf_counter() - start) / calls * 1e3)
    return min(means)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--calls", type=int, default=10)
    args = ap.parse_args()
    on_chip = not interpret_mode()
    device = jax.devices()[0]
    cfg = sambay.PHI4_MINI_FLASH
    own = (cfg.scan_chunk, cfg.scan_channel_block, 8)
    b, l, ch, n = (1, 4096, cfg.d_inner, cfg.d_state) if on_chip else (1, 256, 256, cfg.d_state)
    forms = FORMS if on_chip else [(128, 128, 8), (256, 256, 8), (256, 128, 16)]
    keys = jax.random.split(jax.random.key(7), 4)
    x = jax.random.normal(keys[0], (b, l, ch), jnp.bfloat16)
    steps = math.log(sambay.DT_MIN), math.log(sambay.DT_MAX)
    delta = jnp.exp(jax.random.uniform(keys[1], (b, l, ch), jnp.float32, *steps))
    a = -jnp.broadcast_to(jnp.arange(1, n + 1, dtype=jnp.float32), (ch, n))
    b_in, c_out = (jax.random.normal(k, (b, l, n), jnp.float32) for k in keys[2:])
    d = jnp.ones((ch,), jnp.float32)
    want = np.asarray(mamba_recurrence(x, delta, a, b_in, c_out, d)[0])
    scale = float(np.abs(want).max())
    moved = b * l * (ch * (2 + 2 + 4) + 2 * n * 4)
    ok = True
    for chunk, channel_block, unroll in forms:
        # a new program per form: the tiles are read when the kernel is traced
        form = jax.jit(lambda: mamba_scan(  # noqa: jit-in-loop
            x, delta, a, b_in, c_out, d, chunk=chunk, channel_block=channel_block, unroll=unroll
        ))
        try:
            got = np.asarray(form(), np.float32)
        except Exception as e:  # noqa: BLE001 — a form the compiler refuses is a finding, not a failure
            refused = dict(chunk=chunk, channel_block=channel_block, unroll=unroll, refused=repr(e)[:300])
            print(json.dumps(refused), flush=True)
            continue
        err = float(np.abs(got - want).max() / scale)
        same = err <= 2.0**-7  # y is rounded to bf16 once
        ok = ok and same
        ms = ms_a_call(form, args.calls)
        print(json.dumps(dict(
            chunk=chunk, channel_block=channel_block, unroll=unroll, own_choice=(chunk, channel_block, unroll) == own,
            ms_a_call=ms if on_chip else None, interpreted_ms=None if on_chip else ms,
            hbm_roofline_share=moved / HBM_BYTES_S / (ms * 1e-3) if on_chip else None,
            max_err_over_max_ref=err, equals_the_recurrence=same, shape=[b, l, ch, n],
            platform=device.platform, device_kind=device.device_kind,
        )), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
