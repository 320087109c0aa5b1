"""On-chip check and timing of the causal forward kernel alone (`ops/flash_attention.py` `flash_fwd`).

`flash_fwd` is the first bottleneck of two language-model cells and the second
of a third, and a change to it is decided on the kernel first: one attention
call at each cell's shape (bf16, blocks of 1,024), with a block on the diagonal
computed in row slabs of each height the shapes allow — the block whole (what
the kernel did before PR 38), a half, a quarter, an eighth — by putting another
rule in `_diag_slab`'s place HERE (the program has no option for it). Every
height must give the whole block's output and lse, and the kernel's own choice
should be the fastest or near it. PR 38 read, ms a call, whole / kernel's own:
dots 16.28 / 13.93, longcat 8.17 / 6.99, zaya 0.388 / 0.351, solar 20.57 / 19.14
(`PERF.md` section 6).

Usage: python scripts/flash_causal_ab.py [--cells dots,zaya] [--calls 25]
One JSON line per cell and height. On the CPU the kernel runs interpreted at a
small shape and the time printed is the interpreter's, not a device number.
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
import numpy as np

from cuda_mpi_gpu_cluster_programming_tpu.ops import flash_attention
from cuda_mpi_gpu_cluster_programming_tpu.ops.vma import interpret_mode

# cell: (batch, query heads, key/value heads, tokens, D, Dv, rope width): one attention call of its step
CELLS = {
    "dots": (2, 128, 128, 4096, 128, 128, 64),
    "longcat": (2, 64, 64, 4096, 128, 128, 64),
    "zaya": (1, 8, 2, 4096, 128, 128, 0),
    "solar": (2, 64, 8, 8192, 128, 128, 0),
}
MXU_FLOPS = 197e12  # the v5e's published bf16 peak (benchmark/peaks.json)


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
    ap.add_argument("--cells", default=",".join(CELLS))
    ap.add_argument("--calls", type=int, default=25)
    args = ap.parse_args()
    on_chip = not interpret_mode()
    device = jax.devices()[0]
    block = 1024 if on_chip else 256
    own = flash_attention._diag_slab
    ok = True
    try:
        for cell in args.cells.split(","):
            b, h, hk, l, d, dv, r = CELLS[cell] if on_chip else (1, 2, 1, 4 * block, 128, 128, CELLS[cell][6])
            keys = jax.random.split(jax.random.key(7), 5)
            draw = lambda key, shape: jax.random.normal(key, shape, jnp.bfloat16)
            q, k, v = draw(keys[0], (b, h, l, d)), draw(keys[1], (b, hk, l, d)), draw(keys[2], (b, hk, l, dv))
            rope = dict(q_rope=draw(keys[3], (b, h, r, l)), k_rope=draw(keys[4], (b, r, l))) if r else {}
            kept_flops = 2 * b * h * (l * (l + 1) // 2) * (d + r + dv)  # the scores the mask leaves
            whole = None
            for slab in (block, block // 2, block // 4, block // 8, None):  # None: the kernel's own rule
                flash_attention._diag_slab = own if slab is None else (lambda bq, bk, rows=slab: rows)
                plan = flash_attention.causal_plan(l, block, block)
                # a new program per rule: the slab height is read when the kernel is traced
                form = jax.jit(lambda: flash_attention.flash_forward_bhld(  # noqa: jit-in-loop
                    q, k, v, causal=True, block_q=block, block_k=block, scale=(d + r) ** -0.5, **rope
                ))
                out, lse = (np.asarray(x, np.float32) for x in form())
                whole = whole or (out, lse)
                same = bool(np.array_equal(out, whole[0]) and np.array_equal(lse, whole[1]))
                ok = ok and same
                ms = ms_a_call(form, args.calls * (16 if cell == "zaya" and on_chip else 1))
                print(json.dumps(dict(
                    cell=cell, diag_slab=plan.diag_slab, own_rule=slab is None, grid_steps_a_head=plan.grid_steps,
                    masked_score_share=plan.masked_score_share, ms_a_call=ms if on_chip else None,
                    interpreted_ms=None if on_chip else ms,
                    mxu_peak_share_of_kept_scores=kept_flops / MXU_FLOPS / (ms * 1e-3) if on_chip else None,
                    equals_the_whole_block=same, shape=[b, h, hk, l, d, dv, r], block=block,
                    platform=device.platform, device_kind=device.device_kind,
                )), flush=True)
    finally:
        flash_attention._diag_slab = own
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
