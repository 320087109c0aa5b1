"""The second reading a tolerance is set between (PERF.md section 4): the plain
reference computed in the nearest precision below the one the configuration
states (bf16 throughout: weights, activations, softmax, router, every product's
result), held to the cell's own check against the float32 reference. It has to
come out NOT CORRECT by one of the cell's limits.

    python3 benchmark/tools/precision_reading.py --workload <cell> --seed <n>

Run by hand on the chip (or with ``--rehearse`` on the CPU at a tiny size); for
the token-driven family only, whose reference takes ``compute``.
"""

from __future__ import annotations

import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent.parent
sys.path[:] = [str(ROOT)] + [p for p in sys.path if Path(p or ".").resolve() != HERE]


def main(argv=None) -> int:
    from benchmark import harness

    ctx = harness.open_cell(harness.parse_args(argv))
    code = harness.attach_device(ctx)
    if code:
        return code
    import jax.numpy as jnp

    cfg, traffic = ctx.config, ctx.traffic
    driver = harness.load_plugin("drivers", traffic["driver"])
    params = ctx.adapter.make_params(cfg, ctx.seed)
    ids = driver.make_pool(cfg, int(traffic["batch"]), int(traffic["seq_len"]), 1, ctx.seed)[0]
    n_seq = int(traffic["sample_sequences"])

    def lower(p, batch_ids):
        return ctx.reference.forward(cfg, p, batch_ids[:n_seq], compute=jnp.bfloat16)

    ok = driver.check(ctx, lower, params, ids, n_seq)
    ctx.log(f"the reference in bf16 comes out {'correct: THE LIMITS ARE TOO WIDE' if ok else 'NOT CORRECT, as it must'}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
