"""The readings the decoder-hybrid-decoder cell's tolerance lies between
(PERF.md section 4), all in one process so that the float32 reference is
computed once: the program's own step; the plain reference computed in bf16
throughout (the nearest precision below the stated one); the reference with
the scan's state kept in bf16; and the reference with one term dropped
(``reference/sambay.py`` ``FAULTS``: no ``D x``, ``lambda`` taken as 0, a window
one token wider, a cross layer's keys and values made from its own input).
Each is held to the cell's two limits as ``drivers/offline_tokens_dense.py``
computes them: ``rel_max`` = max|got - ref| / max|ref|
over every token, ``rel_rms`` = rms(got - ref) / rms(ref) of the median token.
Every reading but the program's has to come out NOT CORRECT by at least one.

    python3 benchmark/tools/sambay_readings.py --workload <cell> --seed <n>

Run by hand on the chip (or with ``--rehearse`` on the CPU at a tiny size).
One JSON line a reading.
"""

from __future__ import annotations

import json
import sys
import types
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent.parent
sys.path[:] = [str(ROOT)] + [p for p in sys.path if Path(p or ".").resolve() != HERE]


class _Quiet:
    """What ``driver.check`` asks of a context, its log lines kept."""

    def __init__(self, ctx, want):
        self.config, self.counters, self.lines = ctx.config, {}, []
        self.reference = types.SimpleNamespace(forward=lambda *_args: want)

    def log(self, line: str) -> None:
        self.lines.append(line)


def main(argv=None) -> int:
    from benchmark import harness

    ctx = harness.open_cell(harness.parse_args(argv))
    code = harness.attach_device(ctx)
    if code:
        return code
    import jax
    import jax.numpy as jnp
    import numpy as np

    cfg, traffic, ref = ctx.config, ctx.traffic, ctx.reference
    tol = cfg["tolerance"]
    driver = harness.load_plugin("drivers", traffic["driver"])
    params = ctx.adapter.make_params(cfg, ctx.seed)
    ids = driver.make_pool(cfg, int(traffic["batch"]), int(traffic["seq_len"]), 1, ctx.seed)[0]
    ids = ids[: int(traffic["sample_sequences"])]
    quiet = _Quiet(ctx, ref.forward(cfg, params, ids))  # the float32 reference, computed once
    readings = [("program", lambda: np.asarray(ctx.adapter.build_forward(cfg)(params, ids)))]
    readings.append(("reference_bf16_throughout", lambda: ref.forward(cfg, params, ids, compute=jnp.bfloat16)))
    readings += [(f"reference_{fault}", lambda fault=fault: ref.forward(cfg, params, ids, fault=fault)) for fault in ref.FAULTS[1:]]
    all_as_they_must = True
    for name, run in readings:
        driver.check(quiet, lambda _params, _ids, run=run: run(), params, ids, len(ids))  # the cell's own check
        rel_max, rel_rms = quiet.counters["check.rel_err"], quiet.counters["check.rel_rms"]
        fails = [key for key, err in (("rel_max", rel_max), ("rel_rms", rel_rms)) if err > float(tol[key])]
        as_it_must = (not fails) if name == "program" else bool(fails)
        all_as_they_must = all_as_they_must and as_it_must
        print(json.dumps(dict(
            reading=name, seed=ctx.seed, rel_max=rel_max, rel_rms=rel_rms, limits=[tol["rel_max"], tol["rel_rms"]],
            fails=fails, as_it_must=as_it_must, platform=jax.devices()[0].platform,
        )), flush=True)
    ctx.log("every reading came out as it must" if all_as_they_must else "SOME READING CAME OUT OTHERWISE THAN IT MUST")
    return 0


if __name__ == "__main__":
    sys.exit(main())
