"""The benchmark's one command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of ``BENCHMARK.json`` on the machine it is started on and
prints, as the last line of its standard output, one JSON object with the
keys ``correct``, ``attempted``, ``failed``, ``metrics`` and ``device`` (and
``breakdown`` with ``--trace 1``). With ``--trace 0`` the metrics are the
cell's end-to-end metrics; with ``--trace 1`` its per-layer metrics, read
from a short profiled window.

Everything that belongs to one cell is data or a small file found by name:

    BENCHMARK.json           the cells, configurations and metrics
    configs/<config>.json    one configuration (``family`` names its adapter,
                             reference and shape functions)
    traffic/<traffic>.json   one traffic mix (``driver`` names its driver)
    drivers/<driver>.py      ``run(ctx) -> {"attempted", "failed", "correct",
                             "values": {end-to-end metric: value}}``
    adapters/<family>.py     the way into the program
    reference/<family>.py    the plain float32 forward
    shapes/<family>.py       operations and bytes
    layer_metrics/<name>.py  ``read(ctx) -> value or None``

It exits with a code other than 0, and prints no result, when JAX finds no
TPU or fewer chips than the cell asks for. ``--rehearse`` (with
``JAX_PLATFORMS=cpu`` in the environment) runs the same control flow on the
CPU to find faults: its last line names the CPU under ``device``, carries no
metric, and shows what it computed under ``rehearsal``.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()  # set-up is counted from here

import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
# ``benchmark`` and the program are imported as packages from the checkout's
# root; the script's own directory must not shadow top-level names.
sys.path[:] = [str(HERE.parent)] + [
    p for p in sys.path if Path(p or ".").resolve() != HERE
]

from benchmark import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(t_start=T_START))
