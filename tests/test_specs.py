"""The benchmark's copy of the v5e peaks equals the program's spec table.

``benchmark/peaks.json`` was copied from ``observability/specs.py`` (PR 22)
so that the benchmark imports nothing of the program's observability; the
rooflines in ``PERF.md`` and the ``roofline --live`` verdicts must not
disagree about what the chip can do.
"""

import json
from pathlib import Path

import pytest

from cuda_mpi_gpu_cluster_programming_tpu.observability import specs

ROOT = Path(__file__).resolve().parent.parent
KIND = "TPU v5 lite"  # what jax reports for a v5e


def _copied_row() -> dict:
    rows = json.loads((ROOT / "benchmark" / "peaks.json").read_text())["peaks"]
    (row,) = [r for r in rows if r["match"] in KIND.lower()]
    return row


@pytest.mark.parametrize(
    "field,from_table",
    [
        ("bf16_tflops", lambda: specs.peak_tflops(KIND, "bf16")),
        ("hbm_gbps", lambda: specs.hbm_gbps(KIND)),
        # bf16 / 6, to the four places the file keeps
        ("fp32_tflops", lambda: round(specs.peak_tflops(KIND, "fp32"), 4)),
    ],
    ids=["bf16_tflops", "hbm_gbps", "fp32_tflops"],
)
def test_benchmark_peaks_equal_the_spec_table(field, from_table):
    row = _copied_row()
    assert row[field] == from_table()
    assert row["name"] == specs.spec_for(KIND).name
