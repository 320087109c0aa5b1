"""Doc-collector tests (ref H14: collect_project.sh / collect_p_docs.sh)."""

import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
SCRIPT = ROOT / "scripts" / "collect_docs.py"


def _run(args, tmp_path):
    out = tmp_path / "project.txt"
    proc = subprocess.run(
        [sys.executable, str(SCRIPT), *args, "--out", str(out)],
        capture_output=True,
        text=True,
        cwd=ROOT,
        timeout=60,
    )
    assert proc.returncode == 0, proc.stderr
    return out.read_text()


def test_collect_all(tmp_path):
    text = _run([], tmp_path)
    assert "## Table of contents" in text
    # Curated areas all present, fenced with path headers.
    for marker in (
        "=== README.md",
        "=== cuda_mpi_gpu_cluster_programming_tpu/ops/pallas_kernels.py",
        "=== cuda_mpi_gpu_cluster_programming_tpu/parallel/sharded.py",
        "=== __graft_entry__.py",
    ):
        assert marker in text, marker


def test_collect_area_subset(tmp_path):
    text = _run(["ops"], tmp_path)
    assert "=== cuda_mpi_gpu_cluster_programming_tpu/ops/pallas_kernels.py" in text
    assert "=== tests/" not in text


def test_docs_only(tmp_path):
    text = _run(["--docs-only"], tmp_path)
    assert "=== README.md" in text
    assert ".py" not in text.split("Table of contents")[1].split("Total:")[0]
