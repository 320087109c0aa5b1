"""Unit tests for the conv A/B log summarizer (scripts/conv_ab_report.py)."""

import importlib.util
import sys
from pathlib import Path

spec = importlib.util.spec_from_file_location(
    "conv_ab_report", Path(__file__).parent.parent / "scripts" / "conv_ab_report.py"
)
mod = importlib.util.module_from_spec(spec)
sys.modules["conv_ab_report"] = mod
spec.loader.exec_module(mod)

_PASS = "AlexNet TPU Forward Pass completed in"
SAMPLE = f"""\
=== conv variant A/B on the real chip
conv=taps rb=8 kb=0 bf16 {_PASS} 5.800 ms (amortized over 100 fenced passes; 22068.9 img/s)
conv=taps rb=8 kb=0 fp32 {_PASS} 15.100 ms (amortized over 100 fenced passes; 8476.8 img/s)
conv=pairs rb=16 kb=0 bf16 {_PASS} 2.100 ms (amortized over 100 fenced passes; 60952.4 img/s)
fuse=hpool conv=vcol rb=64 kb=0 bf16 {_PASS} 2.500 ms (amortized over 100 fenced passes; 51200.0 img/s)
unrelated line
"""


def test_parse_extracts_combo_rows():
    rows = mod.parse(SAMPLE)
    assert len(rows) == 4
    assert rows[0] == {
        "conv": "taps", "rowblock": 8, "kblock": 0, "fuse": "none",
        "compute": "bf16", "ms": 5.8, "img_per_sec": 22068.9,
    }
    assert rows[2]["conv"] == "pairs" and rows[2]["rowblock"] == 16
    # The round-5 hpool A/B rows carry a fuse= prefix.
    assert rows[3]["fuse"] == "hpool" and rows[3]["conv"] == "vcol"


def test_report_ranks_and_judges_bar():
    rows = mod.parse(SAMPLE)
    text = mod.report(rows, {"bf16": 102461.8, "fp32": 21668.3})
    # Ranked: pairs (60952) above taps (22068) within bf16.
    assert text.index("| pairs | 16 |") < text.index("| taps | 8 | 0 | none | bf16")
    # 60952/102462 = 0.59x -> bar met.
    assert "BAR MET" in text
    assert "0.59x" in text


def test_report_bar_not_met():
    rows = mod.parse(SAMPLE.replace("60952.4", "30000.0"))
    text = mod.report(rows, {"bf16": 102461.8})
    assert "bar NOT met" in text


def test_report_without_reference_is_na():
    rows = mod.parse(SAMPLE)
    text = mod.report(rows, {})
    assert "n/a" in text and "BAR" not in text


def test_v1_reference_rejects_mismatched_baseline(tmp_path, monkeypatch):
    """A bench_latest captured under a different config or batch must not
    become the bar's denominator (review finding: the capture's config and
    batch were environment-driven, so the committed headline isn't
    guaranteed to be v1_jit b=128)."""
    import json
    perf = tmp_path / "perf"
    perf.mkdir()
    monkeypatch.setattr(mod, "ROOT", tmp_path)
    good = {"config": "v1_jit", "batch": 128, "compute": "fp32",
            "value": 21668.3, "bf16": {"value": 102461.8}}
    perf.joinpath("bench_latest.json").write_text(json.dumps(good))
    assert mod.v1_reference() == {"fp32": 21668.3, "bf16": 102461.8}
    for bad in ({**good, "config": "v3_pallas"}, {**good, "batch": 256}):
        perf.joinpath("bench_latest.json").write_text(json.dumps(bad))
        assert mod.v1_reference() == {}


def test_v3_layer_ab_script_smoke():
    """scripts/v3_layer_ab.py (per-layer Pallas-vs-XLA attribution, run by
    the heal queue) emits its table on the CPU backend — guards the import
    path, the amortized_stats wiring, and the stage list."""
    import subprocess
    import sys
    from pathlib import Path

    from cuda_mpi_gpu_cluster_programming_tpu.utils.env_info import (
        cpu_subprocess_env)

    root = Path(__file__).parent.parent
    out = subprocess.run(
        [sys.executable, str(root / "scripts" / "v3_layer_ab.py"),
         "--batch", "2", "--repeats", "2"],
        capture_output=True, text=True, timeout=600, cwd=root,
        env=cpu_subprocess_env(1),
    )
    assert out.returncode == 0, out.stderr[-800:]
    for stage in ("conv1+relu", "pool1", "conv2+relu", "pool2", "lrn2", "TOTAL"):
        assert stage in out.stdout, (stage, out.stdout[-400:])
