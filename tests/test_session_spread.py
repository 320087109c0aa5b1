"""scripts/session_spread.py: the work-floor protocol's acceptance check.

Validates the comparison logic off-chip (the real input is two TPU
sessions): common-cell matching, the sub-3 ms bar, the exit code
contract, and the real-backend session filter that keeps
--fake-devices smoke sessions out of the auto-selection.
"""

import csv
import importlib.util
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent

_spec = importlib.util.spec_from_file_location(
    "session_spread", ROOT / "scripts" / "session_spread.py"
)
session_spread = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(session_spread)


def write_session(root: Path, name: str, cells, backend: str = "tpu") -> Path:
    """cells: list of (variant, config, np, batch, status, time_ms)."""
    d = root / name
    d.mkdir(parents=True)
    cols = [
        "SessionID", "MachineID", "GitCommit", "Timestamp", "Variant",
        "ConfigKey", "NP", "Batch", "BuildStatus", "BuildMsg", "RunStatus",
        "RunMsg", "ParseStatus", "ParseMsg", "Status", "ExecutionTime_ms",
        "Compile_ms", "OutputShape", "First5Values", "LogFile",
    ]
    with open(d / "summary.csv", "w", newline="") as f:
        w = csv.DictWriter(f, fieldnames=cols)
        w.writeheader()
        for variant, config, np_, batch, status, ms in cells:
            w.writerow({
                "SessionID": name, "Variant": variant, "ConfigKey": config,
                "NP": np_, "Batch": batch, "Status": status,
                "ExecutionTime_ms": "" if ms is None else f"{ms:.3f}",
            })
    (d / "run_case.log").write_text(
        f"$ cmd\nDevices: 1 x TPU v5 lite ({backend})\nFinal Output Shape: x\n"
    )
    return d


def run_main(args, capsys):
    rc = session_spread.main(args)
    return rc, capsys.readouterr().out


def test_pass_within_bar(tmp_path, capsys):
    a = write_session(tmp_path, "bench_a", [("V1", "v1_jit", "1", "32", "OK", 1.00)])
    b = write_session(tmp_path, "bench_b", [("V1", "v1_jit", "1", "32", "OK", 1.05)])
    rc, out = run_main(["--sessions", str(a), str(b)], capsys)
    assert rc == 0
    assert "PASS" in out and "4.9%" in out


def test_fail_over_bar_only_for_sub3ms_cells(tmp_path, capsys):
    # 40% spread on a 10 ms cell is NOT a failure (the claim is about the
    # sub-3 ms rows); 40% on a 1 ms cell is.
    a = write_session(tmp_path, "bench_a", [
        ("V1", "v1_jit", "1", "128", "OK", 10.0),
        ("V3", "v3_pallas", "1", "1", "OK", 1.0),
    ])
    b = write_session(tmp_path, "bench_b", [
        ("V1", "v1_jit", "1", "128", "OK", 15.0),
        ("V3", "v3_pallas", "1", "1", "OK", 1.5),
    ])
    rc, out = run_main(["--sessions", str(a), str(b)], capsys)
    assert rc == 1
    assert "FAIL: V3 np=1 b=1" in out and "V1" not in out.split("FAIL:")[1]


def test_only_common_ok_cells_compared(tmp_path, capsys):
    a = write_session(tmp_path, "bench_a", [
        ("V1", "v1_jit", "1", "32", "OK", 5.0),
        ("V3", "v3_pallas", "1", "32", "TIMEOUT", None),
    ])
    b = write_session(tmp_path, "bench_b", [
        ("V1", "v1_jit", "1", "32", "OK", 5.0),
        ("V3", "v3_pallas", "1", "32", "OK", 5.0),
    ])
    rc, out = run_main(["--sessions", str(a), str(b)], capsys)
    assert rc == 0
    assert "(1 common cells)" in out


def test_auto_selection_skips_cpu_sessions(tmp_path, capsys):
    """A --fake-devices smoke session (Devices banner '(cpu)') between heal
    windows must not be auto-compared against a TPU session."""
    write_session(tmp_path, "bench_1_tpu", [("V1", "v1_jit", "1", "32", "OK", 1.0)])
    write_session(tmp_path, "bench_2_tpu", [("V1", "v1_jit", "1", "32", "OK", 1.0)])
    cpu = write_session(
        tmp_path, "bench_3_cpu", [("V1", "v1_jit", "1", "32", "OK", 400.0)],
        backend="cpu",
    )
    # Make the cpu session the newest — mtime-ordered selection would pick it.
    import os
    import time
    now = time.time()
    os.utime(cpu, (now + 60, now + 60))
    rc, out = run_main(["--logs", str(tmp_path)], capsys)
    assert rc == 0
    assert "bench_1_tpu" in out and "bench_2_tpu" in out and "cpu" not in out


def test_fewer_than_two_real_sessions_is_a_noop(tmp_path, capsys):
    write_session(tmp_path, "bench_only", [("V1", "v1_jit", "1", "32", "OK", 1.0)])
    rc, out = run_main(["--logs", str(tmp_path)], capsys)
    assert rc == 0
    assert "nothing to compare" in out


def test_out_persists_json_and_defaults_off(tmp_path, capsys):
    """--out writes the machine-readable comparison the narrative quotes;
    the default is OFF so test/ad-hoc invocations cannot clobber the
    canonical perf/session_spread_latest.json (review finding)."""
    import json
    write_session(tmp_path, "bench_1_tpu", [("V1", "v1_jit", "1", "1", "OK", 0.2)])
    write_session(tmp_path, "bench_2_tpu", [("V1", "v1_jit", "1", "1", "OK", 0.5)])
    out = tmp_path / "spread.json"
    rc, _ = run_main(["--logs", str(tmp_path), "--out", str(out)], capsys)
    assert rc == 1  # 0.2 vs 0.5 ms: sub-3ms spread way over the bar
    d = json.loads(out.read_text())
    assert d["sessions"] == ["bench_1_tpu", "bench_2_tpu"]
    assert d["failed_cells"] == ["V1 np=1 b=1"]
    assert d["cells"][0]["batch"] == 1 and d["cells"][0]["sub3ms"] is True
    assert 0.85 < d["worst_sub3ms_spread"] < 0.86
    # default: no file appears anywhere
    before = set(Path.cwd().rglob("session_spread_latest.json"))
    rc, _ = run_main(["--logs", str(tmp_path)], capsys)
    assert set(Path.cwd().rglob("session_spread_latest.json")) == before


# keep the module import honest if pytest reruns within one process
sys.modules.setdefault("session_spread", session_spread)
