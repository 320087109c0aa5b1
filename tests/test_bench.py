"""bench.py contract tests: one parseable JSON line per row, and a non-zero
exit whenever a row measured nothing.

The reference's equivalent contract is the ``... completed in X ms`` stdout
line its harness regex consumes (scripts/common_test_utils.sh:296-297); here
the contract is a single JSON object per row whose schema must stay stable
for whatever parses it.
"""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import bench  # noqa: E402


def test_failed_measurement_exits_nonzero(monkeypatch, capsys):
    """A row without a measurement is an error row AND a non-zero exit —
    never "one JSON line, exit 0" — and it echoes no earlier number."""
    monkeypatch.delenv("BENCH_JOURNAL", raising=False)
    monkeypatch.setenv("BENCH_MAX_RETRIES", "0")
    monkeypatch.setattr(bench, "CONFIGS", ["v1_jit"])
    monkeypatch.setattr(
        bench, "_measure_once",
        lambda configs=None: [bench._error_obj("no chip", "unknown", c) for c in configs],
    )
    assert bench.main() == 1
    row = json.loads(capsys.readouterr().out.splitlines()[-1])
    assert row["value"] == 0.0 and row["error"] == "no chip"
    assert not any("last_good" in k for k in row)


def test_measure_child_refuses_a_platform_nobody_asked_for(monkeypatch):
    """The measuring process accepts the TPU, or the CPU when
    JAX_PLATFORMS=cpu names it — not a CPU it merely fell back to."""
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert bench._platform_refusal("cpu") == ""
    assert bench._platform_refusal("tpu") == ""
    monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")
    assert bench._platform_refusal("tpu") == ""
    assert "not a TPU" in bench._platform_refusal("cpu")
    monkeypatch.delenv("JAX_PLATFORMS")
    assert "not a TPU" in bench._platform_refusal("cpu")


def test_default_batch_is_round_comparable():
    """Advisor (round 3): the default-batch headline must stay comparable
    round-over-round; 256 is opt-in via BENCH_BATCH."""
    assert bench.BATCH == 128 or os.environ.get("BENCH_BATCH")


def test_bench_end_to_end_cpu_schema():
    """Full bench.py subprocess on the CPU backend: asserts the fresh-run
    schema, including the bf16 sub-object and the n/CI timing fields."""
    env = dict(os.environ)
    env.update(
        JAX_PLATFORMS="cpu",
        BENCH_BATCH="4",
        BENCH_REPEATS="3",
        BENCH_TIMEOUT="600",
    )
    res = subprocess.run(
        [sys.executable, os.path.join(ROOT, "bench.py")],
        capture_output=True, text=True, timeout=600, cwd=ROOT, env=env,
    )
    assert res.returncode == 0, res.stderr[-2000:]
    line = next(l for l in reversed(res.stdout.splitlines()) if l.startswith("{"))
    out = json.loads(line)
    assert out["metric"] == bench.METRIC
    assert out["value"] > 0
    assert out["batch"] == 4
    assert out["timing_n"] >= 1 and out["timing_ci95_ms"] >= 0.0
    assert out["timing_shadowed"] in (True, False)
    assert out["timing_underconverged"] in (True, False)
    # CPU: no peak table entry, so MFU fields are null and bf16 is skipped
    # (the sub-object is a TPU-capability statement).
    assert out["mfu"] is None
    assert "bf16" not in out
    # ISSUE 9: measure rows carry the per-stage breakdown at the sentinel
    # tap boundaries, and the stage sum holds the sums-to-total contract
    # against the independently measured per_pass_ms (15% CPU-mesh budget).
    bd = out["breakdown"]
    assert set(bd["stages"]) == {"conv1", "pool1", "conv2", "pool2", "lrn2"}
    assert all(ms >= 0 for ms in bd["stages"].values())
    assert bd["stage_sum_ms"] == pytest.approx(out["per_pass_ms"], rel=0.15)
    assert bd["method"] == "prefix-diff" and bd["batch"] == 4
    # The roofline join rides beside the breakdown on a chip in the spec
    # table; the CPU has no roof to be judged against, so the sub-object
    # says so instead of borrowing an assumed chip's.
    assert "not in the spec table" in out["roofline"]["skipped"]
    assert out["assumed_peak_tflops"] is None


def test_bench_multi_config_sweep_one_row_per_config():
    """BENCH_CONFIGS: one parseable JSON row PER config (the V1->V5 story
    measured), each with the standard schema and its own config key."""
    env = dict(os.environ)
    env.update(
        JAX_PLATFORMS="cpu",
        BENCH_CONFIGS="v1_jit,v3_pallas",
        BENCH_BATCH="2",
        BENCH_REPEATS="2",
        BENCH_TIMEOUT="600",
    )
    res = subprocess.run(
        [sys.executable, os.path.join(ROOT, "bench.py")],
        capture_output=True, text=True, timeout=600, cwd=ROOT, env=env,
    )
    assert res.returncode == 0, res.stderr[-2000:]
    rows = [json.loads(l) for l in res.stdout.splitlines() if l.startswith("{")]
    assert [r["config"] for r in rows] == ["v1_jit", "v3_pallas"]
    for r in rows:
        assert r["metric"] == bench.METRIC
        assert r["value"] > 0 and r["batch"] == 2
        assert r["timing_n"] >= 1
    # ISSUE 9: the reference tier attributes for real; the Pallas tier on
    # CPU degrades to a visible note (interpret-mode staging would
    # attribute tracing overhead, not kernels).
    assert rows[0]["breakdown"]["stage_sum_ms"] > 0
    assert "skipped" in rows[1]["breakdown"]


def test_error_rows_carry_their_config():
    """Multi-config error paths label every row; _error_obj defaults to the
    single-config contract otherwise."""
    assert bench._error_obj("down")["config"] == bench.CONFIG
    assert bench._error_obj("down", config="v3_pallas")["config"] == "v3_pallas"


def _good_row(config):
    return {
        "metric": bench.METRIC, "value": 50.0, "unit": "img/s",
        "vs_baseline": 9.2, "platform": "cpu", "config": config, "batch": 2,
    }


def test_bench_journal_resume_restarts_at_first_missing_config(tmp_path, monkeypatch, capsys):
    """BENCH_JOURNAL: a sweep killed after measuring config A relaunches and
    measures ONLY the missing config B, replaying A's journaled row."""
    journal = tmp_path / "bench_journal.jsonl"
    monkeypatch.setenv("BENCH_JOURNAL", str(journal))
    monkeypatch.setenv("BENCH_MAX_RETRIES", "0")
    monkeypatch.setattr(bench, "CONFIGS", ["v1_jit", "v3_pallas"])
    asked = []

    def fake_measure(configs=None):
        asked.append(list(configs))
        # First invocation: A measures, then the process "dies" before B
        # (B yields an error row, as the salvage path reports).
        rows = []
        for c in configs:
            if c == "v3_pallas" and len(asked) == 1:
                rows.append(bench._error_obj("child died before v3_pallas", "cpu", c))
            else:
                rows.append(_good_row(c))
        return rows

    monkeypatch.setattr(bench, "_measure_once", fake_measure)
    assert bench.main() == 1  # B measured nothing: the run says so
    out1 = [json.loads(l) for l in capsys.readouterr().out.splitlines() if l.startswith("{")]
    assert [r["config"] for r in out1] == ["v1_jit", "v3_pallas"]
    assert out1[0]["value"] > 0 and out1[1].get("error")
    assert asked == [["v1_jit", "v3_pallas"]]

    # Relaunch: only the missing config is measured; A replays from the
    # journal with its originally measured value (modulo attempt metadata).
    assert bench.main() == 0
    out2 = [json.loads(l) for l in capsys.readouterr().out.splitlines() if l.startswith("{")]
    assert asked[1] == ["v3_pallas"]
    assert [r["config"] for r in out2] == ["v1_jit", "v3_pallas"]
    assert out2[0]["value"] == out1[0]["value"]
    assert out2[1]["value"] > 0 and "error" not in out2[1]

    # Third launch: everything journaled — nothing measured at all.
    assert bench.main() == 0
    out3 = [json.loads(l) for l in capsys.readouterr().out.splitlines() if l.startswith("{")]
    assert len(asked) == 2
    assert [r["config"] for r in out3] == ["v1_jit", "v3_pallas"]


def test_bench_no_journal_keeps_historical_contract(monkeypatch, capsys):
    """Without BENCH_JOURNAL nothing is journaled and every config is
    measured every run (the historical contract)."""
    monkeypatch.delenv("BENCH_JOURNAL", raising=False)
    monkeypatch.setenv("BENCH_MAX_RETRIES", "0")
    monkeypatch.setattr(bench, "CONFIGS", ["v1_jit"])
    asked = []

    def fake_measure(configs=None):
        asked.append(list(configs))
        return [_good_row(c) for c in configs]

    monkeypatch.setattr(bench, "_measure_once", fake_measure)
    assert bench.main() == 0
    assert bench.main() == 0
    assert asked == [["v1_jit"], ["v1_jit"]]
    rows = [json.loads(l) for l in capsys.readouterr().out.splitlines() if l.startswith("{")]
    assert all(r["attempts"] == 1 for r in rows)


def test_bench_journal_never_journals_error_rows(tmp_path, monkeypatch, capsys):
    """An error row must NOT be journaled — replaying a value=0.0 row on
    resume would recommit the exact garbage the retry loop exists to
    refuse."""
    journal = tmp_path / "bench_journal.jsonl"
    monkeypatch.setenv("BENCH_JOURNAL", str(journal))
    monkeypatch.setenv("BENCH_MAX_RETRIES", "0")
    monkeypatch.setattr(bench, "CONFIGS", ["v1_jit"])
    monkeypatch.setattr(
        bench, "_measure_once",
        lambda configs=None: [bench._error_obj("down", "cpu", c) for c in configs],
    )
    assert bench.main() == 1
    capsys.readouterr()
    from cuda_mpi_gpu_cluster_programming_tpu.resilience.journal import Journal

    assert Journal.completed(Journal.load(journal), "bench_row") == {}
