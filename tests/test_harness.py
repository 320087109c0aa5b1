"""Harness tests: triage classification, stdout-contract parsing, CSV schema,
ASCII table, and one real subprocess sweep on the virtual CPU mesh.

Reference analogue: the bash pipeline of scripts/common_test_utils.sh
(classify :96-116, CSV :71-81, table :133-178) and the sweep drivers.
"""

import csv

from cuda_mpi_gpu_cluster_programming_tpu import harness


def test_classify_ok():
    assert harness.classify(0, "anything") == harness.OK


def test_classify_backend_init_failure_is_a_fail():
    # Asked for the TPU and could not initialise it: FAIL, never a warning.
    assert harness.classify(1, "RuntimeError: Unable to initialize backend 'tpu'") == harness.FAIL
    assert harness.classify(1, "RuntimeError: No TPU devices found (libtpu)") == harness.FAIL


def test_classify_mesh_warn():
    text = "ValueError: config 'v2.2_sharded' with 4 shards needs 4 devices, have 1"
    assert harness.classify(2, text) == harness.MESH_WARN


def test_classify_critical():
    assert harness.classify(139, "Segmentation fault (core dumped)") == harness.CRITICAL


def test_classify_generic_fail():
    assert harness.classify(1, "ValueError: something else") == harness.FAIL


def test_classify_startup_chatter_does_not_mask_failure():
    # JAX's benign startup line must not reclassify a later real error.
    text = (
        "INFO: Unable to initialize backend 'tpu': not found\n"
        "Traceback (most recent call last):\n"
        + "  ...\n" * 10
        + "ValueError: actual bug in the run\n"
    )
    assert harness.classify(1, text) == harness.FAIL


def test_classify_timeout_wrapper_kill_is_timeout():
    assert harness.classify(124, "Compile time: 2000.0 ms\n") == harness.TIMEOUT
    assert harness.classify(124, "some unrelated output") == harness.TIMEOUT


def test_parse_run_log_full():
    r = harness.CaseResult("V1 Serial", "v1_jit", 1, 1)
    r.run_status = harness.OK
    text = (
        "Compile time: 812.0 ms\n"
        "Final Output Shape: 13x13x256\n"
        "Final Output (first 10 values): 29.2932 25.9153 23.3255 1.0 2.0 3.0 4.0 5.0 6.0 7.0\n"
        "AlexNet TPU Forward Pass completed in 1.234 ms (amortized over 10 fenced passes; 810.4 img/s)\n"
    )
    harness.parse_run_log(text, r)
    assert r.parse_status == "OK"
    assert r.time_ms == 1.234
    assert r.compile_ms == 812.0
    assert r.shape == "13x13x256"
    assert r.first5.split() == ["29.2932", "25.9153", "23.3255", "1.0", "2.0"]
    assert r.status == harness.OK


def test_plan_hash_parsed_into_csv_row(tmp_path):
    """The run CLI's 'Tune plan:' line lands in the PlanHash CSV column, so
    tuned rows are attributable to one exact plan (docs/TUNING.md)."""
    for verb in ("swept", "cache", "loaded"):
        m = harness._RE_PLAN.search(
            f"Devices: 1 x cpu (cpu)\nTune plan: {verb} hash=0efe8300ae "
            "key=cpu|blocks12_227x227x3|b1|fp32|rev=abc path=perf/tune_plan.json\n"
        )
        assert m and m.group(1) == "0efe8300ae", verb
    session = harness.Session(log_root=tmp_path)
    r = harness.CaseResult("V3 CUDA", "v3_pallas", 1, 1)
    r.run_status = harness.OK
    r.plan_hash = "0efe8300ae"
    session.log_row(r)
    with open(session.csv_path) as f:
        rows = list(csv.reader(f))
    assert rows[1][rows[0].index("PlanHash")] == "0efe8300ae"


def test_parse_run_log_missing_fields_degrade_to_parse_err():
    # Missing fields → ⚠ Parse Error, not failure (common_test_utils.sh:319-324).
    r = harness.CaseResult("V1 Serial", "v1_jit", 1, 1)
    r.run_status = harness.OK
    harness.parse_run_log("no contract lines here", r)
    assert r.parse_status == harness.PARSE_ERR
    assert r.status == harness.PARSE_ERR
    assert "time" in r.parse_msg and "shape" in r.parse_msg


def test_summary_table_renders():
    r = harness.CaseResult("V1 Serial", "v1_jit", 1, 1)
    r.run_status = harness.OK
    r.time_ms = 1.5
    r.shape = "13x13x256"
    r.first5 = "29.2932 25.9153"
    table = harness.summary_table([r])
    assert "┌" in table and "└" in table
    assert "V1 Serial" in table and "13x13x256" in table


def test_session_csv_schema(tmp_path):
    session = harness.Session(log_root=tmp_path)
    r = harness.CaseResult("V1 Serial", "v1_jit", 1, 1)
    r.run_status = harness.OK
    r.time_ms = 2.0
    session.log_row(r)
    with open(session.csv_path) as f:
        rows = list(csv.reader(f))
    assert rows[0] == harness.CSV_COLUMNS
    # The reference's 20-column schema + the 2 resilience attempt-metadata
    # columns + the tuning PlanHash column + the supervisor incident column
    # + the precision Dtype column (each appended, so historical column
    # indexes are untouched).
    assert len(rows[0]) == 25
    assert rows[0][20:] == [
        "Attempts", "ResilienceMsg", "PlanHash", "SupervisorMsg", "Dtype",
    ]
    assert rows[1][4] == "V1 Serial"
    assert rows[1][14] == harness.OK
    assert rows[1][20] == "1"  # single attempt, no retries
    assert rows[1][22] == ""  # untuned row: no plan hash
    assert rows[1][23] == ""  # unsupervised row: no incident trail
    assert rows[1][24] == ""  # no Precision line parsed: pre-policy log


def test_run_case_subprocess_sweep(tmp_path):
    """End-to-end: real subprocess runs of v1_jit and v2.2_sharded (np=2) on
    a tiny image over the virtual CPU mesh — the --oversubscribe analogue."""
    session = harness.Session(log_root=tmp_path)
    extra = ["--height", "63", "--width", "63", "--repeats", "2", "--warmup", "1"]
    r1 = harness.run_case(
        session, "v1_jit", "V1 Serial", 1, 1, timeout_s=240, fake_devices=2, extra_args=extra
    )
    assert r1.status == harness.OK, (r1.run_msg, r1.parse_msg)
    assert r1.shape == "2x2x256"  # 63 -> conv1 14 -> pool1 6 -> conv2 6 -> pool2 2
    r2 = harness.run_case(
        session, "v2.2_sharded", "V2.2 ScatterHalo", 2, 1, timeout_s=240, fake_devices=2, extra_args=extra
    )
    assert r2.status == harness.OK, (r2.run_msg, r2.parse_msg)
    assert r2.shape == "2x2x256"
    # Sharded and single-device runs agree on the contract values (the
    # reference's cross-version first-5 oracle, SURVEY §4.3).
    assert r1.first5 == r2.first5
    # Mesh-starved case triages as a warning, not a failure.
    r3 = harness.run_case(
        session, "v2.2_sharded", "V2.2 ScatterHalo", 4, 1, timeout_s=240, fake_devices=2, extra_args=extra
    )
    assert r3.status == harness.MESH_WARN
    with open(session.csv_path) as f:
        assert len(list(csv.reader(f))) == 4  # header + 3 rows
