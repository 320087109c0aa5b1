"""BENCHMARK.json against the contract it is checked by, and every file a
cell names found by that name. The rules themselves are in
``manifest_rules.py`` and know no model family; what belongs to AlexNet is
tested here for the three AlexNet configurations by name."""

from __future__ import annotations

import json
import re
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))
sys.path.insert(0, str(Path(__file__).resolve().parent))

import manifest_rules as rules  # noqa: E402
from benchmark import harness  # noqa: E402

MANIFEST = json.loads((REPO / "BENCHMARK.json").read_text())
BENCH = REPO / "benchmark"
CELLS = rules.cells(MANIFEST)
METRICS = MANIFEST["end_to_end"] + MANIFEST["per_layer"]
ALEXNET_CONFIGS = ["alexnet_blocks12", "alexnet_full", "alexnet_blocks12_rows4"]


def test_top_level_keys_and_command():
    rules.check_top_level(MANIFEST, (REPO / "BENCHMARK.json").read_bytes())
    assert MANIFEST["command"] == ["python3", "benchmark/run.py"]
    assert MANIFEST["paths"] == ["benchmark", "tests/benchmark"]
    assert all((REPO / p).is_dir() for p in MANIFEST["paths"])


def test_run_seconds_fits_a_full_check_of_24_cells():
    rules.check_run_seconds(MANIFEST)


@pytest.mark.parametrize("cfg", MANIFEST["configs"], ids=lambda c: c["name"])
def test_configuration_entry_and_file(cfg):
    rules.check_config(MANIFEST, REPO, cfg)


@pytest.mark.parametrize("name", ALEXNET_CONFIGS)
def test_alexnet_configuration_holds_the_published_widths(name):
    cfg = next(c for c in MANIFEST["configs"] if c["name"] == name)
    body = json.loads((REPO / cfg["file"]).read_text())
    assert body["family"] == "alexnet" and body["reduced"] == []  # nothing is cut
    # Krizhevsky et al. 2012
    convs = [l for l in body["layers"] if l["kind"] == "conv"]
    assert [(c["out_channels"], c["filter_size"]) for c in convs][:2] == [(96, 11), (256, 5)]
    assert body["fc"] in ([], [4096, 4096, 1000])
    assert (body["in_height"], body["in_width"], body["in_channels"]) == (227, 227, 3)
    assert body["tolerance"]["rel_max"] <= 0.02


def test_names_are_unique():
    rules.check_names_unique(MANIFEST)


KEPT = json.loads((BENCH / "cells_kept_for_later.json").read_text())


@pytest.mark.parametrize("metric", KEPT["end_to_end"] + KEPT["per_layer"], ids=lambda m: m["name"])
def test_entries_kept_for_later_are_well_formed_and_have_their_files(metric):
    assert rules.NAME.match(metric["name"]) and rules.UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher") and metric["source"] in rules.SOURCES
    assert set(metric["workloads"]) <= {w["name"] for w in KEPT["workloads"]}
    assert metric["name"] not in {m["name"] for m in METRICS}
    if "layer" in metric:
        assert callable(harness.load_plugin("layer_metrics", metric["name"]).read)
        assert metric["moves"] in {m["name"] for m in KEPT["end_to_end"]}


def test_cell_kept_for_later_names_files_that_exist():
    for cell in KEPT["workloads"]:
        assert cell["name"] not in CELLS and rules.line(cell["why"])
        assert any(c["name"] == cell["config"] for c in MANIFEST["configs"])
        traffic = json.loads((BENCH / "traffic" / f"{cell['traffic']}.json").read_text())
        assert hasattr(harness.load_plugin("drivers", traffic["driver"]), "run")


def test_four_chip_cells_within_the_cap():
    rules.check_four_chip_cap(MANIFEST)


@pytest.mark.parametrize("cell", MANIFEST["workloads"], ids=lambda w: w["name"])
def test_cell_entry_and_its_files_are_found_by_name(cell):
    rules.check_cell(MANIFEST, REPO, cell)
    # the harness finds them by the same names
    ctx_cfg = harness.load_config(MANIFEST, cell["config"])
    for kind in ("adapters", "reference", "shapes"):
        assert harness.load_plugin(kind, ctx_cfg["family"])


@pytest.mark.parametrize("cell", CELLS)
def test_cell_reports_setup_another_end_to_end_and_a_per_layer_metric(cell):
    rules.check_cell_reports(MANIFEST, cell)
    for group in ("end_to_end", "per_layer"):  # the harness picks the same metrics
        assert harness.metrics_for(MANIFEST, group, cell) == rules.metrics_of(MANIFEST, group, cell)


@pytest.mark.parametrize("metric", MANIFEST["end_to_end"], ids=lambda m: m["name"])
def test_end_to_end_metric_entry(metric):
    rules.check_end_to_end_metric(MANIFEST, metric)


@pytest.mark.parametrize("metric", MANIFEST["per_layer"], ids=lambda m: m["name"])
def test_per_layer_metric_entry_and_its_reader(metric):
    rules.check_per_layer_metric(MANIFEST, REPO, metric)
    assert callable(harness.load_plugin("layer_metrics", metric["name"]).read)


def test_layer_names_are_those_of_perf_md():
    perf = (REPO / "PERF.md").read_text()
    for layer in {m["layer"] for m in MANIFEST["per_layer"]}:
        assert layer in perf, f"PERF.md does not list the layer {layer!r}"


def test_files_under_paths_use_only_a_name_s_characters():
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for base in MANIFEST["paths"]:
        for path in (REPO / base).rglob("*"):
            rel = path.relative_to(REPO).as_posix()
            if "__pycache__" in rel:
                continue
            assert ok.match(rel), rel


def test_unknown_device_kind_has_no_peak():
    assert harness.peak_row("TPU v5 lite")["bf16_tflops"] == 197.0
    with pytest.raises(harness.BenchmarkError):
        harness.peak_row("TPU v9 imaginary")
