"""The contract's rules for ``BENCHMARK.json`` and the files it names, as
functions of a manifest and the root it lies in. They know no model family:
what is particular to AlexNet is tested beside them, by name. The same rules
are run on the repo's manifest (``test_benchmark_manifest.py``) and on a
temporary copy to which cells, configurations with cuts, a traffic mix,
metrics and a second model family were added by new files alone
(``test_benchmark_cells_cpu.py``)."""

from __future__ import annotations

import importlib.util
import json
import re
from pathlib import Path
from typing import Dict, List

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
TOP_LEVEL = {
    "command", "paths", "run_seconds", "configs", "workloads",
    "end_to_end", "per_layer",
}
# ``reduced`` may never name a width
WIDTH = re.compile(r"(_dim|_rank)$|hidden|intermediate|latent|head_size|expansion|experts_per_tok")


def line(text: str, limit: int = 200) -> bool:
    return 1 <= len(text) <= limit and "\n" not in text and "\t" not in text


def plugin(root: Path, kind: str, name: str):
    """``benchmark/<kind>/<name>.py`` under ``root``, loaded by its path."""
    path = root / "benchmark" / kind / f"{name}.py"
    assert path.is_file(), f"no {kind} file for {name!r}: {path}"
    spec = importlib.util.spec_from_file_location(
        f"rules_{kind}_{re.sub(r'[^A-Za-z0-9]', '_', name)}", path
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def cells(manifest: Dict) -> List[str]:
    return [w["name"] for w in manifest["workloads"]]


def metrics_of(manifest: Dict, group: str, cell: str) -> List[Dict]:
    return [m for m in manifest[group] if cell in m.get("workloads", [cell])]


def check_top_level(manifest: Dict, raw: bytes) -> None:
    assert set(manifest) == TOP_LEVEL
    assert 1 <= len(manifest["command"]) <= 32 and all(line(w) for w in manifest["command"])
    assert 1 <= len(manifest["paths"]) <= 16 and all(PATH.match(p) for p in manifest["paths"])
    assert not any(p.startswith("/") or ".." in p.split("/") for p in manifest["paths"])
    assert len(raw) <= 64 * 1024
    assert 1 <= len(manifest["configs"]) <= 24 and 2 <= len(manifest["workloads"]) <= 24
    assert 1 <= len(manifest["end_to_end"]) <= 16 and 1 <= len(manifest["per_layer"]) <= 128


def check_run_seconds(manifest: Dict) -> None:
    """A whole number that lets a full check of 24 cells fit its limit."""
    rs = manifest["run_seconds"]
    assert isinstance(rs, int) and 1 <= rs <= 51
    assert (2 + 14 * 24) * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200


def check_names_unique(manifest: Dict) -> None:
    for group in ("configs", "workloads"):
        names = [e["name"] for e in manifest[group]]
        assert len(names) == len(set(names)), group
    names = [m["name"] for m in manifest["end_to_end"] + manifest["per_layer"]]
    assert len(names) == len(set(names))
    files = [c["file"] for c in manifest["configs"]]
    assert len(files) == len(set(files))
    pairs = [(w["config"], w["traffic"]) for w in manifest["workloads"]]
    assert len(pairs) == len(set(pairs))


def check_four_chip_cap(manifest: Dict) -> None:
    four = [w for w in manifest["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(manifest["workloads"]) // 4)


def check_config(manifest: Dict, root: Path, cfg: Dict) -> None:
    """A configuration's entry and its file, whatever its family."""
    assert set(cfg) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(cfg["name"]) and line(cfg["source"]) and line(cfg["why"])
    assert any(cfg["file"].startswith(p + "/") for p in manifest["paths"]), cfg["file"]
    assert (root / cfg["file"]).is_file()
    assert any(w["config"] == cfg["name"] for w in manifest["workloads"])
    assert len(cfg["reduced"]) <= 16
    for key in cfg["reduced"]:
        assert NAME.match(key) and not WIDTH.search(key), key
    body = json.loads((root / cfg["file"]).read_text())
    assert body["reduced"] == cfg["reduced"]  # the file says what the entry says
    assert body["chips"] in (1, 4) and line(body["source"])
    # the comparison that decides ``correct`` reads these
    assert body["tolerance"]["rel_max"] > 0 and body["tolerance"]["why"]
    for kind in ("adapters", "reference", "shapes"):
        assert (root / "benchmark" / kind / f"{body['family']}.py").is_file()
    if "baseline_config" in body:
        assert any(c["name"] == body["baseline_config"] for c in manifest["configs"])


def check_cell(manifest: Dict, root: Path, cell: Dict) -> None:
    """A cell's entry, and every file it names found by that name."""
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert NAME.match(cell["name"]) and NAME.match(cell["traffic"]) and line(cell["why"])
    assert cell["chips"] in (1, 4)
    entry = next(c for c in manifest["configs"] if c["name"] == cell["config"])
    config = json.loads((root / entry["file"]).read_text())
    assert config["chips"] == cell["chips"]
    traffic = json.loads(
        (root / "benchmark" / "traffic" / f"{cell['traffic']}.json").read_text()
    )
    assert hasattr(plugin(root, "drivers", traffic["driver"]), "run")


def check_cell_reports(manifest: Dict, cell: str) -> None:
    """``setup_s``, another end-to-end metric and a per-layer metric; a
    per-layer metric only where the metric it moves is."""
    e2e = [m["name"] for m in metrics_of(manifest, "end_to_end", cell)]
    assert "setup_s" in e2e and len(e2e) >= 2
    layer = metrics_of(manifest, "per_layer", cell)
    assert layer
    for m in layer:
        assert m["moves"] in e2e, (m["name"], m["moves"], cell)


def check_metric_common(manifest: Dict, metric: Dict) -> None:
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert set(metric.get("workloads", [])) <= set(cells(manifest))
    if metric["name"].endswith("_roofline"):
        assert metric["unit"] == "%"


def check_end_to_end_metric(manifest: Dict, metric: Dict) -> None:
    assert set(metric) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
    check_metric_common(manifest, metric)
    assert metric["source"] in ("host_clock", "device_trace")
    assert 0.01 <= metric["bound"] <= 0.1


def check_per_layer_metric(manifest: Dict, root: Path, metric: Dict) -> None:
    assert set(metric) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"}
    check_metric_common(manifest, metric)
    assert metric["source"] in SOURCES and line(metric["layer"])
    assert metric["moves"] in {m["name"] for m in manifest["end_to_end"]}
    assert callable(plugin(root, "layer_metrics", metric["name"]).read)


def check_all(manifest: Dict, root: Path) -> None:
    """Every rule on every entry."""
    check_top_level(manifest, (root / "BENCHMARK.json").read_bytes())
    check_run_seconds(manifest)
    check_names_unique(manifest)
    check_four_chip_cap(manifest)
    assert "setup_s" in {m["name"] for m in manifest["end_to_end"]}
    for cfg in manifest["configs"]:
        check_config(manifest, root, cfg)
    for cell in manifest["workloads"]:
        check_cell(manifest, root, cell)
        check_cell_reports(manifest, cell["name"])
    for metric in manifest["end_to_end"]:
        check_end_to_end_metric(manifest, metric)
    for metric in manifest["per_layer"]:
        check_per_layer_metric(manifest, root, metric)
