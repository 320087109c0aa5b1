"""Every cell runs end to end on the CPU at a tiny size and prints the last
line the contract asks for, with no device metric; and a cell, a traffic
mix, a per-layer metric, configurations with cuts and a second model family
are each added by new files and new entries alone, after which the
manifest's rules still hold. The real cells' files are copied, never edited."""

from __future__ import annotations

import json
import subprocess
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))

import bench_tiny  # noqa: E402
import manifest_rules  # noqa: E402

CONTRACT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    root = tmp_path_factory.mktemp("bench")
    bench_tiny.copy_benchmark(root)
    before = {
        p.relative_to(root): p.read_bytes()
        for p in root.rglob("*") if p.is_file() and p.name != "BENCHMARK.json"
    }
    bench_tiny.add_tiny_cells(root)
    # a new per-layer metric: one reader file and one entry
    (root / "benchmark" / "layer_metrics" / "dummy.chains.py").write_text(
        "def read(ctx):\n    return float(len(ctx.spans.get('bench.chain', [])))\n"
    )
    (root / "benchmark" / "layer_metrics" / "dummy.nothing.py").write_text(
        "def read(ctx):\n    return None\n"
    )
    manifest = json.loads((root / "BENCHMARK.json").read_text())
    for name in ("dummy.chains", "dummy.nothing"):
        manifest["per_layer"].append({
            "name": name, "unit": "n", "better": "higher", "source": "program_span",
            "layer": "load generator", "moves": "images_per_s", "workloads": ["tiny_offline"],
        })
    (root / "BENCHMARK.json").write_text(json.dumps(manifest))
    add_toy_family(root)
    for rel, content in before.items():  # nothing that was there was edited
        assert (root / rel).read_bytes() == content
    return root


TOY_ADAPTER = '''
import jax, jax.numpy as jnp

def make_params(cfg, seed):
    keys = jax.random.split(jax.random.key(seed), cfg["depth"])
    return [jax.random.normal(k, (cfg["width"], cfg["width"]), jnp.float32) / cfg["width"] ** 0.5 for k in keys]

def input_shape(cfg, batch):
    return (batch, cfg["width"])

def build_forward(cfg):
    from benchmark.reference import toy_mlp
    return jax.jit(lambda params, x: toy_mlp.forward(cfg, params, x))
'''
TOY_REFERENCE = '''
import jax.numpy as jnp

def forward(cfg, params, x):
    for w in params:
        x = jnp.maximum(jnp.dot(x, w, precision="highest"), 0.0)
    return x
'''
TOY_SHAPES = '''
def matmul_flops_per_image(cfg):
    return 2 * cfg["depth"] * cfg["width"] ** 2

def min_bytes_per_step(cfg, batch):
    return 4 * (2 * batch * cfg["width"] + cfg["depth"] * cfg["width"] ** 2)
'''


def add_toy_family(root) -> None:
    """A model family that is not AlexNet: its adapter, reference and shape
    files, a configuration whose keys are its own and that lists a cut, and
    a cell over a traffic mix that is there."""
    bench = root / "benchmark"
    for kind, text in (("adapters", TOY_ADAPTER), ("reference", TOY_REFERENCE), ("shapes", TOY_SHAPES)):
        (bench / kind / "toy_mlp.py").write_text(text)
    (bench / "configs" / "toy_mlp.json").write_text(json.dumps({
        "family": "toy_mlp", "compute": "fp32", "chips": 1, "width": 16, "depth": 2,
        "source": "no paper: a stand-in for a later family", "reduced": ["depth"],
        "tolerance": {"rel_max": 1e-3, "why": "float32 against float32"},
    }))
    manifest = json.loads((root / "BENCHMARK.json").read_text())
    manifest["configs"].append({
        "name": "toy_mlp", "source": "no paper: a stand-in for a later family",
        "file": "benchmark/configs/toy_mlp.json", "reduced": ["depth"],
        "why": "a second family, to show that a configuration is data",
    })
    manifest["workloads"].append({
        "name": "toy_mlp_offline", "config": "toy_mlp", "traffic": "tiny_offline_b4",
        "chips": 1, "why": "a family the harness has never seen, through the offline driver",
    })
    (root / "BENCHMARK.json").write_text(json.dumps(manifest))


def _last_line(proc) -> dict:
    assert proc.returncode == 0, proc.stderr[-2000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("real", sorted(bench_tiny.TINY))
def test_cell_runs_on_the_cpu_and_reports_no_device_metric(copy, real, trace):
    cell = bench_tiny.TINY[real][0]
    line = _last_line(bench_tiny.run_cell(copy, cell, "--rehearse", trace=trace))
    assert CONTRACT_KEYS <= set(line) <= CONTRACT_KEYS | {"rehearsal"}
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert line["metrics"] == {}  # a CPU number never stands under a device metric's name
    assert line["device"]["platform"] == "cpu" and "breakdown" not in line
    manifest = json.loads((copy / "BENCHMARK.json").read_text())
    group = "per_layer" if trace else "end_to_end"
    allowed = {
        m["name"] for m in manifest[group]
        if "workloads" not in m or cell in m["workloads"]
    }
    assert set(line["rehearsal"]) <= allowed
    if trace:
        assert "build.compile_s" in line["rehearsal"]
    else:
        assert set(line["rehearsal"]) == allowed  # every end-to-end metric of the cell


def test_manifest_rules_hold_for_the_extended_copy(copy):
    manifest = json.loads((copy / "BENCHMARK.json").read_text())
    assert {c["name"] for c in manifest["configs"]} >= {"tiny_blocks12", "toy_mlp"}
    assert any(c["reduced"] for c in manifest["configs"])  # configurations with cuts
    manifest_rules.check_all(manifest, copy)


@pytest.mark.parametrize("trace", [0, 1])
def test_a_second_model_family_is_three_files_a_configuration_and_a_cell(copy, trace):
    line = _last_line(bench_tiny.run_cell(copy, "toy_mlp_offline", "--rehearse", trace=trace))
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert ("build.compile_s" if trace else "images_per_s") in line["rehearsal"]


def test_new_per_layer_metric_is_read_and_an_empty_one_is_left_out(copy):
    line = _last_line(bench_tiny.run_cell(copy, "tiny_offline", "--rehearse", trace=1))
    assert line["rehearsal"]["dummy.chains"] > 0
    assert "dummy.nothing" not in line["rehearsal"]


def test_same_seed_same_check_error_other_seed_other_inputs(copy):
    def err(seed):
        out = bench_tiny.run_cell(copy, "tiny_offline", "--rehearse", seed=seed).stdout
        return next(l for l in out.splitlines() if "max|diff|/max|ref|" in l).split("=")[1].split()[0]

    assert err(3) == err(3) and err(3) != err(4)


def test_without_an_accelerator_it_exits_nonzero_and_prints_no_result(copy):
    proc = bench_tiny.run_cell(copy, "tiny_offline")  # no --rehearse
    assert proc.returncode != 0 and proc.stdout.strip() == ""


def test_four_chip_cell_refuses_fewer_devices(copy):
    proc = bench_tiny.run_cell(
        copy, "tiny_rows4_offline", "--rehearse",
        env={"XLA_FLAGS": "--xla_force_host_platform_device_count=2"},
    )
    assert proc.returncode != 0 and proc.stdout.strip() == ""


def test_unknown_cell_exits_nonzero(copy):
    proc = bench_tiny.run_cell(copy, "no_such_cell", "--rehearse")
    assert proc.returncode != 0 and proc.stdout.strip() == ""


def test_only_the_benchmark_s_files_is_not_enough(copy):
    # BENCHMARK.json and the files under paths alone: no program to measure
    proc = bench_tiny.run_cell(copy, "tiny_offline", "--rehearse", env={"PYTHONPATH": ""})
    assert proc.returncode != 0
    assert not any(l.startswith("{") for l in proc.stdout.splitlines())


def test_find_knee_sweeps_a_served_cell(copy):
    import os

    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(bench_tiny.REPO),
               JAX_COMPILATION_CACHE_DIR=str(copy / ".xla_cache"))
    proc = subprocess.run(
        [*bench_tiny.on_two_cores(), sys.executable, "benchmark/tools/find_knee.py", "--workload", "tiny_served",
         "--rates", "20,40", "--seconds", "0.5", "--rehearse"],
        cwd=copy, env=env, capture_output=True, text=True, timeout=600,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    rows = [l for l in proc.stdout.splitlines() if l.startswith("| ")]
    assert len(rows) == 2 and "knee:" in proc.stdout
