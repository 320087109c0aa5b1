"""The four cells' step programs compile for a described v5e:2x2 at their
real sizes: what the chip's compiler would refuse costs no chip time.

One file, the topology described inside a module-scoped fixture and never
at import (on-chip-measurement guide, section 2): only the worker that is
handed this file loads the TPU's compiler.
"""

from __future__ import annotations

import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh, SingleDeviceSharding

REPO = Path(__file__).resolve().parents[2]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from benchmark import harness, trace_reduce  # noqa: E402


@pytest.fixture(scope="module")
def topo():
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here: nothing to check
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def no_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without a chip: keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def _cell(name: str):
    manifest = harness.load_json(REPO / "BENCHMARK.json")
    kept = harness.load_json(REPO / "benchmark" / "cells_kept_for_later.json")
    cell = harness.find_cell({"workloads": manifest["workloads"] + kept["workloads"]}, name)
    cfg = harness.load_config(manifest, cell["config"])
    traffic = harness.load_json(REPO / "benchmark" / "traffic" / f"{cell['traffic']}.json")
    return cfg, traffic, harness.load_plugin("adapters", cfg["family"])


def _shapes(adapter, cfg, batch: int, sharding):
    from benchmark.shapes import alexnet as shapes

    params = {
        name: {
            "w": jax.ShapeDtypeStruct(ws, jnp.float32, sharding=sharding),
            "b": jax.ShapeDtypeStruct(bs, jnp.float32, sharding=sharding),
        }
        for name, (ws, bs) in shapes.param_shapes(cfg).items()
    }
    x = jax.ShapeDtypeStruct(adapter.input_shape(cfg, batch), jnp.float32, sharding=sharding)
    return params, x


@pytest.mark.parametrize(
    "cell,batch",
    [
        ("blocks12_offline", None),
        ("alexnet_full_offline", None),
        ("blocks12_served", 1),
        ("blocks12_served", 32),
    ],
)
def test_one_chip_step_compiles_at_real_size(topo, no_cache, cell, batch):
    cfg, traffic, adapter = _cell(cell)
    batch = batch or int(traffic["batch"])
    one_chip = SingleDeviceSharding(topo.devices[0])
    params, x = _shapes(adapter, cfg, batch, one_chip)
    compiled = adapter.build_forward(cfg).lower(params, x).compile()
    mem = compiled.memory_analysis()
    hbm = json.loads((REPO / "benchmark" / "peaks.json").read_text())["peaks"][0]["hbm_bytes"]
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes + mem.output_size_in_bytes < hbm
    text = compiled.as_text()
    assert "convolution" in text
    # the trace names a convolution only fusion.<n>: the reduction must be
    # able to tell from this text which fusions hold one
    kinds = trace_reduce.fusion_kinds(text)
    n_conv = sum(1 for l in cfg["layers"] if l["kind"] == "conv") + len(cfg["fc"])
    assert sum(1 for k in kinds.values() if k == "convolution") >= n_conv, kinds


def test_four_chip_step_compiles_with_halo_collectives(topo, no_cache, monkeypatch):
    cfg, traffic, adapter = _cell("blocks12_rows4_offline")
    mesh = Mesh(topo.devices[: cfg["n_shards"]], ("sp",))
    # The program builds its mesh from jax.devices(), which is the CPU here:
    # hand it the described chips instead (steered in the test, not by an
    # option of the program).
    from cuda_mpi_gpu_cluster_programming_tpu.parallel import sharded

    monkeypatch.setattr(sharded, "make_mesh", lambda n, axis_name="sp": mesh)
    params, x = _shapes(adapter, cfg, int(traffic["batch"]), None)
    compiled = adapter.build_forward(cfg).lower(params, x).compile()
    text = compiled.as_text()
    assert "collective-permute" in text, "the halo exchange should be a collective-permute"
    assert "convolution" in text
