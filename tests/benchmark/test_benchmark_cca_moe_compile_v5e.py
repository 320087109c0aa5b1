"""The cell ``zaya1_prefill_s4096``'s step program compiles for a described
v5e at its real size, all 40 layers as one loop, its two kernels (the flash
kernel with 8 query heads over 2 key/value heads, the grouped product over the
stack of every layer's experts) through Mosaic: what the chip's compiler would
refuse (a tile that does not fit VMEM, a model that does not fit the chip)
costs no chip time.

As its siblings ``test_benchmark_{mla,kda}_moe_compile_v5e.py``: the topology
is described inside a module-scoped fixture, never at import, and the fixture
skips where it cannot be described.
"""

from __future__ import annotations

import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

REPO = Path(__file__).resolve().parents[2]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from benchmark import harness, layer_times  # noqa: E402

CELL = "zaya1_prefill_s4096"


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
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def test_prefill_step_compiles_at_real_size_with_its_kernels_and_scopes(topo, no_cache, monkeypatch):
    from cuda_mpi_gpu_cluster_programming_tpu.models import cca_moe, moe_share
    from cuda_mpi_gpu_cluster_programming_tpu.ops import flash_attention, grouped_matmul

    # jax.default_backend() is the CPU here and the kernels would run
    # interpreted: steer them through Mosaic (in the test, not by an option)
    for module in (flash_attention, grouped_matmul):
        monkeypatch.setattr(module, "_interpret", lambda: False)
    manifest = harness.load_json(REPO / "BENCHMARK.json")
    cell = harness.find_cell(manifest, CELL)
    cfg = harness.load_config(manifest, cell["config"])
    traffic = harness.load_json(REPO / "benchmark" / "traffic" / f"{cell['traffic']}.json")
    adapter = harness.load_plugin("adapters", cfg["family"])
    one_chip = SingleDeviceSharding(topo.devices[0])
    params = jax.tree.map(
        lambda leaf: jax.ShapeDtypeStruct(leaf[0], jnp.bfloat16, sharding=one_chip),
        cca_moe.param_shapes(adapter.model_config(cfg)), is_leaf=moe_share._is_leaf,
    )
    ids = jax.ShapeDtypeStruct((int(traffic["batch"]), int(traffic["seq_len"])), jnp.int32, sharding=one_chip)
    compiled = adapter.build_forward(cfg).lower(params, ids).compile()
    mem = compiled.memory_analysis()
    hbm = json.loads((REPO / "benchmark" / "peaks.json").read_text())["peaks"][0]["hbm_bytes"]
    assert mem.argument_size_in_bytes >= 9.0e9  # the weights a deployment holds here
    # two steps' logits may be alive at once in a chain
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes + 2 * mem.output_size_in_bytes < 0.9 * hbm
    text = compiled.as_text()
    # forty layers, compiled once: one flash kernel and three grouped products in the loop's body
    assert text.count('custom_call_target="tpu_custom_call"') == 1 + 3
    assert "flash_fwd" in text and "grouped_matmul" in text
    scopes, _mixed = layer_times.scope_map(text, layer_times.layer_names(cfg))
    assert set(scopes.values()) == set(layer_times.layer_names(cfg))
    # no layer's experts are sliced out of the stack: the kernels read the stack itself
    experts = 40 * 8
    assert f"bf16[{experts},2048,2048]" in text and "bf16[8,2048,2048]" not in text
