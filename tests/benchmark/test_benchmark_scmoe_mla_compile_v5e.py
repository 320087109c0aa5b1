"""The cell ``longcat_flash_prefill_s4096``'s step program compiles for a
described v5e at its real size, its three kernels (the flash kernel at 64
heads of 128 + 64 and 128 twice a layer, the four layers as one loop, the grouped product over a layer's
16 held experts, the combine's row DMAs at 12 places a token) through Mosaic:
what the chip's compiler would refuse (a tile that does not fit VMEM, a model
that does not fit the chip) costs no chip time. And every operation of the
MoE branch carries its scope.

As its siblings ``test_benchmark_{mla,kda,cca}_moe_compile_v5e.py``: the
topology is described inside a module-scoped fixture, never at import, and the
fixture skips where it cannot be described.
"""

from __future__ import annotations

import json
import re
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

CELL = "longcat_flash_prefill_s4096"


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
    from cuda_mpi_gpu_cluster_programming_tpu.models import moe_share, scmoe_mla
    from cuda_mpi_gpu_cluster_programming_tpu.ops import flash_attention, grouped_matmul, moe_combine

    # jax.default_backend() is the CPU here and the kernels would run
    # interpreted: steer them through Mosaic (in the test, not by an option)
    for module in (flash_attention, grouped_matmul, moe_combine):
        monkeypatch.setattr(module, "_interpret", lambda: False)
    manifest = harness.load_json(REPO / "BENCHMARK.json")
    cell = harness.find_cell(manifest, CELL)
    cfg = harness.load_config(manifest, cell["config"])
    traffic = harness.load_json(REPO / "benchmark" / "traffic" / f"{cell['traffic']}.json")
    adapter = harness.load_plugin("adapters", cfg["family"])
    one_chip = SingleDeviceSharding(topo.devices[0])
    params = jax.tree.map(
        lambda leaf: jax.ShapeDtypeStruct(leaf[0], jnp.bfloat16, sharding=one_chip),
        scmoe_mla.param_shapes(adapter.model_config(cfg)), is_leaf=moe_share._is_leaf,
    )
    ids = jax.ShapeDtypeStruct((int(traffic["batch"]), int(traffic["seq_len"])), jnp.int32, sharding=one_chip)
    compiled = adapter.build_forward(cfg).lower(params, ids).compile()
    mem = compiled.memory_analysis()
    hbm = json.loads((REPO / "benchmark" / "peaks.json").read_text())["peaks"][0]["hbm_bytes"]
    assert mem.argument_size_in_bytes >= 10.3e9  # the weights a deployment holds here
    # three steps' logits may be alive at once: the driver's chain runs ahead of the device
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes + 3 * mem.output_size_in_bytes < 0.9 * hbm
    text = compiled.as_text()
    # four layers, compiled once: two flash kernels, three grouped products and one combine in the loop's body
    assert text.count('custom_call_target="tpu_custom_call"') == 2 + 3 + 1
    assert "flash_fwd" in text and "grouped_matmul" in text and "moe_combine" in text
    names = layer_times.layer_names(cfg)
    scopes, _mixed = layer_times.scope_map(text, names)
    assert set(scopes.values()) == set(names)
    # the MoE branch's operations under the branch's own scopes: the top-k and the sort under the route,
    # the kernels of the routed sum under the experts, the identity experts' term under moe.zero
    paths = re.findall(r'op_name="((?:[^"\\]|\\.)*)"', text)
    under = lambda primitive: {
        layer_times.scope_of(path, names) for path in paths if path.split("/")[-1].startswith(primitive) and "/" in path
    }
    assert under("top_k") == {"moe.route"} and under("sort") == {"moe.route"}
    kernels = [line for line in text.splitlines() if 'custom_call_target="tpu_custom_call"' in line]
    for kernel, scope in (("grouped_matmul", "moe.experts"), ("moe_combine", "moe.experts"), ("flash_fwd", "mla.attn")):
        lines = [line for line in kernels if kernel in line]
        assert lines and all(f"/{scope}/" in re.search(r'op_name="([^"]*)"', line).group(1) for line in lines), kernel
    assert any("/moe.zero/" in path for path in paths)
    # no layer's experts are sliced out of the stack: the kernels read the stack itself
    assert "bf16[64,6144,2048]" in text and "bf16[16,6144,2048]" not in text
