"""The cell ``phi4_mini_flash_prefill_s4096``'s step program compiles for a
described v5e at its real size, its kernels (the selective scan over 5,120
channels of 16 states, the flash kernel at 40 query heads of 64 over 20 with
values of 128, windowed and causal) through Mosaic, the 32 layers as two loops
and two layers between them: what the chip's compiler would refuse (a tile
that does not fit VMEM, a model that does not fit the chip beside its logits)
costs no chip time. And every operation of the forward carries a scope of the
family.

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

CELL = "phi4_mini_flash_prefill_s4096"


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
    from cuda_mpi_gpu_cluster_programming_tpu.models import moe_share, sambay
    from cuda_mpi_gpu_cluster_programming_tpu.ops import flash_attention, selective_scan

    # jax.default_backend() is the CPU here and the kernels would run
    # interpreted: steer them through Mosaic (in the test, not by an option)
    for module in (flash_attention, selective_scan):
        monkeypatch.setattr(module, "_interpret", lambda: False)
    manifest = harness.load_json(REPO / "BENCHMARK.json")
    cell = harness.find_cell(manifest, CELL)
    cfg = harness.load_config(manifest, cell["config"])
    traffic = harness.load_json(REPO / "benchmark" / "traffic" / f"{cell['traffic']}.json")
    adapter = harness.load_plugin("adapters", cfg["family"])
    one_chip = SingleDeviceSharding(topo.devices[0])
    params = jax.tree.map(
        lambda leaf: jax.ShapeDtypeStruct(leaf[0], jnp.bfloat16, sharding=one_chip),
        sambay.param_shapes(adapter.model_config(cfg)), is_leaf=moe_share._is_leaf,
    )
    ids = jax.ShapeDtypeStruct((int(traffic["batch"]), int(traffic["seq_len"])), jnp.int32, sharding=one_chip)
    compiled = adapter.build_forward(cfg).lower(params, ids).compile()
    mem = compiled.memory_analysis()
    hbm = json.loads((REPO / "benchmark" / "peaks.json").read_text())["peaks"][0]["hbm_bytes"]
    assert mem.argument_size_in_bytes >= 7.7e9  # the whole model: 45% of the chip
    assert mem.output_size_in_bytes == 4096 * 200064 * 4  # every position's logits over every id
    # a chain of 2 holds two steps' logits beside the weights and the step's temporaries; a third would not fit
    chain = int(traffic["chain_len"])
    held = mem.temp_size_in_bytes + mem.argument_size_in_bytes
    assert chain == 2 and held + chain * mem.output_size_in_bytes < 0.92 * hbm < held + 3 * mem.output_size_in_bytes
    text = compiled.as_text()
    # 32 layers, compiled as two loops and two layers: a scan and a windowed flash kernel in the first loop's body,
    # a scan and a causal flash kernel between the loops, a causal flash kernel in the second loop's body
    assert text.count('custom_call_target="tpu_custom_call"') == 2 + 3
    assert len(re.findall(r"\bwhile\(", text)) == 2
    names = layer_times.layer_names(cfg)
    scopes, _mixed = layer_times.scope_map(text, names)
    assert set(scopes.values()) == set(names)
    kernels = [line for line in text.splitlines() if 'custom_call_target="tpu_custom_call"' in line]
    path = lambda line: re.search(r'op_name="([^"]*)"', line).group(1)
    under = lambda kernel: sorted(layer_times.scope_of(path(line), names) for line in kernels if kernel in line)
    assert under("mamba_scan") == ["mamba.scan"] * 2
    assert under("flash_fwd") == ["diff.attn_full", "diff.attn_full", "diff.attn_window"]
    # every operation inside the forward carries a scope of the family (the arguments' names carry none)
    paths = re.findall(r'op_name="((?:[^"\\]|\\.)*)"', text)
    inside = [p for p in paths if p.startswith("jit(fwd_bf16)/jit(<lambda>)/")]
    assert inside and all(layer_times.scope_of(p, names) is not None for p in inside)
    # the keys and values layer 17 hands down reach the second loop as they are: no cross layer projects its own
    assert "bf16[1,20,4096,64]" in text and "bf16[1,20,4096,128]" in text and "bf16[7,2560,2560]" in text
