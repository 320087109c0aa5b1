"""The LRN of the step programs, read from the text the TPU's compiler gives
for a described v5e: under the scope ``lrn2`` no ``reduce-window`` (the
channel-window sum crosses lanes; ``ops.reference.lrn`` takes it as a product
with a banded 0/1 matrix instead), the whole layer one fusion around that
product (a 1x1 ``convolution`` in the compiled text), and a pool2 fusion that
writes one array (the squares no longer ride it as a second output). What
keeps a later change from putting the lane-crossing window back; no chip, so
nothing here is a time.

The topology is described inside a module-scoped fixture, never at import,
and the fixture skips where it cannot be described (the rule of
``tests/test_mla_step_dataflow_v5e.py``).
"""

from __future__ import annotations

import sys
from pathlib import Path

import jax
import pytest
from jax.sharding import SingleDeviceSharding

REPO = Path(__file__).resolve().parents[1]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from benchmark import layer_times  # noqa: E402  (the scopes of a compiled text)
from cuda_mpi_gpu_cluster_programming_tpu.configs import REGISTRY, build_forward  # noqa: E402
from cuda_mpi_gpu_cluster_programming_tpu.models import alexnet_full, init  # noqa: E402
from cuda_mpi_gpu_cluster_programming_tpu.models.alexnet import BLOCKS12  # noqa: E402
from cuda_mpi_gpu_cluster_programming_tpu.ops import scopes  # noqa: E402

LRN2, POOL2 = scopes.BLOCKS12_LAYERS[4], scopes.BLOCKS12_LAYERS[3]

# (registry key, compute type, batch): the step programs of the three
# one-chip AlexNet cells.
STEPS = [("v1_jit", "bf16", 128), ("v1_jit", "fp32", 128), ("v6_full_jit", "bf16", 256)]


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
def step_texts(topo):
    """``{(key, compute, batch): compiled text}``, each step compiled once.
    A compile for a described chip is written to the persistent cache but
    cannot be read back without a chip: the cache is kept out of it."""
    from jax.experimental.compilation_cache import compilation_cache

    one_chip = SingleDeviceSharding(topo.devices[0])

    def described(tree):
        return jax.tree.map(
            lambda leaf: jax.ShapeDtypeStruct(leaf.shape, leaf.dtype, sharding=one_chip), tree
        )

    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    texts = {}
    try:
        for key, compute, batch in STEPS:
            full = key == "v6_full_jit"
            draw = alexnet_full.init_full_random if full else init.init_params_random
            params = described(jax.eval_shape(draw, jax.random.key(0)))
            x = described(jax.eval_shape(lambda k: init.random_input(k, batch=batch), jax.random.key(1)))
            fwd = build_forward(REGISTRY[key], alexnet_full.ALEXNET if full else BLOCKS12, compute=compute)
            texts[key, compute, batch] = fwd.lower(params, x).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was)
        compilation_cache.reset_cache()
    return texts


def _entry(comps):
    """The instructions of the program's entry computation."""
    return next(body for name, body in comps.items() if name.startswith("main") and any(i.root for i in body))


def _layers(key: str):
    cfg = alexnet_full.ALEXNET if key == "v6_full_jit" else BLOCKS12
    return [name for name, _spec in cfg.layer_chain()]


@pytest.mark.parametrize("step", STEPS, ids=lambda s: f"{s[0]}-{s[1]}-b{s[2]}")
def test_lrn2_is_one_fusion_round_a_product_and_no_window_crosses_lanes(step_texts, step):
    text = step_texts[step]
    layers = _layers(step[0])
    comps = layer_times._computations(text)
    scope_by_name, _mixed = layer_times.scope_map(text, layers)
    entry = _entry(comps)
    # the pools: the text does show reduce-windows
    assert any(i.opcode == "reduce-window" for body in comps.values() for i in body)
    # no reduce-window anywhere, fused or not, carries the LRN's scope
    windows = [
        i.name for body in comps.values() for i in body
        if i.opcode == "reduce-window" and i.op_name and layer_times.scope_of(i.op_name, layers) == LRN2
    ]
    assert windows == []
    # what the trace would show under lrn2: exactly one kernel, a fusion that holds a convolution
    kernels = [i for i in entry if scope_by_name.get(i.name) == LRN2 and i.opcode not in layer_times._PASSIVE]
    assert [i.opcode for i in kernels] == ["fusion"], [(i.name, i.opcode) for i in kernels]
    assert any(i.opcode == "convolution" for i in comps[kernels[0].calls])


@pytest.mark.parametrize("step", STEPS, ids=lambda s: f"{s[0]}-{s[1]}-b{s[2]}")
def test_pool2_writes_one_array(step_texts, step):
    """The squares were a second output of pool2's fusion while the window
    sum was a kernel of its own; a tuple-shaped pool2 fusion means some part
    of the LRN rides the pool again."""
    text = step_texts[step]
    scope_by_name, _mixed = layer_times.scope_map(text, _layers(step[0]))
    pool2 = [
        i.name for i in _entry(layer_times._computations(text))
        if i.opcode == "fusion" and scope_by_name.get(i.name) == POOL2
    ]
    assert len(pool2) == 1, pool2
    line = next(ln for ln in text.splitlines() if f"%{pool2[0]} = " in ln)
    result_type = line.split(" = ", 1)[1].lstrip()
    assert not result_type.startswith("("), line[:200]
