"""The dense MLP of ``models/sambay.py`` for a described v5e: two stacked MLPs
at the phi4 cell's shape (1 x 4,096 tokens of 2,560, F = 10,240, bf16
parameters) compile, as a scan over the stack like the model's own loops, to a
body that writes ONE float32 array F wide (the gate's product), one
``bf16[..., F]`` hidden (the up product with ``silu(gate) * up`` and the cast
in its epilogue) and a ``W_2`` fusion that reads that hidden, and that copies
neither the layer's ``(D, 2F)`` matrix nor a half of it out of the stack. A
later change that brings back the float32 ``(4096, 2F)`` pair, which ``W_2``
had to read back and gate in its prologue, or the copy (0.32 ms an MLP on the
chip: ``PERF.md`` section 6, PR 40), fails here, not in a benchmark. No chip,
so nothing here is a time.

The topology is described inside a module-scoped fixture, never at import,
and the fixture skips where it cannot be described (the rule of
``tests/test_mla_step_dataflow_v5e.py``).
"""

from __future__ import annotations

import re
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax import lax
from jax.sharding import SingleDeviceSharding

REPO = Path(__file__).resolve().parents[1]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from benchmark import layer_times  # noqa: E402  (a compiled text's computations)
from cuda_mpi_gpu_cluster_programming_tpu.models import moe_share, sambay  # noqa: E402

CFG, BATCH, SEQ = sambay.PRESETS["phi4_mini_flash"]
WIDTH = CFG.intermediate_size


@pytest.fixture(scope="module")
def one_chip():
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here: nothing to check
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_cache():
    """The compile cache off: a described device's programs cannot be read back."""
    from jax.experimental.compilation_cache import compilation_cache

    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", cache_was)
    compilation_cache.reset_cache()


def test_two_stacked_mlps_write_one_float32_gate_and_hand_w2_a_bf16_hidden(one_chip, no_cache):
    shape = lambda dims, dtype: jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)
    stack = moe_share.stacked(sambay.mlp_shapes(CFG), 2)
    params = jax.tree.map(lambda leaf: shape(leaf[0], jnp.bfloat16), stack, is_leaf=moe_share._is_leaf)
    def two(stack, x):  # as ``sambay._layers`` runs a loop's MLPs
        step = lambda x, inputs: (sambay._mlp(inputs[0], x, CFG, sambay._w1_halves(stack["w1"], inputs[1])), None)
        return lax.scan(step, x, (stack, jnp.arange(2, dtype=jnp.int32)))[0]

    text = jax.jit(two).lower(params, shape((BATCH, SEQ, CFG.hidden_size), jnp.float32)).compile().as_text()
    wide = lambda dtype, width: re.compile(rf"\b{dtype}\[(?:\d+,)*{width}\]")
    # no float32 pair anywhere, not even as a value inside a fusion
    assert not wide("f32", 2 * WIDTH).search(text)
    comps = layer_times._computations(text)
    lines = {}
    for line in text.splitlines():
        m = layer_times._INSTRUCTION.match(line)
        if m:
            lines[m.group(2).split(" = ")[0].lstrip("%")] = line
    # an instruction's result type stands between its name and its opcode, its operands after it
    writes = lambda i: lines[i.name].split(" = ", 1)[1].split(f" {i.opcode}(", 1)[0]
    operands = lambda i: re.findall(r"%([\w.\-]+)", lines[i.name].split(f" {i.opcode}(", 1)[1].split(")")[0])
    body = comps[re.search(r"\bwhile\(.*body=%?([\w.\-]+)", text).group(1)]
    body = [i for i in body if i.opcode not in layer_times._PASSIVE]
    gates = [i for i in body if wide("f32", WIDTH).search(writes(i))]
    hidden = [i for i in body if wide("bf16", WIDTH).search(writes(i))]
    assert len(gates) == 1 and len(hidden) == 1, [lines[i.name][:120] for i in gates + hidden]
    (gate,), (hid,) = gates, hidden
    # no copy of the layer's (D, 2F) matrix out of the stack, nor of a half of it
    assert not [i.name for i in body if re.search(rf"bf16\[{CFG.hidden_size},({WIDTH}|{2 * WIDTH})\]", writes(i))]
    product = lambda i, spec: any(
        j.opcode == "convolution" and f"dense_mlp/{spec}/dot_general" in j.op_name for j in comps[i.calls]
    )
    # the gate is one first product; the hidden is the other, reads the gate, and leaves in bf16
    assert product(gate, "bsd,df->bsf") and product(hid, "bsd,df->bsf") and gate.name in operands(hid)
    # and W_2 is the one reader of the hidden: a product over the bf16 operand, no gate in its prologue
    (w2,) = [i for i in body if hid.name in operands(i)]
    assert product(w2, "bsf,fd->bsd") and gate.name not in operands(w2)
    assert not [j.name for j in comps[w2.calls] if j.opcode in ("exponential", "logistic", "divide")]
