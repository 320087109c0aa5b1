"""The latent-attention dataflow of the language-model cell's step program,
read from the text the TPU's compiler gives for a described v5e: between the
projections and ``flash_fwd`` no query or key of the whole ``nope + rope``
width is assembled, the one ``k_rope`` is never copied per head, and the
passes of scope ``mla.proj`` that are neither a matmul nor a kernel write
under a stated number of bytes a layer. What keeps a later change from
putting a bandwidth pass back; no chip, so nothing here is a time.

The topology is described inside a module-scoped fixture, never at import,
and the fixture skips where it cannot be described (the rule of
``tests/benchmark/test_benchmark_mla_moe_compile_v5e.py``).
"""

from __future__ import annotations

import math
import re
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

REPO = Path(__file__).resolve().parents[1]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from benchmark import layer_times  # noqa: E402  (the scopes of a compiled text)
from cuda_mpi_gpu_cluster_programming_tpu.models import mla_moe  # noqa: E402
from cuda_mpi_gpu_cluster_programming_tpu.ops import scopes  # noqa: E402

CFG = mla_moe.EP16_SHARE
BATCH, SEQ = mla_moe.PRESETS["ep16_share"][1:]

# Bytes a layer that the passes of ``mla.proj`` may write (nominal: elements x
# width, lane padding not counted). The step as it stands writes 0.277 GB a
# layer (the norms' bf16 results, the one concatenation of q_rope's rotated
# halves, the slices of the weights); with the 192-wide query and key
# assembled and k_rope copied per head it wrote 1.727 GB. One more pass over a
# ``(B, H, S, rope)`` bf16 array (0.134 GB) crosses the line.
PASS_BYTES_A_LAYER = 0.4e9

_SHAPE = re.compile(r"\b(pred|s8|u8|s16|u16|bf16|f16|s32|u32|f32|s64|u64|f64)\[([\d,]*)\]")
_WIDTH = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
          "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8}  # fmt: skip
_RESULT = re.compile(r" = (\(.*?\)|\S+) ([\w\-]+)\(")


def _dims(text: str):
    """The dimensions of every array type in ``text``."""
    return [tuple(int(d) for d in dims.split(",") if d) for _dt, dims in _SHAPE.findall(text)]


def _bytes(result_type: str) -> int:
    widths = [_WIDTH[dt] for dt, _dims in _SHAPE.findall(result_type)]
    return sum(math.prod(dims) * width for dims, width in zip(_dims(result_type), widths))


def _result(line: str):
    """``(result type, opcode)`` of one instruction line."""
    m = _RESULT.search(line)
    return (m.group(1), m.group(2)) if m else ("", "")


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
def step_text(topo):
    """The compiled text of ``build_forward``'s step at the cell's shapes,
    the three kernels through Mosaic (steered here, not by an option)."""
    from jax.experimental.compilation_cache import compilation_cache

    from cuda_mpi_gpu_cluster_programming_tpu.configs import REGISTRY, build_forward
    from cuda_mpi_gpu_cluster_programming_tpu.ops import flash_attention, grouped_matmul, moe_combine

    one_chip = SingleDeviceSharding(topo.devices[0])
    params = jax.tree.map(
        lambda leaf: jax.ShapeDtypeStruct(leaf[0], jnp.bfloat16, sharding=one_chip),
        mla_moe.param_shapes(CFG), is_leaf=mla_moe._is_leaf,
    )
    ids = jax.ShapeDtypeStruct((BATCH, SEQ), jnp.int32, sharding=one_chip)
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(flash_attention, "_interpret", lambda: False)
        patch.setattr(grouped_matmul, "_interpret", lambda: False)
        patch.setattr(moe_combine, "_interpret", lambda: False)
        try:
            fwd = build_forward(REGISTRY["v8_mla_moe"], CFG, n_shards=1, compute="bf16")
            return fwd.lower(params, ids).compile().as_text()
        finally:
            jax.config.update("jax_enable_compilation_cache", cache_was)
            compilation_cache.reset_cache()


def test_no_query_or_key_of_the_whole_width_is_assembled(step_text):
    """Whatever is ``qk_nope + qk_rope`` wide is a piece of the ``q_b``
    weights: it has no batch-and-sequence extent and at most their size."""
    whole = CFG.qk_head_dim
    weights = CFG.q_lora_rank * CFG.num_attention_heads * whole
    wide = {dims for dims in _dims(step_text) if dims and dims[-1] == whole}
    assert (CFG.q_lora_rank, CFG.num_attention_heads, whole) in wide  # the text does show shapes
    assert [d for d in wide if SEQ in d or BATCH * SEQ in d or math.prod(d) > weights] == []


def test_k_rope_is_not_copied_per_head(step_text):
    """No ``broadcast`` anywhere in the program, fused or not, makes an array
    of batch x heads x sequence x rope elements; the kernel is handed the
    ``(B, rope, S)`` key itself."""
    per_head = BATCH * CFG.num_attention_heads * SEQ * CFG.qk_rope_head_dim
    copies = []
    for line in step_text.splitlines():
        result, opcode = _result(line)
        if opcode == "broadcast" and any(math.prod(d) == per_head for d in _dims(result)):
            copies.append(line.strip()[:160])
    assert copies == []
    kernels = [line for line in step_text.splitlines() if _result(line)[1] == "custom-call" and "flash_fwd" in line]
    assert len(kernels) == CFG.num_layers  # one call a layer
    shared_key = (BATCH, CFG.qk_rope_head_dim, SEQ)  # sequence-minor
    for line in kernels:
        operands = line.split("custom-call(", 1)[1].split(")", 1)[0]
        # the two scalar-prefetched tables of the grid's (q-block, k-block) pairs, then q, k, v, q_rope, k_rope
        assert len(operands.split(",")) == 2 + 5, operands
        # the operands' shapes stand in the text before the call: look the last one up
        name = operands.split(",")[-1].strip()
        defined = next(ln for ln in step_text.splitlines() if ln.lstrip().startswith(f"{name} = "))
        assert shared_key in _dims(_result(defined)[0]), defined[:200]


def test_the_passes_of_mla_proj_write_under_the_stated_bytes(step_text):
    """The instructions of scope ``mla.proj`` in the entry computation that
    are neither a matmul (a fusion that holds a convolution: on the TPU a
    ``dot`` is one) nor a kernel, by the bytes of what they write."""
    comps = layer_times._computations(step_text)
    scope_of, _mixed = layer_times.scope_map(step_text, scopes.MLA_MOE_LAYERS)
    entry = re.search(r"(?m)^ENTRY %?([\w.\-]+)", step_text).group(1)
    lines = {}
    for line in step_text.splitlines():
        m = layer_times._INSTRUCTION.match(line)
        if m:
            lines[m.group(2).split(" = ")[0].lstrip("%")] = line
    written, matmuls = 0, 0
    for i in comps[entry]:
        if scope_of.get(i.name) != "mla.proj" or i.opcode in layer_times._PASSIVE:
            continue
        if i.opcode.endswith("-start") or i.opcode == "custom-call":
            continue  # an async copy counts where it is done; a kernel is no pass
        body = comps.get(i.calls, []) if i.opcode == "fusion" else [i]
        if any(b.opcode in ("convolution", "dot") for b in body):
            matmuls += 1
            continue
        written += _bytes(_result(lines[i.name])[0])
    assert matmuls >= 5 * CFG.num_layers  # the parse found the projections
    a_layer = written / CFG.num_layers
    assert 0.05e9 < a_layer < PASS_BYTES_A_LAYER, f"{a_layer / 1e9:.3f} GB a layer"
