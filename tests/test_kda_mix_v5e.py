"""The short convolution's kernel for a described v5e: ``ops.kda_mix`` lowers
through Mosaic at the solar cell's shape with and without the l2norm (what the
chip's compiler would refuse — a slice it cannot shift, a block that does not
fit VMEM — costs no chip time), and the compiled text of one linear-attention
layer at that shape holds the three kernels under ``kda.mix`` and writes no
float32 array of a whole projection's size there but the decay ``g``: a later
change that brings the float32 intermediate of the l2norm back fails here, not
in a benchmark. No chip, so nothing here is a time.

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
from jax.sharding import SingleDeviceSharding

REPO = Path(__file__).resolve().parents[1]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from benchmark import layer_times  # noqa: E402  (the scopes of a compiled text)
from cuda_mpi_gpu_cluster_programming_tpu.models import kda_moe, moe_share  # noqa: E402
from cuda_mpi_gpu_cluster_programming_tpu.ops import kda, kda_mix, scopes  # noqa: E402

CFG, BATCH, SEQ = kda_moe.PRESETS["solar_ep8"]
HEADS, DIM = CFG.linear_attn_num_heads, CFG.linear_attn_head_dim
KERNEL = 'custom_call_target="tpu_custom_call"'


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
def through_mosaic():
    """The kernels through Mosaic (steered here, not by an option), the
    compile cache off: a described device's programs cannot be read back."""
    from jax.experimental.compilation_cache import compilation_cache

    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(kda_mix, "_interpret", lambda: False)
        patch.setattr(kda, "_interpret", lambda: False)
        yield
    jax.config.update("jax_enable_compilation_cache", cache_was)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("l2norm", [True, False], ids=["l2norm", "plain"])
def test_the_kernel_lowers_at_the_cell_s_shape(one_chip, through_mosaic, l2norm):
    assert kda_mix.fits(SEQ, DIM, CFG.short_conv_kernel_size)
    shape = lambda dims, dtype: jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)
    compiled = jax.jit(lambda x, taps: kda_mix.short_conv_mix(x, taps, l2norm=l2norm, out_dtype=jnp.bfloat16)).lower(
        shape((BATCH, HEADS, SEQ, DIM), jnp.float32), shape((CFG.short_conv_kernel_size, HEADS, DIM), jnp.bfloat16)
    ).compile()
    text = compiled.as_text()
    assert text.count(KERNEL) == 1 and "kda_short_conv_mix" in text
    # the projection in, the stored type out, and nothing the size of either beside them
    memory = compiled.memory_analysis()
    assert memory.output_size_in_bytes == BATCH * HEADS * SEQ * DIM * 2
    assert memory.temp_size_in_bytes < BATCH * HEADS * SEQ * DIM // 8


def test_one_linear_layer_holds_the_kernels_and_no_float32_intermediate_of_the_mix(one_chip, through_mosaic):
    shape = lambda dims, dtype: jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)
    layer = kda_moe.param_shapes(CFG)["layers"][CFG.layer_kinds().index("kda")]
    params = jax.tree.map(lambda leaf: shape(leaf[0], jnp.bfloat16), layer, is_leaf=moe_share._is_leaf)
    text = jax.jit(lambda p, x: kda_moe._kda(p, x, CFG)).lower(
        params, shape((BATCH, SEQ, CFG.hidden_size), jnp.float32)
    ).compile().as_text()
    kernels = [ln for ln in text.splitlines() if KERNEL in ln]
    mixes = [ln for ln in kernels if re.match(r"\s*(ROOT )?%?kda_short_conv_mix[\w.]* = ", ln)]
    assert len(mixes) == 3 and len(kernels) == 4 and all("/kda.mix/" in ln for ln in mixes)
    assert all(f"bf16[{BATCH},{HEADS},{SEQ},{DIM}]" in ln.split(" custom-call(")[0] for ln in mixes)
    # what the entry computation's instructions of scope kda.mix write in float32 at a projection's size
    comps = layer_times._computations(text)
    scope_of, _mixed = layer_times.scope_map(text, scopes.KDA_MOE_LAYERS)
    entry = re.search(r"(?m)^ENTRY %?([\w.\-]+)", text).group(1)
    whole = f"f32[{BATCH},{HEADS},{SEQ},{DIM}]"
    lines = {}
    for line in text.splitlines():
        m = layer_times._INSTRUCTION.match(line)
        if m:
            lines[m.group(2).split(" = ")[0].lstrip("%")] = line
    ran = [i for i in comps[entry] if i.opcode not in layer_times._PASSIVE]
    # an instruction's result type stands between its name and its opcode
    writes_whole = [i.name for i in ran if whole in lines[i.name].split(" = ", 1)[1].split(f" {i.opcode}(", 1)[0]]
    in_mix = [i.name for i in ran if scope_of.get(i.name) == "kda.mix"]
    assert in_mix  # the decay and beta keep the scope in the text
    # under kda.mix at most the decay g, where the compiler does not fuse it into its product
    assert len(set(writes_whole) & set(in_mix)) <= 1, writes_whole
    # and in the whole layer: the three projections, the decay's rate or the decay itself, the output gate
    assert 3 <= len(writes_whole) <= 6, writes_whole
