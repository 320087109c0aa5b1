"""The expert tier's combine for a described v5e: ``ops.moe_combine`` lowers
through Mosaic at the three published shapes (what the chip's compiler would
refuse — a row that is no whole tile, a block that does not fit VMEM — costs
no chip time), and the compiled text of one MoE sublayer at the dots cell's
shape holds the kernel under ``moe.experts`` and no gather over every token
there: the one gather left is the dispatch's ``u[pair // k]``, a chunk of rows
at a time. No chip, so nothing here is a time.

The topology is described inside a module-scoped fixture, never at import,
and the fixture skips where it cannot be described (the rule of
``tests/test_mla_step_dataflow_v5e.py``).
"""

from __future__ import annotations

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from cuda_mpi_gpu_cluster_programming_tpu.models import cca_moe, kda_moe, mla_moe, moe_share
from cuda_mpi_gpu_cluster_programming_tpu.ops import grouped_matmul, moe_combine

# tokens, places a token, hidden, rows of a span: the three language-model
# configurations as their presets give them
PUBLISHED = {
    "dots": (mla_moe.PRESETS["ep16_share"], 8192, 8, 7168, 8192),
    "solar": (kda_moe.PRESETS["solar_ep8"], 16384, 8, 4096, 24576),
    "zaya": (cca_moe.PRESETS["zaya1_ep2"], 4096, 1, 2048, 3840),
}


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
        patch.setattr(moe_combine, "_interpret", lambda: False)
        patch.setattr(grouped_matmul, "_interpret", lambda: False)
        yield
    jax.config.update("jax_enable_compilation_cache", cache_was)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("name", sorted(PUBLISHED))
def test_the_kernel_lowers_at_the_published_shape_and_its_blocks_fit_vmem(one_chip, through_mosaic, name):
    (cfg, batch, seq), tokens, k, d, span = PUBLISHED[name]
    assert (batch * seq, cfg.num_experts_per_tok, cfg.hidden_size, cfg.expert_span_rows) == (tokens, k, d, span)
    slab = moe_combine.row_slab(d)
    shape = lambda dims, dtype: jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)
    compiled = jax.jit(moe_combine.moe_combine, donate_argnums=3).lower(
        shape((span, *slab), jnp.bfloat16), shape((tokens, k), jnp.int32), shape((tokens, k), jnp.float32),
        shape((tokens, *slab), jnp.float32), shape((), jnp.int32),
    ).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1 and "moe_combine" in text
    # y goes in and comes out in place: nothing the size of y beside it
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes == tokens * d * 4 and memory.temp_size_in_bytes < tokens * d


def test_one_moe_sublayer_holds_the_kernel_and_no_gather_over_every_token(one_chip, through_mosaic):
    cfg, batch, seq = PUBLISHED["dots"][0]
    tokens, d = batch * seq, cfg.hidden_size
    shape = lambda dims, dtype: jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)
    params = jax.tree.map(
        lambda leaf: shape(leaf[0], jnp.bfloat16),
        moe_share.moe_shapes(d, cfg.moe_intermediate_size, cfg), is_leaf=moe_share._is_leaf,
    )
    params["ffn_norm"] = shape((d,), jnp.bfloat16)
    text = jax.jit(lambda p, h: moe_share._moe(p, h, cfg)).lower(
        params, shape((batch, seq, d), jnp.float32)
    ).compile().as_text()
    kernels = [ln for ln in text.splitlines() if 'custom_call_target="tpu_custom_call"' in ln]
    assert sum("moe_combine" in ln for ln in kernels) == 1 and sum("grouped_matmul" in ln for ln in kernels) == 3
    assert all("/moe.experts/" in ln for ln in kernels)
    gathers = [ln for ln in text.splitlines() if re.search(r'op_name="[^"]*/moe\.experts/[^"]*gather', ln)]
    assert any(f"bf16[{cfg.expert_chunk_rows},{d}]" in ln for ln in gathers)  # the dispatch's, a chunk at a time
    sublanes, lanes = moe_combine.row_slab(d)
    every_token = re.compile(rf"= \(?\w+\[{tokens},({d}|{sublanes},{lanes})\]")
    assert [ln.strip()[:160] for ln in gathers if every_token.search(ln)] == []
