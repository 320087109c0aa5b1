"""``ops.selective_scan.mamba_scan`` against the recurrence one token at a time:
across chunk and channel-block boundaries, at decays that underflow, at steps
at both ends of their range, in both stored types; what it refuses; and,
through Mosaic for a described v5e, the kernel at the published shape (what the
chip's compiler would refuse costs no chip time). No chip, so nothing here is
a time.

The topology is described inside a module-scoped fixture, never at import, and
the fixture skips where it cannot be described (the rule of
``tests/test_moe_combine_v5e.py``).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from cuda_mpi_gpu_cluster_programming_tpu.models import sambay
from cuda_mpi_gpu_cluster_programming_tpu.ops import selective_scan
from cuda_mpi_gpu_cluster_programming_tpu.ops.selective_scan import mamba_recurrence, mamba_scan


def _inputs(seed, b, l, ch, n, dtype=jnp.float32, dt_range=(sambay.DT_MIN, sambay.DT_MAX), a_scale=1.0):
    keys = jax.random.split(jax.random.key(seed), 5)
    x = jax.random.normal(keys[0], (b, l, ch), jnp.float32).astype(dtype)
    delta = jnp.exp(jax.random.uniform(keys[1], (b, l, ch), jnp.float32, math.log(dt_range[0]), math.log(dt_range[1])))
    a = -a_scale * jnp.broadcast_to(jnp.arange(1, n + 1, dtype=jnp.float32), (ch, n))
    bc = [jax.random.normal(k, (b, l, n), jnp.float32) for k in keys[2:4]]
    return x, delta, a, *bc, 1.0 + 0.1 * jax.random.normal(keys[4], (ch,), jnp.float32)


def _close(got, want, tol):
    want = np.asarray(want, np.float32)
    return float(np.abs(np.asarray(got, np.float32) - want).max() / np.abs(want).max()) <= tol


@pytest.mark.parametrize(
    "l,ch,chunk,block,unroll",
    [(256, 256, 128, 128, 8), (256, 256, 256, 256, 16), (384, 128, 128, 128, 8), (32, 128, 256, 512, 8)],
    ids=["2chunks_2blocks", "one_of_each", "3chunks", "the_small_preset"],
)
def test_the_kernel_is_the_recurrence_across_chunk_and_block_boundaries(l, ch, chunk, block, unroll):
    args = _inputs(0, 2, l, ch, 16)
    got = mamba_scan(*args, chunk=chunk, channel_block=block, unroll=unroll)
    want, _last = mamba_recurrence(*args)
    assert got.dtype == jnp.float32 and _close(got, want, 2e-6)


def test_bf16_inputs_give_bf16_outputs_one_rounding_off_the_recurrence():
    args = _inputs(1, 1, 256, 128, 16, jnp.bfloat16)
    got = mamba_scan(*args, chunk=128, channel_block=128)
    assert got.dtype == jnp.bfloat16 and _close(got, mamba_recurrence(*args)[0], 2.0**-7)


@pytest.mark.parametrize(
    "dt_range,a_scale",
    [((5.0, 20.0), 8.0), ((1e-3, 1.001e-3), 1.0), ((0.1, 0.1001), 1.0), ((1e-6, 1e-5), 1.0)],
    ids=["decays_underflow", "least_step", "greatest_step", "hardly_any_decay"],
)
def test_decays_that_underflow_and_steps_at_both_ends_of_their_range(dt_range, a_scale):
    """``Delta A`` down to -2,560 a token: every decay underflows to a zero
    that is the right answer, and a sum over a chunk (-650,000) is never
    exponentiated; at a step of 1e-6 the state remembers the whole sequence."""
    args = _inputs(2, 1, 256, 128, 16, dt_range=dt_range, a_scale=a_scale)
    got = np.asarray(mamba_scan(*args, chunk=128, channel_block=128))
    assert np.isfinite(got).all() and _close(got, mamba_recurrence(*args)[0], 1e-5)


def test_a_token_sees_no_later_token_and_the_state_crosses_the_chunk_boundary():
    x, delta, a, b, c, d = _inputs(3, 1, 256, 128, 16, dt_range=(0.01, 0.02))
    base = np.asarray(mamba_scan(x, delta, a, b, c, d, chunk=128, channel_block=128))
    moved = np.asarray(mamba_scan(x.at[0, 100].add(1.0), delta, a, b, c, d, chunk=128, channel_block=128))
    changed = np.abs(moved - base).max(axis=-1)[0]
    assert not changed[:100].any() and changed[100] > 0 and changed[128:140].min() > 0  # past the boundary at 128


def test_what_it_refuses():
    x, delta, a, b, c, d = _inputs(4, 1, 256, 256, 16)
    for kwargs in ({"chunk": 96}, {"chunk": 192}, {"channel_block": 192}, {"unroll": 4}, {"chunk": 128, "unroll": 24}):
        with pytest.raises(ValueError):
            mamba_scan(x, delta, a, b, c, d, **kwargs)
    with pytest.raises(ValueError):
        mamba_scan(x, delta, a[:, :12], b[..., :12], c[..., :12], d)  # states no whole tile of 8
    with pytest.raises(ValueError):
        mamba_scan(x, delta[:, :128], a, b, c, d)


# ---- through Mosaic for a described v5e ----------------------------------------


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
    """The kernel through Mosaic (steered here, not by an option), the compile
    cache off: a described device's programs cannot be read back."""
    from jax.experimental.compilation_cache import compilation_cache

    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(selective_scan, "_interpret", lambda: False)
        yield
    jax.config.update("jax_enable_compilation_cache", cache_was)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bf16", "fp32"])
def test_the_kernel_lowers_at_the_published_shape_and_its_blocks_fit_vmem(one_chip, through_mosaic, dtype):
    cfg, batch, seq = sambay.PRESETS["phi4_mini_flash"]
    assert (batch, seq, cfg.d_inner, cfg.d_state) == (1, 4096, 5120, 16)
    shape = lambda dims, kind=jnp.float32: jax.ShapeDtypeStruct(dims, kind, sharding=one_chip)
    compiled = jax.jit(
        lambda *args: mamba_scan(*args, chunk=cfg.scan_chunk, channel_block=cfg.scan_channel_block)
    ).lower(
        shape((1, 4096, 5120), dtype), shape((1, 4096, 5120)), shape((5120, 16)), shape((1, 4096, 16)),
        shape((1, 4096, 16)), shape((5120,)),
    ).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1 and "mamba_scan" in text
    # nothing the size of an operand beside the operands: the kernel keeps its state and its tiles in VMEM
    assert compiled.memory_analysis().temp_size_in_bytes < 4096 * 16 * 4 * 2 + 5120 * 16 * 4 + 2**20
