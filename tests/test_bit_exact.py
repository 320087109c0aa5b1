"""Bit-exactness contract — enforced, not just claimed (VERDICT r2 item 3).

The precise contract (stronger than the reference ever achieved — its V1/V3
versions were never numerically comparable at all, SURVEY §4.3):

1. WITHIN a compute tier, sharding is BIT-EXACT for every shard count,
   including non-divisible H=227 splits:
   - XLA-op tier: v2.1_replicated / v2.2_sharded / v7_tp == single-device
     jit(forward_blocks12), np.testing.assert_array_equal.
   - Pallas tier: v4_hybrid / v5_collective == single-device
     jit(forward_blocks12_pallas), likewise bitwise — every layer a Pallas
     kernel per shard, the LRN included (PR 21: with the XLA LRN in the
     shard body the two were last-ulps apart on the TPU; chip_smoke.py
     holds the 4-shard case on the chips). lrn_pallas multiplies fixed
     (LRN_ROWS, C) pixel tiles, so its band matmul has one shape whatever
     the shard's row count (interpreted on XLA:CPU, a dot whose M followed
     the block — 2 or 3 rows at 8 or 5 shards — reassociated by 1 ulp).
2. ACROSS tiers (Pallas vs XLA-op) outputs are NOT bit-identical — the two
   lower conv with different fp32 accumulation orders (tap-matmul
   decomposition vs XLA's conv expansion), and fp32 addition is not
   associative. The gap is bounded (~5e-7 rel, see test_pallas.py
   tolerances) and each tier is individually RUN-TO-RUN deterministic.

The reference's analogous defect for context: its CPU and CUDA versions
disagreed structurally (the CUDA LRN drops the /N scale entirely —
v3_cuda_only/src/layers_cuda.cu:139 vs v1_serial/src/layers_serial.cpp:151).
"""

import jax
import numpy as np
import pytest

from cuda_mpi_gpu_cluster_programming_tpu.configs import REGISTRY, build_forward
from cuda_mpi_gpu_cluster_programming_tpu.models.alexnet import (
    BLOCKS12,
    forward_blocks12,
)
from cuda_mpi_gpu_cluster_programming_tpu.models.init import (
    init_params_random,
    random_input,
)
from cuda_mpi_gpu_cluster_programming_tpu.ops.pallas_model import (
    forward_blocks12_pallas,
)

SHARD_COUNTS = [1, 2, 3, 4, 5, 8]  # incl. non-divisible 227 = 4*56+3 splits


@pytest.fixture(scope="module")
def workload():
    kp, kx = jax.random.split(jax.random.PRNGKey(7))
    params = init_params_random(kp)
    x = random_input(kx, batch=2)
    single_xla = np.asarray(jax.jit(forward_blocks12)(params, x))
    single_pallas = np.asarray(jax.jit(forward_blocks12_pallas)(params, x))
    return params, x, single_xla, single_pallas


@pytest.mark.parametrize("n", SHARD_COUNTS)
def test_xla_tier_sharding_bitwise(workload, n):
    params, x, single_xla, _ = workload
    got = np.asarray(
        build_forward(REGISTRY["v2.2_sharded"], BLOCKS12, n_shards=n)(params, x)
    )
    np.testing.assert_array_equal(got, single_xla)


@pytest.mark.parametrize("n", [1, 2, 4, 8])  # TP shards K: 96/256 must divide
def test_tp_sharding_bitwise(workload, n):
    params, x, single_xla, _ = workload
    got = np.asarray(build_forward(REGISTRY["v7_tp"], BLOCKS12, n_shards=n)(params, x))
    np.testing.assert_array_equal(got, single_xla)


def test_replicated_bitwise(workload):
    params, x, single_xla, _ = workload
    got = np.asarray(
        build_forward(REGISTRY["v2.1_replicated"], BLOCKS12, n_shards=4)(params, x)
    )
    np.testing.assert_array_equal(got, single_xla)


@pytest.mark.parametrize("n", SHARD_COUNTS)
@pytest.mark.parametrize("key", ["v4_hybrid", "v5_collective"])
def test_pallas_tier_sharding_bitwise(workload, key, n):
    params, x, _, single_pallas = workload
    got = np.asarray(build_forward(REGISTRY[key], BLOCKS12, n_shards=n)(params, x))
    np.testing.assert_array_equal(got, single_pallas)


def test_pallas_tier_run_to_run_deterministic(workload):
    params, x, _, single_pallas = workload
    again = np.asarray(jax.jit(forward_blocks12_pallas)(params, x))
    np.testing.assert_array_equal(again, single_pallas)


def test_cross_tier_gap_is_real_and_bounded(workload):
    """Document the cross-tier reality: Pallas and XLA tiers are close but
    NOT bit-identical (different fp32 accumulation orders). If this ever
    becomes bitwise, the README claim can be upgraded."""
    _, _, single_xla, single_pallas = workload
    assert np.allclose(single_pallas, single_xla, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("n", [1, 2, 3, 4])
def test_pallas_tier_sharding_under_g8(workload, monkeypatch, n):
    """Pre-adoption guard for the queued g8 chip A/B: shard-vs-single
    under the phase-packed conv.

    The contract is parity-sensitive (this test found it): a shard whose
    global conv1 output-row start is ODD flips the local phase parity,
    which moves the zero-padding layout inside the phase weight frames —
    same real products, different reduction grouping — and drifted by
    last-ulps (2.3e-7 rel max) when 3 shards split conv1's 55 rows
    19/18/18. Since PR 41 the plan draws conv1's block from pool1's
    (``2 * b_pool1``: 20 rows at 3 shards), so every start is even and
    every case here is bitwise (docs/PALLAS_PERF.md).

    The single-device side passes ``variants`` EXPLICITLY: a bare
    ``jax.jit(forward_blocks12_pallas)`` after the fixture already traced
    the default variant would hit the jit cache and silently compare g8
    against vcol — the documented round-3 footgun the build-per-variant
    workflow exists to avoid (first version of this test did exactly
    that and produced a last-ulps false alarm)."""
    from cuda_mpi_gpu_cluster_programming_tpu.ops import pallas_kernels as pk

    monkeypatch.setenv("TPU_FRAMEWORK_CONV", "g8")
    params, x, _, _ = workload
    single = np.asarray(
        forward_blocks12_pallas(params, x, variants=pk.KernelVariants(conv="g8"))
    )
    got = np.asarray(
        build_forward(REGISTRY["v5_collective"], BLOCKS12, n_shards=n)(params, x)
    )
    np.testing.assert_array_equal(got, single)
