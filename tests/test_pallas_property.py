"""Property-based fuzzing of the Pallas kernels vs the XLA reference ops.

The hand-written kernels are the riskiest numerics in the framework (the
VMEM-OOM and bf16-reshape failures this round were both geometry-dependent
— found only when the real chip saw new shapes). These tests sweep random
geometry x stride x padding x variant through the interpreter-mode kernels
against `ops.reference`, so geometry edge cases (leftover rows, prime
dims, W-alignment padding, fq boundaries) are searched instead of
hand-picked. Deadlines are disabled: interpreter-mode pallas_call tracing
is slow and measured in seconds, not milliseconds.
"""


import jax
import jax.numpy as jnp
import numpy as np
import pytest

# requirements.txt pins hypothesis, but containers built without dev extras
# must still COLLECT cleanly — skip this module instead of erroring the
# whole tier-1 collection.
pytest.importorskip("hypothesis", reason="hypothesis not installed")
from hypothesis import assume, given, settings, strategies as st  # noqa: E402

from cuda_mpi_gpu_cluster_programming_tpu.ops import reference as ops
from cuda_mpi_gpu_cluster_programming_tpu.ops import pallas_kernels as pk

_SETTINGS = dict(max_examples=12, deadline=None, derandomize=True)


def _rand(key, shape):
    return jax.random.normal(jax.random.PRNGKey(key), shape, jnp.float32)


@settings(**_SETTINGS)
@given(
    h=st.integers(7, 33),
    w_dim=st.integers(7, 33),
    c=st.sampled_from([1, 3, 4]),
    k=st.sampled_from([4, 8]),
    f=st.sampled_from([3, 5, 7]),
    stride=st.integers(1, 4),
    padding=st.integers(0, 3),
    relu=st.booleans(),
    variant=st.sampled_from(pk.CONV_VARIANTS),
)
def test_conv_matches_reference(h, w_dim, c, k, f, stride, padding, relu, variant):
    # Reject (regenerate) degenerate geometries instead of silently
    # passing; unreachable with today's ranges, load-bearing if widened.
    assume(h + 2 * padding >= f and w_dim + 2 * padding >= f)
    v = pk.KernelVariants(conv=variant)
    x = _rand(h * 31 + w_dim, (1, h, w_dim, c))
    w = _rand(f, (f, f, c, k)) * 0.2
    b = _rand(k, (k,)) * 0.1
    got = np.asarray(pk.conv2d_pallas(
        x, w, b, stride=stride, padding=padding, relu=relu, variant=v.conv,
        row_block=v.row_block,
    ))
    want = np.asarray(ops.conv2d(x, w, b, stride=stride, padding=padding))
    if relu:
        want = np.maximum(want, 0.0)
    assert got.shape == want.shape, (got.shape, want.shape)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


@settings(**_SETTINGS)
@given(
    h=st.integers(4, 30),
    w_dim=st.integers(4, 30),
    c=st.sampled_from([1, 8, 16]),
    window=st.sampled_from([2, 3]),
    stride=st.integers(1, 3),
)
def test_maxpool_matches_reference(h, w_dim, c, window, stride):
    assume(h >= window and w_dim >= window)
    x = _rand(h * 37 + w_dim, (2, h, w_dim, c))
    got = np.asarray(pk.maxpool_pallas(x, window=window, stride=stride))
    want = np.asarray(ops.maxpool(x, window=window, stride=stride))
    np.testing.assert_array_equal(got, want)  # max is exact


@settings(**_SETTINGS)
@given(
    c=st.sampled_from([4, 16, 32]),
    size=st.sampled_from([3, 5]),
    aos=st.booleans(),
)
def test_lrn_matches_reference(c, size, aos):
    x = _rand(c * 13 + size, (1, 6, 6, c))
    got = np.asarray(
        pk.lrn_pallas(x, size=size, alpha=1e-4, beta=0.75, k=2.0, alpha_over_size=aos)
    )
    want = np.asarray(
        ops.lrn(x, size=size, alpha=1e-4, beta=0.75, k=2.0, alpha_over_size=aos)
    )
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-7)


