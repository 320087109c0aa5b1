"""Pallas kernel tier vs reference tier — the V3≡V1 comparability the
reference never achieved (its CPU and CUDA paths genuinely disagreed)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cuda_mpi_gpu_cluster_programming_tpu.models import (
    BLOCKS12,
    deterministic_input,
    forward_blocks12,
    init_params_deterministic,
    init_params_random,
    random_input,
)
from cuda_mpi_gpu_cluster_programming_tpu.ops import conv2d, lrn, maxpool
from cuda_mpi_gpu_cluster_programming_tpu.ops import pallas_kernels as pk
from cuda_mpi_gpu_cluster_programming_tpu.ops.pallas_model import forward_blocks12_pallas


@pytest.fixture(scope="module")
def rng():
    return np.random.default_rng(3090)


def test_conv_kernel_vs_reference(rng):
    x = jnp.asarray(rng.standard_normal((2, 15, 15, 8)).astype(np.float32))
    w = jnp.asarray(rng.standard_normal((3, 3, 8, 16)).astype(np.float32))
    b = jnp.asarray(rng.standard_normal(16).astype(np.float32))
    got = pk.conv2d_pallas(x, w, b, stride=2, padding=1)
    want = conv2d(x, w, b, stride=2, padding=1)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_conv_kernel_fused_relu(rng):
    x = jnp.asarray(rng.standard_normal((1, 9, 9, 4)).astype(np.float32))
    w = jnp.asarray(rng.standard_normal((5, 5, 4, 8)).astype(np.float32))
    b = jnp.asarray(-np.abs(rng.standard_normal(8)).astype(np.float32))
    got = pk.conv2d_pallas(x, w, b, stride=1, padding=2, relu=True)
    want = jnp.maximum(conv2d(x, w, b, stride=1, padding=2), 0.0)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    assert float(got.min()) == 0.0  # negative bias guarantees some clamping


def test_conv_kernel_asymmetric_padding(rng):
    """H-valid / W-padded mode used by the sharded tier."""
    x = jnp.asarray(rng.standard_normal((1, 11, 9, 4)).astype(np.float32))
    w = jnp.asarray(rng.standard_normal((3, 3, 4, 8)).astype(np.float32))
    b = jnp.zeros(8, jnp.float32)
    got = pk.conv2d_pallas_hvalid(x, w, b, stride=1, padding_w=1)
    # oracle: pad W manually, VALID conv
    xp = jnp.pad(x, ((0, 0), (0, 0), (1, 1), (0, 0)))
    want = conv2d(xp, w, b, stride=1, padding=0)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_pool_kernel_vs_reference(rng):
    x = jnp.asarray(rng.standard_normal((3, 13, 13, 32)).astype(np.float32))
    got = pk.maxpool_pallas(x, window=3, stride=2)
    want = maxpool(x, window=3, stride=2)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("aos", [False, True])
def test_lrn_kernel_vs_reference(rng, aos):
    x = jnp.asarray(rng.standard_normal((2, 5, 5, 16)).astype(np.float32))
    got = pk.lrn_pallas(x, size=5, alpha=1e-4, beta=0.75, k=2.0, alpha_over_size=aos)
    want = lrn(x, size=5, alpha=1e-4, beta=0.75, k=2.0, alpha_over_size=aos)
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-7)


def test_relu_kernel():
    x = jnp.asarray([[-2.0, 0.0, 3.5]])
    np.testing.assert_array_equal(np.asarray(pk.relu_pallas(x)), [[0.0, 0.0, 3.5]])


def test_full_model_golden():
    """Pallas tier must hit the same golden values as the reference tier."""
    params = init_params_deterministic()
    x = deterministic_input(batch=1)
    out = forward_blocks12_pallas(params, x)
    flat = np.asarray(out[0]).reshape(-1)
    golden = [29.2932, 25.9153, 23.3255]
    np.testing.assert_allclose(flat[:3], golden, rtol=2e-5)
    want = np.asarray(jax.jit(forward_blocks12)(params, x))
    np.testing.assert_allclose(np.asarray(out), want, rtol=1e-5, atol=1e-5)


def test_full_model_random_batch():
    key = jax.random.PRNGKey(42)
    kp, kx = jax.random.split(key)
    params = init_params_random(kp)
    x = random_input(kx, batch=2)
    got = np.asarray(forward_blocks12_pallas(params, x))
    want = np.asarray(jax.jit(forward_blocks12)(params, x))
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


def test_small_geometry():
    cfg = dataclasses.replace(BLOCKS12, in_height=63, in_width=63)
    params = init_params_deterministic(cfg)
    x = jax.random.uniform(jax.random.PRNGKey(1), (1, 63, 63, 3))
    got = np.asarray(forward_blocks12_pallas(params, x, cfg))
    want = np.asarray(jax.jit(lambda p, v: forward_blocks12(p, v, cfg))(params, x))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_conv_pallas_leftover_rows():
    """(H - F) % S != 0 geometries must crop, not crash (230 -> 55 rows)."""
    import jax
    import jax.numpy as jnp
    from cuda_mpi_gpu_cluster_programming_tpu.ops import reference as ops
    from cuda_mpi_gpu_cluster_programming_tpu.ops.pallas_kernels import conv2d_pallas

    key = jax.random.PRNGKey(7)
    kx, kw = jax.random.split(key)
    x = jax.random.uniform(kx, (1, 230, 230, 3), jnp.float32)
    w = jax.random.uniform(kw, (11, 11, 3, 8), jnp.float32)
    b = jnp.zeros((8,), jnp.float32)
    got = conv2d_pallas(x, w, b, stride=4, padding=0)
    want = ops.conv2d(x, w, b, stride=4, padding=0)
    assert got.shape == want.shape == (1, 55, 55, 8)
    assert jnp.allclose(got, want, atol=1e-4)


def test_maxpool_pallas_even_window_leftover():
    """window=2 stride=2 on odd H: stride-phase views longer than hp must crop."""
    import jax
    import jax.numpy as jnp
    from cuda_mpi_gpu_cluster_programming_tpu.ops import reference as ops
    from cuda_mpi_gpu_cluster_programming_tpu.ops.pallas_kernels import maxpool_pallas

    x = jax.random.uniform(jax.random.PRNGKey(3), (1, 11, 11, 4), jnp.float32)
    got = maxpool_pallas(x, window=2, stride=2)
    want = ops.maxpool(x, window=2, stride=2)
    assert got.shape == want.shape == (1, 5, 5, 4)
    assert jnp.array_equal(got, want)


def _conv(x, w, b, v=pk.KernelVariants(), **kw):
    """conv2d_pallas under the lowering ``v`` names."""
    return pk.conv2d_pallas(x, w, b, variant=v.conv, row_block=v.row_block, **kw)


def test_conv_row_block_variant_bitwise():
    """The row block changes only the grid tiling, not the per-output
    accumulation order -> every setting is bitwise identical (default is
    64 since the 2026-07-31 on-chip sweep adopted it)."""
    # Tall-narrow input: ho = (267-11)/4+1 = 65, so 8/16/32/64 produce
    # genuinely different grids (nbh 9/5/3/2) — a square 67x67 input
    # (ho=15) silently clamped 16/32/64 to the same single-block lowering
    # and compared a kernel to itself (review finding, 2026-07-31).
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 267, 31, 3))
    w = jax.random.normal(jax.random.PRNGKey(6), (11, 11, 3, 16)) * 0.1
    b = jnp.zeros((16,))
    rdef = np.asarray(_conv(x, w, b, stride=4))
    for rb in (8, 16, 32):
        got = _conv(x, w, b, pk.KernelVariants(row_block=rb), stride=4)
        np.testing.assert_array_equal(np.asarray(got), rdef)


def test_conv_vcol_variant_matches_taps():
    """vcol (in-kernel im2col over the qw taps — the adopted round-5
    default) agrees with the tap-loop lowering to reduction-reorder
    tolerance at conv1-like (stride 4, fq=3) and conv2-like (stride 1,
    fq=5) geometry, and is deterministic within-variant."""
    taps_v, vcol_v = pk.KernelVariants(conv="taps"), pk.KernelVariants(conv="vcol")
    x = jax.random.normal(jax.random.PRNGKey(11), (2, 31, 31, 3))
    w = jax.random.normal(jax.random.PRNGKey(12), (11, 11, 3, 16)) * 0.1
    b = jnp.ones((16,)) * 0.1
    taps = np.asarray(_conv(x, w, b, taps_v, stride=4, relu=True))
    vcol = np.asarray(_conv(x, w, b, vcol_v, stride=4, relu=True))
    vcol2 = np.asarray(_conv(x, w, b, vcol_v, stride=4, relu=True))
    np.testing.assert_allclose(vcol, taps, rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(vcol, vcol2)  # deterministic

    w5 = jax.random.normal(jax.random.PRNGKey(13), (5, 5, 3, 8)) * 0.1
    b5 = jnp.zeros((8,))
    taps5 = np.asarray(_conv(x, w5, b5, taps_v, stride=1, padding=2))
    vcol5 = np.asarray(_conv(x, w5, b5, vcol_v, stride=1, padding=2))
    np.testing.assert_allclose(vcol5, taps5, rtol=1e-5, atol=1e-6)


def test_conv_g8_variant_matches_taps():
    """g8 (phase-packed conv: space-to-depth at g=2s, 2x2 output phases on
    separate grid programs, host-side de-interleave) agrees with the
    tap-loop lowering at strided geometries — conv1-like (s=4, odd
    output), s=2 with padding, s=3 — and falls back to vcol at s=1, where
    there are no phases to pack."""
    taps_v, g8_v = pk.KernelVariants(conv="taps"), pk.KernelVariants(conv="g8")
    x = jax.random.normal(jax.random.PRNGKey(14), (2, 37, 37, 3))
    for f, s, pad, relu in [(11, 4, 0, True), (5, 2, 1, True), (7, 3, 2, False)]:
        w = jax.random.normal(jax.random.PRNGKey(15), (f, f, 3, 16)) * 0.1
        b = jnp.ones((16,)) * 0.1
        kw = dict(stride=s, padding=pad, relu=relu)
        taps = np.asarray(_conv(x, w, b, taps_v, **kw))
        g8 = np.asarray(_conv(x, w, b, g8_v, **kw))
        g8b = np.asarray(_conv(x, w, b, g8_v, **kw))
        assert g8.shape == taps.shape
        np.testing.assert_allclose(g8, taps, rtol=1e-5, atol=1e-6)
        np.testing.assert_array_equal(g8, g8b)  # deterministic

    # s=1: g8 degrades to the vcol lowering (bitwise same as explicit vcol)
    w1 = jax.random.normal(jax.random.PRNGKey(16), (3, 3, 3, 8)) * 0.1
    b1 = jnp.zeros((8,))
    vc = np.asarray(_conv(x, w1, b1, pk.KernelVariants(conv="vcol"), stride=1, padding=1))
    g1 = np.asarray(_conv(x, w1, b1, g8_v, stride=1, padding=1))
    np.testing.assert_array_equal(g1, vc)


def test_conv_hpool_fusion_bitwise():
    """conv2d_pallas(hpool=...) + maxpool_pallas_w (the fused separable
    pool, the round-5 fuse="hpool" lever) is bitwise identical to
    conv then maxpool_pallas: the in-kernel H stage pools the CASTED
    value — exactly the tensor the unfused sep2 H stage reads back — and
    max is exact. Covers fp32 + bf16, relu, odd pooled heights, and both
    conv variants the fusion supports; plus the model-level flag."""
    import numpy as np

    from cuda_mpi_gpu_cluster_programming_tpu.models.init import (
        deterministic_input, init_params_deterministic)
    from cuda_mpi_gpu_cluster_programming_tpu.ops import pallas_kernels as pk
    from cuda_mpi_gpu_cluster_programming_tpu.ops.pallas_model import (
        forward_blocks12_pallas)

    key = jax.random.PRNGKey(17)
    for dt in (jnp.float32, jnp.bfloat16):
        for cv in ("vcol", "taps"):
            x = jax.random.normal(key, (2, 67, 67, 3), dt)
            w = (jax.random.normal(key, (11, 11, 3, 16)) * 0.1).astype(dt)
            b = jax.random.normal(key, (16,), dt)
            ref = pk.maxpool_pallas(
                pk.conv2d_pallas(
                    x, w, b, stride=4, relu=True, variant=cv, row_block=64
                ),
                window=3, stride=2,
            )
            fused = pk.maxpool_pallas_w(
                pk.conv2d_pallas(
                    x, w, b, stride=4, relu=True, variant=cv, row_block=64,
                    hpool=(3, 2),
                ),
                window=3, stride=2,
            )
            np.testing.assert_array_equal(
                np.asarray(ref.astype(jnp.float32)),
                np.asarray(fused.astype(jnp.float32)),
            )

    # Guard rails: unsupported variant / insufficient row block are errors,
    # not silent fallbacks (the model builder is the fallback layer).
    import pytest

    x = jnp.ones((1, 67, 67, 3))
    w = jnp.ones((11, 11, 3, 16))
    b = jnp.zeros((16,))
    with pytest.raises(ValueError, match="taps/vcol"):
        pk.conv2d_pallas(x, w, b, stride=4, variant="g8", hpool=(3, 2))
    with pytest.raises(ValueError, match="whole image"):
        pk.conv2d_pallas(
            x, w, b, stride=4, variant="vcol", row_block=8, hpool=(3, 2)
        )

    # Model-level: the fuse flag changes nothing numerically (golden run).
    p = init_params_deterministic()
    xi = deterministic_input(batch=1)
    base = np.asarray(forward_blocks12_pallas(p, xi, variants=pk.KernelVariants()))
    fz = np.asarray(
        forward_blocks12_pallas(p, xi, variants=pk.KernelVariants(fuse="hpool"))
    )
    np.testing.assert_array_equal(base, fz)


@pytest.mark.parametrize(
    "field, value",
    [("conv", "pairs"), ("conv", "fused"), ("conv", "bogus"), ("row_block", 12),
     ("fuse", "bogus")],
)
def test_a_lowering_outside_the_kept_set_is_refused(field, value):
    """A KernelVariants names only the kept lowerings (taps/vcol/g8, row
    blocks 8-64, fuse none/hpool/block): anything else — the chip's deleted
    losers and plain typos alike — raises where it is built, naming the
    field. A conv name handed straight to the kernel raises too, where it
    used to run taps."""
    with pytest.raises(ValueError, match=f"KernelVariants.{field}"):
        pk.KernelVariants(**{field: value})
    if field == "conv":
        x = jnp.ones((1, 15, 15, 3))
        w = jnp.ones((3, 3, 3, 4))
        with pytest.raises(ValueError, match=repr(value)):
            pk.conv2d_pallas(x, w, jnp.zeros((4,)), stride=1, variant=value)
