"""Reference op tier: the four layer ops in pure JAX/XLA.

Semantics match the reference's serial CPU layer library
(v1_serial/src/layers_serial.cpp:37-175): direct convolution with symmetric
zero padding, in-place ReLU, VALID max-pool, and cross-channel LRN with
edge-truncated windows. The reference computes in fp32 with HWC-interleaved
activations (idx3D, layers_serial.cpp:15-17) and K,C,F,F weights
(layers_serial.cpp:70); here activations are batched NHWC (the TPU-friendly
layout — C maps to VPU lanes) and weights are HWIO ``(F, F, C, K)``.
Converters to/from the reference layout live in ``models.init``.

Everything here is jit-friendly: static shapes, no Python control flow on
traced values, so XLA can fuse bias+ReLU into the conv, tile the matmuls
onto the MXU, and make the whole LRN one fusion round its band product
(squares in, scale, power and divide in the epilogue).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


def mxu_precision(dtype):
    """Matmul precision by INPUT dtype, one rule for every op and kernel.
    fp32 inputs: HIGHEST = true fp32 MACs on the MXU (the default would
    round the operands to bf16 and miss the reference numerics by ~1e-3
    rel). bf16 inputs: native bf16 MACs, fp32 accumulation."""
    return lax.Precision.HIGHEST if dtype == jnp.float32 else lax.Precision.DEFAULT


def conv2d(
    x: jax.Array,
    w: jax.Array,
    b: jax.Array,
    *,
    stride: int,
    padding: int,
    precision: lax.PrecisionLike = lax.Precision.HIGHEST,
    preferred_element_type=None,
) -> jax.Array:
    """Direct 2-D convolution (cross-correlation) with bias.

    Args:
      x: activations ``(N, H, W, C)``.
      w: filters ``(F, F, C, K)`` (HWIO).
      b: biases ``(K,)``.
      stride: spatial stride (same for H and W).
      padding: symmetric zero padding (same for H and W).

    Reference parity: ``serialConvLayer`` (v1_serial/src/layers_serial.cpp:37-81)
    — 7 nested loops, zero padding, bias added per output channel. The
    reference computes correlation (no filter flip), as does lax.conv.

    ``precision`` defaults to HIGHEST (true fp32 MACs) so this tier matches
    the reference's fp32 numerics on TPU, where the MXU's default precision
    would otherwise compute in bf16; perf-oriented configs pass
    ``lax.Precision.DEFAULT`` explicitly.

    ``preferred_element_type`` pins the accumulation dtype — the precision
    subsystem's mixed-dtype paths (bf16/int8w policies, precision.gate)
    thread fp32 here so the accumulation width is stated, never inferred
    (the staticcheck ``implicit-upcast`` contract).
    """
    out = lax.conv_general_dilated(
        x,
        w,
        window_strides=(stride, stride),
        padding=[(padding, padding), (padding, padding)],
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=precision,
        preferred_element_type=preferred_element_type,
    )
    return out + b.astype(out.dtype)


def relu(x: jax.Array) -> jax.Array:
    """Elementwise max(0, x).

    Reference parity: ``serialReluLayer`` (v1_serial/src/layers_serial.cpp:85-90).
    """
    return jnp.maximum(x, jnp.zeros((), dtype=x.dtype))


def maxpool(x: jax.Array, *, window: int, stride: int) -> jax.Array:
    """VALID max pooling over ``window``×``window`` with the given stride.

    Reference parity: ``serialMaxPoolLayer`` (v1_serial/src/layers_serial.cpp:94-129)
    — no padding, window max.
    """
    # Python-scalar init (not jnp.array): under jit the latter becomes a
    # tracer, defeating JAX's max-monoid recognition and losing autodiff.
    return lax.reduce_window(
        x,
        -float("inf"),
        lax.max,
        window_dimensions=(1, window, window, 1),
        window_strides=(1, stride, stride, 1),
        padding="VALID",
    )


def lrn(
    x: jax.Array,
    *,
    size: int,
    alpha: float,
    beta: float,
    k: float,
    alpha_over_size: bool = False,
) -> jax.Array:
    """Cross-channel local response normalization.

    ``out[c] = x[c] / (k + a * sum_{j in win(c)} x[j]^2) ** beta`` with
    ``a = alpha/size`` when ``alpha_over_size`` else ``a = alpha``, and
    ``win(c) = [max(0, c-size//2), min(C-1, c+size//2)]`` — the window is
    truncated at channel edges without renormalizing by the actual count.

    The reference disagrees with itself on ``a``: its CPU layers use
    ``alpha*sumSq/N`` (v1_serial/src/layers_serial.cpp:168,
    2.2_scatter_halo/src/layers_mpi.cpp:81 → printed ``44.4152 42.4612
    40.6967...``) while its CUDA kernels use ``alpha*sum`` with no ``/N``
    (v3_cuda_only/src/layers_cuda.cu:139, v4_mpi_cuda/src/layers_mpi_cuda.cu:86
    → the headline golden ``29.2932 25.9153 23.3255...``). Both forms are
    supported; the default is the CUDA form, which every deterministic
    V3/V4 log in the reference's regression corpus was produced with. The
    CPU-vs-CUDA divide-vs-``powf(scale,-beta)`` discrepancy is standardized
    here on the divide form across all tiers.

    The window sum is a product of the squares with a banded 0/1 matrix
    (``band[i, j] = |i - j| <= size//2``; its edges are the truncated
    windows), the formulation ``pallas_kernels._lrn_kernel`` has always
    used: channels are the lane axis, a ``reduce_window`` along it cost the
    v5e 0.088 ms for this layer's 5.5M elements (PERF.md, PR 30), and as a
    matmul it is 2.5% of conv2's MXU work, with the squares, the scale, the
    power and the divide fused round it into one kernel. Precision by the
    tier's rule (``mxu_precision``): fp32 squares at HIGHEST, bf16 squares
    native. The sum accumulates in float32 and the scale, power and divide
    run on it in float32 in every compute type (a bf16 ``reduce_window``
    added in bf16); the result is cast back to ``x.dtype``.
    """
    half = size // 2
    c = x.shape[-1]
    ci = lax.broadcasted_iota(jnp.int32, (c, c), 0)
    cj = lax.broadcasted_iota(jnp.int32, (c, c), 1)
    band = (jnp.abs(ci - cj) <= half).astype(x.dtype)
    acc = jnp.promote_types(x.dtype, jnp.float32)
    ssum = lax.dot_general(
        x * x,
        band,
        (((x.ndim - 1,), (0,)), ((), ())),
        precision=mxu_precision(x.dtype),
        preferred_element_type=acc,
    )
    a = alpha / size if alpha_over_size else alpha
    scale = k + a * ssum
    return (x.astype(acc) / scale**beta).astype(x.dtype)
