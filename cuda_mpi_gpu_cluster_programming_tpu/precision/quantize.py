"""Symmetric per-output-channel int8 weight quantization + the dequant-free
forward path.

Scheme (the ``int8w`` policy, docs/PRECISION.md):

- **Calibration** comes from the seeded init stream: scales are derived
  from the actual weights the keyed initializers drew, so two processes
  with the same seed quantize identically — no calibration dataset, no
  activation statistics (weights only).
- **Per output channel, symmetric**: for conv weights ``(F, F, C, K)`` each
  output channel k gets ``scale[k] = max|w[..., k]| / 127`` and
  ``q = clip(round(w / scale), -127, 127)`` as int8. Roundtrip error is
  bounded by ``scale/2`` elementwise (tests hold this).
- **Dequant-free compute**: the contraction runs on the RAW quantized
  values cast to bf16 (integers up to 127 are exact in bf16's 8-bit
  mantissa) with fp32 accumulation (explicit ``preferred_element_type`` —
  the accumulation dtype is stated, never inferred), and the per-channel
  ``scale`` multiplies the conv OUTPUT once, before bias and ReLU:
  ``relu(conv(x_bf16, q_bf16) * scale + b)``. Weights are never
  materialized in fp32/bf16 dequantized form — HBM traffic for the filter
  banks drops 4x vs fp32, 2x vs bf16.

Both op tiers are covered: the reference tier lowers through
``lax.conv_general_dilated`` and the Pallas tier through
``ops.pallas_kernels.conv2d_pallas`` with the fused bias/ReLU epilogue
DISABLED (``relu=False``, zero bias) because the channel rescale must
land between the accumulation and the bias add — which is also why the
``hpool`` epilogue fusion is pruned from the int8w candidate space
(``tuning.space.prune_reason``).
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..models.alexnet import BLOCKS12, Blocks12Config
from ..ops import scopes

QMAX = 127  # symmetric int8: [-127, 127]; -128 is unused (no zero-point)


def quantize_channelwise(w: jax.Array, qmax: int = QMAX) -> Tuple[jax.Array, jax.Array]:
    """(q_int8, scale_f32) for a weight tensor whose LAST axis is the
    output-channel axis (HWIO convs and (in, out) matmuls alike).

    ``scale[k] = max|w[..., k]| / qmax`` (1.0 for an all-zero channel so
    the divide is safe and q stays zero); ``q = clip(round(w/scale))``."""
    reduce_axes = tuple(range(w.ndim - 1))
    amax = jnp.max(jnp.abs(w.astype(jnp.float32)), axis=reduce_axes)
    scale = jnp.where(amax > 0, amax / qmax, 1.0).astype(jnp.float32)
    q = jnp.clip(jnp.round(w.astype(jnp.float32) / scale), -qmax, qmax)
    return q.astype(jnp.int8), scale


def dequantize(q: jax.Array, scale: jax.Array) -> jax.Array:
    """fp32 reconstruction (tests/error-bound checks; the forward path never
    calls this — that is the point of the dequant-free layout)."""
    return q.astype(jnp.float32) * scale


def quantize_conv_params(params) -> dict:
    """Per-layer ``{"q", "scale", "b"}`` for every conv entry of a Blocks
    1-2 style param dict. Biases stay fp32 (they are added after the
    rescale, in the accumulation dtype)."""
    out = {}
    for name, p in params.items():
        if isinstance(p, dict) and "w" in p:
            q, scale = quantize_channelwise(p["w"])
            out[name] = {"q": q, "scale": scale, "b": p["b"]}
    return out


def int8w_conv(
    x: jax.Array,
    q: jax.Array,
    scale: jax.Array,
    b: jax.Array,
    *,
    stride: int,
    padding: int,
    relu: bool = True,
    tier: str = "reference",
    variants=None,
) -> jax.Array:
    """One dequant-free int8-weight conv: ``relu(conv(x, q)*scale + b)``.

    ``x`` enters in (or is cast to) bf16; the int8 ``q`` is cast to bf16
    (exact for |q| <= 127) so the MXU's native bf16 MACs apply; the
    accumulate dtype is pinned fp32; rescale/bias/ReLU run in fp32 and the
    result returns to bf16 for the next stage."""
    xq = x.astype(jnp.bfloat16)
    wq = q.astype(jnp.bfloat16)
    if tier == "pallas":
        from ..ops import pallas_kernels as pk

        v = variants if variants is not None else pk.KernelVariants()
        # Fused epilogue off: the channel rescale must land between the
        # kernel's fp32 accumulation and the bias add.
        y = pk.conv2d_pallas(
            xq, wq, jnp.zeros((q.shape[-1],), jnp.bfloat16),
            stride=stride, padding=padding, relu=False,
            variant=v.conv, row_block=v.row_block, k_block=v.k_block,
        ).astype(jnp.float32)
    else:
        y = lax.conv_general_dilated(
            xq,
            wq,
            window_strides=(stride, stride),
            padding=[(padding, padding), (padding, padding)],
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            preferred_element_type=jnp.float32,
        )
    y = y * scale + b.astype(jnp.float32)
    if relu:
        y = jnp.maximum(y, 0.0)
    return y.astype(jnp.bfloat16)


def int8w_conv_then_pool(x, q, scale, b, cspec, pspec, names, v=None, *, tier="pallas", lrn=None):
    """The int8w lowering unit the dtype sweep times — the quantized
    counterpart of ``ops.pallas_model._conv_then_pool`` (conv + rescale +
    bias + ReLU, then the trailing max pool under the same per-layer
    variant plan). ``v.fuse == "block"`` routes the whole block through the
    dequant-free megakernel (``ops.megakernel.int8w_conv_block_pallas``)
    where the geometry gate allows: per-channel rescale in the epilogue on
    the UNCAST fp32 accumulator — which the staged chain cannot do (its
    conv kernel writes bf16 before the host rescale), so megakernel int8w
    parity is tolerance-gated, not bitwise. ``lrn`` (a LrnSpec) folds the
    block's trailing LRN in either way — fused in-kernel, staged via the
    fp32 reference LRN (the same op ``forward_blocks12_int8w`` uses).
    ``names``: the layers' scope names, as ``_conv_then_pool`` takes them."""
    conv_name, pool_name = names[:2]
    ho = (x.shape[1] + 2 * cspec.padding - cspec.filter_size) // cspec.stride + 1
    if tier == "pallas" and v is not None and v.fuse == "block":
        from ..ops import megakernel as mk

        if not mk.block_fusible_reason(
            variant=v.conv, row_block=v.row_block, k_block=v.k_block,
            pool=v.pool, out_h=ho, pool_window=pspec.window,
        ):
            with scopes.layer(*names):
                return mk.int8w_conv_block_pallas(
                    x, q, scale, b, stride=cspec.stride, padding=cspec.padding,
                    pool_window=pspec.window, pool_stride=pspec.stride,
                    lrn=lrn, variant=v.conv, row_block=v.row_block,
                )
    with scopes.layer(conv_name):
        y = int8w_conv(
            x, q, scale, b, stride=cspec.stride, padding=cspec.padding,
            relu=True, tier=tier, variants=v,
        )
    with scopes.layer(pool_name):
        if tier == "pallas":
            from ..ops import pallas_kernels as pk

            pool_variant = v.pool if v is not None else None
            out = pk.maxpool_pallas(
                y, window=pspec.window, stride=pspec.stride, variant=pool_variant
            )
        else:
            from ..ops import reference as ops

            out = ops.maxpool(y, window=pspec.window, stride=pspec.stride)
    if lrn is not None:
        from ..ops import reference as ops

        with scopes.layer(names[2]):
            out = ops.lrn(
                out.astype(jnp.float32),
                size=lrn.size, alpha=lrn.alpha, beta=lrn.beta, k=lrn.k,
                alpha_over_size=lrn.alpha_over_size,
            )
    return out


def forward_blocks12_int8w(
    params,
    x: jax.Array,
    cfg: Blocks12Config = BLOCKS12,
    variants=None,
    tier: str = "reference",
    taps: bool = False,
):
    """Blocks 1-2 forward under the ``int8w`` policy (both op tiers).

    Quantization happens in-graph from the fp32 params (calibration == the
    seeded init stream that drew them), so the function keeps the standard
    ``(params, x) -> out`` shape every builder/caller expects. Activations
    ride bf16 between stages; LRN computes in fp32 (squares + pow need the
    headroom) and the final output is fp32, matching the bf16 path's
    output contract.

    ``taps=True`` additionally returns ``{stage: fp32 array}`` at every
    layer boundary — the per-stage surface the ``ToleranceGate`` screens
    against the fp32 oracle."""
    from ..ops.pallas_model import _layer_variants
    from ..ops import pallas_kernels as pk

    with scopes.cast_in():  # parameters to int8, input to bf16
        qp = quantize_conv_params(params)
        cur = x.astype(jnp.bfloat16)
    c1, p1, c2, p2, n2 = cfg.conv1, cfg.pool1, cfg.conv2, cfg.pool2, cfg.lrn2
    conv1, pool1, conv2, pool2, lrn2 = scopes.BLOCKS12_LAYERS
    v = variants if variants is not None else pk.KernelVariants()
    stages = {}

    def tap(name, arr):
        if taps:
            stages[name] = arr.astype(jnp.float32)

    if tier == "pallas" and not taps and any(
        _layer_variants(v, n).fuse == "block" for n in ("conv1", "conv2")
    ):
        # Megakernel route: each block is one VMEM-resident pass (the
        # trailing LRN folds into block 2). Taps callers (the gate's
        # staged-oracle surface) stay on the staged chain below — a fused
        # block has no interior boundaries to tap; the gate screens fused
        # outputs at BLOCK granularity instead (precision.gate
        # ``screen_blocks``).
        e1, e2 = qp[conv1], qp[conv2]
        cur = int8w_conv_then_pool(
            cur, e1["q"], e1["scale"], e1["b"], c1, p1, (conv1, pool1),
            _layer_variants(v, conv1), tier=tier,
        )
        return int8w_conv_then_pool(
            cur, e2["q"], e2["scale"], e2["b"], c2, p2, (conv2, pool2, lrn2),
            _layer_variants(v, conv2), tier=tier, lrn=n2,
        )

    from ..ops import reference as ops

    for cname, cspec, pname, pspec in (
        (conv1, c1, pool1, p1),
        (conv2, c2, pool2, p2),
    ):
        lv = _layer_variants(v, cname)
        e = qp[cname]
        with scopes.layer(cname):
            cur = int8w_conv(
                cur, e["q"], e["scale"], e["b"],
                stride=cspec.stride, padding=cspec.padding, relu=True,
                tier=tier, variants=lv,
            )
        tap(cname, cur)
        with scopes.layer(pname):
            if tier == "pallas":
                cur = pk.maxpool_pallas(
                    cur, window=pspec.window, stride=pspec.stride, variant=lv.pool
                )
            else:
                cur = ops.maxpool(cur, window=pspec.window, stride=pspec.stride)
        tap(pname, cur)
    with scopes.layer(lrn2):
        out = ops.lrn(
            cur.astype(jnp.float32),
            size=n2.size, alpha=n2.alpha, beta=n2.beta, k=n2.k,
            alpha_over_size=n2.alpha_over_size,
        )
    tap(lrn2, out)
    return (out, stages) if taps else out


def roundtrip_error_bound(w: jax.Array) -> jax.Array:
    """Elementwise quantization error bound, ``scale/2`` broadcast to the
    weight shape — what tests assert the actual roundtrip error against."""
    _q, scale = quantize_channelwise(w)
    return jnp.broadcast_to(scale / 2.0, w.shape)
