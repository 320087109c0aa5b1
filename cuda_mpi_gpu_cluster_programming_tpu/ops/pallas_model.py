"""Blocks 1-2 forward pass on the Pallas kernel tier.

The counterpart of the reference's V3 device pass
(v3_cuda_only/src/alexnet_cuda.cu:22-95: malloc-all → H2D → 7 launches →
D2H), reduced to 5 fused launches (conv+bias+ReLU fused) with no manual
memory management — buffers are XLA-managed, eliminating V3/V4's measured
per-call cudaMalloc/weight-reupload bottleneck (PROBLEMS.txt:114-135).
"""

from __future__ import annotations

import jax

from ..models.alexnet import BLOCKS12, Blocks12Config
from . import pallas_kernels as pk
from . import scopes


def _layer_variants(v, name: str) -> "pk.KernelVariants":
    """Dispatch a variants argument that is either one process-global
    ``KernelVariants`` (the historical shape) or a per-layer
    ``LayerVariants`` plan (the tuner's product) down to ONE layer's
    resolved knobs — the single point where the per-layer refactor meets
    the kernel wrappers."""
    return v.for_layer(name) if isinstance(v, pk.LayerVariants) else v


def _conv_then_pool(x, w, b, cspec, pspec, v: "pk.KernelVariants", names, lrn=None):
    """conv(+relu) then max-pool, the ONE place that decides whether the
    pool rides the conv pass — both forward builders route conv->pool
    adjacencies through here, so the geometry gates cannot drift between
    paths. ``fuse="hpool"`` fuses the pool's H stage into the conv
    epilogue; ``fuse="block"`` goes further and runs the whole block
    (conv+ReLU+pool, plus ``lrn`` when the caller passes the trailing
    LrnSpec) as one VMEM-resident megakernel pass (ops/megakernel.py).
    Both share the geometry regime: taps/vcol lowering, whole image per
    program. hpool is bitwise identical either
    way (_conv_epilogue); block is bitwise for fp32/bf16 (same
    accumulation order, same cast points — tests/test_megakernel.py).
    When ``lrn`` is given but the fused path is not taken, the trailing
    LRN still runs here (staged), so callers hand off the whole block
    either way.

    ``names``: the layers' scope names, ``(conv, pool)`` or ``(conv, pool,
    lrn)``. A kernel that covers several layers runs under all of their
    names (``conv1+pool1``), a staged layer under its own."""
    from . import megakernel as mk

    conv_name, pool_name = names[:2]
    ho = (x.shape[1] + 2 * cspec.padding - cspec.filter_size) // cspec.stride + 1
    if v.fuse == "block" and not mk.block_fusible_reason(
        variant=v.conv, row_block=v.row_block, out_h=ho, pool_window=pspec.window,
    ):
        with scopes.layer(*names):
            return mk.conv_block_pallas(
                x, w, b, stride=cspec.stride, padding=cspec.padding,
                pool_window=pspec.window, pool_stride=pspec.stride,
                lrn=lrn, variant=v.conv, row_block=v.row_block,
            )
    if v.fuse == "hpool" and v.conv in ("taps", "vcol") and v.row_block >= ho:
        with scopes.layer(conv_name, pool_name):  # the pool's H stage rides the conv
            y = pk.conv2d_pallas(
                x, w, b, stride=cspec.stride, padding=cspec.padding, relu=True,
                variant=v.conv, row_block=v.row_block,
                hpool=(pspec.window, pspec.stride),
            )
        with scopes.layer(pool_name):
            out = pk.maxpool_pallas_w(y, window=pspec.window, stride=pspec.stride)
    else:
        with scopes.layer(conv_name):
            y = pk.conv2d_pallas(
                x, w, b, stride=cspec.stride, padding=cspec.padding, relu=True,
                variant=v.conv, row_block=v.row_block,
            )
        with scopes.layer(pool_name):
            out = pk.maxpool_pallas(y, window=pspec.window, stride=pspec.stride)
    if lrn is not None:
        with scopes.layer(names[2]):
            out = pk.lrn_pallas(
                out, size=lrn.size, alpha=lrn.alpha, beta=lrn.beta, k=lrn.k,
                alpha_over_size=lrn.alpha_over_size,
            )
    return out


def forward_blocks12_pallas(
    params,
    x: jax.Array,
    cfg: Blocks12Config = BLOCKS12,
    variants: pk.KernelVariants | pk.LayerVariants | None = None,
) -> jax.Array:
    """``variants``: the lowering, one KernelVariants for every layer or a
    per-layer LayerVariants plan (tuning/); None runs the defaults,
    ``KernelVariants()``."""
    v = variants if variants is not None else pk.KernelVariants()
    c1, p1, c2, p2, n2 = cfg.conv1, cfg.pool1, cfg.conv2, cfg.pool2, cfg.lrn2
    w1, b1 = params["conv1"]["w"], params["conv1"]["b"]
    w2, b2 = params["conv2"]["w"], params["conv2"]["b"]
    conv1, pool1, conv2, pool2, lrn2 = scopes.BLOCKS12_LAYERS
    x = _conv_then_pool(
        x, w1, b1, c1, p1, _layer_variants(v, conv1), (conv1, pool1)
    )
    # Block 2's trailing LRN rides the conv->pool handoff so fuse="block"
    # can fold it into the same pass; staged paths run it after the pool.
    x = _conv_then_pool(
        x, w2, b2, c2, p2, _layer_variants(v, conv2), (conv2, pool2, lrn2), lrn=n2
    )
    return x


def forward_alexnet_pallas(
    params,
    x: jax.Array,
    cfg=None,
    variants: pk.KernelVariants | pk.LayerVariants | None = None,
) -> jax.Array:
    """Full AlexNet on the Pallas tier: chain-driven spatial part (fused
    conv+bias+ReLU launches), then the shared MXU-matmul FC head.
    ``variants``: see :func:`forward_blocks12_pallas`."""
    from ..models.alexnet import ConvSpec, LrnSpec, PoolSpec
    from ..models.alexnet_full import ALEXNET, fc_head

    cfg = cfg or ALEXNET
    v = variants if variants is not None else pk.KernelVariants()
    chain = list(cfg.layer_chain())
    skip_idx: set = set()
    for idx, (name, spec) in enumerate(chain):
        if idx in skip_idx:
            continue  # this pool/LRN was consumed by _conv_then_pool
        lv = _layer_variants(v, name)
        if isinstance(spec, ConvSpec):
            nxt = chain[idx + 1][1] if idx + 1 < len(chain) else None
            if isinstance(nxt, PoolSpec):
                # conv->pool adjacency: the shared helper owns the
                # fuse="hpool"/"block" decision (one gate for both
                # builders); the conv's per-layer plan also governs the
                # pool it feeds. A trailing LRN is part of the block.
                nxt2 = chain[idx + 2][1] if idx + 2 < len(chain) else None
                lrn = nxt2 if isinstance(nxt2, LrnSpec) else None
                names = tuple(n for n, _s in chain[idx : idx + (3 if lrn else 2)])
                x = _conv_then_pool(
                    x, params[name]["w"], params[name]["b"], spec, nxt, lv,
                    names, lrn=lrn,
                )
                skip_idx.add(idx + 1)
                if lrn is not None:
                    skip_idx.add(idx + 2)
                continue
            with scopes.layer(name):
                x = pk.conv2d_pallas(
                    x,
                    params[name]["w"],
                    params[name]["b"],
                    stride=spec.stride,
                    padding=spec.padding,
                    relu=True,
                    variant=lv.conv,
                    row_block=lv.row_block,
                )
        elif isinstance(spec, PoolSpec):
            with scopes.layer(name):
                x = pk.maxpool_pallas(x, window=spec.window, stride=spec.stride)
        elif isinstance(spec, LrnSpec):
            with scopes.layer(name):
                x = pk.lrn_pallas(
                    x,
                    size=spec.size,
                    alpha=spec.alpha,
                    beta=spec.beta,
                    k=spec.k,
                    alpha_over_size=spec.alpha_over_size,
                )
    return fc_head(params, x, cfg)
