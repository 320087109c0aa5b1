"""Blocks 1-2 forward pass on the Pallas kernel tier.

The counterpart of the reference's V3 device pass
(v3_cuda_only/src/alexnet_cuda.cu:22-95: malloc-all → H2D → 7 launches →
D2H), reduced to 5 fused launches (conv+bias+ReLU fused) with no manual
memory management — buffers are XLA-managed, eliminating V3/V4's measured
per-call cudaMalloc/weight-reupload bottleneck (PROBLEMS.txt:114-135).
"""

from __future__ import annotations


import jax
import jax.numpy as jnp

from ..models.alexnet import BLOCKS12, Blocks12Config
from . import pallas_kernels as pk
from . import scopes


def _chain_variant() -> str:
    """TPU_FRAMEWORK_CHAIN=pad128 runs block 1 with the channel axis
    zero-padded 96 -> 128 end to end: conv1 gains full MXU column fill
    (its N dim is the lane axis), pool1 runs on lane-aligned tiles (the
    measured 3.7x regime of the sep2 pool, scripts/pool_ab.py), and conv2
    contracts over 128 channels whose extra 32 are zeros. Padded lanes
    carry exact zeros through conv1 (zero weights, zero bias, relu(0)=0)
    and contribute exact +0.0 terms to conv2's accumulation — bitwise
    identical to the plain chain on TPU (fixed Mosaic accumulation
    order; verified on v5e), within 1 ulp on the CPU backend whose
    matmul retiles the larger contraction (tests/test_pallas.py).
    Measured on v5e b=128: no wall-clock delta vs plain (fp32 15.0 vs
    15.1 ms, bf16 3.886 vs 3.884) — conv fp32 sits at the
    precision-ceiling, not the fill limit, so the extra columns don't
    pay. Kept as a layout experiment. Same scope caveat as
    pallas_kernels.env_variant: resolved at trace time."""
    return pk.env_variant("TPU_FRAMEWORK_CHAIN", "plain", ("plain", "pad128"))


def _pad_axis(a: jax.Array, axis: int, to: int) -> jax.Array:
    if a.shape[axis] >= to:
        return a
    widths = [(0, 0)] * a.ndim
    widths[axis] = (0, to - a.shape[axis])
    return jnp.pad(a, widths)


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
    Both share the geometry regime: taps/vcol lowering, sep2 pool, whole
    image per program, no K-blocking. hpool is bitwise identical either
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
        variant=v.conv, row_block=v.row_block, k_block=v.k_block,
        pool=v.pool, out_h=ho, pool_window=pspec.window,
    ):
        with scopes.layer(*names):
            return mk.conv_block_pallas(
                x, w, b, stride=cspec.stride, padding=cspec.padding,
                pool_window=pspec.window, pool_stride=pspec.stride,
                lrn=lrn, variant=v.conv, row_block=v.row_block,
            )
    if (
        v.fuse == "hpool"
        and v.conv in ("taps", "vcol")
        and v.pool == "sep2"
        and v.row_block >= ho
        and v.k_block == 0
    ):
        with scopes.layer(conv_name, pool_name):  # the pool's H stage rides the conv
            y = pk.conv2d_pallas(
                x, w, b, stride=cspec.stride, padding=cspec.padding, relu=True,
                variant=v.conv, row_block=v.row_block, k_block=0,
                hpool=(pspec.window, pspec.stride),
            )
        with scopes.layer(pool_name):
            out = pk.maxpool_pallas_w(y, window=pspec.window, stride=pspec.stride)
    else:
        with scopes.layer(conv_name):
            y = pk.conv2d_pallas(
                x, w, b, stride=cspec.stride, padding=cspec.padding, relu=True,
                variant=v.conv, row_block=v.row_block, k_block=v.k_block,
            )
        with scopes.layer(pool_name):
            out = pk.maxpool_pallas(
                y, window=pspec.window, stride=pspec.stride, variant=v.pool
            )
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
    chain: str | None = None,
) -> jax.Array:
    """``variants``/``chain``: explicit lowering choices. Build-time callers
    (configs.build_forward) resolve them eagerly and pass them in, so the
    selection is part of the function they jit — re-building after an env
    flip picks up the new variant (the round-3 footgun fix). ``variants``
    may be one KernelVariants for every layer or a per-layer LayerVariants
    plan (tuning/). Direct callers may omit them (env/defaults resolve at
    trace time, as before)."""
    v = variants if variants is not None else pk.KernelVariants.resolve()
    c1, p1, c2, p2, n2 = cfg.conv1, cfg.pool1, cfg.conv2, cfg.pool2, cfg.lrn2
    pad128 = (chain if chain is not None else _chain_variant()) == "pad128"
    w1, b1 = params["conv1"]["w"], params["conv1"]["b"]
    w2, b2 = params["conv2"]["w"], params["conv2"]["b"]
    conv1, pool1, conv2, pool2, lrn2 = scopes.BLOCKS12_LAYERS
    if pad128:
        kp = -(-w1.shape[-1] // 128) * 128  # conv1 output channels -> 128
        with scopes.layer(conv1):
            w1, b1 = _pad_axis(w1, 3, kp), _pad_axis(b1, 0, kp)
        with scopes.layer(conv2):
            w2 = _pad_axis(w2, 2, kp)  # conv2 contraction axis: zero rows
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
    v = variants if variants is not None else pk.KernelVariants.resolve()
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
                    k_block=lv.k_block,
                )
        elif isinstance(spec, PoolSpec):
            with scopes.layer(name):
                x = pk.maxpool_pallas(
                    x, window=spec.window, stride=spec.stride, variant=lv.pool
                )
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
