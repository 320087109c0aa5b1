"""Legal candidate space of the kernel autotuner.

One conv layer (plus the pool it feeds, when adjacent) is the tuning unit —
exactly the granularity ``ops.pallas_model._conv_then_pool`` lowers at. The
space is the cartesian product of every ``KernelVariants`` knob (3 conv
lowerings x 4 row blocks x 3 fusions = 36), PRUNED to combinations that
can actually lower and DEDUPED to distinct effective lowerings, so the
sweep never spends timing budget on a candidate that silently falls back
(g8 at stride 1 runs vcol) or aliases another (row blocks beyond the
output height all clamp to whole-image programs).

Every prune is attributable: ``prune_reason`` returns WHY a combo is out,
and ``candidate_space`` can report each drop to a logger — no silent caps.
"""

from __future__ import annotations

import dataclasses
import itertools
from typing import Callable, List, Optional, Tuple

from ..ops.megakernel import block_fusible_reason
from ..ops.pallas_kernels import CONV_VARIANTS, FUSES, ROW_BLOCKS, KernelVariants


@dataclasses.dataclass(frozen=True)
class ConvGeometry:
    """One conv layer's tuning-relevant geometry (+ its trailing pool)."""

    name: str
    filter_size: int
    stride: int
    padding: int
    in_channels: int
    out_channels: int
    in_h: int
    in_w: int
    pool_window: int = 0  # 0 = no adjacent pool
    pool_stride: int = 0
    # The LRN trailing the pool, when the model has one: (size, alpha,
    # beta, k, alpha_over_size) as a hashable tuple, () = none. Part of
    # the tuning unit because fuse="block" folds it into the megakernel
    # pass — and the timer must include it for STAGED candidates too, or
    # fused-vs-staged timings would compare unequal work.
    lrn: tuple = ()

    @property
    def out_h(self) -> int:
        return (self.in_h - self.filter_size + 2 * self.padding) // self.stride + 1

    @property
    def has_pool(self) -> bool:
        return self.pool_window > 0

    def describe(self) -> str:
        pool = f" pool={self.pool_window}/{self.pool_stride}" if self.has_pool else ""
        return (
            f"{self.name}: {self.filter_size}x{self.filter_size}s{self.stride}"
            f"p{self.padding} K={self.out_channels} "
            f"in={self.in_h}x{self.in_w}x{self.in_channels}{pool}"
        )


def conv_geometries(model_cfg) -> List[ConvGeometry]:
    """The model's conv layers with their input dims and trailing pools —
    driven by the shared ``models.alexnet.layer_dims`` traversal, so tuned
    geometry cannot drift from the FLOP/shape accounting."""
    from ..models.alexnet import ConvSpec, LrnSpec, PoolSpec, layer_dims

    chain = list(layer_dims(model_cfg))
    out: List[ConvGeometry] = []
    for i, (name, spec, (hi, wi, ci), _o) in enumerate(chain):
        if not isinstance(spec, ConvSpec):
            continue
        pw = ps = 0
        lrn: tuple = ()
        if i + 1 < len(chain) and isinstance(chain[i + 1][1], PoolSpec):
            nxt = chain[i + 1][1]
            pw, ps = nxt.window, nxt.stride
            if i + 2 < len(chain) and isinstance(chain[i + 2][1], LrnSpec):
                n = chain[i + 2][1]
                lrn = (
                    n.size, n.alpha, n.beta, n.k, n.alpha_over_size,
                )
        out.append(
            ConvGeometry(
                name=name,
                filter_size=spec.filter_size,
                stride=spec.stride,
                padding=spec.padding,
                in_channels=ci,
                out_channels=spec.out_channels,
                in_h=hi,
                in_w=wi,
                pool_window=pw,
                pool_stride=ps,
                lrn=lrn,
            )
        )
    return out


def prune_reason(v: KernelVariants, g: ConvGeometry, *, dtype: str = "fp32") -> str:
    """Why this combo is out of the sweep ('' = legal). Mirrors the gates in
    _conv2d_pallas / _conv_then_pool — a candidate this accepts must lower
    and run the variant it claims. ``dtype`` is the sweep's precision
    policy: int8w runs the conv with the fused bias/ReLU epilogue disabled
    (the per-channel rescale lands between accumulation and bias —
    precision.quantize), so epilogue fusion is not a legal candidate
    there. fuse="block" IS legal under int8w: the megakernel applies the
    per-channel rescale in its own epilogue, between the fp32
    accumulation and the bias (ops.megakernel) — the staged-chain
    limitation that rules hpool out does not apply."""
    if v.fuse == "hpool" and dtype == "int8w":
        return (
            "hpool fusion needs the in-kernel bias/ReLU epilogue; int8w "
            "applies bias after the per-channel rescale (precision.quantize)"
        )
    if v.conv == "g8" and g.stride < 2:
        return "g8 falls back to vcol at stride 1 (phase packing needs s>=2)"
    if v.fuse == "hpool":
        if not g.has_pool:
            return "hpool fusion needs an adjacent pool"
        if v.conv not in ("taps", "vcol"):
            return f"hpool fusion supports taps/vcol only (conv={v.conv})"
        if v.row_block < g.out_h:
            return (
                f"hpool fusion needs the whole image per program "
                f"(row_block {v.row_block} < ho {g.out_h})"
            )
    if v.fuse == "block":
        # One gate for builder, wrapper, and sweep: ops.megakernel owns
        # the block-fusion geometry rules, so a candidate this accepts is
        # exactly one ops.pallas_model._conv_then_pool would fuse.
        why = block_fusible_reason(
            variant=v.conv, row_block=v.row_block, out_h=g.out_h,
            pool_window=g.pool_window,
        )
        if why:
            return why
    return ""


def _effective_signature(v: KernelVariants, g: ConvGeometry) -> tuple:
    """What actually lowers: row blocks clamp to the output height, and g8
    runs each output phase whole whatever the row block."""
    rows = g.out_h if v.conv == "g8" else min(v.row_block, g.out_h)
    return (v.conv, rows, v.fuse)


def candidate_space(
    g: ConvGeometry,
    *,
    dtype: str = "fp32",
    on_prune: Optional[Callable[[KernelVariants, str], None]] = None,
) -> List[KernelVariants]:
    """Every legal, effectively-distinct candidate for this layer.
    ``dtype``: the sweep's precision policy (int8w excludes epilogue
    fusion — see prune_reason)."""
    seen: set = set()
    out: List[KernelVariants] = []
    for conv, rb, fuse in itertools.product(CONV_VARIANTS, ROW_BLOCKS, FUSES):
        v = KernelVariants(conv=conv, row_block=rb, fuse=fuse)
        why = prune_reason(v, g, dtype=dtype)
        if not why:
            sig = _effective_signature(v, g)
            if sig in seen:
                why = f"duplicate effective lowering {sig}"
            else:
                seen.add(sig)
                out.append(v)
                continue
        if on_prune is not None:
            on_prune(v, why)
    return out


def layer_tuning_units(model_cfg) -> List[Tuple[str, ConvGeometry]]:
    """(layer_name, geometry) pairs in chain order — the sweep's work list."""
    return [(g.name, g) for g in conv_geometries(model_cfg)]
