"""Hand-written Pallas TPU kernels for the four layer ops.

The TPU-native counterpart of the reference's CUDA kernels
(v3_cuda_only/src/layers_cuda.cu:20-152: convKernel, reluKernel, poolKernel,
lrnKernel; hardened V4 copies v4_mpi_cuda/src/layers_mpi_cuda.cu:25-136).
NOT a translation: the CUDA kernels map one thread per output element —
scalar code that would waste the MXU entirely. Here:

- conv: for each (fy, fx) filter tap, a strided window of the image becomes
  a (BH*Wo, C) x (C, K) matmul on the MXU, accumulated in fp32 VMEM. The
  channel axes live on the 128-wide lanes. Bias add + optional ReLU are
  fused into the same kernel (the reference launches ReLU separately).
- maxpool: separable two-stage max (rows then cols). The stride-s phase
  split is a PURE VIEW reshape (H -> (H/s, s) preserves contiguity), so
  no strided gather is ever materialized; the W stage reuses the same
  kernel after an XLA transpose. Measured on v5e (b=128 fp32): 3.7x
  faster than a phase-stack kernel on lane-aligned channels (pool2: 0.39
  vs 1.44 ms), within noise on pool1's C=96 (docs/PALLAS_PERF.md).
- LRN: channel-window sum of squares via shifted adds, one pow + divide —
  both LRN alpha conventions supported (see ops.reference.lrn).

Conv grid: one program per (batch image, BH-row output block). Row tiling
keeps the per-program accumulator and window slices small — the earlier
whole-image-per-program layout blew the 16 MB scoped-VMEM limit at batch
>= 128 on a real v5e (18.5 MB stack allocation). W is padded to a multiple
of 16 so collapsing (BH, Wo, C) windows to 2-D matmul operands is a
layout-legal reshape for fp32 (8-sublane) AND bf16 (16-sublane) — Mosaic
rejects the unaligned collapse outright in bf16 ("unsupported shape cast").
Accumulation order over filter taps is fixed (row-major fy, fx), giving
deterministic numerics across runs; fp32 inputs use HIGHEST (true-fp32)
MXU precision, bf16 inputs the native bf16 MACs with fp32 accumulation.

On non-TPU backends the kernels run in Pallas interpreter mode so the same
code path is unit-testable on the CPU mesh.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .reference import mxu_precision as _mxu_precision
from .vma import interpret_mode as _interpret, vma_struct


def _tc_params(*semantics: str):
    """Grid dimension semantics for the Mosaic scheduler ('parallel' grid
    dims let it pipeline DMA against compute across programs). None in
    interpreter mode, where CompilerParams is ignored anyway."""
    if _interpret():
        return None
    return pltpu.CompilerParams(dimension_semantics=tuple(semantics))


def _vmem_spec(block_shape=None, index_map=None):
    if block_shape is None:
        return pl.BlockSpec(memory_space=pltpu.VMEM)
    return pl.BlockSpec(block_shape, index_map, memory_space=pltpu.VMEM)


# The conv lowerings a KernelVariants may name: those the chip has not
# ruled out (docs/PALLAS_PERF.md keeps the measurements of those it did):
#   "taps"  — fq^2 tap matmuls per row block, static unroll.
#   "vcol"  (default) — in-kernel (VMEM) im2col over the qw taps: taps' 1x
#             HBM traffic with one fq*cs-contraction matmul per qh row (see
#             _conv_vcol_kernel). ADOPTED as the default from the 2026-07-31
#             on-chip A/B: with rowblock 64 it is the grid winner at b=128 —
#             bf16 2.997 ms (42.7k img/s, 0.42x v1_jit, from taps' 0.38x)
#             and fp32 11.003 ms (11.6k img/s, 0.53x v1_jit).
#   "g8"    — phase-packed conv for strided convs (s>=2; s=1 runs vcol):
#             space-to-depth at g=2s puts g*g*C channels on the lanes (conv1:
#             192 vs 48) and computes the 2x2 output phases on separate grid
#             programs (see _conv_g8_kernel). Lowers through Mosaic and agrees
#             with the fp32 XLA forward inside the precision gate at b=128,
#             fp32 and bf16 (v5e, 2026-09-26, PR 21); never timed (ROADMAP S5).
CONV_VARIANTS = ("taps", "vcol", "g8")

# Output rows per conv program: BH * Wo_pad is the matmul M dim. A wider
# block amortizes per-program overhead and weight re-reads across more MXU
# work at more VMEM per program. 64 (i.e. the whole 55/27-row image for the
# AlexNet convs, grid over batch only) won the 2026-07-31 on-chip rowblock
# sweep at every measured cell — 8/16/32/64 bf16 full pass:
# 3.588/3.642/3.219/2.997 ms with vcol — the per-program VMEM footprint
# (conv1 rb=64: ~360 KB window + ~1.4 MB acc) stays well under budget.
ROW_BLOCKS = (8, 16, 32, 64)
# W padded up to this multiple so the (BH, Wo, C) -> (BH*Wo, C) collapse is
# sublane-aligned for fp32 (8) and bf16 (16) alike.
_W_ALIGN = 16

# Epilogue fusion (round-5 lever): "hpool" fuses the separable pool's H
# stage into the conv epilogue where the model's conv feeds a pool (the
# full-height conv output never round-trips HBM; the pool's first kernel
# launch disappears). Bitwise-neutral (exact max on the casted value).
# Applies where the geometry allows (taps/vcol, row_block >= ho); the
# model builder falls back to the separate pool otherwise.
# "block" goes all the way: the whole block (conv+ReLU+pool, +LRN when one
# trails the pool) runs as ONE VMEM-resident pass (ops/megakernel.py) —
# interior activations never touch HBM. Same geometry regime as hpool.
# Neither has been timed on the chip (ROADMAP S5, S11).
FUSES = ("none", "hpool", "block")


def _refuse_unknown(field: str, value, allowed: tuple) -> None:
    if value not in allowed:
        raise ValueError(f"{field} must be one of {allowed}, got {value!r}")


@dataclasses.dataclass(frozen=True)
class KernelVariants:
    """One conv layer's Pallas lowering (and the pool it feeds) — hashable,
    so it can ride closures and jit static args. Which one runs is the
    code's default or a tune plan's per-layer winner
    (``tuning.plan.effective_layer_variants``); a value outside the kept
    sets raises here, where it is built."""

    conv: str = "vcol"
    row_block: int = 64
    fuse: str = "none"

    def __post_init__(self):
        _refuse_unknown("KernelVariants.conv", self.conv, CONV_VARIANTS)
        _refuse_unknown("KernelVariants.row_block", self.row_block, ROW_BLOCKS)
        _refuse_unknown("KernelVariants.fuse", self.fuse, FUSES)

    def label(self) -> str:
        """Compact A/B-row/tuner-log label."""
        return f"conv={self.conv} rb={self.row_block} fuse={self.fuse}"

    def __repr__(self) -> str:
        return f"KernelVariants({self.label()})"


class LayerVariants(NamedTuple):
    """Per-layer lowering plan — the tuner's product (tuning/). Variants are
    no longer process-global: each conv layer (and the pool it feeds) can
    carry its own ``KernelVariants``. Hashable like KernelVariants, so a
    plan can ride closures/static args the same way. Forward builders accept
    either type; ``ops.pallas_model._layer_variants`` dispatches."""

    layers: tuple = ()  # ((layer_name, KernelVariants), ...)
    default: KernelVariants = KernelVariants()

    def for_layer(self, name: str) -> KernelVariants:
        for n, v in self.layers:
            if n == name:
                return v
        return self.default


def _conv_epilogue(acc, b_ref, o_ref, *, bh: int, wo_p: int, k: int, relu: bool,
                   hpool=None, out_index=(0,)):
    """Shared bias + optional-ReLU + cast tail of both conv variants —
    one place, so the variants cannot diverge numerically in the epilogue.
    ``out_index``: the leading unit dims of ``o_ref`` the (bh, wo_p, k)
    result is stored under.

    ``hpool=(window, stride, hp_o)`` (round-5 fusion lever): additionally
    max-pool the H axis in-kernel before the write, so the full-height
    conv output never round-trips HBM and the separable pool's first
    stage disappears. Requires the whole image in one program (bh == ho).
    The pool runs on the CASTED value — exactly the tensor the unfused
    sep2 H-stage would read back — and the row phase-split is a reshape
    of the leading UNTILED axis (the tiled (W, C) dims are untouched), so
    the result is bitwise identical to conv-then-pool
    (tests/test_pallas.py::test_conv_hpool_fusion_bitwise)."""
    out = acc.reshape(bh, wo_p, k) + b_ref[:].astype(jnp.float32)
    if relu:
        out = jnp.maximum(out, 0.0)
    out = out.astype(o_ref.dtype)
    if hpool is not None:
        window, stride, hp_o = hpool
        qmax = (window - 1) // stride
        hq = hp_o + qmax           # H view-rows the pool reads
        if bh < hq * stride:       # pad rows never entering a window
            out = jnp.concatenate(
                [out, jnp.zeros((hq * stride - bh, wo_p, k), out.dtype)], axis=0
            )
        u = out[: hq * stride].reshape(hq, stride, wo_p, k)
        res = None
        for fy in range(window):
            q, p = fy // stride, fy % stride
            win = u[q : q + hp_o, p]
            res = win if res is None else jnp.maximum(res, win)
        out = res
    o_ref[out_index] = out


def _conv_kernel(x_ref, w_ref, b_ref, o_ref, *, fq: int, bh: int, wo_p: int, relu: bool, hpool=None):
    """Space-to-depth conv: x_ref (1, Hs, Ws, S*S*C), w_ref (fq, fq, S*S*C, K).

    Program (i, j) computes output rows [j*bh, (j+1)*bh) of image i. Every
    tap group is a unit-stride window slice feeding one MXU matmul (Mosaic
    forbids strided vector slices, and skinny K-dim matmuls would waste the
    systolic array — the S*S*C contraction axis fixes both).
    """
    cs = x_ref.shape[-1]
    k = w_ref.shape[-1]
    row0 = pl.program_id(1) * bh
    prec = _mxu_precision(x_ref.dtype)

    # Fully static fq x fq tap unroll: with 8-row windows (~100 KB each)
    # the whole tap set fits VMEM comfortably (the pre-row-tiling kernel
    # could only afford a fori_loop over qh — full unrolling of whole-image
    # windows OOMed), and straight-line code lets Mosaic software-pipeline
    # the matmul chain. Fixed (qh outer, qw inner) order => deterministic
    # fp32 accumulation (SURVEY §7.3). The dynamic H start (row0 + qh) is
    # legal because dim 1 is untiled; W taps must be static slices — W is
    # the sublane-tiled dim, where Mosaic requires provable 8-alignment.
    acc = jnp.zeros((bh * wo_p, k), jnp.float32)
    for qh in range(fq):
        for qw in range(fq):
            win = x_ref[0, pl.ds(row0 + qh, bh), qw : qw + wo_p, :]
            wtap = w_ref[qh, qw, :, :]
            acc = acc + jnp.dot(
                win.reshape(bh * wo_p, cs),
                wtap,
                preferred_element_type=jnp.float32,
                precision=prec,
            )
    _conv_epilogue(acc, b_ref, o_ref, bh=bh, wo_p=wo_p, k=k, relu=relu, hpool=hpool)


def _conv_vcol_kernel(x_ref, w_ref, b_ref, o_ref, *, fq: int, bh: int, wo_p: int, relu: bool, hpool=None):
    """VMEM-level im2col over the qw taps (round-5 lever, named from the
    per-layer A/B in scripts/v3_layer_ab.py): same operands and HBM
    traffic as "taps" (1x input), but the fq qw-windows are concatenated
    on the lane axis INSIDE the kernel, so each qh row is ONE matmul with
    an fq*cs contraction (conv1: 144 vs 48 of the MXU's 128 rows; conv2:
    480 vs 96) instead of fq skinny ones — the fill win of a host-side
    im2col without its HBM blowup; the concat is a VMEM lane relayout,
    whose cost is what the A/B measures. Accumulation: one reduction per
    qh over fq*cs (deterministic; differs from taps in the last ulps —
    allclose across variants, bitwise within)."""
    cs = x_ref.shape[-1]
    k = w_ref.shape[-1]
    row0 = pl.program_id(1) * bh
    prec = _mxu_precision(x_ref.dtype)
    acc = jnp.zeros((bh * wo_p, k), jnp.float32)
    for qh in range(fq):
        # The qw windows are sublane-shifted views of the same rows;
        # Mosaic's concat requires matching offsets on non-concat dims
        # ("result/input offset mismatch", measured on v5e), so each
        # window is reshaped to 2-D FIRST — merging the (bh, wo_p) tiles
        # forces an offset-0 materialization — and the concat runs on the
        # lane axis of the already-flat operands.
        wide = jnp.concatenate(
            [
                x_ref[0, pl.ds(row0 + qh, bh), qw : qw + wo_p, :].reshape(
                    bh * wo_p, cs
                )
                for qw in range(fq)
            ],
            axis=-1,
        )
        acc = acc + jnp.dot(
            wide,
            w_ref[qh].reshape(fq * cs, k),
            preferred_element_type=jnp.float32,
            precision=prec,
        )
    _conv_epilogue(acc, b_ref, o_ref, bh=bh, wo_p=wo_p, k=k, relu=relu, hpool=hpool)


def _conv_g8_kernel(x_ref, w_ref, b_ref, o_ref, *, fq8: int, bh: int, wo_p: int, relu: bool):
    """Phase-packed conv (the round-5 verdict-protocol 'next lever' for
    conv1): x_ref (1, hs8, ws8, G) is the input space-to-depth-packed at
    g = 2*stride (G = g*g*C — conv1: 192 lanes vs the stride-s packing's
    48), w_ref (1, 1, fq8, fq8, G, K) is THIS program's phase weight
    frame, o_ref (1, 1, 1, bh, wo_p, K) is one of the 2x2 output phases
    (out row j = 2a + phase_h; the de-interleave is a host-side XLA
    transpose). Grid (N, 2, 2). Every phase-output row a reads g-rows
    [a, a+fq8) regardless of phase — the phase's intra-block offset lives
    entirely in the zero-padded weight frame — so the kernel body is the
    vcol lowering at 4x the lane occupancy and fq8 (=2 for conv1) taps
    per axis instead of fq (=3)."""
    gch = x_ref.shape[-1]
    k = w_ref.shape[-1]
    prec = _mxu_precision(x_ref.dtype)
    acc = jnp.zeros((bh * wo_p, k), jnp.float32)
    for qh in range(fq8):
        wide = jnp.concatenate(
            [
                x_ref[0, qh : qh + bh, qw : qw + wo_p, :].reshape(bh * wo_p, gch)
                for qw in range(fq8)
            ],
            axis=-1,
        )
        acc = acc + jnp.dot(
            wide,
            w_ref[0, 0, qh].reshape(fq8 * gch, k),
            preferred_element_type=jnp.float32,
            precision=prec,
        )
    # Shared epilogue — the one-place invariant holds across all variants.
    # The store indexes the three leading unit dims directly: a phase
    # sub-ref (o_ref.at[0, 0]) is a memref slice, which Mosaic refuses for
    # K=96 ("Slice shape along dimension 5 must be aligned to tiling (128),
    # but is 96" — v5e, 2026-09-26).
    _conv_epilogue(
        acc, b_ref, o_ref, bh=bh, wo_p=wo_p, k=k, relu=relu, out_index=(0, 0, 0)
    )


def _weights_to_phase_depth(w: jax.Array, s: int, g: int, fq8: int) -> jax.Array:
    """(F, F, C, K) -> (2, 2, fq8, fq8, g*g*C, K) phase weight frames.

    Phase (ph, pw) of the g = 2s packing sees the filter at spatial offset
    (ph*s, pw*s) inside its fq8*g-wide zero frame; the frame is then
    depth-packed with the same (g_h, g_w, c) channel order as
    :func:`_space_to_depth`, so frame row v = ph*s + u lands at tap v//g,
    channel block v%g — exactly where input row j*s + u sits in xs8."""
    f, _, c, k = w.shape
    frames = []
    for ph in range(2):
        row = []
        for pw in range(2):
            wp = jnp.pad(
                w,
                (
                    (ph * s, fq8 * g - f - ph * s),
                    (pw * s, fq8 * g - f - pw * s),
                    (0, 0),
                    (0, 0),
                ),
            )
            wp = wp.reshape(fq8, g, fq8, g, c, k)
            row.append(wp.transpose(0, 2, 1, 3, 4, 5).reshape(fq8, fq8, g * g * c, k))
        frames.append(jnp.stack(row))
    return jnp.stack(frames)


def _space_to_depth(x: jax.Array, s: int, hs: int, ws: int) -> jax.Array:
    """(N, H, W, C) -> (N, hs, ws, s*s*C); H, W zero-padded to hs*s, ws*s.

    Geometries where (H - F) % S != 0 leave trailing rows/cols the conv
    never reads — cropped here, matching the reference's floor-division
    output dims (convOutDim, v2_mpi_only/2.2_scatter_halo/include/alexnet.hpp:35-39).
    """
    n, h, w, c = x.shape
    if h < hs * s or w < ws * s:
        x = jnp.pad(
            x, ((0, 0), (0, max(0, hs * s - h)), (0, max(0, ws * s - w)), (0, 0))
        )
    x = x[:, : hs * s, : ws * s, :]
    x = x.reshape(n, hs, s, ws, s, c)
    return x.transpose(0, 1, 3, 2, 4, 5).reshape(n, hs, ws, s * s * c)


def _weights_to_depth(w: jax.Array, s: int, fq: int) -> jax.Array:
    """(F, F, C, K) -> (fq, fq, s*s*C, K), zero taps past F."""
    f, _, c, k = w.shape
    if f < fq * s:
        w = jnp.pad(w, ((0, fq * s - f), (0, fq * s - f), (0, 0), (0, 0)))
    w = w.reshape(fq, s, fq, s, c, k)
    return w.transpose(0, 2, 1, 3, 4, 5).reshape(fq, fq, s * s * c, k)


def conv2d_pallas(
    x: jax.Array,
    w: jax.Array,
    b: jax.Array,
    *,
    stride: int,
    padding: int = 0,
    padding_w: int | None = None,
    relu: bool = False,
    vma=None,
    variant: str = KernelVariants.conv,
    row_block: int = KernelVariants.row_block,
    hpool: tuple | None = None,
) -> jax.Array:
    """Direct conv (+bias, optional fused ReLU) — thin wrapper normalising
    ``vma`` before entering jit. ``variant`` is one of ``CONV_VARIANTS``;
    any other name raises. ``vma``: mesh axes the call varies over inside a
    check_vma=True shard_map (ops.vma). ``hpool=(window, stride)``: fuse the
    separable pool's H stage into the conv epilogue (requires variant
    taps/vcol and row_block >= the conv's output height; see
    _conv_epilogue) — the result has pooled H, full W; run
    :func:`maxpool_pallas_w` after."""
    return _conv2d_pallas(
        x, w, b, stride=stride, padding=padding, padding_w=padding_w,
        relu=relu, variant=variant, row_block=row_block,
        vma=tuple(vma) if vma is not None else None,
        hpool=hpool,
    )


@functools.partial(
    jax.jit,
    static_argnames=(
        "stride", "padding", "padding_w", "relu", "variant", "row_block",
        "vma", "hpool",
    ),
)
def _conv2d_pallas(
    x: jax.Array,
    w: jax.Array,
    b: jax.Array,
    *,
    stride: int,
    padding: int = 0,
    padding_w: int | None = None,
    relu: bool = False,
    variant: str,
    row_block: int,
    vma=None,
    hpool: tuple | None = None,
) -> jax.Array:
    """Direct conv (+bias, optional fused ReLU). x: (N,H,W,C), w: (F,F,C,K).

    ``padding`` pads H; ``padding_w`` (default = padding) pads W — the split
    exists for the row-sharded tier, whose halo machinery supplies the H
    context (VALID on H, padded on W).

    Strided convolution is lowered by phase decomposition (space-to-depth):
    the input is repacked host-side to (N, H/S, W/S, S*S*C) and the weights
    to (ceil(F/S)^2, S*S*C, K); output row i tap fy reads s2d row
    ``i + fy//S`` channel block ``fy%S`` — so the kernel's window slices are
    all unit-stride and each matmul contracts over S*S*C. For S=1 this
    degenerates to the identity packing.
    """
    _refuse_unknown("conv2d_pallas variant", variant, CONV_VARIANTS)
    if hpool is not None and variant not in ("taps", "vcol"):
        raise ValueError(
            f"hpool fusion supports the taps/vcol lowering only, got {variant!r}"
        )
    n, h, wdt, c = x.shape
    f = w.shape[0]
    s = stride
    pw = padding if padding_w is None else padding_w
    ph = padding
    ho = (h - f + 2 * ph) // s + 1
    wo = (wdt - f + 2 * pw) // s + 1
    fq = -(-f // s)  # ceil(F/S): tap groups per axis

    if ph or pw:
        x = jnp.pad(x, ((0, 0), (ph, ph), (pw, pw), (0, 0)))

    if variant == "g8" and s >= 2:
        # Phase-packed lowering (round-5 'next lever', per-layer A/B
        # attribution in docs/PALLAS_PERF.md): repack at g = 2s so the
        # lane dim carries g*g*C channels (conv1: 192 vs 48 — the vcol
        # kernel's window relayouts ran at 37% lane occupancy, which the
        # A/B measured as conv1's dominating cost), compute the 2x2
        # output phases on separate grid programs, and de-interleave with
        # one host-side XLA transpose of the (smaller) output.
        g = 2 * s
        fq8 = -(-(f + s) // g)       # g-taps per axis, max over phases
        ho2, wo2 = -(-ho // 2), -(-wo // 2)
        wo2_p = -(-wo2 // _W_ALIGN) * _W_ALIGN
        bh8 = ho2                    # whole phase image per program
        hs8, ws8 = bh8 + fq8 - 1, wo2_p + fq8 - 1
        xs8 = _space_to_depth(x, g, hs8, ws8)
        w8 = _weights_to_phase_depth(w, s, g, fq8)
        gch = g * g * c
        kk = w.shape[-1]
        out8 = pl.pallas_call(
            functools.partial(
                _conv_g8_kernel, fq8=fq8, bh=bh8, wo_p=wo2_p, relu=relu
            ),
            grid=(n, 2, 2),
            in_specs=[
                _vmem_spec((1, hs8, ws8, gch), lambda i, u, v: (i, 0, 0, 0)),
                _vmem_spec(
                    (1, 1, fq8, fq8, gch, kk),
                    lambda i, u, v: (u, v, 0, 0, 0, 0),
                ),
                _vmem_spec(),
            ],
            out_specs=_vmem_spec(
                (1, 1, 1, bh8, wo2_p, kk), lambda i, u, v: (i, u, v, 0, 0, 0)
            ),
            out_shape=vma_struct((n, 2, 2, bh8, wo2_p, kk), x.dtype, vma),
            compiler_params=_tc_params("parallel", "parallel", "parallel"),
            interpret=_interpret(),
            name="conv2d_g8",
        )(xs8, w8, b)
        # out[j, l] = out8[j%2, l%2, j//2, l//2]: interleave rows/cols by
        # phase, then crop the alignment padding (it lands past ho/wo).
        out = out8.transpose(0, 3, 1, 4, 2, 5).reshape(n, 2 * bh8, 2 * wo2_p, kk)
        return out[:, :ho, :wo, :]
    # Round the output tile up to (row-block, sublane-aligned W); the extra
    # rows/cols read zero padding and are cropped after the call. Cheap:
    # <= _W_ALIGN-1 wasted columns, <= row_block-1 wasted rows.
    bh = min(row_block, ho)
    nbh = -(-ho // bh)
    ho_p = nbh * bh
    wo_p = -(-wo // _W_ALIGN) * _W_ALIGN
    hs, ws = ho_p + fq - 1, wo_p + fq - 1  # s2d rows/cols the kernel reads
    xs = _space_to_depth(x, s, hs, ws)
    ws2d = _weights_to_depth(w, s, fq)
    cs = s * s * c

    operands = (xs, ws2d, b)
    # taps, or the vcol kernel (which g8 runs at stride 1, with no phases
    # to pack).
    kern_fn = _conv_kernel if variant == "taps" else _conv_vcol_kernel
    kernel = functools.partial(kern_fn, fq=fq, bh=bh, wo_p=wo_p, relu=relu)
    kk = w.shape[-1]
    if hpool is not None:
        # Fused H-stage pool (see _conv_epilogue): caller contract, not a
        # silent fallback — the model builder gates on this.
        if bh != ho:
            raise ValueError(
                f"hpool fusion needs the whole image per program "
                f"(row_block {row_block} < ho {ho})"
            )
        pwin, pstr = hpool
        hp_o = (ho - pwin) // pstr + 1
        kernel = functools.partial(
            kern_fn, fq=fq, bh=bh, wo_p=wo_p, relu=relu,
            hpool=(pwin, pstr, hp_o),
        )
        out = pl.pallas_call(
            kernel,
            grid=(n, 1),
            in_specs=[
                _vmem_spec((1, hs, ws, cs), lambda i, j: (i, 0, 0, 0)),
                _vmem_spec(),
                _vmem_spec(),
            ],
            out_specs=_vmem_spec((1, hp_o, wo_p, kk), lambda i, j: (i, j, 0, 0)),
            out_shape=vma_struct((n, hp_o, wo_p, kk), x.dtype, vma),
            compiler_params=_tc_params("parallel", "parallel"),
            interpret=_interpret(),
            name=f"conv2d_{variant}_hpool",
        )(*operands)
        return out[:, :, :wo, :]
    out = pl.pallas_call(
        kernel,
        grid=(n, nbh),
        in_specs=[
            _vmem_spec((1, hs, ws, cs), lambda i, j: (i, 0, 0, 0)),
            _vmem_spec(),
            _vmem_spec(),
        ],
        out_specs=_vmem_spec((1, bh, wo_p, kk), lambda i, j: (i, j, 0, 0)),
        out_shape=vma_struct((n, ho_p, wo_p, kk), x.dtype, vma),
        compiler_params=_tc_params("parallel", "parallel"),
        interpret=_interpret(),
        name=f"conv2d_{variant}",
    )(*operands)
    if ho_p != ho or wo_p != wo:
        out = out[:, :ho, :wo, :]
    return out


def conv2d_pallas_hvalid(
    x, w, b, *, stride: int, padding_w: int, vma=None,
    variant: str = KernelVariants.conv, row_block: int = KernelVariants.row_block,
):
    """Sharded-tier entry: VALID on H (halo-provided), padded on W, fused ReLU
    is NOT applied here (the sharded pipeline masks then relus)."""
    return conv2d_pallas(
        x, w, b, stride=stride, padding=0, padding_w=padding_w, vma=vma,
        variant=variant, row_block=row_block,
    )


@functools.partial(jax.jit, static_argnames=("window", "stride", "vma"))
def maxpool_pallas(x: jax.Array, *, window: int, stride: int, vma=None) -> jax.Array:
    """Window max as a separable two-stage pool: rows, transpose, rows
    again, transpose. ``vma``: a tuple of mesh axes, see ops.vma."""
    y = _pool_rows(x, window=window, stride=stride, vma=vma)  # (N, ho, W, C)
    yt = jnp.swapaxes(y, 1, 2)                           # (N, W, ho, C)
    z = _pool_rows(yt, window=window, stride=stride, vma=vma)  # (N, wo, ho, C)
    return jnp.swapaxes(z, 1, 2)                         # (N, ho, wo, C)


def _axis_pool_kernel(x_ref, o_ref, *, window: int, stride: int, ho: int):
    """Pool along H only. x_ref: (1, hq, s, W, C) — dims 1-2 are the
    view-split H (half-row, phase), both untiled; W and C carry the 8x128
    tiling unchanged. Output row i = max over taps fy of input row
    i*s + fy = view element (i + fy//s, fy%s). Max is associative and
    exact in floating point, so the two-stage split cannot change results.
    """
    out = None
    for fy in range(window):
        q, p = fy // stride, fy % stride
        win = x_ref[0, q : q + ho, p]
        out = win if out is None else jnp.maximum(out, win)
    o_ref[0] = out


def _pool_rows(x: jax.Array, *, window: int, stride: int, vma=None) -> jax.Array:
    """Max-pool the H axis via the view-reshape phase split. x: (N,H,W,C).

    The reshape H -> (hq, s) is contiguity-preserving — XLA emits no data
    movement — which is the whole advantage over a phase-stack pool
    (whose s*s strided gathers cost more than the pool itself)."""
    n, h, w, c = x.shape
    s = stride
    ho = (h - window) // s + 1
    qmax = (window - 1) // s
    hq = ho + qmax  # H view-rows the kernel reads
    if h < hq * s:
        x = jnp.pad(x, ((0, 0), (0, hq * s - h), (0, 0), (0, 0)))
    xv = x[:, : hq * s].reshape(n, hq, s, w, c)
    kernel = functools.partial(_axis_pool_kernel, window=window, stride=s, ho=ho)
    return pl.pallas_call(
        kernel,
        grid=(n,),
        in_specs=[_vmem_spec((1, hq, s, w, c), lambda i: (i, 0, 0, 0, 0))],
        out_specs=_vmem_spec((1, ho, w, c), lambda i: (i, 0, 0, 0)),
        out_shape=vma_struct((n, ho, w, c), x.dtype, vma),
        compiler_params=_tc_params("parallel"),
        interpret=_interpret(),
        name="maxpool_rows",
    )(xv)


@functools.partial(jax.jit, static_argnames=("window", "stride", "vma"))
def maxpool_pallas_w(x: jax.Array, *, window: int, stride: int, vma=None) -> jax.Array:
    """W-axis-only pool stage — the second half of the separable pool, for
    outputs whose H stage was already fused into the conv epilogue
    (``conv2d_pallas(..., hpool=...)``). Same kernel, same numerics as the
    sep2 W stage, so fused-conv + this == conv + maxpool_pallas bitwise."""
    yt = jnp.swapaxes(x, 1, 2)                           # (N, W, hpooled, C)
    z = _pool_rows(yt, window=window, stride=stride, vma=vma)
    return jnp.swapaxes(z, 1, 2)


# Pixels per LRN program. The op is pointwise over (H, W) and its band
# matmul is the only place a block's row count could reach the numerics
# (XLA:CPU picks its dot strategy, and so its summation order, by M), so
# every program multiplies one (LRN_ROWS, C) tile whatever the image or
# shard height: a shard of 2 rows and the whole image run the same dot.
LRN_ROWS = 128


def _lrn_kernel(x_ref, o_ref, *, size: int, alpha: float, beta: float, k: float, alpha_over_size: bool):
    """Cross-channel LRN over one (LRN_ROWS, C) tile of pixels; the
    channel-window sum of squares is a banded 0/1-matrix matmul on the MXU
    — no lane-dimension slicing, and the band edges implement the
    reference's window truncation exactly. Both tiers share the
    formulation: ``ops.reference.lrn`` takes the same product through
    ``lax.dot_general`` and lets XLA fuse the rest round it."""
    # All math in fp32 regardless of the activation dtype: the band matmul
    # must be dtype-homogeneous (Mosaic rejects a bf16 lhs against the f32
    # band — "Bad lhs type"), and the scale/power path is precision-critical.
    x = x_ref[0].astype(jnp.float32)  # (LRN_ROWS, C)
    c = x.shape[-1]
    half = size // 2
    ci = lax.broadcasted_iota(jnp.int32, (c, c), 0)
    cj = lax.broadcasted_iota(jnp.int32, (c, c), 1)
    band = (jnp.abs(ci - cj) <= half).astype(jnp.float32)
    ssum = jnp.dot(
        x * x, band, preferred_element_type=jnp.float32, precision=lax.Precision.HIGHEST
    )
    a = alpha / size if alpha_over_size else alpha
    scale = k + a * ssum
    o_ref[0] = (x / scale**beta).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("size", "alpha", "beta", "k", "alpha_over_size", "vma")
)
def lrn_pallas(
    x: jax.Array,
    *,
    size: int,
    alpha: float,
    beta: float,
    k: float,
    alpha_over_size: bool = False,
    vma=None,
) -> jax.Array:
    """Cross-channel LRN. ``vma``: see ops.vma (a tuple, for the jit).

    The pixels are flattened to (N, H*W, C) and zero-padded to whole
    LRN_ROWS tiles (a zero pixel normalises to zero and is sliced off), so
    the result for a pixel does not depend on how many rows came with it."""
    n, h, wdt, c = x.shape
    m = h * wdt
    tiles = pl.cdiv(m, LRN_ROWS)
    xp = jnp.pad(x.reshape(n, m, c), ((0, 0), (0, tiles * LRN_ROWS - m), (0, 0)))
    kernel = functools.partial(
        _lrn_kernel, size=size, alpha=alpha, beta=beta, k=k, alpha_over_size=alpha_over_size
    )
    out = pl.pallas_call(
        kernel,
        grid=(n, tiles),
        in_specs=[_vmem_spec((1, LRN_ROWS, c), lambda i, j: (i, j, 0))],
        out_specs=_vmem_spec((1, LRN_ROWS, c), lambda i, j: (i, j, 0)),
        out_shape=vma_struct(xp.shape, x.dtype, vma),
        compiler_params=_tc_params("parallel", "parallel"),
        interpret=_interpret(),
        name="lrn_band",
    )(xp)
    return out[:, :m].reshape(n, h, wdt, c)


def relu_pallas(x: jax.Array) -> jax.Array:
    """Standalone elementwise ReLU kernel (reference: reluKernel,
    layers_cuda.cu:66-75). The conv kernel fuses ReLU, so this exists for
    parity/benchmarking of the unfused launch sequence.

    Gridded over the leading axis for ndim >= 3: a gridless whole-array
    VMEM mapping would exceed the ~16 MB scoped-VMEM limit for any real
    batch of activations (e.g. conv1 at b >= 32). ndim <= 2 stays
    gridless — a (1, M) block over a 2-D array would put 1 in the
    sublane dim, which Mosaic's last-two-dims tiling rule rejects (the
    same constraint as the flash LSE layout), and 2-D inputs here are
    small parity-test vectors."""

    def kernel(x_ref, o_ref):
        o_ref[:] = jnp.maximum(x_ref[:], 0.0).astype(o_ref.dtype)

    if x.ndim >= 3:
        n = x.shape[0]
        rest = x.shape[1:]
        block = (1, *rest)
        idx = lambda i: (i,) + (0,) * len(rest)  # noqa: E731
        return pl.pallas_call(
            kernel,
            grid=(n,),
            in_specs=[_vmem_spec(block, idx)],
            out_specs=_vmem_spec(block, idx),
            out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
            compiler_params=_tc_params("parallel"),
            interpret=_interpret(),
            name="relu",
        )(x)
    return pl.pallas_call(
        kernel,
        in_specs=[_vmem_spec()],
        out_specs=_vmem_spec(),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        interpret=_interpret(),
        name="relu",
    )(x)
