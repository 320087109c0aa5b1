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
  kernel after an XLA transpose. Measured on v5e (scripts/pool_ab.py,
  b=128 fp32): 3.7x faster than the phase-stack kernel on lane-aligned
  channels (pool2: 0.39 vs 1.44 ms), within noise on pool1's C=96.
  TPU_FRAMEWORK_POOL=phases restores the old single-kernel lowering.
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


def env_variant(env_name: str, default: str, allowed: tuple) -> str:
    """Resolve a lowering-variant switch from the environment (shared by
    TPU_FRAMEWORK_CONV / _POOL here and _CHAIN in pallas_model).

    Resolved at TRACE time — outside the per-op jit, so the variant
    participates in the jit cache key. Build-time callers
    (configs.build_forward, the sharded tier) resolve variants EAGERLY via
    KernelVariants.resolve() and close over the result, so re-calling
    build_forward after an env flip returns a function with the new
    variant — the supported A/B workflow is build-per-variant (the round-3
    process-per-variant footgun is gone; tests/test_configs.py holds
    this)."""
    import os

    v = os.environ.get(env_name, "").strip().lower()
    if not v:
        return default  # unset or set-but-empty: the default
    if v not in allowed:
        raise ValueError(f"{env_name} must be {'|'.join(allowed)}, got {v!r}")
    return v


# Conv lowering variants:
#   "taps"  (default) — fq^2 tap matmuls per row block, static unroll.
#   "pairs" — adjacent-qw taps fused two-at-a-time: a host-side shifted
#             concat doubles the contraction dim (conv1: 48 -> 96 of the
#             MXU's 128 rows, the round-3 verdict's named underfill lever)
#             at 2x input HBM — the midpoint between "taps" (1x HBM, 48
#             contraction) and "fused" (fq^2 x HBM, measured 2x slower).
#   "fused" — host-side im2col + ONE big matmul per row block. Measured
#             ~2x SLOWER on v5e (docs/PALLAS_PERF.md round-3 results);
#             kept as the recorded negative result.
#   "vcol"  — in-kernel (VMEM) im2col over the qw taps: taps' 1x HBM
#             traffic with one fq*cs-contraction matmul per qh row
#             (round-5 lever; see _conv_vcol_kernel). ADOPTED as the
#             default from the 2026-07-31 on-chip A/B: with rowblock 64
#             it is the grid winner at b=128 — bf16 2.997 ms (42.7k
#             img/s, 0.42x v1_jit, from taps' 0.38x) and fp32 11.003 ms
#             (11.6k img/s, 0.53x v1_jit — the first tier/compute cell
#             to clear the 0.5x adoption bar).
#   "g8"    — phase-packed conv for strided convs (s>=2; s=1 falls back
#             to vcol): space-to-depth at g=2s puts g*g*C channels on the
#             lanes (conv1: 192 vs 48) and computes the 2x2 output phases
#             on separate grid programs (see _conv_g8_kernel). Round-5
#             named lever targeting conv1's measured data-movement bound.
#             Lowers through Mosaic and agrees with the fp32 XLA forward
#             inside the precision gate at b=128, fp32 and bf16 (v5e,
#             2026-09-26, PR 21 — after its output store stopped going
#             through a memref slice Mosaic refused); never timed.
def _conv_variant() -> str:
    return env_variant("TPU_FRAMEWORK_CONV", "vcol", ("taps", "pairs", "fused", "vcol", "g8"))


# Default output rows per conv program (TPU_FRAMEWORK_ROWBLOCK overrides).
# BH * Wo_pad is the matmul M dim. 64 (i.e. the whole 55/27-row image for
# the AlexNet convs, grid over batch only) won the 2026-07-31 on-chip
# rowblock sweep at every measured cell — 8/16/32/64 bf16 full pass:
# 3.588/3.642/3.219/2.997 ms with vcol — the per-program VMEM footprint
# (conv1 rb=64: ~360 KB window + ~1.4 MB acc) stays well under budget.
_ROW_BLOCK = 64
# W padded up to this multiple so the (BH, Wo, C) -> (BH*Wo, C) collapse is
# sublane-aligned for fp32 (8) and bf16 (16) alike.
_W_ALIGN = 16


# Output-row block height (the matmul M dim is rowblock * Wo_pad): a wider
# block amortizes per-program overhead and weight re-reads across more MXU
# work at more VMEM per program — the round-3 verdict's lever (b), made
# measurable now that the sep2 pool freed VMEM headroom.
def _row_block() -> int:
    return int(env_variant("TPU_FRAMEWORK_ROWBLOCK", str(_ROW_BLOCK), ("8", "16", "32", "64")))


# Output-channel (K) grid blocking for the taps variant — the third lever,
# named in the round-4 verdict as the follow-up if pairs/rowblock miss the
# bar. 0 = off (whole K per program, the historical layout). A K block
# splits the filter bank across grid programs: each program's weight slice
# and accumulator shrink K/nk-fold (conv2: 256 -> 128 halves the VMEM-
# resident weights and the fp32 acc), buying Mosaic pipelining headroom at
# the cost of re-reading the input window once per K block. Output blocks
# are disjoint, accumulation order per output element is unchanged —
# bitwise identical to unblocked, like the rowblock lever.
def _k_block() -> int:
    return int(env_variant("TPU_FRAMEWORK_KBLOCK", "0", ("0", "64", "128")))


# One warning per (k_block, K) per process: trace-time, so an unbounded
# per-call stream would drown the A/B log it is trying to protect.
_K_BLOCK_WARNED: set = set()


def _warn_k_block_dropped(k_block: int, kk: int) -> None:
    key = (k_block, kk)
    if key in _K_BLOCK_WARNED:
        return
    _K_BLOCK_WARNED.add(key)
    import warnings

    warnings.warn(
        f"requested k_block={k_block} does not apply to K={kk} (needs "
        f"K % k_block == 0 and K > k_block) — this conv runs UNBLOCKED; "
        "label its A/B rows kb=0 (KernelVariants.bind(K) makes the repr "
        f"state this: kb={k_block}->0(K={kk}))",
        RuntimeWarning,
        stacklevel=3,
    )


# Epilogue fusion (round-5 lever): "hpool" fuses the separable pool's H
# stage into the conv epilogue where the model's conv feeds a pool (the
# full-height conv output never round-trips HBM; the pool's first kernel
# launch disappears). Bitwise-neutral (exact max on the casted value).
# Applies where the geometry allows (taps/vcol, row_block >= ho); the
# model builder falls back to the separate pool otherwise.
# "block" goes all the way: the whole block (conv+ReLU+pool, +LRN when one
# trails the pool) runs as ONE VMEM-resident pass (ops/megakernel.py) —
# interior activations never touch HBM. Same geometry regime as hpool
# (taps/vcol, sep2, whole image per program, no k_block).
def _fuse_variant() -> str:
    return env_variant("TPU_FRAMEWORK_FUSE", "none", ("none", "hpool", "block"))


class KernelVariants(NamedTuple):
    """Resolved lowering-variant set — hashable, so it can ride jit static
    args. ``resolve()`` reads the environment ONCE; build-time callers
    (configs.build_forward, the sharded tier) resolve eagerly and close
    over the result, which kills the round-3 footgun where flipping an env
    var after the first forward silently kept the old variant inside the
    outer jit's trace: every ``build_forward`` call now re-reads the env
    and returns a fresh function carrying its variants explicitly."""

    conv: str = "vcol"
    pool: str = "sep2"
    row_block: int = _ROW_BLOCK
    k_block: int = 0
    fuse: str = "none"
    # Layer-binding metadata, NOT a lowering knob: the conv's output-channel
    # count when the variants are bound to one layer (``bind``; the tuner's
    # per-layer plans always bind). 0 = unbound/process-global. Lets the repr
    # state the EFFECTIVE k_block next to the requested one, so tuner logs
    # and A/B rows are self-labeling even though _warn_k_block_dropped fires
    # only once per process.
    k_channels: int = 0

    @classmethod
    def resolve(cls) -> "KernelVariants":
        return cls(
            conv=_conv_variant(), pool=_pool_variant(), row_block=_row_block(),
            k_block=_k_block(), fuse=_fuse_variant(),
        )

    def bind(self, k_channels: int) -> "KernelVariants":
        """The same knobs bound to a conv with K output channels."""
        return self._replace(k_channels=k_channels)

    def knobs(self) -> "KernelVariants":
        """The lowering knobs alone (binding stripped) — the equality the
        tuner's candidate dedup and tests should compare on."""
        return self._replace(k_channels=0)

    @property
    def effective_k_block(self) -> int:
        """The k_block that actually applies at K=k_channels (the geometry
        gate in _conv2d_pallas: K % k_block == 0 and K > k_block, else the
        conv runs unblocked). Unbound variants report the requested value —
        only a bound layer has a geometry to judge against. The hardware
        lane rule (k_block % 128) is NOT folded in: on chip that case
        raises rather than silently degrading."""
        if not self.k_block or not self.k_channels:
            return self.k_block
        if self.k_channels % self.k_block == 0 and self.k_channels > self.k_block:
            return self.k_block
        return 0

    def label(self) -> str:
        """Compact A/B-row/tuner-log label; requested->effective k_block is
        spelled out when a bound geometry drops the request."""
        kb = str(self.k_block)
        if self.k_channels and self.effective_k_block != self.k_block:
            kb = f"{self.k_block}->{self.effective_k_block}(K={self.k_channels})"
        return (
            f"conv={self.conv} pool={self.pool} rb={self.row_block} "
            f"kb={kb} fuse={self.fuse}"
        )

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


def _conv_fused_kernel(x_ref, w_ref, b_ref, o_ref, *, bh: int, wo_p: int, relu: bool):
    """im2col variant: x_ref (1, bh, wo_p, fq^2*cs), w_ref (fq^2*cs, K)."""
    kdim = x_ref.shape[-1]
    k = w_ref.shape[-1]
    acc = jnp.dot(
        x_ref[0].reshape(bh * wo_p, kdim),
        w_ref[:],
        preferred_element_type=jnp.float32,
        precision=_mxu_precision(x_ref.dtype),
    )
    _conv_epilogue(acc, b_ref, o_ref, bh=bh, wo_p=wo_p, k=k, relu=relu)


def _pairs_acc(xp_ref, wp_ref, leftover, *, fq: int, bh: int, wo_p: int):
    """Shared pair-matmul accumulation of both pairs kernels: xp_ref
    (1, Hs, Ws-1, 2*S*S*C) holds column j's and j+1's channels concatenated
    (host-side shifted concat), so tap pair (qw=2p, 2p+1) is ONE matmul with
    a doubled contraction dim. ``leftover`` is ``(x_ref, wl_ref)`` for the
    odd trailing tap (fq odd; reads the plain s2d buffer), or None when fq
    is even. Accumulation order is fixed (qh outer; pairs left-to-right,
    then the leftover), so results stay deterministic — but differ from
    "taps" in the last ulps (one 2cs-wide reduction vs two cs-wide adds);
    tests hold bitwise equality within a variant, allclose across variants.
    """
    cs2 = xp_ref.shape[-1]
    k = wp_ref.shape[-1]
    row0 = pl.program_id(1) * bh
    prec = _mxu_precision(xp_ref.dtype)
    n_pairs = fq // 2
    acc = jnp.zeros((bh * wo_p, k), jnp.float32)
    for qh in range(fq):
        for p in range(n_pairs):
            win = xp_ref[0, pl.ds(row0 + qh, bh), 2 * p : 2 * p + wo_p, :]
            acc = acc + jnp.dot(
                win.reshape(bh * wo_p, cs2),
                wp_ref[qh, p, :, :],
                preferred_element_type=jnp.float32,
                precision=prec,
            )
        if leftover is not None:
            x_ref, wl_ref = leftover
            cs = x_ref.shape[-1]
            win = x_ref[0, pl.ds(row0 + qh, bh), fq - 1 : fq - 1 + wo_p, :]
            acc = acc + jnp.dot(
                win.reshape(bh * wo_p, cs),
                wl_ref[qh, :, :],
                preferred_element_type=jnp.float32,
                precision=prec,
            )
    return acc


def _conv_pairs_kernel(
    xp_ref, x_ref, wp_ref, wl_ref, b_ref, o_ref, *, fq: int, bh: int, wo_p: int, relu: bool
):
    """Odd-fq pairs variant: pair matmuls plus the leftover tap from x_ref."""
    acc = _pairs_acc(xp_ref, wp_ref, (x_ref, wl_ref), fq=fq, bh=bh, wo_p=wo_p)
    _conv_epilogue(acc, b_ref, o_ref, bh=bh, wo_p=wo_p, k=wp_ref.shape[-1], relu=relu)


def _conv_pairs_even_kernel(
    xp_ref, wp_ref, b_ref, o_ref, *, fq: int, bh: int, wo_p: int, relu: bool
):
    """Even-fq pairs variant: pairs cover every tap, so the plain s2d buffer
    and the leftover weight tap are not operands at all — the round-4
    advisor flagged their dead VMEM residency/HBM traffic in the variant
    whose whole point is better HBM/MXU balance."""
    acc = _pairs_acc(xp_ref, wp_ref, None, fq=fq, bh=bh, wo_p=wo_p)
    _conv_epilogue(acc, b_ref, o_ref, bh=bh, wo_p=wo_p, k=wp_ref.shape[-1], relu=relu)


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
    480 vs 96) instead of fq skinny ones. This is "pairs"/"fused"'s fill
    win without their host-side HBM blowup — the concat is a VMEM lane
    relayout, whose cost is what the A/B measures. Accumulation: one
    reduction per qh over fq*cs (deterministic; differs from taps in the
    last ulps like the other variants — allclose across variants, bitwise
    within)."""
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
    variant: str | None = None,
    row_block: int | None = None,
    k_block: int | None = None,
    hpool: tuple | None = None,
) -> jax.Array:
    """Direct conv (+bias, optional fused ReLU) — thin wrapper resolving the
    lowering variant (explicit arg wins; env var otherwise) before entering
    jit. ``vma``: mesh axes the call varies over inside a check_vma=True
    shard_map (ops.vma). ``hpool=(window, stride)``: fuse the separable
    pool's H stage into the conv epilogue (requires variant taps/vcol and
    row_block >= the conv's output height; see _conv_epilogue) — the
    result has pooled H, full W; run :func:`maxpool_pallas_w` after."""
    return _conv2d_pallas(
        x, w, b, stride=stride, padding=padding, padding_w=padding_w,
        relu=relu,
        variant=variant if variant is not None else _conv_variant(),
        row_block=row_block if row_block is not None else _row_block(),
        k_block=k_block if k_block is not None else _k_block(),
        vma=tuple(vma) if vma is not None else None,
        hpool=hpool,
    )


@functools.partial(
    jax.jit,
    static_argnames=(
        "stride", "padding", "padding_w", "relu", "variant", "row_block",
        "k_block", "vma", "hpool",
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
    variant: str = "taps",
    row_block: int = _ROW_BLOCK,
    k_block: int = 0,
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

    if variant == "fused":
        # im2col variant: XLA materializes the tap-concatenated input
        # host-side (HBM cost ~fq^2 x input, still << compute at these
        # sizes) and the kernel is ONE (bh*wo_p, fq^2*cs) x (fq^2*cs, K)
        # MXU matmul per row block — 3-9x better array fill than the
        # tap-loop on conv1. Accumulation: one reduction over the whole
        # contraction (deterministic, but a DIFFERENT fixed order than the
        # tap-loop variant — pick one variant per process; tests hold
        # within-variant bitwise equality).
        xcol = jnp.concatenate(
            [
                xs[:, qh : qh + ho_p, qw : qw + wo_p, :]
                for qh in range(fq)
                for qw in range(fq)
            ],
            axis=-1,
        )  # (N, ho_p, wo_p, fq^2*cs)
        operands = (xcol, ws2d.reshape(fq * fq * cs, w.shape[-1]), b)
        kernel = functools.partial(_conv_fused_kernel, bh=bh, wo_p=wo_p, relu=relu)
        in_specs = [
            _vmem_spec((1, bh, wo_p, fq * fq * cs), lambda i, j: (i, j, 0, 0)),
            _vmem_spec(),
            _vmem_spec(),
        ]
    elif variant == "pairs" and fq >= 2:
        # Host-side shifted concat: xpair[..., j, :] carries column j's AND
        # j+1's channel blocks, so each kernel matmul contracts over 2*cs
        # (conv1: 96/128 MXU rows vs taps' 48/128) at 2x input HBM traffic.
        xpair = jnp.concatenate([xs[:, :, :-1, :], xs[:, :, 1:, :]], axis=-1)
        m = fq // 2
        wpair = jnp.concatenate(
            [ws2d[:, 0 : 2 * m : 2], ws2d[:, 1 : 2 * m : 2]], axis=2
        )  # (fq, m, 2*cs, K)
        if fq % 2:
            wlast = ws2d[:, fq - 1]  # (fq, cs, K): the odd leftover tap
            operands = (xpair, xs, wpair, wlast, b)
            kernel = functools.partial(
                _conv_pairs_kernel, fq=fq, bh=bh, wo_p=wo_p, relu=relu
            )
            in_specs = [
                _vmem_spec((1, hs, ws - 1, 2 * cs), lambda i, j: (i, 0, 0, 0)),
                _vmem_spec((1, hs, ws, cs), lambda i, j: (i, 0, 0, 0)),
                _vmem_spec(),
                _vmem_spec(),
                _vmem_spec(),
            ]
        else:
            # Even fq: pairs cover all taps — xs/wlast are not operands
            # (dead VMEM residency + HBM traffic otherwise; round-4 advisor).
            operands = (xpair, wpair, b)
            kernel = functools.partial(
                _conv_pairs_even_kernel, fq=fq, bh=bh, wo_p=wo_p, relu=relu
            )
            in_specs = [
                _vmem_spec((1, hs, ws - 1, 2 * cs), lambda i, j: (i, 0, 0, 0)),
                _vmem_spec(),
                _vmem_spec(),
            ]
    else:  # "taps"/"vcol" (and "pairs" at fq == 1, where there is nothing to pair)
        operands = (xs, ws2d, b)
        kern_fn = _conv_vcol_kernel if variant in ("vcol", "g8") else _conv_kernel
        kernel = functools.partial(kern_fn, fq=fq, bh=bh, wo_p=wo_p, relu=relu)
        kk = w.shape[-1]
        if hpool is not None:
            # Fused H-stage pool (see _conv_epilogue): caller contract, not
            # a silent fallback — the model builder gates on these.
            if bh != ho:
                raise ValueError(
                    f"hpool fusion needs the whole image per program "
                    f"(row_block {row_block} < ho {ho})"
                )
            if k_block:
                raise ValueError(
                    "hpool fusion does not compose with k_block (the fused "
                    "path has no K grid dim); unset one of them"
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
        # Mosaic constraint (measured on the real v5e, 2026-07-31): every
        # blocked operand's minor dim is k_block, and the lane tiling is 128
        # — a non-multiple (the env's 64 setting) cannot lower on chip
        # ("block shape is a multiple of the tiling size"). Interpret mode
        # has no tiling, so CI keeps exercising 64; on hardware the request
        # is REFUSED rather than silently dropped (same raise-not-fallback
        # policy as hpool): an A/B row labeled kb=64 measuring kb=0 is
        # mislabeled perf evidence (ADVICE round-5 item 1).
        k_block_ok = k_block % 128 == 0 or _interpret()
        if k_block and not k_block_ok:
            raise ValueError(
                f"k_block={k_block} cannot lower on {jax.default_backend()}: "
                "the lane tiling is 128, so k_block must be a multiple of 128 "
                "on hardware (interpret mode has no tiling); unset "
                "TPU_FRAMEWORK_KBLOCK or use 128"
            )
        if k_block and not (kk % k_block == 0 and kk > k_block):
            # Geometry fallback (e.g. conv1's K=96 under kb=128): legitimate
            # per-layer degradation, but it must be VISIBLE — a one-time
            # warning per (k_block, K) so A/B logs can label this layer kb=0.
            _warn_k_block_dropped(k_block, kk)
        if k_block and kk % k_block == 0 and kk > k_block:
            # Third grid dim over K blocks (the round-4 verdict's named
            # next lever): each program owns k_block output channels, so
            # the VMEM-resident weight slice and fp32 accumulator shrink
            # kk/k_block-fold at the cost of re-reading the input window
            # per K block (the x spec ignores the k index, so Mosaic can
            # keep the window resident across the inner dim). Outputs are
            # disjoint and per-element accumulation order is untouched —
            # bitwise identical to unblocked, like the rowblock lever.
            nk = kk // k_block
            # Bias rides as (1, K) with a (1, k_block) block: a rank-1
            # (k_block,) spec is illegal on chip — rank-1 tiling is
            # 256 for bf16 (128 lanes x 2 packing), so a 128 block was
            # rejected by the lowering. Rank-2 puts k_block on the lane
            # dim where 128 is exactly the tile. The epilogue's
            # broadcast add is rank-agnostic.
            operands = (xs, ws2d, b.reshape(1, kk))
            in_specs = [
                _vmem_spec((1, hs, ws, cs), lambda i, j, k: (i, 0, 0, 0)),
                _vmem_spec((fq, fq, cs, k_block), lambda i, j, k: (0, 0, 0, k)),
                _vmem_spec((1, k_block), lambda i, j, k: (0, k)),
            ]
            out = pl.pallas_call(
                kernel,
                grid=(n, nbh, nk),
                in_specs=in_specs,
                out_specs=_vmem_spec(
                    (1, bh, wo_p, k_block), lambda i, j, k: (i, j, 0, k)
                ),
                out_shape=vma_struct((n, ho_p, wo_p, kk), x.dtype, vma),
                compiler_params=_tc_params("parallel", "parallel", "parallel"),
                interpret=_interpret(),
                name=f"conv2d_{variant}_kblock",
            )(*operands)
            if ho_p != ho or wo_p != wo:
                out = out[:, :ho, :wo, :]
            return out
        in_specs = [
            _vmem_spec((1, hs, ws, cs), lambda i, j: (i, 0, 0, 0)),
            _vmem_spec(),
            _vmem_spec(),
        ]
    out = pl.pallas_call(
        kernel,
        grid=(n, nbh),
        in_specs=in_specs,
        out_specs=_vmem_spec((1, bh, wo_p, w.shape[-1]), lambda i, j: (i, j, 0, 0)),
        out_shape=vma_struct((n, ho_p, wo_p, w.shape[-1]), x.dtype, vma),
        compiler_params=_tc_params("parallel", "parallel"),
        interpret=_interpret(),
        name=f"conv2d_{variant}",
    )(*operands)
    if ho_p != ho or wo_p != wo:
        out = out[:, :ho, :wo, :]
    return out


def conv2d_pallas_hvalid(
    x, w, b, *, stride: int, padding_w: int, vma=None,
    variant: str | None = None, row_block: int | None = None,
    k_block: int | None = None,
):
    """Sharded-tier entry: VALID on H (halo-provided), padded on W, fused ReLU
    is NOT applied here (the sharded pipeline masks then relus)."""
    return conv2d_pallas(
        x, w, b, stride=stride, padding=0, padding_w=padding_w, vma=vma,
        variant=variant, row_block=row_block, k_block=k_block,
    )


def _pool_kernel(x_ref, o_ref, *, window: int, stride: int, ho: int, wo: int):
    """x_ref: (s*s, 1, hp, wp, C) stacked stride-phases; max over window taps.

    Tap (fy, fx) lives in phase (fy % s)*s + (fx % s) at spatial offset
    (fy//s, fx//s) — every in-kernel slice is unit-stride (Mosaic forbids
    strided vector slices; the phase split is done host-side by XLA).
    """
    s = stride
    c = x_ref.shape[-1]
    out = None
    for fy in range(window):
        for fx in range(window):
            ph = (fy % s) * s + (fx % s)
            qh, qw = fy // s, fx // s
            win = lax.slice(
                x_ref[ph, 0], (qh, qw, 0), (qh + ho, qw + wo, c)
            )
            out = win if out is None else jnp.maximum(out, win)
    o_ref[0] = out


def _pool_phases(x: jax.Array, s: int, hp: int, wp: int) -> jax.Array:
    """(N,H,W,C) -> (s*s, N, hp, wp, C): stride-phase views, zero-padded.

    Padded rows/cols are never read: kernel taps stop at fy,fx < window.
    """
    n, h, w, c = x.shape
    phases = []
    for r in range(s):
        for p in range(s):
            v = x[:, r::s, p::s, :][:, :hp, :wp, :]  # crop phases longer than hp/wp
            phases.append(
                jnp.pad(v, ((0, 0), (0, hp - v.shape[1]), (0, wp - v.shape[2]), (0, 0)))
            )
    return jnp.stack(phases)


def _pool_variant() -> str:
    # sep2 is the measured default (scripts/pool_ab.py).
    return env_variant("TPU_FRAMEWORK_POOL", "sep2", ("sep2", "phases"))


def maxpool_pallas(
    x: jax.Array, *, window: int, stride: int, vma=None, variant: str | None = None
) -> jax.Array:
    """Window max — thin wrapper resolving the lowering variant (explicit
    arg wins; env var otherwise) before entering jit. ``vma``: see ops.vma."""
    vma = tuple(vma) if vma is not None else None
    if (variant if variant is not None else _pool_variant()) == "phases":
        return _maxpool_phases(x, window=window, stride=stride, vma=vma)
    return _maxpool_sep2(x, window=window, stride=stride, vma=vma)


@functools.partial(jax.jit, static_argnames=("window", "stride", "vma"))
def _maxpool_phases(x: jax.Array, *, window: int, stride: int, vma=None) -> jax.Array:
    n, h, wdt, c = x.shape
    s = stride
    ho = (h - window) // s + 1
    wo = (wdt - window) // s + 1
    qmax = (window - 1) // s
    hp, wp = ho + qmax, wo + qmax
    xph = _pool_phases(x, s, hp, wp)
    kernel = functools.partial(_pool_kernel, window=window, stride=s, ho=ho, wo=wo)
    return pl.pallas_call(
        kernel,
        grid=(n,),
        in_specs=[_vmem_spec((s * s, 1, hp, wp, c), lambda i: (0, i, 0, 0, 0))],
        out_specs=_vmem_spec((1, ho, wo, c), lambda i: (i, 0, 0, 0)),
        out_shape=vma_struct((n, ho, wo, c), x.dtype, vma),
        compiler_params=_tc_params("parallel"),
        interpret=_interpret(),
        name="maxpool_phases",
    )(xph)


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
    movement — which is the whole advantage over the phase-stack path
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
def _maxpool_sep2(x: jax.Array, *, window: int, stride: int, vma=None) -> jax.Array:
    """Separable two-stage pool: rows, transpose, rows again, transpose."""
    y = _pool_rows(x, window=window, stride=stride, vma=vma)  # (N, ho, W, C)
    yt = jnp.swapaxes(y, 1, 2)                           # (N, W, ho, C)
    z = _pool_rows(yt, window=window, stride=stride, vma=vma)  # (N, wo, ho, C)
    return jnp.swapaxes(z, 1, 2)                         # (N, ho, wo, C)


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
