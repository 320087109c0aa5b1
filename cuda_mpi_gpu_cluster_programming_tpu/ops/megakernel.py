"""Fused-block Pallas megakernels: one VMEM-resident pass per Blocks 1-2
block (ROADMAP item 1 — the kernel the roofline layer built the judge for).

``observability.roofline.fused_blocks`` prices what a block-fused pass is
worth before it exists: staged execution round-trips every interior
activation through HBM (conv writes, pool reads, pool writes, LRN reads),
and ``staged − fused`` bytes is exactly those 2x-interior-activation
round-trips. This module deletes them: block 1 = Conv1→ReLU→Pool1 and
block 2 = Conv2→ReLU→Pool2→LRN2 each run as ONE ``pallas_call`` whose
program reads the block input + params from HBM once and writes the block
output once — everything between lives in VMEM registers.

In-kernel structure per program (one image; grid over batch only):

- **conv**: the vcol/taps accumulation from ``pallas_kernels`` verbatim —
  per-qh lane-axis concat (vcol) or tap loop (taps) over the space-to-depth
  input, fp32 accumulator, fixed order — so the conv numerics are bitwise
  the staged kernel's (whole image per program == row_block >= ho, the
  same regime hpool fusion requires).
- **epilogue**: bias + ReLU + cast for fp32/bf16; for int8w the per-channel
  rescale lands BETWEEN the fp32 accumulation and the bias
  (``precision.quantize``'s contract) — on the UNCAST accumulator, which
  the staged chain cannot do (its conv kernel writes bf16 before the host
  rescale), so the int8w megakernel is gated by tolerance, not bitwise.
- **pool**: the separable sep2 pool, both stages in-register: the H stage
  is the untiled-leading-axis phase-split reshape (``_axis_pool_kernel``'s
  math), then an in-register axis swap puts W leading for the same split.
  Valid windows never read the W alignment padding (max tap column is
  ``wo - 1``), so the relu(bias) garbage in padded columns stays inert.
- **LRN** (block 2): the banded 0/1-matrix matmul of ``_lrn_kernel``, all
  math fp32, on the pooled value.

Off-TPU the kernel runs in Pallas interpreter mode like every kernel in
``pallas_kernels`` — CPU tests hold fp32/bf16 outputs bitwise equal to the
staged Pallas chain (tests/test_megakernel.py). On the chip both blocks
lower through Mosaic — the in-register W-axis swap included — and agree
with the fp32 XLA forward inside the precision gate at b=128, 227x227,
fp32 and bf16 (v5e, 2026-09-26, PR 21; compiled and compared, not timed).
The autotuner only selects the fused candidate where it measures faster
under a ToleranceGate pass.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from . import pallas_kernels as pk
from .vma import vma_struct


def block_fusible_reason(
    *,
    variant: str,
    row_block: int,
    out_h: int,
    pool_window: int,
) -> str:
    """Why ``fuse="block"`` cannot lower for this knob/geometry set
    ('' = it can). The ONE gate the model builder
    (``pallas_model._conv_then_pool``), the tuner's candidate space
    (``tuning.space.prune_reason``), and the kernel wrapper all consult,
    so the three cannot drift."""
    if pool_window <= 0:
        return "block fusion needs an adjacent pool"
    if variant not in ("taps", "vcol"):
        return f"block fusion supports taps/vcol only (conv={variant})"
    if row_block < out_h:
        return (
            f"block fusion needs the whole image per program "
            f"(row_block {row_block} < ho {out_h})"
        )
    return ""


def _pool_leading_axis(out: jax.Array, *, window: int, stride: int, po: int) -> jax.Array:
    """Max-pool the LEADING axis of a (rows, width, K) in-register value via
    the untiled-leading-axis phase split — the same math as
    ``pallas_kernels._axis_pool_kernel``, on a value instead of a ref.
    Zero-padded rows never enter a valid window (taps stop at
    ``(po-1)*stride + window - 1 < rows``), mirroring ``_pool_rows``."""
    qmax = (window - 1) // stride
    q_rows = po + qmax
    rows, width, k = out.shape
    if rows < q_rows * stride:
        out = jnp.concatenate(
            [out, jnp.zeros((q_rows * stride - rows, width, k), out.dtype)],
            axis=0,
        )
    u = out[: q_rows * stride].reshape(q_rows, stride, width, k)
    res = None
    for fy in range(window):
        q, p = fy // stride, fy % stride
        win = u[q : q + po, p]
        res = win if res is None else jnp.maximum(res, win)
    return res


def _block_kernel(
    *refs,
    fq: int,
    ho: int,
    wo_p: int,
    conv_variant: str,
    pool: tuple,
    lrn: tuple | None,
    has_scale: bool,
    mid_dtype,
):
    """One fused block for one image: conv accumulation → epilogue →
    two-stage in-register pool → optional LRN → single write.

    ``pool`` = (window, stride, hp_o, wp_o); ``lrn`` = (size, alpha, beta,
    k, alpha_over_size) or None; ``mid_dtype`` is the interior compute
    dtype the staged chain would round-trip (x.dtype, or bf16 for int8w).
    """
    if has_scale:
        x_ref, w_ref, b_ref, s_ref, o_ref = refs
    else:
        x_ref, w_ref, b_ref, o_ref = refs
        s_ref = None
    cs = x_ref.shape[-1]
    k = w_ref.shape[-1]
    prec = pk._mxu_precision(x_ref.dtype)
    acc = jnp.zeros((ho * wo_p, k), jnp.float32)
    if conv_variant == "vcol":
        # _conv_vcol_kernel's accumulation verbatim (row0 = 0: whole image).
        for qh in range(fq):
            wide = jnp.concatenate(
                [
                    x_ref[0, pl.ds(qh, ho), qw : qw + wo_p, :].reshape(
                        ho * wo_p, cs
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
    else:  # taps — _conv_kernel's fixed (qh, qw) order
        for qh in range(fq):
            for qw in range(fq):
                win = x_ref[0, pl.ds(qh, ho), qw : qw + wo_p, :]
                acc = acc + jnp.dot(
                    win.reshape(ho * wo_p, cs),
                    w_ref[qh, qw, :, :],
                    preferred_element_type=jnp.float32,
                    precision=prec,
                )
    out = acc.reshape(ho, wo_p, k)
    if s_ref is not None:
        # int8w epilogue rescale: per-channel scale between the fp32
        # accumulation and the bias, on the uncast accumulator.
        out = out * s_ref[:]
    out = out + b_ref[:].astype(jnp.float32)
    out = jnp.maximum(out, 0.0)  # the block contract is Conv→ReLU→Pool
    out = out.astype(mid_dtype)
    pwin, pstr, hp_o, wp_o = pool
    out = _pool_leading_axis(out, window=pwin, stride=pstr, po=hp_o)
    out = jnp.swapaxes(out, 0, 1)  # (wo_p, hp_o, K): W leads for stage 2
    out = _pool_leading_axis(out, window=pwin, stride=pstr, po=wp_o)
    out = jnp.swapaxes(out, 0, 1)  # (hp_o, wp_o, K)
    if lrn is not None:
        size, alpha, beta, lk, aos = lrn
        xf = out.astype(jnp.float32)  # _lrn_kernel: all math fp32
        h2, w2, c2 = xf.shape
        half = size // 2
        ci = lax.broadcasted_iota(jnp.int32, (c2, c2), 0)
        cj = lax.broadcasted_iota(jnp.int32, (c2, c2), 1)
        band = (jnp.abs(ci - cj) <= half).astype(jnp.float32)
        sq = (xf * xf).reshape(h2 * w2, c2)
        ssum = jnp.dot(
            sq, band,
            preferred_element_type=jnp.float32,
            precision=lax.Precision.HIGHEST,
        ).reshape(h2, w2, c2)
        a = alpha / size if aos else alpha
        out = xf / (lk + a * ssum) ** beta
    o_ref[0] = out.astype(o_ref.dtype)


def conv_block_pallas(
    x: jax.Array,
    w: jax.Array,
    b: jax.Array,
    *,
    stride: int,
    padding: int,
    pool_window: int,
    pool_stride: int,
    lrn=None,
    variant: str = pk.KernelVariants.conv,
    row_block: int = pk.KernelVariants.row_block,
    scale: jax.Array | None = None,
    vma=None,
) -> jax.Array:
    """One fused block: conv(+bias+ReLU) → max-pool (→ LRN) in a single
    Pallas pass. x: (N,H,W,C); w: (F,F,C,K) — for int8w pass the int8
    values cast to bf16 plus their per-channel fp32 ``scale``.

    ``lrn``: a ``models.alexnet.LrnSpec`` (or None) to fold the block's
    trailing LRN into the same pass (block 2). ``scale``: the int8w
    epilogue rescale, applied between accumulation and bias. Output dtype:
    x.dtype for fp32/bf16; for int8w, bf16 (block 1) or fp32 after the
    in-kernel LRN (block 2) — matching the staged quantized chain's
    stage-boundary dtypes. Geometry the gate refuses is a raise, never a
    silent fallback (same policy as hpool)."""
    lrn_t = None
    if lrn is not None:
        lrn_t = (
            int(lrn.size), float(lrn.alpha), float(lrn.beta), float(lrn.k),
            bool(lrn.alpha_over_size),
        )
    return _conv_block(
        x, w, b, scale,
        stride=stride,
        padding=padding,
        pool_window=pool_window,
        pool_stride=pool_stride,
        lrn=lrn_t,
        variant=variant,
        row_block=row_block,
        vma=tuple(vma) if vma is not None else None,
    )


@functools.partial(
    jax.jit,
    static_argnames=(
        "stride", "padding", "pool_window", "pool_stride", "lrn", "variant",
        "row_block", "vma",
    ),
)
def _conv_block(
    x: jax.Array,
    w: jax.Array,
    b: jax.Array,
    scale: jax.Array | None,
    *,
    stride: int,
    padding: int,
    pool_window: int,
    pool_stride: int,
    lrn: tuple | None,
    variant: str,
    row_block: int,
    vma=None,
) -> jax.Array:
    n, h, wdt, c = x.shape
    f = w.shape[0]
    s = stride
    ho = (h - f + 2 * padding) // s + 1
    wo = (wdt - f + 2 * padding) // s + 1
    why = block_fusible_reason(
        variant=variant, row_block=row_block, out_h=ho, pool_window=pool_window,
    )
    if why:
        raise ValueError(why)
    fq = -(-f // s)
    if padding:
        x = jnp.pad(x, ((0, 0), (padding, padding), (padding, padding), (0, 0)))
    # Whole image per program: bh == ho (the hpool regime), W sublane-aligned.
    wo_p = -(-wo // pk._W_ALIGN) * pk._W_ALIGN
    hs, ws = ho + fq - 1, wo_p + fq - 1
    xs = pk._space_to_depth(x, s, hs, ws)
    ws2d = pk._weights_to_depth(w, s, fq)
    cs = s * s * c
    kk = w.shape[-1]
    hp_o = (ho - pool_window) // pool_stride + 1
    wp_o = (wo - pool_window) // pool_stride + 1
    if scale is not None:
        mid_dtype = jnp.bfloat16
        out_dtype = jnp.float32 if lrn is not None else jnp.bfloat16
    else:
        mid_dtype = out_dtype = x.dtype
    kernel = functools.partial(
        _block_kernel,
        fq=fq, ho=ho, wo_p=wo_p, conv_variant=variant,
        pool=(pool_window, pool_stride, hp_o, wp_o),
        lrn=lrn, has_scale=scale is not None, mid_dtype=mid_dtype,
    )
    in_specs = [
        pk._vmem_spec((1, hs, ws, cs), lambda i: (i, 0, 0, 0)),
        pk._vmem_spec(),
        pk._vmem_spec(),
    ]
    operands = [xs, ws2d, b]
    if scale is not None:
        in_specs.append(pk._vmem_spec())
        operands.append(scale)
    return pl.pallas_call(
        kernel,
        grid=(n,),
        in_specs=in_specs,
        out_specs=pk._vmem_spec((1, hp_o, wp_o, kk), lambda i: (i, 0, 0, 0)),
        out_shape=vma_struct((n, hp_o, wp_o, kk), out_dtype, vma),
        compiler_params=pk._tc_params("parallel"),
        interpret=pk._interpret(),
        name=(
            f"conv_block_{variant}_pool"
            + ("_lrn" if lrn is not None else "")
            + ("_int8w" if scale is not None else "")
        ),
    )(*operands)


def int8w_conv_block_pallas(
    x: jax.Array,
    q: jax.Array,
    scale: jax.Array,
    b: jax.Array,
    *,
    stride: int,
    padding: int,
    pool_window: int,
    pool_stride: int,
    lrn=None,
    variant: str = pk.KernelVariants.conv,
    row_block: int = pk.KernelVariants.row_block,
    vma=None,
) -> jax.Array:
    """The dequant-free int8w megakernel variant: int8 weights cast to bf16
    (exact for |q| <= 127), bf16 MACs, fp32 accumulate, per-channel rescale
    in the epilogue — ``precision.quantize.int8w_conv``'s numerics fused
    through the whole block, minus the staged path's bf16 round-trip of the
    accumulator before the rescale."""
    return conv_block_pallas(
        x.astype(jnp.bfloat16),
        q.astype(jnp.bfloat16),
        b.astype(jnp.float32),
        stride=stride,
        padding=padding,
        pool_window=pool_window,
        pool_stride=pool_stride,
        lrn=lrn,
        variant=variant,
        row_block=row_block,
        scale=scale.astype(jnp.float32),
        vma=vma,
    )
