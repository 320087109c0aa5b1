"""Pallas short convolution of the linear-attention mix: one pass over a
projection makes the stored ``q``, ``k`` or ``v``.

``short_conv_mix(x (B, H, S, E) float32, taps (K, H, E), *, l2norm,
out_dtype)`` returns ``f(silu(sum_j taps[j] x[t - (K-1) + j]))`` in
``out_dtype``, zero history before a sequence's first token, ``f`` the
division by ``sqrt(sum over E of the squares + L2_EPS)`` where ``l2norm`` and
nothing otherwise: what ``models.kda_moe._conv_mix_plain`` writes as
``_l2norm(jax.nn.silu(_short_conv(x, taps))).astype(out_dtype)``, term by
term, in that order and in float32.

**Why a kernel.** The sequence lies along the sublanes, so ``x[t - back]`` is
a read shifted by ``back`` sublanes. XLA's fusions of the ``jax.numpy`` form
run those reads at a vector register every ~24 cycles, and the l2norm costs a
second pass over a float32 intermediate: at ``(2, 64, 8192, 128)`` five passes
and 13.2 ms for the three arrays of a layer, whose bytes (float32 in, bf16
out: 2.42 GB) take 2.95 ms at the v5e's HBM peak. Here the projection's tile
is read once, the shifted terms are taken from it in VMEM, the sum of squares
never leaves the registers and only the stored type reaches HBM.

**The kernel.** Grid ``(B, H / heads, S / tile)``, every axis parallel. A
program holds ``(heads, tile, E)`` of ``x``, the 8 rows before the tile
through a second ``BlockSpec`` of the same array (taken as zero where the tile
is the sequence's first, so no program waits for another), and its heads'
taps. It walks the tile ``ROWS`` rows at a time: the rows, under the 8 before
them, are one value of ``ROWS + 8`` rows, and the term ``back`` places before
is its slice ``[8 - back, 8 - back + ROWS)`` — a sublane offset the compiler
turns into a rotate and a select per register (``pltpu.roll`` with the first
rows patched reads the same time to a percent).

**Its sizes, from the v5e** (one array at ``(2, 64, 8192, 128)``, float32 in,
bf16 out, with / without the l2norm; a kernel that only casts reads 1.21-1.25
ms, the ``jax.numpy`` form 4.98-5.02 / 3.35-3.38; PERF.md section 6 has the
sweep). The step of the walk has to be long enough that the lane reduction's
latency hides behind other rows: 16, 32, 64, 128, 256 rows read 8.90, 4.53,
2.66, 1.80, 1.58 ms with the l2norm at a tile of 1,024 (1.49-1.59 without,
but for 2.42 at 16). The block has to be large enough that a grid step's
fixed cost hides behind its copy: tiles of 256, 512, 1,024, 2,048, 4,096,
8,192 rows of one head read 2.24, 1.79, 1.49, 1.38, 1.32, 1.29 ms without the
l2norm, and two or four heads of a shorter tile read what one head of the
same elements reads. ``BLOCK_ELEMENTS`` is 4,096 rows of 128: 1.29 / 1.32 ms
(76% of the HBM peak for the three arrays of a layer) in 6 MB of VMEM, where
twice that buys 2% for 12 of the 16 MB a kernel may scope.

**What it tells the scheduler.** The call carries a ``CostEstimate`` (its
bytes, a few operations an element). Without one the compiler's scheduler
takes a custom call to cost nothing and starts no prefetch under it: in the
solar step the next projection then read its 67 MB of weights from HBM and
not from VMEM, and each of the nine q/k/v products ran 5.98 ms for 5.77
(``kernels.kda_proj_roofline`` 85.3% for 87.5%; PERF.md section 6).

**Where it runs.** The kernel wants the head's channels in whole lanes and
the sequence in whole registers of the stored type: :func:`fits` says so from
the shapes alone, and the caller keeps the ``jax.numpy`` form for what does
not fit.

Runs in Pallas interpreter mode off the TPU (``ops.vma.interpret_mode``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .grouped_matmul import fit_tile
from .vma import interpret_mode as _interpret

LANES = 128
L2_EPS = 1e-6
HISTORY = 8  # rows of the block before a tile: one float32 register's sublanes
UNIT = 16  # rows a tile or a step is whole multiples of: one bf16 register's sublanes
# Elements of one program's block of x (float32 in and at most float32 out, each
# double-buffered: 16 bytes an element of the default 16 MB of scoped VMEM).
BLOCK_ELEMENTS = 4096 * 128
ROWS = 256  # rows of one step of the walk over the tile


def fits(seq: int, channels: int, taps: int) -> bool:
    """Whether the kernel takes a ``(B, H, seq, channels)`` projection: the
    channels fill whole lanes, the filter reaches no further back than the
    history block, and the sequence is whole registers of the stored type."""
    return channels % LANES == 0 and 1 <= taps <= HISTORY + 1 and seq % UNIT == 0


def _kernel(x_ref, before_ref, taps_ref, out_ref, *, l2norm, rows):
    heads, tile, _e = x_ref.shape
    n = taps_ref.shape[1]
    first = pl.program_id(2) == 0

    def head(h):
        taps = taps_ref[h].astype(jnp.float32)  # (K, E)
        before = jnp.where(first, 0.0, before_ref[h])

        def step(i, carry):
            r0 = pl.multiple_of(i * rows, rows)
            cur = x_ref[h, pl.ds(r0, rows), :]
            prev = x_ref[h, pl.ds(pl.multiple_of(jnp.maximum(r0 - HISTORY, 0), HISTORY), HISTORY), :]
            ext = jnp.concatenate([jnp.where(i == 0, before, prev), cur], axis=0)
            acc = cur * taps[n - 1][None, :]
            for back in range(1, n):  # the token ``back`` places before
                acc = acc + ext[HISTORY - back : HISTORY - back + rows] * taps[n - 1 - back][None, :]
            y = jax.nn.silu(acc)
            if l2norm:
                y = y * lax.rsqrt(jnp.sum(y * y, axis=-1, keepdims=True) + L2_EPS)
            out_ref[h, pl.ds(r0, rows), :] = y.astype(out_ref.dtype)
            return carry

        lax.fori_loop(0, tile // rows, step, 0)

    for h in range(heads):
        head(h)


def short_conv_mix(
    x: jax.Array,
    taps: jax.Array,
    *,
    l2norm: bool,
    out_dtype,
    block_elements: int = BLOCK_ELEMENTS,
    rows: int = ROWS,
) -> jax.Array:
    """``f(silu(conv(x)))`` as the module says. x float32 ``(B, H, S, E)``;
    taps ``(K, H, E)`` in any float type; the result ``(B, H, S, E)`` in
    ``out_dtype``. The shapes must satisfy :func:`fits`. A program holds the
    longest tile of one head's sequence that ``block_elements`` allow, and
    several heads of a sequence shorter than that."""
    b, h, seq, e = x.shape
    n = taps.shape[0]
    if x.dtype != jnp.float32 or taps.shape != (n, h, e) or not fits(seq, e, n):
        raise ValueError(f"short_conv_mix: x {x.shape} {x.dtype} and taps {taps.shape} do not fit the kernel")
    ts = fit_tile(seq, max(UNIT, block_elements // e), unit=UNIT)
    hb = fit_tile(h, max(1, block_elements // (ts * e)), unit=1)
    rows = fit_tile(ts, rows, unit=UNIT)
    block = lambda bi, hi, si: (bi, hi, si, 0)
    blocks = ts // HISTORY  # history blocks a tile
    return pl.pallas_call(
        functools.partial(_kernel, l2norm=l2norm, rows=rows),
        grid=(b, h // hb, seq // ts),
        in_specs=[
            pl.BlockSpec((None, hb, ts, e), block),
            # the HISTORY rows before the tile; the first tile's are taken as zero
            pl.BlockSpec((None, hb, HISTORY, e), lambda bi, hi, si: (bi, hi, jnp.maximum(si * blocks - 1, 0), 0)),
            pl.BlockSpec((hb, n, e), lambda bi, hi, si: (hi, 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, hb, ts, e), block),
        out_shape=jax.ShapeDtypeStruct(x.shape, out_dtype),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "parallel", "parallel")),
        # without it the scheduler starts no prefetch of the next product's weights under the call
        cost_estimate=pl.CostEstimate(
            flops=x.size * (2 * n + 8), transcendentals=2 * x.size,
            bytes_accessed=x.size * (4 + jnp.dtype(out_dtype).itemsize) + taps.size * taps.dtype.itemsize,
        ),
        interpret=_interpret(),
        name="kda_short_conv_mix",
    )(x, x, jnp.swapaxes(taps, 0, 1))
