"""Pallas grouped matrix product: the expert tier's dropless block engine.

``grouped_matmul(lhs (M, K), rhs (G, K, N), tile_group (M // tm,))`` computes,
for every row tile ``i`` of ``tm`` rows, ``lhs[i*tm:(i+1)*tm] @ rhs[tile_group[i]]``.
The caller lays the rows out sorted by group with each group padded to whole
tiles (``models.mla_moe`` does, for the experts a chip holds), so a tile
belongs to exactly one group and the kernel is a plain tiled matmul whose
right-hand block is chosen by a scalar-prefetched table. Nothing is dropped
and no capacity is assumed: the number of tiles follows the rows routed.

Grid ``(row tile, N tile, K tile)``, K innermost with a float32 accumulator in
VMEM scratch. Consecutive row tiles of one group name the same right-hand
blocks. Operands go to the MXU in the type they are stored in
(``ops.reference.mxu_precision``: bf16 native, float32 at HIGHEST).

Runs in Pallas interpreter mode off the TPU (``ops.vma.interpret_mode``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .reference import mxu_precision
from .vma import interpret_mode as _interpret


def _kernel(tile_group_ref, lhs_ref, rhs_ref, out_ref, acc_sc):
    del tile_group_ref  # read by the index maps only
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_sc[...] = jnp.zeros_like(acc_sc)

    acc_sc[...] += lax.dot_general(
        lhs_ref[...], rhs_ref[0], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32, precision=mxu_precision(lhs_ref.dtype),
    )

    @pl.when(ki == pl.num_programs(2) - 1)
    def _finalize():
        out_ref[...] = acc_sc[...].astype(out_ref.dtype)


def fit_tile(size: int, want: int, unit: int = 128) -> int:
    """The largest divisor of ``size`` that is a multiple of ``unit`` and at
    most ``want``; ``size`` itself where it is no larger than ``want`` or has
    no such divisor (a block equal to the array's extent is always legal)."""
    if size <= want:
        return size
    for tile in range(want - want % unit, 0, -unit):
        if size % tile == 0:
            return tile
    return size


def grouped_matmul(
    lhs: jax.Array,
    rhs: jax.Array,
    tile_group: jax.Array,
    *,
    tile_rows: int,
    tile_k: int = 1024,
    tile_n: int = 1024,
    out_dtype=jnp.float32,
) -> jax.Array:
    """``out[i*tm:(i+1)*tm] = lhs[i*tm:(i+1)*tm] @ rhs[tile_group[i]]``.

    lhs ``(M, K)`` with ``M`` a multiple of ``tile_rows``; rhs ``(G, K, N)``;
    tile_group int32 ``(M // tile_rows,)`` with values in ``[0, G)``.
    """
    m, k = lhs.shape
    g, k2, n = rhs.shape
    tm = tile_rows
    if k != k2 or m % tm or tile_group.shape != (m // tm,):
        raise ValueError(
            f"grouped_matmul: lhs {lhs.shape}, rhs {rhs.shape}, tile_group "
            f"{tile_group.shape} do not fit tiles of {tm} rows"
        )
    tk, tn = fit_tile(k, tile_k), fit_tile(n, tile_n)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(m // tm, n // tn, k // tk),
        in_specs=[
            pl.BlockSpec((tm, tk), lambda i, j, kk, tg: (i, kk)),
            pl.BlockSpec((1, tk, tn), lambda i, j, kk, tg: (tg[i], kk, j)),
        ],
        out_specs=pl.BlockSpec((tm, tn), lambda i, j, kk, tg: (i, j)),
        scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)],
    )
    return pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        interpret=_interpret(),
        name="grouped_matmul",
    )(tile_group.astype(jnp.int32), lhs, rhs)


@functools.partial(jax.jit, static_argnames=("tile_rows",))
def grouped_matmul_reference(lhs, rhs, tile_group, *, tile_rows: int):
    """The same product in plain ``jax.numpy`` (the kernel's test oracle)."""
    tiles = lhs.reshape(-1, tile_rows, lhs.shape[1])
    out = jnp.einsum(
        "tmk,tkn->tmn", tiles, rhs[tile_group],
        preferred_element_type=jnp.float32, precision=mxu_precision(lhs.dtype),
    )
    return out.reshape(lhs.shape[0], rhs.shape[2])
