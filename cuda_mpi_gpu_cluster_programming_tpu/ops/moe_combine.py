"""Pallas combine of the expert tier: every token collects the rows that were
computed for it, and only those.

``moe_combine(results (span, D), row (T, k), weights (T, k), y (T, D), base)``
returns ``y + sum_j [base <= row[:, j] < base + span] weights[:, j] *
results[row[:, j] - base]`` in float32: ``results`` holds a span of the
padded rows of ``ops.grouped_matmul``'s last product, ``row[t, j]`` names the
row of token ``t``'s ``j``-th expert (-1 where no chip here holds it), and a
place outside the span is a place of no work. A chip that holds ``held / all``
of the experts computed ``T k held / all`` rows, and that many rows move, one
DMA each; the gather form (:func:`moe_combine_reference`) moves ``T k``.

**Rows as slabs.** The TPU's memory keeps a 2-D array in tiles of 8 rows (in
bf16, pairs of rows interleaved element by element), so one row of it is
neither contiguous nor something a DMA may slice (Mosaic: "Slice shape along
dimension 0 must be aligned to tiling"). The same numbers as ``(rows, D //
128, 128)`` keep every row in whole tiles of its own, 128 lanes wide and
contiguous: one row is one DMA, and in VMEM it is dense vector registers.
``moe_combine`` therefore takes ``results`` and ``y`` with any trailing shape
(the same for both) and :func:`row_slab` gives the one to use; the caller
keeps its arrays in that shape for as long as it can, because the compiler
moves bytes to change it (a 2-D call is reshaped here, for the tests).

**Held places first.** Before the kernel, in plain ``jax.numpy``: each token's
places that fall in the span are moved to the front of its ``k``, in place
order, as rows of the span, -1 behind them. A comparison of ``k x k`` places a
token, one fused pass over ``(T, k)``.

**The kernel.** Grid ``(token block, slab tile)``. ``results`` stays in HBM;
the block's rows and weights arrive in SMEM, ``y``'s block in VMEM (aliased to
the output, so a second span adds its rows and costs its rows). One scalar
loop over the block's tokens, and for a token over its places until the first
that is absent: a held place starts the DMA of its row into a ring of slots
and, once the ring is full, first lands the oldest (wait, then ``y[token] +=
weight * row`` in float32). Rows land in the order their DMAs were started,
so a token's places are summed in place order and the result equals the
gather form's bit for bit on the chip (the CPU backend contracts a multiply
and an add into one rounding, differently in the two programs: 1e-6 there).

**Where it is worth it.** A row costs the kernel about what 16,384 gathered
elements cost the gathers, whatever its width, so narrow rows at few places a
token are cheaper gathered: :func:`worth_a_kernel` decides from the shapes,
and ``models.moe_share`` keeps both forms.

Runs in Pallas interpreter mode off the TPU (``ops.vma.interpret_mode``).
"""

from __future__ import annotations

import functools
import math
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .grouped_matmul import fit_tile
from .vma import interpret_mode as _interpret

LANES = 128
# Rows in flight: what hides one DMA's latency behind the others' (each row is
# 4-14 KB; the ring costs slots x one row of VMEM). On the v5e 8, 16, 32, 64
# read 2.03, 1.96, 1.87, 1.73 ms a call at solar's shape (PERF.md section 6).
SLOTS = 64
# What one row costs the kernel beside its bytes (the DMA's issue and wait, the
# ring's bookkeeping: 50-60 ns on the v5e), in elements the gather form moves
# in that time (3.6-20 ps an element and place): `scripts/moe_combine_ab.py`
# measures both forms at five shapes, PERF.md section 6 has the readings.
ROW_COST_ELEMENTS = 16384
# Elements of one y block (float32, in and out, each double-buffered: 16 bytes
# an element of the default 16 MB of scoped VMEM).
BLOCK_ELEMENTS = 512 * 1024


def row_slab(d: int) -> Tuple[int, int]:
    """The trailing shape in which a row of ``d`` numbers is whole tiles of
    its own: 128 lanes wide where ``d`` divides, else one sublane of ``d``."""
    return (d // LANES, LANES) if d % LANES == 0 else (1, d)


def _kernel(row_ref, w_ref, res_ref, y_ref, out_ref, ring, sem, token_sc, weight_sc, *, k, slots):
    tb, sublanes, _lanes = out_ref.shape
    first = pl.program_id(1) * sublanes  # this slab tile's first sublane of a row
    # the SMEM blocks hold the places of several token blocks: this one's begin at
    mine = pl.program_id(0) % (row_ref.shape[0] // (tb * k)) * (tb * k)
    out_ref[...] = y_ref[...]

    def fetch(r, slot):
        return pltpu.make_async_copy(res_ref.at[r, pl.ds(first, sublanes)], ring.at[slot], sem.at[slot])

    def land(slot):
        fetch(0, slot).wait()
        t = token_sc[slot]
        out_ref[t] = out_ref[t] + weight_sc[slot] * ring[slot].astype(jnp.float32)

    def start(t, place, started):
        slot = started % slots

        @pl.when(started >= slots)
        def _oldest():
            land(slot)

        token_sc[slot] = t
        weight_sc[slot] = w_ref[place]
        fetch(row_ref[place], slot).start()
        return started + 1

    def token(t, started):
        # the token's held places stand first: stop at the first that is absent
        held = lambda state: (state[0] < k) & (row_ref[mine + t * k + jnp.minimum(state[0], k - 1)] >= 0)
        step = lambda state: (state[0] + 1, start(t, mine + t * k + state[0], state[1]))
        return lax.while_loop(held, step, (jnp.int32(0), started))[1]

    started = lax.fori_loop(0, tb, token, jnp.int32(0))
    waiting = jnp.minimum(started, slots)
    lax.fori_loop(0, waiting, lambda j, c: (land((started - waiting + j) % slots), c)[1], 0)


def _held_first(results_rows: int, row, weights, base):
    """``(rows, weights)`` with every token's held places first, in place
    order, as rows of this span; -1 from the first absent place on. What lets
    the kernel's scalar loop pass over a token in one step where it holds
    nothing, and visit only what it holds where it does."""
    local = row - base
    held = (local >= 0) & (local < results_rows)
    places = jnp.arange(row.shape[1], dtype=jnp.int32)
    # a held place's position among the held: the held places before it (no cumulative sum: a
    # comparison of k x k places a token, which the compiler fuses into one pass)
    rank = jnp.sum(held[:, None, :] & (places[None, :] < places[:, None]), axis=2, dtype=jnp.int32)
    lands = held[:, :, None] & (rank[:, :, None] == places)  # (T, place, position)
    absent = ~jnp.any(lands, axis=1)
    rows = jnp.sum(jnp.where(lands, local[:, :, None], 0), axis=1) - absent
    return rows, jnp.sum(jnp.where(lands, weights[:, :, None], 0.0), axis=1)  # one term a position: exact


def moe_combine(
    results: jax.Array,
    row: jax.Array,
    weights: jax.Array,
    y: jax.Array,
    base,
    *,
    block_elements: int = BLOCK_ELEMENTS,
    slots: int = SLOTS,
) -> jax.Array:
    """``y + sum_j [base <= row[:, j] < base + span] weights[:, j] * results[row[:, j] - base]``.

    results ``(span, *slab)`` in any float type; row int32 ``(T, k)``; weights
    float32 ``(T, k)``; y float32 ``(T, *slab)``, given up to the result; base
    an int32 scalar. ``slab`` is ``(D,)`` or the ``(sublanes, lanes)`` of
    :func:`row_slab`.
    """
    tokens, k = row.shape
    if results.ndim == 2:
        slab = row_slab(results.shape[1])
        out = moe_combine(
            results.reshape(-1, *slab), row, weights, y.reshape(-1, *slab), base,
            block_elements=block_elements, slots=slots,
        )
        return out.reshape(y.shape)
    span, sublanes, lanes = results.shape
    if weights.shape != row.shape or (y.shape, y.dtype) != ((tokens, sublanes, lanes), jnp.float32):
        raise ValueError(
            f"moe_combine: results {results.shape}, row {row.shape}, weights {weights.shape} "
            f"and y {y.shape} {y.dtype} do not fit each other"
        )
    # eight tokens of a whole row where that fits a block, else a tile of the row
    ts = fit_tile(sublanes, max(8, block_elements // (8 * lanes)), unit=8)
    tb = fit_tile(tokens, max(8, block_elements // (ts * lanes)), unit=8)
    # a 1-D block of SMEM is whole tiles of 1,024 words (or the whole array)
    shared = 1024 // math.gcd(tb * k, 1024)  # token blocks that share one block of places
    if (tokens // tb) % shared:
        shared = tokens // tb
    places = pl.BlockSpec((shared * tb * k,), lambda i, j: (i // shared,), memory_space=pltpu.SMEM)
    block = pl.BlockSpec((tb, ts, lanes), lambda i, j: (i, j, 0))
    rows, held_weights = _held_first(span, row.astype(jnp.int32), weights.astype(jnp.float32), base)
    return pl.pallas_call(
        functools.partial(_kernel, k=k, slots=slots),
        grid=(tokens // tb, sublanes // ts),
        in_specs=[places, places, pl.BlockSpec(memory_space=pl.ANY), block],
        out_specs=block,
        scratch_shapes=[
            pltpu.VMEM((slots, ts, lanes), results.dtype),
            pltpu.SemaphoreType.DMA((slots,)),
            pltpu.SMEM((slots,), jnp.int32),
            pltpu.SMEM((slots,), jnp.float32),
        ],
        out_shape=jax.ShapeDtypeStruct((tokens, sublanes, lanes), jnp.float32),
        input_output_aliases={3: 0},
        interpret=_interpret(),
        name="moe_combine",
    )(rows.reshape(-1), held_weights.reshape(-1), results, y)


def moe_combine_reference(results, row, weights, y, base):
    """The same sum in plain ``jax.numpy``, one gather over every token per
    place and a float32 multiply-add after it: the kernel's test oracle, the
    form the kernel replaced, and the form the routed sum keeps where
    :func:`worth_a_kernel` says the kernel has nothing to save."""
    inside = (row >= base) & (row < base + results.shape[0])
    for place in range(row.shape[1]):
        wide = (slice(None), place) + (None,) * (results.ndim - 1)  # [:, place, None] for rows of one axis
        rows = results[jnp.where(inside[:, place], row[:, place] - base, 0)].astype(jnp.float32)
        y = y + jnp.where(inside[wide], rows * weights[wide], 0.0)
    return y


def worth_a_kernel(tokens: int, k: int, d: int, span: int) -> bool:
    """Whether the kernel is the cheaper combine, from the shapes alone. The
    gathers move ``tokens x k x d`` elements whatever is held; the kernel
    passes over ``y`` once (as they do) and pays :data:`ROW_COST_ELEMENTS` for
    each row it fetches, of which a span holds at most ``span``. Wide rows
    and many places a token make the kernel worth it (dots: 16,384 x 8,192
    against 8,192 x 8 x 7,168; 3.49 ms against 0.98 on the v5e), narrow rows
    and few places do not (zaya: 16,384 x 3,840 against 4,096 x 1 x 2,048;
    0.155 ms against 0.26)."""
    return span * ROW_COST_ELEMENTS < tokens * k * d
