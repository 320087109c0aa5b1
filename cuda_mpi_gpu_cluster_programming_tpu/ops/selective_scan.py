"""Pallas selective scan (Mamba-1, arXiv:2312.00752): the state-space tier's
block engine, a scan on the vector units.

Per sequence, with a state ``h (C, N)`` in float32 that starts at zero, ``C``
channels and ``N`` states a channel::

    h_t = exp(Delta_t A) * h_{t-1} + (Delta_t x_t) B_t^T
    y_t = h_t C_t + D * x_t

``Delta_t (C,)`` is the step of every channel at token ``t``, ``A (C, N)`` is
negative, ``B_t`` and ``C_t (N,)`` are the token's input and output maps, one
for all channels. The decay ``exp(Delta_t[c] A[c, n])`` differs for every
(channel, state) pair, so unlike ``ops.kda``'s gated delta rule a chunk has no
matmul form: nothing here touches the MXU. :func:`mamba_recurrence` is exactly
the above, one token at a time, and is the truth the tests hold the kernel to.

**The kernel is a token loop.** Grid ``(batch, channel block, chunk)``, the
chunk axis sequential with the block's state in VMEM scratch (as
``ops.kda.kda_chunked`` carries its own). The state lies states-on-sublanes,
channels-on-lanes: ``(N, channel_block)``, a ``(16, 128)`` pair of tiles per
128 channels, carried by the loop across a chunk's tokens. A token then costs,
per 128 channels, one ``exp`` and five multiply-adds over that pair of tiles:
``Delta_t`` and ``Delta_t x_t`` are rows, broadcast down the sublanes as they
are loaded; ``B_t`` and ``C_t`` are columns, broadcast across the lanes ONCE a
chunk for all the block's channels (the preamble), so the loop reads them as
whole tiles. The sum over the states is left half done inside the loop (the
two tiles of a pair added: 8 partial sums a channel) and finished for the
whole chunk at once after it.

**Every decay is the exponential of ONE token's ``Delta A``**: at most 1, and
an underflow is a state that is zero in float32 anyway. No sum of ``Delta A``
over several tokens is ever exponentiated, alone or as a difference (the
lesson of ``kda.chunk_log_decay_min``: such a sum reaches the hundreds within
a chunk). A log-depth scan over a chunk would need the same care and three
times the vector work for a unit that is the bottleneck already; it was not
built (``scripts/mamba_scan_ab.py`` records what was tried).

``x`` and ``y`` are in the stored type; ``Delta``, ``A``, ``B``, ``C``, ``D``,
the state and every product are float32. Forward only, from a zero state.

Runs in Pallas interpreter mode off the TPU (``ops.vma.interpret_mode``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .vma import interpret_mode as _interpret

_LANES = 128  # channels of one tile
_SUBLANES = 8  # states of one tile
# A chunk's B and C across the lanes and its half-summed outputs are 16 KB a
# token and 32 bytes a (token, channel): 25 MB at 256 tokens of 2,560 channels,
# 36 MB with the double-buffered blocks in bf16 and 65 MB in float32, past the 16 MB
# a kernel is given unasked (a v5e core has 128 MiB).
_VMEM_LIMIT = 96 * 1024 * 1024


def _kernel(x_ref, delta_ref, a_ref, b_ref, c_ref, d_ref, y_ref, state_sc, bb_sc, cc_sc, u_sc, part_sc, *, unroll):
    """One (batch, channel block, chunk) program. x_ref, delta_ref, y_ref:
    (1, T, cb); a_ref: (N, cb); b_ref, c_ref: (1, N, T) — sequence-minor, a
    token's map a column; d_ref: (1, cb). Scratch: the state (N, cb); the
    chunk's B and C as one (N, 128) tile a token; Delta x (T, cb); the
    half-summed outputs (T, 8, cb)."""
    ci = pl.program_id(2)
    f32 = jnp.float32
    n, cb = state_sc.shape
    t_chunk = u_sc.shape[0]
    groups = cb // _LANES

    @pl.when(ci == 0)
    def _init():
        state_sc[...] = jnp.zeros_like(state_sc)

    # the preamble: every token's B and C across the lanes, once for all the block's channels
    b_all, c_all = b_ref[0], c_ref[0]  # (N, T)
    for t in range(t_chunk):
        bb_sc[t] = jnp.broadcast_to(b_all[:, t : t + 1], (n, _LANES))
        cc_sc[t] = jnp.broadcast_to(c_all[:, t : t + 1], (n, _LANES))
    u_sc[...] = delta_ref[0] * x_ref[0].astype(f32)  # (T, cb)
    a = [a_ref[:, g * _LANES : (g + 1) * _LANES] for g in range(groups)]  # (N, 128) each

    def trip(i, h):
        """``unroll`` tokens from token ``i * unroll`` on, written out (Mosaic
        loads rows at a dynamic offset only as whole tiles of 8, and unrolls a
        loop fully or not at all); ``h`` is the state before them, a (N, 128)
        pair of tiles per 128 channels."""
        t0 = pl.multiple_of(i * unroll, unroll)
        h = list(h)
        for g in range(groups):  # independent chains: the scheduler interleaves them
            at = slice(g * _LANES, (g + 1) * _LANES)
            dt, u = delta_ref[0, pl.ds(t0, unroll), at], u_sc[pl.ds(t0, unroll), at]  # (unroll, 128)
            for j in range(unroll):
                h[g] = jnp.exp(dt[j : j + 1] * a[g]) * h[g] + u[j : j + 1] * bb_sc[t0 + j]
                out = h[g] * cc_sc[t0 + j]
                part_sc[t0 + j, :, at] = out.reshape(n // _SUBLANES, _SUBLANES, _LANES).sum(axis=0)
        return tuple(h)

    h0 = tuple(state_sc[:, g * _LANES : (g + 1) * _LANES] for g in range(groups))
    last = lax.fori_loop(0, t_chunk // unroll, trip, h0)
    for g in range(groups):
        state_sc[:, g * _LANES : (g + 1) * _LANES] = last[g]
    y = jnp.sum(part_sc[...], axis=1) + d_ref[...] * x_ref[0].astype(f32)
    y_ref[0] = y.astype(y_ref.dtype)


def mamba_scan(x, delta, a, b, c, d, *, chunk: int = 256, channel_block: int = 2560, unroll: int = 8):
    """``y (B, L, C)`` in ``x``'s type for ``x (B, L, C)``, ``delta (B, L, C)``
    (the step, positive), ``a (C, N)`` (negative), ``b, c (B, L, N)`` and
    ``d (C,)``; all but ``x`` are taken in float32. ``L`` must be whole
    chunks, ``C`` whole channel blocks, a channel block whole tiles of 128
    channels, ``N`` whole tiles of 8 states and ``chunk`` whole tiles of 128
    tokens (or the whole sequence). ``unroll`` tokens are one trip of
    the token loop, written out."""
    bt, l, ch = x.shape
    n = a.shape[-1]
    chunk, cb = min(chunk, l), min(channel_block, ch)
    if l % chunk or (chunk % _LANES and chunk != l):
        raise ValueError(f"sequence length {l} is not whole chunks of {chunk} tokens, a chunk whole tiles of {_LANES}")
    if ch % cb or cb % _LANES:
        raise ValueError(f"{ch} channels are not whole blocks of {cb}, a block whole tiles of {_LANES}")
    if n % _SUBLANES:
        raise ValueError(f"{n} states are not whole tiles of {_SUBLANES}")
    if unroll % _SUBLANES or chunk % unroll:
        raise ValueError(f"a chunk of {chunk} tokens is not whole trips of {unroll}, a trip whole tiles of {_SUBLANES}")
    if delta.shape != x.shape or a.shape != (ch, n) or b.shape != (bt, l, n) or c.shape != b.shape or d.shape != (ch,):
        raise ValueError(
            f"mamba_scan: x {x.shape}, delta {delta.shape}, a {a.shape}, b {b.shape}, c {c.shape}, d {d.shape}"
        )
    f32 = jnp.float32
    spec = lambda shape, index_map: pl.BlockSpec(shape, index_map, memory_space=pltpu.VMEM)
    tokens = lambda bi, cj, ci: (bi, ci, cj)  # a (T, cb) block of a (B, L, C) operand
    columns = lambda bi, cj, ci: (bi, 0, ci)  # a (N, T) block of a (B, N, L) operand
    channels = lambda bi, cj, ci: (0, cj)
    return pl.pallas_call(
        functools.partial(_kernel, unroll=unroll),
        grid=(bt, ch // cb, l // chunk),
        in_specs=[
            spec((1, chunk, cb), tokens),
            spec((1, chunk, cb), tokens),
            spec((n, cb), channels),
            spec((1, n, chunk), columns),
            spec((1, n, chunk), columns),
            spec((1, cb), channels),
        ],
        out_specs=spec((1, chunk, cb), tokens),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        scratch_shapes=[
            pltpu.VMEM((n, cb), f32),
            pltpu.VMEM((chunk, n, _LANES), f32),
            pltpu.VMEM((chunk, n, _LANES), f32),
            pltpu.VMEM((chunk, cb), f32),
            pltpu.VMEM((chunk, _SUBLANES, cb), f32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"), vmem_limit_bytes=_VMEM_LIMIT
        ),
        interpret=_interpret(),
        name="mamba_scan",
    )(
        x, delta.astype(f32), a.astype(f32).T, jnp.swapaxes(b.astype(f32), 1, 2), jnp.swapaxes(c.astype(f32), 1, 2),
        d.astype(f32).reshape(1, ch),
    )


@jax.jit
def mamba_recurrence(x, delta, a, b, c, d):
    """The same in plain ``jax.numpy``, float32, one token at a time (the
    kernel's test oracle): ``(y (B, L, C) float32, the last state (B, C, N))``."""
    f32 = jnp.float32
    a, d = a.astype(f32), d.astype(f32)

    def step(h, token):
        x_t, dt, b_t, c_t = token  # (B, C), (B, C), (B, N), (B, N)
        h = jnp.exp(dt[..., None] * a) * h + (dt * x_t)[..., None] * b_t[:, None, :]
        return h, jnp.sum(h * c_t[:, None, :], axis=-1) + d * x_t

    tokens = tuple(jnp.moveaxis(v.astype(f32), 1, 0) for v in (x, delta, b, c))
    last, y = lax.scan(step, jnp.zeros((x.shape[0], *a.shape), f32), tokens)
    return jnp.moveaxis(y, 0, 1), last
