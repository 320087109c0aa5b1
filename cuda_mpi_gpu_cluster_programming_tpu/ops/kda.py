"""Pallas chunked scan of the gated delta rule with a per-channel decay (Kimi
Delta Attention, arXiv:2510.26692): the linear-attention tier's block engine.

Per head, with a state ``S (dk, dv)`` in float32 that starts at zero::

    S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T
    o_t = scale * S_t^T q_t

``g <= 0`` is the log decay of every key channel and ``beta`` the write
strength (up to 2: ``I - beta k k^T`` may then have a negative eigenvalue).
:func:`kda_recurrence` is exactly this, one token at a time, and is the truth
the tests hold the kernel to. The kernel runs it ``chunk`` tokens at a time.
With ``G`` the cumulative ``g`` inside a chunk, ``D_rs = exp(G_r - G_s)`` and
``S_0`` the state the chunk starts from::

    A   = beta * strictly_lower(sum_c k_rc k_sc D_rsc)      (chunk, chunk)
    U   = (I + A)^-1 Diag(beta) (V - (K exp(G)) S_0)        the values written
    O   = scale * ((Q exp(G)) S_0 + lower(sum_c q_rc k_sc D_rsc) U)
    S_C = Diag(exp(G_C)) S_0 + (K exp(G_C - G))^T U

**No decay is exponentiated alone.** ``exp(-G)`` overflows float32 after a
few steeply decaying tokens, and a product ``exp(G_r - M) exp(M - G_s)`` is
lost wherever ``M`` is not between ``s`` and ``r``. So a pair ``r > s`` is
taken at the one level of a binary hierarchy at which they part: in the
smallest block of ``2^l`` tokens that holds both, ``r`` lies in the upper
half and ``s`` in the lower, ``M`` is the cumulative decay at the boundary,
and both ``exp(G_r - M)`` and ``exp(M - G_s)`` are sums of ``g`` over tokens
between ``s`` and ``r``: at most 1 each, and an underflow is a product that
is zero in float32 anyway. One product per level (``log2(chunk)`` of them)
masked to that level's pairs gives ``A`` and the scores; every exponent is
a row of one 0/1 matrix times ``g`` (:func:`decay_plan`). ``(I + A)^-1`` is
built over the same hierarchy (:func:`block_inverse`): the inverse of a block
is ``[[T11, 0], [-T22 A21 T11, T22]]`` of its halves' inverses, as stable as
forward substitution is by blocks and all on the MXU in float32. A level
writes only the upper halves' rows, and its blocks lie side by side in as
many rows as a half-block has, so each level does the work its blocks hold:
level 1 is ``I - A`` in closed form; levels 2 and 3 update the diagonal blocks
of 8 as one strip of 8 rows; level ``l >= 4`` streams the ``2^(l-1)`` rows of
the upper halves (8, 16, 32, 64 at chunk 128) against the inverse so far and
writes those rows back. Two products a level, 272 rows in all at chunk 128
where whole-matrix updates streamed 1,536.

Grid ``(batch, head block, chunk)``, the chunk axis sequential with the state
of every head of the block in VMEM scratch. Operands go to the MXU in the type
``q`` is stored in (``ops.reference.mxu_precision``); the decays, every
accumulation, the state, ``beta`` and the solve are float32. Forward only.

Runs in Pallas interpreter mode off the TPU (``ops.vma.interpret_mode``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .reference import mxu_precision
from .vma import interpret_mode as _interpret


def decay_plan(chunk: int):
    """``(plan (rows, chunk) float32 of 0/1, level (chunk, chunk) int32)``.

    ``plan @ g`` gives every exponent a chunk needs, ``chunk`` rows each:
    rows ``[0, chunk)`` the decay since the chunk's start (``G_r``), rows
    ``[chunk, 2 chunk)`` the decay still to come (``G_C - G_r``), then for
    level ``l = 1 .. log2(chunk)`` the decay between token ``r`` and the
    middle of its block of ``2^l`` tokens (from the middle down to ``r`` for
    a token of the upper half, from ``r`` up to the middle for one of the
    lower). ``level[r, s]`` is the level at which ``r > s`` part, 0 on the
    diagonal and -1 above it."""
    r = np.arange(chunk)[:, None]
    j = np.arange(chunk)[None, :]
    blocks = [j <= r, j > r]
    for level in range(1, chunk.bit_length()):
        middle = (r >> level << level) + (1 << level - 1)
        blocks.append(np.where(r >= middle, (middle <= j) & (j <= r), (r < j) & (j < middle)))
    parted = np.frompyfunc(lambda a, b: int(a ^ b).bit_length(), 2, 1)(r, j).astype(np.int32)
    return np.concatenate(blocks).astype(np.float32), np.where(r >= j, parted, -1).astype(np.int32)


_SUBLANES = 8  # a float32 tile's rows: the fewest a product streams


def _dot32(x, y):  # the solve: float32 operands, six bf16 passes
    return jnp.dot(x, y, preferred_element_type=jnp.float32, precision=lax.Precision.HIGHEST)


def block_inverse(a, level):
    """``(I + a)^-1`` in float32 for ``a (C, C)`` strictly lower triangular and
    ``level`` as :func:`decay_plan` gives it, every product at ``HIGHEST``.

    The inverse of a block of ``2^l`` tokens is ``[[T11, 0], [-T22 A21 T11,
    T22]]`` of its halves' inverses: level ``l`` writes ``N = T22 A21 T11``
    where ``level == l`` and nothing else, and ``A21``, ``T22`` and ``N`` of
    ALL its blocks lie side by side in as many rows as one half-block has,
    since the blocks' columns are disjoint. Such a strip ``X`` against a
    block-diagonal ``Y`` as it stands is the strip of ``X Y``, and a product
    costs what its left operand has rows:

    * level 1: a pair's inverse is ``I - A``, no product;
    * level ``l >= 2``: ``fold`` adds the upper halves' rows of every block
      into one strip of ``2^(l-1)`` rows (8, 16, 32, 64 for levels 4 to 7 at
      chunk 128; at levels 2 and 3, where a half-block is less than a tile of
      8 rows, the whole tiles of 8 that hold the blocks), ``P = A21 T11`` is
      ``a``'s strip against ``inv`` as it stands, ``lay`` puts a strip back
      into the rows it came from, each block's entries in its own columns
      and zero elsewhere, and ``N`` is ``inv``'s strip against ``P`` laid
      back.

    Two products a level of 8 to ``C / 2`` rows (272 rows in all at chunk 128)
    where ``inv - inv (A_l inv)`` on whole matrices streamed ``C`` rows in
    each. Row slices are whole sublane tiles; nothing is gathered."""
    c = a.shape[0]
    inv = jnp.where(level == 0, 1.0, 0.0) - jnp.where(level == 1, a, 0.0)
    for lv in range(2, c.bit_length()):
        rows = max(1 << lv - 1, _SUBLANES)  # the strip's
        size = max(1 << lv, _SUBLANES)  # of a block, or of the tile that holds it
        at = level == lv
        fold = lambda x: x.reshape(c // size, size, c)[:, size - rows :].sum(axis=0)
        lay = lambda s: jnp.where(at, jnp.concatenate([s] * (c // rows)), 0.0)
        inv = inv - lay(_dot32(fold(inv), lay(_dot32(fold(jnp.where(at, a, 0.0)), inv))))
    return inv


def _one_chunk(q, k, v, g, beta_row, state, plan, level, *, scale):
    """One chunk of one head: ``q, k (C, dk)``, ``v (C, dv)``, ``g (C, dk)``
    float32, ``beta_row (1, C)`` float32, ``state (dv, dk)`` float32 (the
    transpose of ``S``: a channel's decay then scales a column, and a row
    vector broadcasts over it), ``plan`` in bf16 (0/1: exact) -> ``(o (C, dv)
    float32, the state after)``."""
    c, dt, f32 = q.shape[0], q.dtype, jnp.float32
    prec = mxu_precision(dt)

    def dot(a, b, over):  # operands in the stored type, float32 out
        return lax.dot_general(a, b, (over, ((), ())), preferred_element_type=f32, precision=prec)

    nt = ((1,), (1,))  # contract the last axis of both
    # plan @ g in three passes: the plan is 0/1, exact in bf16, and g is the
    # sum of three bf16 pieces to float32's last bit
    g_hi = g.astype(jnp.bfloat16)
    rest = g - g_hi.astype(f32)
    g_mid = rest.astype(jnp.bfloat16)
    g_lo = (rest - g_mid.astype(f32)).astype(jnp.bfloat16)
    decay = jnp.exp(sum(jnp.dot(plan, piece, preferred_element_type=f32) for piece in (g_hi, g_mid, g_lo)))
    since, to_end = decay[:c], decay[c : 2 * c]  # (C, dk) each: every factor at most 1
    qf, kf = q.astype(f32), k.astype(f32)
    diagonal = level == 0
    beta = jnp.sum(jnp.where(diagonal, beta_row, 0.0), axis=1, keepdims=True)  # (C, 1)

    # the pairs inside the chunk, level by level: queries and keys in one product
    qk = jnp.where(diagonal, jnp.sum(qf * kf, axis=1, keepdims=True), 0.0)
    kk = jnp.zeros((c, c), f32)
    for lv in range(1, c.bit_length()):
        to_middle = decay[(lv + 1) * c : (lv + 2) * c]
        k_lv = (kf * to_middle).astype(dt)
        pairs = dot(jnp.concatenate([(qf * to_middle).astype(dt), k_lv]), k_lv, nt)  # (2C, C)
        parted = level == lv
        qk = qk + jnp.where(parted, pairs[:c], 0.0)
        kk = kk + jnp.where(parted, pairs[c:], 0.0)
    a = beta * kk

    inv = block_inverse(a, level)

    held = state.astype(dt)
    written = _dot32(inv, beta * (v.astype(f32) - dot((kf * since).astype(dt), held, nt)))  # U (C, dv)
    u = written.astype(dt)
    o = scale * (dot((qf * since).astype(dt), held, nt) + dot(qk.astype(dt), u, ((1,), (0,))))
    decay_all = jnp.exp(jnp.sum(g, axis=0, keepdims=True))  # (1, dk)
    return o, state * decay_all + dot(u, (kf * to_end).astype(dt), ((0,), (0,)))


def _kernel(plan_ref, level_ref, q_ref, k_ref, v_ref, g_ref, beta_ref, o_ref, state_sc, *, heads, scale):
    """One (batch, head block, chunk) program; the state of each head of the
    block lives in VMEM scratch across the chunk axis (sequential)."""
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        state_sc[...] = jnp.zeros_like(state_sc)

    plan, level = plan_ref[...], level_ref[...]
    for h in range(heads):  # independent chains: the scheduler interleaves them
        o, state = _one_chunk(
            q_ref[0, h], k_ref[0, h], v_ref[0, h], g_ref[0, h], beta_ref[0, h, pl.ds(ci, 1), :],
            state_sc[h], plan, level, scale=scale,
        )
        o_ref[0, h] = o.astype(o_ref.dtype)
        state_sc[h] = state


def kda_chunked(q, k, v, g, beta, *, chunk: int, head_block: int = 1, scale=None):
    """``o (B, H, L, dv)`` in ``q``'s type for ``q, k (B, H, L, dk)``,
    ``v (B, H, L, dv)``, ``g (B, H, L, dk)`` (log decay, at most 0) and
    ``beta (B, H, L)``; ``g`` and ``beta`` are taken in float32. ``L`` must
    be whole chunks, ``chunk`` a power of two of at least 16 tokens, and
    ``head_block`` (heads whose chains one program interleaves) a divisor of
    ``H``. ``scale`` defaults to ``dk**-0.5``."""
    b, h, l, dk = q.shape
    dv = v.shape[-1]
    if chunk < 16 or chunk & (chunk - 1):
        raise ValueError(f"chunk {chunk} is not a power of two of at least 16")
    if l % chunk:
        raise ValueError(f"sequence length {l} is not whole chunks of {chunk}")
    if h % head_block:
        raise ValueError(f"head_block {head_block} does not divide {h} heads")
    if k.shape != q.shape or v.shape != (b, h, l, dv) or g.shape != q.shape or beta.shape != (b, h, l):
        raise ValueError(f"kda_chunked: q {q.shape}, k {k.shape}, v {v.shape}, g {g.shape}, beta {beta.shape}")
    if scale is None:
        scale = dk**-0.5
    plan, level = decay_plan(chunk)
    n_chunks = l // chunk
    at = lambda bi, hi, ci: (bi, hi, ci, 0)
    whole = lambda bi, hi, ci: (0, 0)
    spec = lambda shape, index_map: pl.BlockSpec(shape, index_map, memory_space=pltpu.VMEM)
    return pl.pallas_call(
        functools.partial(_kernel, heads=head_block, scale=scale),
        grid=(b, h // head_block, n_chunks),
        in_specs=[
            spec(plan.shape, whole),
            spec(level.shape, whole),
            spec((1, head_block, chunk, dk), at),
            spec((1, head_block, chunk, dk), at),
            spec((1, head_block, chunk, dv), at),
            spec((1, head_block, chunk, dk), at),
            # a head's beta whole, a row per chunk: fetched once per head block
            spec((1, head_block, n_chunks, chunk), lambda bi, hi, ci: (bi, hi, 0, 0)),
        ],
        out_specs=spec((1, head_block, chunk, dv), at),
        out_shape=jax.ShapeDtypeStruct((b, h, l, dv), q.dtype),
        scratch_shapes=[pltpu.VMEM((head_block, dv, dk), jnp.float32)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=_interpret(),
        name="kda_chunked",
    )(
        jnp.asarray(plan, jnp.bfloat16), jnp.asarray(level), q, k, v,
        g.astype(jnp.float32), beta.astype(jnp.float32).reshape(b, h, n_chunks, chunk),
    )


@functools.partial(jax.jit, static_argnames=("scale",))
def kda_recurrence(q, k, v, g, beta, *, scale=None):
    """The same in plain ``jax.numpy``, float32, one token at a time (the
    kernel's test oracle): ``(o (B, H, L, dv), the last state (B, H, dk, dv))``."""
    f32 = jnp.float32
    b, h, l, dk = q.shape
    scale = dk**-0.5 if scale is None else scale
    mm = functools.partial(jnp.einsum, precision="highest")

    def step(state, token):
        q_t, k_t, v_t, g_t, beta_t = token  # (B, H, dk) ..., beta (B, H)
        state = state * jnp.exp(g_t)[..., None]
        write = beta_t[..., None] * (v_t - mm("bhk,bhkv->bhv", k_t, state))
        state = state + k_t[..., None] * write[..., None, :]
        return state, scale * mm("bhk,bhkv->bhv", q_t, state)

    tokens = tuple(jnp.moveaxis(x.astype(f32), 2, 0) for x in (q, k, v, g, beta))
    last, o = lax.scan(step, jnp.zeros((b, h, dk, v.shape[-1]), f32), tokens)
    return jnp.moveaxis(o, 0, 2), last
