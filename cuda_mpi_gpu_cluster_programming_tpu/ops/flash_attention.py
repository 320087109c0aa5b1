"""Pallas flash attention: fused online-softmax attention for TPU.

The hot-op counterpart of ``ops.attention.attention`` (which materializes
the full (L, L) score matrix in HBM). Forward and backward are both O(L)
memory:

- **Forward**: grid (batch, head, pair) — one step per (q-block, k-block)
  pair that contributes, q-block-major (causal: the pairs at or below the
  diagonal and no others). K/V are *streamed through VMEM one block at a
  time by the grid* (only (block, D) tiles are ever resident, not
  whole-L), carrying the numerically-stable running (max, numerator,
  denominator) in VMEM scratch across a q-block's pairs. QK^T and PV ride
  the MXU with fp32 accumulation. A block the diagonal crosses corner to
  corner is computed in row slabs, each against the keys up to its own
  last row (``causal_plan``). The forward also emits the per-row
  log-sum-exp (LSE) for the backward.
- **Backward**: FlashAttention-2-style recompute — no residual score
  matrix. Two kernels: dQ (stream K/V per q-block) and dK/dV (stream Q/dO
  per k-block), each recomputing the normalized probabilities from Q, K and
  the saved LSE, so peak memory stays O(L·D) end to end. The O(L^2) VJP
  fallback from round 1 is gone.

Composes with the sequence-parallel tier: ``ring_attention`` shards the
sequence *across* chips; this kernel is the *within-chip* block engine
(same online-softmax recurrence, one level down the memory hierarchy).

Runs in Pallas interpreter mode on non-TPU backends so the CPU test mesh
exercises the identical code path (tests/test_flash_attention.py).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .attention import NEG_INF
from .reference import mxu_precision
from .vma import interpret_mode as _interpret, vma_struct as _vma_struct


def _spec(block_shape, index_map):
    return pl.BlockSpec(block_shape, index_map, memory_space=pltpu.VMEM)


def _causal_mask(s, qi, ki, bq, bk):
    q_pos = qi * bq + lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_pos = ki * bk + lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    return jnp.where(q_pos >= k_pos, s, NEG_INF)


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------


def _slab_mask(s, row0):
    """The mask of rows ``row0 ...`` of a block on the diagonal against its
    keys from the first on (where the block's first query is its first key)."""
    rows = row0 + lax.broadcasted_iota(jnp.int32, s.shape, 0)
    keys = lax.broadcasted_iota(jnp.int32, s.shape, 1)
    return jnp.where(rows >= keys, s, NEG_INF)


def _band_mask(s, qi, ki, bq, bk, window):
    """The causal mask and the window's: a query sees itself and the
    ``window - 1`` tokens before it."""
    q_pos = qi * bq + lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_pos = ki * bk + lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    return jnp.where((q_pos >= k_pos) & (q_pos - k_pos < window), s, NEG_INF)


def _fwd_kernel(qi_ref, ki_ref, *refs, bq, bk, nk, causal, scale, rope, slab, window=None):
    """One (batch, head, pair) program: k-block ``ki_ref[pair]`` against
    q-block ``qi_ref[pair]`` (the two scalar-prefetched tables of the pairs
    that contribute, q-block-major).

    q_ref: (1, 1, bq, D); k_ref: (1, 1, bk, D); v_ref: (1, 1, bk, Dv) — ONE
    k/v block, indexed by the grid (streaming); the value width may differ
    from the query/key width. With ``rope`` two more operands follow them,
    sequence-minor: qr_ref (1, 1, R, bq) and kr_ref (1, R, bk), the part of
    the queries and keys that is one key for all heads; a score is then the
    sum of the two products. Running stats live in VMEM scratch across a
    q-block's pairs (the grid is sequential on TPU and in interpret mode).
    ``slab`` < bq: a block on the diagonal is computed in row slabs of that
    height. ``window`` (causal only): the pairs are the band's, a q-block's
    first k-block is the one that holds its first query's farthest key, and a
    block the band's lower edge crosses is masked as the diagonal's are.
    """
    q_ref, k_ref, v_ref, *rest = refs
    if rope:
        qr_ref, kr_ref, *rest = rest
    o_ref, lse_ref, m_sc, den_sc, acc_sc = rest
    pair = pl.program_id(2)
    qi = qi_ref[pair]
    ki = ki_ref[pair]

    first = 0 if window is None else jnp.maximum(qi * bq - (window - 1), 0) // bk

    @pl.when(ki == first)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        den_sc[...] = jnp.zeros_like(den_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    def update(*pieces):
        # ``pieces``: the static (rows, keys, mask) parts of the block this
        # step computes — the whole block, or the row slabs of a block on the
        # diagonal, each against the keys up to its own last row, so that no
        # row is touched twice; ``mask`` is applied to a piece's scores, or is
        # None. Operands go to the MXU in the type they are
        # stored in (bf16 stays bf16: one pass; float32 runs at HIGHEST),
        # accumulation is float32, and the scale is applied to the float32
        # scores. Every piece's products stand before any piece's softmax:
        # the MXU runs the next slab's scores under this slab's vector work.
        prec = mxu_precision(q_ref.dtype)

        def qk(a, b, over):  # contract axis ``over`` of both -> (rows, keys)
            return lax.dot_general(
                a, b, (((over,), (over,)), ((), ())),
                preferred_element_type=jnp.float32, precision=prec,
            )

        scored = []
        for rows, keys, mask in pieces:
            q = q_ref[0, 0, rows, :]  # (rows, D)
            k_blk = k_ref[0, 0, keys, :]  # (keys, D)
            v_blk = v_ref[0, 0, keys, :]  # (keys, Dv)
            s = qk(q, k_blk, 1)
            if rope:
                s = s + qk(qr_ref[0, 0, :, rows], kr_ref[0, :, keys], 0)  # (R, rows) x (R, keys)
            s = scale * s
            if mask is not None:
                s = mask(s)
            scored.append((rows, s, v_blk))
        for rows, s, v_blk in scored:
            m_prev = m_sc[rows, 0]  # (rows,)
            den_prev = den_sc[rows, 0]
            blk_max = jnp.max(s, axis=-1)
            m_new = jnp.maximum(m_prev, blk_max)
            corr = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new[:, None])  # (rows, keys), float32
            acc_sc[rows, :] = acc_sc[rows, :] * corr[:, None] + lax.dot_general(
                p.astype(v_blk.dtype), v_blk, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32, precision=prec,
            )
            den_new = den_prev * corr + jnp.sum(p, axis=-1)
            stat = (s.shape[0], m_sc.shape[1])
            m_sc[rows, :] = jnp.broadcast_to(m_new[:, None], stat)
            den_sc[rows, :] = jnp.broadcast_to(den_new[:, None], stat)

    # Causal: the grid holds no pair above the diagonal. Only the blocks the
    # diagonal crosses need the mask: a block wholly below it skips the iota,
    # the compare and the select, which the VPU pays per score.
    whole = lambda mask=None: update((slice(0, bq), slice(0, bk), mask))
    # a block on the diagonal in row slabs, each against the keys up to its own last row
    diagonal = lambda: update(*[
        (slice(r, r + slab), slice(0, r + slab), functools.partial(_slab_mask, row0=r)) for r in range(0, bq, slab)
    ])
    if not causal:
        whole()
    elif window is not None:
        # the diagonal crosses a block with a key after some query; the band's lower edge one
        # whose first key lies more than ``window - 1`` before its last query
        crossed = ((ki + 1) * bk - 1 > qi * bq) | ((qi + 1) * bq - 1 - ki * bk > window - 1)
        if slab < bq <= window:  # the lower edge never reaches a block on the diagonal
            pl.when(ki == qi)(diagonal)
            crossed = crossed & (ki != qi)
            pl.when(jnp.logical_not(crossed) & (ki != qi))(whole)
        else:
            pl.when(jnp.logical_not(crossed))(whole)
        pl.when(crossed)(lambda: whole(lambda s: _band_mask(s, qi, ki, bq, bk, window)))
    elif slab < bq:  # square blocks: the diagonal crosses (qi, qi) corner to corner, and no other
        pl.when(ki == qi)(diagonal)
        pl.when(ki < qi)(whole)
    else:
        crossed = (ki + 1) * bk - 1 > qi * bq  # some key of the block lies after some query
        pl.when(crossed)(lambda: whole(lambda s: _causal_mask(s, qi, ki, bq, bk)))
        pl.when(jnp.logical_not(crossed))(whole)

    last = jnp.minimum(nk - 1, ((qi + 1) * bq - 1) // bk) if causal else nk - 1

    @pl.when(ki == last)
    def _finalize():
        m = m_sc[:, 0]
        den = jnp.maximum(den_sc[:, 0], 1e-30)
        o_ref[0, 0] = (acc_sc[...] / den[:, None]).astype(o_ref.dtype)
        lse_ref[0, 0, 0] = m + jnp.log(den)


def _pairs(l, bq, bk, causal, window=None):
    """The (q-block, k-block) pairs that contribute, q-block-major: all of
    them, or causal those with a key at or before the block's last query;
    with a ``window``, of those the ones with a key no more than ``window -
    1`` before the block's first query."""
    return [
        (qi, ki) for qi in range(l // bq) for ki in range(l // bk)
        if (not causal or (qi + 1) * bq - 1 >= ki * bk)
        and (window is None or (ki + 1) * bk - 1 >= qi * bq - (window - 1))
    ]


class CausalPlan(NamedTuple):
    """What the causal forward does for ONE head of ``l`` tokens."""

    pairs: int  # (q-block, k-block) pairs computed
    diag_slab: int  # rows of the slabs a block on the diagonal is computed in (block_q: whole)
    scores_computed: int
    scores_kept: int  # the scores the mask leaves: l (l + 1) / 2, or with a window sum_q min(q + 1, window)

    @property
    def grid_steps(self) -> int:
        """Steps of the grid: one per pair computed, none for a pair the mask empties."""
        return self.pairs

    @property
    def masked_score_share(self) -> float:
        """Scores computed that the mask throws away, over scores computed."""
        return 1.0 - self.scores_kept / self.scores_computed


def _diag_slab(bq: int, bk: int) -> int:
    """Rows of the slabs a block on the diagonal is computed in: one lane tile
    of 128 rows (at most eight slabs a block: a multiple of it from blocks of
    2,048 up) where a square block is whole slabs and more than one, else the
    block whole (``bq``: unequal blocks, blocks of 128, heights no tile
    divides). Chosen on the chip at the four prefill cells' shapes, blocks of
    1,024 (PR 38: ``scripts/flash_causal_ab.py``, ``PERF.md`` section 6)."""
    slab = 128 * max(1, bq // 1024)
    return slab if bq == bk and bq > slab and bq % slab == 0 else bq


def _window_slab(bq: int, bk: int, window) -> int:
    """``_diag_slab`` where the band's lower edge never reaches a block on the
    diagonal (``window`` None, or at least a block), else the block whole."""
    return _diag_slab(bq, bk) if window is None or bq <= window else bq


def causal_plan(l: int, block_q: int = 128, block_k: int = 128, window=None) -> CausalPlan:
    """The work ``flash_forward_bhld(..., causal=True, window=window)`` does
    for one head of ``l`` tokens at these blocks, from the shapes alone (the
    kernel's own ``_diag_slab`` among them); ``flash.masked_score_share`` is
    this plan's, ``flash.window_masked_score_share`` the windowed one's."""
    bq, bk = min(block_q, l), min(block_k, l)
    if l % bq or l % bk:
        raise ValueError(f"sequence length {l} not divisible by blocks ({bq}, {bk})")
    nq, pairs = l // bq, len(_pairs(l, bq, bk, True, window))
    w = _window_slab(bq, bk, window)
    n = bq // w  # slab i of n sees (i + 1) w keys: w^2 n (n + 1) / 2 = bq^2 (n + 1) / (2n) scores a block
    on_diagonal = nq * w * w * n * (n + 1) // 2 if n > 1 else nq * bq * bk
    seen = l if window is None else min(window, l)  # keys the last query sees
    kept = seen * (seen + 1) // 2 + (l - seen) * seen
    return CausalPlan(pairs, w, (pairs - nq) * bq * bk + on_diagonal, kept)


# Lane width of the (bq,)-shaped running stats held in VMEM scratch: Mosaic
# wants >= 2D tiles, so the vectors are broadcast across a 128-lane axis.
_STAT_LANES = 128


def _flash_forward(q, k, v, *, causal, block_q, block_k, return_lse, vma=None):
    # (B, L, H, D) -> (B, H, L, D): heads become a grid axis, L contiguous.
    tr = lambda a: jnp.transpose(a, (0, 2, 1, 3))
    out, lse = flash_forward_bhld(
        tr(q), tr(k), tr(v), causal=causal, block_q=block_q, block_k=block_k, vma=vma
    )
    return (tr(out), lse) if return_lse else tr(out)


def flash_forward_bhld(
    q, k, v, *, causal, block_q=128, block_k=128, scale=None, vma=None, q_rope=None, k_rope=None, window=None
):
    """The forward kernel on heads-major operands: q ``(B, H, L, D)``, k
    ``(B, Hk, L, D)``, v ``(B, Hk, L, Dv)`` -> ``(out (B, H, L, Dv), lse
    (B, H, 1, L))``.

    ``Hk`` divides ``H`` (grouped-query attention): query head ``h`` reads
    key/value head ``h // (H // Hk)`` through the block index map, so no key
    or value is repeated in HBM; with ``Hk == H`` the kernel built is the one
    equal head counts always built. ``Dv`` may differ from ``D``. Latent attention hands its queries and keys
    over in the two parts its projections produce: ``q``, ``k`` the part each
    head has keys of its own for, and ``q_rope (B, H, R, L)``, ``k_rope
    (B, R, L)`` the rotated part, whose key is ONE per position for all heads
    (its block is fetched by position alone). A score is then
    ``q . k + q_rope . k_rope``: no ``D + R``-wide query or key is assembled
    and ``k_rope`` is never copied per head. The rope operands are
    sequence-minor because ``R`` is narrow (64 where a lane tile is 128): so
    laid out they are dense in HBM and in VMEM, and it is how the compiler
    writes a product that narrow anyway. ``scale`` defaults to the whole
    width's ``(D + R)**-0.5``. ``window`` (with ``causal``): a query sees
    itself and the ``window - 1`` tokens before it; the grid then runs over the
    (q-block, k-block) pairs the band touches and no others
    (``causal_plan(l, bq, bk, window)`` says what is computed), and with
    ``window=None`` the kernel built is the one built without the argument.
    Forward only: the differentiable entry points above take one width for q,
    k and v and no rope operands.
    """
    b, h, l, d = q.shape
    hk, dv = k.shape[1], v.shape[-1]
    bq = min(block_q, l)
    bk = min(block_k, l)
    if l % bq or l % bk:
        raise ValueError(f"sequence length {l} not divisible by blocks ({bq}, {bk})")
    if h % hk or k.shape != (b, hk, l, d) or v.shape != (b, hk, l, dv):
        raise ValueError(
            f"q {q.shape}, k {k.shape}, v {v.shape}: want (B, H, L, D), (B, Hk, L, D), (B, Hk, L, Dv), Hk | H"
        )
    if (q_rope is None) != (k_rope is None):
        raise ValueError("q_rope and k_rope come together")
    if window is not None and (not causal or window < 1):
        raise ValueError(f"window {window}: a window is at least one token and comes with causal=True")
    rope = q_rope is not None
    r = q_rope.shape[2] if rope else 0
    if rope and (q_rope.shape != (b, h, r, l) or k_rope.shape != (b, r, l)):
        raise ValueError(f"q_rope {q_rope.shape} / k_rope {k_rope.shape}: want (B, H, R, L) / (B, R, L)")
    if scale is None:
        scale = 1.0 / ((d + r) ** 0.5)  # Python math: stays static under jit tracing

    # The grid's last axis runs over the (q-block, k-block) pairs that
    # contribute, q-block-major: causal, those at or below the diagonal. Two
    # scalar-prefetched tables name each step's blocks, so that no step is
    # spent on a pair the mask empties and none of its blocks is fetched.
    pairs = _pairs(l, bq, bk, causal, window)
    tables = [jnp.asarray(np.asarray(column, np.int32)) for column in zip(*pairs)]
    group = h // hk  # query heads that share one key/value head
    kv_head = (lambda hi: hi) if group == 1 else (lambda hi: hi // group)
    q_at = lambda bi, hi, pi, qis, kis: (bi, hi, qis[pi], 0)
    kv_at = lambda bi, hi, pi, qis, kis: (bi, kv_head(hi), kis[pi], 0)
    q_minor_at = lambda bi, hi, pi, qis, kis: (bi, hi, 0, qis[pi])  # a q-block along the LAST axis

    operands = [q, k, v]
    in_specs = [_spec((1, 1, bq, d), q_at), _spec((1, 1, bk, d), kv_at), _spec((1, 1, bk, dv), kv_at)]
    if rope:
        operands += [q_rope, k_rope]
        in_specs += [
            _spec((1, 1, r, bq), q_minor_at),
            _spec((1, r, bk), lambda bi, hi, pi, qis, kis: (bi, 0, kis[pi])),
        ]
    kernel = functools.partial(
        _fwd_kernel, bq=bq, bk=bk, nk=l // bk, causal=causal, scale=scale, rope=rope,
        slab=_diag_slab(bq, bk) if causal else bq,
    )
    if window is not None:
        kernel = functools.partial(kernel, slab=_window_slab(bq, bk, window), window=window)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, h, len(pairs)),
            in_specs=in_specs,
            out_specs=[
                _spec((1, 1, bq, dv), q_at),
                # LSE rides as (B, H, 1, L): Mosaic requires the block's last two
                # dims to be (sublane-divisible | equal-to-array), which a
                # (1, 1, bq) block over (B, H, L) violates (H is second-minor).
                # The explicit singleton makes the block (1, bq) vs array (1, L)
                # — legal, and caught only on real TPU (interpret mode doesn't
                # enforce tiling).
                _spec((1, 1, 1, bq), q_minor_at),
            ],
            scratch_shapes=[
                pltpu.VMEM((bq, _STAT_LANES), jnp.float32),  # running max
                pltpu.VMEM((bq, _STAT_LANES), jnp.float32),  # running denominator
                pltpu.VMEM((bq, dv), jnp.float32),  # output accumulator
            ],
        ),
        out_shape=[
            _vma_struct((b, h, l, dv), q.dtype, vma),
            _vma_struct((b, h, 1, l), jnp.float32, vma),
        ],
        interpret=_interpret(),
        name="flash_fwd",
    )(*tables, *operands)


# ---------------------------------------------------------------------------
# Backward (FlashAttention-2 recompute: no (L, L) residency anywhere)
# ---------------------------------------------------------------------------


def _recompute_p(q_ref, k_ref, lse_ref, qi, ki, bq, bk, causal, scale):
    """Normalized probabilities for one (q-block, k-block) tile, from the
    saved LSE: p = exp(s - lse) = softmax(s) exactly, no running max needed."""
    q = q_ref[0, 0].astype(jnp.float32) * scale
    k_blk = k_ref[0, 0].astype(jnp.float32)
    s = lax.dot_general(
        q, k_blk, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32, precision=mxu_precision(q_ref.dtype),
    )
    if causal:
        s = _causal_mask(s, qi, ki, bq, bk)
    return jnp.exp(s - lse_ref[0, 0, 0][:, None])  # (bq, bk)


def _dq_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, dq_sc, *, bq, bk, causal, scale
):
    """dQ for one q-block, streaming K/V blocks over the last grid axis."""
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        dq_sc[...] = jnp.zeros_like(dq_sc)

    contributes = (not causal) or ((qi + 1) * bq - 1 >= ki * bk)

    @pl.when(contributes)
    def _update():
        p = _recompute_p(q_ref, k_ref, lse_ref, qi, ki, bq, bk, causal, scale)
        do = do_ref[0, 0].astype(jnp.float32)  # (bq, D)
        v_blk = v_ref[0, 0].astype(jnp.float32)  # (bk, D)
        prec = mxu_precision(q_ref.dtype)
        dp = lax.dot_general(
            do, v_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32, precision=prec,
        )  # (bq, bk)
        ds = p * (dp - delta_ref[0, 0, 0][:, None])  # (bq, bk)
        dq_sc[...] += scale * lax.dot_general(
            ds, k_ref[0, 0].astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=prec,
        )

    @pl.when(ki == nk - 1)
    def _finalize():
        dq_ref[0, 0] = dq_sc[...].astype(dq_ref.dtype)


def _dkv_kernel(
    k_ref, v_ref, q_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref, dk_sc, dv_sc,
    *, bq, bk, causal, scale,
):
    """dK and dV for one k-block, streaming Q/dO blocks over the last grid axis."""
    ki = pl.program_id(2)
    qi = pl.program_id(3)
    nq = pl.num_programs(3)

    @pl.when(qi == 0)
    def _init():
        dk_sc[...] = jnp.zeros_like(dk_sc)
        dv_sc[...] = jnp.zeros_like(dv_sc)

    contributes = (not causal) or ((qi + 1) * bq - 1 >= ki * bk)

    @pl.when(contributes)
    def _update():
        p = _recompute_p(q_ref, k_ref, lse_ref, qi, ki, bq, bk, causal, scale)
        do = do_ref[0, 0].astype(jnp.float32)  # (bq, D)
        v_blk = v_ref[0, 0].astype(jnp.float32)
        prec = mxu_precision(q_ref.dtype)
        dv_sc[...] += lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=prec,
        )  # (bk, D)
        dp = lax.dot_general(
            do, v_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32, precision=prec,
        )  # (bq, bk)
        ds = p * (dp - delta_ref[0, 0, 0][:, None])
        dk_sc[...] += scale * lax.dot_general(
            ds, q_ref[0, 0].astype(jnp.float32), (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=prec,
        )  # (bk, D)

    @pl.when(qi == nq - 1)
    def _finalize():
        dk_ref[0, 0] = dk_sc[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_sc[...].astype(dv_ref.dtype)


def _flash_backward(q, k, v, out, lse, g, *, causal, block_q, block_k, vma=None, lse_grad=None):
    b, l, h, d = q.shape
    bq = min(block_q, l)
    bk = min(block_k, l)
    scale = 1.0 / (d**0.5)

    qt = jnp.transpose(q, (0, 2, 1, 3))
    kt = jnp.transpose(k, (0, 2, 1, 3))
    vt = jnp.transpose(v, (0, 2, 1, 3))
    dot = jnp.transpose(out, (0, 2, 1, 3))
    gt = jnp.transpose(g, (0, 2, 1, 3))

    # delta_i = sum_d dO_i * O_i — O(L) rowwise term of dS (FA-2 eq. 4).
    # (b, h, 1, l) — same explicit-singleton layout as the LSE (see the
    # forward out_specs note on Mosaic's block tiling rule).
    delta = jnp.sum(
        gt.astype(jnp.float32) * dot.astype(jnp.float32), axis=-1, keepdims=True
    ).swapaxes(-1, -2)
    if lse_grad is not None:
        # Joint (out, lse) VJP: lse_i = logsumexp(s_i) has d(lse_i)/d(s_ij)
        # = p_ij, so an lse cotangent g_lse adds p_ij * g_lse_i to dS —
        # algebraically dS_ij = p_ij (dP_ij - (delta_i - g_lse_i)), i.e.
        # the SAME kernels with delta shifted by -g_lse. dV is untouched
        # (lse does not depend on V). This one-line shift is what makes
        # the ring engine's per-hop LSE merge differentiable end to end.
        delta = delta - lse_grad.astype(jnp.float32)[:, :, None, :]

    qb = lambda bi, hi, qi, ki: (bi, hi, qi, 0)
    kb = lambda bi, hi, qi, ki: (bi, hi, ki, 0)
    rowq = lambda bi, hi, qi, ki: (bi, hi, 0, qi)

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, bq=bq, bk=bk, causal=causal, scale=scale),
        grid=(b, h, l // bq, l // bk),
        in_specs=[
            _spec((1, 1, bq, d), qb),
            _spec((1, 1, bk, d), kb),
            _spec((1, 1, bk, d), kb),
            _spec((1, 1, bq, d), qb),
            _spec((1, 1, 1, bq), rowq),
            _spec((1, 1, 1, bq), rowq),
        ],
        out_specs=_spec((1, 1, bq, d), qb),
        out_shape=_vma_struct((b, h, l, d), q.dtype, vma),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        interpret=_interpret(),
        name="flash_dq",
    )(qt, kt, vt, gt, lse, delta)

    # k-block outer, q-block streamed innermost.
    kb2 = lambda bi, hi, ki, qi: (bi, hi, ki, 0)
    qb2 = lambda bi, hi, ki, qi: (bi, hi, qi, 0)
    rowq2 = lambda bi, hi, ki, qi: (bi, hi, 0, qi)
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, bq=bq, bk=bk, causal=causal, scale=scale),
        grid=(b, h, l // bk, l // bq),
        in_specs=[
            _spec((1, 1, bk, d), kb2),
            _spec((1, 1, bk, d), kb2),
            _spec((1, 1, bq, d), qb2),
            _spec((1, 1, bq, d), qb2),
            _spec((1, 1, 1, bq), rowq2),
            _spec((1, 1, 1, bq), rowq2),
        ],
        out_specs=[
            _spec((1, 1, bk, d), kb2),
            _spec((1, 1, bk, d), kb2),
        ],
        out_shape=[
            _vma_struct((b, h, l, d), k.dtype, vma),
            _vma_struct((b, h, l, d), v.dtype, vma),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, d), jnp.float32),
            pltpu.VMEM((bk, d), jnp.float32),
        ],
        interpret=_interpret(),
        name="flash_dkv",
    )(kt, vt, qt, gt, lse, delta)

    tr = lambda a: jnp.transpose(a, (0, 2, 1, 3))
    return tr(dq), tr(dk), tr(dv)


# ---------------------------------------------------------------------------
# Public API + custom VJP
# ---------------------------------------------------------------------------


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = False,
    block_q: int = 128,
    block_k: int = 128,
    vma=None,
) -> jax.Array:
    """Fused attention. q,k,v: (B, L, H, D) -> (B, L, H, D).

    ``L`` must be divisible by the (clamped) block sizes. Only (block, D)
    K/V tiles are VMEM-resident at a time (the grid streams them), so L is
    bounded by HBM, not VMEM.

    Differentiable with O(L)-memory: the custom VJP recomputes probabilities
    blockwise from the saved log-sum-exp (FlashAttention-2 backward) in two
    Pallas kernels — training at long L never materializes (L, L).

    ``vma``: mesh axes this call varies over when used inside a
    ``shard_map`` body with ``check_vma=True`` (e.g. the ulysses engine);
    tags the kernels' out_shapes so the caller keeps the vma checker on.
    """
    vma = tuple(vma) if vma is not None else None  # hashable static arg
    return _flash_diff(causal, block_q, block_k, vma, q, k, v)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3))
def _flash_diff(causal, block_q, block_k, vma, q, k, v):
    return _flash_forward(
        q, k, v, causal=causal, block_q=block_q, block_k=block_k,
        return_lse=False, vma=vma,
    )


def _flash_diff_fwd(causal, block_q, block_k, vma, q, k, v):
    out, lse = _flash_forward(
        q, k, v, causal=causal, block_q=block_q, block_k=block_k,
        return_lse=True, vma=vma,
    )
    return out, (q, k, v, out, lse)


def _flash_diff_bwd(causal, block_q, block_k, vma, res, g):
    q, k, v, out, lse = res
    return _flash_backward(
        q, k, v, out, lse, g, causal=causal, block_q=block_q, block_k=block_k,
        vma=vma,
    )


_flash_diff.defvjp(_flash_diff_fwd, _flash_diff_bwd)


def flash_block(l: int, block_q: int = 128) -> int:
    """The clamped flash block size for sequence length ``l`` — THE shared
    source of the ``l % flash_block(l) == 0`` divisibility rule, so CLI
    pre-checks (examples/lm.py) and the library validations
    (sequence_parallel) cannot drift from the kernel's actual tiling."""
    return min(block_q, l)


def flash_attention_with_lse(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = False,
    block_q: int = 128,
    block_k: int = 128,
    vma=None,
) -> tuple:
    """Fused attention returning ``(out, lse)``, differentiable in both.

    ``out``: (B, L, H, D) normalized attention; ``lse``: (B, H, L) per-row
    log-sum-exp of the scaled scores. Two normalized partials over disjoint
    key sets merge exactly via their LSEs::

        lse  = logaddexp(lse1, lse2)
        out  = exp(lse1 - lse) * out1 + exp(lse2 - lse) * out2

    which is what the ring-attention flash engine does per hop
    (parallel.sequence_parallel). DIFFERENTIABLE, jointly in both outputs:
    the custom VJP accepts cotangents for ``out`` AND ``lse`` (the lse
    cotangent shifts the FA-2 backward's delta term by -g_lse — see
    ``_flash_backward``), which is exactly what flowing gradients through
    the ring engine's per-hop LSE merge requires. Memory stays O(L)
    (blockwise recompute, no (L, L) residency). ``vma``: see
    :func:`flash_attention`.
    """
    vma = tuple(vma) if vma is not None else None
    return _flash_lse(causal, block_q, block_k, vma, q, k, v)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3))
def _flash_lse(causal, block_q, block_k, vma, q, k, v):
    out, lse = _flash_forward(
        q, k, v, causal=causal, block_q=block_q, block_k=block_k,
        return_lse=True, vma=vma,
    )
    return out, lse[:, :, 0, :]  # (B, H, 1, L) internal layout -> (B, H, L)


def _flash_lse_fwd(causal, block_q, block_k, vma, q, k, v):
    out, lse = _flash_forward(
        q, k, v, causal=causal, block_q=block_q, block_k=block_k,
        return_lse=True, vma=vma,
    )
    # Residual lse keeps the kernels' (B, H, 1, L) layout; the primal
    # output exposes (B, H, L).
    return (out, lse[:, :, 0, :]), (q, k, v, out, lse)


def _flash_lse_bwd(causal, block_q, block_k, vma, res, g):
    q, k, v, out, lse = res
    g_o, g_lse = g
    return _flash_backward(
        q, k, v, out, lse, g_o.astype(q.dtype), causal=causal,
        block_q=block_q, block_k=block_k, vma=vma, lse_grad=g_lse,
    )


_flash_lse.defvjp(_flash_lse_fwd, _flash_lse_bwd)
