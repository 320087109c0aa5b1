"""Long-context sequence parallelism: ring attention + Ulysses all-to-all.

The reference's only long-axis decomposition is its 1-D image-row scatter
with neighbor halo exchange (SURVEY §5.7 — "mechanically identical to
context-parallel stencil pipelines"). This module is the genuine long-context
tier built on the same mesh machinery:

- :func:`ring_attention` — blockwise attention with online-softmax
  accumulation; K/V blocks circulate the ring via ``lax.ppermute`` over ICI
  while every shard keeps only ``L/n`` of the sequence resident. Memory per
  chip is O(L/n), so context length scales linearly with the ring size.
- :func:`ulysses_attention` — all-to-all sequence parallelism: reshard from
  sequence-sharded to head-sharded with ``lax.all_to_all``, run exact local
  attention over the full sequence for the local heads, reshard back.
  Communication is two all-to-alls instead of n ppermute hops; needs
  ``n_heads % n_shards == 0``.

Both are validated shard-vs-single against ``ops.attention.attention`` on
the virtual 8-device mesh (tests/test_sequence_parallel.py), the same
equivalence discipline as the conv pipeline.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P

from ..ops.attention import NEG_INF
from ..ops.reference import mxu_precision
from ..ops.vma import kernel_check_vma
from .mesh import make_mesh


def _block_scores(q, k, scale):
    """(B, Lq, H, D) x (B, Lk, H, D) -> fp32 scores (B, H, Lq, Lk)."""
    return jnp.einsum(
        "blhd,bmhd->bhlm", q.astype(jnp.float32), k.astype(jnp.float32),
        precision=mxu_precision(q.dtype),
    ) * scale


def _ring_attention_local(q, k, v, *, axis_name: str, n_shards: int, causal: bool, vary_axes=None):
    """Per-shard body: online-softmax over ring-circulating K/V blocks.

    q/k/v: this shard's (B, Lb, H, D) block. At step t the resident K/V
    block is the one originally owned by shard ``(me - t) mod n`` (each step
    ppermutes blocks one hop forward around the ring).
    """
    b, lb, h, d = q.shape
    me = lax.axis_index(axis_name)
    scale = 1.0 / jnp.sqrt(jnp.asarray(d, jnp.float32))
    q_pos = me * lb + jnp.arange(lb)  # global positions of my queries

    def step(t, carry):
        k_blk, v_blk, m, num, den = carry
        src = (me - t) % n_shards  # original owner of the resident block
        s = _block_scores(q, k_blk, scale)  # (B, H, Lb, Lb)
        if causal:
            k_pos = src * lb + jnp.arange(lb)
            mask = q_pos[:, None] >= k_pos[None, :]
            s = jnp.where(mask[None, None], s, NEG_INF)
        blk_max = jnp.max(s, axis=-1)  # (B, H, Lb)
        m_new = jnp.maximum(m, blk_max)
        corr = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])  # (B, H, Lb, Lb)
        num = num * corr[..., None] + jnp.einsum(
            "bhlm,bmhd->bhld", p, v_blk.astype(jnp.float32),
            precision=mxu_precision(q.dtype),
        )
        den = den * corr + jnp.sum(p, axis=-1)
        # Circulate K/V one hop: shard i -> shard (i+1) mod n.
        perm = [(i, (i + 1) % n_shards) for i in range(n_shards)]
        k_blk = lax.ppermute(k_blk, axis_name, perm)
        v_blk = lax.ppermute(v_blk, axis_name, perm)
        return k_blk, v_blk, m_new, num, den

    # The zero/neg-inf initials are shard-invariant, but the loop carries
    # shard-varying updates — fori_loop needs both sides typed alike.
    _to_varying = _to_varying_fn(vary_axes or (axis_name,))
    m0 = _to_varying(jnp.full((b, h, lb), NEG_INF, jnp.float32))
    num0 = _to_varying(jnp.zeros((b, h, lb, d), jnp.float32))
    den0 = _to_varying(jnp.zeros((b, h, lb), jnp.float32))
    # lax.fori_loop keeps the compiled program size O(1) in ring size (a
    # Python loop would unroll n_shards copies of the body — fine at 8,
    # wasteful at pod scale). The causal mask already indexes by the traced
    # step (`src = (me - t) % n`), and the ppermute count is exactly
    # n_shards, so the last rotation restores K/V ownership.
    _, _, _, num, den = lax.fori_loop(0, n_shards, step, (k, v, m0, num0, den0))
    out = num / jnp.maximum(den, 1e-30)[..., None]  # (B, H, Lb, D)
    return jnp.transpose(out, (0, 2, 1, 3)).astype(q.dtype)


def _to_varying_fn(axes):
    # ``axes``: every mesh axis the loop carry varies over — with a
    # head_axis (sp x tp composition) the K/V inputs vary over BOTH, and
    # fori_loop demands carry-in/carry-out type equality.
    axes = tuple(axes)
    return lambda a: lax.pcast(a, axes, to="varying")


def _ring_attention_local_flash(q, k, v, *, axis_name: str, n_shards: int, causal: bool, vary_axes=None):
    """Flash-engine ring body: each hop runs the Pallas flash kernel on the
    resident K/V block and merges the normalized partial via its per-row
    LSE — exact, because partials over disjoint key sets satisfy

        lse  = logaddexp(lse1, lse2)
        out  = exp(lse1 - lse)*out1 + exp(lse2 - lse)*out2.

    Removes the einsum engine's (B, H, Lb, Lb) score residency: memory is
    O(Lb·D) per chip on top of the ring's O(L/n) — the two-level long-
    context composition (ring across chips × flash within chip). Causal
    hops split three ways on the block's global position: src < me = full
    attention, src == me = in-block causal, src > me = skipped (the flash
    kernel's causal mask is block-local, so the split is done here).

    Differentiable end to end: each hop's kernel call carries the joint
    (out, lse) VJP, the LSE-merge arithmetic is plain XLA, and the
    fori_loop/ppermute/switch all have transpose rules — so this body
    needs no custom backward of its own.
    """
    from ..ops.flash_attention import flash_attention_with_lse

    b, lb, h, d = q.shape
    me = lax.axis_index(axis_name)
    vary = tuple(vary_axes or (axis_name,))
    tv = _to_varying_fn(vary)

    # The kernel calls carry vma on their out_shapes and skip_fn pcasts its
    # constants, so all three lax.switch branches type-check as varying and
    # the shard_map keeps check_vma=True (scoped fix: the checker still
    # guards the ppermutes and the LSE merge).
    def full_fn(q, kb, vb):
        o, s = flash_attention_with_lse(q, kb, vb, causal=False, vma=vary)
        return o.astype(jnp.float32), s

    def causal_fn(q, kb, vb):
        o, s = flash_attention_with_lse(q, kb, vb, causal=True, vma=vary)
        return o.astype(jnp.float32), s

    def skip_fn(q, kb, vb):
        return (
            tv(jnp.zeros((b, lb, h, d), jnp.float32)),
            tv(jnp.full((b, h, lb), NEG_INF, jnp.float32)),
        )

    def step(t, carry):
        k_blk, v_blk, out, lse = carry
        src = (me - t) % n_shards
        if causal:
            idx = jnp.where(src < me, 0, jnp.where(src == me, 1, 2))
            o_t, lse_t = lax.switch(idx, [full_fn, causal_fn, skip_fn], q, k_blk, v_blk)
        else:
            o_t, lse_t = full_fn(q, k_blk, v_blk)
        lse_new = jnp.logaddexp(lse, lse_t)  # (B, H, Lb)
        w_old = jnp.exp(lse - lse_new)
        w_t = jnp.exp(lse_t - lse_new)
        out = (
            out * jnp.transpose(w_old, (0, 2, 1))[..., None]
            + o_t * jnp.transpose(w_t, (0, 2, 1))[..., None]
        )
        perm = [(i, (i + 1) % n_shards) for i in range(n_shards)]
        k_blk = lax.ppermute(k_blk, axis_name, perm)
        v_blk = lax.ppermute(v_blk, axis_name, perm)
        return k_blk, v_blk, out, lse_new

    out0 = tv(jnp.zeros((b, lb, h, d), jnp.float32))
    lse0 = tv(jnp.full((b, h, lb), NEG_INF, jnp.float32))
    _, _, out, _ = lax.fori_loop(0, n_shards, step, (k, v, out0, lse0))
    return out.astype(q.dtype)


def _validate_engine(engine: str) -> None:
    if engine not in ("einsum", "flash"):
        raise ValueError(f"engine must be einsum|flash, got {engine!r}")


def _validate_mesh_axis_size(mesh, axis_name: str, n_shards: int) -> None:
    """n_shards must equal the mesh's axis size. Ring: the fori_loop runs
    n_shards hops and the ppermute permutation has n_shards entries, so a
    mismatch silently computes attention over a subset of the K/V blocks
    (verified: max abs error ~0.8 vs the oracle). Ulysses: the L/H split
    arithmetic assumes the all_to_all group size equals n_shards."""
    if mesh is not None and dict(mesh.shape).get(axis_name) != n_shards:
        raise ValueError(
            f"n_shards ({n_shards}) != mesh axis {axis_name!r} size "
            f"({dict(mesh.shape).get(axis_name)}); the ring/reshard hop "
            "count is n_shards"
        )


def _validate_head_axis_mesh(mesh, head_axis: str) -> int:
    """Shared sp x tp pre-validation: explicit mesh, axis present. Returns
    the head-axis size so each caller applies its own divisibility rule
    (ring: tp; ulysses: sp*tp) — all with global numbers, so failures
    never surface as raw shard_map errors quoting shard-local shapes."""
    if mesh is None:
        raise ValueError("head_axis needs an explicit mesh containing both axes")
    if head_axis not in mesh.shape:
        raise ValueError(
            f"head_axis {head_axis!r} not in mesh axes {tuple(mesh.shape)}"
        )
    return dict(mesh.shape)[head_axis]


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    n_shards: int,
    causal: bool = False,
    mesh: Optional[Mesh] = None,
    axis_name: str = "sp",
    engine: str = "einsum",
    head_axis: Optional[str] = None,
    with_digests: bool = False,
) -> jax.Array:
    """Sequence-sharded blockwise ring attention. q,k,v: (B, L, H, D).

    ``with_digests``: return ``(out, {"qkv": (n,), "out": (n,)})`` — one
    in-graph activation digest per shard of the inputs and of the attention
    output, computed inside the shard_map body (the SDC sentinel taps; see
    ``parallel.sharded``). Screening is host-side and off the timed path.

    The sequence axis is sharded ``n_shards`` ways; K/V blocks ride the ring
    via ``ppermute`` (ICI neighbor traffic, the same collective as the conv
    halo exchange). Requires ``L % n_shards == 0``.

    ``head_axis``: optional second mesh axis sharding H — the sp×tp
    composition (Megatron attention heads over ``tp``, sequence over
    ``sp``). Heads are embarrassingly parallel in attention, so the ring
    body is unchanged; only the shard_map spec names the extra axis. The
    caller's ``mesh`` must contain both axes.

    ``engine``: ``"einsum"`` (default) materializes each hop's (Lb, Lb)
    score block with XLA ops — differentiable. ``"flash"`` runs the Pallas
    flash kernel per hop and merges partials by LSE — O(Lb·D) within-chip
    memory for long per-chip blocks, and ALSO differentiable: the kernel's
    joint (out, lse) VJP (ops.flash_attention) lets gradients flow through
    the per-hop merge, so autodiff reverses the whole ring (ppermutes
    transpose to reversed permutations, the merge arithmetic is plain XLA).
    Gradient-vs-oracle equivalence is tested at n∈{2,4}, causal and not
    (tests/test_flash_attention.py).
    """
    b, l, h, d = q.shape
    if l % n_shards != 0:
        raise ValueError(f"sequence length {l} not divisible by {n_shards} shards")
    _validate_engine(engine)
    if engine == "flash":
        # The flash kernel tiles each shard's block at (up to) 128 rows, so
        # the PER-SHARD length must divide by its clamped block size —
        # validate here with global numbers, or the error would surface
        # from inside the shard_map trace quoting the shard-local length.
        from ..ops.flash_attention import flash_block

        lb = l // n_shards
        blk = flash_block(lb)
        if lb % blk:
            raise ValueError(
                f"engine='flash' needs the per-shard block (L/n = {lb}) to be "
                f"a multiple of the flash block size ({blk}); L={l}, "
                f"n_shards={n_shards}. Use the einsum engine or pad L."
            )
    _validate_mesh_axis_size(mesh, axis_name, n_shards)
    if head_axis is not None:
        tp = _validate_head_axis_mesh(mesh, head_axis)
        if h % tp:
            raise ValueError(f"head count {h} not divisible by {head_axis}={tp} shards")
    if mesh is None:
        mesh = make_mesh(n_shards, axis_name=axis_name)
    local = _ring_attention_local_flash if engine == "flash" else _ring_attention_local
    vary = (axis_name,) + ((head_axis,) if head_axis else ())
    body = functools.partial(
        local, axis_name=axis_name, n_shards=n_shards, causal=causal,
        vary_axes=vary,
    )
    spec = P(None, axis_name, head_axis, None)
    fn = shard_map(
        _with_stage_digests(body) if with_digests else body,
        mesh=mesh, in_specs=(spec, spec, spec),
        out_specs=(
            (spec, {"qkv": P(axis_name), "out": P(axis_name)})
            if with_digests
            else spec
        ),
        # Flash engine: checker ON wherever the kernels can tag vma (real
        # TPU) — ops.vma.kernel_check_vma; the blanket disable now only
        # survives in interpret mode, where jax's own interpreter can't
        # propagate vma. Einsum engine: always on.
        check_vma=(engine != "flash" or kernel_check_vma()),
    )
    return fn(q, k, v)


def _with_stage_digests(body):
    """Wrap a per-shard attention body with in-graph sentinel taps: digest
    the (q, k, v) inputs and the output on each shard (one float32 scalar
    apiece, concatenated across shards by the caller's out_specs)."""
    from ..resilience.sentinel import tree_digest

    def tapped(q, k, v):
        out = body(q, k, v)
        digs = {"qkv": tree_digest((q, k, v))[None], "out": tree_digest(out)[None]}
        return out, digs

    return tapped


def _ulysses_local(q, k, v, *, axis_name: str, causal: bool, engine: str, vary_axes=None):  # noqa: D401
    """Per-shard body: all_to_all L-shard -> H-shard, exact attention, back.

    After the reshard each shard holds the FULL sequence for its local
    heads, so ``engine='flash'`` is just :func:`ops.flash_attention` on
    that call — the whole-sequence signature with the standard flash VJP
    (the ring engine instead differentiates through its per-hop joint
    (out, lse) VJP) — dropping the (L, L) score residency of the einsum
    path.
    """
    if engine == "flash":
        from ..ops.flash_attention import flash_attention

        # vma-tagged kernel out_shapes keep the caller's check_vma=True
        # guarding the two all_to_alls (scoped round-3-advisor fix).
        attention = functools.partial(
            flash_attention, vma=tuple(vary_axes or (axis_name,))
        )
    else:
        from ..ops.attention import attention

    # (B, Lb, H, D) -> (B, L, Hb, D): concat sequence, split heads.
    def to_heads(x):
        return lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1, tiled=True)

    def to_seq(x):
        return lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2, tiled=True)

    out = attention(to_heads(q), to_heads(k), to_heads(v), causal=causal)
    return to_seq(out)


def ulysses_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    n_shards: int,
    causal: bool = False,
    mesh: Optional[Mesh] = None,
    axis_name: str = "sp",
    engine: str = "einsum",
    head_axis: Optional[str] = None,
    with_digests: bool = False,
) -> jax.Array:
    """All-to-all (Ulysses-style) sequence parallelism. q,k,v: (B, L, H, D).

    ``with_digests``: as in :func:`ring_attention` — per-shard in-graph
    digests of the inputs and output ride alongside the result.

    Resharding sequence->heads makes each shard run *exact* attention over
    the full sequence for ``H/n`` heads; two tiled ``all_to_all`` collectives
    replace the ring's n ppermute hops. Requires ``L % n == 0`` and
    ``H % n == 0``.

    ``engine='flash'`` swaps the local exact attention for the Pallas flash
    kernel — O(L) instead of O(L^2) memory per shard, and still
    differentiable (the local call is the whole-sequence signature the
    flash custom VJP covers). Requires ``L`` to divide by the flash block
    (128 when ``L >= 128``).
    """
    b, l, h, d = q.shape
    if l % n_shards != 0:
        raise ValueError(f"sequence length {l} not divisible by {n_shards} shards")
    if h % n_shards != 0:
        raise ValueError(f"head count {h} not divisible by {n_shards} shards")
    _validate_mesh_axis_size(mesh, axis_name, n_shards)
    if head_axis is not None:
        # sp x tp: heads are pre-sharded over tp; the all_to_all then splits
        # each tp shard's local heads over sp, so H must divide by BOTH.
        tp = _validate_head_axis_mesh(mesh, head_axis)
        if h % (n_shards * tp):
            raise ValueError(
                f"head count {h} not divisible by sp x {head_axis} = "
                f"{n_shards} x {tp} shards"
            )
    _validate_engine(engine)
    if engine == "flash":
        from ..ops.flash_attention import flash_block

        blk = flash_block(l)
        if l % blk:
            raise ValueError(
                f"engine='flash' needs L ({l}) to be a multiple of the flash "
                f"block size ({blk}). Use the einsum engine or pad L."
            )
    if mesh is None:
        mesh = make_mesh(n_shards, axis_name=axis_name)
    body = functools.partial(
        _ulysses_local, axis_name=axis_name, causal=causal, engine=engine,
        vary_axes=(axis_name,) + ((head_axis,) if head_axis else ()),
    )
    spec = P(None, axis_name, head_axis, None)
    fn = shard_map(
        _with_stage_digests(body) if with_digests else body,
        mesh=mesh, in_specs=(spec, spec, spec),
        out_specs=(
            (spec, {"qkv": P(axis_name), "out": P(axis_name)})
            if with_digests
            else spec
        ),
        # Same policy as ring: flash keeps the checker wherever the kernel
        # can tag vma (real TPU); einsum always.
        check_vma=(engine != "flash" or kernel_check_vma()),
    )
    return fn(q, k, v)
