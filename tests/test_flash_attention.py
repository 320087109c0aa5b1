"""Pallas flash attention vs the O(L^2) reference op (interpret mode on CPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cuda_mpi_gpu_cluster_programming_tpu.ops.attention import attention
from cuda_mpi_gpu_cluster_programming_tpu.ops.flash_attention import flash_attention


def qkv(key, b=2, l=128, h=4, d=32, dtype=jnp.float32):
    kq, kk, kv = jax.random.split(key, 3)
    shape = (b, l, h, d)
    return (
        jax.random.normal(kq, shape, dtype),
        jax.random.normal(kk, shape, dtype),
        jax.random.normal(kv, shape, dtype),
    )


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize(
    "l,block_q,block_k",
    [
        (128, 128, 128),
        (256, 64, 64),
        (256, 64, 128),
        # Non-dividing block ratio: fractional block offsets carry, which the
        # causal trip count must cover ((qi+1)*bq spans a partial k-block).
        (24, 8, 12),
        (192, 48, 64),
    ],
)
def test_matches_reference(causal, l, block_q, block_k):
    q, k, v = qkv(jax.random.PRNGKey(0), l=l)
    want = attention(q, k, v, causal=causal)
    got = flash_attention(q, k, v, causal=causal, block_q=block_q, block_k=block_k)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)


def test_small_sequence_clamps_blocks():
    q, k, v = qkv(jax.random.PRNGKey(1), l=32)
    want = attention(q, k, v, causal=True)
    got = flash_attention(q, k, v, causal=True)  # blocks clamp 128 -> 32
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)


def test_bf16():
    q, k, v = qkv(jax.random.PRNGKey(2), dtype=jnp.bfloat16)
    want = attention(q, k, v, causal=True)
    got = flash_attention(q, k, v, causal=True, block_q=64, block_k=64)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), rtol=3e-2, atol=3e-2
    )


def test_indivisible_rejected():
    q, k, v = qkv(jax.random.PRNGKey(0), l=96)
    with pytest.raises(ValueError, match="not divisible"):
        flash_attention(q, k, v, block_q=64, block_k=64)


def test_jit():
    q, k, v = qkv(jax.random.PRNGKey(3), l=64)
    got = jax.jit(lambda q, k, v: flash_attention(q, k, v, causal=True))(q, k, v)
    want = attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize(
    "l,block_q,block_k",
    # the last: blocks of 256 are computed in row slabs on the diagonal, and the
    # backward kernels read that forward's lse
    [(256, 64, 64), (192, 48, 64), (24, 8, 12), (512, 256, 256)],
)
def test_grad_matches_reference_blocked(causal, l, block_q, block_k):
    """Pallas recompute backward vs the O(L^2) oracle, incl. non-dividing
    block ratios and causal masking."""
    q, k, v = qkv(jax.random.PRNGKey(7), b=2, l=l, h=2, d=32)
    g = jax.random.normal(jax.random.PRNGKey(8), q.shape, q.dtype)

    def run(fn):
        out, vjp = jax.vjp(lambda q, k, v: fn(q, k, v), q, k, v)
        return out, vjp(g)

    want_out, want_grads = run(lambda q, k, v: attention(q, k, v, causal=causal))
    got_out, got_grads = run(
        lambda q, k, v: flash_attention(
            q, k, v, causal=causal, block_q=block_q, block_k=block_k
        )
    )
    np.testing.assert_allclose(np.asarray(got_out), np.asarray(want_out), rtol=2e-5, atol=2e-5)
    for got, want, name in zip(got_grads, want_grads, "qkv"):
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=5e-5, atol=5e-5,
            err_msg=f"d{name} mismatch",
        )


def test_backward_never_materializes_LxL():
    """The memory claim, asserted structurally: at L=1024 the compiled
    forward+backward contains NO (L, L) tensor anywhere (the round-1 VJP
    fallback materialized f32[...,1024,1024] score/grad matrices — at the
    lengths this kernel exists for, that is OOM by construction)."""
    l = 1024
    q, k, v = qkv(jax.random.PRNGKey(9), b=1, l=l, h=1, d=32)

    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True) ** 2)

    lowered = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(q, k, v)
    hlo = lowered.compile().as_text()
    assert f"{l},{l}" not in hlo, "compiled grad materializes an (L, L) tensor"
    # sanity: the same probe DOES flag the quadratic reference path
    ref_hlo = (
        jax.jit(jax.grad(lambda q, k, v: jnp.sum(attention(q, k, v, causal=True) ** 2), argnums=(0, 1, 2)))
        .lower(q, k, v)
        .compile()
        .as_text()
    )
    assert f"{l},{l}" in ref_hlo


def test_forward_lse_matches_reference():
    """The saved LSE (backward residual) equals log-sum-exp of the true
    scaled scores."""
    from cuda_mpi_gpu_cluster_programming_tpu.ops.flash_attention import _flash_forward

    b, l, h, d = 2, 128, 2, 16
    q, k, v = qkv(jax.random.PRNGKey(10), b=b, l=l, h=h, d=d)
    _, lse = _flash_forward(q, k, v, causal=False, block_q=64, block_k=32, return_lse=True)
    s = jnp.einsum("blhd,bmhd->bhlm", q, k) / jnp.sqrt(jnp.asarray(d, jnp.float32))
    want = jax.scipy.special.logsumexp(s, axis=-1)  # (b,h,l)
    # LSE rides as (b,h,1,l) — Mosaic block-tiling-legal layout (see
    # _flash_forward out_specs).
    np.testing.assert_allclose(
        np.asarray(lse)[:, :, 0, :], np.asarray(want), rtol=1e-5, atol=1e-5
    )


def test_grad_matches_reference():
    q, k, v = qkv(jax.random.PRNGKey(4), b=1, l=64, h=2, d=16)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(attention(q, k, v, causal=True) ** 2)

    g_flash = jax.jit(jax.grad(loss_flash, argnums=(0, 1, 2)))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for gf, gr in zip(g_flash, g_ref):
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gr), rtol=1e-4, atol=1e-4)


def test_with_lse_joint_vjp_matches_oracle():
    """The joint (out, lse) VJP (supersedes the round-3 advisor's clean
    forward-only error): a loss touching BOTH outputs must match the XLA
    oracle's gradients — the lse cotangent shifts the FA-2 delta term."""
    from cuda_mpi_gpu_cluster_programming_tpu.ops.flash_attention import (
        flash_attention_with_lse,
    )

    b, l, h, d = 2, 64, 2, 16
    q, k, v = qkv(jax.random.PRNGKey(11), b=b, l=l, h=h, d=d)

    def oracle(q, k, v, causal):
        s = jnp.einsum("blhd,bmhd->bhlm", q, k) / jnp.sqrt(jnp.asarray(d, jnp.float32))
        if causal:
            s = jnp.where(jnp.tril(jnp.ones((l, l), bool))[None, None], s, -1e30)
        out = jnp.einsum("bhlm,bmhd->blhd", jax.nn.softmax(s, -1), v)
        return out, jax.scipy.special.logsumexp(s, -1)

    for causal in (False, True):
        def loss_f(q, k, v):
            o, s = flash_attention_with_lse(q, k, v, causal=causal)
            return jnp.sum(o**2) + jnp.sum(jnp.sin(s))

        def loss_o(q, k, v):
            o, s = oracle(q, k, v, causal)
            return jnp.sum(o**2) + jnp.sum(jnp.sin(s))

        gf = jax.grad(loss_f, (0, 1, 2))(q, k, v)
        go = jax.grad(loss_o, (0, 1, 2))(q, k, v)
        for a, b_ in zip(gf, go):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b_), rtol=1e-4, atol=1e-4)


def test_ring_flash_grad_matches_oracle():
    """ring_attention(engine='flash') is differentiable end to end: the
    per-hop joint VJP + ppermute/fori_loop/switch transpose rules reverse
    the whole ring; gradients must match whole-sequence attention."""
    from cuda_mpi_gpu_cluster_programming_tpu.parallel.sequence_parallel import (
        ring_attention,
    )

    q, k, v = qkv(jax.random.PRNGKey(12), b=2, l=64, h=4, d=16)
    for n in (2, 4):
        for causal in (False, True):
            def loss_r(q, k, v):
                out = ring_attention(q, k, v, n_shards=n, causal=causal, engine="flash")
                return jnp.sum(out**2)

            def loss_o(q, k, v):
                return jnp.sum(attention(q, k, v, causal=causal) ** 2)

            gr = jax.jit(jax.grad(loss_r, (0, 1, 2)))(q, k, v)
            go = jax.grad(loss_o, (0, 1, 2))(q, k, v)
            for a, b_ in zip(gr, go):
                np.testing.assert_allclose(
                    np.asarray(a), np.asarray(b_), rtol=1e-4, atol=5e-4
                )


def test_vma_struct_policy():
    """vma tagging: plain without axes; dropped in interpret mode (CPU test
    backend), where kernel_check_vma also prescribes the checker off."""
    from cuda_mpi_gpu_cluster_programming_tpu.ops.vma import (
        interpret_mode,
        kernel_check_vma,
        vma_struct,
    )

    assert vma_struct((2, 2), "float32").vma is None
    assert interpret_mode()  # the test mesh is the CPU backend
    assert kernel_check_vma() is False
    # In interpret mode the tag is dropped (jax's interpreter cannot
    # propagate vma through discharged kernels).
    assert vma_struct((2, 2), "float32", ("sp",)).vma is None


def test_shape_dtype_struct_vma_kwarg_exists():
    """API-drift guard (round-4 advisor): the tagged path only runs on a
    TPU, so a jax upgrade renaming the ``vma=`` kwarg must surface HERE,
    in CI, not on the chip. Constructs the tagged struct directly —
    independent of interpret-mode dropping."""
    import jax

    s = jax.ShapeDtypeStruct((2, 2), "float32", vma=frozenset({"sp"}))
    assert s.vma == frozenset({"sp"})
    assert jax.ShapeDtypeStruct((2, 2), "float32").vma is None


# ---- grouped key/value heads (forward kernel on heads-major operands) --------

GQA_CASES = {
    # name: (H, Hk, L, D, Dv, block_q, block_k, causal)
    "eight_to_one": (8, 1, 32, 16, 16, 16, 16, True),
    "four_to_two_unequal_blocks": (4, 2, 32, 24, 16, 8, 16, True),
    "six_to_three_one_block": (6, 3, 16, 16, 24, 64, 64, True),
    "four_to_two_not_causal": (4, 2, 32, 16, 16, 16, 8, False),
    "equal_heads": (4, 4, 32, 16, 16, 16, 16, True),
}


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5), (jnp.bfloat16, 3e-2)])
@pytest.mark.parametrize("case", sorted(GQA_CASES))
def test_grouped_key_value_heads_equal_the_reference_on_repeated_heads(case, dtype, tol):
    """Query head ``h`` reads key/value head ``h // (H / Hk)`` through the
    block index map: the output is ``ops.attention`` on keys and values
    repeated per query head, and no repeat is ever built for the kernel."""
    from cuda_mpi_gpu_cluster_programming_tpu.ops.flash_attention import flash_forward_bhld

    h, hk, l, d, dv, block_q, block_k, causal = GQA_CASES[case]
    b = 2
    keys = jax.random.split(jax.random.key(21), 3)
    draw = lambda key, shape: jax.random.normal(key, shape, jnp.float32).astype(dtype)
    q, k, v = draw(keys[0], (b, h, l, d)), draw(keys[1], (b, hk, l, d)), draw(keys[2], (b, hk, l, dv))
    out, lse = flash_forward_bhld(q, k, v, causal=causal, block_q=block_q, block_k=block_k)
    assert out.shape == (b, h, l, dv) and out.dtype == dtype and lse.shape == (b, h, 1, l)
    lhd = lambda x: jnp.transpose(x, (0, 2, 1, 3))  # (B, H, L, D) -> (B, L, H, D)
    repeated_k = jnp.repeat(k, h // hk, axis=1)
    if d == dv:
        want = lhd(attention(lhd(q), lhd(repeated_k), lhd(jnp.repeat(v, h // hk, axis=1)), causal=causal))
    else:  # the reference op takes one width: the kernel itself on repeated heads
        want, _lse = flash_forward_bhld(
            q, repeated_k, jnp.repeat(v, h // hk, axis=1), causal=causal, block_q=block_q, block_k=block_k
        )
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(want, np.float32), rtol=tol, atol=tol)
    # each query head reads ITS key/value head: moving another head's keys moves nothing of it
    if hk > 1:
        other = flash_forward_bhld(q, k.at[:, 1].add(1.0), v, causal=causal, block_q=block_q, block_k=block_k)[0]
        mine = slice(0, h // hk)  # the query heads of key/value head 0
        assert np.array_equal(np.asarray(out[:, mine], np.float32), np.asarray(other[:, mine], np.float32))
        rest = slice(h // hk, None)
        assert not np.array_equal(np.asarray(out[:, rest], np.float32), np.asarray(other[:, rest], np.float32))


def test_key_value_heads_that_do_not_divide_the_query_heads_are_refused():
    from cuda_mpi_gpu_cluster_programming_tpu.ops.flash_attention import flash_forward_bhld

    q, kv = jnp.zeros((1, 4, 16, 8)), jnp.zeros((1, 3, 16, 8))
    with pytest.raises(ValueError, match=r"Hk \| H"):
        flash_forward_bhld(q, kv, kv, causal=True)
    with pytest.raises(ValueError, match=r"Hk \| H"):
        flash_forward_bhld(q, kv[:, :2], kv[:, :1], causal=True)  # keys and values disagree


# ---- a block on the diagonal in row slabs; the grid over the pairs that contribute ----

SLAB_CASES = {
    # name: (H, Hk, L, D, Dv, R, block): square blocks of 256 (two slabs of 128 rows) or 512 (four)
    "equal_heads_l512_b256": (2, 2, 512, 32, 32, 0, 256),
    "equal_heads_l1024_b512": (2, 2, 1024, 16, 16, 0, 512),
    "grouped_heads_l1024_b256": (4, 1, 1024, 16, 16, 0, 256),
    "value_width_differs_l512_b256": (2, 2, 512, 32, 16, 0, 256),
    "rope_operands_l512_b256": (2, 2, 512, 32, 16, 8, 256),
    "rope_operands_grouped_l1024_b512": (4, 2, 1024, 16, 32, 8, 512),
}
WHOLE_CASES = {
    # name: (H, Hk, L, D, Dv, R, block_q, block_k, causal): shapes that admit no slabs
    "unequal_blocks": (2, 2, 512, 32, 32, 0, 256, 128, True),
    "unequal_blocks_wider_keys": (2, 1, 512, 16, 32, 8, 128, 256, True),
    "blocks_of_128": (2, 2, 512, 32, 32, 0, 128, 128, True),
    "not_causal": (2, 2, 512, 32, 16, 8, 256, 256, False),
}


def _operands(h, hk, l, d, dv, r, dtype, seed=31):
    keys = jax.random.split(jax.random.key(seed), 5)
    draw = lambda key, shape: jax.random.normal(key, shape, jnp.float32).astype(dtype)
    q, k, v = draw(keys[0], (1, h, l, d)), draw(keys[1], (1, hk, l, d)), draw(keys[2], (1, hk, l, dv))
    rope = dict(q_rope=draw(keys[3], (1, h, r, l)), k_rope=draw(keys[4], (1, r, l))) if r else {}
    return q, k, v, rope


def _reference_out_and_lse(q, k, v, rope, causal):
    """``ops.attention.attention`` in float32 on the whole ``D + R``-wide
    queries and keys, keys and values repeated per query head, and the
    log-sum-exp of the same scaled scores."""
    h, hk, l = q.shape[1], k.shape[1], q.shape[2]
    f32 = lambda x: x.astype(jnp.float32)
    k, v = jnp.repeat(f32(k), h // hk, axis=1), jnp.repeat(f32(v), h // hk, axis=1)
    q = f32(q)
    if rope:
        q = jnp.concatenate([q, jnp.swapaxes(f32(rope["q_rope"]), 2, 3)], axis=-1)
        k_rope = jnp.broadcast_to(jnp.swapaxes(f32(rope["k_rope"]), 1, 2)[:, None], (1, h, l, rope["k_rope"].shape[1]))
        k = jnp.concatenate([k, k_rope], axis=-1)
    lhd = lambda x: jnp.transpose(x, (0, 2, 1, 3))
    out = lhd(attention(lhd(q), lhd(k), lhd(v), causal=causal))
    s = jnp.einsum("bhld,bhmd->bhlm", q, k, precision="highest") / jnp.sqrt(jnp.float32(q.shape[-1]))
    if causal:
        s = jnp.where(jnp.tril(jnp.ones((l, l), bool)), s, -jnp.inf)
    return out, jax.scipy.special.logsumexp(s, axis=-1)


def _products(fn):
    return str(jax.make_jaxpr(lambda: fn())()).count("dot_general")  # a new function: traced anew


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5), (jnp.bfloat16, 3e-2)])
@pytest.mark.parametrize("case", sorted(SLAB_CASES))
def test_the_diagonal_in_row_slabs_equals_the_reference(case, dtype, tol, monkeypatch):
    """Square blocks of 256 and up: a block on the diagonal is computed as row
    slabs, each against the keys up to its own last row. Output AND lse equal
    the float32 reference, and — every row still meets its keys in one update,
    in the same order, less only scores the mask zeroed — the kernel that
    computes the block whole, bit for bit."""
    from cuda_mpi_gpu_cluster_programming_tpu.ops import flash_attention as fa

    h, hk, l, d, dv, r, block = SLAB_CASES[case]
    q, k, v, rope = _operands(h, hk, l, d, dv, r, dtype)
    run = lambda: fa.flash_forward_bhld(q, k, v, causal=True, block_q=block, block_k=block, **rope)
    plan = fa.causal_plan(l, block, block)
    n = block // plan.diag_slab
    assert n == (4 if block == 512 else 2) and plan.scores_computed < plan.pairs * block * block
    per_update = 2 + bool(r)  # scores (two products with rope operands) and values
    assert _products(run) == per_update * (n + 1)  # the slabs of the diagonal's branch + the block below it
    out, lse = run()
    assert out.shape == (1, h, l, dv) and out.dtype == dtype and lse.shape == (1, h, 1, l)
    want, want_lse = _reference_out_and_lse(q, k, v, rope, causal=True)
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(want), rtol=tol, atol=tol)
    np.testing.assert_allclose(np.asarray(lse[:, :, 0]), np.asarray(want_lse), rtol=tol, atol=tol)
    monkeypatch.setattr(fa, "_diag_slab", lambda bq, bk: bq)  # here, not by an option: the block whole
    assert _products(run) == per_update * 2
    whole_out, whole_lse = run()
    assert np.array_equal(np.asarray(out, np.float32), np.asarray(whole_out, np.float32))
    assert np.array_equal(np.asarray(lse), np.asarray(whole_lse))


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5), (jnp.bfloat16, 3e-2)])
@pytest.mark.parametrize("case", sorted(WHOLE_CASES))
def test_shapes_that_admit_no_slabs_keep_the_whole_block_update(case, dtype, tol, monkeypatch):
    """Unequal blocks, blocks of 128 and attention without a mask: the plan
    names the block whole, the kernel traced holds the two updates it always
    held (masked and unmasked; one without a mask) and the result is that
    kernel's — the same with the slabs' rule taken away — and the reference's."""
    from cuda_mpi_gpu_cluster_programming_tpu.ops import flash_attention as fa

    h, hk, l, d, dv, r, block_q, block_k, causal = WHOLE_CASES[case]
    q, k, v, rope = _operands(h, hk, l, d, dv, r, dtype, seed=37)
    run = lambda: fa.flash_forward_bhld(q, k, v, causal=causal, block_q=block_q, block_k=block_k, **rope)
    if causal:
        plan = fa.causal_plan(l, block_q, block_k)
        assert plan.diag_slab == block_q and plan.scores_computed == plan.pairs * block_q * block_k
    assert _products(run) == (2 + bool(r)) * (2 if causal else 1)
    out, lse = run()
    want, want_lse = _reference_out_and_lse(q, k, v, rope, causal=causal)
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(want), rtol=tol, atol=tol)
    np.testing.assert_allclose(np.asarray(lse[:, :, 0]), np.asarray(want_lse), rtol=tol, atol=tol)
    monkeypatch.setattr(fa, "_diag_slab", lambda bq, bk: bq)
    whole_out, whole_lse = run()
    assert np.array_equal(np.asarray(out, np.float32), np.asarray(whole_out, np.float32))
    assert np.array_equal(np.asarray(lse), np.asarray(whole_lse))


CAUSAL_PLANS = {
    # (l, block_q, block_k): (grid steps the (l/bq) x (l/bk) grid took, pairs computed, blocks on the
    # diagonal, slabs a block): the numbers under ISSUE 38's Motivation
    (4096, 1024, 1024): (16, 10, 4, 8),
    (8192, 1024, 1024): (64, 36, 8, 8),
    (1024, 256, 256): (16, 10, 4, 2),
    (512, 128, 128): (16, 10, 4, 1),
    (512, 256, 128): (8, 6, 2, 1),
}


@pytest.mark.parametrize("shape", sorted(CAUSAL_PLANS))
def test_causal_plan_counts_the_steps_the_pairs_and_the_scores(shape):
    """The plan is the kernel's, from the shapes alone: one grid step per pair
    at or below the diagonal (where the rectangular grid visited every pair),
    whole blocks below the diagonal, ``bq^2 (n + 1) / (2n)`` scores of a block
    on it in ``n`` slabs, and ``l (l + 1) / 2`` scores kept."""
    from cuda_mpi_gpu_cluster_programming_tpu.ops.flash_attention import causal_plan

    l, bq, bk = shape
    rectangle, pairs, on_diagonal, n = CAUSAL_PLANS[shape]
    plan = causal_plan(l, bq, bk)
    assert rectangle == (l // bq) * (l // bk)
    assert (plan.grid_steps, plan.pairs, bq // plan.diag_slab) == (pairs, pairs, n)
    whole = pairs * bq * bk  # what the kernel computed with every block whole
    assert plan.scores_kept == l * (l + 1) // 2
    if n == 1:
        assert plan.scores_computed == whole
    else:
        assert plan.scores_computed == whole - on_diagonal * (bq * bq - bq * bq * (n + 1) // (2 * n))
    assert plan.masked_score_share == pytest.approx(1 - plan.scores_kept / plan.scores_computed)
    if shape == (4096, 1024, 1024):
        assert (whole, plan.scores_kept) == (10_485_760, 8_390_656)  # 20.0% thrown away ...
        assert 1 - plan.scores_kept / whole == pytest.approx(0.200, abs=5e-4)
        assert plan.masked_score_share == pytest.approx(0.0301, abs=5e-5)  # ... and 3.0%
    if shape == (8192, 1024, 1024):
        assert 1 - plan.scores_kept / whole == pytest.approx(0.111, abs=5e-4)
        assert plan.masked_score_share == pytest.approx(0.0153, abs=5e-5)
    with pytest.raises(ValueError, match="not divisible"):
        causal_plan(l + 8, bq, bk)


def test_masked_score_share_is_read_through_a_family_s_statistics(monkeypatch):
    """``flash.masked_score_share`` beside the routing gauges of a decoder
    family that calls the kernel: four blocks of 256 a head throw away a fifth
    of their scores computed whole, and what ``causal_plan`` says in slabs."""
    import dataclasses

    from cuda_mpi_gpu_cluster_programming_tpu.models import mla_moe
    from cuda_mpi_gpu_cluster_programming_tpu.observability import metrics
    from cuda_mpi_gpu_cluster_programming_tpu.ops import flash_attention as fa

    cfg = dataclasses.replace(mla_moe.SMALL, attn_block=256)
    params = mla_moe.init(jax.random.key(0), cfg, jnp.float32)
    ids = jax.random.randint(jax.random.key(1), (1, 1024), 0, cfg.vocab_size, jnp.int32)
    metrics.registry().reset()
    stats = mla_moe.routing_statistics(params, ids, cfg)
    share = stats[metrics.FLASH_MASKED_SCORE_SHARE]
    assert share == fa.causal_plan(1024, 256, 256).masked_score_share == pytest.approx(0.1102, abs=5e-5)
    assert metrics.registry().summary()[metrics.FLASH_MASKED_SCORE_SHARE] == share
    monkeypatch.setattr(fa, "_diag_slab", lambda bq, bk: bq)  # the blocks whole, as they were
    whole = mla_moe.routing_statistics(params, ids, cfg)[metrics.FLASH_MASKED_SCORE_SHARE]
    assert whole == pytest.approx(0.200, abs=1e-3) and share < whole
    metrics.registry().reset()


# The step program of ``v8_mla_moe`` at the small preset as jax 0.9.0 lowers it
# (``.lower(...).as_text()``: the program as traced, before any compiler of a
# particular machine touches it; the kernels are in it as the interpreter
# discharges them, their block index maps too): sha256 of the text. Equal head
# counts must keep building this kernel and this program (the dots cell's
# step may not change under a change made for another caller). A change that
# means to alter that program records the new digests here and says so: these
# are PR 38's, whose forward runs its grid over the contributing (q-block,
# k-block) pairs alone, two scalar-prefetched tables naming them (until then
# the digests were those of PR 30's tree, 62892c5, before grouped key/value
# heads: 7d671698... and 2f163250...). The small preset's one block of 32
# tokens admits no slabs, so the update in it is the one that was there.
EQUAL_HEADS_STEP_SHA256 = {
    "bf16": "09df0f200b51a915e954d5006f09577a4270a349c057b3df562f85d5de421d0c",
    "fp32": "c20b1fb4479faacdb60ba4992df73c267f9ed2bcf3c8979b66ce1b75dab0daba",
}


@pytest.mark.parametrize("compute", sorted(EQUAL_HEADS_STEP_SHA256))
def test_with_equal_head_counts_the_program_built_is_the_one_built_before(compute):
    import hashlib

    from cuda_mpi_gpu_cluster_programming_tpu.configs import REGISTRY, build_forward
    from cuda_mpi_gpu_cluster_programming_tpu.models import mla_moe

    if jax.__version__ != "0.9.0":
        pytest.skip("the digests are of jax 0.9.0's lowering")
    dtype = jnp.bfloat16 if compute == "bf16" else jnp.float32
    params = jax.eval_shape(lambda: mla_moe.init(jax.random.key(0), mla_moe.SMALL, dtype))
    ids = jax.ShapeDtypeStruct((2, 32), jnp.int32)
    text = build_forward(REGISTRY["v8_mla_moe"], mla_moe.SMALL, compute=compute).lower(params, ids).as_text()
    assert "loc(" not in text  # no source location in it: moving code changes nothing
    assert hashlib.sha256(text.encode()).hexdigest() == EQUAL_HEADS_STEP_SHA256[compute]


# ---- the window mask (PR 39) ---------------------------------------------------


def _dense_window(q, k, v, window):
    """Masked dense softmax, grouped heads: a query sees itself and the
    ``window - 1`` tokens before it (``window`` None: causal alone)."""
    group, l = q.shape[1] // k.shape[1], q.shape[2]
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k, precision="highest") * q.shape[-1] ** -0.5
    rows, keys = jnp.arange(l)[:, None], jnp.arange(l)[None, :]
    seen = rows >= keys if window is None else (rows >= keys) & (rows - keys < window)
    probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", probs, v, precision="highest")


@pytest.mark.parametrize(
    "l,bq,bk,window",
    [
        (64, 16, 16, 8), (64, 16, 16, 16), (64, 16, 16, 24), (64, 16, 16, 37), (64, 16, 16, 1), (64, 16, 16, 64),
        (64, 16, 16, 100), (64, 16, 8, 5), (64, 8, 16, 40), (512, 256, 256, 256), (512, 256, 256, 300),
        (512, 256, 256, 100),
    ],
    ids=lambda v: str(v),
)
def test_the_windowed_forward_is_the_masked_dense_softmax(l, bq, bk, window):
    """Windows below, at and above a block and no multiple of one, at heads
    half as wide as their values over grouped key/value heads; at square blocks
    over 128 the diagonal runs in slabs where the window is at least a block."""
    from cuda_mpi_gpu_cluster_programming_tpu.ops.flash_attention import causal_plan, flash_forward_bhld

    keys = jax.random.split(jax.random.key(l + bq + window), 3)
    q = jax.random.normal(keys[0], (1, 4, l, 16))
    k, v = jax.random.normal(keys[1], (1, 2, l, 16)), jax.random.normal(keys[2], (1, 2, l, 32))
    out, lse = flash_forward_bhld(q, k, v, causal=True, block_q=bq, block_k=bk, window=window)
    assert out.shape == (1, 4, l, 32) and lse.shape == (1, 4, 1, l)
    np.testing.assert_allclose(out, _dense_window(q, k, v, window), atol=2e-6)
    plan = causal_plan(l, bq, bk, window)
    rows, cols = np.arange(l)[:, None], np.arange(l)[None, :]
    assert plan.scores_kept == int(((rows >= cols) & (rows - cols < window)).sum())
    band = [(qi, ki) for qi in range(l // bq) for ki in range(l // bk)
            if ((rows >= cols) & (rows - cols < window))[qi * bq : (qi + 1) * bq, ki * bk : (ki + 1) * bk].any()]
    assert plan.pairs == len(band)  # the pairs the band touches and no others
    assert plan.diag_slab == (128 if (bq, bk) == (256, 256) and window >= 256 else bq)
    assert plan.scores_kept <= plan.scores_computed <= plan.pairs * bq * bk
    if window >= l:  # a window that holds the sequence is the causal mask
        assert plan == causal_plan(l, bq, bk)


def test_the_window_at_the_published_shape_cuts_the_causal_scores_four_times():
    from cuda_mpi_gpu_cluster_programming_tpu.ops.flash_attention import causal_plan, flash_forward_bhld

    window, causal = causal_plan(4096, 512, 512, 512), causal_plan(4096, 1024, 1024)
    assert window.scores_kept == 512 * 513 // 2 + 3584 * 512 == 1_966_336
    assert causal.scores_kept / window.scores_kept == pytest.approx(4.27, abs=5e-3)
    assert window.pairs == 1 + 7 * 2 and window.diag_slab == 128
    # 7 blocks whole and 8 on the diagonal in four slabs of 128 (10 / 16 of a block): 3,145,728 scores for 1,966,336
    assert window.scores_computed == 7 * 262144 + 8 * 163840
    assert window.masked_score_share == pytest.approx(0.3749, abs=5e-5)
    smaller = causal_plan(4096, 256, 256, 512)  # blocks of 256 mask less and run slower (PERF.md section 6)
    assert smaller.pairs == 1 + 2 + 14 * 3 and smaller.masked_score_share == pytest.approx(0.2682, abs=5e-5)
    q = jnp.zeros((1, 2, 64, 16))
    with pytest.raises(ValueError, match="window"):
        flash_forward_bhld(q, q, q, causal=False, block_q=16, block_k=16, window=8)
    with pytest.raises(ValueError, match="window"):
        flash_forward_bhld(q, q, q, causal=True, block_q=16, block_k=16, window=0)


# The step programs of the four accepted language-model cells at their real
# shapes (the benchmark's presets, bf16) as jax 0.9.0 lowers them
# (``.lower(...).as_text()``, no source location in it): sha256 of the text, as
# PR 38's tree (b04fa42), the commit BEFORE the window, lowers them. With
# ``window=None`` the kernel built is the one that was built: no accepted cell
# can move under the band's code. A change that means to alter one of these
# steps records the new digest here and says so.
ACCEPTED_STEP_SHA256 = {
    ("v8_mla_moe", "ep16_share"): "a7e34e95a624285f51ec9dd45b5c6f178ec9509f80114a001aea1f1d9fb62c9a",
    ("v9_kda_moe", "solar_ep8"): "26d613a94daf0abc378e791f3e67ea338ec5b04b2e84e4b020d16976a5deea57",
    ("v10_cca_moe", "zaya1_ep2"): "4eb995d440fdbda4bffeb79b736be536a8b2febf1d8c32671e9c70303871344b",
    ("v11_scmoe_mla", "longcat_ep32"): "40f94650c47f306b0c538b0e3f1b670fbe907769515b2ab9ee5253b1cb9b9ca9",
}


@pytest.mark.parametrize("key,preset", sorted(ACCEPTED_STEP_SHA256))
def test_without_a_window_the_accepted_cells_step_programs_are_the_parents(key, preset):
    import hashlib

    from cuda_mpi_gpu_cluster_programming_tpu.configs import REGISTRY, build_forward, language_model

    if jax.__version__ != "0.9.0":
        pytest.skip("the digests are of jax 0.9.0's lowering")
    model = language_model(REGISTRY[key])
    cfg, batch, seq = model.PRESETS[preset]
    params = jax.eval_shape(lambda: model.init(jax.random.key(0), cfg, jnp.bfloat16))
    ids = jax.ShapeDtypeStruct((batch, seq), jnp.int32)
    text = build_forward(REGISTRY[key], cfg, n_shards=1, compute="bf16").lower(params, ids).as_text()
    assert "loc(" not in text  # no source location in it: moving code changes nothing
    assert hashlib.sha256(text.encode()).hexdigest() == ACCEPTED_STEP_SHA256[(key, preset)]
