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
@pytest.mark.parametrize("l,block_q,block_k", [(256, 64, 64), (192, 48, 64), (24, 8, 12)])
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
