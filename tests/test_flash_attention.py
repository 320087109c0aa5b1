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


# The step program of ``v8_mla_moe`` at the small preset as jax 0.9.0 lowers it
# (``.lower(...).as_text()``: the program as traced, before any compiler of a
# particular machine touches it; the kernels are in it as the interpreter
# discharges them, their block index maps too), as the commit BEFORE grouped
# key/value heads built it (PR 30's tree, 62892c5): sha256 of the text. Equal
# head counts must still build that kernel and that program (the dots cell's
# step may not change under it). A change that means to alter that program
# records the new digests here and says so.
EQUAL_HEADS_STEP_SHA256 = {
    "bf16": "7d67169888c1a7da7ab634382942b31f13fa11b8a478c911af78dc9d260a518f",
    "fp32": "2f16325032e0cf8f2758752f97924dd1aa244cceaee34146fce7acfe1b64c16f",
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
