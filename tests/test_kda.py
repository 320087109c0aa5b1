"""The chunked gated-delta-rule scan (``ops/kda.py``) on the CPU, interpreted:
the kernel against the plain recurrence, one token at a time, over batch,
head, chunk and head-block shapes; under decays so steep that ``exp(-G)``
would overflow float32 inside a chunk; with the write strength near 0 and near
2 and with identical keys (where ``I - beta k k^T`` has its eigenvalue -1); in
float32 and bf16; causality; the shapes it refuses; its decay plan by hand."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cuda_mpi_gpu_cluster_programming_tpu.ops.kda import decay_plan, kda_chunked, kda_recurrence


def operands(seed, b, h, l, dk, dv, *, decay, beta="uniform", dtype=jnp.float32, same_keys=False):
    """Unit-norm queries and keys, normal values, ``g = -decay * U(0, 1)^3``
    (most channels decay slowly, a few ``decay`` a token) and ``beta``."""
    kq, kk, kv, kg, kb = jax.random.split(jax.random.key(seed), 5)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(jax.random.normal(kq, (b, h, l, dk)))
    k = unit(jax.random.normal(kk, (b, h, 1 if same_keys else l, dk)))
    k = jnp.broadcast_to(k, (b, h, l, dk))
    v = jax.random.normal(kv, (b, h, l, dv))
    g = -decay * jax.random.uniform(kg, (b, h, l, dk)) ** 3
    beta = {
        "uniform": 2.0 * jax.random.uniform(kb, (b, h, l)),
        "near_0": jnp.full((b, h, l), 1e-3),
        "near_2": jnp.full((b, h, l), 1.999),
    }[beta]
    return q.astype(dtype), k.astype(dtype), v.astype(dtype), g, beta


def rel_err(got, want) -> float:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.abs(got - want).max() / np.abs(want).max())


SHAPES = {
    # name: (B, H, L, dk, dv, chunk, head_block)
    "one_chunk": (1, 2, 16, 16, 16, 16, 1),
    "many_chunks": (2, 2, 128, 16, 16, 16, 2),
    "chunk_64": (1, 4, 128, 32, 32, 64, 4),
    "value_width_of_its_own": (1, 2, 64, 16, 24, 32, 1),
    "all_heads_in_one_program": (2, 3, 64, 16, 16, 16, 3),
}


CASES = [(shape, 30.0) for shape in sorted(SHAPES)] + [("chunk_64", 1.6), ("chunk_64", 0.05), ("many_chunks", 0.05)]


@pytest.mark.parametrize("shape,decay", CASES, ids=[f"{shape}-decay_{decay:g}" for shape, decay in CASES])
def test_kernel_agrees_with_the_recurrence_in_float32(shape, decay):
    """At up to 30 a token (the seeded decays of the real configuration reach
    that) the cumulative decay of one chunk passes 88, where ``exp(-G)`` alone
    is infinite in float32, and at 1.6 a token it passes 20 in a chunk of 64:
    the kernel forms only differences between a pair's tokens and stays on
    the recurrence."""
    b, h, l, dk, dv, chunk, head_block = SHAPES[shape]
    q, k, v, g, beta = operands(3, b, h, l, dk, dv, decay=decay)
    if decay == 30.0:
        summed = np.asarray(g).reshape(b, h, l // chunk, chunk, dk).sum(axis=3)
        with np.errstate(over="ignore"):
            assert summed.min() < -88.0 and not np.isfinite(np.exp(-summed.min(), dtype=np.float32))
    got = kda_chunked(q, k, v, g, beta, chunk=chunk, head_block=head_block)
    want, _state = kda_recurrence(q, k, v, g, beta)
    assert got.shape == (b, h, l, dv) and got.dtype == jnp.float32 and np.isfinite(np.asarray(got)).all()
    assert rel_err(got, want) < 5e-6


@pytest.mark.parametrize("beta", ["near_0", "near_2"])
@pytest.mark.parametrize("same_keys", [False, True], ids=["random_keys", "one_key"])
def test_write_strength_near_0_and_near_2(beta, same_keys):
    """With one key for every token and beta near 2 every step reflects the
    state along that key (eigenvalue -1, nothing decays): the inverse of
    ``I + A`` has entries of alternating sign that a series in powers of ``A``
    would lose; the block inverse keeps them. That case is ill-conditioned for
    the recurrence itself (128 reflections that barely decay): float32 agrees
    to parts in 1e5 there, parts in 1e6 elsewhere."""
    q, k, v, g, b = operands(5, 1, 2, 128, 16, 16, decay=1e-3, beta=beta, same_keys=same_keys)
    got = kda_chunked(q, k, v, g, b, chunk=64, head_block=2)
    want, _state = kda_recurrence(q, k, v, g, b)
    assert rel_err(got, want) < (1e-4 if same_keys and beta == "near_2" else 5e-6)


def test_bf16_operands_stay_within_bf16_of_the_recurrence_and_visibly_off_float32():
    """Stored in bf16 the operands reach the MXU in bf16 (decayed keys, the
    state and the written values are rounded to 8 bits of mantissa once each);
    accumulation, decays, state and the solve stay float32: parts in a
    thousand of the largest output, not parts in a hundred."""
    q, k, v, g, beta = operands(7, 2, 2, 128, 32, 32, decay=1.6, dtype=jnp.bfloat16)
    got = kda_chunked(q, k, v, g, beta, chunk=32, head_block=2)
    want, _state = kda_recurrence(q, k, v, g, beta)  # float32, from the same rounded operands
    assert got.dtype == jnp.bfloat16
    assert 1e-4 < rel_err(got, want) < 1.5e-2


def test_a_change_to_later_tokens_moves_no_earlier_output():
    q, k, v, g, beta = operands(9, 1, 2, 96, 16, 16, decay=1.0)
    cut = 40  # inside the second chunk of 32
    later = lambda x, s: x.at[:, :, cut:].set(jax.random.normal(jax.random.key(s), x[:, :, cut:].shape).astype(x.dtype))
    moved = (later(q, 1), later(k, 2), later(v, 3), -jnp.abs(later(g, 4)), jnp.abs(later(beta, 5)) % 2.0)
    first = np.asarray(kda_chunked(q, k, v, g, beta, chunk=32))
    second = np.asarray(kda_chunked(*moved, chunk=32))
    assert np.array_equal(first[:, :, :cut], second[:, :, :cut])
    assert not np.allclose(first[:, :, cut:], second[:, :, cut:])


def test_another_chunking_is_the_same_scan():
    """Four chunks of 16 or two of 32: the state the scratch carries from
    chunk to chunk makes both the one recurrence."""
    q, k, v, g, beta = operands(11, 1, 2, 64, 16, 16, decay=1.0)
    whole = kda_chunked(q, k, v, g, beta, chunk=16)
    halves = kda_chunked(q, k, v, g, beta, chunk=32)
    assert rel_err(halves, whole) < 5e-6  # another chunking, the same scan


@pytest.mark.parametrize(
    "change,match",
    [
        (dict(l=40), "whole chunks"),
        (dict(chunk=24), "power of two"),
        (dict(chunk=8), "power of two"),
        (dict(head_block=3), "does not divide"),
    ],
)
def test_shapes_the_kernel_refuses(change, match):
    l, chunk, head_block = change.get("l", 64), change.get("chunk", 16), change.get("head_block", 1)
    q, k, v, g, beta = operands(0, 1, 2, l, 16, 16, decay=1.0)
    with pytest.raises(ValueError, match=match):
        kda_chunked(q, k, v, g, beta, chunk=chunk, head_block=head_block)


def test_beta_of_another_shape_is_refused():
    q, k, v, g, beta = operands(0, 1, 2, 32, 16, 16, decay=1.0)
    with pytest.raises(ValueError, match="kda_chunked"):
        kda_chunked(q, k, v, g, beta[..., None], chunk=16)


def test_decay_plan_by_hand():
    """Chunk of 4: tokens 0..3, two levels. Every row of the plan sums ``g``
    over tokens BETWEEN a pair, never from the chunk's start to one of them."""
    plan, level = decay_plan(4)
    assert plan.shape == (4 * 4, 4) and level.shape == (4, 4)
    since, to_end, pairs, halves = plan[:4], plan[4:8], plan[8:12], plan[12:]
    assert np.array_equal(since, np.tril(np.ones((4, 4))))  # G_r: g_0 .. g_r
    assert np.array_equal(to_end, np.triu(np.ones((4, 4)), 1))  # G_C - G_r: g_(r+1) .. g_3
    # level 1, blocks {0,1} and {2,3}: the upper token decays by its own g, the lower by nothing
    assert np.array_equal(pairs, np.diag([0.0, 1.0, 0.0, 1.0]))
    # level 2, the block {0..3}, middle at 2: token 3 by g_2 + g_3, token 2 by g_2, token 1 by nothing, token 0 by g_1
    assert np.array_equal(halves, np.array([[0, 1, 0, 0], [0, 0, 0, 0], [0, 0, 1, 0], [0, 0, 1, 1.0]]))
    assert np.array_equal(level, np.array([[0, -1, -1, -1], [1, 0, -1, -1], [2, 2, 0, -1], [2, 2, 1, 0]]))
    # so a pair's two factors multiply to exp(G_r - G_s): e.g. r = 3, s = 1 at level 2
    g = np.array([-0.3, -0.5, -0.7, -1.1])
    assert np.exp(halves[3] @ g) * np.exp(halves[1] @ g) == pytest.approx(np.exp(g[2] + g[3]))
