"""The chunked gated-delta-rule scan (``ops/kda.py``) on the CPU, interpreted:
the kernel against the plain recurrence, one token at a time, over batch,
head, chunk and head-block shapes; under decays so steep that ``exp(-G)``
would overflow float32 inside a chunk; with the write strength near 0 and near
2 and with identical keys (where ``I - beta k k^T`` has its eigenvalue -1); in
float32 and bf16; causality; the shapes it refuses; its decay plan by hand; the
block inverse alone against the whole-matrix update it replaced and against
float64; and, from the traced chunk, what its products stream (rows x passes),
so that the full-width float32 products cannot come back unnoticed."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from jax import lax

from cuda_mpi_gpu_cluster_programming_tpu.ops.kda import (
    _one_chunk,
    block_inverse,
    decay_plan,
    kda_chunked,
    kda_recurrence,
)


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
    "chunk_128": (1, 4, 256, 128, 128, 128, 4),  # the cell's tiles: every level of the inverse, four heads a program
}


CASES = [(shape, 30.0) for shape in sorted(SHAPES)]
CASES += [("chunk_64", 1.6), ("chunk_64", 0.05), ("many_chunks", 0.05), ("chunk_128", 0.05)]


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


def whole_matrix_inverse(a, level):
    """What ``block_inverse`` replaced (PR 31's form): every level updates the
    whole matrix, ``inv - inv (A_l inv)``, two full-width float32 products."""
    dot32 = lambda x, y: jnp.dot(x, y, preferred_element_type=jnp.float32, precision=lax.Precision.HIGHEST)
    inv = jnp.where(level == 0, 1.0, 0.0) - jnp.where(level == 1, a, 0.0)
    for lv in range(2, a.shape[0].bit_length()):
        inv = inv - dot32(inv, dot32(jnp.where(level == lv, a, 0.0), inv))
    return inv


@pytest.mark.parametrize("keys", ["unit_keys", "one_key"])
@pytest.mark.parametrize("chunk", [16, 32, 64, 128])
def test_block_inverse_alone(chunk, keys):
    """``A = beta * strictly_lower(K K^T)`` with ``beta`` up to 2: of unit keys
    (entries of either sign, most small) and of ONE key for every token with
    ``beta`` 1.999 throughout (every entry 1.999, the inverse's entries
    alternate in sign and ``I + A`` is as ill-conditioned as the kernel meets
    it: parts in 1e5 at chunk 128, as the kernel's own test of that case
    allows). The strips give what the whole-matrix update gave, and both give
    float64's inverse."""
    rng = np.random.default_rng(chunk)
    k = rng.normal(size=(1 if keys == "one_key" else chunk, 16))
    k = np.broadcast_to(k / np.linalg.norm(k, axis=1, keepdims=True), (chunk, 16))
    beta = np.full((chunk, 1), 1.999) if keys == "one_key" else 2.0 * rng.uniform(size=(chunk, 1))
    a = beta * np.tril(k @ k.T, -1)
    assert np.abs(a).max() > (1.99 if keys == "one_key" else 1.0)
    want = np.linalg.inv(np.eye(chunk) + a)
    a32, level = jnp.asarray(a, jnp.float32), jnp.asarray(decay_plan(chunk)[1])
    got, before = jax.jit(block_inverse)(a32, level), jax.jit(whole_matrix_inverse)(a32, level)
    assert got.shape == (chunk, chunk) and got.dtype == jnp.float32
    assert np.array_equal(np.triu(np.asarray(got), 1), np.zeros((chunk, chunk)))  # nothing above the diagonal
    limit = 1e-4 if keys == "one_key" else 1e-6
    assert rel_err(got, want) < limit and rel_err(before, want) < limit
    assert rel_err(got, before) < limit


def products_of(jaxpr):
    """Every ``dot_general`` of a jaxpr and of what it calls: ``(rows its left
    operand streams, whether the operands are float32, its precision)``."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            lhs = eqn.invars[0].aval
            (contract, _rhs), (batch, _) = eqn.params["dimension_numbers"]
            rows = int(np.prod([n for axis, n in enumerate(lhs.shape) if axis not in (*contract, *batch)]))
            is_f32 = jnp.float32 in (lhs.dtype, eqn.invars[1].aval.dtype)
            found.append((rows, is_f32, eqn.params["precision"]))
        for value in eqn.params.values():
            for inner in value if isinstance(value, (list, tuple)) else [value]:
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    found += products_of(inner)
    return found


def at_highest(precision) -> bool:
    return all(p == lax.Precision.HIGHEST for p in (precision if isinstance(precision, tuple) else (precision,)))


@functools.cache
def traced_chunk(chunk=128, d=128, dtype=jnp.bfloat16):
    """``_one_chunk`` as the cell runs it (bf16 operands, chunk 128, dk = dv = 128), traced on the CPU."""
    plan, level = decay_plan(chunk)
    x, f = jax.ShapeDtypeStruct((chunk, d), dtype), jax.ShapeDtypeStruct((chunk, d), jnp.float32)
    beta_row = jax.ShapeDtypeStruct((1, chunk), jnp.float32)
    trace = jax.make_jaxpr(lambda *args: _one_chunk(*args, scale=1.0))
    return trace(x, x, x, f, beta_row, f, jnp.asarray(plan, jnp.bfloat16), jnp.asarray(level)).jaxpr


# rows x passes of one chunk and head at chunk 128, as shipped (8,160): the plan 3 x 1,152, seven level products of
# 256, the inverse (6 x 8 + 2 x 16 + 2 x 32 + 2 x 64) x 6, its product with the values 128 x 6, four of 128 in bf16
ROW_PASSES = 3 * 1152 + 7 * 256 + 272 * 6 + 128 * 6 + 4 * 128


def test_row_passes_of_a_chunk_are_what_was_shipped():
    """A bf16 product streams its left operand's rows once, a float32 one at
    ``HIGHEST`` six times. PR 31 had 15,744 a chunk and head; the inverse by
    strips leaves 8,160, and ISSUE 32 set 12,800 as the most a half-done job
    could leave."""
    total = sum(rows * (6 if is_f32 else 1) for rows, is_f32, _ in products_of(traced_chunk()))
    assert total <= ROW_PASSES < 12800


def test_every_float32_product_of_a_chunk_is_at_highest():
    f32_products = [p for p in products_of(traced_chunk()) if p[1]]
    assert len(f32_products) == 13 and all(at_highest(precision) for _, _, precision in f32_products)
    # and with float32 operands stored, the other products too (ops.reference.mxu_precision)
    assert all(at_highest(precision) for _, is_f32, precision in products_of(traced_chunk(dtype=jnp.float32)) if is_f32)


@pytest.mark.parametrize("chunk", [16, 32, 64, 128])
def test_no_product_of_the_inverse_streams_the_whole_chunk(chunk):
    """Levels 2 and 3 stream one tile of 8 rows, level ``l >= 4`` the
    ``2^(l-1)`` rows of the upper halves: never the chunk's ``C`` rows (the one
    float32 product of a chunk that does is the inverse times the values)."""
    a, level = jax.ShapeDtypeStruct((chunk, chunk), jnp.float32), jnp.asarray(decay_plan(chunk)[1])
    rows = [rows for rows, is_f32, _ in products_of(jax.make_jaxpr(block_inverse)(a, level).jaxpr) if is_f32]
    assert rows == [8] * 4 + [1 << lv - 1 for lv in range(4, chunk.bit_length()) for _ in range(2)]
    assert max(rows) <= chunk // 2
    if chunk == 128:
        whole = [rows for rows, is_f32, _ in products_of(traced_chunk()) if is_f32 and rows >= chunk]
        assert whole == [chunk]
