"""``ops.moe_combine``: the Pallas combine of the expert tier against the gather
form it replaced (``moe_combine_reference``, plain ``jax.numpy``), interpreted
on the CPU.

Two kinds of agreement. With weights that are powers of two every product is
exact, so the sums are equal bit for bit exactly when the places are added in
the same order: that is the claim "a token's places are summed in place
order". With any weights the CPU backend is free to contract a multiply and
an add into one rounding, and does so differently in the two programs: there
the two agree to float32 rounding (1e-6 of the largest output). On the chip,
whose vector unit has no fused multiply-add, ``scripts/moe_combine_ab.py``
compares them bit for bit at the published shapes.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cuda_mpi_gpu_cluster_programming_tpu.ops.moe_combine import (
    moe_combine,
    moe_combine_reference,
    row_slab,
    worth_a_kernel,
)


def operands(seed, tokens, k, d, span, base, *, dtype=jnp.bfloat16, held=0.4, exact=False):
    """Rows, a table whose places are held with probability ``held`` and point
    anywhere from half a span below ``base`` to half a span past its end (so
    some are in the span, some under it, some past it), weights, and a ``y``
    that is not zero. ``exact``: weights are powers of two."""
    keys = jax.random.split(jax.random.key(seed), 5)
    results = jax.random.normal(keys[0], (span, d), jnp.float32).astype(dtype)
    row = jax.random.randint(keys[1], (tokens, k), base - span // 2, base + span + span // 2)
    row = jnp.where(jax.random.uniform(keys[2], (tokens, k)) < held, jnp.maximum(row, 0), -1).astype(jnp.int32)
    weights = jax.random.uniform(keys[3], (tokens, k), jnp.float32, 0.05, 1.0)
    if exact:
        weights = jnp.exp2(jnp.floor(jnp.log2(weights)))
    y = jax.random.normal(keys[4], (tokens, d), jnp.float32)
    return results, row, weights, y


def close(got, want):
    got, want = np.asarray(got), np.asarray(want)
    return np.abs(got - want).max() <= 1e-6 * np.abs(want).max()


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bf16", "f32"])
@pytest.mark.parametrize("k", [1, 4, 8])
def test_the_kernel_sums_a_tokens_places_in_place_order(k, dtype):
    """Exact products: bit for bit the gather form, which adds place 0 first."""
    results, row, weights, y = operands(k, 48, k, 256, 64, 0, dtype=dtype, exact=True)
    got = moe_combine(results, row, weights, y, 0)
    want = moe_combine_reference(results, row, weights, y, 0)
    assert got.shape == y.shape and got.dtype == jnp.float32
    assert np.array_equal(np.asarray(got), np.asarray(want))
    assert not np.array_equal(np.asarray(got), np.asarray(y))  # something was held


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bf16", "f32"])
@pytest.mark.parametrize("k", [1, 4, 8])
def test_the_kernel_is_the_gather_form_to_float32_rounding(k, dtype):
    results, row, weights, y = operands(10 + k, 64, k, 128, 40, 0, dtype=dtype)
    assert close(moe_combine(results, row, weights, y, 0), moe_combine_reference(results, row, weights, y, 0))


@pytest.mark.parametrize("k", [1, 8])
def test_no_place_held_returns_y_as_given(k):
    results, _row, weights, y = operands(2, 32, k, 128, 16, 0)
    for absent in (jnp.full((32, k), -1, jnp.int32), jnp.full((32, k), 16, jnp.int32)):
        got = moe_combine(results, absent, weights, y, 0)
        assert np.array_equal(np.asarray(got), np.asarray(y))
    zero = moe_combine(results, jnp.full((32, k), -1, jnp.int32), weights, jnp.zeros_like(y), 0)
    assert not np.asarray(zero).any()


@pytest.mark.parametrize("k", [4, 8])
def test_one_token_with_every_place_held(k):
    """One token collects k rows, every other token none; more rows wait than
    the ring has slots, so the oldest is landed before its slot is taken."""
    results, _row, weights, y = operands(3, 24, k, 128, 32, 0, dtype=jnp.float32, exact=True)
    row = jnp.full((24, k), -1, jnp.int32).at[5].set(jnp.arange(k, dtype=jnp.int32) * 3)
    got = np.asarray(moe_combine(results, row, weights, y, 0, slots=2))
    want = np.asarray(y).copy()
    for place in range(k):
        want[5] = want[5] + np.asarray(weights)[5, place] * np.asarray(results)[3 * place]
    assert np.array_equal(got, want)
    assert np.array_equal(got, np.asarray(moe_combine(results, row, weights, y, 0)))  # whatever the ring


@pytest.mark.parametrize("base", [40, 80])
def test_a_later_span_adds_only_its_own_rows(base):
    """``base > 0``: a place under the span or past its end is no work, one
    inside it reads ``results[row - base]``."""
    results, row, weights, y = operands(4, 48, 4, 128, 40, base, exact=True)
    table = np.asarray(row)
    inside = (table >= base) & (table < base + 40)
    assert inside.any() and (table >= base + 40).any() and ((table < base) & (table >= 0)).any()
    got = np.asarray(moe_combine(results, row, weights, y, jnp.int32(base)))
    assert np.array_equal(got, np.asarray(moe_combine_reference(results, row, weights, y, base)))
    untouched = ~inside.any(axis=1)
    assert untouched.any() and np.array_equal(got[untouched], np.asarray(y)[untouched])


def test_two_spans_one_after_the_other_are_the_whole_sum():
    """What ``_routed_experts`` does: the spans' results in turn, ``y`` handed
    on. Span by span is the gather form span by span, bit for bit, and the
    whole sum in another order."""
    whole, row, weights, y = operands(5, 32, 4, 128, 64, 0, held=0.7, exact=True)
    row = jnp.where(row >= 0, row % 64, -1)
    first = moe_combine(whole[:32], row, weights, y, 0)
    both = moe_combine(whole[32:], row, weights, first, 32)
    want = moe_combine_reference(whole[32:], row, weights, moe_combine_reference(whole[:32], row, weights, y, 0), 32)
    assert np.array_equal(np.asarray(both), np.asarray(want))
    assert close(both, moe_combine_reference(whole, row, weights, y, 0))


@pytest.mark.parametrize("block_elements", [8 * 128, 256 * 128, 1024 * 128])
def test_a_row_wider_than_a_block_is_taken_tile_by_tile(block_elements):
    """2,048 wide = 16 sublanes of 128 lanes. The smallest block holds eight
    tokens of eight sublanes, so the grid grows a second axis and a row is two
    DMAs; the middle one whole rows of 16 tokens (four token blocks share the
    one block of SMEM); the largest all 64 tokens at once."""
    results, row, weights, y = operands(6, 64, 4, 2048, 32, 0, exact=True)
    got = moe_combine(results, row, weights, y, 0, block_elements=block_elements)
    assert np.array_equal(np.asarray(got), np.asarray(moe_combine_reference(results, row, weights, y, 0)))


@pytest.mark.parametrize("d", [64, 96, 128, 384])
def test_rows_as_slabs(d):
    """``row_slab`` gives whole lanes where the width divides into them, one
    sublane else; the 2-D call is the slab call reshaped."""
    slab = row_slab(d)
    assert slab == ((d // 128, 128) if d % 128 == 0 else (1, d)) and slab[0] * slab[1] == d
    results, row, weights, y = operands(7, 16, 2, d, 24, 0, dtype=jnp.float32, exact=True)
    flat = moe_combine(results, row, weights, y, 0)
    slabs = moe_combine(results.reshape(24, *slab), row, weights, y.reshape(16, *slab), 0)
    assert slabs.shape == (16, *slab) and np.array_equal(np.asarray(slabs).reshape(16, d), np.asarray(flat))
    assert np.array_equal(np.asarray(flat), np.asarray(moe_combine_reference(results, row, weights, y, 0)))
    want = moe_combine_reference(results.reshape(24, *slab), row, weights, y.reshape(16, *slab), 0)
    assert np.array_equal(np.asarray(slabs), np.asarray(want))  # the oracle takes slabs too


def test_operands_that_do_not_fit_are_refused():
    results, row, weights, y = operands(8, 16, 2, 128, 8, 0)
    with pytest.raises(ValueError):
        moe_combine(results, row, weights, y[:8], 0)
    with pytest.raises(ValueError):
        moe_combine(results, row, weights[:, :1], y, 0)
    with pytest.raises(ValueError):
        moe_combine(results, row, weights, y.astype(jnp.bfloat16), 0)


def _routed_jaxpr(tokens=48):
    from cuda_mpi_gpu_cluster_programming_tpu.models import mla_moe, moe_share

    cfg = mla_moe.SMALL
    params = mla_moe.init(jax.random.key(0), cfg, jnp.float32)
    layer = next(p for p in params["layers"] if "moe" in p)
    u = jax.random.normal(jax.random.key(1), (tokens, cfg.hidden_size), jnp.float32)
    chosen, weights = moe_share.route(layer["moe"], u, cfg)
    text = str(jax.make_jaxpr(lambda: moe_share._routed(layer["moe"]["experts"], u, chosen, weights, cfg))())
    every_token = f"f32[{tokens},{cfg.hidden_size}]"
    wide = [ln for ln in text.splitlines() if " gather[" in ln and every_token in ln.split("=")[0]]
    return text, wide, cfg


def test_the_routed_sum_runs_the_kernel_once_a_span_and_gathers_no_results(monkeypatch):
    """Where the shapes say the kernel is worth it (steered here: the small
    preset's are not), ``_routed_experts``' combine is the kernel: one
    ``moe_combine`` in the traced program, no gather over every token (the
    one gather left is the dispatch's ``u[pair // k]``, a chunk at a time)."""
    from cuda_mpi_gpu_cluster_programming_tpu.models import moe_share

    monkeypatch.setattr(moe_share, "worth_a_kernel", lambda *shape: True)
    text, wide, _cfg = _routed_jaxpr()
    assert text.count("name=moe_combine") == 1 and text.count("name=grouped_matmul") == 3
    assert wide == []


def test_the_routed_sum_keeps_the_gathers_where_rows_are_narrow_and_places_few():
    """By the rule the small preset (64 wide, 4 places, spans of 32 rows)
    gathers, one gather over every token per place, and runs no kernel; the
    published shapes divide as the chip measured them (PR 34)."""
    text, wide, cfg = _routed_jaxpr()
    assert "name=moe_combine" not in text and len(wide) == cfg.num_experts_per_tok
    assert worth_a_kernel(8192, 8, 7168, 8192) and worth_a_kernel(16384, 8, 4096, 24576)  # dots, solar
    assert not worth_a_kernel(4096, 1, 2048, 3840)  # zaya: one place a token, rows of 4 KB
    assert not worth_a_kernel(4096, 4, 2048, 3840)  # and the same rows at four places: 0.38 ms against 0.26


@pytest.mark.parametrize("family", ["mla_moe", "kda_moe", "cca_moe"])
def test_a_models_logits_are_the_same_by_either_combine(family, monkeypatch):
    """The whole small model, the routed sums by the gathers (the rule's
    choice there) and by the kernel (steered): the same logits to float32
    rounding; ``cca_moe`` reaches the kernel inside its scan over the layers,
    every layer's experts from one stack (``group_base``)."""
    import importlib

    from cuda_mpi_gpu_cluster_programming_tpu.models import moe_share

    model = importlib.import_module(f"cuda_mpi_gpu_cluster_programming_tpu.models.{family}")
    cfg, batch, seq = model.PRESETS["small"]
    params = model.init(jax.random.key(3), cfg, jnp.float32)
    ids = jax.random.randint(jax.random.key(4), (batch, seq), 0, cfg.vocab_size)
    assert not worth_a_kernel(batch * seq, cfg.num_experts_per_tok, cfg.hidden_size, cfg.expert_span_rows)
    want = np.asarray(model.forward(params, ids, cfg))
    monkeypatch.setattr(moe_share, "worth_a_kernel", lambda *shape: True)
    got = np.asarray(model.forward(params, ids, cfg))
    assert np.abs(got - want).max() <= 1e-5 * np.abs(want).max() and np.abs(want).max() > 0
