"""The short convolution's kernel (``ops/kda_mix.py``) on the CPU, interpreted:
against ``_conv_mix_plain`` (``_l2norm(silu(_short_conv(...)))``) of ``models/kda_moe.py`` with and
without the l2norm, stored in float32 and in bf16, over sequences of one tile,
of several and of a tile and a bit (the history across a tile's edge), two
sequences a batch (the second sees zero history, not the first's tail), more
heads than a program holds, and filters that differ from head to head; the
shape rule that chooses between the kernel and the ``jax.numpy`` form; and the
model's forward with the rule forced off, with the gauge that counts the
layers that took the kernel."""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cuda_mpi_gpu_cluster_programming_tpu.models import kda_moe
from cuda_mpi_gpu_cluster_programming_tpu.ops import kda_mix


def operands(seed, b, h, seq, e, taps=4):
    kx, kt = jax.random.split(jax.random.key(seed))
    # filters of the scale ``init`` draws (fan_in**-0.5), another for every head and channel
    return jax.random.normal(kx, (b, h, seq, e), jnp.float32), 0.5 * jax.random.normal(kt, (taps, h, e), jnp.float32)


def bf16_places_apart(got, want) -> np.ndarray:
    """The distance of every element in last places of bf16 (8 bits of
    significand) at the reference's magnitude, or at 1e-3 where the terms
    cancel to less (the sum keeps the float32 error of its terms)."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    place = 2.0 ** (np.floor(np.log2(np.maximum(np.abs(want), 1e-3))) - 7)
    return np.abs(got - want) / place


# under blocks of 64 rows of 128: one tile; two; five tiles of 16 (80 has no larger divisor: the
# history block is every second 8-row block); six heads of a whole sequence where a block holds four
# (two programs of three); 256 channels (two registers a row, tiles of 32)
CASES = {
    "one_tile": dict(b=2, h=3, seq=64, e=128, heads=1),
    "two_tiles": dict(b=2, h=4, seq=128, e=128, heads=1),
    "tile_and_a_bit": dict(b=2, h=2, seq=80, e=128, heads=1),
    "heads_over_a_program": dict(b=2, h=6, seq=64, e=128, heads=4),
    "two_registers_a_row": dict(b=2, h=2, seq=128, e=256, heads=1),
}
TILE = 64 * 128


@pytest.mark.parametrize("out_dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bf16"])
@pytest.mark.parametrize("l2norm", [True, False], ids=["l2norm", "plain"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_the_kernel_agrees_with_the_jax_numpy_form(case, l2norm, out_dtype):
    shape = dict(CASES[case])
    heads = shape.pop("heads")
    x, taps = operands(len(case), **shape)
    got = kda_mix.short_conv_mix(x, taps, l2norm=l2norm, out_dtype=out_dtype, block_elements=heads * TILE, rows=16)
    want = kda_moe._conv_mix_plain(x, taps, out_dtype, l2norm=l2norm)
    assert got.shape == x.shape and got.dtype == out_dtype
    # the same terms in the same order: what differs is the CPU's contraction of a multiply and an
    # add into one rounding, a few last places in float32 and one place of a few elements in bf16
    if out_dtype == jnp.float32:
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-6, atol=2e-6)
    else:
        apart = bf16_places_apart(got, want)
        assert apart.max() <= 1.0 and (apart > 0).mean() < 1e-3


def test_a_sequence_starts_from_zero_history_whatever_stands_before_it():
    """The second sequence of a batch, and the second head, give what they
    give alone: no token of what lies before them in memory reaches their
    first three outputs."""
    x, taps = operands(3, 2, 2, 128, 128)
    mix = functools.partial(kda_mix.short_conv_mix, l2norm=True, out_dtype=jnp.float32, block_elements=TILE, rows=16)
    whole = np.asarray(mix(x, taps))
    for b in range(2):
        for h in range(2):
            alone = np.asarray(mix(x[b : b + 1, h : h + 1], taps[:, h : h + 1]))
            np.testing.assert_array_equal(whole[b, h], alone[0, 0])
    # and the history is the tile's own past: a change in a tile's last three tokens moves the next tile's first three
    moved = np.asarray(mix(x.at[:, :, 61:64].add(1.0), taps))
    assert np.array_equal(moved[:, :, :61], whole[:, :, :61]) and np.array_equal(moved[:, :, 67:], whole[:, :, 67:])
    assert not np.allclose(moved[:, :, 64:67], whole[:, :, 64:67], atol=1e-3)


@pytest.mark.parametrize(
    "seq, channels, taps, takes",
    [(8192, 128, 4, True), (64, 128, 4, True), (64, 16, 4, False), (72, 128, 4, False), (64, 192, 4, False),
     (64, 128, 9, True), (64, 128, 10, False)],
)  # fmt: skip
def test_the_rule_reads_the_shapes_alone(seq, channels, taps, takes):
    assert kda_mix.fits(seq, channels, taps) is takes
    x, filters = operands(5, 1, 2, seq, channels, taps)
    if not takes:
        with pytest.raises(ValueError, match="do not fit"):
            kda_mix.short_conv_mix(x, filters, l2norm=True, out_dtype=jnp.float32)
    # the model's mix takes whichever form the rule names and gives the same numbers
    got = kda_moe._conv_mix(x, filters, jnp.float32, l2norm=True)
    np.testing.assert_allclose(got, kda_moe._conv_mix_plain(x, filters, jnp.float32, l2norm=True), rtol=2e-6, atol=2e-6)
    text = str(jax.make_jaxpr(lambda a, w: kda_moe._conv_mix(a, w, jnp.bfloat16, l2norm=False))(x, filters))
    assert ("name=kda_short_conv_mix" in text) is takes


def test_the_forward_with_the_kernel_equals_the_forward_without(monkeypatch):
    """``linear_attn_head_dim = 128`` at a tiny size: the three linear layers
    take the kernel; with the rule forced off (here, not by an option) they
    take the ``jax.numpy`` form, and the logits agree as tightly as the
    program agrees with its reference (``tests/test_kda_moe.py``)."""
    cfg = dataclasses.replace(kda_moe.SMALL, linear_attn_num_heads=2, linear_attn_head_dim=128)
    params = kda_moe.init(jax.random.key(4), cfg, jnp.float32)
    ids = jax.random.randint(jax.random.key(5), (2, 64), 0, cfg.vocab_size, jnp.int32)
    forward = lambda: jax.jit(lambda p, i: kda_moe.forward(p, i, cfg))
    assert str(jax.make_jaxpr(forward())(params, ids)).count("name=kda_short_conv_mix") == 3 * 3
    fused = np.asarray(forward()(params, ids))
    assert kda_moe.layer_statistics(params, ids, cfg)["kda.mix_fused_layers"] == 3  # the gauge says it engaged
    monkeypatch.setattr(kda_mix, "fits", lambda *shape: False)
    assert "kda_short_conv_mix" not in str(jax.make_jaxpr(forward())(params, ids))
    unfused = np.asarray(forward()(params, ids))
    assert np.abs(fused - unfused).max() / np.abs(unfused).max() < 1e-5
