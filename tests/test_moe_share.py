"""``models/moe_share.py`` between the families that share it: the routed sum a
caller's own router reaches through ``_routed`` is, bit for bit on the CPU,
what ``_moe`` computed inline before the two were parted, for the two
sigmoid-routed families at their small presets; ``_moe`` itself is that sum
beside the residual and the shared expert; an index outside the held experts
is no work whatever it stands for; and the checks each kind of configuration
asks for are the ones it needs."""

from __future__ import annotations

import dataclasses
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cuda_mpi_gpu_cluster_programming_tpu.models import kda_moe, mla_moe, moe_share

FAMILIES = {"mla_moe": mla_moe, "kda_moe": kda_moe}


def _moe_layer(model, seed: int):
    """One MoE sublayer's parameters as ``_moe`` takes them, and normed-sized
    tokens on the residual stream, at the family's small preset."""
    cfg = model.SMALL
    params = model.init(jax.random.key(seed), cfg, jnp.float32)
    layer = next(p for p in params["layers"] if "moe" in p)
    p = {**layer["moe"], "ffn_norm": layer["ffn_norm"]}
    h = jax.random.normal(jax.random.key(seed + 1), (2, 24, cfg.hidden_size), jnp.float32)
    return cfg, p, h


def _routed_as_before(p, u, chosen, weights, cfg):
    """The routed sum as ``_moe`` computed it inline before ``_routed`` was
    parted from it: the dispatch, then the held experts' weighted sum."""
    dispatch = moe_share._dispatch(chosen, cfg)
    return moe_share._routed_experts(p["experts"], u, weights, dispatch, cfg), dispatch[1]


@pytest.mark.parametrize("family", sorted(FAMILIES))
@pytest.mark.parametrize("seed", [0, 3])
def test_the_routed_sum_is_bitwise_what_it_was_before_the_router_was_parted_from_it(family, seed):
    cfg, p, h = _moe_layer(FAMILIES[family], seed)
    flat = h.reshape(-1, h.shape[-1])
    u = moe_share._rms_norm(flat, p["ffn_norm"], cfg.rms_norm_eps).astype(p["router"].dtype)
    chosen, weights = moe_share.route(p, u, cfg)
    before, sizes_before = _routed_as_before(p, u, chosen, weights, cfg)
    after, sizes_after = moe_share._routed(p["experts"], u, chosen, weights, cfg)
    assert np.array_equal(np.asarray(before), np.asarray(after)) and np.abs(np.asarray(after)).max() > 0
    assert np.array_equal(np.asarray(sizes_before), np.asarray(sizes_after)) and int(sizes_after.sum()) > 0
    # and the sublayer is that sum beside the residual and the shared expert, with the same pair counts
    out, sizes = moe_share._moe(p, h, cfg, with_sizes=True)
    want = (flat + before + moe_share._swiglu(p["shared"], u)).reshape(h.shape)
    assert np.array_equal(np.asarray(out), np.asarray(want))
    assert np.array_equal(np.asarray(sizes), np.asarray(sizes_after))


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_an_index_outside_the_held_experts_is_a_pair_of_no_work(family):
    """Another chip's expert and an output past every expert (a router's
    "no expert") alike: no row, no count, a zero sum."""
    cfg, p, h = _moe_layer(FAMILIES[family], 5)
    u = h.reshape(-1, h.shape[-1])
    k = cfg.num_experts_per_tok
    absent = [cfg.n_routed_experts]  # past every expert
    if cfg.experts_first + cfg.experts_held < cfg.n_routed_experts:
        absent.append(cfg.experts_first + cfg.experts_held)  # the next chip's first
    for index in absent:
        chosen = jnp.full((u.shape[0], k), index, jnp.int32)
        routed, sizes = moe_share._routed(p["experts"], u, chosen, jnp.ones((u.shape[0], k)), cfg)
        assert not np.asarray(routed).any() and int(sizes.sum()) == 0


def test_each_kind_of_configuration_is_asked_for_what_it_needs():
    """``check_share`` (the routed sum alone) wants the held experts inside
    the router's width and whole tiles; ``check_sigmoid_moe`` wants whole
    groups and the one shared expert besides."""
    routed_only = types.SimpleNamespace(
        n_routed_experts=4, num_experts_per_tok=1, experts_held=2, experts_first=0,
        expert_tile_rows=8, expert_chunk_rows=16, expert_span_rows=32,
    )
    moe_share.check_share(routed_only)  # no group, no shared expert: nothing to say of them
    with pytest.raises(AttributeError):
        moe_share.check_sigmoid_moe(routed_only)
    for bad in (dict(experts_first=3), dict(expert_chunk_rows=12), dict(expert_span_rows=24)):
        with pytest.raises(ValueError):
            moe_share.check_share(types.SimpleNamespace(**{**vars(routed_only), **bad}))
    for model in FAMILIES.values():
        with pytest.raises(ValueError):
            dataclasses.replace(model.SMALL, n_shared_experts=0)


def test_balanced_bias_takes_the_routing_function_it_balances():
    """Any router's choice under a bias: here a top-1 over five outputs whose
    scores favour the first; the balanced bias evens the load and has one
    entry per output."""
    scores = jax.random.normal(jax.random.key(0), (512, 5)) + jnp.array([1.0, 0.0, 0.0, 0.0, -1.0])
    choose = lambda bias: jnp.argmax(scores + bias, axis=-1)[:, None]
    load = lambda bias: np.bincount(np.asarray(choose(bias))[:, 0], minlength=5)
    start = jnp.zeros((5,), jnp.float32)
    balanced = moe_share.balanced_bias(start, choose, rounds=48, step=0.05)
    assert balanced.shape == (5,) and balanced.dtype == start.dtype
    assert load(start).max() / load(start).mean() > 1.8
    assert load(balanced).max() / load(balanced).mean() < 1.25


def _share(tile: int, held: int = 3, k: int = 2):
    return types.SimpleNamespace(
        n_routed_experts=held + 2, num_experts_per_tok=k, experts_held=held, experts_first=1,
        expert_tile_rows=tile, expert_chunk_rows=2 * tile, expert_span_rows=4 * tile,
    )


@pytest.mark.parametrize(
    "loads,tile,want",
    [
        ([[1, 256, 257]], 256, 1024),  # 256 + 256 + 512: a pair past a whole tile costs a tile
        ([[1, 256, 257], [300, 0, 5]], 256, 1024 + 768),  # layer by layer, not of the layers' sum
        ([[1, 256, 257]], 320, 960),
        ([[0, 0, 0]], 256, 0),  # an expert nobody chose pads nothing
        ([], 256, 0),  # no MoE layer at all
    ],
)
def test_the_padded_rows_gauge_is_the_tile_arithmetic_of_the_loads(loads, tile, want):
    """``moe.rows_padded`` rounds every held expert's pairs of every layer up
    to whole tiles; the three gauges beside it read as they did."""
    from cuda_mpi_gpu_cluster_programming_tpu.observability import metrics

    cfg, tokens = _share(tile), 400
    stats = moe_share.set_routing_gauges([np.asarray(load) for load in loads], tokens, cfg)
    held = np.sum(loads, axis=0) if loads else np.zeros(3)
    assert stats == {
        metrics.MOE_PAIRS_HELD: float(held.sum()),
        metrics.MOE_PAIRS_ALL: float(len(loads) * tokens * cfg.num_experts_per_tok),
        metrics.MOE_EXPERT_LOAD_MAX_OVER_MEAN: float(held.max() / held.mean()) if held.sum() else 0.0,
        metrics.MOE_ROWS_PADDED: float(want),
    }
    assert tuple(stats) == metrics.MOE_ROUTING_GAUGES
    summary = metrics.registry().summary()
    assert {name: summary[name] for name in metrics.MOE_ROUTING_GAUGES} == stats


@pytest.mark.parametrize("tile", [8, 16])
@pytest.mark.parametrize("seed", [0, 1])
def test_the_padded_rows_gauge_is_what_the_dispatch_pads_to(tile, seed):
    """From the held experts' pair counts alone the gauge is ``_dispatch``'s
    ``pad_end[-1]``, the rows the grouped products run."""
    from cuda_mpi_gpu_cluster_programming_tpu.observability import metrics

    cfg = _share(tile)
    chosen = jax.random.randint(jax.random.key(seed), (50, cfg.num_experts_per_tok), 0, cfg.n_routed_experts)
    _order, sizes, _start, _pad_start, pad_end, row = moe_share._dispatch(chosen, cfg)
    stats = moe_share.set_routing_gauges([np.asarray(sizes)], chosen.shape[0], cfg)
    assert stats[metrics.MOE_ROWS_PADDED] == float(pad_end[-1]) >= stats[metrics.MOE_PAIRS_HELD] > 0
    assert int(row.max()) < int(pad_end[-1])
