"""The compressed-convolutional-attention mixture-of-experts decoder
(``models/cca_moe.py``) at the small preset on the CPU: the program's forward
against the benchmark's plain reference on seeded weights, in float32 and bf16;
causality of the convolutions and of the value shift; each piece alone against
the page; a skipped token; the chip's share (the 2 shares of one MoE sublayer
add up to the uncut layer's routed sum); the parameter count against the
benchmark's shape functions; the statistics that fill the gauges; the
balancing of the routers; the way through ``build_forward`` and ``run.py``."""

from __future__ import annotations

import dataclasses
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[1]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from benchmark.reference import cca_moe as reference  # noqa: E402
from benchmark.shapes import cca_moe as shapes  # noqa: E402
from cuda_mpi_gpu_cluster_programming_tpu.configs import REGISTRY, build_forward  # noqa: E402
from cuda_mpi_gpu_cluster_programming_tpu.models import cca_moe, moe_share  # noqa: E402
from cuda_mpi_gpu_cluster_programming_tpu.ops import scopes  # noqa: E402

SMALL = cca_moe.SMALL  # hidden 64, 4 query heads of 16 over 2 key/value heads, 4 experts + skip, 2 held, 4 layers


def file_config(c: cca_moe.CcaMoeConfig) -> dict:
    """What a configuration file says of ``c``: the reference and the shape
    functions read the publisher's keys, not the program's object."""
    return dict(
        hidden_size=c.hidden_size, num_attention_heads=c.num_attention_heads,
        num_key_value_heads=c.num_key_value_heads, head_dim=c.head_dim, cca_time0=c.cca_time0, cca_time1=c.cca_time1,
        partial_rotary_factor=c.partial_rotary_factor,
        rope_parameters=dict(hybrid=dict(rope_theta=c.rope_theta, partial_rotary_factor=c.partial_rotary_factor)),
        rms_norm_eps=c.rms_norm_eps, num_layers=c.num_layers, moe_intermediate_size=c.moe_intermediate_size,
        num_experts=c.experts_held, experts_first=c.experts_first, published=dict(num_experts=c.num_experts),
        num_experts_per_tok=c.num_experts_per_tok, router_hidden_size=c.router_hidden_size,
        vocab_size=c.vocab_size, compute="bf16", seq_len=64,
    )


def ids_for(c, seed=1, shape=(2, 64)):
    return jax.random.randint(jax.random.key(seed), shape, 0, c.vocab_size, jnp.int32)


def rel_err(got, want) -> float:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.abs(got - want).max() / np.abs(want).max())


def layer_of(params, i):
    return jax.tree.map(lambda leaf: leaf[i], params["layers"])


@pytest.fixture(scope="module")
def fwd32():
    """The float32 forward, built once: every test that runs it shares its compilation."""
    return build_forward(REGISTRY["v10_cca_moe"], SMALL)


@pytest.fixture(scope="module")
def fwd16():
    return build_forward(REGISTRY["v10_cca_moe"], SMALL, compute="bf16")


@pytest.fixture(scope="module")
def params32():
    return cca_moe.init(jax.random.key(2), SMALL, jnp.float32)


@pytest.fixture(scope="module")
def params16():
    return cca_moe.init(jax.random.key(1), SMALL, jnp.bfloat16)


# ---- the forward against the plain reference --------------------------------


@pytest.mark.parametrize("seed", [0, 1])
def test_float32_forward_agrees_with_the_reference_tightly(seed, fwd32):
    """One scan over stacked layers against a Python loop with the router state
    passed along, the flash kernel with shared key/value heads against
    materialised scores, shifted multiply-adds against padded windows, the
    grouped product over the stack against one expert at a time: parts in a
    million."""
    params = cca_moe.init(jax.random.key(seed), SMALL, jnp.float32)
    ids = ids_for(SMALL, seed + 10)
    got = fwd32(params, ids)
    want, _slack, pairs = reference.forward_checked(file_config(SMALL), params, ids)
    assert got.shape == (2, 64, SMALL.vocab_size) and got.dtype == jnp.float32
    assert rel_err(got, want) < 1e-5 and 0 < pairs < ids.size * SMALL.num_layers


def test_bf16_forward_agrees_under_the_stated_tolerance(fwd16, params16):
    """bf16 operands, float32 accumulation, at this toy width (a 64-wide norm
    rounds to a part in a hundred): over the tokens the reference finds far
    from a routing tie, the typical token within 3% of the largest logit,
    their rms within 5%, none beyond 15%; and visibly not float32."""
    params, ids = params16, ids_for(SMALL, 4)
    got = np.asarray(fwd16(params, ids))
    want, slack, _pairs = reference.forward_checked(file_config(SMALL), params, ids)
    want, clear = np.asarray(want), np.asarray(slack) >= 0.005
    assert got.dtype == np.float32 and clear.mean() > 0.1
    err = np.abs(got - want).max(axis=-1) / np.abs(want).max()
    assert 1e-4 < np.median(err[clear]) < 0.03 and err[clear].max() < 0.15
    assert np.sqrt(np.mean((got[clear] - want[clear]) ** 2) / np.mean(want[clear] ** 2)) < 0.05


def test_logits_do_not_look_ahead(fwd32, params32):
    ids = ids_for(SMALL, 6)
    first, second = np.asarray(fwd32(params32, ids)), np.asarray(fwd32(params32, ids.at[:, 40:].set(7)))
    # to rounding, not bitwise: the routed pairs of the whole batch are sorted and summed together
    np.testing.assert_allclose(first[:, :40], second[:, :40], rtol=1e-5, atol=1e-5)
    assert not np.allclose(first[:, 40:], second[:, 40:], atol=1e-2)


def test_a_change_at_token_t_moves_nothing_before_it_and_the_next_tokens_shifted_value(params32):
    """The convolutions and the value shift read the past only: a change of
    the latent at token ``t`` leaves ``q, k, v`` before ``t`` as they were,
    moves them at ``t``, and reaches the tokens after through the first tap of
    each convolution (two of them in series: two tokens on, no further) and
    through value head 1, which at ``t + 1`` IS token ``t``'s."""
    p, t = layer_of(params32, 0), 20
    key = jax.random.split(jax.random.key(0), 4)
    q_lat = jax.random.normal(key[0], (1, 4, 32, 16))
    k_lat = jax.random.normal(key[1], (1, 2, 32, 16))
    v1, v2 = jax.random.normal(key[2], (1, 32, 16)), jax.random.normal(key[3], (1, 32, 16))
    tables = cca_moe._rope_tables(32, SMALL)
    base = cca_moe._mix(p, q_lat, k_lat, v1, v2, tables, SMALL)
    moved = cca_moe._mix(
        p, q_lat.at[:, :, t].add(1.0), k_lat.at[:, :, t].add(1.0), v1, v2.at[:, t].add(1.0), tables, SMALL
    )
    for a, b in zip(base, moved):
        assert np.array_equal(a[:, :, :t], b[:, :, :t])
        assert np.array_equal(a[:, :, t + 3 :], b[:, :, t + 3 :])  # two taps twice: no further than two tokens on
    q, k, v = (np.abs(np.asarray(b - a)) for a, b in zip(base, moved))
    assert q[:, :, t].min(axis=-1).max() > 0 and q[:, :, t + 1].max() > 0 and k[:, :, t + 1].max() > 0
    assert v[:, 0].max() == 0  # head 0 is the token's own W_v1 row, which did not move
    assert v[0, 1, t].max() == 0 and np.allclose(v[0, 1, t + 1], 1.0)  # head 1 at t + 1 is token t's W_v2 row
    assert np.array_equal(base[2][0, 1, 0], np.zeros(16)) and np.array_equal(base[2][0, 1, 1:], v2[0, :-1])


# ---- each piece alone against the page --------------------------------------


def test_the_two_convolutions_put_the_last_tap_on_the_token_itself():
    c = jax.random.normal(jax.random.key(0), (1, 3, 8, 4))
    a, a_b = jax.random.normal(jax.random.key(1), (2, 3, 4)), jax.random.normal(jax.random.key(2), (3, 4))
    m, m_b = jax.random.normal(jax.random.key(3), (2, 3, 4, 4)), jax.random.normal(jax.random.key(4), (3, 4))
    cn, an, mn = np.asarray(c), np.asarray(a), np.asarray(m)
    depthwise, mixing = np.zeros_like(cn), np.zeros_like(cn)
    for t in range(8):
        depthwise[:, :, t] = an[1] * cn[:, :, t] + np.asarray(a_b) + (an[0] * cn[:, :, t - 1] if t else 0.0)
        for h in range(3):
            mixing[0, h, t] = cn[0, h, t] @ mn[1, h] + np.asarray(m_b)[h] + (cn[0, h, t - 1] @ mn[0, h] if t else 0.0)
    np.testing.assert_allclose(np.asarray(cca_moe._depthwise_conv(c, a, a_b)), depthwise, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(cca_moe._head_conv(c, m, m_b)), mixing, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(reference.causal_conv(c, a, a_b, False)), depthwise, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(reference.causal_conv(c, m, m_b, True)), mixing, rtol=1e-5, atol=1e-5)


def test_the_qk_mean_groups_the_query_heads_of_one_key_head(params32):
    """With both convolutions silenced, ``q`` and ``k`` are the q-k mean alone,
    normalised: query head ``h`` with key head ``h // 2``, key head ``g`` the
    mean over its two query heads."""
    p = dict(layer_of(params32, 0))
    for name in ("conv0", "conv0_b", "conv1", "conv1_b"):
        p[name] = jnp.zeros_like(p[name])
    p["tau"] = jnp.asarray([1.0, 2.0])
    q_lat = jax.random.normal(jax.random.key(0), (1, 4, 8, 16))
    k_lat = jax.random.normal(jax.random.key(1), (1, 2, 8, 16))
    zeros = jnp.zeros((1, 8, 16))
    no_rope = (jnp.ones((8, 4)), jnp.zeros((8, 4)))
    q, k, _v = cca_moe._mix(p, q_lat, k_lat, zeros, zeros, no_rope, SMALL)
    qn, kn = np.asarray(q_lat), np.asarray(k_lat)
    m_q = np.stack([(qn[:, h] + kn[:, h // 2]) / 2 for h in range(4)], axis=1)
    m_k = np.stack([(m_q[:, 0] + m_q[:, 1]) / 2, (m_q[:, 2] + m_q[:, 3]) / 2], axis=1)
    unit = lambda x: 4.0 * x / np.sqrt((x * x).sum(axis=-1, keepdims=True) + 1e-6)  # sqrt(16) x / |x|
    np.testing.assert_allclose(np.asarray(q), unit(m_q), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(k), unit(m_k) * np.asarray([1.0, 2.0])[:, None, None], rtol=1e-5, atol=1e-6)


def test_the_rotary_embedding_touches_the_first_half_of_a_heads_channels_only():
    x = jax.random.normal(jax.random.key(0), (1, 2, 8, 16))
    tables = cca_moe._rope_tables(8, SMALL)
    got = np.asarray(cca_moe._rope(x, tables, SMALL.rotary_dim))
    assert SMALL.rotary_dim == 8 and np.array_equal(got[..., 8:], np.asarray(x)[..., 8:])
    x = np.asarray(x)
    assert np.array_equal(got[:, :, 0], x[:, :, 0]) and not np.allclose(got[:, :, 1:, :8], x[:, :, 1:, :8])
    np.testing.assert_allclose(got, np.asarray(reference.rope(x, SMALL.rope_theta, 8)), rtol=1e-5, atol=1e-6)
    # position t, pair (i, i + 4), angle t * theta^(-2 i / 8): a rotation keeps the pair's length
    pair = lambda a, i: np.hypot(a[..., i], a[..., i + 4])
    np.testing.assert_allclose(pair(got, 1), pair(np.asarray(x), 1), rtol=1e-5)
    angle = 3 * SMALL.rope_theta ** (-2 * 1 / 8)
    x3, g3 = np.asarray(x)[0, 0, 3], got[0, 0, 3]
    assert g3[1] == pytest.approx(x3[1] * np.cos(angle) - x3[5] * np.sin(angle), rel=1e-4, abs=1e-6)


def test_the_router_state_of_a_layer_reaches_the_next_one_un_normed(params32):
    """``r_l = u W_d + b_d + gamma_l r_{l-1}``: with the second layer's gamma
    at 1 and at 0, its state differs by the first layer's state itself, not by
    its normed form."""
    two = dataclasses.replace(SMALL, num_layers=2)
    one = dataclasses.replace(SMALL, num_layers=1)
    # the first layer's down-projection three times as large, so that its state is far from unit rms
    wide = {**params32["layers"], "w_d": params32["layers"]["w_d"].at[0].multiply(3.0)}
    cut = lambda n: {**params32, "layers": jax.tree.map(lambda leaf: leaf[:n], wide)}
    ids = ids_for(SMALL, 8)
    state = lambda p, c: np.asarray(jax.jit(lambda p, i: cca_moe._layers(p, i, c)[1])(p, ids))
    r0 = state(cut(1), one)
    with_gamma = cut(2)
    without = {**with_gamma, "layers": {**with_gamma["layers"], "gamma": with_gamma["layers"]["gamma"].at[1].set(0.0)}}
    np.testing.assert_allclose(state(with_gamma, two) - state(without, two), r0, rtol=1e-4, atol=1e-5)
    assert np.sqrt(np.mean(r0 * r0)) > 2.0  # and r0 is no unit-rms vector
    # one layer's state by the page, from the reference's own router
    p = layer_of(params32, 0)
    u = jax.random.normal(jax.random.key(3), (16, SMALL.hidden_size))
    r_prev = jax.random.normal(jax.random.key(4), (16, SMALL.router_hidden_size))
    r, _chosen, _weights, _slack = reference.route(file_config(SMALL), p, u, r_prev)
    want = np.asarray(u) @ np.asarray(p["w_d"]) + np.asarray(p["b_d"]) + np.asarray(p["gamma"]) * np.asarray(r_prev)
    np.testing.assert_allclose(np.asarray(r), want, rtol=1e-4, atol=1e-5)


def test_a_skipped_tokens_moe_output_is_exactly_the_merge_of_zero(params32):
    p = dict(layer_of(params32, 1))
    experts = p.pop("experts")
    p["bias"] = p["bias"].at[SMALL.skip_index].set(10.0)  # every token's top-1 is the skip output
    x = jax.random.normal(jax.random.key(0), (2, 16, SMALL.hidden_size))
    r = jnp.zeros((32, SMALL.router_hidden_size))
    out, _r, (_bias, chosen, sizes) = jax.jit(lambda p, e, x, r: cca_moe._moe(p, e, x, r, 0, SMALL))(p, experts, x, r)
    assert np.all(np.asarray(chosen) == SMALL.skip_index) and int(sizes.sum()) == 0
    # the routed sum is exactly 0 (no pair was dispatched), so the sublayer is the merge of 0 (fused or not: an ulp)
    routed, _sizes = moe_share._routed(experts, x.reshape(32, -1), chosen[:, None], jnp.ones((32, 1)), SMALL)
    assert not np.asarray(routed).any()
    merged = jax.jit(cca_moe._merge)(p["moe_merge"], x, jnp.zeros_like(x))
    np.testing.assert_allclose(np.asarray(out), np.asarray(merged), rtol=1e-6, atol=1e-6)


# ---- the chip's share --------------------------------------------------------


def test_the_two_shares_of_one_moe_sublayer_add_up_to_the_uncut_layers_routed_sum():
    """Two chips hold two experts each, as the deployment's two hold eight.
    Each routes over all four and the skip and computes its own experts' part;
    their parts are the uncut reference layer's routed sum, and a token that
    skips is in neither."""
    whole = dataclasses.replace(SMALL, experts_held=SMALL.num_experts)
    params = cca_moe.init(jax.random.key(5), whole, jnp.float32)
    p = dict(layer_of(params, 2))
    experts = p.pop("experts")
    x = jax.random.normal(jax.random.key(6), (64, SMALL.hidden_size))
    r_prev = jax.random.normal(jax.random.key(7), (64, SMALL.router_hidden_size))
    u, _r, probs = cca_moe._router(p, x, r_prev, whole)
    chosen, weights = cca_moe._top1(probs, p["bias"])
    parts, pairs = [], 0
    for first in (0, 2):
        share = dataclasses.replace(SMALL, experts_first=first)
        mine = {name: w[first : first + 2] for name, w in experts.items()}
        part, sizes = jax.jit(lambda e, u, c, w, s=share: moe_share._routed(e, u, c, w, s))(mine, u, chosen, weights)
        parts.append(np.asarray(part))
        pairs += int(sizes.sum())
    _r, ref_chosen, ref_weights, _slack = reference.route(file_config(whole), p, u, r_prev)
    uncut, ref_pairs = reference.held_experts(file_config(whole), experts, u, ref_chosen, ref_weights)
    skipped = np.asarray(chosen)[:, 0] == SMALL.skip_index
    assert np.array_equal(np.asarray(chosen), np.asarray(ref_chosen)) and 0 < skipped.sum() < 64
    assert pairs == ref_pairs == 64 - skipped.sum()
    assert rel_err(parts[0] + parts[1], uncut) < 1e-5
    # no share is idle; skips in neither
    assert all(np.abs(part).max() > 0 and not part[skipped].any() for part in parts)
    assert not (np.abs(parts[0]).sum(axis=-1) * np.abs(parts[1]).sum(axis=-1)).any()  # a token has ONE expert


def test_a_layers_experts_are_read_from_the_stack_at_its_own_place():
    """``group_base``: the grouped products of layer ``l`` over the stack of
    every layer's experts give what the layer's own slice gives."""
    params = cca_moe.init(jax.random.key(8), SMALL, jnp.float32)
    experts = params["layers"]["experts"]
    stack = {name: w.reshape(-1, *w.shape[2:]) for name, w in experts.items()}
    u = jax.random.normal(jax.random.key(9), (48, SMALL.hidden_size))
    chosen = jax.random.randint(jax.random.key(10), (48, 1), 0, SMALL.num_experts + 1, jnp.int32)
    weights = jax.random.uniform(jax.random.key(11), (48, 1))
    for layer in (0, 3):
        own = {name: w[layer] for name, w in experts.items()}
        want, sizes = moe_share._routed(own, u, chosen, weights, SMALL)
        got, sizes_stack = jax.jit(
            lambda s, l: moe_share._routed(s, u, chosen, weights, SMALL, group_base=l * SMALL.experts_held)
        )(stack, layer)
        assert np.array_equal(np.asarray(got), np.asarray(want)) and np.array_equal(sizes, sizes_stack)


# ---- parameters ----------------------------------------------------------------


@pytest.mark.parametrize(
    "cfg", [SMALL, dataclasses.replace(SMALL, num_layers=2, experts_held=4, cca_time0=3)], ids=["small", "whole_layer"]
)
def test_parameter_count_is_the_shape_functions_and_the_trees(cfg):
    params = cca_moe.init(jax.random.key(0), cfg)
    leaves = jax.tree.leaves(params)
    assert all(leaf.dtype == jnp.bfloat16 for leaf in leaves)
    assert all(leaf.shape[0] == cfg.num_layers for leaf in jax.tree.leaves(params["layers"]))
    assert sum(leaf.size for leaf in leaves) == cca_moe.param_count(cfg) == shapes.param_count(file_config(cfg))
    assert "head" not in params  # tied: the head is the embedding transposed
    assert set(scopes.CCA_MOE_LAYERS) <= set(scopes.LAYERS) and len(set(scopes.LAYERS)) == len(scopes.LAYERS)


def test_the_seeded_draw_and_the_same_seed_twice(params16):
    again = cca_moe.init(jax.random.key(1), SMALL, jnp.bfloat16)  # the fixture's seed and type
    for mine, theirs in zip(jax.tree.leaves(again), jax.tree.leaves(params16)):
        assert np.array_equal(mine, theirs)
    params = cca_moe.init(jax.random.key(0), SMALL, jnp.float32)
    layers = params["layers"]
    assert not np.array_equal(np.asarray(layers["q"][0]), np.asarray(layers["q"][1]))  # every layer its own draw
    assert np.array_equal(layers["tau"], np.ones((4, 2))) and np.array_equal(layers["gamma"], np.ones((4, 1)))
    assert np.array_equal(layers["attn_norm"], np.ones((4, 64)))
    scale, shift = np.asarray(layers["moe_merge"]["s_h"]), np.asarray(layers["attn_merge"]["b_r"])
    assert abs(scale.mean() - 1.0) < 0.05 and scale.std() == pytest.approx(0.1, rel=0.3)  # 1 + 0.1 n: no identity
    assert abs(shift.mean()) < 0.01 and shift.std() == pytest.approx(0.02, rel=0.3)
    assert np.asarray(layers["conv0"]).std() == pytest.approx(2**-0.5, rel=0.2)  # 2 taps
    assert np.asarray(layers["conv1"]).std() == pytest.approx(32**-0.5, rel=0.2)  # 2 taps x 16 channels
    assert np.asarray(params["embed"]).std() == pytest.approx(1.0, rel=0.1)
    assert 0 < np.abs(np.asarray(layers["bias"])).max() < 0.05 and layers["bias"].shape == (4, 5)


def test_configurations_that_the_layer_cannot_compute_are_refused():
    with pytest.raises(ValueError, match="top-1"):
        dataclasses.replace(SMALL, num_experts_per_tok=2)
    with pytest.raises(ValueError, match="two key/value heads"):
        dataclasses.replace(SMALL, num_key_value_heads=4)
    with pytest.raises(ValueError, match="inside the router's width"):
        dataclasses.replace(SMALL, experts_first=3)  # [3, 5) of 4 experts: the skip is no expert to hold
    with pytest.raises(ValueError, match="even number"):
        dataclasses.replace(SMALL, partial_rotary_factor=0.45)
    assert SMALL.skip_index == SMALL.n_routed_experts == 4 and cca_moe.ZAYA1_EP2_SHARE.skip_index == 16


# ---- statistics ------------------------------------------------------------------


def test_layer_statistics_fill_the_gauges_and_agree_with_the_reference(params32):
    from cuda_mpi_gpu_cluster_programming_tpu.observability import metrics

    ids = ids_for(SMALL, 9)
    metrics.registry().reset()
    stats = cca_moe.layer_statistics(params32, ids, SMALL)
    _logits, _slack, pairs = reference.forward_checked(file_config(SMALL), params32, ids)
    assert stats["moe.pairs_held"] == pairs and stats["moe.expert_load_max_over_mean"] >= 1.0
    assert stats["moe.pairs_all"] == ids.size * SMALL.num_layers  # one pair a token and layer
    assert 0.0 < stats["moe.skip_share"] < 0.6
    assert stats["moe.pairs_held"] <= (1 - stats["moe.skip_share"]) * stats["moe.pairs_all"]
    assert 1.0 < stats["router.state_rms_last"] < 4.0  # four unit-rms projections added up: about 2
    summary = metrics.registry().summary()
    gauges = metrics.MOE_ROUTING_GAUGES + metrics.CCA_GAUGES + (metrics.FLASH_MASKED_SCORE_SHARE,)
    assert {name: summary[name] for name in gauges} == stats


def test_balancing_the_routers_evens_the_outputs_and_moves_only_the_selection_bias(params32):
    """A selection bias that sends most tokens to a few outputs is replaced,
    layer by layer, by one under which every output of the router, the skip
    among them, is chosen alike; nothing else in the tree moves, and the same
    ids give the same bias."""
    skew = jnp.asarray(np.linspace(-0.2, 0.2, SMALL.num_experts + 1), jnp.float32)
    params = {**params32, "layers": {**params32["layers"], "bias": jnp.tile(skew, (SMALL.num_layers, 1))}}
    ids = ids_for(SMALL, 12, (8, 64))

    def loads(tree):  # how often each of the 5 outputs is chosen, every layer, by the program's own routing
        chosen = jax.jit(lambda p, i: cca_moe._layers(p, i, SMALL, with_routing=True)[2][1])(tree, ids)
        return np.stack([np.bincount(row, minlength=5) for row in np.asarray(chosen)])

    balanced = cca_moe.balance_routers(params, ids, SMALL)
    before, after = loads(params), loads(balanced)
    assert (before.max(axis=1) / before.mean(axis=1)).min() > 2.0
    assert (after.max(axis=1) / after.mean(axis=1)).max() < 1.3 and (after.min(axis=1) / after.mean(axis=1)).min() > 0.7
    stats = cca_moe.layer_statistics(balanced, ids, SMALL)
    assert abs(stats["moe.skip_share"] - 1 / 5) < 0.03
    assert abs(stats["moe.pairs_held"] / stats["moe.pairs_all"] - 2 / 5) < 0.03
    for (path, old), new in zip(jax.tree_util.tree_leaves_with_path(params), jax.tree.leaves(balanced)):
        assert jax.tree_util.keystr(path).endswith("['bias']") != np.array_equal(old, new), path
    again = cca_moe.balance_routers(params, ids, SMALL)
    assert np.array_equal(again["layers"]["bias"], balanced["layers"]["bias"])


# ---- build_forward, run.py ---------------------------------------------------


def test_integer_ids_survive_the_bf16_wrapper_and_other_strategies_are_refused(fwd16, params16):
    params, fwd = params16, fwd16
    ids = ids_for(SMALL, 3).at[0, 0].set(127)
    got = fwd(params, ids)
    direct = jax.jit(lambda p, i: cca_moe.forward(p, i, SMALL))(params, ids)
    assert np.array_equal(np.asarray(got), np.asarray(direct))
    assert not np.array_equal(np.asarray(got[0, 0]), np.asarray(fwd(params, ids.at[0, 0].set(126))[0, 0]))
    with pytest.raises(ValueError):
        build_forward(dataclasses.replace(REGISTRY["v10_cca_moe"], strategy="halo"), SMALL, n_shards=2)
    # one loop over the layers: one flash kernel and three grouped products, whatever the depth
    text = str(jax.make_jaxpr(lambda p, i: cca_moe.forward(p, i, SMALL))(params, ids))
    assert text.count("name=flash_fwd") == 1 and text.count("name=grouped_matmul") == 3


def test_run_py_runs_the_config_one_shot_and_refuses_to_serve_it(capsys):
    from cuda_mpi_gpu_cluster_programming_tpu import run

    assert run.main(["--config", "v10_cca_moe", "--repeats", "2"]) == 0
    out = capsys.readouterr().out
    assert "V10 CCA-MoE Share" in out and "Final Output Shape: 64x128" in out and "tokens/s" in out
    assert "experts [0, 2) of 4" in out
    assert run.main(["--config", "v10_cca_moe", "--dtype", "bf16", "--repeats", "1"]) == 0
    assert "dtype=bf16" in capsys.readouterr().out
    assert run.main(["--config", "v10_cca_moe", "--serve"]) == 2
    assert "--serve supports the Blocks 1-2 configs only" in capsys.readouterr().err
    assert run.main(["--config", "v10_cca_moe", "--preset", "solar_ep8"]) == 2  # another family's preset
    assert "is not one of v10_cca_moe's" in capsys.readouterr().err
    assert "zaya1_ep2" in cca_moe.PRESETS and cca_moe.PRESETS["zaya1_ep2"][1:] == (1, 4096)
