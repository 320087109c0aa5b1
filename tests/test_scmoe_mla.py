"""The shortcut-connected mixture-of-experts decoder over latent attention
(``models/scmoe_mla.py``) at the small preset on the CPU: the program's forward
against the benchmark's plain reference on seeded weights, in float32 and bf16,
whole and layer by layer; the router against the page; the chip's share (the 4
shares of one layer's routed sum, with the identity term, both dense FFNs and
both attentions counted once, add up to the uncut reference's layer); tokens
that send no row; the plain rotary embedding as YaRN at factor 1; the two
latent scales; the parameter count against the benchmark's shape functions;
the statistics that fill the gauges; the way through ``build_forward`` and
``run.py``; and the dots cell's step program as the parent lowered it."""

from __future__ import annotations

import dataclasses
import hashlib
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[1]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from benchmark.reference import scmoe_mla as reference  # noqa: E402
from benchmark.shapes import scmoe_mla as shapes  # noqa: E402
from cuda_mpi_gpu_cluster_programming_tpu.configs import REGISTRY, build_forward  # noqa: E402
from cuda_mpi_gpu_cluster_programming_tpu.models import mla_moe, moe_share, scmoe_mla  # noqa: E402
from cuda_mpi_gpu_cluster_programming_tpu.ops import scopes  # noqa: E402

SMALL = scmoe_mla.SMALL  # hidden 64, 4 heads, 8 experts + 4 identity experts, top-3, 2 held, 2 layers


def file_config(c: scmoe_mla.ScmoeMlaConfig) -> dict:
    """What a configuration file says of ``c``: the reference and the shape
    functions read the publisher's keys, not the program's object."""
    return dict(
        hidden_size=c.hidden_size, num_attention_heads=c.num_attention_heads, q_lora_rank=c.q_lora_rank,
        kv_lora_rank=c.kv_lora_rank, qk_nope_head_dim=c.qk_nope_head_dim, qk_rope_head_dim=c.qk_rope_head_dim,
        v_head_dim=c.v_head_dim, mla_scale_q_lora=c.mla_scale_q_lora, mla_scale_kv_lora=c.mla_scale_kv_lora,
        rms_norm_eps=c.rms_norm_eps, rope_theta=c.rope_theta, num_layers=c.num_layers,
        ffn_hidden_size=c.ffn_hidden_size, expert_ffn_hidden_size=c.expert_ffn_hidden_size,
        n_routed_experts=c.experts_held, experts_first=c.experts_first,
        published=dict(n_routed_experts=c.n_routed_experts), zero_expert_num=c.zero_expert_num,
        moe_topk=c.moe_topk, routed_scaling_factor=c.routed_scaling_factor,
        vocab_size=c.vocab_size, compute="bf16", seq_len=32,
    )


def ids_for(c, seed=1, shape=(2, 32)):
    return jax.random.randint(jax.random.key(seed), shape, 0, c.vocab_size, jnp.int32)


def layer_of(params, i):
    """Layer ``i`` of the layer-stacked tree."""
    return jax.tree.map(lambda leaf: leaf[i], params["layers"])


def run_layer(p, x, cfg):
    """The program's layer on one layer's parameters ``p`` (its experts a stack of one layer)."""
    rest = {k: v for k, v in p.items() if k != "experts"}
    return jax.jit(lambda rest, experts, x: scmoe_mla._layer(rest, experts, x, 0, cfg))(rest, p["experts"], x)


def run_moe(p, h, cfg):
    rest = {k: v for k, v in p.items() if k != "experts"}
    return jax.jit(lambda rest, experts, h: scmoe_mla._moe(rest, experts, h, 0, cfg))(rest, p["experts"], h)


def rel_err(got, want) -> float:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.abs(got - want).max() / np.abs(want).max())


@pytest.fixture(scope="module")
def fwd32():
    """The float32 forward, built once: every test that runs it shares its compilation."""
    return build_forward(REGISTRY["v11_scmoe_mla"], SMALL)


@pytest.fixture(scope="module")
def params32():
    return scmoe_mla.init(jax.random.key(2), SMALL, jnp.float32)


# ---- the forward against the plain reference --------------------------------


@pytest.mark.parametrize("seed", [0, 1])
def test_float32_forward_agrees_with_the_reference_tightly(seed, fwd32):
    """The flash kernel against materialised scores, the rotation on halves
    against interleaved pairs, the grouped product over sorted pairs against one
    expert at a time, the branch carried past three sublayers against the
    page's order: parts in a million."""
    params = scmoe_mla.init(jax.random.key(seed), SMALL, jnp.float32)
    ids = ids_for(SMALL, seed + 10)
    got = fwd32(params, ids)
    want, _slack, pairs = reference.forward_checked(file_config(SMALL), params, ids)
    assert got.shape == (2, 32, SMALL.vocab_size) and got.dtype == jnp.float32
    assert rel_err(got, want) < 1e-5 and 0 < pairs < ids.size * SMALL.num_layers * SMALL.moe_topk


def test_bf16_forward_agrees_under_the_stated_tolerance():
    """bf16 operands, float32 accumulation, at this toy width (a 64-wide norm
    rounds to a part in a hundred): over the tokens the reference finds far
    from a routing tie, the typical token within 2% of the largest logit, none
    beyond 10%, their rms within 3%; and visibly not float32."""
    params, ids = scmoe_mla.init(jax.random.key(1), SMALL, jnp.bfloat16), ids_for(SMALL, 4)
    got = np.asarray(build_forward(REGISTRY["v11_scmoe_mla"], SMALL, compute="bf16")(params, ids))
    want, slack, _pairs = reference.forward_checked(file_config(SMALL), params, ids)
    want, clear = np.asarray(want), np.asarray(slack) >= 0.003
    assert got.dtype == np.float32 and clear.mean() > 0.3
    err = np.abs(got - want).max(axis=-1) / np.abs(want).max()
    assert 1e-4 < np.median(err[clear]) < 0.02 and err[clear].max() < 0.1
    assert np.sqrt(np.mean((got[clear] - want[clear]) ** 2) / np.mean(want[clear] ** 2)) < 0.03


@pytest.mark.parametrize("compute", ["fp32", "bf16"])
@pytest.mark.parametrize("index", [0, 1])
def test_one_layer_agrees_with_the_references_layer(index, compute):
    """Layer by layer on the same input: the stream out, and (float32) the
    pairs that fell to the held experts."""
    dtype = jnp.float32 if compute == "fp32" else jnp.bfloat16
    params = scmoe_mla.init(jax.random.key(7), SMALL, dtype)
    x = jax.random.normal(jax.random.key(index), (2, 32, SMALL.hidden_size), jnp.float32)
    got, (_chosen, sizes) = run_layer(layer_of(params, index), x, SMALL)
    want, slack, pairs = reference.layer(file_config(SMALL), params["layers"], index, x)
    if compute == "fp32":
        assert rel_err(got, want) < 1e-5 and int(sizes.sum()) == pairs
    else:  # a token near a tie may take another expert under bf16: hold the clear ones
        clear = np.asarray(slack).reshape(2, 32) >= 0.003
        assert clear.mean() > 0.5 and rel_err(np.asarray(got)[clear], np.asarray(want)[clear]) < 0.05


def test_logits_do_not_look_ahead(fwd32, params32):
    ids = ids_for(SMALL, 6)
    first, second = np.asarray(fwd32(params32, ids)), np.asarray(fwd32(params32, ids.at[:, 20:].set(7)))
    # to rounding, not bitwise: the routed pairs of the whole batch are sorted and summed together
    np.testing.assert_allclose(first[:, :20], second[:, :20], rtol=1e-5, atol=1e-5)
    assert not np.allclose(first[:, 20:], second[:, 20:], atol=1e-2)


# ---- the router against the page ---------------------------------------------


def test_the_softmax_router_selects_on_the_biased_scores_and_weights_by_the_unbiased_ones():
    key = jax.random.split(jax.random.key(0), 3)
    u = jax.random.normal(key[0], (16, SMALL.hidden_size))
    p = {
        "router": jax.random.normal(key[1], (SMALL.hidden_size, SMALL.router_outputs)) * SMALL.hidden_size**-0.5,
        "bias": jax.random.normal(key[2], (SMALL.router_outputs,)) * 0.05,  # large: selection and weighting part
    }
    chosen, weights = moe_share.route_softmax(p, u, SMALL)
    z = np.asarray(u, np.float64) @ np.asarray(p["router"], np.float64)
    s = np.exp(z - z.max(-1, keepdims=True))
    s /= s.sum(-1, keepdims=True)
    biased = s + np.asarray(p["bias"], np.float64)
    want = np.argsort(-biased, axis=-1)[:, : SMALL.moe_topk]
    assert chosen.dtype == jnp.int32 and np.array_equal(np.sort(chosen, -1), np.sort(want, -1))
    assert not np.array_equal(np.sort(want, -1), np.sort(np.argsort(-s, axis=-1)[:, : SMALL.moe_topk], -1))
    np.testing.assert_allclose(weights, 6.0 * np.take_along_axis(s, np.asarray(chosen), -1), rtol=1e-5)
    assert np.all(np.asarray(weights).sum(-1) < 6.0)  # not renormalised: the chosen scores do not sum to 1
    ref_chosen, ref_weights, slack = reference.route(file_config(SMALL), p["router"], p["bias"], u)
    assert np.array_equal(np.sort(ref_chosen, -1), np.sort(want, -1)) and np.all(np.asarray(slack) >= 0)
    np.testing.assert_allclose(np.sort(ref_weights, -1), np.sort(weights, -1), rtol=1e-5)


def test_the_routing_slack_counts_only_outputs_whose_crossing_changes_this_chip():
    """Three outputs near the boundary of a top-1 over 2 real experts (1 held:
    output 0) and 1 identity (output 2): a tie between the two real experts'
    scores is no tie here when the held one is far from the boundary."""
    cfg = dict(file_config(SMALL), n_routed_experts=1, published=dict(n_routed_experts=2), zero_expert_num=1)
    cfg["moe_topk"] = 1
    logits = jnp.log(jnp.asarray([[0.2, 0.5, 0.3], [0.45, 0.05, 0.5], [0.05, 0.48, 0.47]]))
    router, u = jnp.eye(3), logits  # u W_r = logits
    _chosen, _weights, slack = reference.route(cfg, router, jnp.zeros(3), u)
    # token 0: chosen 1 (absent); nearest that matters: identity at 0.3 -> 0.2 from the last taken
    # token 1: chosen the identity 0.5, first out the held 0.45: both 0.05 from the boundary
    # token 2: chosen absent 0.48, the identity 0.47 is 0.01 away
    np.testing.assert_allclose(slack, [0.2, 0.05, 0.01], atol=1e-6)


# ---- the chip's share --------------------------------------------------------


def test_the_four_shares_of_one_layer_add_up_to_the_uncut_references_layer():
    """Four chips hold two of the eight real experts each. Every one routes
    over all twelve outputs and computes its own experts' pairs; the identity
    term, both attentions and both dense FFNs are computed alike on every chip
    and counted once. Together: the uncut reference's layer."""
    whole = dataclasses.replace(SMALL, experts_held=SMALL.n_routed_experts)
    params = scmoe_mla.init(jax.random.key(5), whole, jnp.float32)
    p = layer_of(params, 1)
    x = jax.random.normal(jax.random.key(6), (2, 32, SMALL.hidden_size), jnp.float32)
    uncut, _slack, all_pairs = reference.layer(file_config(whole), params["layers"], 1, x)

    h1 = mla_moe._mla(p["sub"][0], x, SMALL, SMALL.q_scale, SMALL.kv_scale)
    normed = moe_share._rms_norm(h1.reshape(-1, SMALL.hidden_size), p["sub"][0]["ffn_norm"], SMALL.rms_norm_eps)
    routed_parts, pairs, zero = [], 0, None
    for first in range(0, SMALL.n_routed_experts, SMALL.experts_held):
        share = dataclasses.replace(SMALL, experts_first=first)
        mine = {**p, "experts": {n: w[first : first + SMALL.experts_held] for n, w in p["experts"].items()}}
        u, m, (chosen, sizes) = run_moe(mine, h1, share)
        weights = moe_share.route_softmax(p, u, share)[1]
        zero = np.asarray(scmoe_mla._zero_experts(normed, chosen, weights, share)).reshape(x.shape)
        routed_parts.append(np.asarray(m) - zero)
        pairs += int(sizes.sum())
        if first == 0:  # one chip's whole layer: everything that is counted once, and its own routed part
            y0 = np.asarray(run_layer(mine, x, share)[0])
    real_places = int((np.asarray(chosen) < SMALL.n_routed_experts).sum())
    assert pairs == real_places == all_pairs  # every real pair fell to exactly one share
    total = y0 + sum(routed_parts[1:])
    assert rel_err(total, uncut) < 1e-5
    assert all(np.abs(part).max() > 0 for part in routed_parts) and np.abs(zero).max() > 0


@pytest.mark.parametrize("where", ["identity", "absent"])
def test_a_token_whose_chosen_outputs_are_identities_or_absent_sends_no_row_but_keeps_its_identity_term(
    where, params32
):
    p = layer_of(params32, 0)
    outputs = np.arange(SMALL.router_outputs)
    favoured = outputs >= SMALL.n_routed_experts if where == "identity" else (
        (outputs >= SMALL.experts_held) & (outputs < SMALL.n_routed_experts)
    )
    p["bias"] = jnp.where(favoured, 10.0, 0.0)  # every token's top-3 lies among the favoured outputs
    h = jax.random.normal(jax.random.key(0), (1, 32, SMALL.hidden_size))
    u, m, (chosen, sizes) = run_moe(p, h, SMALL)
    h, m = h[0], m[0]
    assert np.all(favoured[np.asarray(chosen)]) and int(sizes.sum()) == 0
    weights = moe_share.route_softmax(p, u, SMALL)[1]
    routed, _sizes = moe_share._routed(p["experts"], u, chosen, weights, SMALL)
    assert not np.asarray(routed).any()  # no pair was dispatched: the routed sum is exactly 0
    normed = np.asarray(moe_share._rms_norm(h, p["sub"][0]["ffn_norm"], SMALL.rms_norm_eps))
    if where == "identity":
        want = np.asarray(weights).sum(-1, keepdims=True) * normed
        np.testing.assert_allclose(np.asarray(m), want, rtol=1e-6, atol=1e-7)
        assert np.abs(want).max() > 0.01
    else:
        assert not np.asarray(m).any()


# ---- the attention: plain rotary embedding, two latent scales -----------------


def test_rope_factor_1_gives_the_bases_own_frequencies_and_the_plain_score_scale():
    for cfg in (SMALL, scmoe_mla.EP32_SHARE):
        dim = cfg.qk_rope_head_dim
        own = cfg.rope_theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
        np.testing.assert_allclose(mla_moe.yarn_inv_freq(cfg), own, rtol=1e-6)
        np.testing.assert_allclose(reference.inv_freq(file_config(cfg)), own, rtol=1e-6)
        assert mla_moe.softmax_scale(cfg) == cfg.qk_head_dim**-0.5
        cos, sin = mla_moe._rope_tables(cfg, 8)
        np.testing.assert_allclose(cos, np.cos(np.arange(8)[:, None] * own), rtol=1e-5, atol=1e-6)
    assert mla_moe.softmax_scale(scmoe_mla.EP32_SHARE) == 192**-0.5
    assert (scmoe_mla.EP32_SHARE.q_scale, scmoe_mla.EP32_SHARE.kv_scale) == (2.0, 12**0.5)
    off = dataclasses.replace(SMALL, mla_scale_q_lora=False, mla_scale_kv_lora=False)
    assert (off.q_scale, off.kv_scale) == (1.0, 1.0) and (SMALL.q_scale, SMALL.kv_scale) == (2**0.5, 2.0)


def test_the_two_latent_scales_reach_queries_keys_nope_part_and_values_and_not_k_r(monkeypatch, params32):
    """What the attention kernel is handed with the scales at (2, 3) against
    (1, 1): both parts of the queries twice, keys' nope part and values three
    times, the one rope key unchanged."""
    handed = []

    def capture(q_nope, k_nope, v, *, q_rope, k_rope, **kwargs):
        handed.append([np.asarray(a) for a in (q_nope, q_rope, k_nope, v, k_rope)])
        return jnp.zeros(q_nope.shape[:-1] + v.shape[-1:], q_nope.dtype), None

    monkeypatch.setattr(mla_moe, "flash_forward_bhld", capture)
    p = layer_of(params32, 0)["sub"][1]
    x = jax.random.normal(jax.random.key(3), (1, 16, SMALL.hidden_size), jnp.float32)
    mla_moe._mla(p, x, SMALL)
    mla_moe._mla(p, x, SMALL, 2.0, 3.0)
    for (plain, scaled), ratio in zip(zip(*handed), (2.0, 2.0, 3.0, 3.0, 1.0)):
        np.testing.assert_allclose(scaled, ratio * plain, rtol=1e-5, atol=1e-6)
    # and the reference's latents: c_q and c_kv scaled, k_rope not
    cfg = file_config(SMALL)
    c_q, c_kv, k_rope = reference.mla_latents(cfg, p, x)
    off = dict(cfg, mla_scale_q_lora=False, mla_scale_kv_lora=False)
    c_q0, c_kv0, k_rope0 = reference.mla_latents(off, p, x)
    np.testing.assert_allclose(c_q, 2**0.5 * np.asarray(c_q0), rtol=1e-6)
    np.testing.assert_allclose(c_kv, 2.0 * np.asarray(c_kv0), rtol=1e-6)
    assert np.array_equal(k_rope, k_rope0)


# ---- parameters, statistics, the ways in --------------------------------------


def test_parameter_count_is_the_benchmarks_and_the_real_share_is_5_173b():
    assert scmoe_mla.param_count(SMALL) == shapes.param_count(file_config(SMALL))
    real = scmoe_mla.EP32_SHARE
    assert scmoe_mla.param_count(real) == shapes.param_count(file_config(real)) == 5_172_749_312
    params = jax.eval_shape(lambda: scmoe_mla.init(jax.random.key(0), SMALL))
    assert sum(leaf.size for leaf in jax.tree.leaves(params)) == scmoe_mla.param_count(SMALL)
    assert all(leaf.dtype == jnp.bfloat16 for leaf in jax.tree.leaves(params))


def test_the_selection_bias_is_drawn_at_a_softmax_scores_scale():
    params = scmoe_mla.init(jax.random.key(0), dataclasses.replace(SMALL, zero_expert_num=504), jnp.float32)
    bias = np.asarray(params["layers"]["bias"])
    assert bias.shape == (2, 512) and 0.5 * scmoe_mla.BIAS_SCALE < bias.std() < 2 * scmoe_mla.BIAS_SCALE
    assert scmoe_mla.BIAS_SCALE < moe_share.BIAS_SCALE / 10
    assert np.all(np.asarray(params["layers"]["sub"][0]["attn_norm"]) == 1.0)


def test_the_matrices_that_expand_a_scaled_latent_are_drawn_at_the_models_width():
    """``q_b`` and ``kv_b`` at ``hidden**-0.5`` where their latent is scaled by
    ``sqrt(hidden / rank)`` (at ``rank**-0.5`` where it is not), so that
    queries, keys and values come out at unit scale either way."""
    wide = dataclasses.replace(SMALL, hidden_size=256, q_lora_rank=64, kv_lora_rank=16, num_layers=1)
    for cfg in (wide, dataclasses.replace(wide, mla_scale_q_lora=False, mla_scale_kv_lora=False)):
        sub = scmoe_mla.init(jax.random.key(0), cfg, jnp.float32)["layers"]["sub"][0]
        q_std, kv_std = float(np.std(sub["q_b"])), float(np.std(sub["kv_b"]))
        assert q_std * cfg.q_scale == pytest.approx(64**-0.5, rel=0.05)
        assert kv_std * cfg.kv_scale == pytest.approx(16**-0.5, rel=0.1)
    assert np.std(sub["q_a"]) == pytest.approx(256**-0.5, rel=0.05)


def test_routing_statistics_fill_the_gauges(params32):
    from cuda_mpi_gpu_cluster_programming_tpu.observability import metrics

    metrics.registry().reset()
    ids = ids_for(SMALL, 9)
    out = scmoe_mla.routing_statistics(params32, ids, SMALL)
    summary = metrics.registry().summary()
    for name in metrics.MOE_ROUTING_GAUGES + metrics.SCMOE_GAUGES:
        assert summary[name] == out[name], name
    assert out[metrics.MOE_PAIRS_ALL] == ids.size * SMALL.num_layers * SMALL.moe_topk
    _want, _slack, pairs = reference.forward_checked(file_config(SMALL), params32, ids)
    assert out[metrics.MOE_PAIRS_HELD] == pairs
    assert 0.15 < out[metrics.MOE_ZERO_PAIR_SHARE] < 0.55  # 4 of 12 outputs are identities
    assert 0 <= out[metrics.MOE_REAL_EXPERTS_PER_TOKEN_MIN] < out[metrics.MOE_REAL_EXPERTS_PER_TOKEN_MAX] <= 3
    metrics.registry().reset()


def test_a_share_outside_the_real_experts_or_more_places_than_outputs_is_refused():
    with pytest.raises(ValueError, match="inside the router's width"):
        dataclasses.replace(SMALL, experts_first=7)  # [7, 9) runs into the identity experts
    with pytest.raises(ValueError, match="moe_topk"):
        dataclasses.replace(SMALL, moe_topk=13)


def test_the_scopes_are_the_vocabularys_and_the_branch_carries_its_own():
    assert "moe.zero" in scopes.LAYERS and set(scopes.SCMOE_MLA_LAYERS) <= set(scopes.LAYERS)
    params = jax.eval_shape(lambda: scmoe_mla.init(jax.random.key(0), SMALL))
    ids = jax.ShapeDtypeStruct((2, 32), jnp.int32)
    jaxpr = jax.make_jaxpr(lambda p, i: scmoe_mla.forward(p, i, SMALL))(params, ids)

    def walk(jaxpr, prefix=""):  # (primitive, its whole name stack) through the loop's body too
        for eqn in jaxpr.eqns:
            stack = "/".join(part for part in (prefix, str(eqn.source_info.name_stack)) if part)
            yield eqn.primitive.name, stack
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from walk(sub, stack)

    found = list(walk(jaxpr.jaxpr))
    # every operation of the step stands under one of the family's layers, the branch's under the branch's own
    assert all(set(stack.split("/")) & set(scopes.SCMOE_MLA_LAYERS) for _name, stack in found)
    assert {part for _name, stack in found for part in stack.split("/")} >= set(scopes.SCMOE_MLA_LAYERS)
    under = lambda primitive: {stack for name, stack in found if name == primitive}
    assert all("moe.route/route.score" in stack for stack in under("top_k")) and under("top_k")
    assert all("moe.route/route.sort" in stack for stack in under("sort")) and under("sort")
    assert any("moe.zero" in stack.split("/") for _name, stack in found)


def test_run_py_runs_the_small_preset_and_refuses_to_serve(capsys):
    from cuda_mpi_gpu_cluster_programming_tpu import run

    assert run.main(["--config", "v11_scmoe_mla", "--dtype", "bf16", "--repeats", "1"]) == 0
    out = capsys.readouterr().out
    assert "V11 ScMoE-MLA Share" in out and "Final Output Shape: 32x256" in out and "experts [0, 2) of 8" in out
    assert scmoe_mla.PRESETS["longcat_ep32"] == (scmoe_mla.EP32_SHARE, 2, 4096)
    assert run.main(["--config", "v11_scmoe_mla", "--preset", "zaya1_ep2"]) == 2
    assert run.main(["--config", "v11_scmoe_mla", "--serve"]) != 0


# ---- the sibling that shares the attention: its step program did not change -----

# The step program of ``v8_mla_moe`` at the dots cell's real shapes (the
# ``ep16_share`` preset, 2 x 4,096 ids, bf16) as jax 0.9.0 lowers it
# (``.lower(...).as_text()``: the program as traced, before any compiler of a
# particular machine touches it, no source location in it): sha256 of the
# text. ``_mla`` got two scales for this family; at 1 it must build what it
# built. A change that means to alter the dots step records the new digest
# here and says so: this is PR 38's, which changed ``flash_fwd`` for every
# caller (the grid over the contributing pairs alone, a block on the diagonal
# in row slabs); until then it was 79b582f0..., the text of PR 36's tree
# (8e338f2), the commit BEFORE this family.
DOTS_STEP_SHA256 = "a7e34e95a624285f51ec9dd45b5c6f178ec9509f80114a001aea1f1d9fb62c9a"


def test_the_dots_cells_step_program_is_the_one_the_parent_lowered():
    if jax.__version__ != "0.9.0":
        pytest.skip("the digest is of jax 0.9.0's lowering")
    cfg, batch, seq = mla_moe.PRESETS["ep16_share"]
    params = jax.eval_shape(lambda: mla_moe.init(jax.random.key(0), cfg, jnp.bfloat16))
    ids = jax.ShapeDtypeStruct((batch, seq), jnp.int32)
    text = build_forward(REGISTRY["v8_mla_moe"], cfg, n_shards=1, compute="bf16").lower(params, ids).as_text()
    assert "loc(" not in text  # no source location in it: moving code changes nothing
    assert hashlib.sha256(text.encode()).hexdigest() == DOTS_STEP_SHA256
