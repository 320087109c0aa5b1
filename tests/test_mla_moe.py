"""The latent-attention mixture-of-experts decoder (``models/mla_moe.py``) at
the small preset on the CPU: the program's forward against the benchmark's
plain reference on seeded weights; the chip's share (the shares of one MoE
layer add up to the uncut layer); the router on hand-made cases; dropless
dispatch under total imbalance; the rotary frequencies and the score scale
against closed forms; the two kernels it runs on; the way through
``build_forward`` and ``run.py``."""

from __future__ import annotations

import dataclasses
import functools
import math
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[1]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from benchmark.reference import mla_moe as reference  # noqa: E402
from cuda_mpi_gpu_cluster_programming_tpu.configs import REGISTRY, build_forward  # noqa: E402
from cuda_mpi_gpu_cluster_programming_tpu.models import mla_moe  # noqa: E402
from cuda_mpi_gpu_cluster_programming_tpu.ops import scopes  # noqa: E402
from cuda_mpi_gpu_cluster_programming_tpu.ops.flash_attention import flash_forward_bhld  # noqa: E402
from cuda_mpi_gpu_cluster_programming_tpu.ops.grouped_matmul import (  # noqa: E402
    fit_tile,
    grouped_matmul,
    grouped_matmul_reference,
)

SMALL = mla_moe.SMALL  # hidden 64, 4 heads, 16 experts in 4 groups, 4 held, 1 dense + 2 MoE layers


def file_config(c: mla_moe.MlaMoeConfig) -> dict:
    """What a configuration file says of ``c``: the reference reads the
    publisher's keys, not the program's object."""
    return dict(
        hidden_size=c.hidden_size, num_attention_heads=c.num_attention_heads,
        q_lora_rank=c.q_lora_rank, kv_lora_rank=c.kv_lora_rank,
        qk_nope_head_dim=c.qk_nope_head_dim, qk_rope_head_dim=c.qk_rope_head_dim,
        v_head_dim=c.v_head_dim, rms_norm_eps=c.rms_norm_eps, rope_theta=c.rope_theta,
        rope_scaling=dict(
            factor=c.rope_factor, beta_fast=c.rope_beta_fast, beta_slow=c.rope_beta_slow,
            mscale=c.rope_mscale, mscale_all_dim=c.rope_mscale_all_dim,
            original_max_position_embeddings=c.rope_original_max_position_embeddings,
        ),
        first_k_dense_replace=c.first_k_dense_replace, num_layers=c.num_layers,
        intermediate_size=c.intermediate_size, moe_intermediate_size=c.moe_intermediate_size,
        n_group=c.n_group, topk_group=c.topk_group, num_experts_per_tok=c.num_experts_per_tok,
        routed_scaling_factor=c.routed_scaling_factor, n_routed_experts=c.experts_held,
        experts_first=c.experts_first, published=dict(n_routed_experts=c.n_routed_experts),
        vocab_size=c.vocab_size,
    )


def ids_for(c, seed=1, shape=(2, 32)):
    return jax.random.randint(jax.random.key(seed), shape, 0, c.vocab_size, jnp.int32)


def rel_err(got, want) -> float:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.abs(got - want).max() / np.abs(want).max())


# ---- the forward against the plain reference --------------------------------


@pytest.mark.parametrize("seed", [0, 1])
def test_float32_forward_agrees_with_the_reference_tightly(seed):
    params = mla_moe.init(jax.random.key(seed), SMALL, jnp.float32)
    ids = ids_for(SMALL, seed + 10)
    got = build_forward(REGISTRY["v8_mla_moe"], SMALL)(params, ids)
    want = reference.forward(file_config(SMALL), params, ids)
    assert got.shape == (2, 32, SMALL.vocab_size) and got.dtype == jnp.float32
    assert rel_err(got, want) < 1e-5


def test_bf16_forward_agrees_under_the_stated_tolerance():
    """bf16 operands, float32 accumulation, at this toy width (a 64-wide norm
    rounds to a part in a hundred, and a token early in the sequence inherits
    what a neighbour's flipped routing did): over the tokens the reference
    finds far from a routing tie, the typical token within 1% of the largest
    logit, their rms within 3%, none beyond 10%; and visibly not float32."""
    params = mla_moe.init(jax.random.key(1), SMALL, jnp.bfloat16)
    ids = ids_for(SMALL, 4)
    got = np.asarray(build_forward(REGISTRY["v8_mla_moe"], SMALL, compute="bf16")(params, ids))
    want, slack, _pairs = reference.forward_checked(file_config(SMALL), params, ids)
    want, clear = np.asarray(want), np.asarray(slack) >= 0.05
    assert got.dtype == np.float32 and clear.mean() > 0.1
    err = np.abs(got - want).max(axis=-1) / np.abs(want).max()
    assert 1e-4 < np.median(err[clear]) < 0.01 and err[clear].max() < 0.1
    assert np.sqrt(np.mean((got[clear] - want[clear]) ** 2) / np.mean(want[clear] ** 2)) < 0.03


# ---- the chip's share --------------------------------------------------------


def test_the_shares_of_one_moe_layer_add_up_to_the_uncut_layer():
    """Four chips hold four experts each. Every one routes over all sixteen and
    computes its own experts' part plus the shared expert; their parts, the
    shared expert and the residual counted once, are the uncut reference layer."""
    whole = dataclasses.replace(SMALL, experts_held=SMALL.n_routed_experts)
    params = mla_moe.init(jax.random.key(5), whole, jnp.float32)
    layer = params["layers"][-1]
    moe = {**layer["moe"], "ffn_norm": layer["ffn_norm"]}
    h = jax.random.normal(jax.random.key(6), (2, 16, SMALL.hidden_size), jnp.float32)
    u = reference.rms_norm(h.reshape(-1, SMALL.hidden_size), layer["ffn_norm"], SMALL.rms_norm_eps)
    shared = np.asarray(reference.swiglu(layer["moe"]["shared"], u)).reshape(h.shape)
    parts, pairs = [], 0
    for first in range(0, SMALL.n_routed_experts, SMALL.experts_held):
        share = dataclasses.replace(SMALL, experts_first=first)
        held = slice(first, first + SMALL.experts_held)
        mine = {**moe, "experts": {k: w[held] for k, w in moe["experts"].items()}}
        out, sizes = jax.jit(lambda p, x, c=share: mla_moe._moe(p, x, c, with_sizes=True))(mine, h)
        parts.append(np.asarray(out) - np.asarray(h) - shared)  # this share's routed part
        pairs += int(sizes.sum())
    assert pairs == h.shape[0] * h.shape[1] * SMALL.num_experts_per_tok  # every pair fell to one share
    uncut, _slack, ref_pairs = reference.moe_ffn(file_config(whole), layer["moe"], u)
    assert ref_pairs == pairs
    total = sum(parts) + shared
    assert rel_err(total, np.asarray(uncut).reshape(h.shape)) < 1e-5
    assert all(np.abs(p).max() > 0 for p in parts)  # no share is idle here


# ---- the router --------------------------------------------------------------


def _logits_to_route(logits, bias, cfg, which):
    """Route one token whose router logits are ``logits``: the router is the
    identity over a hidden size equal to the number of experts."""
    n = cfg.n_routed_experts
    u = jnp.asarray(logits, jnp.float32)[None, :]
    router, bias = jnp.eye(n, dtype=jnp.float32), jnp.asarray(bias, jnp.float32)
    if which == "program":
        chosen, weights = mla_moe.route({"router": router, "bias": bias}, u, cfg)
    else:
        chosen, weights, _slack = reference.route(file_config(cfg), router, bias, u)
    return set(np.asarray(chosen)[0].tolist()), dict(zip(np.asarray(chosen)[0].tolist(), np.asarray(weights)[0]))


def _sigmoid(x):
    return 1.0 / (1.0 + math.exp(-x))


@pytest.mark.parametrize("which", ["program", "reference"])
def test_a_biased_expert_is_selected_but_weighted_by_its_unbiased_score(which):
    # 16 experts in 4 groups of 4, 2 groups kept, 4 experts a token
    logits = np.full(16, -2.0)
    logits[[0, 1, 4, 5]] = [2.0, 1.5, 1.0, 0.5]  # groups 0 and 1 lead
    logits[6] = 0.2  # would lose to expert 5 ...
    bias = np.zeros(16)
    bias[6] = 0.2  # ... but its selection bias lifts it over
    chosen, weights = _logits_to_route(logits, bias, SMALL, which)
    assert chosen == {0, 1, 4, 6}
    scores = {e: _sigmoid(logits[e]) for e in chosen}  # the weights ignore the bias
    for e in chosen:
        assert weights[e] == pytest.approx(2.5 * scores[e] / sum(scores.values()), rel=1e-5)
    assert sum(weights.values()) == pytest.approx(SMALL.routed_scaling_factor, rel=1e-5)


@pytest.mark.parametrize("which", ["program", "reference"])
def test_a_strong_expert_in_a_dropped_group_is_not_chosen(which):
    logits = np.full(16, -3.0)
    logits[[0, 1]] = [1.0, 0.9]  # group 0: two good experts
    logits[[4, 5]] = [0.8, 0.7]  # group 1: two good experts
    logits[12] = 4.0  # group 3: the strongest expert of all, alone: top-2 sum 0.98 + 0.05
    chosen, weights = _logits_to_route(logits, np.zeros(16), SMALL, which)
    assert 12 not in chosen and {0, 1, 4, 5} <= chosen
    assert sum(weights.values()) == pytest.approx(2.5, rel=1e-5)


def test_reference_slack_is_small_at_a_tie_and_large_away_from_it():
    cfg = file_config(SMALL)  # experts 0..3 held
    eye = jnp.eye(16, dtype=jnp.float32)
    clear = np.full(16, -3.0)
    clear[[0, 1, 4, 5]] = [2.0, 1.5, 1.0, 0.5]
    tie = clear.copy()
    tie[[1, 2]] = [0.5, 0.4999]  # held expert 2 a hair from taking the last place
    slack = reference.route(cfg, eye, jnp.zeros(16), jnp.asarray([clear, tie], jnp.float32))[2]
    assert float(slack[0]) > 0.05 and float(slack[1]) < 1e-3


# ---- dropless under total imbalance ------------------------------------------


@pytest.mark.parametrize("case", ["all_to_one_held", "none_to_held"])
def test_dropless_under_total_imbalance(case):
    """Every token to one held expert (as many rows as tokens, many chunks),
    and no token to any (the loop runs no chunk): nothing is dropped, nothing
    is invented."""
    first = 0 if case == "all_to_one_held" else 8
    cfg = dataclasses.replace(SMALL, experts_held=1, experts_first=first)
    params = mla_moe.init(jax.random.key(7), cfg, jnp.float32)
    bias = np.zeros(16, np.float32)
    bias[[0, 1, 4, 5]] = 5.0  # every token chooses experts 0, 1, 4, 5
    for layer in params["layers"]:
        if "moe" in layer:
            layer["moe"]["bias"] = jnp.asarray(bias)
    ids = ids_for(cfg, 8)
    stats = mla_moe.routing_statistics(params, ids, cfg)
    want_pairs = ids.size * cfg.num_moe_layers if case == "all_to_one_held" else 0
    assert stats["moe.pairs_held"] == want_pairs
    assert stats["moe.pairs_all"] == ids.size * cfg.num_experts_per_tok * cfg.num_moe_layers
    got = jax.jit(lambda p, i: mla_moe.forward(p, i, cfg))(params, ids)
    want, _slack, pairs = reference.forward_checked(file_config(cfg), params, ids)
    assert pairs == want_pairs
    assert rel_err(got, want) < 1e-5


def test_routing_statistics_fill_the_registry_and_agree_with_the_reference():
    from cuda_mpi_gpu_cluster_programming_tpu.observability import metrics

    params = mla_moe.init(jax.random.key(2), SMALL, jnp.float32)
    ids = ids_for(SMALL, 9)
    stats = mla_moe.routing_statistics(params, ids, SMALL)
    _logits, _slack, pairs = reference.forward_checked(file_config(SMALL), params, ids)
    assert stats["moe.pairs_held"] == pairs
    assert stats["moe.expert_load_max_over_mean"] >= 1.0
    summary = metrics.registry().summary()
    gauges = metrics.MOE_ROUTING_GAUGES + (metrics.FLASH_MASKED_SCORE_SHARE,)
    assert {summary[name] for name in gauges} == set(stats.values())


# ---- closed forms ------------------------------------------------------------


@pytest.mark.parametrize("which", ["program", "reference"])
def test_yarn_frequencies_and_the_score_scale_against_closed_forms(which):
    """Published values: rotary width 64, base 10000, factor 40, original length
    4096, beta_fast 32, beta_slow 1. The correction dimensions are
    64 ln(4096 / (32 * 2 pi)) / (2 ln 10000) = 10.47 and, for beta_slow, 22.5: the
    base's own frequencies up to i = 10, a fortieth of them from i = 23."""
    cfg = mla_moe.EP16_SHARE
    if which == "program":
        inv_freq, scale = mla_moe.yarn_inv_freq(cfg), mla_moe.softmax_scale(cfg)
    else:
        inv_freq, scale = reference.yarn_inv_freq(file_config(cfg)), reference.softmax_scale(file_config(cfg))
    own = 10000.0 ** (-np.arange(32) / 32.0)
    assert inv_freq.shape == (32,)
    np.testing.assert_allclose(inv_freq[:11], own[:11], rtol=1e-6)
    np.testing.assert_allclose(inv_freq[23:], own[23:] / 40.0, rtol=1e-6)
    ramp = (15 - 10) / (23 - 10)  # a linear blend between
    np.testing.assert_allclose(inv_freq[15], own[15] * (1 - ramp) + own[15] / 40 * ramp, rtol=1e-6)
    m = 0.1 * math.log(40.0) + 1.0
    assert scale == pytest.approx(192**-0.5 * m * m, rel=1e-9)
    assert scale == pytest.approx(0.135234, rel=1e-5)  # 0.0721688 x 1.368888**2


@pytest.mark.parametrize("form", ["pairs", "halves", "halves_of_the_weights"])
def test_rotation_keeps_the_scores_of_the_published_layout(form):
    """Pairs rotated in place give the scores the published layout (evens moved
    before odds, then rotate-half) gives: queries and keys are permuted alike.
    So does the rotation on halves, the form ``_mla`` runs: the evens and the
    odds taken apart (of the activations here, or of the projection's weight
    columns, which is the same product in another order), rotated, the
    firsts laid before the seconds."""
    cfg = SMALL
    cos, sin = mla_moe._rope_tables(cfg, 8)
    q = jax.random.normal(jax.random.key(0), (8, cfg.qk_rope_head_dim))
    k = jax.random.normal(jax.random.key(1), (8, cfg.qk_rope_head_dim))

    def published(x):
        x = jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)
        half = x.shape[-1] // 2
        rotated = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
        return x * jnp.concatenate([cos, cos], -1) + rotated * jnp.concatenate([sin, sin], -1)

    want = published(q) @ published(k).T
    if form == "pairs":
        ours = mla_moe._rope(q, cos, sin) @ mla_moe._rope(k, cos, sin).T
    elif form == "halves":
        halves = lambda x: mla_moe._rope_halves(x[..., 0::2].T, x[..., 1::2].T, cos.T, sin.T)  # (dim, S)
        ours = halves(q).T @ halves(k)
        # the same numbers as the pairs' rotation, moved: evens first
        np.testing.assert_allclose(halves(q)[: q.shape[1] // 2].T, mla_moe._rope(q, cos, sin)[:, 0::2], rtol=1e-6)
    else:
        # q and k as projections of a latent: the weights' columns are split, not the results
        c = jax.random.normal(jax.random.key(2), (8, 12))
        wq = jax.random.normal(jax.random.key(3), (12, cfg.qk_rope_head_dim))
        wk = jax.random.normal(jax.random.key(4), (12, cfg.qk_rope_head_dim))
        mm = functools.partial(jnp.matmul, precision="highest")
        halves = lambda w: mla_moe._rope_halves(mm(c, w[:, 0::2]).T, mm(c, w[:, 1::2]).T, cos.T, sin.T)
        ours = mm(halves(wq).T, halves(wk))
        want = mm(published(mm(c, wq)), published(mm(c, wk)).T)
    np.testing.assert_allclose(ours, want, rtol=1e-4, atol=1e-4)


# ---- build_forward, run.py ---------------------------------------------------


def test_integer_ids_survive_the_bf16_wrapper_and_bf16_parameters_are_left_alone():
    params = mla_moe.init(jax.random.key(0), SMALL, jnp.bfloat16)
    fwd = build_forward(REGISTRY["v8_mla_moe"], SMALL, compute="bf16")
    ids = jnp.asarray([[256, 257, 511]], jnp.int32)  # bf16 would read 257 as 256
    got = fwd(params, ids)
    direct = jax.jit(lambda p, i: mla_moe.forward(p, i, SMALL))(params, ids)
    assert np.array_equal(np.asarray(got), np.asarray(direct))
    assert not np.array_equal(np.asarray(got[0, 0]), np.asarray(fwd(params, ids.at[0, 0].set(257))[0, 0]))
    text = fwd.lower(params, ids).as_text()
    assert "bf16" in text and f"tensor<{SMALL.vocab_size}x{SMALL.hidden_size}xf32>" not in text


def test_other_strategies_and_int8_weights_are_refused():
    with pytest.raises(ValueError):
        build_forward(dataclasses.replace(REGISTRY["v8_mla_moe"], strategy="halo"), SMALL, n_shards=2)
    with pytest.raises(ValueError, match="Blocks 1-2"):
        build_forward(REGISTRY["v8_mla_moe"], SMALL, policy="int8w")


def test_run_py_runs_the_config_one_shot_and_refuses_to_serve_it(capsys):
    from cuda_mpi_gpu_cluster_programming_tpu import run

    assert run.main(["--config", "v8_mla_moe", "--repeats", "2"]) == 0
    out = capsys.readouterr().out
    assert "Final Output Shape: 32x512" in out and "tokens/s" in out
    assert run.main(["--config", "v8_mla_moe", "--serve"]) == 2
    assert "--serve supports the Blocks 1-2 configs only" in capsys.readouterr().err


def test_parameter_count_and_bf16_storage():
    params = mla_moe.init(jax.random.key(0), SMALL)
    leaves = jax.tree.leaves(params)
    assert all(leaf.dtype == jnp.bfloat16 for leaf in leaves)
    assert sum(leaf.size for leaf in leaves) == mla_moe.param_count(SMALL)
    again = mla_moe.init(jax.random.key(0), SMALL)
    other = mla_moe.init(jax.random.key(1), SMALL)
    assert np.array_equal(params["head"], again["head"]) and not np.array_equal(params["head"], other["head"])
    bias = np.asarray(params["layers"][-1]["moe"]["bias"], np.float32)
    assert 0 < np.abs(bias).max() < 0.2  # drawn small, not zero
    assert scopes.MLA_MOE_LAYERS[0] == "embed" and set(scopes.MLA_MOE_LAYERS) <= set(scopes.LAYERS)


# ---- the kernels -------------------------------------------------------------


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-5), (jnp.bfloat16, 2e-2)])
def test_grouped_matmul_multiplies_each_row_tile_by_its_groups_matrix(dtype, tol):
    key = jax.random.key(3)
    lhs = jax.random.normal(key, (48, 32), jnp.float32).astype(dtype)
    rhs = (jax.random.normal(jax.random.fold_in(key, 1), (3, 32, 24), jnp.float32) / 6).astype(dtype)
    tile_group = jnp.asarray([2, 0, 0, 1, 2, 2], jnp.int32)
    got = grouped_matmul(lhs, rhs, tile_group, tile_rows=8)
    want = grouped_matmul_reference(lhs, rhs, tile_group, tile_rows=8)
    plain = np.concatenate([
        np.asarray(lhs[i * 8 : (i + 1) * 8], np.float32) @ np.asarray(rhs[g], np.float32)
        for i, g in enumerate([2, 0, 0, 1, 2, 2])
    ])
    assert got.dtype == jnp.float32 and rel_err(got, want) < tol and rel_err(got, plain) < tol
    with pytest.raises(ValueError):
        grouped_matmul(lhs, rhs, tile_group[:5], tile_rows=8)


def test_tiles_divide_what_they_tile():
    assert fit_tile(7168, 1024) == 1024 and fit_tile(2048, 1024) == 1024
    assert fit_tile(96, 1024) == 96 and fit_tile(7168, 3000) == 1792  # 7168 = 4 x 1792
    assert fit_tile(1000, 512) == 1000  # no multiple of 128 divides it: the whole extent


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5), (jnp.bfloat16, 3e-2)])
def test_flash_attention_takes_a_value_width_of_its_own_and_a_scale(dtype, tol):
    key = jax.random.key(4)
    b, h, l, d, dv = 1, 2, 32, 24, 16
    q = jax.random.normal(key, (b, h, l, d), jnp.float32).astype(dtype)
    k = jax.random.normal(jax.random.fold_in(key, 1), (b, h, l, d), jnp.float32).astype(dtype)
    v = jax.random.normal(jax.random.fold_in(key, 2), (b, h, l, dv), jnp.float32).astype(dtype)
    out, lse = flash_forward_bhld(q, k, v, causal=True, scale=0.3, block_q=8, block_k=16)
    s = 0.3 * jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32), k.astype(jnp.float32))
    s = jnp.where(jnp.arange(l)[:, None] >= jnp.arange(l)[None, :], s, -jnp.inf)
    want = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, axis=-1), v.astype(jnp.float32))
    assert out.shape == (b, h, l, dv) and out.dtype == dtype
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(want), rtol=tol, atol=tol)
    np.testing.assert_allclose(
        np.asarray(lse)[:, :, 0, :], np.asarray(jax.scipy.special.logsumexp(s, axis=-1)), rtol=tol, atol=tol
    )


ROPE_CASES = {
    # name: (L, D, R, Dv, block_q, block_k, causal)
    "causal_unequal_blocks": (32, 24, 8, 16, 8, 16, True),
    "causal_wide_key_blocks": (32, 16, 8, 16, 16, 8, True),
    "one_block": (16, 16, 8, 24, 64, 64, True),
    "value_width_of_its_own": (32, 16, 16, 40, 16, 16, True),
    "not_causal": (32, 24, 8, 16, 8, 16, False),
}


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-6), (jnp.bfloat16, 3e-2)])
@pytest.mark.parametrize("case", sorted(ROPE_CASES))
def test_flash_attention_on_parts_equals_the_kernel_on_concatenated_operands(case, dtype, tol):
    """``q . k + q_rope . k_rope`` with ONE ``k_rope`` for all heads (the rope
    operands sequence-minor, ``(B, H, R, L)`` and ``(B, R, L)``) is the score
    of the concatenated query against the concatenated key with ``k_rope``
    copied per head: output and log-sum-exp both, float32 tightly
    (the same products summed in another order), bf16 under the tolerance the
    kernel's other tests state. The scale defaults to the whole width's."""
    l, d, r, dv, block_q, block_k, causal = ROPE_CASES[case]
    b, h = 2, 3
    keys = jax.random.split(jax.random.key(11), 5)
    draw = lambda key, shape: jax.random.normal(key, shape, jnp.float32).astype(dtype)
    q, k, v = draw(keys[0], (b, h, l, d)), draw(keys[1], (b, h, l, d)), draw(keys[2], (b, h, l, dv))
    q_rope, k_rope = draw(keys[3], (b, h, l, r)), draw(keys[4], (b, l, r))
    blocks = dict(causal=causal, block_q=block_q, block_k=block_k)
    parts = dict(q_rope=jnp.swapaxes(q_rope, 2, 3), k_rope=jnp.swapaxes(k_rope, 1, 2))
    whole_q = jnp.concatenate([q, q_rope], axis=-1)
    whole_k = jnp.concatenate([k, jnp.broadcast_to(k_rope[:, None], (b, h, l, r))], axis=-1)
    for scale in (None, 0.3):  # the default, and a given one: it reaches both products
        out, lse = flash_forward_bhld(q, k, v, **parts, scale=scale, **blocks)
        want, want_lse = flash_forward_bhld(whole_q, whole_k, v, scale=scale, **blocks)
        assert out.shape == (b, h, l, dv) and out.dtype == dtype and lse.shape == (b, h, 1, l)
        np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(want, np.float32), rtol=tol, atol=tol)
        np.testing.assert_allclose(np.asarray(lse), np.asarray(want_lse), rtol=tol, atol=tol)


def test_flash_attention_refuses_one_rope_operand_or_a_key_per_head():
    q = jnp.zeros((1, 2, 16, 8))
    with pytest.raises(ValueError, match="together"):
        flash_forward_bhld(q, q, q, causal=True, q_rope=q)
    with pytest.raises(ValueError, match=r"\(B, R, L\)"):
        flash_forward_bhld(q, q, q, causal=True, q_rope=q, k_rope=q)  # a k_rope per head
