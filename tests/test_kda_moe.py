"""The hybrid linear-attention mixture-of-experts decoder (``models/kda_moe.py``)
at the small preset on the CPU: the program's forward against the benchmark's
plain reference on seeded weights, in float32 and bf16; the layer kinds follow
``gqa_layers``; the chip's share (the 8 shares of one MoE layer add up to the
uncut layer); the parameter count against the benchmark's shape functions;
the statistics that fill the gauges; the seeded draw of the decay's
parameters; the short convolution; the way through ``build_forward`` and
``run.py``."""

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

from benchmark.reference import kda_moe as reference  # noqa: E402
from benchmark.reference import mla_moe as moe_reference  # noqa: E402
from benchmark.shapes import kda_moe as shapes  # noqa: E402
from cuda_mpi_gpu_cluster_programming_tpu.configs import REGISTRY, build_forward  # noqa: E402
from cuda_mpi_gpu_cluster_programming_tpu.models import kda_moe, moe_share  # noqa: E402
from cuda_mpi_gpu_cluster_programming_tpu.ops import scopes  # noqa: E402

SMALL = kda_moe.SMALL  # hidden 64, 4 heads of 16 (2 key/value heads), 16 experts, 4 held, layers gqa kda kda kda


def file_config(c: kda_moe.KdaMoeConfig) -> dict:
    """What a configuration file says of ``c``: the reference and the shape
    functions read the publisher's keys, not the program's object."""
    return dict(
        hidden_size=c.hidden_size, num_attention_heads=c.num_attention_heads,
        num_key_value_heads=c.num_key_value_heads, head_dim=c.head_dim,
        linear_attn_config=dict(
            num_heads=c.linear_attn_num_heads, head_dim=c.linear_attn_head_dim,
            short_conv_kernel_size=c.short_conv_kernel_size,
        ),
        kda_allow_neg_eigval=c.kda_allow_neg_eigval, rms_norm_eps=c.rms_norm_eps,
        num_layers=c.num_layers, gqa_layers=list(c.gqa_layers),
        moe_intermediate_size=c.moe_intermediate_size, n_group=c.n_group, topk_group=c.topk_group,
        num_experts_per_tok=c.num_experts_per_tok, routed_scaling_factor=c.routed_scaling_factor,
        n_routed_experts=c.experts_held, experts_first=c.experts_first,
        published=dict(n_routed_experts=c.n_routed_experts), vocab_size=c.vocab_size, compute="bf16", seq_len=64,
    )


def ids_for(c, seed=1, shape=(2, 64)):
    return jax.random.randint(jax.random.key(seed), shape, 0, c.vocab_size, jnp.int32)


def rel_err(got, want) -> float:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.abs(got - want).max() / np.abs(want).max())


@pytest.fixture(scope="module")
def fwd32():
    """The float32 forward, built once: every test that runs it shares its compilation."""
    return build_forward(REGISTRY["v9_kda_moe"], SMALL)


@pytest.fixture(scope="module")
def fwd16():
    return build_forward(REGISTRY["v9_kda_moe"], SMALL, compute="bf16")


@pytest.fixture(scope="module")
def params16():
    return kda_moe.init(jax.random.key(1), SMALL, jnp.bfloat16)


# ---- the forward against the plain reference --------------------------------


@pytest.mark.parametrize("seed", [0, 1])
def test_float32_forward_agrees_with_the_reference_tightly(seed, fwd32):
    """The chunked scan against the recurrence one token at a time, the flash
    kernel with shared key/value heads against materialised scores, shifted
    multiply-adds against stacked windows: parts in a million."""
    params = kda_moe.init(jax.random.key(seed), SMALL, jnp.float32)
    ids = ids_for(SMALL, seed + 10)
    got = fwd32(params, ids)
    want = reference.forward(file_config(SMALL), params, ids)
    assert got.shape == (2, 64, SMALL.vocab_size) and got.dtype == jnp.float32
    assert rel_err(got, want) < 1e-5


def test_bf16_forward_agrees_under_the_stated_tolerance(fwd16, params16):
    """bf16 operands, float32 accumulation, at this toy width (a 64-wide norm
    rounds to a part in a hundred, and the scan's state carries a rounding
    on through the sequence): over the tokens the reference finds far from a
    routing tie, the typical token within 2% of the largest logit, their rms
    within 4%, none beyond 12%; and visibly not float32."""
    params, ids = params16, ids_for(SMALL, 4)
    got = np.asarray(fwd16(params, ids))
    want, slack, _pairs = reference.forward_checked(file_config(SMALL), params, ids)
    want, clear = np.asarray(want), np.asarray(slack) >= 0.02
    assert got.dtype == np.float32 and clear.mean() > 0.1
    err = np.abs(got - want).max(axis=-1) / np.abs(want).max()
    assert 1e-4 < np.median(err[clear]) < 0.02 and err[clear].max() < 0.12
    assert np.sqrt(np.mean((got[clear] - want[clear]) ** 2) / np.mean(want[clear] ** 2)) < 0.04


def test_the_layer_kinds_follow_gqa_layers():
    cfg = dataclasses.replace(SMALL, gqa_layers=(1, 3))
    assert cfg.layer_kinds() == ("kda", "gqa", "kda", "gqa") and SMALL.layer_kinds() == ("gqa", "kda", "kda", "kda")
    params = kda_moe.init(jax.random.key(3), cfg, jnp.float32)
    assert ["a_log" in layer for layer in params["layers"]] == [True, False, True, False]
    assert all(("gate" in layer) != ("a_log" in layer) for layer in params["layers"])
    ids = ids_for(cfg, 5)
    text = str(jax.make_jaxpr(lambda p, i: kda_moe.forward(p, i, cfg))(params, ids))
    assert text.count("name=kda_chunked") == 2 and text.count("name=flash_fwd") == 2
    want = reference.forward(file_config(cfg), params, ids)
    assert rel_err(jax.jit(lambda p, i: kda_moe.forward(p, i, cfg))(params, ids), want) < 1e-5
    with pytest.raises(ValueError, match="gqa_layers"):
        dataclasses.replace(SMALL, gqa_layers=(4,))
    with pytest.raises(ValueError, match="use_rope"):
        dataclasses.replace(SMALL, use_rope=True)


def test_logits_do_not_look_ahead(fwd32):
    params = kda_moe.init(jax.random.key(2), SMALL, jnp.float32)
    ids = ids_for(SMALL, 6)
    first, second = np.asarray(fwd32(params, ids)), np.asarray(fwd32(params, ids.at[:, 40:].set(7)))
    # to rounding, not bitwise: the routed pairs of the whole batch are sorted and summed together
    np.testing.assert_allclose(first[:, :40], second[:, :40], rtol=1e-5, atol=1e-5)
    assert not np.allclose(first[:, 40:], second[:, 40:], atol=1e-2)


# ---- the chip's share --------------------------------------------------------


def test_the_eight_shares_of_one_moe_layer_add_up_to_the_uncut_layer():
    """Eight chips hold two experts each, as the deployment's eight hold forty.
    Every one routes over all sixteen (one group: a plain top-4) and computes
    its own experts' part plus the shared expert; their parts, the shared
    expert and the residual counted once, are the uncut reference layer."""
    held = 2
    share0 = dataclasses.replace(SMALL, experts_held=held)
    whole = dataclasses.replace(SMALL, experts_held=SMALL.n_routed_experts)
    params = kda_moe.init(jax.random.key(5), whole, jnp.float32)
    layer = params["layers"][-1]
    moe = {**layer["moe"], "ffn_norm": layer["ffn_norm"]}
    h = jax.random.normal(jax.random.key(6), (2, 16, SMALL.hidden_size), jnp.float32)
    u = moe_reference.rms_norm(h.reshape(-1, SMALL.hidden_size), layer["ffn_norm"], SMALL.rms_norm_eps)
    shared = np.asarray(moe_reference.swiglu(layer["moe"]["shared"], u)).reshape(h.shape)
    parts, pairs = [], 0
    for first in range(0, SMALL.n_routed_experts, held):
        share = dataclasses.replace(share0, experts_first=first)
        mine = {**moe, "experts": {k: w[first : first + held] for k, w in moe["experts"].items()}}
        out, sizes = jax.jit(lambda p, x, c=share: moe_share._moe(p, x, c, with_sizes=True))(mine, h)
        parts.append(np.asarray(out) - np.asarray(h) - shared)  # this share's routed part
        pairs += int(sizes.sum())
    assert len(parts) == 8 and pairs == h.shape[0] * h.shape[1] * SMALL.num_experts_per_tok
    uncut, _slack, ref_pairs = moe_reference.moe_ffn(file_config(whole), layer["moe"], u)
    assert ref_pairs == pairs
    assert rel_err(sum(parts) + shared, np.asarray(uncut).reshape(h.shape)) < 1e-5
    assert all(np.abs(p).max() > 0 for p in parts)  # no share is idle here


# ---- parameters ----------------------------------------------------------------


TWO_AND_TWO = dataclasses.replace(SMALL, gqa_layers=(0, 2), experts_held=8)


@pytest.mark.parametrize("cfg", [SMALL, TWO_AND_TWO], ids=["small", "two_and_two"])
def test_parameter_count_is_the_shape_functions_and_the_trees(cfg):
    params = kda_moe.init(jax.random.key(0), cfg)
    leaves = jax.tree.leaves(params)
    assert all(leaf.dtype == jnp.bfloat16 for leaf in leaves)
    assert sum(leaf.size for leaf in leaves) == kda_moe.param_count(cfg) == shapes.param_count(file_config(cfg))
    assert set(scopes.KDA_MOE_LAYERS) <= set(scopes.LAYERS) and len(set(scopes.LAYERS)) == len(scopes.LAYERS)


def test_the_seeded_draw_of_the_decay_and_the_same_seed_twice(params16):
    params = kda_moe.init(jax.random.key(0), SMALL, jnp.float32)
    again = kda_moe.init(jax.random.key(1), SMALL, jnp.bfloat16)  # the fixture's seed and type
    for mine, theirs in zip(jax.tree.leaves(again), jax.tree.leaves(params16)):
        assert np.array_equal(mine, theirs)
    assert not np.array_equal(np.asarray(params["head"], np.float32), np.asarray(again["head"], np.float32))
    linear = params["layers"][1]
    rate = np.exp(np.asarray(linear["a_log"]))
    assert rate.shape == (4,) and (rate >= 1.0).all() and (rate <= 16.0).all()  # A_log = log U(1, 16)
    step = np.asarray(jax.nn.softplus(linear["dt_bias"]))  # dt_bias = softplus^-1 of the step
    assert step.shape == (4, 16) and step.min() >= 0.999e-3 and step.max() <= 0.1001
    assert np.asarray(linear["conv_q"]).std() == pytest.approx(0.5, rel=0.2)  # 4 taps: 4^-0.5
    assert np.array_equal(linear["o_norm"], np.ones(16)) and 0 < np.abs(np.asarray(linear["moe"]["bias"])).max() < 0.2


def test_short_convolution_is_causal_with_the_last_tap_on_the_token_itself():
    x = jax.random.normal(jax.random.key(0), (1, 2, 8, 3))
    taps = jax.random.normal(jax.random.key(1), (4, 2, 3))
    got = np.asarray(kda_moe._short_conv(x, taps))
    xn, wn = np.asarray(x), np.asarray(taps)
    want = np.zeros_like(xn)
    for t in range(8):
        for j in range(4):
            if t - 3 + j >= 0:
                want[:, :, t] += wn[j][None] * xn[:, :, t - 3 + j]
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(reference.short_conv(x, taps)), want, rtol=1e-5, atol=1e-6)


# ---- statistics ------------------------------------------------------------------


def test_layer_statistics_fill_the_gauges_and_agree_with_the_reference():
    from cuda_mpi_gpu_cluster_programming_tpu.observability import metrics

    params = kda_moe.init(jax.random.key(2), SMALL, jnp.float32)
    ids = ids_for(SMALL, 9)
    metrics.registry().reset()
    stats = kda_moe.layer_statistics(params, ids, SMALL)
    _logits, _slack, pairs = reference.forward_checked(file_config(SMALL), params, ids)
    assert stats["moe.pairs_held"] == pairs and stats["moe.expert_load_max_over_mean"] >= 1.0
    assert stats["moe.pairs_all"] == ids.size * SMALL.num_experts_per_tok * SMALL.num_layers
    # the decays summed over a chunk of 16: by the reference's own g of the three linear layers
    assert stats["kda.chunk_log_decay_min"] < -1.0 and 0.5 < stats["kda.beta_mean"] < 1.5
    # 16 channels a head fill no lane: every layer's mix takes the jax.numpy form (at 128 channels
    # the three linear layers take the kernel: tests/test_kda_mix.py)
    assert stats["kda.mix_fused_layers"] == 0
    summary = metrics.registry().summary()
    gauges = metrics.MOE_ROUTING_GAUGES + metrics.KDA_GAUGES + (metrics.FLASH_MASKED_SCORE_SHARE,)
    assert {name: summary[name] for name in gauges} == stats


def test_balancing_the_routers_evens_the_load_and_moves_only_the_selection_bias():
    """A selection bias that sends most tokens to a few experts (as a stream
    with a token-independent part does at the real widths) is replaced, layer
    by layer, by one under which every expert of the router's width is loaded
    alike; nothing else in the tree moves, and the same ids give the same bias."""
    params = kda_moe.init(jax.random.key(3), SMALL, jnp.float32)
    skew = jnp.asarray(np.linspace(-0.3, 0.3, SMALL.n_routed_experts), jnp.float32)
    for layer in params["layers"]:
        layer["moe"]["bias"] = skew
    ids = ids_for(SMALL, 12, (4, 64))

    def loads(tree):  # the load of every one of the 16 experts in the last layer, by the reference's router
        x = jax.random.normal(jax.random.key(0), (ids.size, SMALL.hidden_size))
        moe = tree["layers"][-1]["moe"]
        chosen, _w, _s = moe_reference.route(file_config(SMALL), moe["router"], moe["bias"], x)
        return np.bincount(np.asarray(chosen).reshape(-1), minlength=SMALL.n_routed_experts)

    balanced = kda_moe.balance_routers(params, ids, SMALL)
    before, after = loads(params), loads(balanced)
    assert before.max() / before.mean() > 2.0 and after.max() / after.mean() < 1.5
    stats = kda_moe.layer_statistics(balanced, ids, SMALL)
    assert abs(stats["moe.pairs_held"] / stats["moe.pairs_all"] - SMALL.experts_held / SMALL.n_routed_experts) < 0.03
    for (path, old), new in zip(jax.tree_util.tree_leaves_with_path(params), jax.tree.leaves(balanced)):
        assert jax.tree_util.keystr(path).endswith("['moe']['bias']") != np.array_equal(old, new), path
    again = kda_moe.balance_routers(params, ids, SMALL)
    assert np.array_equal(again["layers"][2]["moe"]["bias"], balanced["layers"][2]["moe"]["bias"])


# ---- build_forward, run.py ---------------------------------------------------


def test_integer_ids_survive_the_bf16_wrapper_and_other_strategies_are_refused(fwd16, params16):
    params, fwd = params16, fwd16
    ids = ids_for(SMALL, 3).at[0, 0].set(257)  # bf16 would read 257 as 256
    got = fwd(params, ids)
    direct = jax.jit(lambda p, i: kda_moe.forward(p, i, SMALL))(params, ids)
    assert np.array_equal(np.asarray(got), np.asarray(direct))
    assert not np.array_equal(np.asarray(got[0, 0]), np.asarray(fwd(params, ids.at[0, 0].set(256))[0, 0]))
    with pytest.raises(ValueError):
        build_forward(dataclasses.replace(REGISTRY["v9_kda_moe"], strategy="halo"), SMALL, n_shards=2)
    with pytest.raises(ValueError, match="whole chunks"):
        fwd(params, ids[:, :40])


def test_run_py_runs_the_config_one_shot_and_refuses_to_serve_it(capsys):
    from cuda_mpi_gpu_cluster_programming_tpu import run

    assert run.main(["--config", "v9_kda_moe", "--repeats", "2"]) == 0
    out = capsys.readouterr().out
    assert "V9 KDA-MoE Share" in out and "Final Output Shape: 64x512" in out and "tokens/s" in out
    assert run.main(["--config", "v9_kda_moe", "--serve"]) == 2
    assert "--serve supports the Blocks 1-2 configs only" in capsys.readouterr().err
    assert run.main(["--config", "v9_kda_moe", "--preset", "ep16_share"]) == 2  # the other family's preset
    assert "is not one of v9_kda_moe's" in capsys.readouterr().err
    assert "solar_ep8" in kda_moe.PRESETS and kda_moe.PRESETS["solar_ep8"][1:] == (2, 8192)
