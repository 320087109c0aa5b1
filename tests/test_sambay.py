"""The decoder-hybrid-decoder family (``models.sambay``) at its small preset on
the CPU: the forward against the benchmark's plain reference, whole and kind
of layer by kind of layer; what crosses the depth (layer ``L/2``'s scan output,
layer ``L/2 + 1``'s keys and values) reaches every layer that reads it and no
cross layer holds a key or value parameter; the window's edge; the
differential head against its dense formula; the seeded draw; the gauges; the
normal path (``REGISTRY`` -> ``build_forward`` -> ``run.py``)."""

from __future__ import annotations

import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[1]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from benchmark import harness  # noqa: E402
from cuda_mpi_gpu_cluster_programming_tpu.configs import REGISTRY, build_forward  # noqa: E402
from cuda_mpi_gpu_cluster_programming_tpu.models import moe_share, sambay  # noqa: E402
from cuda_mpi_gpu_cluster_programming_tpu.observability import metrics  # noqa: E402

SMALL = sambay.SMALL
HALF = SMALL.num_hidden_layers // 2
REF = harness.load_plugin("reference", "sambay")
DTYPES = {"fp32": jnp.float32, "bf16": jnp.bfloat16}


def small_file() -> dict:
    """The benchmark's configuration file at the small preset's sizes: what the
    reference reads."""
    cfg = json.loads((REPO / "benchmark" / "configs" / "phi4_mini_flash_reasoning.json").read_text())
    for key in ("vocab_size", "hidden_size", "intermediate_size", "num_hidden_layers", "num_attention_heads",
                "num_key_value_heads", "sliding_window"):
        cfg[key] = getattr(SMALL, key)
    cfg["assumed"] = dict(cfg["assumed"], dt_rank=SMALL.dt_rank)
    cfg["seq_len"] = 32
    return cfg


FILE = small_file()


def _params(seed=0, dtype=jnp.float32):
    return sambay.init(jax.random.key(seed), SMALL, dtype)


def _ids(seed=0, batch=2, seq=32):
    return jax.random.randint(jax.random.key(100 + seed), (batch, seq), 0, SMALL.vocab_size, jnp.int32)


def _stream(seed=0, batch=2, seq=32):
    return jax.random.normal(jax.random.key(200 + seed), (batch, seq, SMALL.hidden_size), jnp.float32)


FORWARD = jax.jit(lambda params, ids: sambay.forward(params, ids, SMALL))  # one program a stored type


def _err(got, want):
    want = np.asarray(want, np.float32)
    return float(np.abs(np.asarray(got, np.float32) - want).max() / np.abs(want).max())


# ---- the forward against the reference ------------------------------------------


@pytest.mark.parametrize("seed", [0, 1])
def test_float32_forward_is_the_reference(seed):
    params, ids = _params(seed), _ids(seed)
    got = FORWARD(params, ids)
    assert got.shape == (2, 32, SMALL.vocab_size) and got.dtype == jnp.float32
    assert _err(got, REF.forward(FILE, params, ids)) < 5e-6


def test_bf16_forward_is_a_rounding_off_the_reference_and_every_fault_several():
    params, ids = _params(2, jnp.bfloat16), _ids(2)
    want = REF.forward(FILE, params, ids)
    # at 64 wide a rounding is a part in a thousand; at the published widths the file's limit is 5e-4 (PERF.md §4)
    rounding = _err(FORWARD(params, ids), want)
    assert rounding < 5e-3
    # a dropped term is no rounding: each departure the reference can make moves the logits several times as far
    for fault in ("no_d", "lambda0", "window_plus_one", "own_kv"):
        assert _err(REF.forward(FILE, params, ids, fault=fault), want) > 3 * rounding, fault
    with pytest.raises(ValueError):
        REF.forward(FILE, params, ids, fault="no_such_fault")


def test_logits_do_not_look_ahead():
    params, ids = _params(3), _ids(3, batch=1)
    base = np.asarray(FORWARD(params, ids))
    moved = np.asarray(FORWARD(params, ids.at[0, 20].set((ids[0, 20] + 1) % SMALL.vocab_size)))
    assert np.array_equal(base[0, :20], moved[0, :20]) and np.abs(base[0, 20:] - moved[0, 20:]).max(axis=-1).min() > 0


# ---- kind of layer by kind of layer ---------------------------------------------


def _mid_and_pairs(params):
    first = jax.tree.map(lambda leaf: leaf[0], params["first"])
    last = jax.tree.map(lambda leaf: leaf[0], params["last"])
    return first, params["mid"], last


@pytest.mark.parametrize("compute", ["fp32", "bf16"])
@pytest.mark.parametrize("kind", ["mamba", "mamba_hand_off", "gmu", "window", "full", "cross", "mlp"])
def test_each_kind_of_layer_is_the_references_layer(kind, compute):
    dtype = DTYPES[compute]
    params = _params(4, dtype)
    first, mid, last = _mid_and_pairs(params)
    x = _stream(4)
    run = REF._blocks(FILE, jnp.float32)
    tol = 2e-5 if compute == "fp32" else 2e-2
    if kind in ("mamba", "mamba_hand_off"):
        p = first["mamba"] if kind == "mamba" else mid["mamba"]
        got, y, _seen = sambay._mamba(p, x, SMALL)
        want, want_y = REF.mamba_layer(FILE, p, x, run)
        assert y.dtype == dtype and _err(y, want_y) < tol  # what layer L/2 hands down
    elif kind == "gmu":
        memory = jax.random.normal(jax.random.key(9), (2, 32, SMALL.d_inner), jnp.float32).astype(dtype)
        got, want = sambay._gmu(last["gmu"], x, memory, SMALL), run["gmu"](last["gmu"], x, memory)
    elif kind == "mlp":
        got, want = sambay._mlp(first["mlp_a"], x, SMALL), run["mlp"](first["mlp_a"], x)
    else:
        layer = {"window": 1, "full": HALF + 1, "cross": HALF + 3}[kind]
        _x, kv = sambay._diff_attn(mid["attn"], x, HALF + 1, SMALL)
        _x, ref_kv = REF.attn_layer(FILE, mid["attn"], x, HALF + 1, run)
        if kind == "cross":
            other = _stream(5)
            got, _kv = sambay._diff_attn(last["attn"], other, layer, SMALL, kv=kv)
            want, _kv = REF.attn_layer(FILE, last["attn"], other, layer, run, kv=ref_kv)
        else:
            p = first["attn"] if kind == "window" else mid["attn"]
            got, _kv = sambay._diff_attn(p, x, layer, SMALL, window=SMALL.sliding_window if kind == "window" else None)
            want, _kv = REF.attn_layer(FILE, p, x, layer, run)
    assert _err(got - x, want - x) < tol, kind  # the mixer's own addition to the stream


def _mlp_one_product(p, x, halves=lambda gate, up: (gate, up)):
    """The form ``sambay._mlp`` replaced: ONE product of the stored ``(D, 2F)``
    matrix, split into gate and up (``halves`` may swap them)."""
    u = sambay._layer_norm(x, p["norm"], SMALL.layer_norm_eps)
    gate, up = halves(*jnp.split(moe_share._mm("bsd,df->bsf", u, p["w1"]), 2, axis=-1))
    return x + moe_share._mm("bsf,fd->bsd", up * jax.nn.silu(gate), p["w2"])


@pytest.mark.parametrize("compute", ["fp32", "bf16"])
def test_the_mlp_of_two_products_is_the_one_product_split(compute):
    # (B, S, D, F) = (2, 32, 64, 128); the stream is of unit size, the MLP's addition a fraction of it
    p, x = _mid_and_pairs(_params(7, DTYPES[compute]))[1]["mlp_b"], _stream(7)
    got, want = sambay._mlp(p, x, SMALL) - x, _mlp_one_product(p, x) - x
    # float32 at HIGHEST: the same sums in another order; bf16: the hidden may round the other way, once
    assert _err(got, want) < (1e-6 if compute == "fp32" else 2.0**-8)


def test_the_mlp_takes_the_gate_from_the_first_half_of_w1_and_up_from_the_second():
    # the draw's halves differ, and silu(g) * u is not silu(u) * g: the swapped form is another function
    p, x = _mid_and_pairs(_params(8))[1]["mlp_a"], _stream(8)
    width = p["w1"].shape[-1] // 2
    assert float(jnp.abs(p["w1"][:, :width] - p["w1"][:, width:]).max()) > 0.1
    got = sambay._mlp(p, x, SMALL) - x
    assert _err(got, _mlp_one_product(p, x) - x) < 1e-6
    assert _err(got, _mlp_one_product(p, x, halves=lambda gate, up: (up, gate)) - x) > 0.1


@pytest.mark.parametrize("pair", [0, 2])
def test_a_stacks_halves_are_the_halves_of_that_pair_s_matrix(pair):
    # what a loop's MLP reads: each half sliced out of the stack (n, D, 2F) at a traced index
    stack = _params(9)["first"]["mlp_b"]["w1"]
    gate, up = jax.jit(sambay._w1_halves)(stack, jnp.int32(pair))
    want_gate, want_up = sambay._w1_halves(stack[pair])
    assert np.array_equal(gate, want_gate) and np.array_equal(up, want_up) and not np.array_equal(gate, up)
    assert np.array_equal(jnp.concatenate([gate, up], axis=-1), stack[pair])


def test_the_differential_head_is_its_dense_formula_with_lambda_from_the_four_vectors():
    params = _params(6)
    p, x, layer = params["mid"]["attn"], _stream(6, batch=1), HALF + 1
    got = np.asarray(sambay._diff_attn(p, x, layer, SMALL)[0] - x)[0]
    f = lambda a: np.asarray(a, np.float64)
    e, h, hk = SMALL.head_dim, SMALL.num_attention_heads, SMALL.num_key_value_heads
    u = f(x[0]) - f(x[0]).mean(-1, keepdims=True)
    u = u / np.sqrt((u * u).mean(-1, keepdims=True) + SMALL.layer_norm_eps)
    u = u * f(p["norm"]["gain"]) + f(p["norm"]["bias"])
    qkv = u @ f(p["qkv"]) + f(p["qkv_bias"])
    q = qkv[:, : h * e].reshape(-1, h, e)
    k, v = qkv[:, h * e : (h + hk) * e].reshape(-1, hk, e), qkv[:, (h + hk) * e :].reshape(-1, hk, e)
    lq1, lk1, lq2, lk2 = f(p["lambda"])
    lam0 = 0.8 - 0.6 * np.exp(-0.3 * layer)
    lam = np.exp(lq1 @ lk1) - np.exp(lq2 @ lk2) + lam0
    assert float(sambay.diff_lambda(p["lambda"], layer)) == pytest.approx(lam, rel=1e-6)
    causal = np.tril(np.ones((32, 32), bool))

    def softmax_v(qh, kh, value):
        s = np.where(causal, qh @ kh.T / np.sqrt(e), -np.inf)
        w = np.exp(s - s.max(-1, keepdims=True))
        return w / w.sum(-1, keepdims=True) @ value

    heads = []
    for j in range(h // 2):
        pair = j // (h // hk)
        value = np.concatenate([v[:, 2 * pair], v[:, 2 * pair + 1]], axis=-1)
        o = softmax_v(q[:, 2 * j], k[:, 2 * pair], value) - lam * softmax_v(q[:, 2 * j + 1], k[:, 2 * pair + 1], value)
        o = o / np.sqrt((o * o).mean(-1, keepdims=True) + SMALL.layer_norm_eps) * f(p["subln"]) * (1 - lam0)
        heads.append(o)
    want = np.concatenate(heads, axis=-1) @ f(p["o"]) + f(p["o_bias"])
    assert _err(got, want) < 2e-5


# ---- the window's edge -----------------------------------------------------------


def test_the_farthest_key_inside_the_window_moves_a_query_and_the_first_outside_does_not():
    params = _params(7)
    p = jax.tree.map(lambda leaf: leaf[0], params["first"])["attn"]
    x, t, w = _stream(7, batch=1), 20, SMALL.sliding_window
    layer = lambda stream: np.asarray(sambay._diff_attn(p, stream, 1, SMALL, window=w)[0] - stream)[0]
    base = layer(x)
    nudge = jax.random.normal(jax.random.key(70), (SMALL.hidden_size,))  # no constant: the norm would take it out
    inside, outside = layer(x.at[0, t - (w - 1)].add(nudge)), layer(x.at[0, t - w].add(nudge))
    assert np.abs(inside[t] - base[t]).max() > 1e-4  # 7 tokens back: the last key the query sees
    assert np.array_equal(outside[t], base[t])  # 8 back: the first it does not
    assert np.abs(outside[t - 1] - base[t - 1]).max() > 1e-4  # the query before still sees it


# ---- what crosses the depth --------------------------------------------------------


def test_layer_17s_keys_reach_every_cross_layer_and_no_cross_layer_holds_a_key_or_value():
    cfg = sambay.SambayConfig(num_hidden_layers=16)  # 4 + 2 + 3 pairs: more than one cross layer
    params = sambay.init(jax.random.key(8), cfg, jnp.float32)
    x, half = _stream(8), cfg.num_hidden_layers // 2
    e, h, hk = cfg.head_dim, cfg.num_attention_heads, cfg.num_key_value_heads
    mid = params["mid"]["attn"]
    assert mid["qkv"].shape == (cfg.hidden_size, (h + 2 * hk) * e)
    assert params["last"]["attn"]["qkv"].shape == (cfg.last_pairs, cfg.hidden_size, h * e)  # W_q alone
    assert set(sambay.attn_shapes(cfg, cross=True)) == set(sambay.attn_shapes(cfg))  # and nothing under another name
    _x, kv = sambay._diff_attn(mid, x, half + 1, cfg)
    moved = dict(mid, qkv=mid["qkv"].at[:, h * e : (h + hk) * e].multiply(1.5))  # W_k alone
    _x, kv_moved = sambay._diff_attn(moved, x, half + 1, cfg)
    assert np.array_equal(kv[1], kv_moved[1]) and not np.array_equal(kv[0], kv_moved[0])
    for j in range(cfg.last_pairs):
        p = jax.tree.map(lambda leaf: leaf[j], params["last"])["attn"]
        layer = half + 3 + 2 * j
        a, _ = sambay._diff_attn(p, x, layer, cfg, kv=kv)
        b, _ = sambay._diff_attn(p, x, layer, cfg, kv=kv_moved)
        assert np.abs(np.asarray(a - b)).max() > 1e-4, layer
    # and through the whole forward: the logits move with layer 17's W_k
    ids = jax.random.randint(jax.random.key(1), (1, 32), 0, cfg.vocab_size)
    forward = jax.jit(lambda p: sambay.forward(p, ids, cfg))
    assert _err(forward({**params, "mid": {**params["mid"], "attn": moved}}), forward(params)) > 1e-4


def test_layer_16s_scan_reaches_every_memory_unit():
    cfg = sambay.SambayConfig(num_hidden_layers=16)
    params = sambay.init(jax.random.key(9), cfg, jnp.float32)
    x = _stream(9)
    mamba = params["mid"]["mamba"]
    _x, memory, _seen = sambay._mamba(mamba, x, cfg)
    # the D term is part of what is handed down
    _x, moved, _seen = sambay._mamba(dict(mamba, d=mamba["d"] * 0.5), x, cfg)
    assert np.abs(np.asarray(memory - moved)).max() > 1e-3
    for j in range(cfg.last_pairs):
        p = jax.tree.map(lambda leaf: leaf[j], params["last"])["gmu"]
        assert np.abs(np.asarray(sambay._gmu(p, x, memory, cfg) - sambay._gmu(p, x, moved, cfg))).max() > 1e-5, j
        assert set(p) == {"norm", "w1", "w2"}  # no scan and no convolution of its own


# ---- parameters ---------------------------------------------------------------------


def test_parameter_count_by_hand_and_by_the_program():
    d, v, width, di, n, r, e = 2560, 200064, 10240, 5120, 16, 160, 64
    mlp = d * 2 * width + width * d
    mamba = d * 2 * di + (4 * di + di) + di * (r + 2 * n) + (r * di + di) + di * n + di + di * d
    gmu = 2 * d * di
    attn = d * 80 * e + 80 * e + 4 * e + 2 * e + 40 * e * d + d
    cross = d * 40 * e + 40 * e + 4 * e + 2 * e + 40 * e * d + d
    norms = (2 * 32 + 1) * 2 * d
    assert (v * d, 32 * mlp, 9 * mamba, 7 * gmu, 9 * attn, 7 * cross, norms) == (
        512_163_840, 2_516_582_400, 371_174_400, 183_500_800, 177_019_776, 91_788_928, 332_800
    )
    total = v * d + 32 * mlp + 9 * mamba + 7 * gmu + 9 * attn + 7 * cross + norms
    assert total == sambay.param_count(sambay.PHI4_MINI_FLASH) == 3_852_562_944
    leaves = jax.tree.leaves(_params())
    assert sum(leaf.size for leaf in leaves) == sambay.param_count(SMALL) == moe_share.count(sambay.param_shapes(SMALL))


def test_the_seeded_draw():
    params, again, other = _params(0, jnp.bfloat16), _params(0, jnp.bfloat16), _params(1, jnp.bfloat16)
    assert all(a.dtype == jnp.bfloat16 for a in jax.tree.leaves(params))
    assert all(np.array_equal(a, b) for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(again)))
    assert not np.array_equal(params["embed"], other["embed"])
    params = _params(0)
    mamba, attn = params["first"]["mamba"], params["first"]["attn"]
    assert mamba["in"].shape[0] == SMALL.first_pairs and params["last"]["gmu"]["w1"].shape[0] == SMALL.last_pairs
    assert np.allclose(np.exp(mamba["a_log"][0, 0]), np.arange(1, 17)) and np.all(np.asarray(mamba["d"]) == 1)
    step = np.log1p(np.exp(np.asarray(mamba["dt_bias"], np.float64)))  # softplus: the drawn step itself
    assert step.min() >= sambay.DT_MIN * 0.999 and step.max() <= sambay.DT_MAX * 1.001 and step.max() / step.min() > 10
    assert np.all(np.asarray(attn["norm"]["gain"]) == 1) and not np.asarray(attn["norm"]["bias"]).any()
    for bias in (attn["qkv_bias"], attn["o_bias"], mamba["conv_bias"]):  # small and NOT zero
        assert 0 < np.abs(np.asarray(bias)).mean() < 0.2
    # the four matrices that write to the stream are drawn 1 / sqrt(sublayers) smaller
    ratio = np.asarray(params["first"]["mlp_a"]["w2"]).std() * (SMALL.intermediate_size * SMALL.sublayers) ** 0.5
    assert 0.9 < ratio < 1.1 and 0.9 < np.asarray(mamba["in"]).std() * SMALL.hidden_size**0.5 < 1.1
    assert sambay.lambda_init(0) == pytest.approx(0.2) and sambay.lambda_init(31) == pytest.approx(0.8, abs=1e-4)
    with pytest.raises(ValueError):
        sambay.SambayConfig(num_hidden_layers=6)
    with pytest.raises(ValueError):
        sambay.SambayConfig(num_key_value_heads=3)


# ---- the gauges -----------------------------------------------------------------------


def test_layer_statistics_fill_the_gauges_outside_the_forward():
    metrics.registry().reset()
    params, ids = _params(5), _ids(5)
    stats = sambay.layer_statistics(params, ids, SMALL)
    assert set(stats) == set(metrics.SAMBAY_GAUGES) | {metrics.FLASH_MASKED_SCORE_SHARE}
    summary = metrics.registry().summary()
    assert {name: summary[name] for name in stats} == stats
    # Delta A summed over a chunk: far below what float32 can exponentiate the negative of
    assert stats[metrics.SSM_CHUNK_LOG_DECAY_MIN] < -16 * 32 * sambay.DT_MIN
    assert sambay.DT_MIN < stats[metrics.SSM_DT_MEAN] < 1.0
    assert 0.0 < stats[metrics.DIFF_LAMBDA_MIN] <= stats[metrics.DIFF_LAMBDA_MAX] < 1.5
    # 32 tokens, a window of 8 at blocks of 8: the diagonal block and the one before it, half of each kept
    assert stats[metrics.FLASH_WINDOW_MASKED_SCORE_SHARE] == pytest.approx(1 - (8 * 9 // 2 + 24 * 8) / (7 * 64))
    assert stats[metrics.FLASH_MASKED_SCORE_SHARE] == pytest.approx(1 - (32 * 33 // 2) / 1024)
    metrics.registry().reset()
    FORWARD(params, ids)  # the forward itself sets nothing
    assert not set(metrics.registry().summary()) & set(stats)


# ---- the normal path ---------------------------------------------------------------------


def test_registry_entry_builds_the_forward_in_both_compute_types():
    exec_cfg = REGISTRY["v12_sambay"]
    assert exec_cfg.model == "sambay" and exec_cfg.strategy == "single"
    params, ids = _params(0, jnp.bfloat16), _ids(0)
    out = build_forward(exec_cfg, SMALL, compute="bf16")(params, ids)
    assert out.dtype == jnp.float32 and _err(out, FORWARD(params, ids)) == 0.0
    assert build_forward(exec_cfg, compute="fp32")(_params(0), ids).shape == (2, 32, SMALL.vocab_size)


def test_run_one_shot_and_serve_is_refused(capsys):
    from cuda_mpi_gpu_cluster_programming_tpu import run

    assert run.main(["--config", "v12_sambay", "--dtype", "bf16", "--repeats", "1"]) == 0
    out = capsys.readouterr().out
    assert "Final Output Shape: 32x256" in out and "a dense model, whole" in out and "preset=small" in out
    assert run.main(["--config", "v12_sambay", "--serve"]) != 0
    assert run.main(["--config", "v12_sambay", "--preset", "longcat_ep32"]) == 2  # another family's preset
    assert sambay.PRESETS["phi4_mini_flash"] == (sambay.PHI4_MINI_FLASH, 1, 4096)
