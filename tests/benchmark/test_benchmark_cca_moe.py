"""The compressed-convolutional-attention mixture-of-experts family in the
benchmark: its configuration file against the catalog row it is cut from and
against the program's preset; its shape functions against counts reckoned by
hand; its cell run end to end on the CPU at a tiny size in a temporary copy;
its scopes in the compiled program and its readers on a synthetic trace; the
manifest's rules on the repo's manifest as it now stands."""

from __future__ import annotations

import json
import sys
import types
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

REPO = Path(__file__).resolve().parents[2]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))
sys.path.insert(0, str(Path(__file__).resolve().parent))

import bench_tiny  # noqa: E402
import manifest_rules  # noqa: E402
from benchmark import harness, layer_times, scope_roofline, trace_reduce  # noqa: E402
from benchmark.shapes import cca_moe as shapes  # noqa: E402

MANIFEST = json.loads((REPO / "BENCHMARK.json").read_text())
CELL = "zaya1_prefill_s4096"
CONFIG = json.loads((REPO / "benchmark" / "configs" / "zaya1_8b_ep2.json").read_text())
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")
ROPE = {
    "hybrid": {"partial_rotary_factor": 0.5, "rope_theta": 5000000, "rope_type": "default"},
    "hybrid_sliding": {"partial_rotary_factor": 0.5, "rope_theta": 10000, "rope_type": "default"},
    "rope_type": "default",
}
# the published config.json of the source, as the catalog row holds it, but for the two cut keys
PUBLISHED = {
    "attention_bias": False, "cca_time0": 2, "cca_time1": 2, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 2048, "layer_types": ["hybrid"] * 40, "lm_head_bias": False, "max_position_embeddings": 131072,
    "model_type": "zaya", "moe_intermediate_size": 2048, "num_attention_heads": 8, "num_experts_per_tok": 1,
    "num_hidden_layers": 40, "num_key_value_heads": 2, "partial_rotary_factor": 0.5, "rms_norm_eps": 1e-05,
    "rope_parameters": ROPE, "router_hidden_size": 256, "sliding_window": None, "tie_word_embeddings": True,
}
CUT = {"num_experts": (16, 8), "vocab_size": (262272, 131136)}
NEW_METRICS = [
    "kernels.cca_proj_roofline", "kernels.cca_mix_ms", "kernels.cca_attn_roofline", "kernels.router_mlp_ms",
    "kernels.experts_top1_roofline", "moe.skip_share",
]
LAYERS = ["embed", "layer_loop", "cca.proj", "cca.mix", "cca.attn", "moe.route", "moe.experts", "head"]


# ---- the configuration file ---------------------------------------------------


def test_every_width_is_the_published_one_and_every_cut_is_listed():
    for key, value in PUBLISHED.items():
        assert CONFIG[key] == value, key
    for key, (published, here) in CUT.items():
        assert CONFIG[key] == here and CONFIG["published"][key] == published and key in CONFIG["reduced"]
    assert CONFIG["reduced"] == ["num_experts", "vocab_size"]  # no depth and no width is cut
    assert CONFIG["num_layers"] == CONFIG["num_hidden_layers"] == 40
    assert CONFIG["deployment"]["expert_parallel_chips"] == 2
    assert CONFIG["num_experts"] * 2 == CONFIG["published"]["num_experts"]
    assert CONFIG["vocab_size"] * 2 == CONFIG["published"]["vocab_size"]
    assert CONFIG["compute"] == "bf16" and CONFIG["chips"] == 1 and CONFIG["family"] == "cca_moe"
    # the floors of a cut: at least 4 layers, 8 experts, an eighth of the vocabulary
    assert CONFIG["num_layers"] >= 4 and CONFIG["num_experts"] >= 8
    # what the source does not give is written down as assumed
    assert {"eda_skip_merge", "skipped_token", "biases", "gelu", "qk_norm", "rotary", "convolutions", "value_shift",
            "router_input", "selection_bias", "merge_vectors", "experts_held"} <= set(CONFIG["assumed"])
    assert "4.545B parameters = 9.09 GB" in CONFIG["deployment"]["parameters_here"]
    assert len(CONFIG["source"]) <= 200 and all(len(v) > 5 for v in CONFIG["assumed"].values())


def test_the_file_holds_every_number_of_the_catalog_row_or_lists_the_key():
    if not CATALOG.is_file():
        pytest.skip("no catalog beside the guides here")
    row = next(json.loads(l) for l in CATALOG.read_text().splitlines() if '"name": "ZAYA1-8B"' in l)
    assert row["source_url"] in CONFIG["source"] and row["config"] == {**PUBLISHED, **{k: v[0] for k, v in CUT.items()}}
    for key, value in row["config"].items():
        if key in CONFIG["reduced"]:
            assert CONFIG["published"][key] == value, key
        else:
            assert CONFIG[key] == value, key


def test_cell_configuration_and_traffic_are_as_named():
    cell = harness.find_cell(MANIFEST, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("zaya1_8b_ep2", "offline_tokens_b1_s4096", 1)
    traffic = harness.load_json(REPO / "benchmark" / "traffic" / "offline_tokens_b1_s4096.json")
    assert (traffic["batch"], traffic["seq_len"], traffic["pool_batches"], traffic["chain_len"]) == (1, 4096, 16, 4)
    assert traffic["sample_sequences"] == 1 and traffic["trace_seconds"] == 3 and traffic["driver"] == "offline_tokens"
    assert [m["name"] for m in MANIFEST["per_layer"] if m.get("workloads") == [CELL]] == NEW_METRICS
    assert MANIFEST["workloads"][-1] == cell and MANIFEST["configs"][-1]["name"] == "zaya1_8b_ep2"  # appended
    tol = CONFIG["tolerance"]
    assert all(0 < tol[key] < 0.05 for key in ("rel_rms", "rel_max", "route_margin")) and 0 < tol["flip_share"] <= 0.005
    assert tol["min_clear_share"] >= 0.2


def test_manifest_rules_hold_for_the_repos_manifest():
    manifest_rules.check_all(MANIFEST, REPO)


def test_adapter_builds_the_programs_preset_from_the_file():
    from cuda_mpi_gpu_cluster_programming_tpu.models import cca_moe
    from cuda_mpi_gpu_cluster_programming_tpu.ops import scopes

    adapter = harness.load_plugin("adapters", "cca_moe")
    assert adapter.model_config(CONFIG) == cca_moe.ZAYA1_EP2_SHARE
    assert adapter.input_shape(CONFIG, 1) == (1, 4096) == cca_moe.PRESETS["zaya1_ep2"][1:]
    assert [layer["name"] for layer in CONFIG["layers"]] == list(scopes.CCA_MOE_LAYERS) == LAYERS


# ---- operations, bytes and parameters, reckoned by hand ------------------------


def test_parameter_counts_by_hand():
    # W_q and W_o 2048 x 1024 each, W_k 2048 x 256, W_v1 and W_v2 2048 x 128 each
    assert shapes.cca_proj_params(CONFIG) == 2 * 2_097_152 + 524_288 + 2 * 262_144 == 5_242_880
    assert shapes.cca_conv_params(CONFIG) == 2 * 10 * 128 * 128 == 327_680
    assert shapes.cca_small_params(CONFIG) == 2 * 1280 + 1280 + 1280 + 2  # the depthwise taps, two biases, tau
    assert shapes.router_matmul_params(CONFIG) == 2048 * 256 + 2 * 256 * 256 + 256 * 17 == 659_712
    # three biases, the norm, gamma, the selection bias
    assert shapes.router_small_params(CONFIG) == 3 * 256 + 256 + 1 + 17
    assert shapes.expert_params(CONFIG) == 3 * 2048 * 2048 == 12_582_912
    assert shapes.layer_params(CONFIG) == 106_920_212
    total = shapes.param_count(CONFIG)
    assert total == 40 * 106_920_212 + 131_136 * 2048 + 2048  # the tied embedding once
    assert round(total / 1e9, 3) == 4.545 and round(2 * total / 1e9, 2) == 9.09  # bf16: 9.09 GB


def test_parameter_count_is_the_programs():
    from cuda_mpi_gpu_cluster_programming_tpu.models import cca_moe

    assert shapes.param_count(CONFIG) == cca_moe.param_count(cca_moe.ZAYA1_EP2_SHARE)
    adapter = harness.load_plugin("adapters", "cca_moe")
    assert shapes.param_count(_tiny_config()) == cca_moe.param_count(adapter.model_config(_tiny_config()))


def test_step_operations_and_bytes_by_hand():
    assert round(shapes.matmul_flops_per_image(CONFIG) / 1e12, 2) == 7.56  # one sequence a step
    assert shapes.cca_proj_flops(CONFIG, 1) == 2 * 4096 * 5_242_880
    assert round(shapes.cca_proj_flops(CONFIG, 1) / 1e9, 1) == 42.9
    # the residual read and written in float32, the matrices, 12 latent heads written and 8 read in bf16
    assert shapes.cca_proj_bytes(CONFIG, 1) == 2 * 4 * 4096 * 2048 + 2 * 5_242_880 + 2 * 4096 * 128 * 20
    # the causal half, 8 query heads of 128; keys and values of 2 heads read once
    assert shapes.cca_attn_flops(CONFIG, 1) == 2 * 8 * 4096 * 4096 * 256 / 2
    assert shapes.cca_attn_bytes(CONFIG, 1) == 2 * 4096 * 128 * (2 * 8 + 2 * 2)
    pairs = shapes.expected_pairs_per_step(CONFIG, 1)
    assert pairs == pytest.approx(40 * 4096 * 8 / 17) and shapes.held_share(CONFIG) == 8 / 17
    assert round(shapes.experts_flops(CONFIG, pairs) / 1e12, 2) == 1.94  # 48.5 GFLOP a layer
    assert shapes.experts_bytes(CONFIG, 0) == 40 * 8 * 12_582_912 * 2  # every held expert read once
    assert shapes.min_bytes_per_step(CONFIG, 1) == 2 * shapes.param_count(CONFIG) + 4096 * 4 + 4096 * 131136 * 4
    assert shapes.n_moe_layers(CONFIG) == 40


def test_forward_roofline_reads_the_family_through_the_names_it_calls():
    read = harness.load_plugin("layer_metrics", "kernels.forward_roofline").read
    ctx = types.SimpleNamespace(
        trace=types.SimpleNamespace(step_durations_ms=lambda: [100.0]), config=CONFIG, shapes=shapes,
        peaks=harness.peak_row("TPU v5 lite"), counters={"offline.batch": 1}, devices=[None], log=lambda m: None,
    )
    assert read(ctx) == pytest.approx(100 * (shapes.matmul_flops_per_image(CONFIG) / 197e12) / 0.1)


# ---- the cell, end to end on the CPU at a tiny size -----------------------------


def _tiny_config() -> dict:
    cfg = dict(CONFIG)
    cfg.update(
        hidden_size=64, num_attention_heads=4, num_key_value_heads=2, head_dim=16, moe_intermediate_size=32,
        num_experts=2, router_hidden_size=16, vocab_size=128, seq_len=64, num_layers=4,
        program_tiles={"attn_block": 16, "expert_tile_rows": 8, "expert_chunk_rows": 16, "expert_span_rows": 32},
        published=dict(CONFIG["published"], num_experts=4),
        # a rehearsal of the control flow: at this width a rounding is a part in a hundred
        tolerance=dict(CONFIG["tolerance"], rel_max=0.5, rel_rms=0.5, route_margin=0.002, min_clear_share=0.05),
    )
    return cfg


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    """The benchmark copied, and the tiny cell added as a later PR adds one:
    a configuration file, a traffic file and entries, no edit."""
    root = tmp_path_factory.mktemp("bench_cca_moe")
    bench_tiny.copy_benchmark(root)
    bench = root / "benchmark"
    (bench / "configs" / "tiny_cca_moe.json").write_text(json.dumps(_tiny_config()))
    traffic = json.loads((bench / "traffic" / "offline_tokens_b1_s4096.json").read_text())
    traffic.update(batch=2, seq_len=64, pool_batches=3, chain_len=2, trace_seconds=0.2)
    (bench / "traffic" / "tiny_tokens_b2_s64.json").write_text(json.dumps(traffic))
    manifest = json.loads((root / "BENCHMARK.json").read_text())
    manifest["configs"].append({
        "name": "tiny_cca_moe", "source": CONFIG["source"], "file": "benchmark/configs/tiny_cca_moe.json",
        "reduced": CONFIG["reduced"], "why": "CPU rehearsal size",
    })
    manifest["workloads"].append({
        "name": "tiny_cca_prefill", "config": "tiny_cca_moe", "traffic": "tiny_tokens_b2_s64", "chips": 1,
        "why": f"{CELL} at a CPU rehearsal size",
    })
    for metric in manifest["per_layer"]:
        if CELL in metric.get("workloads", []):
            metric["workloads"].append("tiny_cca_prefill")
    (root / "BENCHMARK.json").write_text(json.dumps(manifest))
    return root


@pytest.mark.parametrize("trace", [0, 1])
def test_the_cell_runs_end_to_end_on_the_cpu_at_a_tiny_size(copy, trace):
    proc = bench_tiny.run_cell(copy, "tiny_cca_prefill", "--rehearse", trace=trace, seed=2**31 + 17)
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert line["metrics"] == {} and line["device"]["platform"] == "cpu"
    if trace:
        assert "build.compile_s" in line["rehearsal"] and "routing of one batch" in proc.stdout
        assert line["rehearsal"]["moe.skip_share"] > 0  # a counter: read on the CPU too
        assert "moe.skip_share" in proc.stdout and "router.state_rms_last" in proc.stdout
    else:
        assert set(line["rehearsal"]) == {"images_per_s", "setup_s"}
        assert "tokens/s" in proc.stdout and "routing slack" in proc.stdout


# ---- scopes: in the compiled program, and through the per-layer reduction --------


@pytest.fixture(scope="module")
def tiny_step_text():
    adapter = harness.load_plugin("adapters", "cca_moe")
    cfg = _tiny_config()
    params = jax.eval_shape(lambda: adapter.make_params(cfg, 0))
    ids = jax.ShapeDtypeStruct((2, 64), jnp.int32)
    return cfg, adapter.build_forward(cfg).lower(params, ids).compile().as_text()


def test_every_scope_of_the_compiled_forward_is_in_the_configurations_layers(tiny_step_text):
    import re

    cfg, text = tiny_step_text
    names = layer_times.layer_names(cfg)
    assert names == LAYERS
    scopes, _mixed = layer_times.scope_map(text, names)
    assert set(scopes.values()) == set(names)
    # the loop over the layers stands under its own scope, its body's operations under theirs
    loops = [n for n, s in scopes.items() if s == "layer_loop" and n.lstrip("%").startswith("while")]
    assert loops
    # and no dotted component of any op_name is a scope the file does not list
    parts = {p for path in re.findall(r'op_name="((?:[^"\\]|\\.)*)"', text) for p in path.split("/")[:-1]}
    assert {p for p in parts if p.split(".")[0] in ("cca", "moe")} <= set(names)


def test_the_new_readers_on_a_synthetic_trace(tiny_step_text):
    """One operation per instruction of the compiled tiny program, 1 us each,
    inside two runs of the step program: the new readers find their scopes, a
    share of a roofline stays a share, every operation is scoped; and without
    a trace, a scope or the program's gauge they return nothing and do not
    raise."""
    from cuda_mpi_gpu_cluster_programming_tpu.observability import metrics

    cfg, text = tiny_step_text
    scopes, _mixed = layer_times.scope_map(text, layer_times.layer_names(cfg))
    containers = [n for n in scopes if n.lstrip("%").split(".")[0] in scope_roofline.CONTAINERS]
    ops, t = [], 1000
    for _run in range(2):
        for name in scopes:
            if name not in containers:
                ops.append([f"{name} f32[2]", "fusion", t, 1000])
                t += 1000
    half = (t - 1000) // 2
    modules = [["jit_fwd_bf16(1)", 1000, half], ["jit_fwd_bf16(1)", 1000 + half, half]]
    trace = {"devices": {"/device:TPU:0": {"ops": ops, "modules": modules}}, "host": []}
    logs = []
    adapter = harness.load_plugin("adapters", "cca_moe")
    ctx = types.SimpleNamespace(
        trace=trace_reduce.Reduced(trace), peaks=harness.peak_row("TPU v5 lite"), config=cfg,
        shapes=harness.load_plugin("shapes", "cca_moe"), adapter=adapter,
        devices=[None], counters={"offline.batch": 2}, samples={}, spans={}, log=logs.append, step_hlo_text=text,
    )
    read = {name: harness.load_plugin("layer_metrics", name).read for name in NEW_METRICS + ["kernels.scoped_share"]}
    assert read["kernels.scoped_share"](ctx) == pytest.approx(100.0)
    for name in ("kernels.cca_proj_roofline", "kernels.cca_attn_roofline", "kernels.experts_top1_roofline"):
        assert 0 < read[name](ctx) < 100, name
    assert read["kernels.cca_mix_ms"](ctx) > 0 and read["kernels.router_mlp_ms"](ctx) > 0
    assert all(any(f"roofline of {scope}" in line for line in logs) for scope in ("cca.attn", "moe.experts"))
    metrics.registry().reset()
    assert read["moe.skip_share"](ctx) is None  # the gauge was never filled
    metrics.registry().gauge(metrics.MOE_SKIP_SHARE).set(0.0625)
    assert read["moe.skip_share"](ctx) == pytest.approx(6.25)
    metrics.registry().reset()
    bare = types.SimpleNamespace(
        trace=None, counters={}, peaks=None, config=cfg, spans={}, samples={}, shapes=ctx.shapes, adapter=adapter
    )
    assert all(read[name](bare) is None for name in NEW_METRICS)
    # a program that carries no such scope (another family's, the parent's) reads 0 before any shape function is asked
    kept = {key: value for key, value in vars(ctx).items() if key != "layer_times"}  # the split is made anew
    unscoped = types.SimpleNamespace(**{**kept, "step_hlo_text": "", "shapes": None})
    assert read["kernels.cca_proj_roofline"](unscoped) == 0.0 and read["kernels.cca_attn_roofline"](unscoped) == 0.0
