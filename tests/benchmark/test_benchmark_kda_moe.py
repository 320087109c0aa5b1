"""The hybrid linear-attention mixture-of-experts family in the benchmark: its
configuration file against the catalog row it is cut from and against the
program's preset; its shape functions against counts reckoned by hand; its
cell run end to end on the CPU at a tiny size in a temporary copy; its scopes
in the compiled program and its readers on a synthetic trace; the manifest's
rules on the repo's manifest as it now stands."""

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
from benchmark.shapes import kda_moe as shapes  # noqa: E402

MANIFEST = json.loads((REPO / "BENCHMARK.json").read_text())
CELL = "solar_open2_prefill_s8192"
CONFIG = json.loads((REPO / "benchmark" / "configs" / "solar_open2_ep8.json").read_text())
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")
# the published config.json of the source, as the catalog row holds it
PUBLISHED = {
    "model_type": "solar_open2", "partial_rotary_factor": 1, "hidden_size": 4096, "num_hidden_layers": 48,
    "num_attention_heads": 64, "head_dim": 128, "num_key_value_heads": 8, "intermediate_size": 10240,
    "moe_intermediate_size": 1280, "rms_norm_eps": 1e-05, "rope_theta": 10000, "tie_word_embeddings": False,
    "max_position_embeddings": 1048576, "first_k_dense_replace": 0, "use_rope": False, "gqa_interval": 3,
    "use_gqa_gate": True, "kda_use_full_proj": False, "kda_allow_neg_eigval": True, "n_shared_experts": 1,
    "norm_topk_prob": True, "routed_scaling_factor": 1, "num_experts_per_tok": 8,
    "linear_attn_config": {"short_conv_kernel_size": 4, "head_dim": 128, "num_heads": 64, "num_kv_heads": None},
}
CUT = {"gqa_layers": (list(range(0, 48, 4)), [0]), "n_routed_experts": (320, 40), "vocab_size": (196608, 24576)}
NEW_METRICS = [
    "kernels.kda_scan_roofline", "kernels.kda_proj_roofline", "kernels.kda_mix_ms", "kernels.gqa_attn_roofline",
]


# ---- the configuration file ---------------------------------------------------


def test_every_width_is_the_published_one_and_every_cut_is_listed():
    for key, value in PUBLISHED.items():
        assert CONFIG[key] == value, key
    for key, (published, here) in CUT.items():
        assert CONFIG[key] == here and CONFIG["published"][key] == published and key in CONFIG["reduced"]
    assert CONFIG["reduced"] == ["num_layers", "gqa_layers", "n_routed_experts", "vocab_size"]
    assert CONFIG["num_layers"] == 4 and CONFIG["published"]["num_hidden_layers"] == 48
    assert CONFIG["deployment"]["expert_parallel_chips"] == 8
    assert CONFIG["n_routed_experts"] * 8 == CONFIG["published"]["n_routed_experts"]
    assert CONFIG["vocab_size"] * 8 == CONFIG["published"]["vocab_size"]  # an eighth: the floor
    assert CONFIG["compute"] == "bf16" and CONFIG["chips"] == 1 and CONFIG["family"] == "kda_moe"
    # the floors of a cut: one whole period (a softmax layer and gqa_interval linear ones), 8 experts
    assert CONFIG["num_layers"] == 1 + CONFIG["gqa_interval"] >= 4 and CONFIG["n_routed_experts"] >= 8
    # what the source does not give is written down as assumed
    assert {"router", "gqa_gate", "kda_projections", "short_conv", "decay", "experts_held"} <= set(CONFIG["assumed"])
    assert (CONFIG["n_group"], CONFIG["topk_group"], CONFIG["scoring_func"]) == (1, 1, "sigmoid")
    assert "3.308B parameters = 6.62 GB" in CONFIG["deployment"]["parameters_here"]


def test_the_file_holds_every_number_of_the_catalog_row_or_lists_the_key():
    if not CATALOG.is_file():
        pytest.skip("no catalog beside the guides here")
    row = next(json.loads(l) for l in CATALOG.read_text().splitlines() if '"Solar-Open2-250B"' in l)
    assert row["source_url"] in CONFIG["source"] and row["config"] == {**PUBLISHED, **{k: v[0] for k, v in CUT.items()}}
    for key, value in row["config"].items():
        if key in CONFIG["reduced"]:
            assert CONFIG["published"][key] == value, key
        else:
            assert CONFIG[key] == value, key


def test_cell_configuration_and_traffic_are_as_named():
    cell = harness.find_cell(MANIFEST, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("solar_open2_ep8", "offline_tokens_b2_s8192", 1)
    traffic = harness.load_json(REPO / "benchmark" / "traffic" / "offline_tokens_b2_s8192.json")
    assert (traffic["batch"], traffic["seq_len"], traffic["pool_batches"], traffic["chain_len"]) == (2, 8192, 16, 2)
    assert traffic["sample_sequences"] == 1 and traffic["trace_seconds"] == 3 and traffic["driver"] == "offline_tokens"
    assert [m["name"] for m in MANIFEST["per_layer"] if m.get("workloads") == [CELL]] == NEW_METRICS
    tol = CONFIG["tolerance"]
    assert 0 < tol["rel_rms"] < tol["rel_max"] < 0.1 and 0 < tol["route_margin"] < 0.05


def test_manifest_rules_hold_for_the_repos_manifest():
    manifest_rules.check_all(MANIFEST, REPO)


def test_adapter_builds_the_programs_preset_from_the_file():
    from cuda_mpi_gpu_cluster_programming_tpu.models import kda_moe
    from cuda_mpi_gpu_cluster_programming_tpu.ops import scopes

    adapter = harness.load_plugin("adapters", "kda_moe")
    assert adapter.model_config(CONFIG) == kda_moe.SOLAR_EP8_SHARE
    assert adapter.input_shape(CONFIG, 2) == (2, 8192) == kda_moe.PRESETS["solar_ep8"][1:]
    assert [layer["name"] for layer in CONFIG["layers"]] == list(scopes.KDA_MOE_LAYERS)


# ---- operations, bytes and parameters, reckoned by hand ------------------------


def test_parameter_counts_by_hand():
    # softmax layer: q, gate, o 3 x 4096 x 8192, k and v 2 x 4096 x 1024
    assert shapes.gqa_params(CONFIG) == 3 * 33_554_432 + 2 * 4_194_304 == 109_051_904
    # linear layer: q, k, v, o; two pairs 4096x128 + 128x8192; beta 4096x64; filters, A_log, dt_bias, gain
    assert shapes.kda_matmul_params(CONFIG) == 4 * 33_554_432 + 2 * (524_288 + 1_048_576) + 262_144
    assert shapes.kda_small_params(CONFIG) == 3 * 4 * 8192 + 64 + 8192 + 128
    assert shapes.expert_params(CONFIG) == 3 * 4096 * 1280 == 15_728_640
    assert shapes.router_params(CONFIG) == 4096 * 320
    assert shapes.moe_matmul_params(CONFIG) == 1_310_720 + 41 * 15_728_640
    total = shapes.param_count(CONFIG)
    assert round(total / 1e9, 3) == 3.308 and round(2 * total / 1e9, 2) == 6.62  # bf16: 6.62 GB


def test_parameter_count_is_the_programs():
    from cuda_mpi_gpu_cluster_programming_tpu.models import kda_moe

    assert shapes.param_count(CONFIG) == kda_moe.param_count(kda_moe.SOLAR_EP8_SHARE)
    adapter = harness.load_plugin("adapters", "kda_moe")
    assert shapes.param_count(_tiny_config()) == kda_moe.param_count(adapter.model_config(_tiny_config()))


def test_step_operations_and_bytes_by_hand():
    assert round(2 * shapes.matmul_flops_per_image(CONFIG) / 1e12, 1) == 27.3  # 2 sequences a step
    # the scan is counted as the recurrence: 7 d^2 a token and head, whatever the chunk
    assert shapes.kda_scan_flops(CONFIG, 2) == 7 * 16384 * 64 * 128 * 128
    assert shapes.kda_scan_bytes(CONFIG, 2) == 16384 * 64 * (4 * 128 * 2 + 128 * 4 + 4)
    assert round(shapes.kda_scan_flops(CONFIG, 2) / 1e12, 2) == 0.12
    assert round(shapes.kda_scan_bytes(CONFIG, 2) / 1e9, 2) == 1.61
    # the softmax layer: the causal half, 64 query heads; keys and values of 8 heads read once
    assert shapes.gqa_attn_flops(CONFIG, 2) == 2 * 2 * 64 * 8192 * 8192 * 256 / 2
    assert shapes.gqa_attn_bytes(CONFIG, 2) == 2 * 16384 * 128 * (2 * 64 + 2 * 8)
    assert round(shapes.kda_proj_flops(CONFIG, 2) / 1e12, 2) == 4.51  # x 3 layers = 13.5 T
    pairs = shapes.expected_pairs_per_step(CONFIG, 2)
    assert pairs == 4 * 16384 * 8 * 40 / 320 == 65536
    assert round(shapes.experts_flops(CONFIG, pairs) / 1e12, 2) == 2.06
    assert shapes.experts_bytes(CONFIG, 0) == 4 * 40 * 15_728_640 * 2  # every held expert read once
    assert shapes.min_bytes_per_step(CONFIG, 2) == 2 * shapes.param_count(CONFIG) + 16384 * 4 + 16384 * 24576 * 4
    assert (shapes.n_moe_layers(CONFIG), shapes.n_gqa_layers(CONFIG), shapes.n_kda_layers(CONFIG)) == (4, 1, 3)


def test_forward_roofline_reads_the_family_through_the_names_it_calls():
    read = harness.load_plugin("layer_metrics", "kernels.forward_roofline").read
    ctx = types.SimpleNamespace(
        trace=types.SimpleNamespace(step_durations_ms=lambda: [300.0]), config=CONFIG, shapes=shapes,
        peaks=harness.peak_row("TPU v5 lite"), counters={"offline.batch": 2}, devices=[None], log=lambda m: None,
    )
    assert read(ctx) == pytest.approx(100 * (2 * shapes.matmul_flops_per_image(CONFIG) / 197e12) / 0.3)


# ---- the cell, end to end on the CPU at a tiny size -----------------------------


def _tiny_config() -> dict:
    cfg = dict(CONFIG)
    cfg.update(
        hidden_size=64, num_attention_heads=4, num_key_value_heads=2, head_dim=16,
        linear_attn_config=dict(CONFIG["linear_attn_config"], head_dim=16, num_heads=4),
        moe_intermediate_size=32, num_experts_per_tok=4, n_routed_experts=4, vocab_size=512, seq_len=64,
        program_tiles={"attn_block": 16, "kda_chunk": 16, "kda_head_block": 2, "expert_tile_rows": 8,
                       "expert_chunk_rows": 16, "expert_span_rows": 32},
        published=dict(CONFIG["published"], n_routed_experts=16),
        # a rehearsal of the control flow: at this width a rounding is a part in a hundred
        tolerance=dict(CONFIG["tolerance"], rel_max=0.5, rel_rms=0.5, route_margin=0.02, min_clear_share=0.05),
    )
    return cfg


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    """The benchmark copied, and the tiny cell added as a later PR adds one:
    a configuration file, a traffic file and entries, no edit."""
    root = tmp_path_factory.mktemp("bench_kda_moe")
    bench_tiny.copy_benchmark(root)
    bench = root / "benchmark"
    (bench / "configs" / "tiny_kda_moe.json").write_text(json.dumps(_tiny_config()))
    traffic = json.loads((bench / "traffic" / "offline_tokens_b2_s8192.json").read_text())
    traffic.update(seq_len=64, pool_batches=3, trace_seconds=0.2)
    (bench / "traffic" / "tiny_tokens_s64.json").write_text(json.dumps(traffic))
    manifest = json.loads((root / "BENCHMARK.json").read_text())
    manifest["configs"].append({
        "name": "tiny_kda_moe", "source": CONFIG["source"], "file": "benchmark/configs/tiny_kda_moe.json",
        "reduced": CONFIG["reduced"], "why": "CPU rehearsal size",
    })
    manifest["workloads"].append({
        "name": "tiny_kda_prefill", "config": "tiny_kda_moe", "traffic": "tiny_tokens_s64", "chips": 1,
        "why": f"{CELL} at a CPU rehearsal size",
    })
    for metric in manifest["per_layer"]:
        if CELL in metric.get("workloads", []):
            metric["workloads"].append("tiny_kda_prefill")
    (root / "BENCHMARK.json").write_text(json.dumps(manifest))
    return root


@pytest.mark.parametrize("trace", [0, 1])
def test_the_cell_runs_end_to_end_on_the_cpu_at_a_tiny_size(copy, trace):
    proc = bench_tiny.run_cell(copy, "tiny_kda_prefill", "--rehearse", trace=trace, seed=2**31 + 11)
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert line["metrics"] == {} and line["device"]["platform"] == "cpu"
    if trace:
        assert "build.compile_s" in line["rehearsal"] and "routing of one batch" in proc.stdout
        assert "kda.chunk_log_decay_min" in proc.stdout and "kda.beta_mean" in proc.stdout
    else:
        assert set(line["rehearsal"]) == {"images_per_s", "setup_s"}
        assert "tokens/s" in proc.stdout and "routing slack" in proc.stdout


# ---- scopes: in the compiled program, and through the per-layer reduction --------


@pytest.fixture(scope="module")
def tiny_step_text():
    adapter = harness.load_plugin("adapters", "kda_moe")
    cfg = _tiny_config()
    params = jax.eval_shape(lambda: adapter.make_params(cfg, 0))
    ids = jax.ShapeDtypeStruct((2, 64), jnp.int32)
    return cfg, adapter.build_forward(cfg).lower(params, ids).compile().as_text()


def test_every_scope_of_the_compiled_forward_is_in_the_configurations_layers(tiny_step_text):
    import re

    cfg, text = tiny_step_text
    names = layer_times.layer_names(cfg)
    assert names == ["embed", "gqa.proj", "gqa.attn", "kda.proj", "kda.mix", "kda.scan",
                     "moe.route", "moe.experts", "moe.shared", "head"]
    scopes, _mixed = layer_times.scope_map(text, names)
    assert set(scopes.values()) == set(names)
    # and no dotted component of any op_name is a scope the file does not list
    parts = {p for path in re.findall(r'op_name="((?:[^"\\]|\\.)*)"', text) for p in path.split("/")[:-1]}
    assert {p for p in parts if p.split(".")[0] in ("gqa", "kda", "moe")} <= set(names)


def test_the_new_readers_on_a_synthetic_trace(tiny_step_text):
    """One operation per instruction of the compiled tiny program, 1 us each,
    inside two runs of the step program: the four new readers find their
    scopes, a share of a roofline stays a share, every operation is scoped;
    and without a trace, or a scope, they return nothing and do not raise."""
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
    ctx = types.SimpleNamespace(
        trace=trace_reduce.Reduced(trace), peaks=harness.peak_row("TPU v5 lite"), config=cfg,
        shapes=harness.load_plugin("shapes", "kda_moe"), adapter=harness.load_plugin("adapters", "kda_moe"),
        devices=[None], counters={"offline.batch": 2}, samples={}, spans={}, log=logs.append, step_hlo_text=text,
    )
    read = {name: harness.load_plugin("layer_metrics", name).read for name in NEW_METRICS + ["kernels.scoped_share"]}
    assert read["kernels.scoped_share"](ctx) == pytest.approx(100.0)
    for name in ("kernels.kda_scan_roofline", "kernels.kda_proj_roofline", "kernels.gqa_attn_roofline"):
        assert 0 < read[name](ctx) < 100, name
    assert read["kernels.kda_mix_ms"](ctx) > 0
    assert any("roofline of kda.scan" in line and "memory-bound" in line for line in logs)
    bare = types.SimpleNamespace(trace=None, counters={}, peaks=None, config=cfg, spans={}, samples={})
    assert all(read[name](bare) is None for name in NEW_METRICS)
