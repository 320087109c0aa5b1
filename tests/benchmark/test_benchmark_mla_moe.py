"""The latent-attention mixture-of-experts family in the benchmark: its
configuration file against the catalog row it is cut from and against the
program's preset; its shape functions against counts reckoned by hand; its
cell run end to end on the CPU at a tiny size in a temporary copy; its scopes
in the compiled program and in the per-layer reduction of a synthetic trace;
the manifest's rules on the repo's manifest as it now stands."""

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
from benchmark import harness, layer_times, trace_reduce  # noqa: E402
from benchmark.shapes import mla_moe as shapes  # noqa: E402

MANIFEST = json.loads((REPO / "BENCHMARK.json").read_text())
CELL = "dots_vlm1_prefill_s4096"
CONFIG = json.loads((REPO / "benchmark" / "configs" / "dots_vlm1_lm_ep16.json").read_text())
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")
# the published config.json of the source, as the catalog row holds it
PUBLISHED = {
    "hidden_size": 7168, "intermediate_size": 18432, "moe_intermediate_size": 2048,
    "num_attention_heads": 128, "num_key_value_heads": 128, "q_lora_rank": 1536,
    "kv_lora_rank": 512, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "v_head_dim": 128,
    "n_group": 8, "topk_group": 4, "num_experts_per_tok": 8, "n_shared_experts": 1,
    "routed_scaling_factor": 2.5, "rope_theta": 10000, "rms_norm_eps": 1e-06,
    "num_hidden_layers": 61, "max_position_embeddings": 163840,
}
CUT = {"first_k_dense_replace": (3, 1), "n_routed_experts": (256, 16), "vocab_size": (129280, 16160),
       "num_nextn_predict_layers": (1, 0)}


# ---- the configuration file ---------------------------------------------------


def test_every_width_is_the_published_one_and_every_cut_is_listed():
    for key, value in PUBLISHED.items():
        assert CONFIG[key] == value, key
    assert CONFIG["rope_scaling"] == {
        "beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 1, "mscale_all_dim": 1,
        "original_max_position_embeddings": 4096, "type": "yarn",
    }
    for key, (published, here) in CUT.items():
        assert CONFIG[key] == here and CONFIG["published"][key] == published and key in CONFIG["reduced"]
    assert CONFIG["num_layers"] == 5 and "num_layers" in CONFIG["reduced"] and "vision_tower" in CONFIG["reduced"]
    assert CONFIG["deployment"]["expert_parallel_chips"] == 16
    assert CONFIG["n_routed_experts"] * 16 == CONFIG["published"]["n_routed_experts"]
    assert CONFIG["vocab_size"] * 8 == CONFIG["published"]["vocab_size"]  # an eighth: the floor
    assert CONFIG["compute"] == "bf16" and CONFIG["chips"] == 1 and CONFIG["family"] == "mla_moe"
    # the floors of a cut: a whole period and four layers after the dense ones, 8 experts
    assert CONFIG["num_layers"] - CONFIG["first_k_dense_replace"] >= 4 and CONFIG["n_routed_experts"] >= 8


def test_the_file_holds_every_number_of_the_catalog_row_or_lists_the_key():
    if not CATALOG.is_file():
        pytest.skip("no catalog beside the guides here")
    row = next(json.loads(l) for l in CATALOG.read_text().splitlines() if '"dots.vlm1.inst"' in l)
    assert row["source_url"] in CONFIG["source"]
    for key, value in row["config"].items():
        if key in CONFIG["reduced"]:
            assert CONFIG["published"][key] == value, key
        else:
            assert CONFIG[key] == value, key


def test_cell_configuration_and_traffic_are_as_named():
    cell = harness.find_cell(MANIFEST, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("dots_vlm1_lm_ep16", "offline_tokens_b2_s4096", 1)
    traffic = harness.load_json(REPO / "benchmark" / "traffic" / "offline_tokens_b2_s4096.json")
    assert (traffic["batch"], traffic["seq_len"], traffic["pool_batches"], traffic["chain_len"]) == (2, 4096, 16, 2)
    assert traffic["sample_sequences"] == 1 and traffic["trace_seconds"] == 3 and traffic["driver"] == "offline_tokens"
    mine = [m["name"] for m in MANIFEST["per_layer"] if m.get("workloads") == [CELL]]
    assert mine == [
        "kernels.mla_attn_roofline", "kernels.mla_proj_roofline", "kernels.moe_experts_roofline",
        "kernels.moe_route_ms", "moe.expert_load_max_over_mean",
    ]
    tol = CONFIG["tolerance"]
    assert 0 < tol["rel_rms"] < tol["rel_max"] < 0.1 and 0 < tol["route_margin"] < 0.05


def test_manifest_rules_hold_for_the_repos_manifest():
    manifest_rules.check_all(MANIFEST, REPO)


def test_adapter_builds_the_programs_preset_from_the_file():
    from cuda_mpi_gpu_cluster_programming_tpu.models import mla_moe

    adapter = harness.load_plugin("adapters", "mla_moe")
    assert adapter.model_config(CONFIG) == mla_moe.EP16_SHARE
    assert adapter.input_shape(CONFIG, 2) == (2, 4096)
    assert [layer["name"] for layer in CONFIG["layers"]] == list(
        __import__("cuda_mpi_gpu_cluster_programming_tpu.ops.scopes", fromlist=["x"]).MLA_MOE_LAYERS
    )


# ---- operations, bytes and parameters, reckoned by hand ------------------------


def test_parameter_counts_by_hand():
    # MLA: 7168x1536 + 1536x128x192 + 7168x576 + 512x128x256 + 128x128x7168
    assert shapes.mla_params(CONFIG) == 11_010_048 + 37_748_736 + 4_128_768 + 16_777_216 + 117_440_512
    assert shapes.expert_params(CONFIG) == 3 * 7168 * 2048 == 44_040_192
    assert shapes.router_params(CONFIG) == 7168 * 256
    moe_layer = shapes.moe_layer_matmul_params(CONFIG)  # 187.1M + 44.0M + 1.8M + 16 x 44.0M
    assert moe_layer == 187_105_280 + 44_040_192 + 1_835_008 + 704_643_072
    assert round(moe_layer / 1e6, 1) == 937.6
    assert round(shapes.dense_layer_matmul_params(CONFIG) / 1e6, 1) == 583.5
    total = shapes.param_count(CONFIG)
    assert round(total / 1e9, 3) == 4.566 and round(2 * total / 1e9, 2) == 9.13  # bf16: 9.13 GB


def test_parameter_count_is_the_programs():
    from cuda_mpi_gpu_cluster_programming_tpu.models import mla_moe

    assert shapes.param_count(CONFIG) == mla_moe.param_count(mla_moe.EP16_SHARE)
    small = dict(_tiny_config(), seq_len=32)
    adapter = harness.load_plugin("adapters", "mla_moe")
    assert shapes.param_count(small) == mla_moe.param_count(adapter.model_config(small))


def test_step_operations_and_bytes_by_hand():
    per_sequence = shapes.matmul_flops_per_image(CONFIG)
    assert round(2 * per_sequence / 1e12, 1) == 35.0  # 2 sequences a step
    # a MoE layer at 8,192 tokens: projections 3.07 T, causal scores 1.37 T, shared 0.72 T, routed 0.36 T
    assert round(shapes.proj_flops(CONFIG, 2) / 1e12, 2) == 3.07
    assert round(shapes.attn_flops(CONFIG, 2) / 1e12, 2) == 1.37
    assert shapes.attn_flops(CONFIG, 2) == 2 * 2 * 128 * 4096 * 4096 * (192 + 128) / 2
    assert round(2 * 8192 * shapes.expert_params(CONFIG) / 1e12, 2) == 0.72
    pairs = shapes.expected_pairs_per_step(CONFIG, 2)
    assert pairs == 4 * 8192 * 8 * 16 / 256 == 16384
    assert round(shapes.experts_flops(CONFIG, pairs / 4) / 1e12, 2) == 0.36
    assert shapes.min_bytes_per_step(CONFIG, 2) == 2 * shapes.param_count(CONFIG) + 8192 * 4 + 8192 * 16160 * 4
    assert shapes.experts_bytes(CONFIG, 0) == 4 * 16 * 44_040_192 * 2  # every held expert read once


def test_forward_roofline_reads_the_family_through_the_names_it_calls():
    read = harness.load_plugin("layer_metrics", "kernels.forward_roofline").read
    ctx = types.SimpleNamespace(
        trace=types.SimpleNamespace(step_durations_ms=lambda: [400.0]), config=CONFIG, shapes=shapes,
        peaks=harness.peak_row("TPU v5 lite"), counters={"offline.batch": 2}, devices=[None], log=lambda m: None,
    )
    assert read(ctx) == pytest.approx(100 * (2 * shapes.matmul_flops_per_image(CONFIG) / 197e12) / 0.4)


# ---- the cell, end to end on the CPU at a tiny size -----------------------------


def _tiny_config() -> dict:
    cfg = dict(CONFIG)
    cfg.update(
        hidden_size=64, num_attention_heads=4, q_lora_rank=32, kv_lora_rank=16, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16, intermediate_size=128, moe_intermediate_size=32, n_group=4,
        topk_group=2, num_experts_per_tok=4, n_routed_experts=4, vocab_size=512, num_layers=3, seq_len=32,
        program_tiles={"attn_block": 16, "expert_tile_rows": 8, "expert_chunk_rows": 16},
        published=dict(CONFIG["published"], n_routed_experts=16),
        # a rehearsal of the control flow: at this width a rounding is a part in a hundred
        tolerance=dict(CONFIG["tolerance"], rel_max=0.5, rel_rms=0.5, route_margin=0.03, min_clear_share=0.05),
    )
    return cfg


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    """The benchmark copied, and the tiny cell added as a later PR adds one:
    a configuration file, a traffic file and entries, no edit."""
    root = tmp_path_factory.mktemp("bench_mla_moe")
    bench_tiny.copy_benchmark(root)
    bench = root / "benchmark"
    (bench / "configs" / "tiny_mla_moe.json").write_text(json.dumps(_tiny_config()))
    traffic = json.loads((bench / "traffic" / "offline_tokens_b2_s4096.json").read_text())
    traffic.update(seq_len=32, pool_batches=3, trace_seconds=0.2)
    (bench / "traffic" / "tiny_tokens.json").write_text(json.dumps(traffic))
    manifest = json.loads((root / "BENCHMARK.json").read_text())
    manifest["configs"].append({
        "name": "tiny_mla_moe", "source": CONFIG["source"], "file": "benchmark/configs/tiny_mla_moe.json",
        "reduced": CONFIG["reduced"], "why": "CPU rehearsal size",
    })
    manifest["workloads"].append({
        "name": "tiny_prefill", "config": "tiny_mla_moe", "traffic": "tiny_tokens", "chips": 1,
        "why": f"{CELL} at a CPU rehearsal size",
    })
    for metric in manifest["per_layer"]:
        if CELL in metric.get("workloads", []):
            metric["workloads"].append("tiny_prefill")
    (root / "BENCHMARK.json").write_text(json.dumps(manifest))
    return root


@pytest.mark.parametrize("trace", [0, 1])
def test_the_cell_runs_end_to_end_on_the_cpu_at_a_tiny_size(copy, trace):
    proc = bench_tiny.run_cell(copy, "tiny_prefill", "--rehearse", trace=trace, seed=2**31 + 7)
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert line["metrics"] == {} and line["device"]["platform"] == "cpu"
    if trace:
        assert line["rehearsal"]["moe.expert_load_max_over_mean"] >= 1.0
        assert "build.compile_s" in line["rehearsal"] and "routing of one batch" in proc.stdout
    else:
        assert set(line["rehearsal"]) == {"images_per_s", "setup_s"}
        assert "tokens/s" in proc.stdout and "routing slack" in proc.stdout


def test_the_reference_in_bf16_is_held_to_the_same_check(copy):
    """``tools/precision_reading.py``: the reading a tolerance is set against."""
    import os
    import subprocess

    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(REPO),
               JAX_COMPILATION_CACHE_DIR=str(copy / ".xla_cache"))
    proc = subprocess.run(
        [*bench_tiny.on_two_cores(), sys.executable, "benchmark/tools/precision_reading.py",
         "--workload", "tiny_prefill", "--seed", "5", "--rehearse"],
        cwd=copy, env=env, capture_output=True, text=True, timeout=600,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "the reference in bf16 comes out" in proc.stdout and "rms(diff)/rms(ref)" in proc.stdout


# ---- scopes: in the compiled program, and through the per-layer reduction --------


@pytest.fixture(scope="module")
def tiny_step_text():
    adapter = harness.load_plugin("adapters", "mla_moe")
    cfg = _tiny_config()
    params = jax.eval_shape(lambda: adapter.make_params(cfg, 0))
    ids = jax.ShapeDtypeStruct((2, 32), jnp.int32)
    return cfg, adapter.build_forward(cfg).lower(params, ids).compile().as_text()


def test_every_scope_of_the_compiled_forward_is_in_the_configurations_layers(tiny_step_text):
    import re

    cfg, text = tiny_step_text
    names = layer_times.layer_names(cfg)
    assert names == ["embed", "mla.proj", "mla.attn", "dense_mlp", "moe.route", "moe.experts", "moe.shared", "head"]
    scopes, _mixed = layer_times.scope_map(text, names)
    assert set(scopes.values()) == set(names)
    # and no dotted component of any op_name is a scope the file does not list
    parts = {p for path in re.findall(r'op_name="((?:[^"\\]|\\.)*)"', text) for p in path.split("/")[:-1]}
    assert {p for p in parts if p.split(".")[0] in ("mla", "moe")} <= set(names)


def test_scoped_share_and_the_new_readers_on_a_synthetic_trace(tiny_step_text):
    """One operation per instruction of the compiled tiny program, 1 us each,
    inside two runs of the step program: the readers find their scopes, a
    loop's own duration is left out of a roofline's time, and a share of a
    roofline stays a share."""
    cfg, text = tiny_step_text
    scopes, _mixed = layer_times.scope_map(text, layer_times.layer_names(cfg))
    whiles = [n for n in scopes if n.lstrip("%").startswith("while")]
    assert whiles, "the chunk loop of the routed experts is a while"
    ops, t = [], 1000
    for _run in range(2):
        for name in scopes:
            if name in whiles:
                continue
            ops.append([f"{name} f32[2]", "fusion", t, 1000])
            t += 1000
        ops.append([f"{whiles[0]} s32[]", "while", t - 5000, 5000])  # lasts as long as its body
    half = (t - 1000) // 2
    modules = [["jit_fwd_bf16(1)", 1000, half], ["jit_fwd_bf16(1)", 1000 + half, half]]
    trace = {"devices": {"/device:TPU:0": {"ops": ops, "modules": modules}}, "host": []}
    logs = []
    ctx = types.SimpleNamespace(
        trace=trace_reduce.Reduced(trace), peaks=harness.peak_row("TPU v5 lite"), config=cfg,
        shapes=harness.load_plugin("shapes", "mla_moe"), adapter=harness.load_plugin("adapters", "mla_moe"),
        devices=[None], counters={"offline.batch": 2}, samples={}, spans={}, log=logs.append, step_hlo_text=text,
    )
    read = {m["name"]: harness.load_plugin("layer_metrics", m["name"]).read
            for m in MANIFEST["per_layer"] if CELL in m.get("workloads", [CELL])}
    assert read["kernels.scoped_share"](ctx) == pytest.approx(100.0)
    lt = layer_times.of(ctx)
    from benchmark import scope_roofline

    with_loop = lt.step_ms(layer_times.exactly("moe.experts"))
    assert scope_roofline.body_ms(ctx, lt, "moe.experts") == pytest.approx(with_loop - 0.005)
    assert scope_roofline.body_ms(ctx, lt, "mla.attn") == pytest.approx(lt.step_ms(layer_times.exactly("mla.attn")))
    for name in ("kernels.mla_attn_roofline", "kernels.mla_proj_roofline", "kernels.moe_experts_roofline"):
        assert 0 < read[name](ctx) < 100, name
    assert read["kernels.moe_route_ms"](ctx) > 0
    assert any("roofline of moe.experts" in line for line in logs)
    # the counter's reader: nothing before the program's routing statistics ran, then the gauge
    from cuda_mpi_gpu_cluster_programming_tpu.observability import metrics

    metrics.registry().reset()
    assert read["moe.expert_load_max_over_mean"](ctx) is None
    metrics.registry().gauge(metrics.MOE_EXPERT_LOAD_MAX_OVER_MEAN).set(1.25)
    assert read["moe.expert_load_max_over_mean"](ctx) == 1.25
    assert read["moe.expert_load_max_over_mean"](types.SimpleNamespace(trace=None, counters={})) is None
