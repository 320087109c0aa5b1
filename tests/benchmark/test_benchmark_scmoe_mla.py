"""The shortcut-connected mixture-of-experts family in the benchmark: its
configuration file against the catalog row it is cut from and against the
program's preset; its shape functions against counts reckoned by hand; its cell
run end to end on the CPU at a tiny size in a temporary copy; its scopes in the
compiled program and its readers on a synthetic trace; the manifest's rules on
the repo's manifest as it now stands."""

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
from benchmark.shapes import scmoe_mla as shapes  # noqa: E402

MANIFEST = json.loads((REPO / "BENCHMARK.json").read_text())
CELL = "longcat_flash_prefill_s4096"
NAME = "longcat_flash_omni_lm_ep32"
CONFIG = json.loads((REPO / "benchmark" / "configs" / f"{NAME}.json").read_text())
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")
# the catalog row's config, as the source publishes it, but for the three cut keys
PUBLISHED = {
    "attention_bias": False, "hidden_size": 6144, "ffn_hidden_size": 12288, "expert_ffn_hidden_size": 2048,
    "num_attention_heads": 64, "kv_lora_rank": 512, "q_lora_rank": 1536, "qk_rope_head_dim": 64, "v_head_dim": 128,
    "qk_nope_head_dim": 128, "mla_scale_q_lora": True, "mla_scale_kv_lora": True, "routed_scaling_factor": 6,
    "max_position_embeddings": 131072, "rms_norm_eps": 1e-05, "rope_theta": 10000000, "attention_method": "MLA",
    "zero_expert_num": 256, "zero_expert_type": "identity", "moe_topk": 12,
}
CUT = {"num_layers": (28, 4), "n_routed_experts": (512, 16), "vocab_size": (131072, 16384)}
NEW_METRICS = [
    "kernels.scmoe_dense_roofline", "kernels.scmoe_mla_proj_roofline", "kernels.scmoe_mla_attn_roofline",
    "kernels.scmoe_experts_roofline", "kernels.scmoe_route_ms", "kernels.scmoe_zero_ms", "moe.zero_pair_share",
    "moe.held_load_max_over_mean",
    # the phases of moe.experts and moe.route: the five readers of those phases, by import, under this cell's names
    "kernels.scmoe_products_roofline", "kernels.scmoe_gather_ms", "kernels.scmoe_combine_ms", "kernels.scmoe_sort_ms",
    "moe.held_tile_fill_share",
]
ROOFLINES = [name for name in NEW_METRICS if name.endswith("_roofline")]
PHASE_READERS = {  # this cell's name -> the reader whose body it is
    "kernels.scmoe_products_roofline": "kernels.moe_products_roofline",
    "kernels.scmoe_gather_ms": "kernels.moe_gather_ms",
    "kernels.scmoe_combine_ms": "kernels.moe_combine_ms", "kernels.scmoe_sort_ms": "kernels.moe_sort_ms",
    "moe.held_tile_fill_share": "moe.tile_fill_share",
}
LAYERS = ["embed", "layer_loop", "mla.proj", "mla.attn", "dense_mlp", "moe.route", "moe.experts", "moe.zero", "head"]


# ---- the configuration file ---------------------------------------------------


def test_every_width_is_the_published_one_and_every_cut_is_listed():
    for key, value in PUBLISHED.items():
        assert CONFIG[key] == value, key
    for key, (published, here) in CUT.items():
        assert CONFIG[key] == here and CONFIG["published"][key] == published and key in CONFIG["reduced"]
    assert CONFIG["reduced"] == ["num_layers", "n_routed_experts", "vocab_size", "omni_towers"]
    assert not any(manifest_rules.WIDTH.search(key) for key in CONFIG["reduced"])  # no width is cut
    assert CONFIG["deployment"]["expert_parallel_chips"] == 32
    assert CONFIG["n_routed_experts"] * 32 == CONFIG["published"]["n_routed_experts"]
    assert CONFIG["vocab_size"] * 8 == CONFIG["published"]["vocab_size"]
    assert CONFIG["compute"] == "bf16" and CONFIG["chips"] == 1 and CONFIG["family"] == "scmoe_mla"
    # the floors of a cut: at least 4 layers (the pattern's period is one layer), 8 experts, an eighth of the vocabulary
    assert CONFIG["num_layers"] >= 4 and CONFIG["n_routed_experts"] >= 8
    # what the source does not give is written down as assumed
    assert {"norm_topk_prob", "router_bias", "rotary", "mla_scales", "norm_gains", "weights", "selection_bias",
            "experts_held", "token_ids", "seq_len"} <= set(CONFIG["assumed"])
    assert "5.173B parameters = 10.35 GB" in CONFIG["deployment"]["parameters_here"]
    assert len(CONFIG["source"]) <= 200 and "language model" in CONFIG["source"]
    assert "one of 32 expert-parallel chips" in CONFIG["source"]
    assert all(len(str(v)) > 2 for v in CONFIG["assumed"].values())


def test_the_file_holds_every_number_of_the_catalog_row_or_lists_the_key():
    if not CATALOG.is_file():
        pytest.skip("no catalog beside the guides here")
    row = next(json.loads(l) for l in CATALOG.read_text().splitlines() if '"name": "LongCat-Flash-Omni"' in l)
    assert row["source_url"] in CONFIG["source"] and row["config"] == {**PUBLISHED, **{k: v[0] for k, v in CUT.items()}}
    for key, value in row["config"].items():
        if key in CONFIG["reduced"]:
            assert CONFIG["published"][key] == value, key
        else:
            assert CONFIG[key] == value, key


def test_cell_configuration_and_traffic_are_as_named():
    cell = harness.find_cell(MANIFEST, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (NAME, "offline_tokens_b2_s4096", 1)
    traffic = harness.load_json(REPO / "benchmark" / "traffic" / "offline_tokens_b2_s4096.json")
    assert (traffic["batch"], traffic["seq_len"], traffic["pool_batches"], traffic["chain_len"]) == (2, 4096, 16, 2)
    assert [m["name"] for m in MANIFEST["per_layer"] if m.get("workloads") == [CELL]] == NEW_METRICS
    # appended after what the benchmark had, in their order (a later PR appends after them: not "last")
    names = [m["name"] for m in MANIFEST["per_layer"]]
    first = names.index(NEW_METRICS[0])
    assert names[first : first + len(NEW_METRICS)] == NEW_METRICS and names[first - 1] == "moe.tile_fill_share"
    cells = [w["name"] for w in MANIFEST["workloads"]]
    assert cells[cells.index(CELL) - 1] == "zaya1_prefill_s4096"
    entry = next(c for c in MANIFEST["configs"] if c["name"] == NAME)
    assert entry["source"] == CONFIG["source"] and entry["reduced"] == CONFIG["reduced"]
    tol = CONFIG["tolerance"]
    assert all(0 < tol[key] < 0.05 for key in ("rel_rms", "rel_max")) and 0 < tol["flip_share"] <= 0.005
    assert 0 < tol["route_margin"] <= 5e-4  # a softmax score's scale, not a sigmoid's
    assert tol["min_clear_share"] >= 0.2 and "bf16" in tol["why"]


def test_manifest_rules_hold_for_the_repos_manifest():
    manifest_rules.check_all(MANIFEST, REPO)
    assert sum(w["chips"] == 4 for w in MANIFEST["workloads"]) == 1 and len(MANIFEST["workloads"]) >= 8


def test_adapter_builds_the_programs_preset_from_the_file():
    from cuda_mpi_gpu_cluster_programming_tpu.models import scmoe_mla
    from cuda_mpi_gpu_cluster_programming_tpu.ops import scopes

    adapter = harness.load_plugin("adapters", "scmoe_mla")
    assert adapter.model_config(CONFIG) == scmoe_mla.EP32_SHARE
    assert adapter.input_shape(CONFIG, 2) == (2, 4096) == scmoe_mla.PRESETS["longcat_ep32"][1:]
    assert [layer["name"] for layer in CONFIG["layers"]] == list(scopes.SCMOE_MLA_LAYERS) == LAYERS


# ---- operations, bytes and parameters, reckoned by hand ------------------------


def test_parameter_counts_by_hand():
    # q_a 6144 x 1536, q_b 1536 x 64 x 192, kv_a 6144 x 576, kv_b 512 x 64 x 256, o 64 x 128 x 6144
    assert shapes.mla_params(CONFIG) == 9_437_184 + 18_874_368 + 3_538_944 + 8_388_608 + 50_331_648 == 90_570_752
    assert shapes.dense_mlp_params(CONFIG) == 3 * 6144 * 12288 == 226_492_416
    assert shapes.router_params(CONFIG) == 6144 * 768 == 4_718_592 and shapes.router_outputs(CONFIG) == 768
    assert shapes.expert_params(CONFIG) == 3 * 6144 * 2048 == 37_748_736
    assert shapes.norm_params(CONFIG) == 2 * (2 * 6144 + 1536 + 512) == 28_672
    assert shapes.layer_matmul_params_outside_experts(CONFIG) == 2 * 90_570_752 + 2 * 226_492_416 + 4_718_592
    assert shapes.layer_params(CONFIG) == 638_844_928 + 16 * 37_748_736 + 28_672 + 768 == 1_242_854_144
    total = shapes.param_count(CONFIG)
    assert total == 4 * 1_242_854_144 + 2 * 16384 * 6144 + 6144 == 5_172_749_312
    assert round(total / 1e9, 3) == 5.173 and round(2 * total / 1e9, 2) == 10.35  # bf16: 10.35 GB
    # the whole model by the same counts: 28 layers of 512 experts, the whole vocabulary: the family's 560B
    whole = 28 * (638_844_928 + 28_672 + 768 + 512 * 37_748_736) + 2 * 131072 * 6144 + 6144
    assert round(whole / 1e9) == 561


def test_parameter_count_is_the_programs():
    from cuda_mpi_gpu_cluster_programming_tpu.models import scmoe_mla

    assert shapes.param_count(CONFIG) == scmoe_mla.param_count(scmoe_mla.EP32_SHARE) == 5_172_749_312
    adapter = harness.load_plugin("adapters", "scmoe_mla")
    assert shapes.param_count(_tiny_config()) == scmoe_mla.param_count(adapter.model_config(_tiny_config()))


def test_step_operations_and_bytes_by_hand():
    tokens = 2 * 4096
    assert shapes.proj_flops(CONFIG, 2) == 2 * tokens * 90_570_752  # one attention's projections: 1.48 TFLOP
    assert shapes.attn_flops(CONFIG, 2) == 2 * 2 * 64 * 4096 * 4096 * (192 + 128) / 2  # 0.69 TFLOP
    assert shapes.attn_bytes(CONFIG, 2) == 2 * 2 * 64 * 4096 * (2 * 192 + 2 * 128)
    assert shapes.dense_flops(CONFIG, 2) == 2 * tokens * 226_492_416  # 3.71 TFLOP
    assert shapes.dense_bytes(CONFIG, 2) == 2 * 226_492_416 + 2 * 4 * tokens * 6144
    pairs = shapes.expected_pairs_per_step(CONFIG, 2)
    assert pairs == 4 * tokens * 12 * 16 / 768 == 8192 and shapes.held_share(CONFIG) == 16 / 768
    assert shapes.experts_flops(CONFIG, pairs) == 2 * 8192 * 37_748_736  # 0.62 TFLOP
    assert shapes.experts_bytes(CONFIG, 0) == 4 * 16 * 37_748_736 * 2  # every held expert read once
    assert shapes.experts_bytes(CONFIG, 1) - shapes.experts_bytes(CONFIG, 0) == 6144 * (2 + 8)
    step = 2 * shapes.matmul_flops_per_image(CONFIG)
    by_parts = (
        8 * shapes.proj_flops(CONFIG, 2) + 8 * shapes.attn_flops(CONFIG, 2) + 8 * shapes.dense_flops(CONFIG, 2)
        + shapes.experts_flops(CONFIG, pairs) + 4 * 2 * tokens * 4_718_592 + 2 * tokens * 16384 * 6144
    )
    assert step == pytest.approx(by_parts) and round(step / 1e12, 1) == 49.6
    assert shapes.min_bytes_per_step(CONFIG, 2) == 2 * shapes.param_count(CONFIG) + tokens * 4 + tokens * 16384 * 4
    assert shapes.n_moe_layers(CONFIG) == 4 and shapes.SUBLAYERS == 2


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
        qk_rope_head_dim=8, v_head_dim=16, ffn_hidden_size=128, expert_ffn_hidden_size=32, n_routed_experts=2,
        zero_expert_num=4, moe_topk=3, num_experts_per_tok=3, vocab_size=256, seq_len=64, num_layers=2,
        program_tiles={"attn_block": 16, "expert_tile_rows": 8, "expert_chunk_rows": 16, "expert_span_rows": 32},
        published=dict(CONFIG["published"], n_routed_experts=8),
        # a rehearsal of the control flow: at this width a rounding is a part in a hundred
        tolerance=dict(CONFIG["tolerance"], rel_max=0.5, rel_rms=0.5, route_margin=0.002, min_clear_share=0.05),
    )
    return cfg


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    """The benchmark copied, and the tiny cell added as a later PR adds one:
    a configuration file, a traffic file and entries, no edit."""
    root = tmp_path_factory.mktemp("bench_scmoe_mla")
    bench_tiny.copy_benchmark(root)
    bench = root / "benchmark"
    (bench / "configs" / "tiny_scmoe_mla.json").write_text(json.dumps(_tiny_config()))
    traffic = json.loads((bench / "traffic" / "offline_tokens_b2_s4096.json").read_text())
    traffic.update(batch=2, seq_len=64, pool_batches=3, chain_len=2, trace_seconds=0.2)
    (bench / "traffic" / "tiny_tokens_b2_s64.json").write_text(json.dumps(traffic))
    manifest = json.loads((root / "BENCHMARK.json").read_text())
    manifest["configs"].append({
        "name": "tiny_scmoe_mla", "source": CONFIG["source"], "file": "benchmark/configs/tiny_scmoe_mla.json",
        "reduced": CONFIG["reduced"], "why": "CPU rehearsal size",
    })
    manifest["workloads"].append({
        "name": "tiny_scmoe_prefill", "config": "tiny_scmoe_mla", "traffic": "tiny_tokens_b2_s64", "chips": 1,
        "why": f"{CELL} at a CPU rehearsal size",
    })
    for metric in manifest["per_layer"]:
        if CELL in metric.get("workloads", []):
            metric["workloads"].append("tiny_scmoe_prefill")
    (root / "BENCHMARK.json").write_text(json.dumps(manifest))
    return root


@pytest.mark.parametrize("trace", [0, 1])
def test_the_cell_runs_end_to_end_on_the_cpu_at_a_tiny_size(copy, trace):
    proc = bench_tiny.run_cell(copy, "tiny_scmoe_prefill", "--rehearse", trace=trace, seed=2**31 + 37)
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert line["metrics"] == {} and line["device"]["platform"] == "cpu"
    if trace:
        assert "build.compile_s" in line["rehearsal"] and "routing of one batch" in proc.stdout
        # counters: read on the CPU too
        read = line["rehearsal"]
        assert 10 < read["moe.zero_pair_share"] < 60 and read["moe.held_load_max_over_mean"] >= 1
        assert 0 < read["moe.held_tile_fill_share"] <= 100
        assert "moe.real_experts_per_token_max" in proc.stdout
    else:
        assert set(line["rehearsal"]) == {"images_per_s", "setup_s"}
        assert "tokens/s" in proc.stdout and "routing slack" in proc.stdout


# ---- scopes: in the compiled program, and through the per-layer reduction --------


@pytest.fixture(scope="module")
def tiny_step_text():
    adapter = harness.load_plugin("adapters", "scmoe_mla")
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
    assert [n for n, s in scopes.items() if s == "layer_loop" and n.lstrip("%").startswith("while")]
    # and no dotted component of any op_name is a scope the file does not list
    parts = {p for path in re.findall(r'op_name="((?:[^"\\]|\\.)*)"', text) for p in path.split("/")[:-1]}
    assert {p for p in parts if p.split(".")[0] in ("mla", "moe")} <= set(names)


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
    adapter = harness.load_plugin("adapters", "scmoe_mla")
    ctx = types.SimpleNamespace(
        trace=trace_reduce.Reduced(trace), peaks=harness.peak_row("TPU v5 lite"), config=cfg,
        shapes=harness.load_plugin("shapes", "scmoe_mla"), adapter=adapter,
        devices=[None], counters={"offline.batch": 2}, samples={}, spans={}, log=logs.append, step_hlo_text=text,
    )
    read = {name: harness.load_plugin("layer_metrics", name).read for name in NEW_METRICS + ["kernels.scoped_share"]}
    assert read["kernels.scoped_share"](ctx) == pytest.approx(100.0)
    for name in ROOFLINES:
        assert 0 < read[name](ctx) < 100, name
    assert read["kernels.scmoe_route_ms"](ctx) > 0 and read["kernels.scmoe_zero_ms"](ctx) > 0
    # the phases: each reader is the accepted one's body, and a layer's phases add up to the layer
    for name, accepted in PHASE_READERS.items():
        if name != "moe.held_tile_fill_share":
            assert read[name](ctx) == harness.load_plugin("layer_metrics", accepted).read(ctx) > 0, name
    assert read["kernels.scmoe_sort_ms"](ctx) < read["kernels.scmoe_route_ms"](ctx)
    whole = layer_times.ms(ctx, layer_times.exactly("moe.experts"))
    assert read["kernels.scmoe_gather_ms"](ctx) + read["kernels.scmoe_combine_ms"](ctx) < whole
    for scope in ("dense_mlp", "mla.attn", "moe.experts"):
        assert any(f"roofline of {scope}" in line for line in logs), scope
    # the phases' readers log the table of the routed sum's phases and of the route's
    assert any("phase times:" in line and "experts.products" in line for line in logs)
    assert any("phase times:" in line and "route.sort" in line for line in logs)
    metrics.registry().reset()
    assert read["moe.zero_pair_share"](ctx) is None and read["moe.held_load_max_over_mean"](ctx) is None
    assert read["moe.held_tile_fill_share"](ctx) == 0.0  # a registry with no such gauge, as the accepted reader says
    metrics.registry().gauge(metrics.MOE_ZERO_PAIR_SHARE).set(0.3125)
    metrics.registry().gauge(metrics.MOE_EXPERT_LOAD_MAX_OVER_MEAN).set(1.25)
    assert read["moe.zero_pair_share"](ctx) == pytest.approx(31.25)
    assert read["moe.held_load_max_over_mean"](ctx) == pytest.approx(1.25)
    metrics.registry().gauge(metrics.MOE_PAIRS_HELD).set(96.0)
    metrics.registry().gauge(metrics.MOE_ROWS_PADDED).set(192.0)
    assert read["moe.held_tile_fill_share"](ctx) == pytest.approx(50.0)
    # the pairs the reference routed, where the check has run, are the experts' work
    logs.clear()
    kept = {key: value for key, value in vars(ctx).items() if key not in ("layer_times", "phase_times")}
    counters = {"offline.batch": 2, "check.ref_pairs_held": 40.0, "check.ref_tokens": 64.0}
    checked = types.SimpleNamespace(**{**kept, "counters": counters})
    metrics.registry().gauge(metrics.MOE_PAIRS_ALL).set(768.0)
    metrics.registry().gauge(metrics.MOE_PAIRS_HELD).set(96.0)
    assert 0 < read["kernels.scmoe_experts_roofline"](checked) < 100
    assert any("pairs to the held experts: 80 a step" in line and "0.1250 of all" in line for line in logs)
    metrics.registry().reset()
    bare = types.SimpleNamespace(
        trace=None, counters={}, peaks=None, config=cfg, spans={}, samples={}, shapes=ctx.shapes, adapter=adapter
    )
    assert all(read[name](bare) is None for name in NEW_METRICS if name != "moe.held_tile_fill_share")
    assert read["moe.held_tile_fill_share"](bare) == 0.0
    # a program that carries no such scope (another family's, the parent's) reads 0 before any shape function is asked
    unscoped = types.SimpleNamespace(**{**kept, "step_hlo_text": "", "shapes": None})
    for name in ROOFLINES:
        assert read[name](unscoped) == 0.0, name
