"""The decoder-hybrid-decoder family in the benchmark: its configuration file
against the catalog row it is taken from, whole, and against the program's
preset; its shape functions against counts reckoned by hand; its cell run end
to end on the CPU at a tiny size in a temporary copy; its scopes in the
compiled program and its readers on a synthetic trace; the manifest's rules on
the repo's manifest as it now stands."""

from __future__ import annotations

import json
import re
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
from benchmark.shapes import sambay as shapes  # noqa: E402

MANIFEST = json.loads((REPO / "BENCHMARK.json").read_text())
CELL = "phi4_mini_flash_prefill_s4096"
NAME = "phi4_mini_flash_reasoning"
TRAFFIC = "offline_tokens_b1_s4096_chain2"
CONFIG = json.loads((REPO / "benchmark" / "configs" / f"{NAME}.json").read_text())
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")
# the catalog row's config, as the source publishes it: nothing is cut
PUBLISHED = {
    "embd_pdrop": 0, "hidden_act": "silu", "hidden_size": 2560, "intermediate_size": 10240, "layer_norm_eps": 1e-05,
    "max_position_embeddings": 262144, "mb_per_layer": 2, "model_type": "phi4flash", "num_attention_heads": 40,
    "num_hidden_layers": 32, "num_key_value_heads": 20, "resid_pdrop": 0, "sliding_window": 512,
    "tie_word_embeddings": True, "mlp_bias": False, "lm_head_bias": False, "vocab_size": 200064,
}
NEW_METRICS = [
    "kernels.mamba_scan_roofline", "kernels.mamba_proj_roofline", "kernels.mamba_mix_ms", "kernels.gmu_roofline",
    "kernels.diff_proj_roofline", "kernels.diff_attn_window_roofline", "kernels.diff_attn_full_roofline",
    "kernels.sambay_mlp_roofline", "flash.window_masked_score_share",
]
ROOFLINES = [name for name in NEW_METRICS if name.endswith("_roofline")]
LAYERS = [
    "embed", "layer_loop", "mamba.proj", "mamba.mix", "mamba.scan", "gmu", "diff.proj", "diff.attn_window",
    "diff.attn_full", "dense_mlp", "head",
]


# ---- the configuration file ---------------------------------------------------


def test_every_width_is_the_published_one_and_nothing_is_cut():
    for key, value in PUBLISHED.items():
        assert CONFIG[key] == value, key
    assert CONFIG["reduced"] == [] and "published" not in CONFIG  # the model whole
    assert CONFIG["compute"] == "bf16" and CONFIG["chips"] == 1 and CONFIG["family"] == "sambay"
    assert CONFIG["exec_config"] == "v12_sambay" and CONFIG["seq_len"] == 4096
    # what config.json does not give is written down as assumed, each with its reason
    assumed = CONFIG["assumed"]
    assert (assumed["d_state"], assumed["d_conv"], assumed["expand"], assumed["dt_rank"]) == (16, 4, 2, 160)
    assert assumed["dt_rank"] == -(-CONFIG["hidden_size"] // 16)
    assert {"state_space_sizes", "biases", "layer_pattern", "window", "lambda_init", "head_pairing", "positions",
            "norms", "weights", "state_space_draws", "small_draws", "token_ids", "seq_len"} <= set(assumed)
    assert all(len(str(v)) > 2 for k, v in assumed.items() if not isinstance(v, int))
    assert "3,852,562,944 parameters = 7.71 GB" in CONFIG["memory"]
    source = CONFIG["source"]
    assert len(source) <= 200 and "arXiv:2507.06607" in source and "whole on one chip" in source


def test_the_file_holds_every_number_of_the_catalog_row():
    if not CATALOG.is_file():
        pytest.skip("no catalog beside the guides here")
    row = next(json.loads(l) for l in CATALOG.read_text().splitlines() if '"name": "Phi-4-mini-flash-reasoning"' in l)
    assert row["source_url"] in CONFIG["source"] and row["config"] == PUBLISHED
    for key, value in row["config"].items():
        assert CONFIG[key] == value, key


def test_cell_configuration_and_traffic_are_as_named():
    cell = harness.find_cell(MANIFEST, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (NAME, TRAFFIC, 1)
    assert "cache" in cell["why"] and "decode" in cell["why"]  # what the cell cannot show is said in its why
    traffic = harness.load_json(REPO / "benchmark" / "traffic" / f"{TRAFFIC}.json")
    assert (traffic["driver"], traffic["batch"], traffic["seq_len"]) == ("offline_tokens_dense", 1, 4096)
    assert [traffic[k] for k in ("pool_batches", "chain_len", "sample_sequences", "trace_seconds")] == [16, 2, 1, 3]
    assert [m["name"] for m in MANIFEST["per_layer"] if m.get("workloads") == [CELL]] == NEW_METRICS
    # appended after what the benchmark had, in their order (a later PR appends after them: not "last")
    names = [m["name"] for m in MANIFEST["per_layer"]]
    first = names.index(NEW_METRICS[0])
    assert names[first : first + len(NEW_METRICS)] == NEW_METRICS and names[first - 1] == "moe.held_tile_fill_share"
    cells = [w["name"] for w in MANIFEST["workloads"]]
    assert cells[cells.index(CELL) - 1] == "longcat_flash_prefill_s4096"
    entry = next(c for c in MANIFEST["configs"] if c["name"] == NAME)
    assert entry["source"] == CONFIG["source"] and entry["reduced"] == [] == CONFIG["reduced"]
    for metric in MANIFEST["per_layer"][first : first + len(NEW_METRICS)]:
        assert metric["moves"] == "images_per_s" and metric["layer"] in ("kernels", "model step")
    # a dense model routes nothing: every token is held to both limits, and there is no routing key
    tol = CONFIG["tolerance"]
    assert set(tol) == {"rel_max", "rel_rms", "why"}
    assert all(0 < tol[key] < 0.05 for key in ("rel_rms", "rel_max")) and "bf16" in tol["why"]


def test_manifest_rules_hold_for_the_repos_manifest():
    manifest_rules.check_all(MANIFEST, REPO)
    assert sum(w["chips"] == 4 for w in MANIFEST["workloads"]) == 1 and len(MANIFEST["workloads"]) >= 9


def test_adapter_builds_the_programs_preset_from_the_file():
    from cuda_mpi_gpu_cluster_programming_tpu.models import sambay
    from cuda_mpi_gpu_cluster_programming_tpu.ops import scopes

    adapter = harness.load_plugin("adapters", "sambay")
    assert adapter.model_config(CONFIG) == sambay.PHI4_MINI_FLASH
    assert adapter.input_shape(CONFIG, 1) == (1, 4096) == sambay.PRESETS["phi4_mini_flash"][1:]
    assert [layer["name"] for layer in CONFIG["layers"]] == list(scopes.SAMBAY_LAYERS) == LAYERS
    assert callable(adapter.layer_statistics) and callable(adapter.registry_summary)


def test_the_reference_imports_nothing_from_the_program():
    text = (REPO / "benchmark" / "reference" / "sambay.py").read_text()
    assert not re.search(r"^\s*(from|import)\s+cuda_mpi_gpu_cluster_programming_tpu", text, re.M)
    assert 'precision="highest"' in text and "lax.scan" in text  # one token at a time


# ---- operations, bytes and parameters, reckoned by hand ------------------------


def test_parameter_counts_by_hand():
    assert shapes.mlp_params(CONFIG) == 3 * 2560 * 10240 == 78_643_200
    assert shapes.mamba_matmul_params(CONFIG) == 26_214_400 + 5120 * 192 + 160 * 5120 + 13_107_200 == 41_123_840
    assert shapes.mamba_small_params(CONFIG) == 4 * 5120 + 5120 + 5120 + 5120 * 16 + 5120 == 117_760
    assert shapes.gmu_params(CONFIG) == 2 * 2560 * 5120 == 26_214_400
    assert shapes.attn_matmul_params(CONFIG) == 2560 * 5120 + 2560 * 2560 == 19_660_800
    assert shapes.attn_matmul_params(CONFIG, cross=True) == 2 * 2560 * 2560 == 13_107_200
    assert shapes.attn_small_params(CONFIG) == 5120 + 2560 + 256 + 128
    assert shapes.attn_small_params(CONFIG, True) == 5504
    assert shapes.norm_params(CONFIG) == 5120
    counts = (shapes.n_mamba_layers(CONFIG), shapes.n_gmu_layers(CONFIG), shapes.n_window_layers(CONFIG),
              shapes.n_full_layers(CONFIG), shapes.n_cross_layers(CONFIG))
    assert counts == (9, 7, 8, 8, 7)
    total = shapes.param_count(CONFIG)
    assert total == (
        512_163_840 + 32 * 78_643_200 + 9 * 41_241_600 + 7 * 26_214_400 + 9 * 19_668_864 + 7 * 13_112_704 + 65 * 5120
    ) == 3_852_562_944
    assert round(2 * total / 1e9, 2) == 7.71  # bf16: 45% of the chip's 16 GB (peaks.json: 17.18e9 bytes)


def test_parameter_count_is_the_programs():
    from cuda_mpi_gpu_cluster_programming_tpu.models import sambay

    assert shapes.param_count(CONFIG) == sambay.param_count(sambay.PHI4_MINI_FLASH) == 3_852_562_944
    adapter = harness.load_plugin("adapters", "sambay")
    assert shapes.param_count(_tiny_config()) == sambay.param_count(adapter.model_config(_tiny_config()))


def test_step_operations_and_bytes_by_hand():
    s = 4096
    assert shapes.mlp_flops(CONFIG, 1) == 2 * s * 78_643_200
    assert shapes.mlp_bytes(CONFIG, 1) == 2 * 78_643_200 + 8 * s * 2560
    assert shapes.mamba_proj_flops(CONFIG, 1) == 2 * s * 41_123_840
    assert shapes.mamba_scan_flops(CONFIG, 1) == 6 * s * 5120 * 16  # the recurrence's own, whatever runs it
    # x'' and y in bf16, Delta in float32, B and C once
    assert shapes.mamba_scan_bytes(CONFIG, 1) == s * (5120 * (2 + 2 + 4) + 2 * 16 * 4)
    assert shapes.gmu_flops(CONFIG, 1) == 2 * s * 26_214_400
    assert shapes.gmu_bytes(CONFIG, 1) == 2 * 26_214_400 + 2 * s * 5120 + 8 * s * 2560
    assert shapes.diff_proj_flops(CONFIG, 1) == 2 * s * 19_660_800
    assert shapes.diff_proj_flops(CONFIG, 1, True) == 2 * s * 13_107_200
    # the scores the masks leave: the causal half, or sum_q min(q + 1, 512)
    assert shapes.scores_kept(CONFIG) == s * (s + 1) // 2
    assert shapes.scores_kept(CONFIG, 512) == 512 * 513 // 2 + 3584 * 512
    assert shapes.scores_kept(CONFIG) / shapes.scores_kept(CONFIG, 512) == pytest.approx(4.27, abs=5e-3)
    assert shapes.diff_attn_flops(CONFIG, 1) == 2 * 40 * (s * (s + 1) // 2) * (64 + 128)
    assert shapes.diff_attn_flops(CONFIG, 1, 512) == 2 * 40 * 1_966_336 * 192
    assert shapes.diff_attn_bytes(CONFIG, 1) == 2 * s * (40 * 192 + 20 * 128)
    step = shapes.matmul_flops_per_image(CONFIG)
    by_parts = (
        32 * shapes.mlp_flops(CONFIG, 1) + 9 * shapes.mamba_proj_flops(CONFIG, 1) + 7 * shapes.gmu_flops(CONFIG, 1)
        + 9 * shapes.diff_proj_flops(CONFIG, 1) + 7 * shapes.diff_proj_flops(CONFIG, 1, True)
        + 8 * shapes.diff_attn_flops(CONFIG, 1, 512) + 8 * shapes.diff_attn_flops(CONFIG, 1)
        + 9 * shapes.mamba_scan_flops(CONFIG, 1) + 2 * s * 200064 * 2560
    )
    assert step == pytest.approx(by_parts) and round(step / 1e12, 1) == 32.8
    layer_matmuls = 32 * 78_643_200 + 9 * 41_123_840 + 7 * 26_214_400 + 9 * 19_660_800 + 7 * 13_107_200
    assert round(2 * layer_matmuls / 1e9, 2) == 6.68 and round(2 * 200064 * 2560 / 1e9, 2) == 1.02  # GFLOP a token
    assert round(9 * shapes.mamba_scan_bytes(CONFIG, 1) / 1e9, 1) == 1.5  # the scans move 1.5 GB
    assert shapes.min_bytes_per_step(CONFIG, 1) == 2 * 3_852_562_944 + s * 4 + s * 200064 * 4
    # weights and the two steps' logits a chain of 2 holds: 14.3 GB; a third does not fit the chip
    assert round((2 * 3_852_562_944 + 2 * s * 200064 * 4) / 1e9, 1) == 14.3


def test_forward_roofline_reads_the_family_through_the_names_it_calls():
    read = harness.load_plugin("layer_metrics", "kernels.forward_roofline").read
    ctx = types.SimpleNamespace(
        trace=types.SimpleNamespace(step_durations_ms=lambda: [250.0]), config=CONFIG, shapes=shapes,
        peaks=harness.peak_row("TPU v5 lite"), counters={"offline.batch": 1}, devices=[None], log=lambda m: None,
    )
    assert read(ctx) == pytest.approx(100 * (shapes.matmul_flops_per_image(CONFIG) / 197e12) / 0.25)


# ---- the cell, end to end on the CPU at a tiny size -----------------------------


def _tiny_config() -> dict:
    cfg = dict(CONFIG)
    cfg.update(
        hidden_size=64, intermediate_size=128, num_hidden_layers=8, num_attention_heads=4, num_key_value_heads=2,
        sliding_window=8, vocab_size=256, seq_len=32,
        assumed=dict(CONFIG["assumed"], dt_rank=4),
        program_tiles={"attn_block": 16, "window_block": 8, "scan_chunk": 256, "scan_channel_block": 512},
        # a rehearsal of the control flow: at this width a rounding is a part in a hundred
        tolerance=dict(CONFIG["tolerance"], rel_max=0.2, rel_rms=0.2),
    )
    return cfg


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    """The benchmark copied, and the tiny cell added as a later PR adds one:
    a configuration file, a traffic file and entries, no edit."""
    root = tmp_path_factory.mktemp("bench_sambay")
    bench_tiny.copy_benchmark(root)
    bench = root / "benchmark"
    (bench / "configs" / "tiny_sambay.json").write_text(json.dumps(_tiny_config()))
    traffic = json.loads((bench / "traffic" / f"{TRAFFIC}.json").read_text())
    traffic.update(batch=1, seq_len=32, pool_batches=3, chain_len=2, trace_seconds=0.2)
    (bench / "traffic" / "tiny_tokens_b1_s32.json").write_text(json.dumps(traffic))
    manifest = json.loads((root / "BENCHMARK.json").read_text())
    manifest["configs"].append({
        "name": "tiny_sambay", "source": CONFIG["source"], "file": "benchmark/configs/tiny_sambay.json",
        "reduced": [], "why": "CPU rehearsal size",
    })
    manifest["workloads"].append({
        "name": "tiny_sambay_prefill", "config": "tiny_sambay", "traffic": "tiny_tokens_b1_s32", "chips": 1,
        "why": f"{CELL} at a CPU rehearsal size",
    })
    for metric in manifest["per_layer"]:
        if CELL in metric.get("workloads", []):
            metric["workloads"].append("tiny_sambay_prefill")
    (root / "BENCHMARK.json").write_text(json.dumps(manifest))
    return root


@pytest.mark.parametrize("trace", [0, 1])
def test_the_cell_runs_end_to_end_on_the_cpu_at_a_tiny_size(copy, trace):
    proc = bench_tiny.run_cell(copy, "tiny_sambay_prefill", "--rehearse", trace=trace, seed=2**31 + 39)
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert line["metrics"] == {} and line["device"]["platform"] == "cpu"
    if trace:
        assert "build.compile_s" in line["rehearsal"] and "gauges of one batch" in proc.stdout
        # the counter: read on the CPU too; the five gauges logged
        assert line["rehearsal"]["flash.window_masked_score_share"] == pytest.approx(100 * (1 - (36 + 192) / 448))
        for gauge in ("ssm.chunk_log_decay_min", "ssm.dt_mean", "diff.lambda_min", "diff.lambda_max",
                      "flash.window_masked_score_share", "flash.masked_score_share"):
            assert gauge in proc.stdout, gauge
    else:
        assert set(line["rehearsal"]) == {"images_per_s", "setup_s"}
        # every one of the 32 tokens is held to the limits: a dense model routes nothing
        assert "tokens/s" in proc.stdout and "every one of the 32 tokens" in proc.stdout


def test_the_readings_tool_holds_every_departure_to_the_cells_limits(copy):
    import os
    import subprocess

    cache = str(copy / ".xla_cache")
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(REPO), JAX_COMPILATION_CACHE_DIR=cache)
    proc = subprocess.run(
        [*bench_tiny.on_two_cores(), sys.executable, "benchmark/tools/sambay_readings.py", "--workload",
         "tiny_sambay_prefill", "--seed", "7", "--rehearse"],
        cwd=copy, env=env, capture_output=True, text=True, timeout=600,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [json.loads(l) for l in proc.stdout.splitlines() if l.startswith("{")]
    names = [line["reading"] for line in lines]
    assert names[:2] == ["program", "reference_bf16_throughout"] and "reference_state_bf16" in names and len(names) == 7
    program = lines[0]
    assert program["fails"] == [] and program["as_it_must"]
    assert 0 < program["rel_rms"] < 0.2 and 0 < program["rel_max"] < 0.2
    dropped = {line["reading"]: line for line in lines[2:]}
    # a dropped term is no rounding: several times the program's own error
    for name in ("reference_no_d", "reference_lambda0", "reference_window_plus_one", "reference_own_kv"):
        assert dropped[name]["rel_max"] > 3 * program["rel_max"], name


# ---- scopes: in the compiled program, and through the per-layer reduction --------


@pytest.fixture(scope="module")
def tiny_step_text():
    adapter = harness.load_plugin("adapters", "sambay")
    cfg = _tiny_config()
    params = jax.eval_shape(lambda: adapter.make_params(cfg, 0))
    ids = jax.ShapeDtypeStruct((1, 32), jnp.int32)
    return cfg, adapter.build_forward(cfg).lower(params, ids).compile().as_text()


def test_every_operation_of_the_compiled_forward_carries_a_scope_of_the_family(tiny_step_text):
    cfg, text = tiny_step_text
    names = layer_times.layer_names(cfg)
    assert names == LAYERS
    scopes, _mixed = layer_times.scope_map(text, names)
    assert set(scopes.values()) == set(names)
    # two loops over pairs of layers, each under ``layer_loop``, their bodies' operations under their own scopes
    loops = [n for n, s in scopes.items() if s == "layer_loop" and n.lstrip("%").startswith("while")]
    assert len(loops) == 2
    # every operation inside the forward carries a scope: what carries none names no primitive (the program's
    # arguments, and the bare path of the two jits on constants and the plumbing of the interpreted kernels)
    paths = re.findall(r'op_name="((?:[^"\\]|\\.)*)"', text)
    unscoped = {p for p in paths if layer_times.scope_of(p, names) is None}
    assert not {p for p in unscoped if p.startswith("jit(fwd_bf16)/jit(<lambda>)/")}, sorted(unscoped)[:5]
    # and no dotted component of any op_name is a scope the file does not list
    parts = {p for path in paths for p in path.split("/")[:-1]}
    assert {p for p in parts if p.split(".")[0] in ("mamba", "diff", "gmu")} <= set(names)


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
    adapter = harness.load_plugin("adapters", "sambay")
    ctx = types.SimpleNamespace(
        trace=trace_reduce.Reduced(trace), peaks=harness.peak_row("TPU v5 lite"), config=cfg,
        shapes=harness.load_plugin("shapes", "sambay"), adapter=adapter,
        devices=[None], counters={"offline.batch": 1}, samples={}, spans={}, log=logs.append, step_hlo_text=text,
    )
    read = {name: harness.load_plugin("layer_metrics", name).read for name in NEW_METRICS + ["kernels.scoped_share"]}
    assert read["kernels.scoped_share"](ctx) == pytest.approx(100.0)
    for name in ROOFLINES:
        assert 0 < read[name](ctx) < 100, name
    assert read["kernels.mamba_mix_ms"](ctx) > 0
    for scope in ("mamba.scan", "mamba.proj", "gmu", "diff.proj", "diff.attn_window", "diff.attn_full", "dense_mlp"):
        assert any(f"roofline of {scope}" in line for line in logs), scope
    assert any("roofline of mamba.scan" in line and "memory-bound" in line for line in logs)
    metrics.registry().reset()
    assert read["flash.window_masked_score_share"](ctx) is None
    metrics.registry().gauge(metrics.FLASH_WINDOW_MASKED_SCORE_SHARE).set(0.25)
    assert read["flash.window_masked_score_share"](ctx) == pytest.approx(25.0)
    metrics.registry().reset()
    kept = {key: value for key, value in vars(ctx).items() if key not in ("layer_times", "phase_times")}
    bare = types.SimpleNamespace(
        trace=None, counters={}, peaks=None, config=cfg, spans={}, samples={}, shapes=ctx.shapes, adapter=adapter
    )
    assert all(read[name](bare) is None for name in NEW_METRICS)
    # a program that carries no such scope (another family's, the parent's) reads 0 before any shape function is asked
    unscoped = types.SimpleNamespace(**{**kept, "step_hlo_text": "", "shapes": None})
    for name in ROOFLINES:
        assert read[name](unscoped) == 0.0, name
    assert read["kernels.mamba_mix_ms"](unscoped) in (0.0, None)


# ---- the dense token driver's check ------------------------------------------------


def _check(got, want, tol):
    import numpy as np

    driver = harness.load_plugin("drivers", "offline_tokens_dense")
    logs = []
    ctx = types.SimpleNamespace(
        config={"tolerance": tol}, counters={}, log=logs.append,
        reference=types.SimpleNamespace(forward=lambda _cfg, _params, ids: want[: len(ids)]),
    )
    ok = driver.check(ctx, lambda _params, _ids: got, None, np.zeros((got.shape[0], got.shape[1]), np.int32), 1)
    return ok, ctx.counters, logs


def test_the_dense_check_is_the_routed_checks_two_limits_over_every_token():
    """``rel_max`` = max|got - ref| / max|ref| and ``rel_rms`` = rms(diff) / rms(ref)
    of the median token, as ``drivers/offline_tokens.py`` computes them when
    every token is clear, a block of tokens at a time."""
    import numpy as np

    driver = harness.load_plugin("drivers", "offline_tokens_dense")
    rng = np.random.default_rng(0)
    want = rng.normal(size=(1, 3 * driver.TOKEN_BLOCK + 17, 40)).astype(np.float32)  # no whole number of blocks
    got = want + 1e-3 * rng.normal(size=want.shape).astype(np.float32)
    got[0, 5, 7] += 0.05  # one token far off: it moves the maximum, not the median
    tol = {"rel_max": 0.02, "rel_rms": 0.002}
    ok, counters, logs = _check(got, want, tol)
    flat_got, flat_want = got.reshape(-1, 40).astype(np.float64), want.reshape(-1, 40).astype(np.float64)
    rel_max = np.abs(flat_got - flat_want).max() / np.abs(flat_want).max()
    token_ms = np.mean((flat_got - flat_want) ** 2, axis=-1) / np.mean(flat_want**2)
    assert counters["check.rel_err"] == pytest.approx(rel_max) and counters["check.ref_tokens"] == flat_got.shape[0]
    assert counters["check.rel_rms"] == pytest.approx(np.sqrt(np.median(token_ms)))
    assert ok and "correct" in logs[-1] and "NOT CORRECT" not in logs[-1]
    assert not _check(got, want, dict(tol, rel_max=0.005))[0]  # the far token fails the maximum
    assert not _check(got, want, dict(tol, rel_rms=0.0005))[0]  # every token's rounding fails the median
    bad = got.copy()
    bad[0, 100, 3] = np.nan
    ok, counters, logs = _check(bad, want, tol)
    assert not ok and counters["check.rel_err"] == float("inf") and "NOT CORRECT" in logs[-1]
    ok, _counters, logs = _check(got[:, :-1], want, tol)  # another shape
    assert not ok and "NOT CORRECT" in logs[-1]
